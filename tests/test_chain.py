"""Chain-shared kernel (analyzer/chain.py) vs the per-goal kernels.

The chain kernels must reproduce the per-goal search exactly when the
selection size matches (moves_per_round == num_sources makes the static
top-m identical across both paths), for every (active goal, prior set)
combination — that is the compile-once-run-for-every-goal contract.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.analyzer.chain import (
    chain_goal_stats, chain_optimize_rounds, optimize_chain,
    optimize_goal_in_chain,
)
from cruise_control_tpu.analyzer.constraint import BalancingConstraint
from cruise_control_tpu.analyzer.goals import (
    LeaderReplicaDistributionGoal, NetworkOutboundUsageDistributionGoal,
    PreferredLeaderElectionGoal, RackAwareGoal, ReplicaCapacityGoal,
    ReplicaDistributionGoal,
)
from cruise_control_tpu.analyzer.search import (
    ExclusionMasks, SearchConfig, optimize_goal, optimize_round,
)
from cruise_control_tpu.model.fixtures import random_cluster

CHAIN = (RackAwareGoal(), ReplicaCapacityGoal(),
         NetworkOutboundUsageDistributionGoal(),
         LeaderReplicaDistributionGoal(), PreferredLeaderElectionGoal())
# moves_per_round == num_sources ⇒ the old path's static top-m equals the
# chain path's max(moves_per_round, num_sources) for every goal.
CFG1 = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                    max_rounds=1)


def _cluster():
    return random_cluster(num_brokers=12, num_topics=6, num_partitions=96,
                          rf=2, num_racks=3, seed=3, skew_to_first=2.0)


def _prior(i):
    return jnp.asarray([j < i for j in range(len(CHAIN))])


@pytest.mark.parametrize("i", range(len(CHAIN)))
def test_single_round_matches_per_goal_kernel(i):
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()

    old_state, applied = optimize_round(
        state, CHAIN[i], CHAIN[:i], constraint, CFG1, meta.num_topics, masks)
    new_state, moves, rounds = chain_optimize_rounds(
        state, jnp.int32(i), _prior(i), CHAIN, constraint, CFG1,
        meta.num_topics, masks)

    assert int(rounds) == 1
    assert int(moves) == int(applied)
    np.testing.assert_array_equal(np.asarray(new_state.assignment),
                                  np.asarray(old_state.assignment))
    np.testing.assert_array_equal(np.asarray(new_state.leader_slot),
                                  np.asarray(old_state.leader_slot))


def test_full_chain_driver_matches_per_goal_outcome():
    """Same convergence config ⇒ the chain driver and the per-goal driver
    walk identical trajectories goal by goal."""
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                       max_rounds=60)

    st_old = state
    for i, g in enumerate(CHAIN):
        st_old, _ = optimize_goal(st_old, g, CHAIN[:i], constraint, cfg,
                                  meta.num_topics, masks)
    st_new = state
    for i in range(len(CHAIN)):
        st_new, _ = optimize_goal_in_chain(st_new, CHAIN, i, constraint, cfg,
                                           meta.num_topics, masks)
    np.testing.assert_array_equal(np.asarray(st_new.assignment),
                                  np.asarray(st_old.assignment))
    np.testing.assert_array_equal(np.asarray(st_new.leader_slot),
                                  np.asarray(st_old.leader_slot))


def test_fused_full_chain_matches_per_goal_chain():
    """chain_optimize_full (one dispatch for the whole chain) must walk the
    same trajectory as optimize_goal_in_chain called per goal, and report
    the same per-goal outcome stats."""
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                       max_rounds=60)

    st_seq = state
    seq_infos = []
    for i in range(len(CHAIN)):
        st_seq, info = optimize_goal_in_chain(st_seq, CHAIN, i, constraint,
                                              cfg, meta.num_topics, masks)
        seq_infos.append(info)

    st_fused, fused_infos = optimize_chain(state, CHAIN, constraint, cfg,
                                           meta.num_topics, masks)
    np.testing.assert_array_equal(np.asarray(st_fused.assignment),
                                  np.asarray(st_seq.assignment))
    np.testing.assert_array_equal(np.asarray(st_fused.leader_slot),
                                  np.asarray(st_seq.leader_slot))
    for seq, fused in zip(seq_infos, fused_infos):
        assert fused["goal"] == seq["goal"]
        assert fused["succeeded"] == seq["succeeded"]
        assert fused["moves_applied"] == seq["moves_applied"]
        assert fused["swaps_applied"] == seq["swaps_applied"]
        assert fused["residual_violation"] == pytest.approx(
            seq["residual_violation"], rel=1e-5, abs=1e-5)


def test_bounded_dispatch_matches_unbounded():
    """dispatch_rounds caps rounds per XLA execution (so no single
    dispatch runs unbounded); the host loop must walk the IDENTICAL trajectory
    to the unbounded driver — same final assignment, moves, and swaps."""
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                       max_rounds=60)

    st_unbounded = state
    infos_unbounded = []
    for i in range(len(CHAIN)):
        st_unbounded, info = optimize_goal_in_chain(
            st_unbounded, CHAIN, i, constraint, cfg, meta.num_topics, masks)
        infos_unbounded.append(info)

    for k in (1, 3):
        st_bounded = state
        infos_bounded = []
        for i in range(len(CHAIN)):
            st_bounded, info = optimize_goal_in_chain(
                st_bounded, CHAIN, i, constraint, cfg, meta.num_topics,
                masks, dispatch_rounds=k)
            infos_bounded.append(info)
        np.testing.assert_array_equal(np.asarray(st_bounded.assignment),
                                      np.asarray(st_unbounded.assignment))
        np.testing.assert_array_equal(np.asarray(st_bounded.leader_slot),
                                      np.asarray(st_unbounded.leader_slot))
        for a, b in zip(infos_unbounded, infos_bounded):
            assert a["moves_applied"] == b["moves_applied"], (k, a["goal"])
            assert a["swaps_applied"] == b["swaps_applied"], (k, a["goal"])
            assert a["succeeded"] == b["succeeded"]


def test_optimizer_switches_to_bounded_path_at_scale():
    """GoalOptimizer must route clusters above solver.fused.chain.max.brokers
    through the bounded per-goal path, with identical results."""
    from cruise_control_tpu.analyzer.optimizer import (
        GoalOptimizer, goals_by_priority,
    )
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )
    from cruise_control_tpu.model.fixtures import Dist, random_cluster

    state, meta = random_cluster(num_brokers=12, num_topics=6,
                                 num_partitions=240, rf=2, num_racks=4,
                                 dist=Dist.EXPONENTIAL, seed=3,
                                 target_utilization=0.5)
    cfg_fused = CruiseControlConfig()
    cfg_bounded = CruiseControlConfig(
        {"solver.fused.chain.max.brokers": "8",
         "solver.dispatch.max.rounds": "4"})
    _, res_fused = GoalOptimizer(cfg_fused).optimizations(
        state, meta, goals=goals_by_priority(cfg_fused))
    _, res_bounded = GoalOptimizer(cfg_bounded).optimizations(
        state, meta, goals=goals_by_priority(cfg_bounded))
    assert sorted((p.topic, p.partition) for p in res_bounded.proposals) == \
        sorted((p.topic, p.partition) for p in res_fused.proposals)
    assert res_bounded.balancedness_after == pytest.approx(
        res_fused.balancedness_after)


def test_fused_chain_skips_satisfied_goals():
    """A goal with zero violations and no offline replicas on entry runs
    zero rounds in the fused kernel (the on-device fast path)."""
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                       max_rounds=60)
    # Converge once, then re-run on the balanced state: every goal that is
    # already satisfied must report 0 rounds.
    st, infos = optimize_chain(state, CHAIN, constraint, cfg,
                               meta.num_topics, masks)
    _st2, infos2 = optimize_chain(st, CHAIN, constraint, cfg,
                                  meta.num_topics, masks)
    for info in infos2:
        if info["residual_violation"] == 0.0:
            assert info["rounds"] == 0, info


def test_moves_per_round_caps_deduped_goals():
    """solver.moves.per.round is a true per-round accept cap for
    broker-deduped goals even though the static selection size is larger."""
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=64, num_dests=8, moves_per_round=3,
                       max_rounds=1)
    i = 2  # NetworkOutboundUsageDistributionGoal with two priors: deduped
    _st, moves, rounds = chain_optimize_rounds(
        state, jnp.int32(i), _prior(i), CHAIN, constraint, cfg,
        meta.num_topics, masks)
    assert int(rounds) == 1
    assert int(moves) <= 3


def test_chain_goal_stats_matches_eager():
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    from cruise_control_tpu.analyzer.derived import compute_derived

    derived = compute_derived(state)
    for i, g in enumerate(CHAIN):
        viol, obj, offline = chain_goal_stats(
            state, jnp.int32(i), CHAIN, constraint, meta.num_topics, masks)
        aux = g.prepare(state, derived, constraint, meta.num_topics)
        expect = float(g.broker_violations(state, derived, constraint,
                                           aux).sum())
        assert float(viol) == pytest.approx(expect, rel=1e-5, abs=1e-5)


def test_chain_satisfies_hard_goals_and_reduces_soft():
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=64, num_dests=8, moves_per_round=16,
                       max_rounds=120)
    chain = (RackAwareGoal(), ReplicaCapacityGoal(),
             ReplicaDistributionGoal(),
             NetworkOutboundUsageDistributionGoal())
    st = state
    infos = []
    for i in range(len(chain)):
        st, info = optimize_goal_in_chain(st, chain, i, constraint, cfg,
                                          meta.num_topics, masks)
        infos.append(info)
    assert all(info["succeeded"] for info in infos[:2])  # hard goals
    # Rack invariant: no partition has two replicas on the same rack when
    # racks >= rf (checked via the goal's own violation readback).
    viol, _obj, _ = chain_goal_stats(st, jnp.int32(0), chain, constraint,
                                     meta.num_topics, masks)
    assert float(viol) == 0.0


def test_adaptive_dispatch_sizing():
    """AdaptiveDispatch grows the round budget while full dispatches finish
    under target/2, shrinks above 2x target, never learns from a partial
    dispatch (a pass hitting its fixed point says nothing about cost), and
    never drops below the configured initial budget."""
    from cruise_control_tpu.analyzer.chain import AdaptiveDispatch

    d = AdaptiveDispatch(16, target_s=2.0)
    assert d.budget(1000) == 16
    d.observe(16, 16, 0.5)          # fast full dispatch -> double
    assert d.k == 32
    d.observe(32, 32, 0.5)
    assert d.k == 64
    d.observe(10, 64, 0.1)          # partial dispatch -> unchanged
    assert d.k == 64
    d.observe(64, 64, 5.0)          # overshoot -> halve
    assert d.k == 32
    d.observe(32, 32, 100.0)
    assert d.k == 16                # floors at the initial budget
    d.observe(16, 16, 100.0)
    assert d.k == 16
    assert d.budget(7) == 7         # remaining pass budget caps it
    # target 0 = adaptation disabled entirely.
    d0 = AdaptiveDispatch(8, target_s=0.0)
    d0.observe(8, 8, 0.0001)
    assert d0.k == 8


def test_adaptive_dispatch_trajectory_invariance():
    """The search trajectory must be identical for ANY dispatch-budget
    sequence: an aggressive controller (tiny target, max growth) walks the
    same rounds as fixed-size dispatches, only the XLA-execution boundaries
    differ."""
    from cruise_control_tpu.analyzer.chain import AdaptiveDispatch

    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                       max_rounds=60)

    st_fixed = state
    infos_fixed = []
    for i in range(len(CHAIN)):
        st_fixed, info = optimize_goal_in_chain(
            st_fixed, CHAIN, i, constraint, cfg, meta.num_topics, masks,
            dispatch_rounds=2)
        infos_fixed.append(info)

    controller = AdaptiveDispatch(1, target_s=1e9)   # grows every dispatch
    st_adapt = state
    infos_adapt = []
    for i in range(len(CHAIN)):
        st_adapt, info = optimize_goal_in_chain(
            st_adapt, CHAIN, i, constraint, cfg, meta.num_topics, masks,
            dispatch_rounds=1, dispatch=controller)
        infos_adapt.append(info)
    assert controller.k > 1          # it did grow
    np.testing.assert_array_equal(np.asarray(st_adapt.assignment),
                                  np.asarray(st_fixed.assignment))
    # NOTE: the "rounds" counter is dispatch-boundary-DEPENDENT (the
    # terminal zero-apply round is re-run when a dispatch ends exactly at
    # the fixed point), so only state/moves/outcome are invariant.
    for a, b in zip(infos_fixed, infos_adapt):
        assert a["moves_applied"] == b["moves_applied"], a["goal"]
        assert a["succeeded"] == b["succeeded"], a["goal"]


def test_bounded_single_device_skips_satisfied_goals():
    """Parity with the fused kernel's per-goal fast path: a goal with zero
    violations and no offline replicas on entry reports 0 rounds on the
    bounded per-goal path too (no driver dispatches at all)."""
    state, meta = _cluster()
    constraint = BalancingConstraint()
    masks = ExclusionMasks()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                       max_rounds=60)
    st = state
    for i in range(len(CHAIN)):
        st, _ = optimize_goal_in_chain(st, CHAIN, i, constraint, cfg,
                                       meta.num_topics, masks,
                                       dispatch_rounds=4)
    before = np.asarray(st.assignment).copy()
    for i in range(len(CHAIN)):
        st, info = optimize_goal_in_chain(st, CHAIN, i, constraint, cfg,
                                          meta.num_topics, masks,
                                          dispatch_rounds=4)
        if info["residual_violation"] == 0.0:
            assert info["rounds"] == 0, info
    np.testing.assert_array_equal(np.asarray(st.assignment), before)


def test_wide_batch_config_derivation():
    """Goal.prefers_wide_batches widens the source grid only in regime:
    above solver.wide.batch.min.brokers, with a wide goal in the chain,
    floored at the base config, disabled by threshold 0."""
    from cruise_control_tpu.analyzer.goals import (
        RackAwareGoal, TopicReplicaDistributionGoal,
    )
    from cruise_control_tpu.analyzer.optimizer import GoalOptimizer
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )

    assert TopicReplicaDistributionGoal().prefers_wide_batches
    # r4: RackAwareGoal joined the wide-batch class (validated at 1k:
    # rounds 145 -> 38, balancedness + violated set unchanged).
    assert RackAwareGoal().prefers_wide_batches
    from cruise_control_tpu.analyzer.goals import CpuCapacityGoal
    assert not CpuCapacityGoal().prefers_wide_batches
    opt = GoalOptimizer(CruiseControlConfig())
    base = SearchConfig(num_sources=256, num_dests=250, moves_per_round=500,
                        max_rounds=2000)
    chain = [RackAwareGoal(), TopicReplicaDistributionGoal()]
    wide = opt._wide_config(base, chain, num_brokers=1000)
    # r4: wide sources = min(2048, base x multiplier(8), B) — width beyond
    # ~B only inflates per-round cost (measured, optimizer._widen).
    assert wide.num_sources == 1000 and wide.moves_per_round == 1000
    assert wide.num_dests == base.num_dests
    assert opt._wide_config(base, chain, num_brokers=7000).num_sources == 2048
    # Below the regime threshold / no wide goal in the chain -> None.
    assert opt._wide_config(base, chain, num_brokers=100) is None
    assert opt._wide_config(base, [CpuCapacityGoal()], 1000) is None
    # An operator-raised base can never exceed the "wide" config.
    big = SearchConfig(num_sources=2048, num_dests=250, moves_per_round=4096,
                       max_rounds=2000)
    wide = opt._wide_config(big, chain, num_brokers=1000)
    assert wide.num_sources >= big.num_sources
    assert wide.moves_per_round >= big.moves_per_round
    # Threshold 0 disables wide batches entirely.
    opt_off = GoalOptimizer(CruiseControlConfig(
        {"solver.wide.batch.min.brokers": "0"}))
    assert opt_off._wide_config(base, chain, num_brokers=5000) is None
