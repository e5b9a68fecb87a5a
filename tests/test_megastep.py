"""Megastep dispatch (analyzer/chain.py round-10 machinery): donated
multi-round dispatches, async stats readback, deficit-aware count-goal
sizing.

The load-bearing contract is dispatch-boundary invariance: the bounded
megastep path must walk the BYTE-IDENTICAL trajectory of the per-round
bounded path and of the fused whole-chain kernel, for any dispatch budget
K, with async readback on or off, at any padded bucket size — only the
XLA-execution boundaries and readback timing may differ.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.analyzer.chain import (
    AdaptiveDispatch, DispatchStats, MegastepConfig, chain_optimize_rounds,
    deficit_sized_config, donation_enabled, optimize_chain,
    optimize_goal_in_chain, run_bounded_pass, strip_mutable,
)
from cruise_control_tpu.analyzer.constraint import BalancingConstraint
from cruise_control_tpu.analyzer.goals import (
    NetworkOutboundUsageDistributionGoal, PreferredLeaderElectionGoal,
    RackAwareGoal, ReplicaCapacityGoal, ReplicaDistributionGoal,
    TopicReplicaDistributionGoal,
)
from cruise_control_tpu.analyzer.search import ExclusionMasks, SearchConfig
from cruise_control_tpu.model.fixtures import random_cluster

CHAIN = (RackAwareGoal(), ReplicaCapacityGoal(),
         NetworkOutboundUsageDistributionGoal(), ReplicaDistributionGoal(),
         PreferredLeaderElectionGoal())
CFG = SearchConfig(num_sources=32, num_dests=8, moves_per_round=32,
                   max_rounds=60)
MEGA = MegastepConfig(donate=True, async_readback=True, deficit_moves_cap=0)


def _cluster(partition_bucket: int = 0):
    return random_cluster(num_brokers=12, num_topics=6, num_partitions=96,
                          rf=2, num_racks=3, seed=3, skew_to_first=2.0,
                          partition_bucket=partition_bucket)


def _run_chain(state, meta, masks, megastep, dispatch_rounds):
    infos = []
    for i in range(len(CHAIN)):
        state, info = optimize_goal_in_chain(
            state, CHAIN, i, BalancingConstraint(), CFG, meta.num_topics,
            masks, dispatch_rounds=dispatch_rounds, megastep=megastep,
            donate_input=infos and any(x["rounds"] > 0 for x in infos))
        infos.append(info)
    return state, infos


# The two pinned bucket sizes: 32 keeps P=96 unpadded, 128 pads to 128
# rows — the megastep path must be trajectory-exact on padded shapes too
# (pad partitions are masked, never moved).
@pytest.mark.parametrize("bucket", [32, 128])
def test_megastep_parity_per_round_vs_k_vs_fused(bucket):
    state, meta = _cluster(partition_bucket=bucket)
    masks = ExclusionMasks()
    # Reference: per-round dispatching (K=1, synchronous, no donation).
    ref_state, ref_infos = _run_chain(
        state, meta, masks,
        MegastepConfig(donate=False, async_readback=False), 1)
    # Fused whole-chain kernel.
    fused_state, _ = optimize_chain(state, CHAIN, BalancingConstraint(),
                                    CFG, meta.num_topics, masks)
    np.testing.assert_array_equal(np.asarray(fused_state.assignment),
                                  np.asarray(ref_state.assignment))
    # Megasteps at two K values, async readback + donation requested
    # (donation resolves to off on this CPU backend — the gate under test
    # in test_donation_gated_off_on_zero_copy_backend).
    for k in (4, 64):
        st, infos = _run_chain(state, meta, masks, MEGA, k)
        np.testing.assert_array_equal(np.asarray(st.assignment),
                                      np.asarray(ref_state.assignment))
        np.testing.assert_array_equal(np.asarray(st.leader_slot),
                                      np.asarray(ref_state.leader_slot))
        for a, b in zip(ref_infos, infos):
            assert a["moves_applied"] == b["moves_applied"], (k, a["goal"])
            assert a["succeeded"] == b["succeeded"], (k, a["goal"])


def test_deficit_sizing_invariant_across_dispatch_budgets():
    """Deficit-aware sizing reads only the goal's ENTRY violations, so the
    sized trajectory is identical for any dispatch-budget sequence."""
    state, meta = _cluster()
    masks = ExclusionMasks()
    mega = MegastepConfig(donate=False, async_readback=True,
                          deficit_moves_cap=256)
    st1, infos1 = _run_chain(state, meta, masks, mega, 1)
    st2, infos2 = _run_chain(state, meta, masks, mega, 16)
    np.testing.assert_array_equal(np.asarray(st1.assignment),
                                  np.asarray(st2.assignment))
    for a, b in zip(infos1, infos2):
        assert a["moves_applied"] == b["moves_applied"], a["goal"]


def test_on_device_early_exit_freezes_state():
    """A megastep dispatched on an already-converged state must run exactly
    ONE zero-apply round (the while_loop's early-exit flag) and return the
    state byte-identical — the guarantee the async pump's speculative
    post-convergence dispatch relies on."""
    state, meta = _cluster()
    masks = ExclusionMasks()
    constraint = BalancingConstraint()
    st = state
    for i in range(len(CHAIN)):
        st, _ = optimize_goal_in_chain(st, CHAIN, i, constraint, CFG,
                                       meta.num_topics, masks)
    before = np.asarray(st.assignment).copy()
    for i in range(len(CHAIN)):
        new_st, moves, rounds = chain_optimize_rounds(
            st, jnp.int32(i), jnp.asarray([j < i for j in range(len(CHAIN))]),
            CHAIN, constraint, CFG, meta.num_topics, masks,
            budget=jnp.int32(50))
        assert int(rounds) == 1, CHAIN[i].name
        assert int(moves) == 0, CHAIN[i].name
        np.testing.assert_array_equal(np.asarray(new_st.assignment), before)


def test_donation_gated_off_on_zero_copy_backend():
    """model/refresh.py's snapshot rule: on CPU, device arrays may alias
    host buffers the model pipeline still owns, so the megastep path must
    refuse donation there — the input state stays alive and readable after
    a full bounded run with donation REQUESTED."""
    assert jax.default_backend() == "cpu"
    assert not donation_enabled(MegastepConfig(donate=True))
    assert not donation_enabled(None)
    state, meta = _cluster()
    host_assignment = np.asarray(state.assignment).copy()
    st, _ = _run_chain(state, meta, ExclusionMasks(),
                       MegastepConfig(donate=True, async_readback=True), 4)
    # The ORIGINAL state must not have been donated/deleted or mutated.
    np.testing.assert_array_equal(np.asarray(state.assignment),
                                  host_assignment)


def test_strip_mutable_excludes_topology_from_donation_set():
    state, _meta = _cluster()
    rest = strip_mutable(state)
    assert rest.assignment.shape == (0, state.max_replication_factor)
    assert rest.leader_slot.shape == (0,)
    # Topology leaves are passed through UNTOUCHED (same arrays — they are
    # exactly the buffers the model cache shares across generations).
    assert rest.topic is state.topic
    assert rest.capacity is state.capacity
    merged = dataclasses.replace(rest, assignment=state.assignment,
                                 leader_slot=state.leader_slot)
    np.testing.assert_array_equal(np.asarray(merged.assignment),
                                  np.asarray(state.assignment))


class _Script:
    """Fake dispatch kernel: a pass that applies moves for ``work`` rounds
    then reaches its fixed point (every later round applies 0)."""

    def __init__(self, work: int):
        self.work = work
        self.done = 0
        self.enqueued: list[int] = []

    def __call__(self, st, budget: int):
        self.enqueued.append(budget)
        rounds = 0
        applied = 0
        remaining = max(0, self.work - self.done)
        if remaining == 0:
            rounds = 1          # the terminal zero-apply round re-runs
        else:
            rounds = min(budget, remaining)
            applied = rounds
            self.done += rounds
            if rounds < budget:
                rounds += 1     # the in-dispatch zero-apply round
                rounds = min(rounds, budget)
        return st + applied, applied, rounds, False, None


class _SpyController(AdaptiveDispatch):
    def __init__(self, k):
        super().__init__(k, target_s=0.0)
        self.events: list[tuple] = []

    def budget(self, remaining: int) -> int:
        b = super().budget(remaining)
        self.events.append(("budget", b))
        return b

    def observe(self, rounds_run, budget, elapsed_s):
        self.events.append(("observe", rounds_run, budget))
        super().observe(rounds_run, budget, elapsed_s)


def test_async_pump_one_behind_and_speculative_drain():
    """Async readback keeps one dispatch in flight: the controller observes
    dispatch N only AFTER dispatch N+1's budget was requested (the
    staleness contract), and the speculative post-convergence dispatch is
    drained WITHOUT touching the pass totals — it applies nothing and its
    round must not be counted, or the async path would burn cfg.max_rounds
    budget the synchronous path does not."""
    script = _Script(work=5)
    ctl = _SpyController(2)
    st, applied, rounds = run_bounded_pass(script, 0, 100, ctl,
                                           async_readback=True)
    assert st == 5 and applied == 5
    # 4 real dispatches (2+2+[1+zero round]+[terminal zero round]) + 1
    # speculative zero-apply re-run enqueued while the 4th was unread.
    assert script.enqueued == [2, 2, 2, 2, 2]
    # Pass totals match the sync path exactly: the speculative dispatch
    # contributes zero rounds.
    assert rounds == 2 + 2 + 2 + 1
    # One-behind: the first observe lands after the SECOND budget request.
    kinds = [e[0] for e in ctl.events]
    assert kinds[:3] == ["budget", "budget", "observe"]


def test_sync_pump_reads_before_enqueueing():
    script = _Script(work=5)
    ctl = _SpyController(2)
    st, applied, rounds = run_bounded_pass(script, 0, 100, ctl,
                                           async_readback=False)
    assert st == 5 and applied == 5
    assert script.enqueued == [2, 2, 2, 2]   # no speculative dispatch
    assert rounds == 2 + 2 + 2 + 1
    kinds = [e[0] for e in ctl.events]
    assert kinds[:3] == ["budget", "observe", "budget"]


def test_pump_never_overshoots_pass_cap():
    for async_rb in (False, True):
        script = _Script(work=1000)
        ctl = _SpyController(8)
        _st, applied, rounds = run_bounded_pass(script, 0, 20, ctl,
                                                async_readback=async_rb)
        assert applied == 20 and rounds == 20, async_rb
        assert sum(script.enqueued) <= 24     # ≤ cap + one in-flight budget


def test_deficit_sized_config_quantization():
    cfg = SearchConfig(num_sources=64, num_dests=16, moves_per_round=32,
                       max_rounds=100)
    # Small violations: no resize (the configured width already covers it).
    assert deficit_sized_config(cfg, 40.0, 2048) is cfg
    # ~50 moves needed -> next pow2 (64).
    sized = deficit_sized_config(cfg, 100.0, 2048)
    assert sized.moves_per_round == 64 and sized.num_sources == 64
    assert sized.num_dests == 16 and sized.max_rounds == 100
    # Huge imbalance: capped.
    sized = deficit_sized_config(cfg, 1_000_000.0, 2048)
    assert sized.moves_per_round == 2048 and sized.num_sources == 2048
    # Cap 0 disables via the caller gate; the function itself floors at cfg.
    assert deficit_sized_config(cfg, 10.0, 2048) is cfg
    # count_based is set on exactly the three count-distribution goals.
    assert ReplicaDistributionGoal().count_based
    assert TopicReplicaDistributionGoal().count_based
    assert not RackAwareGoal().count_based
    assert not NetworkOutboundUsageDistributionGoal().count_based


def test_dispatch_stats_accounting():
    s = DispatchStats()
    for r in (16, 2, 8):
        s.record("move", r)
    s.record("swap", 1, donated=True, speculative=True)
    d = s.as_dict()
    assert d["dispatch_count"] == 4
    # a speculative dispatch searches no round: the median is of [2, 8, 16]
    assert d["rounds_per_dispatch_p50"] == 8.0
    assert s.rounds_per_dispatch == [16, 2, 8]
    assert d["donated_dispatches"] == 1
    assert d["speculative_dispatches"] == 1


def test_optimizer_reports_dispatch_stats():
    from cruise_control_tpu.analyzer.optimizer import (
        GoalOptimizer, goals_by_priority,
    )
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )
    state, meta = random_cluster(num_brokers=12, num_topics=6,
                                 num_partitions=240, rf=2, num_racks=4,
                                 seed=3, target_utilization=0.5)
    cfg = CruiseControlConfig({"solver.fused.chain.max.brokers": "8",
                               "solver.dispatch.max.rounds": "4"})
    opt = GoalOptimizer(cfg)
    assert opt.last_dispatch_stats() == {}
    opt.optimizations(state, meta, goals=goals_by_priority(cfg))
    ds = opt.last_dispatch_stats()
    assert ds["dispatch_count"] > 0
    assert ds["rounds_per_dispatch_p50"] >= 1.0
    # Fused path records the whole chain as one dispatch.
    opt_fused = GoalOptimizer(CruiseControlConfig())
    opt_fused.optimizations(state, meta, goals=goals_by_priority(
        CruiseControlConfig()))
    assert opt_fused.last_dispatch_stats()["dispatch_count"] == 1
