"""Sharded (multi-device mesh) search vs single-device search.

Runs on the 8-device virtual CPU platform from conftest.py. Mirrors the
reference's approach of testing multi-node behavior in-process (SURVEY.md §4:
embedded brokers + model-level simulation) — here the mesh IS real SPMD, just
on virtual devices.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from cruise_control_tpu.analyzer.constraint import BalancingConstraint
from cruise_control_tpu.analyzer.derived import compute_derived
from cruise_control_tpu.analyzer.agg import compute_agg
from cruise_control_tpu.analyzer.chain import _scored_candidates
from cruise_control_tpu.analyzer.goals import (
    LeaderReplicaDistributionGoal, NetworkOutboundUsageDistributionGoal,
    PreferredLeaderElectionGoal, RackAwareGoal, ReplicaCapacityGoal,
    ReplicaDistributionGoal, TopicReplicaDistributionGoal,
)
from cruise_control_tpu.analyzer.search import ExclusionMasks, SearchConfig, optimize_goal
from cruise_control_tpu.model.fixtures import random_cluster
from cruise_control_tpu.model.tensors import broker_load, broker_replica_counts
from cruise_control_tpu.parallel import (
    make_mesh, optimize_chain_sharded, shard_cluster,
)
from cruise_control_tpu.parallel.mesh import (
    _mask_specs, _psum, _state_specs,
)

CONSTRAINT = BalancingConstraint()
CFG = SearchConfig(num_sources=32, num_dests=8, moves_per_round=8, max_rounds=40)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


@pytest.fixture(scope="module")
def cluster():
    # 16 partitions/shard × 8 shards; skewed so there is work to do.
    return random_cluster(num_brokers=12, num_topics=6, num_partitions=128,
                          rf=2, num_racks=4, seed=7, skew_to_first=2.0,
                          partition_bucket=8)


def test_shard_cluster_roundtrip(mesh, cluster):
    state, meta = cluster
    sharded = shard_cluster(state, mesh)
    np.testing.assert_array_equal(np.asarray(sharded.assignment),
                                  np.asarray(state.assignment))
    assert sharded.assignment.sharding.spec[0] == "p"


def test_sharded_replica_distribution_balances(mesh, cluster):
    state, meta = cluster
    goal = ReplicaDistributionGoal()
    sharded = shard_cluster(state, mesh)
    out, (info,) = optimize_chain_sharded(sharded, (goal,), CONSTRAINT, CFG,
                                          meta.num_topics, mesh)
    assert info["moves_applied"] > 0
    # Single-device reference run reaches the same satisfied end state.
    out_ref, info_ref = optimize_goal(state, goal, (), CONSTRAINT, CFG,
                                      meta.num_topics)
    assert info["succeeded"] and info_ref["succeeded"]
    counts = np.asarray(broker_replica_counts(jax.device_get(out)))
    counts_ref = np.asarray(broker_replica_counts(out_ref))
    assert counts.max() - counts.min() <= counts_ref.max() - counts_ref.min() + 2


def _rack_violation(state) -> float:
    full = jax.device_get(state)
    derived = compute_derived(full)
    return float(RackAwareGoal().broker_violations(
        full, derived, CONSTRAINT, None).sum())


def test_sharded_respects_prior_goal_acceptance(mesh, cluster):
    state, meta = cluster
    sharded = shard_cluster(state, mesh)
    out, infos = optimize_chain_sharded(
        sharded, (RackAwareGoal(), ReplicaDistributionGoal()), CONSTRAINT,
        CFG, meta.num_topics, mesh)
    assert infos[1]["moves_applied"] > 0
    # Rack-awareness must not regress after the second goal ran.
    assert _rack_violation(out) <= 1e-6


def test_sharded_resource_distribution_improves_balance(mesh, cluster):
    state, meta = cluster
    goal = NetworkOutboundUsageDistributionGoal()
    before = np.asarray(broker_load(state))[:, 2]
    sharded = shard_cluster(state, mesh)
    out, _infos = optimize_chain_sharded(sharded, (goal,), CONSTRAINT, CFG,
                                         meta.num_topics, mesh)
    after = np.asarray(broker_load(jax.device_get(out)))[:, 2]
    assert after.std() < before.std()


def test_sharded_swap_round_matches_single_device(mesh, cluster):
    """The chain's card-gather swap kernel must find the same swap batch as
    the single-device chain swap round: per-broker global top-j merged from
    per-shard top-j is exact, and selection is score-rank deterministic."""
    from cruise_control_tpu.analyzer.chain import chain_swap_rounds
    from cruise_control_tpu.parallel.chain_sharded import (
        _make_chain_phase_kernels,
    )

    state, meta = cluster
    goals = (NetworkOutboundUsageDistributionGoal(),)
    masks = ExclusionMasks()
    idx, prior, one = jnp.int32(0), jnp.asarray([False]), jnp.int32(1)
    ref_state, ref_n, ref_rounds = chain_swap_rounds(
        state, idx, prior, goals, CONSTRAINT, meta.num_topics, masks,
        budget=one)
    assert int(ref_rounds) == 1 and int(ref_n) > 0
    swap = _make_chain_phase_kernels(
        mesh, goals, CONSTRAINT, CFG, meta.num_topics,
        (False, False, False), 8, 64)[1]
    out, n, rounds = swap(shard_cluster(state, mesh), masks, idx, prior, one)
    assert int(rounds) == 1
    assert int(n) == int(ref_n)
    np.testing.assert_array_equal(np.asarray(jax.device_get(out).assignment),
                                  np.asarray(ref_state.assignment))


def test_sharded_swap_respects_prior_rack_goal(mesh, cluster):
    """Swap legs are leg-accepted by prior structural goals on the owning
    device: rack-awareness must survive a swap phase under the mesh."""
    state, meta = cluster
    sharded = shard_cluster(state, mesh)
    out, infos = optimize_chain_sharded(
        sharded, (RackAwareGoal(), NetworkOutboundUsageDistributionGoal()),
        CONSTRAINT, CFG, meta.num_topics, mesh)
    assert infos[1]["moves_applied"] > 0 and infos[1]["rounds"] > 0
    assert _rack_violation(out) <= 1e-6


def test_distributed_single_process_path(mesh, cluster):
    """initialize() is a no-op single-host; global_mesh spans all devices
    and drives the sharded solver."""
    from cruise_control_tpu.parallel import distributed

    distributed.initialize()  # no coordinator configured: no-op
    info = distributed.process_info()
    assert info["process_count"] == 1
    gmesh = distributed.global_mesh()
    assert gmesh.devices.size == len(jax.devices())
    state, meta = cluster
    sharded = shard_cluster(state, gmesh)
    out, (res,) = optimize_chain_sharded(
        sharded, (ReplicaDistributionGoal(),), CONSTRAINT, CFG,
        meta.num_topics, gmesh)
    assert res["succeeded"]


# The five goals of tests/test_chain.py's CHAIN: structural, capacity,
# resource distribution (partition-additive scores), leader distribution,
# leadership-only.
SEAM_CHAIN = (RackAwareGoal(), ReplicaCapacityGoal(),
              NetworkOutboundUsageDistributionGoal(),
              LeaderReplicaDistributionGoal(), PreferredLeaderElectionGoal())


# A capacity that the skewed fixture's first brokers exceed, so that the
# capacity goal has sources too.
SEAM_CONSTRAINT = BalancingConstraint(max_replicas_per_broker=24)


def _scoring_half(i: int, num_topics: int, psum):
    """The move round's scoring half for goal ``i`` of SEAM_CHAIN under its
    prior goals, as arrays: (score, accept, the deltas' leaves)."""
    prior = jnp.asarray([j < i for j in range(len(SEAM_CHAIN))])

    def half(state, masks):
        agg = compute_agg(state, num_topics, psum=psum)
        sc = _scored_candidates(
            state, agg, jnp.int32(i), prior, SEAM_CHAIN, SEAM_CONSTRAINT,
            CFG, num_topics, masks, global_partitions=state.num_partitions,
            psum=psum)
        assert sc.deltas.grid is not None
        return sc.score, sc.accept, jax.tree.leaves(sc.deltas.without_grid())

    return half


@pytest.mark.parametrize("i", range(len(SEAM_CHAIN)))
def test_scoring_half_on_a_mesh_of_one_equals_one_chip(i, cluster):
    """The seam itself: with ``psum`` set, on a mesh of ONE device (every
    sum is the identity, targets stay on), ``_scored_candidates`` returns
    score, accept and deltas byte-equal to the one-chip call. Every leader
    sits on its second replica, so the leadership-only goal has work."""
    state, meta = cluster
    state = dataclasses.replace(state,
                                leader_slot=jnp.ones_like(state.leader_slot))
    masks = ExclusionMasks()
    mesh1 = make_mesh(1)
    one_chip = jax.jit(_scoring_half(i, meta.num_topics, None))(state, masks)
    on_mesh = jax.jit(shard_map(
        _scoring_half(i, meta.num_topics, _psum), mesh=mesh1,
        in_specs=(_state_specs(), _mask_specs((False, False, False))),
        out_specs=P(), check_vma=False))(shard_cluster(state, mesh1), masks)
    assert np.isfinite(np.asarray(one_chip[0])).any()
    for a, b in zip(jax.tree.leaves(one_chip), jax.tree.leaves(on_mesh),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mesh_round_body_scores_on_the_candidate_grid(mesh, cluster,
                                                      monkeypatch):
    """The mesh body's deltas carry a CandidateGrid: per-broker tables are
    looked up on the grid's margins under ``shard_map`` as on one chip."""
    from cruise_control_tpu.analyzer import chain as chain_mod
    from cruise_control_tpu.parallel import chain_sharded

    seen = []
    real = chain_mod._scored_candidates

    def spy(*args, **kwargs):
        sc = real(*args, **kwargs)
        seen.append((kwargs["psum"], sc.deltas.grid))
        return sc

    monkeypatch.setattr(chain_sharded, "_scored_candidates", spy)
    state, meta = cluster
    goals = (ReplicaDistributionGoal(),)
    move = chain_sharded._make_chain_phase_kernels.__wrapped__(
        mesh, goals, CONSTRAINT, CFG, meta.num_topics,
        (False, False, False), 8, 64)[0]
    _out, applied, rounds = move(
        shard_cluster(state, mesh), ExclusionMasks(), jnp.int32(0),
        jnp.asarray([False]), jnp.int32(1))
    assert int(rounds) == 1 and int(applied) > 0
    assert seen and all(psum is _psum and grid is not None
                        for psum, grid in seen)
    assert chain_mod.accept_lookup() == "grid"


def test_sharded_full_chain_matches_single_device_outcome(mesh, cluster):
    """The fused whole-chain mesh kernel (parallel/chain_sharded.py) must
    reach the same per-goal OUTCOME as the single-device whole-chain kernel:
    identical success/violation profile and comparable balance. (Bitwise
    trajectory equality is not expected — per-device top-k candidate
    generation explores a different, equally valid move order.)"""
    from cruise_control_tpu.analyzer.chain import optimize_chain

    state, meta = cluster
    chain = (RackAwareGoal(), ReplicaCapacityGoal(),
             ReplicaDistributionGoal(),
             NetworkOutboundUsageDistributionGoal(),
             PreferredLeaderElectionGoal())
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=8,
                       max_rounds=60)

    st_single, infos_single = optimize_chain(state, chain, CONSTRAINT, cfg,
                                             meta.num_topics)
    sharded = shard_cluster(state, mesh)
    st_mesh, infos_mesh = optimize_chain_sharded(
        sharded, chain, CONSTRAINT, cfg, meta.num_topics, mesh)

    for s, m in zip(infos_single, infos_mesh):
        assert m["goal"] == s["goal"]
        assert m["succeeded"] == s["succeeded"], (s, m)
    # Replica-count spread after the chain is comparable.
    counts_s = np.asarray(broker_replica_counts(st_single))
    counts_m = np.asarray(broker_replica_counts(jax.device_get(st_mesh)))
    spread_s = counts_s.max() - counts_s.min()
    spread_m = counts_m.max() - counts_m.min()
    assert spread_m <= spread_s + 2
    # Rack-awareness holds on the mesh result.
    full = jax.device_get(st_mesh)
    derived = compute_derived(full)
    viol = RackAwareGoal().broker_violations(full, derived, CONSTRAINT, None)
    assert float(viol.sum()) <= 1e-6


@pytest.mark.slow  # ~18 s: bounded-vs-fused trajectory sweep; the
# full-chain mesh-vs-single-device pin stays tier-1.
def test_sharded_bounded_dispatch_matches_fused(mesh, cluster):
    """The bounded per-goal sharded driver (dispatch_rounds > 0) must walk
    the IDENTICAL trajectory to the fused whole-chain mesh kernel — same
    final assignment and per-goal moves/swaps (both run the same per-device
    round bodies; only dispatch boundaries differ)."""
    state, meta = cluster
    chain = (RackAwareGoal(), ReplicaCapacityGoal(),
             ReplicaDistributionGoal(),
             NetworkOutboundUsageDistributionGoal())
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=8,
                       max_rounds=60)
    sharded = shard_cluster(state, mesh)
    st_fused, infos_fused = optimize_chain_sharded(
        sharded, chain, CONSTRAINT, cfg, meta.num_topics, mesh)
    st_bounded, infos_bounded = optimize_chain_sharded(
        shard_cluster(state, mesh), chain, CONSTRAINT, cfg,
        meta.num_topics, mesh, dispatch_rounds=3)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_bounded).assignment),
        np.asarray(jax.device_get(st_fused).assignment))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_bounded).leader_slot),
        np.asarray(jax.device_get(st_fused).leader_slot))
    for f, b in zip(infos_fused, infos_bounded):
        assert f["goal"] == b["goal"]
        assert f["succeeded"] == b["succeeded"]
        assert f["moves_applied"] == b["moves_applied"], f["goal"]
        assert f["swaps_applied"] == b["swaps_applied"], f["goal"]


def test_goal_optimizer_uses_mesh(mesh, cluster):
    """GoalOptimizer(mesh=...) routes optimizations through the sharded
    chain kernel and reports the device count."""
    from cruise_control_tpu.analyzer.optimizer import (
        GoalOptimizer, goals_by_priority,
    )
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )

    state, meta = cluster
    cfg = CruiseControlConfig()
    opt = GoalOptimizer(cfg, mesh=mesh)
    assert opt.solver_devices() == 8
    chain = goals_by_priority(cfg, ["RackAwareGoal",
                                    "ReplicaDistributionGoal"])
    _st, result = opt.optimizations(state, meta, goals=chain)
    assert result.balancedness_after >= result.balancedness_before
    assert all(r.succeeded for r in result.goal_results
               if r.name == "RackAwareGoal")


def test_sharded_topic_replica_aux_psum(mesh, cluster):
    """TopicReplicaDistributionGoal's [T, B] aux is additive across shards —
    the production sharded chain kernel (psum'd aux + joint cumulative
    selection) must reach the single-device outcome."""
    from cruise_control_tpu.analyzer.chain import optimize_chain

    state, meta = cluster
    goal = TopicReplicaDistributionGoal()
    chain = (goal,)
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=8,
                       max_rounds=120)
    sharded = shard_cluster(state, mesh)
    _out, infos = optimize_chain_sharded(sharded, chain, CONSTRAINT, cfg,
                                         meta.num_topics, mesh)
    _out_ref, infos_ref = optimize_chain(state, chain, CONSTRAINT, cfg,
                                         meta.num_topics)
    # The two paths walk different (both valid) trajectories; on a tiny
    # fixture a soft goal may strand a residual count-unit in one local
    # optimum and not the other. Require comparable quality, not identical
    # outcomes.
    assert infos[0]["moves_applied"] > 0
    assert infos[0]["residual_violation"] <= \
        infos_ref[0]["residual_violation"] + 2


def _direct_chain():
    return (RackAwareGoal(), ReplicaCapacityGoal(),
            ReplicaDistributionGoal(), TopicReplicaDistributionGoal())


def test_sharded_direct_prepass_mesh1_matches_single_device_bytes(cluster):
    """The mesh direct pre-pass at rank_stride=1 (a 1-device mesh) must
    be BYTE-identical to the single-device bounded trajectory with the
    same megastep — the stride layout at stride 1 is algebraically the
    plain kernel, so any divergence is a mesh-path bug, not a different
    valid basin. Assignment AND leader_slot are pinned."""
    from cruise_control_tpu.analyzer.chain import (
        DispatchStats, MegastepConfig, optimize_goal_in_chain,
    )

    state, meta = cluster
    chain = _direct_chain()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=8,
                       max_rounds=60)
    ms = MegastepConfig(direct_assignment=True, direct_max_sweeps=16)

    st1 = state
    for i in range(len(chain)):
        st1, _ = optimize_goal_in_chain(st1, chain, i, CONSTRAINT, cfg,
                                        meta.num_topics, dispatch_rounds=3,
                                        megastep=ms)
    mesh1 = make_mesh(1)
    stats = DispatchStats()
    stm, _ = optimize_chain_sharded(
        shard_cluster(state, mesh1), chain, CONSTRAINT, cfg,
        meta.num_topics, mesh1, dispatch_rounds=3, megastep=ms,
        stats=stats)
    assert stats.by_kind.get("direct", 0) >= 1
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(stm).assignment),
        np.asarray(st1.assignment))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(stm).leader_slot),
        np.asarray(st1.leader_slot))


def test_sharded_direct_prepass_runs_deterministically_on_mesh(mesh,
                                                               cluster):
    """On the 8-way mesh the direct pre-pass actually dispatches
    (kind="direct"), the chain lands rack-clean with replica spread no
    worse than the single-device direct run +2, and the interleaved
    rank_stride layout replays byte-identically run to run (the crc32
    rounding contract has no host RNG to drift)."""
    from cruise_control_tpu.analyzer.chain import (
        DispatchStats, MegastepConfig, optimize_goal_in_chain,
    )

    state, meta = cluster
    chain = _direct_chain()
    cfg = SearchConfig(num_sources=32, num_dests=8, moves_per_round=8,
                       max_rounds=60)
    ms = MegastepConfig(direct_assignment=True, direct_max_sweeps=16)

    outs = []
    for _ in range(2):
        stats = DispatchStats()
        st8, infos = optimize_chain_sharded(
            shard_cluster(state, mesh), chain, CONSTRAINT, cfg,
            meta.num_topics, mesh, dispatch_rounds=3, megastep=ms,
            stats=stats)
        assert stats.by_kind.get("direct", 0) >= 1
        outs.append((np.asarray(jax.device_get(st8).assignment).copy(),
                     np.asarray(jax.device_get(st8).leader_slot).copy()))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])

    full = jax.device_get(st8)
    derived = compute_derived(full)
    viol = RackAwareGoal().broker_violations(full, derived, CONSTRAINT, None)
    assert float(viol.sum()) <= 1e-6

    st1 = state
    for i in range(len(chain)):
        st1, _ = optimize_goal_in_chain(st1, chain, i, CONSTRAINT, cfg,
                                        meta.num_topics, dispatch_rounds=3,
                                        megastep=ms)
    c8 = np.asarray(broker_replica_counts(full))
    c1 = np.asarray(broker_replica_counts(st1))
    assert (c8.max() - c8.min()) <= (c1.max() - c1.min()) + 2
