"""Tensor cluster model tests.

Mirrors the intents of model/LoadConsistencyTest, CreateOrDeleteReplicasTest
and ClusterModelStats tests: load accounting stays consistent under
functional moves; stats reductions match hand computations.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.common import BrokerState, Resource
from cruise_control_tpu.model import (
    ClusterModelBuilder, apply_leadership_move, apply_replica_move, apply_swap,
    broker_leader_counts, broker_load, broker_replica_counts, cluster_stats,
    fixtures, offline_replicas, potential_nw_out, rack_partition_counts,
    set_broker_state, topic_broker_replica_counts,
)

CAP = {Resource.CPU: 100.0, Resource.NW_IN: 1000.0, Resource.NW_OUT: 1000.0,
       Resource.DISK: 10000.0}
LOAD = {Resource.CPU: 10.0, Resource.NW_IN: 50.0, Resource.NW_OUT: 60.0,
        Resource.DISK: 300.0}


def two_broker_cluster():
    b = ClusterModelBuilder()
    b.add_broker(0, "rA", CAP).add_broker(1, "rB", CAP)
    b.add_partition("t", 0, [0, 1], leader_load=LOAD)
    b.add_partition("t", 1, [1, 0], leader_load=LOAD)
    return b.build()


def test_broker_load_accounting():
    state, meta = two_broker_cluster()
    load = np.asarray(broker_load(state))
    # Each broker: one leader (full load) + one follower (follower load:
    # CPU*0.4, NW_IN same, NW_OUT 0, DISK same).
    assert load[0, Resource.CPU] == pytest.approx(10.0 + 4.0)
    assert load[0, Resource.NW_IN] == pytest.approx(100.0)
    assert load[0, Resource.NW_OUT] == pytest.approx(60.0)
    assert load[0, Resource.DISK] == pytest.approx(600.0)
    np.testing.assert_allclose(load[0], load[1])


def test_replica_and_leader_counts():
    state, _ = two_broker_cluster()
    assert np.asarray(broker_replica_counts(state)).tolist() == [2, 2]
    assert np.asarray(broker_leader_counts(state)).tolist() == [1, 1]


def test_replica_move_conserves_total_load():
    state, _ = two_broker_cluster()
    before = np.asarray(broker_load(state)).sum(axis=0)
    # Move follower of partition 0 (slot 1, on broker 1) to broker 0 is
    # illegal (already hosts p0); move it from broker 1 to... only 2 brokers,
    # so build a 3rd-broker cluster instead.
    b = ClusterModelBuilder()
    b.add_broker(0, "rA", CAP).add_broker(1, "rB", CAP).add_broker(2, "rC", CAP)
    b.add_partition("t", 0, [0, 1], leader_load=LOAD)
    state, _ = b.build()
    before = np.asarray(broker_load(state)).sum(axis=0)
    moved = apply_replica_move(state, jnp.array(0), jnp.array(1), jnp.array(2))
    after_b = np.asarray(broker_load(moved))
    np.testing.assert_allclose(after_b.sum(axis=0), before, rtol=1e-6)
    assert after_b[1].sum() == 0.0
    assert after_b[2, Resource.NW_IN] == pytest.approx(50.0)


def test_leadership_move_shifts_nw_out():
    state, _ = two_broker_cluster()
    moved = apply_leadership_move(state, jnp.array(0), jnp.array(1))
    load = np.asarray(broker_load(moved))
    # Partition 0's leader now on broker 1: broker 1 has 2 leaders.
    assert np.asarray(broker_leader_counts(moved)).tolist() == [0, 2]
    assert load[1, Resource.NW_OUT] == pytest.approx(120.0)
    assert load[0, Resource.NW_OUT] == pytest.approx(0.0)


def test_swap_action():
    b = ClusterModelBuilder()
    b.add_broker(0, "rA", CAP).add_broker(1, "rB", CAP)
    b.add_partition("t", 0, [0], leader_load=LOAD)
    b.add_partition("t", 1, [1], leader_load={Resource.CPU: 2.0})
    state, _ = b.build()
    swapped = apply_swap(state, jnp.array(0), jnp.array(0), jnp.array(1), jnp.array(0))
    load = np.asarray(broker_load(swapped))
    assert load[1, Resource.CPU] == pytest.approx(10.0)
    assert load[0, Resource.CPU] == pytest.approx(2.0)


def test_potential_nw_out():
    state, _ = two_broker_cluster()
    pot = np.asarray(potential_nw_out(state))
    # Every broker hosts replicas of both partitions → potential = 120 each.
    np.testing.assert_allclose(pot[:2], [120.0, 120.0])


def test_rack_partition_counts():
    state, meta = fixtures.rack_aware_satisfiable()
    counts = np.asarray(rack_partition_counts(state, len(meta.rack_names)))
    # Partition 0 has both replicas in rack rA (index 0).
    assert counts[0].tolist() == [2, 0, 0]
    assert counts[1].tolist() == [1, 1, 0]


def test_topic_broker_replica_counts():
    state, meta = two_broker_cluster()
    tb = np.asarray(topic_broker_replica_counts(state, meta.num_topics))
    assert tb.shape[0] == 1
    assert tb[0].tolist() == [2, 2]


def test_offline_replicas_and_set_state():
    state, _ = fixtures.dead_broker_cluster()
    off = np.asarray(offline_replicas(state))
    assert off.sum() == 4  # four replicas on the dead broker 3
    healed = set_broker_state(state, jnp.array(3), int(BrokerState.ALIVE))
    assert np.asarray(offline_replicas(healed)).sum() == 0


def test_cluster_stats_sane():
    state, _ = fixtures.small_unbalanced()
    stats = cluster_stats(state)
    assert int(stats.num_alive_brokers) == 3
    # Broker 0 holds all leaders → max util > avg util for NW_OUT.
    r = int(Resource.NW_OUT)
    assert float(stats.utilization_max[r]) > float(stats.utilization_avg[r])
    assert float(stats.utilization_std[r]) > 0


def test_builder_padding_and_validation():
    b = ClusterModelBuilder(partition_bucket=16, broker_bucket=8)
    b.add_broker(0, "r", CAP)
    b.add_partition("t", 0, [0], leader_load=LOAD)
    state, meta = b.build()
    assert state.num_partitions == 16
    assert state.num_brokers == 8
    assert int(state.partition_mask.sum()) == 1
    assert int(state.broker_mask.sum()) == 1
    # Padded brokers contribute nothing.
    assert np.asarray(broker_load(state))[1:].sum() == 0

    bad = ClusterModelBuilder()
    bad.add_broker(0, "r", CAP)
    bad.add_partition("t", 0, [0, 0], leader_load=LOAD)
    with pytest.raises(ValueError):
        bad.build()

    bad2 = ClusterModelBuilder()
    bad2.add_broker(0, "r", CAP)
    bad2.add_partition("t", 0, [99], leader_load=LOAD)
    with pytest.raises(ValueError):
        bad2.build()


def test_random_cluster_shapes():
    state, meta = fixtures.random_cluster(num_brokers=10, num_topics=5,
                                          num_partitions=100, rf=3, seed=7)
    assert state.num_partitions == 100
    assert int(state.partition_mask.sum()) == 100
    assert np.asarray(broker_replica_counts(state)).sum() == 300
    # skewed variant concentrates load on low brokers
    skew, _ = fixtures.random_cluster(num_brokers=10, num_topics=5,
                                      num_partitions=100, rf=3, seed=7,
                                      skew_to_first=3.0)
    counts = np.asarray(broker_replica_counts(skew))
    assert counts[0] > counts[-1]


def test_random_cluster_bulk_path_invariants():
    """The vectorized LinkedIn-scale generator (>=200k partitions) must
    satisfy the same layout invariants as the per-partition path: valid
    broker-diverse replica rows, (topic, partition) row ordering, leaders
    in slot 0, and the configured placement skew."""
    state, meta = fixtures.random_cluster(
        num_brokers=500, num_topics=50, num_partitions=200_000, rf=3,
        num_racks=8, dist=fixtures.Dist.EXPONENTIAL, seed=11,
        skew_to_first=2.0, target_utilization=0.55)
    a = np.asarray(state.assignment)
    assert a.shape == (200_000, 3)
    assert (a >= 0).all() and (a < 500).all()
    srt = np.sort(a, axis=1)
    assert not (srt[:, 1:] == srt[:, :-1]).any(), "duplicate replicas"
    assert meta.partition_index == sorted(meta.partition_index)
    assert (np.asarray(state.leader_slot) == 0).all()
    counts = np.bincount(a.reshape(-1), minlength=500)
    assert counts[0] > counts[499], "skew_to_first must bias placement"
    # utilization normalization holds on the bulk path too
    from cruise_control_tpu.model.tensors import broker_load
    from cruise_control_tpu.common.resources import Resource
    load = np.asarray(broker_load(state))
    util = load[:, int(Resource.NW_OUT)].mean() / 1000.0
    assert 0.4 < util < 0.7, util


def test_host_level_rack_fallback():
    """Host topology (model/Host.java + ClusterModel.createBroker rack ==
    null ? host : rack): rackless co-hosted brokers share ONE fault
    domain, so RackAwareGoal keeps a partition's replicas host-disjoint
    (VERDICT r3 missing #4)."""
    import jax.numpy as jnp

    from cruise_control_tpu.model.builder import ClusterModelBuilder
    from cruise_control_tpu.model.fixtures import _CAP

    b = ClusterModelBuilder()
    # 6 rackless brokers on 3 hosts (2 per host).
    for i in range(6):
        b.add_broker(i, rack="", capacity=_CAP, host=f"host{i // 2}")
    b.add_partition("t", 0, [0, 2, 4], leader_index=0,
                    leader_load={})
    # Replicas 0 and 1 share host0: a host-domain violation.
    b.add_partition("t", 1, [0, 1, 4], leader_index=0, leader_load={})
    state, meta = b.build()
    assert meta.host_names == ["host0", "host1", "host2"]
    # Effective rack == host: brokers 0,1 share rack index; 2,3 share, etc.
    rack = list(map(int, state.rack))
    assert rack[0] == rack[1] and rack[2] == rack[3] and rack[4] == rack[5]
    assert len({rack[0], rack[2], rack[4]}) == 3
    host = list(map(int, state.host))
    assert host == rack[:len(host)] or host[0] == host[1]  # hosts shared

    from cruise_control_tpu.analyzer.constraint import BalancingConstraint
    from cruise_control_tpu.analyzer.derived import compute_derived
    from cruise_control_tpu.analyzer.goals import RackAwareGoal

    goal = RackAwareGoal()
    derived = compute_derived(state)
    aux = goal.prepare(state, derived, BalancingConstraint(), meta.num_topics)
    viol = goal.broker_violations(state, derived, BalancingConstraint(), aux)
    # Partition t-1 hosts replicas on both brokers of host0 -> exactly one
    # duplicated replica; t-0 is host-disjoint.
    assert float(viol.sum()) == 1.0


def test_host_aware_optimization_separates_cohosted_replicas():
    """End-to-end: with racks unset and 2 brokers/host, the optimizer must
    leave no partition with two replicas on one host (RackAwareGoal.java:229
    behavior via the host fallback)."""
    import numpy as np

    from cruise_control_tpu.analyzer.optimizer import (
        GoalOptimizer, goals_by_priority,
    )
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )
    from cruise_control_tpu.model.fixtures import Dist, random_cluster

    state, meta = random_cluster(
        num_brokers=12, num_topics=4, num_partitions=96, rf=3, num_racks=0,
        brokers_per_host=2, dist=Dist.UNIFORM, seed=7, skew_to_first=2.0)
    assert len(meta.host_names) == 6
    cfg = CruiseControlConfig({"max.solver.rounds": 300})
    final, _res = GoalOptimizer(cfg).optimizations(
        state, meta, goals=goals_by_priority(cfg))
    assignment = np.asarray(final.assignment)
    host = np.asarray(final.host)
    for p in range(final.num_partitions):
        reps = assignment[p][assignment[p] >= 0]
        hosts = host[reps]
        assert len(set(hosts.tolist())) == len(reps), \
            f"partition {p} has co-hosted replicas: brokers {reps.tolist()}"


def test_slot_major_flat_axis_matches_partition_major(monkeypatch):
    """The accelerator layout of the flat replica axis (slot-major, see
    tensors.slot_major_flat) forced on CPU: segment reductions agree with
    the partition-major layout, the source selection picks the same
    replicas, and a full chain solve still lands a valid result."""
    import jax

    from cruise_control_tpu.analyzer.candidates import select_sources
    from cruise_control_tpu.analyzer.optimizer import GoalOptimizer
    from cruise_control_tpu.model import tensors
    from cruise_control_tpu.model.fixtures import random_cluster

    state, meta = random_cluster(num_brokers=12, num_topics=4,
                                 num_partitions=96, rf=3, num_racks=4,
                                 seed=3, skew_to_first=2.0)
    rng = np.random.default_rng(0)
    # Distinct weights: no ties, so the selection cannot depend on order.
    weight = jnp.asarray(rng.permutation(state.assignment.size).reshape(
        state.assignment.shape).astype(np.float32))
    score = jnp.asarray(rng.random(state.num_brokers).astype(np.float32))

    def snapshot():
        p, s, ok, _on_source, _fallback = select_sources(
            state, score, weight, 32)
        picked = {(int(a), int(b)) for a, b, v in zip(p, s, ok) if v}
        return (np.asarray(tensors.broker_load(state)),
                np.asarray(tensors.topic_broker_replica_counts(
                    state, meta.num_topics)), picked)

    load_pm, counts_pm, picked_pm = snapshot()
    monkeypatch.setattr(tensors, "slot_major_flat", lambda: True)
    jax.clear_caches()      # jitted traces baked the other layout in
    try:
        idx = jnp.arange(state.assignment.size)
        p, s = tensors.slot_coords(idx, state.num_partitions,
                                   state.max_replication_factor)
        np.testing.assert_array_equal(
            np.asarray(tensors.flatten_slots(state.assignment)),
            np.asarray(state.assignment)[np.asarray(p), np.asarray(s)])
        load_sm, counts_sm, picked_sm = snapshot()
        np.testing.assert_allclose(load_sm, load_pm, rtol=1e-5)
        np.testing.assert_array_equal(counts_sm, counts_pm)
        assert picked_sm == picked_pm and picked_sm
        # A short chain keeps the compile small: a hard goal, a count
        # goal, a swap-capable resource goal and a leadership goal cover
        # every consumer of the flat axis (moves, swaps, leader grid).
        from cruise_control_tpu.analyzer.goals import (
            LeaderReplicaDistributionGoal,
            NetworkOutboundUsageDistributionGoal, RackAwareGoal,
            ReplicaDistributionGoal,
        )
        _final, result = GoalOptimizer().optimizations(state, meta, goals=[
            RackAwareGoal(), ReplicaDistributionGoal(),
            NetworkOutboundUsageDistributionGoal(),
            LeaderReplicaDistributionGoal()])
        assert "RackAwareGoal" not in result.violated_goals_after
        assert result.balancedness_after >= result.balancedness_before
        assert result.proposals
    finally:
        monkeypatch.undo()
        jax.clear_caches()
