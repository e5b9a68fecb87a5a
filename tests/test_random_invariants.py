"""Randomized property-style optimizer invariants.

Reference parity: analyzer/OptimizationVerifier.java:69-339 — the tier-2
pattern of SURVEY.md §4: run a goal chain over parameterized random
clusters and assert INVARIANTS (hard goals satisfied, dead brokers
drained, stats never regress, exclusions honored), never golden outputs.
Mirrors RandomClusterTest / RandomGoalTest / RandomSelfHealingTest /
ExcludedTopicsTest across UNIFORM/LINEAR/EXPONENTIAL load distributions
and multiple seeds.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.analyzer.constraint import (
    BalancingConstraint, OptimizationOptions,
)
from cruise_control_tpu.analyzer.optimizer import (
    GoalOptimizer, goals_by_priority,
)
from cruise_control_tpu.common.broker_state import BrokerState
from cruise_control_tpu.config.cruise_control_config import CruiseControlConfig
from cruise_control_tpu.model import fixtures
from cruise_control_tpu.model.fixtures import Dist
from cruise_control_tpu.model.tensors import (
    broker_load, broker_replica_counts, offline_replicas, replica_exists,
    set_broker_state,
)

CFG = CruiseControlConfig({"max.solver.rounds": 200,
                           "failed.brokers.file.path": ""})
CHAIN = ["RackAwareGoal", "ReplicaCapacityGoal", "DiskCapacityGoal",
         "NetworkOutboundCapacityGoal", "ReplicaDistributionGoal",
         "NetworkOutboundUsageDistributionGoal",
         "TopicReplicaDistributionGoal", "LeaderReplicaDistributionGoal"]


def _cluster(dist: Dist, seed: int):
    return fixtures.random_cluster(
        num_brokers=16, num_topics=8, num_partitions=192, rf=3, num_racks=4,
        dist=dist, seed=seed, skew_to_first=2.0, target_utilization=0.5)


def _assert_consistent(state, meta):
    """Structural sanity after any optimization (LoadConsistencyTest role):
    every partition keeps its replica count, no duplicate brokers within a
    partition, leader slot holds a live replica."""
    a = np.asarray(state.assignment)
    mask = np.asarray(state.partition_mask)
    leader = np.asarray(state.leader_slot)
    for p in np.nonzero(mask)[0]:
        replicas = a[p][a[p] >= 0]
        assert len(replicas) == len(set(replicas)), f"dup broker, p={p}"
        assert a[p, leader[p]] >= 0, f"leader on empty slot, p={p}"


@pytest.mark.parametrize("dist", [Dist.UNIFORM, Dist.LINEAR,
                                  Dist.EXPONENTIAL])
@pytest.mark.parametrize("seed", [0, 7])
def test_random_cluster_hard_goals_and_no_regression(dist, seed):
    """GOAL_VIOLATION + REGRESSION verifications: on every distribution and
    seed, all hard goals end satisfied, balancedness never decreases, and
    replica-count structure stays consistent."""
    state, meta = _cluster(dist, seed)
    rf_before = np.asarray(replica_exists(state)).sum()
    opt = GoalOptimizer(CFG)
    final, result = opt.optimizations(state, meta,
                                      goals=goals_by_priority(CFG, CHAIN))
    hard = {r.name for r in result.goal_results if r.is_hard}
    violated = set(result.violated_goals_after)
    assert not (hard & violated), (dist, seed, hard & violated)
    assert result.balancedness_after >= result.balancedness_before - 1e-6
    assert np.asarray(replica_exists(final)).sum() == rf_before
    _assert_consistent(final, meta)


@pytest.mark.parametrize("dist", [Dist.UNIFORM, Dist.EXPONENTIAL])
def test_random_self_healing_drains_dead_brokers(dist):
    """BROKEN_BROKERS verification (RandomSelfHealingTest): after killing
    brokers, optimization leaves ZERO replicas on them and hard goals hold
    on the survivors."""
    state, meta = _cluster(dist, seed=3)
    dead = [13, 14, 15]
    state = set_broker_state(state, jnp.asarray(dead), BrokerState.DEAD)
    assert int(offline_replicas(state).sum()) > 0
    opt = GoalOptimizer(CFG)
    final, result = opt.optimizations(state, meta,
                                      goals=goals_by_priority(CFG, CHAIN))
    counts = np.asarray(broker_replica_counts(final))
    assert counts[dead].sum() == 0, counts[dead]
    assert int(offline_replicas(final).sum()) == 0
    hard = {r.name for r in result.goal_results if r.is_hard}
    assert not (hard & set(result.violated_goals_after))
    _assert_consistent(final, meta)


@pytest.mark.parametrize("chain,new", [
    (["ReplicaDistributionGoal", "NetworkOutboundUsageDistributionGoal"],
     [14, 15]),
    # the cluster is drawn without regard to racks, so RackAwareGoal has
    # replicas to move: one new broker on each of the four racks offers
    # every partition a rack it does not use (two would leave the hard
    # goal unsatisfiable under the rule, as upstream's does)
    (None, [12, 13, 14, 15])], ids=["two-goals", "default-chain"])
def test_random_new_broker_gating(chain, new):
    """NEW_BROKERS verification (RandomClusterNewBrokerTest): brokers in NEW
    state are the only ones gaining replicas, under two distribution goals
    and under the full default chain (hard goals, PotentialNwOutGoal and
    the swap rounds included: ``derived.replica_dest_ok``)."""
    state, meta = _cluster(Dist.LINEAR, seed=11)
    state = set_broker_state(state, jnp.asarray(new), BrokerState.NEW)
    before = np.asarray(state.assignment)
    opt = GoalOptimizer(CFG)
    final, _res = opt.optimizations(
        state, meta, goals=goals_by_priority(CFG, chain))
    after = np.asarray(final.assignment)
    placed = (after >= 0) \
        & ~(after[:, :, None] == before[:, None, :]).any(axis=2)
    assert placed.any()
    assert set(np.unique(after[placed]).tolist()) <= set(new)


def test_random_excluded_brokers_for_replica_move_gain_nothing():
    """ExcludedBrokersForReplicaMoveTest: brokers excluded for replica
    moves never GAIN a replica during a full chain run (they may shed —
    requireLessLoad includes excluded brokers,
    ResourceDistributionGoal.java:387)."""
    state, meta = _cluster(Dist.EXPONENTIAL, seed=3)
    excluded = [2, 9]
    excluded_ids = tuple(meta.broker_ids[b] for b in excluded)
    before = np.asarray(state.assignment).copy()
    opt = GoalOptimizer(CFG)
    final, _res = opt.optimizations(
        state, meta, goals=goals_by_priority(CFG, CHAIN),
        options=OptimizationOptions(
            excluded_brokers_for_replica_move=excluded_ids))
    after = np.asarray(final.assignment)
    for b in excluded:
        hosted_before = set(map(tuple, np.argwhere(before == b)))
        hosted_after = set(map(tuple, np.argwhere(after == b)))
        gained = {p for p, _s in hosted_after} - {p for p, _s in hosted_before}
        assert not gained, f"excluded broker {b} gained partitions {gained}"
    _assert_consistent(final, meta)


def test_random_excluded_brokers_for_leadership_gain_no_leaders():
    """ExcludedBrokersForLeadershipTest: brokers excluded for leadership
    never end up leading a partition they did not already lead."""
    state, meta = _cluster(Dist.LINEAR, seed=9)
    excluded = [0, 5]
    excluded_ids = tuple(meta.broker_ids[b] for b in excluded)
    a0 = np.asarray(state.assignment)
    l0 = np.asarray(state.leader_slot)
    leaders_before = {p: a0[p, l0[p]] for p in range(a0.shape[0])}
    opt = GoalOptimizer(CFG)
    final, _res = opt.optimizations(
        state, meta, goals=goals_by_priority(CFG, CHAIN),
        options=OptimizationOptions(
            excluded_brokers_for_leadership=excluded_ids))
    a1 = np.asarray(final.assignment)
    l1 = np.asarray(final.leader_slot)
    mask = np.asarray(final.partition_mask)
    for p in np.nonzero(mask)[0]:
        leader = a1[p, l1[p]]
        if leader in excluded:
            assert leaders_before[p] == leader, \
                f"excluded broker {leader} GAINED leadership of {p}"
    _assert_consistent(final, meta)


def test_random_excluded_topics_never_move():
    """ExcludedTopicsTest: replicas of excluded topics keep their exact
    placement through a full chain run."""
    state, meta = _cluster(Dist.EXPONENTIAL, seed=5)
    excluded = meta.topic_names[0]
    topic_idx = 0
    rows = np.asarray(state.topic) == topic_idx
    before = np.asarray(state.assignment)[rows].copy()
    opt = GoalOptimizer(CFG)
    final, _res = opt.optimizations(
        state, meta, goals=goals_by_priority(CFG, CHAIN),
        options=OptimizationOptions(excluded_topics=(excluded,)))
    after = np.asarray(final.assignment)[rows]
    np.testing.assert_array_equal(after, before)


@pytest.mark.parametrize("order_seed", [1, 2])
def test_random_goal_order_keeps_hard_goals(order_seed):
    """RandomGoalTest: shuffling the SOFT goal order never breaks hard
    goals (the lexicographic acceptance stack is order-independent for
    hard-goal preservation)."""
    rng = np.random.default_rng(order_seed)
    hard = CHAIN[:4]
    soft = CHAIN[4:]
    rng.shuffle(soft)
    state, meta = _cluster(Dist.UNIFORM, seed=2)
    opt = GoalOptimizer(CFG)
    final, result = opt.optimizations(
        state, meta, goals=goals_by_priority(CFG, hard + soft))
    hard_names = {r.name for r in result.goal_results if r.is_hard}
    assert not (hard_names & set(result.violated_goals_after))
    _assert_consistent(final, meta)


def test_random_cluster_load_conserved():
    """Total cluster load is invariant under optimization (moves relocate
    load, never create or destroy it)."""
    state, meta = _cluster(Dist.EXPONENTIAL, seed=9)
    total_before = np.asarray(broker_load(state)).sum(axis=0)
    opt = GoalOptimizer(CFG)
    final, _res = opt.optimizations(state, meta,
                                    goals=goals_by_priority(CFG, CHAIN))
    total_after = np.asarray(broker_load(final)).sum(axis=0)
    np.testing.assert_allclose(total_after, total_before, rtol=1e-4)


@pytest.mark.parametrize("dist", [Dist.UNIFORM, Dist.EXPONENTIAL])
def test_random_bounded_dispatch_equivalence(dist):
    """The bounded-dispatch production path (the large-cluster watchdog
    mitigation) walks the identical trajectory to the fused chain on
    random clusters — exact same proposals and balancedness."""
    state, meta = _cluster(dist, seed=3)
    fused = GoalOptimizer(CFG)
    bounded = GoalOptimizer(CruiseControlConfig({
        "max.solver.rounds": 200, "failed.brokers.file.path": "",
        "solver.fused.chain.max.brokers": "4",
        "solver.dispatch.max.rounds": "5"}))
    _f, rf_ = fused.optimizations(state, meta,
                                  goals=goals_by_priority(CFG, CHAIN))
    _b, rb_ = bounded.optimizations(state, meta,
                                    goals=goals_by_priority(CFG, CHAIN))
    assert sorted((p.topic, p.partition, p.new_replicas, p.new_leader)
                  for p in rb_.proposals) == \
        sorted((p.topic, p.partition, p.new_replicas, p.new_leader)
               for p in rf_.proposals)
    assert rb_.balancedness_after == pytest.approx(rf_.balancedness_after)
