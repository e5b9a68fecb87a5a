"""Exactness of the incrementally-maintained aggregate carry (analyzer.agg).

The chain drivers read every per-broker aggregate the goals score and accept
against from an AggCarry updated by O(moves) scatters instead of O(P·S)
segment-sums. These tests pin the carry to the full recompute after many
rounds of moves, leadership transfers, and swaps: integer counts must match
EXACTLY; float sums within accumulation tolerance. (Trajectory-level
agg-on == agg-off parity is covered by tests/test_chain.py's chain-vs-
per-goal-oracle comparisons — the oracle kernels carry no agg.)
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.analyzer.agg import compute_agg
from cruise_control_tpu.analyzer.chain import (
    _chain_round_body, _chain_swap_body,
)
from cruise_control_tpu.analyzer.constraint import BalancingConstraint
from cruise_control_tpu.analyzer.optimizer import goals_by_priority
from cruise_control_tpu.analyzer.search import ExclusionMasks, SearchConfig
from cruise_control_tpu.config.cruise_control_config import CruiseControlConfig
from cruise_control_tpu.model.fixtures import Dist, random_cluster


@pytest.fixture(scope="module")
def setup():
    cfg = CruiseControlConfig()
    state, meta = random_cluster(
        num_brokers=24, num_topics=8, num_partitions=768, rf=3, num_racks=4,
        dist=Dist.EXPONENTIAL, seed=11, skew_to_first=2.0,
        target_utilization=0.6)
    goals = tuple(goals_by_priority(cfg))
    constraint = BalancingConstraint.from_config(cfg)
    return state, meta, goals, constraint


def _check_against_recompute(agg, state, num_topics):
    fresh = compute_agg(state, num_topics)
    np.testing.assert_array_equal(np.asarray(agg.broker_replicas),
                                  np.asarray(fresh.broker_replicas))
    np.testing.assert_array_equal(np.asarray(agg.broker_leaders),
                                  np.asarray(fresh.broker_leaders))
    np.testing.assert_array_equal(np.asarray(agg.topic_counts),
                                  np.asarray(fresh.topic_counts))
    np.testing.assert_allclose(np.asarray(agg.broker_load),
                               np.asarray(fresh.broker_load),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(agg.pot_nw_out),
                               np.asarray(fresh.pot_nw_out),
                               rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(agg.lbi), np.asarray(fresh.lbi),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.slow  # ~25 s: many-round carry-vs-recompute sweep; tier-2.
def test_carry_tracks_moves_and_leadership(setup):
    """Rounds of the chain move body (replica moves + leadership transfers,
    goal switched mid-stream) keep the carry equal to the recompute."""
    state, meta, goals, constraint = setup
    cfg = SearchConfig(num_sources=32, num_dests=12, moves_per_round=16,
                       max_rounds=50)
    masks = ExclusionMasks()
    agg = compute_agg(state, meta.num_topics)
    # Mid-chain resource goal first (moves), then the leadership-only tail
    # goal (leadership movements), with all prior goals' acceptance stacked.
    total = 0
    for active, rounds in ((8, 6), (14, 4)):
        prior = jnp.asarray([j < active for j in range(len(goals))])
        for _ in range(rounds):
            state, agg, applied, _stat, _fb = _chain_round_body(
                state, agg, jnp.int32(active), prior, goals, constraint,
                cfg, meta.num_topics, masks)
            total += int(applied)
    assert total > 0, "fixture applied no moves: carry never exercised"
    _check_against_recompute(agg, state, meta.num_topics)


def test_carry_tracks_swaps(setup):
    """Swap rounds (two directional legs each) scatter both legs' exact
    effect onto the carry."""
    state, meta, goals, constraint = setup
    masks = ExclusionMasks()
    agg = compute_agg(state, meta.num_topics)
    active = 8  # DiskUsageDistributionGoal: supports_swap
    prior = jnp.asarray([j < active for j in range(len(goals))])
    total = 0
    for _ in range(5):
        state, agg, applied = _chain_swap_body(
            state, agg, jnp.int32(active), prior, goals, constraint,
            meta.num_topics, masks)
        total += int(applied)
    assert total > 0, "fixture applied no swaps: swap-leg carry not exercised"
    _check_against_recompute(agg, state, meta.num_topics)


def test_agg_backed_goal_aux_matches_recompute(setup):
    """partial_from_agg must agree with prepare_partial on the same state
    (TopicReplicaDistribution counts plane, LeaderBytesIn lbi)."""
    state, meta, goals, constraint = setup
    agg = compute_agg(state, meta.num_topics)
    for g in goals:
        from_agg = g.partial_from_agg(agg)
        if from_agg is None:
            continue
        fresh = g.prepare_partial(state, meta.num_topics)
        for key in fresh:
            np.testing.assert_allclose(np.asarray(from_agg[key]),
                                       np.asarray(fresh[key]), rtol=1e-6)
