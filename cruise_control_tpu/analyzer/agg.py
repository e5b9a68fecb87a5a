"""Incrementally-maintained per-broker aggregates for the search loop.

Every search round needs the per-broker aggregate state (load [B, R],
replica/leader counts, potential NW-out, leader bytes-in, per-(topic,
broker) replica counts). Recomputing them from the [P, S] assignment is a
set of segment-sum scatters over every replica — O(P·S) work per round that
dominates the round body at scale (measured at 7k brokers / 1M partitions:
``broker_load`` alone ~40 ms of a ~160 ms host-CPU round; the scatters
together are more than half the round).

A move batch touches at most ``moves_per_round`` partitions, and its exact
per-broker effect is already known (CandidateDeltas), so the aggregates can
be UPDATED in O(moves) scatters instead. This module provides the carry:

- :func:`compute_agg` — the full recompute (loop entry / refresh).
- :func:`apply_deltas_to_agg` — scatter the selected move batch's effect.

Integer counts stay exact under incremental updates. Float sums
(broker_load, pot_nw_out, lbi) accumulate rounding drift relative to a
fresh segment-sum (different summation order), so the loop refreshes the
carry every :data:`REFRESH_EVERY` rounds — the drift window is ~64 rounds
of f32 adds (relative error ~1e-6, far inside the 1e-6-absolute epsilons
of the acceptance bands, which judge O(1)-magnitude normalized loads).

The reference maintains the same aggregates incrementally inside its object
graph (Broker.load updated by Replica relocation — ClusterModel.java:380
relocateReplica → Broker.removeReplica/addReplica); this is that design,
vectorized.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..common.resources import Resource
from ..model.tensors import (
    ClusterTensors, broker_leader_counts, broker_load,
    broker_replica_counts, leader_bytes_in, potential_nw_out,
    topic_broker_replica_counts,
)

# Full-recompute cadence inside a fused loop (bounds f32 drift; counts are
# exact regardless). Power of two so the modulo folds to a bit-mask.
REFRESH_EVERY = 64


@partial(jax.tree_util.register_dataclass,
         data_fields=["broker_load", "broker_replicas", "broker_leaders",
                      "pot_nw_out", "lbi", "topic_counts"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class AggCarry:
    """Replicated per-broker aggregate state threaded through the round
    loop. On a sharded mesh every field is the GLOBAL (psum'd) value — the
    selected move batch is replicated across devices, so incremental
    updates stay replicated without further collectives."""

    broker_load: jax.Array      # [B, R] f32
    broker_replicas: jax.Array  # [B] i32
    broker_leaders: jax.Array   # [B] i32
    pot_nw_out: jax.Array       # [B] f32
    lbi: jax.Array              # [B] f32 (leader NW_IN per broker)
    topic_counts: jax.Array     # [T, B] i32


def _compute_agg(state: ClusterTensors, num_topics: int,
                 psum=None) -> AggCarry:
    p = psum or (lambda x: x)
    return AggCarry(
        broker_load=p(broker_load(state)),
        broker_replicas=p(broker_replica_counts(state)),
        broker_leaders=p(broker_leader_counts(state)),
        pot_nw_out=p(potential_nw_out(state)),
        lbi=p(leader_bytes_in(state)),
        topic_counts=p(topic_broker_replica_counts(state, num_topics)),
    )


@jax.named_scope("goal.agg")
def compute_agg(state: ClusterTensors, num_topics: int,
                psum=None) -> AggCarry:
    """Full aggregate recompute (the segment-sum path), once a goal or a
    dispatch. ``psum`` combines the partition-local partials across a
    sharded mesh."""
    return _compute_agg(state, num_topics, psum)


@dataclasses.dataclass(frozen=True)
class AggDelta:
    """Minimal per-candidate effect view for :func:`apply_deltas_to_agg`
    when a full CandidateDeltas is not at hand (sharded swap legs)."""

    src_broker: jax.Array
    dst_broker: jax.Array
    load_delta: jax.Array
    replica_delta: jax.Array
    leader_delta: jax.Array
    topic: jax.Array


def apply_deltas_to_agg(agg: AggCarry, sub, sel: jax.Array,
                        pot_delta: jax.Array, lbi_delta: jax.Array,
                        ) -> AggCarry:
    """Scatter the effect of the accepted candidates onto the carry.

    ``sub`` is the selected CandidateDeltas batch (or anything exposing the
    AggDelta fields, e.g. a swap leg), ``sel`` the accepted mask;
    ``pot_delta``/``lbi_delta`` the per-candidate potential-NW-out /
    leader-bytes-in transfer scalars (the same values cumulative_select
    feeds attach_cumulative). Non-selected rows route to the out-of-bounds
    bucket and are dropped — mirroring apply_selected's scatter
    discipline."""
    b = agg.broker_load.shape[0]
    oob = jnp.int32(b)
    src = jnp.where(sel, sub.src_broker, oob)
    dst = jnp.where(sel, sub.dst_broker, oob)
    rep = sub.replica_delta.astype(jnp.int32)
    lead = sub.leader_delta.astype(jnp.int32)
    return AggCarry(
        broker_load=agg.broker_load
        .at[src].add(-sub.load_delta, mode="drop")
        .at[dst].add(sub.load_delta, mode="drop"),
        broker_replicas=agg.broker_replicas
        .at[src].add(-rep, mode="drop").at[dst].add(rep, mode="drop"),
        broker_leaders=agg.broker_leaders
        .at[src].add(-lead, mode="drop").at[dst].add(lead, mode="drop"),
        pot_nw_out=agg.pot_nw_out
        .at[src].add(-pot_delta, mode="drop")
        .at[dst].add(pot_delta, mode="drop"),
        lbi=agg.lbi
        .at[src].add(-lbi_delta, mode="drop")
        .at[dst].add(lbi_delta, mode="drop"),
        topic_counts=agg.topic_counts
        .at[sub.topic, src].add(-rep, mode="drop")
        .at[sub.topic, dst].add(rep, mode="drop"),
    )


def pot_lbi_deltas(state: ClusterTensors, sub) -> tuple[jax.Array, jax.Array]:
    """(pot_delta, lbi_delta) for a candidate batch: potential NW-out
    travels with the replica (PotentialNwOutGoal counts every replica as a
    would-be leader), leader bytes-in with the leadership."""
    pot = jnp.where(sub.replica_delta > 0,
                    state.leader_load[sub.partition, int(Resource.NW_OUT)],
                    0.0)
    lbi = jnp.where(sub.leader_delta > 0,
                    state.leader_load[sub.partition, int(Resource.NW_IN)],
                    0.0)
    return pot, lbi


@jax.named_scope("round.agg_refresh")
def maybe_refresh(agg: AggCarry, state: ClusterTensors, num_topics: int,
                  rounds_done: jax.Array, psum=None) -> AggCarry:
    """Fresh recompute every REFRESH_EVERY rounds (f32 drift bound); the
    cheap incremental carry otherwise. Under a mesh the psum must run
    unconditionally (collectives cannot sit in one cond branch), so the
    refresh is NOT gated there — callers on the sharded path refresh at
    dispatch boundaries instead (entry recompute)."""
    if psum is not None:
        return agg
    return jax.lax.cond(
        (rounds_done % REFRESH_EVERY) == (REFRESH_EVERY - 1),
        lambda: _compute_agg(state, num_topics),
        lambda: agg)
