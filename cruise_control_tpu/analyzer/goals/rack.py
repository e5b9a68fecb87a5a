"""Rack-awareness goals.

Reference parity: analyzer/goals/RackAwareGoal.java (strict: no two replicas
of a partition in one rack) and RackAwareDistributionGoal.java (relaxed:
replicas spread over racks as evenly as possible, allowing more replicas
than racks).

Kernel design: with S = max RF small (≤ 8), per-partition rack duplication
is computed from the [P, S, S] pairwise same-rack comparison instead of a
[P, num_racks] one-hot — O(P·S²) with tiny constants, no T×B style blowup.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ...model.tensors import (
    flatten_slots, replica_exists, replica_load_total,
)
from ..candidates import CandidateDeltas
from .base import Goal


def _slot_racks(state):
    """[P, S] rack index per replica slot (num_racks for empty slots)."""
    b = state.num_brokers
    pad_rack = state.rack.max() + 1
    rack_pad = jnp.concatenate([state.rack, jnp.array([pad_rack], dtype=state.rack.dtype)])
    return jnp.where(state.assignment >= 0,
                     rack_pad[jnp.clip(state.assignment, 0, b)], -1 - jnp.arange(
                         state.max_replication_factor, dtype=state.rack.dtype)[None, :])


def _duplicate_mask(state):
    """[P, S] — replica shares its rack with an earlier existing slot of the
    same partition (the 'extra' replicas that violate rack-awareness)."""
    racks = _slot_racks(state)  # [P, S]; empty slots get unique negatives
    same = racks[:, :, None] == racks[:, None, :]  # [P, S, S]
    s = state.max_replication_factor
    earlier = jnp.tril(jnp.ones((s, s), dtype=bool), k=-1)[None]
    exists = replica_exists(state)
    return (same & earlier).any(axis=2) & exists


@dataclasses.dataclass(frozen=True)
class RackAwareGoal(Goal):
    """Strict rack-awareness (RackAwareGoal.java): every replica of a
    partition lives in a distinct rack. Leadership moves always accepted;
    replica moves accepted iff the destination rack hosts no other replica
    of the partition (AbstractRackAwareGoal.java:96-130)."""

    def broker_violations(self, state, derived, constraint, aux):
        # Replicas of EXCLUDED topics cannot be moved, so their rack
        # duplicates are not counted as violations (the reference's rack
        # goal skips excluded topics rather than failing on them —
        # GoalUtils excluded-topic filtering).
        dup = _duplicate_mask(state) & derived.movable_partition[:, None]
        b = state.num_brokers
        seg = flatten_slots(
            jnp.where(state.assignment >= 0, state.assignment, b))
        out = jax.ops.segment_sum(flatten_slots(dup.astype(jnp.float32)), seg,
                                  num_segments=b + 1)
        return out[:b]

    def _dst_rack_conflict(self, state, deltas: CandidateDeltas):
        """[N] — destination rack already hosts another replica of the
        partition (excluding the moving slot itself)."""
        b = state.num_brokers
        p = deltas.partition
        assign_p = state.assignment[p]  # [N, S]
        rack_pad = jnp.concatenate([state.rack, state.rack[:1]])
        slot_racks = jnp.where(assign_p >= 0, rack_pad[jnp.clip(assign_p, 0, b - 1)], -1)
        dst_rack = state.rack[deltas.dst_broker]
        s = state.max_replication_factor
        not_moving = jnp.arange(s, dtype=jnp.int32)[None, :] != deltas.src_slot[:, None]
        return ((slot_racks == dst_rack[:, None]) & not_moving & (assign_p >= 0)).any(axis=1)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        is_move = deltas.replica_delta > 0
        return jnp.where(is_move, ~self._dst_rack_conflict(state, deltas), True)

    def improvement(self, state, derived, constraint, aux, deltas):
        dup = _duplicate_mask(state)
        # A move improves iff the moving replica currently duplicates a rack
        # and the destination rack is conflict-free; it regresses iff it
        # creates a new conflict.
        cur_dup = dup[deltas.partition, deltas.src_slot].astype(jnp.float32)
        new_conflict = self._dst_rack_conflict(state, deltas).astype(jnp.float32)
        is_move = deltas.replica_delta > 0
        imp = jnp.where(is_move, cur_dup - new_conflict, 0.0)
        return jnp.where(deltas.valid, imp, -jnp.inf)

    def dest_score(self, state, derived, constraint, aux):
        # Prefer emptier allowed brokers; per-partition feasibility is left
        # to acceptance/improvement.
        return jnp.where(derived.allowed_replica_move,
                         -derived.broker_replicas.astype(jnp.float32), -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        dup = _duplicate_mask(state)
        return jnp.where(dup, 1.0 + replica_load_total(state), -jnp.inf)

    def source_score(self, state, derived, constraint, aux):
        # Sources = brokers hosting duplicated replicas.
        return self.broker_violations(state, derived, constraint, aux)


@dataclasses.dataclass(frozen=True)
class RackAwareDistributionGoal(RackAwareGoal):
    """Relaxed rack-awareness (RackAwareDistributionGoal.java:449LoC):
    replicas balanced across racks — a rack may hold at most
    ceil(RF / num_racks) replicas of a partition."""

    def _limits(self, state):
        num_racks = state.rack.max() + 1
        rf = replica_exists(state).sum(axis=1)  # [P]
        return jnp.ceil(rf / jnp.maximum(num_racks, 1)).astype(jnp.int32)

    def _rack_counts_at(self, state, deltas, rack_of_broker):
        b = state.num_brokers
        p = deltas.partition
        assign_p = state.assignment[p]
        slot_racks = jnp.where(assign_p >= 0,
                               jnp.concatenate([state.rack, state.rack[:1]])[
                                   jnp.clip(assign_p, 0, b - 1)], -1)
        not_moving = (jnp.arange(state.max_replication_factor, dtype=jnp.int32)[None, :]
                      != deltas.src_slot[:, None])
        counts = ((slot_racks == rack_of_broker[:, None]) & not_moving
                  & (assign_p >= 0)).sum(axis=1)
        return counts

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        limit = self._limits(state)[deltas.partition]
        dst_rack = state.rack[deltas.dst_broker]
        dst_count = self._rack_counts_at(state, deltas, dst_rack)
        is_move = deltas.replica_delta > 0
        return jnp.where(is_move, dst_count + 1 <= limit, True)

    def improvement(self, state, derived, constraint, aux, deltas):
        limit = self._limits(state)[deltas.partition]
        src_rack = state.rack[deltas.src_broker]
        dst_rack = state.rack[deltas.dst_broker]
        src_count = self._rack_counts_at(state, deltas, src_rack)  # excludes mover
        dst_count = self._rack_counts_at(state, deltas, dst_rack)
        over_before = jnp.maximum(src_count + 1 - limit, 0) + jnp.maximum(dst_count - limit, 0)
        over_after = jnp.maximum(src_count - limit, 0) + jnp.maximum(dst_count + 1 - limit, 0)
        is_move = deltas.replica_delta > 0
        imp = jnp.where(is_move, (over_before - over_after).astype(jnp.float32), 0.0)
        return jnp.where(deltas.valid, imp, -jnp.inf)

    def broker_violations(self, state, derived, constraint, aux):
        # Violation: replicas beyond the per-rack ceiling, attributed to the
        # brokers hosting them (approximated by the strict duplicate count
        # beyond the ceiling).
        limit = self._limits(state)
        racks = _slot_racks(state)
        same = racks[:, :, None] == racks[:, None, :]
        s = state.max_replication_factor
        earlier = jnp.tril(jnp.ones((s, s), dtype=bool), k=0)[None]
        rank_in_rack = (same & earlier).sum(axis=2)  # 1-based occurrence rank
        over = (rank_in_rack > limit[:, None]) & replica_exists(state) \
            & derived.movable_partition[:, None]
        b = state.num_brokers
        seg = flatten_slots(
            jnp.where(state.assignment >= 0, state.assignment, b))
        out = jax.ops.segment_sum(flatten_slots(over.astype(jnp.float32)), seg,
                                  num_segments=b + 1)
        return out[:b]
