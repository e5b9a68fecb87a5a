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


def other_replica_racks(state, partition, src_slot):
    """[rows, S] — rack of every OTHER live replica of each row's
    partition (the moving slot ``src_slot`` and empty slots read a rack no
    broker has). A row is a grid row or a source card: partition and
    moving slot are fixed there."""
    b = state.num_brokers
    assign_p = state.assignment[partition]  # [rows, S]
    rack_pad = jnp.concatenate([state.rack, state.rack[:1]])
    slot_racks = rack_pad[jnp.clip(assign_p, 0, b - 1)]
    s = state.max_replication_factor
    not_moving = jnp.arange(s, dtype=jnp.int32)[None, :] \
        != src_slot[:, None]
    no_rack = jnp.iinfo(slot_racks.dtype).min
    return jnp.where(not_moving & (assign_p >= 0), slot_racks, no_rack)


def _other_replica_racks(state, deltas: CandidateDeltas):
    """[N, S] — ``other_replica_racks`` per candidate: built once per grid
    row, then broadcast."""
    return deltas.from_rows(other_replica_racks(
        state, deltas.row_partition, deltas.row_src_slot))


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
        dst_rack = deltas.at_dst(state.rack)
        return (_other_replica_racks(state, deltas)
                == dst_rack[:, None]).any(axis=1)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        is_move = deltas.replica_delta > 0
        return jnp.where(is_move, ~self._dst_rack_conflict(state, deltas), True)

    def card_dest_ok(self, state, cand_p, cand_s):
        # ``acceptance`` as far as it depends on the card alone: the
        # brokers whose rack hosts no OTHER replica of the card's partition.
        others = other_replica_racks(state, cand_p, cand_s)     # [k, S]
        return ~(state.rack[None, None, :] == others[:, :, None]) \
            .any(axis=1)

    def improvement(self, state, derived, constraint, aux, deltas):
        dup = _duplicate_mask(state)
        # A move improves iff the moving replica currently duplicates a rack
        # and the destination rack is conflict-free; it regresses iff it
        # creates a new conflict.
        cur_dup = deltas.at_src_slot(dup).astype(jnp.float32)
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
        return (_other_replica_racks(state, deltas)
                == rack_of_broker[:, None]).sum(axis=1)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        limit = deltas.at_partition(self._limits(state))
        dst_rack = deltas.at_dst(state.rack)
        dst_count = self._rack_counts_at(state, deltas, dst_rack)
        is_move = deltas.replica_delta > 0
        return jnp.where(is_move, dst_count + 1 <= limit, True)

    def card_dest_ok(self, state, cand_p, cand_s):
        others = other_replica_racks(state, cand_p, cand_s)     # [k, S]
        in_rack = (state.rack[None, None, :] == others[:, :, None]) \
            .sum(axis=1)
        return in_rack + 1 <= self._limits(state)[cand_p][:, None]

    def improvement(self, state, derived, constraint, aux, deltas):
        limit = deltas.at_partition(self._limits(state))
        src_rack = deltas.at_src(state.rack)
        dst_rack = deltas.at_dst(state.rack)
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
