"""Kafka-assigner emulation mode.

Reference parity: analyzer/kafkaassigner/ —
KafkaAssignerEvenRackAwareGoal.java:523 (strict rack-awareness PLUS an even
per-broker replica ceiling, the kafka-assigner tool's placement contract)
and KafkaAssignerDiskUsageDistributionGoal.java:722 (disk balance within a
threshold band). The reference's swap-based inner loop is re-expressed as
the batched move search: the conflict-free accept step reaches the same
balance band invariant that the pairwise swaps do, one fused round at a
time (the two halves of a swap land in consecutive rounds).

Reference-parity deviation (deliberate): the reference's swap inner loop
never exceeds the even ceiling at ANY intermediate state, while this
goal's deadlock-breaking acceptance lets a rack-duplicate-fixing move
land on a broker at ceiling+1 transiently (see ``acceptance``); later
rounds shed the overage (2·rack + count strictly decreases, so the
two-step path terminates). Failure mode if the shed move is vetoed by a
stacked goal or the round cap: the final placement can retain a
ceiling+1 broker — the overage is counted in ``broker_violations``, so
the hard goal REPORTS as violated (OptimizationFailureError) rather than
failing silently. Randomized skewed-rack sweeps exercising both the
curated deadlock fixture and the property-level invariant live in
tests/test_kafka_assigner_property.py.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ...common.resources import Resource
from ..candidates import CandidateDeltas
from .base import Goal, pair_improvement
from .rack import RackAwareGoal, _duplicate_mask


@dataclasses.dataclass(frozen=True)
class KafkaAssignerEvenRackAwareGoal(RackAwareGoal):
    """Rack-aware + ceil(total/alive) replica-count ceiling per broker."""

    name: str = "KafkaAssignerEvenRackAwareGoal"
    is_hard: bool = True
    # The reference's inner loop is SWAP-based (per-position exchanges
    # that never disturb per-broker counts); the move search covers most
    # shapes, but max-tight layouts (a rack at exactly B/RF brokers)
    # need a count-preserving exchange: a duplicate leaves its crowded
    # rack for an at-ceiling broker whose own movable replica returns to
    # the freed under-ceiling broker. See swap_improvement/
    # swap_dest_score below.
    supports_swap: bool = True

    def _ceiling(self, derived) -> jnp.ndarray:
        total = (derived.broker_replicas * derived.alive).sum()
        n = jnp.maximum(derived.alive.sum(), 1)
        return jnp.ceil(total / n).astype(jnp.int32)

    def broker_violations(self, state, derived, constraint, aux):
        rack_v = super().broker_violations(state, derived, constraint, aux)
        over = jnp.maximum(
            derived.broker_replicas - self._ceiling(derived), 0)
        return rack_v + jnp.where(derived.alive, over, 0).astype(jnp.float32)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        rack_ok = super().acceptance(state, derived, constraint, aux, deltas)
        cap = self._ceiling(derived)
        dst_after = deltas.at_dst(derived.broker_replicas) \
            + deltas.pre0("pre_dst_count") + 1
        under_cap = dst_after <= cap
        # Deadlock breaker: a RACK-duplicate-fixing move may overshoot the
        # even ceiling by ONE. On skewed clusters every under-ceiling
        # broker in a partition's free rack can sit exactly at the
        # ceiling, and a pure greedy stalls where the reference's
        # swap-based inner loop (KafkaAssignerEvenRackAwareGoal.java's
        # per-position swaps) proceeds; the overshoot converts the rack
        # violation into a count violation that later rounds shed
        # (improvement weights rack 2x count, so both steps score > 0).
        #
        # Overshoot GUARD (r5 property-sweep finding): an overshoot onto a
        # broker with no shed channel — no hosted replica with a feasible
        # rack-compatible under-cap destination — is a dead end: the
        # ceiling+1 count violation can never be shed, and near-tight
        # layouts (e.g. 9/4/4/1 racks at RF 2) stalled exactly there. The
        # reference never hits this because its swap exchanges the two
        # replicas atomically; here the overshoot leg is only admitted
        # where the shed leg exists, so the two-step path stays live.
        fixes_dup = deltas.at_src_slot(_duplicate_mask(state))
        shed_count = self._shed_count_per_broker(state, derived)
        # COUNT-matched, not boolean: each same-round overshoot onto a
        # broker must claim a DISTINCT shed channel (pre_dst_count is the
        # cumulative same-round inflow, conservatively overcounted), else
        # two overshoots can share one channel and strand a ceiling+1
        # overage on a broker that can no longer shed.
        tolerant = fixes_dup & (dst_after <= cap + 1) \
            & (under_cap
               | (deltas.pre0("pre_dst_count")
                  < deltas.at_dst(shed_count)))
        is_move = deltas.replica_delta > 0
        return rack_ok & jnp.where(is_move, under_cap | tolerant, True)

    def improvement(self, state, derived, constraint, aux, deltas):
        rack_imp = super().improvement(state, derived, constraint, aux, deltas)
        cap = self._ceiling(derived).astype(jnp.float32)
        counts = derived.broker_replicas.astype(jnp.float32)
        count_imp = pair_improvement(
            counts, deltas, deltas.replica_delta.astype(jnp.float32),
            lambda v, _at: jnp.maximum(v - cap, 0.0))
        # Rack fixes outweigh the count violation they may create (the
        # two-step deadlock-breaking path above must score positive at
        # both steps; terminates because 2*rack + count strictly falls).
        return jnp.where(deltas.valid, 2.0 * rack_imp + count_imp, -jnp.inf)

    def source_score(self, state, derived, constraint, aux):
        return self.broker_violations(state, derived, constraint, aux)

    def dest_score(self, state, derived, constraint, aux):
        cap = self._ceiling(derived)
        room = (cap - derived.broker_replicas).astype(jnp.float32)
        # room >= 0 (not > 0): AT-CAP brokers must stay in the candidate
        # grid — the duplicate-fixing overshoot path in ``acceptance`` is
        # unreachable if dest_score filters them to -inf before scoring.
        return jnp.where(derived.allowed_replica_move & (room >= 0), room,
                         -jnp.inf)

    def swap_leg_acceptance(self, state, derived, constraint, aux, leg):
        # Swaps keep per-broker counts, so only the RACK check applies per
        # leg — the inherited move acceptance (count ceiling) would veto
        # every swap once brokers sit at the even ceiling (the steady state
        # of kafka-assigner mode).
        return RackAwareGoal.acceptance(self, state, derived, constraint,
                                        aux, leg)

    def swap_improvement(self, state, derived, constraint, aux,
                         fwd, rev, net):
        # Each directional leg judged as a rack move (duplicate fixed
        # minus conflict created); counts are swap-invariant so the even
        # ceiling needs no term. A swap that fixes one duplicate while
        # creating another sums to 0 and is never applied.
        imp_f = RackAwareGoal.improvement(self, state, derived, constraint,
                                          aux, fwd)
        imp_r = RackAwareGoal.improvement(self, state, derived, constraint,
                                          aux, rev)
        both = jnp.where(jnp.isfinite(imp_f), imp_f, 0.0) \
            + jnp.where(jnp.isfinite(imp_r), imp_r, 0.0)
        return jnp.where(net.valid, both, -jnp.inf)

    def swap_dest_score(self, state, derived, constraint, aux):
        # Counterparties for the exchange: AT-ceiling brokers with a shed
        # channel (a hosted replica that can move into an under-ceiling
        # rack without creating a duplicate — the replica the reverse leg
        # sends back). dest_score would exclude them all (room <= 0),
        # which is exactly why moves alone stall on max-tight layouts.
        # OVER-ceiling brokers are EXCLUDED: a count-preserving exchange
        # does nothing for their overage but consumes the very replica
        # their shed needs (the measured strand: a ceiling+1 broker whose
        # channel a swap ate). The SOURCE side needs no twin exclusion:
        # move passes run to their fixed point before each swap pass, so
        # a shed-feasible replica on an over broker (duplicate or not)
        # has already been moved out as a plain shed/dup-fix before any
        # swap could trade it away.
        over = derived.broker_replicas > self._ceiling(derived)
        has_shed = (self._shed_count_per_broker(state, derived) > 0
                    ).astype(jnp.float32)
        ok = derived.allowed_replica_move & derived.alive & ~over
        return jnp.where(ok, has_shed + 0.1, -jnp.inf)

    def _shed_count_per_broker(self, state, derived):
        """[B] int32 — number of hosted replicas with a feasible
        rack-compatible strictly-under-cap destination (shed channels);
        shared by the overshoot guard and swap_dest_score."""
        _dup_ok, shed_ok = self._rack_dest_feasibility(state, derived)
        b = state.num_brokers
        seg = jnp.where(state.assignment >= 0, state.assignment, b)
        return jnp.zeros(b + 1, jnp.int32).at[seg].add(
            shed_ok.astype(jnp.int32))[:b]

    def _rack_dest_feasibility(self, state, derived):
        """([P, S] dup-feasible, [P, S] shed-feasible): does a
        rack-compatible destination currently exist for this replica —
        at-cap brokers count for duplicate fixes (the ceiling+1 overshoot
        path), strictly-under-cap for plain count sheds. A replica's
        destination rack may be (a) any rack the partition does not use,
        or (b) its OWN rack (same-rack relocation never creates a
        duplicate). Rack scatter sizes are bounded by B (rack ids < B),
        so everything stays static-shaped."""
        from .rack import _slot_racks
        from ...model.tensors import replica_exists

        b = state.num_brokers
        room = self._ceiling(derived) - derived.broker_replicas
        ok = derived.allowed_replica_move & derived.alive
        under = ok & (room > 0)
        at = ok & (room >= 0)
        rack_of = jnp.clip(state.rack, 0, b - 1)
        n_under_by_rack = jnp.zeros(b, jnp.int32).at[rack_of].add(
            under.astype(jnp.int32))
        n_at_by_rack = jnp.zeros(b, jnp.int32).at[rack_of].add(
            at.astype(jnp.int32))      # [B]-indexed by rack id

        racks = _slot_racks(state)          # [P, S]; empty slots negative
        exists = replica_exists(state)
        same = racks[:, :, None] == racks[:, None, :]
        s = state.max_replication_factor
        earlier = jnp.tril(jnp.ones((s, s), dtype=bool), k=-1)[None]
        first_occ = exists & ~(same & earlier).any(axis=2)
        safe_racks = jnp.clip(racks, 0, b - 1)

        own_broker = jnp.where(state.assignment >= 0, state.assignment, b)

        def feasible(room, n_by_rack):
            # (a) an unused rack with room: #rooms racks > #distinct used
            # rooms racks (used non-room racks never block an unused one).
            has_room = n_by_rack > 0
            n_rooms = has_room.sum()
            used_rooms = (first_occ & has_room[safe_racks]).sum(axis=1)
            unused_rack = (n_rooms > used_rooms)[:, None]         # [P, 1]
            # (b) own-rack relocation: this slot's rack has a room-bearing
            # broker OTHER THAN the replica's own, and no other slot of
            # the partition shares the rack. The own broker must be
            # excluded from its rack's room count: a replica cannot
            # relocate onto the broker already hosting it, and counting
            # it manufactured a self-referential "shed channel" that let
            # the overshoot guard admit a same-round ceiling+1 overshoot
            # (ADVICE round-5 finding).
            sole = ~((same & ~jnp.eye(s, dtype=bool)[None]) & exists[:, None, :]
                     ).any(axis=2)
            self_room = jnp.concatenate(
                [room, jnp.array([False])])[own_broker]
            others_room = n_by_rack[safe_racks] - self_room.astype(jnp.int32)
            own_ok = (others_room > 0) & sole & exists
            return (unused_rack & exists) | own_ok

        return (feasible(at, n_at_by_rack),
                feasible(under, n_under_by_rack))

    def replica_weight(self, state, derived, constraint, aux):
        # Unlike the pure rack goal (which only moves duplicated replicas),
        # the count ceiling needs ordinary replicas movable too. Priority
        # is FEASIBILITY-AWARE (property-sweep finding: on heavily skewed
        # layouts the deterministic top-k filled with currently-unmovable
        # duplicates while the over-cap sheds that would free the needed
        # headroom never surfaced — a stall the reference's swap inner
        # loop sidesteps by exchanging in place):
        #   1. duplicates with a feasible rack-compatible destination,
        #   2. replicas on over-ceiling brokers with a feasible
        #      strictly-under-cap destination (the headroom openers),
        #   3. everything else (retried as feasibility shifts).
        from ...model.tensors import replica_exists, replica_load_total
        dup = _duplicate_mask(state)
        load = replica_load_total(state)
        peak = load.max() + 1.0
        dup_ok, shed_ok = self._rack_dest_feasibility(state, derived)
        over = derived.broker_replicas > self._ceiling(derived)
        b = state.num_brokers
        on_over = jnp.concatenate([over, jnp.array([False])])[
            jnp.where(state.assignment >= 0, state.assignment, b)]
        w = jnp.where(replica_exists(state), peak - load, -jnp.inf)
        # Shed-feasible replicas on NON-over brokers rank LIGHTEST: they
        # are the replicas the swap grid's light-side selection must
        # offer as the exchange's reverse leg (at-ceiling counterparties,
        # swap_dest_score). Move-grid sources are unaffected — their
        # brokers have zero violations, so on_source excludes them.
        w = jnp.where(shed_ok & ~on_over & ~dup, 0.5 * peak - load, w)
        w = jnp.where(on_over & shed_ok & ~dup, 3 * peak + load, w)
        w = jnp.where(dup & dup_ok, 5 * peak + load, w)
        return jnp.where(dup & ~dup_ok, peak + load, w)

    def target_dests(self, state, derived, constraint, aux,
                     cand_p, cand_s, src_valid, rank_stride=1,
                     rank_offset=0):
        # Per-card RACK-COMPATIBLE destination: the shared top-num_dests
        # list ranks by count headroom alone, and on skewed layouts every
        # listed destination can be rack-conflicted for the specific
        # partitions that must shed (property-sweep stall: count
        # violations at a fixed point). Choose, per card, the
        # most-headroom broker whose rack hosts no OTHER replica of the
        # card's partition; duplicate-fixing cards may also target at-cap
        # brokers (the ceiling+1 overshoot path in ``acceptance``).
        # O(k·B) mask — kafka-assigner chains run at tool scale.
        b = state.num_brokers
        s = state.max_replication_factor
        assign_p = state.assignment[cand_p]                        # [k, S]
        slot_racks = jnp.where(assign_p >= 0,
                               state.rack[jnp.clip(assign_p, 0, b - 1)], -1)
        not_moving = jnp.arange(s, dtype=jnp.int32)[None, :] \
            != cand_s[:, None]
        used = jnp.where(not_moving & (assign_p >= 0), slot_racks, -1)
        conflict = (state.rack[None, None, :] == used[:, :, None]) \
            .any(axis=1)                                           # [k, B]
        room = (self._ceiling(derived) - derived.broker_replicas) \
            .astype(jnp.float32)                                   # [B]
        fixes_dup = _duplicate_mask(state)[cand_p, cand_s]
        min_room = jnp.where(fixes_dup, 0.0, 1.0)
        score = jnp.where(
            derived.allowed_replica_move[None, :] & derived.alive[None, :]
            & ~conflict & (room[None, :] >= min_room[:, None]),
            room[None, :], -jnp.inf)
        dst = jnp.argmax(score, axis=1).astype(jnp.int32)
        ok = jnp.isfinite(jnp.max(score, axis=1)) & src_valid
        return dst, ok


@dataclasses.dataclass(frozen=True)
class KafkaAssignerDiskUsageDistributionGoal(Goal):
    """Disk usage of every alive broker within
    avg·(1 ± (threshold-1)·margin) (KafkaAssignerDiskUsageDistributionGoal's
    balance band; the reference fixed margin is also 0.9 via
    BALANCE_MARGIN)."""

    name: str = "KafkaAssignerDiskUsageDistributionGoal"
    is_hard: bool = False

    def _band(self, derived, constraint):
        avg = derived.avg_util[Resource.DISK]
        lo_mult, hi_mult = constraint.balance_band(Resource.DISK)
        return avg * lo_mult, avg * hi_mult

    def _util(self, state, derived):
        cap = jnp.maximum(state.capacity[:, Resource.DISK], 1e-9)
        return derived.broker_load[:, Resource.DISK] / cap

    def broker_violations(self, state, derived, constraint, aux):
        lower, upper = self._band(derived, constraint)
        util = self._util(state, derived)
        over = jnp.maximum(util - upper, 0.0) + jnp.maximum(lower - util, 0.0)
        return jnp.where(derived.alive, over, 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        # Destination must stay inside the upper band after the move.
        _lower, upper = self._band(derived, constraint)
        dst_cap = jnp.maximum(
            deltas.at_dst(state.capacity[:, Resource.DISK]), 1e-9)
        dst_util_after = (deltas.at_dst(derived.broker_load[:, Resource.DISK])
                          + deltas.pre_load("pre_dst_load", int(Resource.DISK))
                          + deltas.load_delta[:, Resource.DISK]) / dst_cap
        is_move = deltas.replica_delta > 0
        return jnp.where(is_move, dst_util_after <= upper, True)

    def improvement(self, state, derived, constraint, aux, deltas):
        lower, upper = self._band(derived, constraint)
        load = derived.broker_load[:, Resource.DISK]
        cap = jnp.maximum(state.capacity[:, Resource.DISK], 1e-9)

        def viol(value, at):
            util = value / at(cap)
            return jnp.maximum(util - upper, 0.0) + jnp.maximum(lower - util, 0.0)

        return pair_improvement(load, deltas,
                                deltas.load_delta[:, Resource.DISK], viol)

    def source_score(self, state, derived, constraint, aux):
        from .base import donor_widened_shed
        lower, upper = self._band(derived, constraint)
        return donor_widened_shed(self._util(state, derived), lower, upper,
                                  derived)

    def dest_score(self, state, derived, constraint, aux):
        _lower, upper = self._band(derived, constraint)
        util = self._util(state, derived)
        room = upper - util
        return jnp.where(derived.allowed_replica_move & (room > 0), room,
                         -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        from ...model.tensors import replica_load_column
        return replica_load_column(state, int(Resource.DISK))
