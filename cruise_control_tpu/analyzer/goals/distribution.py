"""Soft distribution goals.

Reference parity: analyzer/goals/ResourceDistributionGoal.java (1,078 LoC;
per-resource balance band avg·(1±threshold·margin), move-out/move-in/swap),
ReplicaDistributionGoal.java / LeaderReplicaDistributionGoal.java /
TopicReplicaDistributionGoal.java over ReplicaDistributionAbstractGoal.java,
PotentialNwOutGoal.java, LeaderBytesInDistributionGoal.java,
PreferredLeaderElectionGoal.java, MinTopicLeadersPerBrokerGoal.java.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ...common.resources import Resource
from ...model.tensors import (
    is_leader_slot, replica_exists, replica_load_column, replica_load_total,
    topic_broker_leader_counts,
    topic_broker_replica_counts,
)
from ..candidates import CandidateDeltas
from ..derived import count_limits, resource_limits
from ..fill import (
    best_fit_dests, deficit_fill_dests, exclusive_rank, rank_within_group,
)
from .base import Goal, donor_widened_shed, pair_improvement


def _band_viol(value, lower, upper):
    return jnp.maximum(value - upper, 0.0) + jnp.maximum(lower - value, 0.0)


def _int_deficit_headroom(counts, lower, upper):
    """Integer (deficit, remaining-headroom) planes from a float count
    plane and band: deficit = whole replicas needed to reach the lower
    band (capped by what fits under the upper band), headroom = whole
    replicas addable beyond that while staying at or under the upper
    band. Shapes broadcast ([G, B] counts with [G, 1] or scalar bands)."""
    h_int = jnp.floor(jnp.maximum(upper - counts, 0.0) + 1e-6)
    d_int = jnp.minimum(h_int, jnp.ceil(
        jnp.maximum(lower - counts, 0.0) - 1e-6))
    return jnp.maximum(d_int, 0.0), h_int - jnp.maximum(d_int, 0.0)


@dataclasses.dataclass(frozen=True)
class ResourceDistributionGoal(Goal):
    """Per-resource balance band around the cluster-average utilization
    (ResourceDistributionGoal.java §A.1-A.2 of SURVEY.md)."""

    resource: Resource = Resource.DISK

    def _limits(self, state, derived, constraint):
        return resource_limits(state, derived, constraint, self.resource)

    def _low_util(self, derived, constraint):
        # avg ≤ low.utilization.threshold flips the goal into no-op
        # (over-provisioned detection; ResourceDistributionGoal.java:262-277).
        r = int(self.resource)
        return derived.avg_util[r] <= constraint.low_utilization_threshold[r]

    def broker_violations(self, state, derived, constraint, aux):
        r = int(self.resource)
        lower, upper, _cap = self._limits(state, derived, constraint)
        load = derived.broker_load[:, r]
        viol = _band_viol(load, lower, upper)
        viol = jnp.where(derived.alive & derived.allowed_replica_move, viol, 0.0)
        return jnp.where(self._low_util(derived, constraint),
                         jnp.zeros_like(viol), viol)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        # ResourceDistributionGoal.actionAcceptance (MOVE/LEADERSHIP arm):
        # 1) if src above lower AND dst under upper now, require both to stay
        #    in band after; 2) otherwise require the move not to increase the
        #    pairwise utilization gap.
        r = int(self.resource)
        lower, upper, _cap = self._limits(state, derived, constraint)
        load = derived.broker_load[:, r]
        d = deltas.load_delta[:, r]
        eps = 1e-6
        load_src, load_dst = deltas.at_src(load), deltas.at_dst(load)
        lower_src, upper_dst = deltas.at_src(lower), deltas.at_dst(upper)
        # Round-start loads shifted by same-round higher-ranked candidates.
        ls = load_src - deltas.pre_load("pre_src_load", r)
        ld = load_dst + deltas.pre_load("pre_dst_load", r)

        # BRANCH CHOICE uses the UNSHIFTED loads: the pre terms may
        # overcount (rejected earlier candidates are included), and a
        # shifted predicate could flip from the strict stays_in_band branch
        # to the looser no_worse branch — non-monotone in the overcount,
        # breaking the conservative-relaxation contract. The band/util
        # CHECKS inside each branch use the shifted loads, where overcount
        # is strictly stricter.
        src_above_lower = load_src >= lower_src - eps
        dst_under_upper = load_dst <= upper_dst + eps
        stays_in_band = (ld + d <= upper_dst + eps) \
            & (ls - d >= lower_src - eps)

        cap_src = jnp.maximum(deltas.at_src(state.capacity[:, r]), 1e-9)
        cap_dst = jnp.maximum(deltas.at_dst(state.capacity[:, r]), 1e-9)
        util_src_before = ls / cap_src
        util_dst_after = (ld + d) / cap_dst
        no_worse = util_dst_after <= util_src_before + eps

        accept = jnp.where(src_above_lower & dst_under_upper, stays_in_band, no_worse)
        return accept | (d <= eps) | self._low_util(derived, constraint) \
            | (~deltas.at_src(derived.alive))

    def improvement(self, state, derived, constraint, aux, deltas):
        r = int(self.resource)
        lower, upper, _cap = self._limits(state, derived, constraint)

        def viol(value, at):
            return _band_viol(value, at(lower), at(upper))

        imp = pair_improvement(derived.broker_load[:, r], deltas,
                               deltas.load_delta[:, r], viol)
        # Tiebreak among BAND-FIXING moves only: prefer the one narrowing
        # the pair gap most. Never applied to imp <= 0 candidates — an
        # unconditional variance term accepts unbounded in-band refinement
        # churn (O(P) moves the reference never makes: its greedy only
        # acts on brokers outside the band, ResourceDistributionGoal
        # .java:380-435).
        load = derived.broker_load[:, r]
        d = deltas.load_delta[:, r]
        gap_before = deltas.at_src(load) - deltas.at_dst(load)
        gap_after = gap_before - 2 * d
        var_gain = (gap_before ** 2 - gap_after ** 2) * 1e-6
        return jnp.where(deltas.valid,
                         imp + jnp.where(imp > 0, var_gain, 0.0), -jnp.inf)

    def source_score(self, state, derived, constraint, aux):
        r = int(self.resource)
        lower, upper, _cap = self._limits(state, derived, constraint)
        shed = donor_widened_shed(derived.broker_load[:, r], lower, upper,
                                  derived)
        # Low-utilization state is a no-op for balancing (the goal flips to
        # over-provisioned detection, ResourceDistributionGoal.java:262-277):
        # no sources, so the search — fused or per-goal — generates no
        # candidates, consistent with broker_violations returning zeros.
        return jnp.where(self._low_util(derived, constraint),
                         jnp.zeros_like(shed), shed)

    def dest_score(self, state, derived, constraint, aux):
        r = int(self.resource)
        lower, upper, _cap = self._limits(state, derived, constraint)
        load = derived.broker_load[:, r]
        headroom = upper - load
        under_bonus = jnp.maximum(lower - load, 0.0) * 10.0
        return jnp.where(derived.allowed_replica_move & (headroom > 0),
                         headroom + under_bonus, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        # TWO-SIDED FIT-PRIORITY ordering (r5): replicas that can actually
        # complete an in-band move — small enough for some destination's
        # band gap AND for their own broker's surplus above its lower
        # band — rank above the rest (largest-fitting first,
        # first-fit-decreasing). The convergence tail stalls on the SRC
        # side of the stays_in_band acceptance: donors just above their
        # lower band cannot shed a replica bigger than their surplus, and
        # a size-descending order fills the grid with exactly those
        # vetoed moves (~52 accepted/round of a 256-source grid at 7k).
        # A pure feasibility MASK measured neutral-to-negative in r4
        # (oversized replicas must stay reachable for the no-worse
        # branch); this only reorders priority.
        r = int(self.resource)
        size = replica_load_column(state, r)
        lower, upper, _cap = self._limits(state, derived, constraint)
        load = derived.broker_load[:, r]
        headroom = upper - load
        elig = derived.replica_dest_ok & (headroom > 0)
        max_gap = jnp.max(jnp.where(elig, headroom, 0.0))
        b = state.num_brokers
        src_room = jnp.concatenate([load - lower, jnp.array([0.0])])[
            jnp.where(state.assignment >= 0, state.assignment, b)]
        peak = jnp.max(size) + 1.0
        fits = (size <= max_gap) & (size <= src_room) & (size > 0)
        return jnp.where(fits, peak + size, size)

    def swap_light_weight(self, state, derived, constraint, aux):
        # The replica's load of this resource. ``replica_weight`` lifts
        # the replicas that FIT a move above the rest (each against its
        # OWN broker's surplus over the lower band), so on a counterparty
        # near its lower band the "lightest" by it were its smallest
        # NON-fitting replicas, which can be larger than what the
        # overloaded broker offers: such a grid's swaps all carry load
        # TOWARDS the overloaded side (PERF.md, PR 34).
        return replica_load_column(state, int(self.resource))

    def target_dests(self, state, derived, constraint, aux,
                     cand_p, cand_s, src_valid, rank_stride=1,
                     rank_offset=0):
        # Size-matched (first-fit-decreasing) destination per card: the
        # shared top-num_dests list starves the convergence tail — once
        # only small under-band gaps remain, a heavy card fits none of
        # the listed destinations and the round stalls at a handful of
        # accepted moves (r4, docs/DESIGN.md "destination-limited tail").
        r = int(self.resource)
        lower, upper, _cap = self._limits(state, derived, constraint)
        headroom = upper - derived.broker_load[:, r]
        size = replica_load_column(state, r)[cand_p, cand_s]
        rank = exclusive_rank(src_valid) * rank_stride + rank_offset
        dst, ok = best_fit_dests(size, rank, headroom,
                                 derived.replica_dest_ok & (headroom > 0))
        return dst, ok & src_valid \
            & ~self._low_util(derived, constraint)

    def swap_leg_acceptance(self, state, derived, constraint, aux, leg):
        # Judged on the net transfer only (leg-wise band checks would veto
        # swaps whose net effect stays inside the band).
        return jnp.ones(leg.valid.shape[0], dtype=bool)

    def swap_net_acceptance(self, state, derived, constraint, aux, net):
        # Net transfer is SIGNED; accept iff the PAIR's band violation does
        # not worsen (two-sided — the one-sided move acceptance would let a
        # src-gaining swap blow past the source's band).
        r = int(self.resource)
        lower, upper, _cap = self._limits(state, derived, constraint)
        load = derived.broker_load[:, r]
        d = net.load_delta[:, r]

        def viol(value, at):
            return _band_viol(value, at(lower), at(upper))

        l_src, l_dst = net.at_src(load), net.at_dst(load)
        before = viol(l_src, net.at_src) + viol(l_dst, net.at_dst)
        after = viol(l_src - d, net.at_src) + viol(l_dst + d, net.at_dst)
        return (after <= before + 1e-6) \
            | self._low_util(derived, constraint)


@dataclasses.dataclass(frozen=True)
class CountDistributionGoal(Goal):
    """Replica- / leader-count balance
    (ReplicaDistributionGoal.java, LeaderReplicaDistributionGoal.java)."""

    leaders: bool = False
    count_based: bool = True
    supports_direct: bool = True

    def _counts(self, derived):
        return (derived.broker_leaders if self.leaders
                else derived.broker_replicas).astype(jnp.float32)

    def _limits(self, derived, constraint):
        if self.leaders:
            return count_limits(derived.avg_leaders,
                                constraint.leader_replica_balance_threshold)
        return count_limits(derived.avg_replicas, constraint.replica_balance_threshold)

    def _delta(self, deltas):
        return (deltas.leader_delta if self.leaders else deltas.replica_delta) \
            .astype(jnp.float32)

    def broker_violations(self, state, derived, constraint, aux):
        lower, upper = self._limits(derived, constraint)
        viol = _band_viol(self._counts(derived), lower, upper)
        return jnp.where(derived.alive, viol, 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        # ReplicaDistributionGoal.actionAcceptance: leadership/swap ACCEPT;
        # moves must keep dst under upper and src above lower (counting
        # same-round higher-ranked candidates' in/outflow).
        lower, upper = self._limits(derived, constraint)
        counts = self._counts(derived)
        d = self._delta(deltas)
        pre_dst = deltas.pre0("pre_dst_leaders" if self.leaders
                              else "pre_dst_count")
        pre_src = deltas.pre0("pre_src_leaders" if self.leaders
                              else "pre_src_count")
        dst_ok = deltas.at_dst(counts) + pre_dst + d <= upper + 1e-6
        src_ok = deltas.at_src(counts) - pre_src - d >= lower - 1e-6
        return (d == 0) | (dst_ok & src_ok) \
            | (~deltas.at_src(derived.alive))

    def improvement(self, state, derived, constraint, aux, deltas):
        lower, upper = self._limits(derived, constraint)

        def viol(value, _at):
            return _band_viol(value, lower, upper)

        imp = pair_improvement(self._counts(derived), deltas, self._delta(deltas), viol)
        counts = self._counts(derived)
        d = self._delta(deltas)
        gap_before = deltas.at_src(counts) - deltas.at_dst(counts)
        # Band-fixing tiebreak only (see ResourceDistributionGoal): an
        # unconditional variance term would accept O(P) in-band churn.
        var_gain = (gap_before ** 2 - (gap_before - 2 * d) ** 2) * 1e-6
        return jnp.where(deltas.valid,
                         imp + jnp.where(imp > 0, var_gain, 0.0), -jnp.inf)

    def source_score(self, state, derived, constraint, aux):
        lower, upper = self._limits(derived, constraint)
        return donor_widened_shed(self._counts(derived), lower, upper, derived)

    def dest_score(self, state, derived, constraint, aux):
        lower, upper = self._limits(derived, constraint)
        counts = self._counts(derived)
        headroom = upper - counts
        under_bonus = jnp.maximum(lower - counts, 0.0) * 10.0
        return jnp.where(derived.allowed_replica_move & (headroom > 0),
                         headroom + under_bonus, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        w = -replica_load_total(state)  # light replicas first
        if self.leaders:
            return jnp.where(is_leader_slot(state), w, -jnp.inf)
        return w

    def target_dests(self, state, derived, constraint, aux,
                     cand_p, cand_s, src_valid, rank_stride=1,
                     rank_offset=0):
        # Deficit-proportional fill over the single cluster-wide count
        # band (T = 1 case of the TopicReplica kernel): under-band
        # brokers absorb cards first, then remaining whole-count
        # headroom, each destination at most its integer gap per round.
        lower, upper = self._limits(derived, constraint)
        counts = self._counts(derived)
        deficit, headroom = _int_deficit_headroom(counts[None, :],
                                                  lower, upper)
        rank = exclusive_rank(src_valid) * rank_stride + rank_offset
        dst, ok = deficit_fill_dests(
            jnp.zeros_like(cand_p), rank, deficit,
            headroom, derived.replica_dest_ok)
        return dst, ok & src_valid

    def direct_spec(self, state, derived, constraint, aux, num_topics):
        # One cluster-wide group: the [B] count plane and its band. The
        # leaders variant relocates LEADER replicas (leadership travels
        # with the slot, so a relocation shifts the leader count exactly
        # like the greedy's leader-replica moves).
        lower, upper = self._limits(derived, constraint)
        counts = self._counts(derived)[None, :]
        group = jnp.zeros(state.assignment.shape, jnp.int32)
        movable = is_leader_slot(state) if self.leaders \
            else replica_exists(state)
        return (counts, jnp.reshape(lower, (1, 1)).astype(jnp.float32),
                jnp.reshape(upper, (1, 1)).astype(jnp.float32), group,
                movable)

    def swap_leg_acceptance(self, state, derived, constraint, aux, leg):
        # Counts are judged on the net transfer only.
        return jnp.ones(leg.valid.shape[0], dtype=bool)

    def swap_net_acceptance(self, state, derived, constraint, aux, net):
        # Replica counts are swap-invariant; leadership may transfer with
        # the heavier replica (net.leader_delta ∈ {-1, 0, 1}, signed) —
        # accept iff the pair's count-band violation does not worsen.
        lower, upper = self._limits(derived, constraint)
        counts = self._counts(derived)
        d = self._delta(net)

        def viol(value):
            return _band_viol(value, lower, upper)

        c_src, c_dst = net.at_src(counts), net.at_dst(counts)
        before = viol(c_src) + viol(c_dst)
        after = viol(c_src - d) + viol(c_dst + d)
        return after <= before + 1e-6


@dataclasses.dataclass(frozen=True)
class TopicReplicaDistributionGoal(Goal):
    """Per-topic replica balance across brokers
    (TopicReplicaDistributionGoal.java:594LoC). Uses a [T, B] count plane —
    fine up to mid-size clusters; sharded over the mesh at large T×B."""

    prefers_wide_batches: bool = True
    count_based: bool = True
    supports_direct: bool = True

    def prepare_partial(self, state, num_topics):
        return {"counts": topic_broker_replica_counts(state, num_topics)
                .astype(jnp.float32)}

    def partial_from_agg(self, agg):
        return {"counts": agg.topic_counts.astype(jnp.float32)}

    def finalize_aux(self, partial, state, derived, constraint):
        counts = partial["counts"]
        n_alive = jnp.maximum(derived.alive.sum(), 1)
        avg = (counts * derived.alive[None, :]).sum(axis=1) / n_alive  # [T]
        upper = jnp.ceil(avg * constraint.topic_replica_balance_threshold)
        lower = jnp.floor(avg / constraint.topic_replica_balance_threshold)
        return {"counts": counts, "upper": upper, "lower": lower}

    def broker_violations(self, state, derived, constraint, aux):
        viol = _band_viol(aux["counts"], aux["lower"][:, None], aux["upper"][:, None])
        return jnp.where(derived.alive, viol.sum(axis=0), 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        d = deltas.replica_delta.astype(jnp.float32)
        dst_cnt = deltas.at_dst_topic(aux["counts"]) \
            + deltas.pre0("pre_dst_topic_count")
        src_cnt = deltas.at_src_topic(aux["counts"]) \
            - deltas.pre0("pre_src_topic_count")
        dst_ok = dst_cnt + d <= deltas.at_topic(aux["upper"]) + 1e-6
        src_ok = src_cnt - d >= deltas.at_topic(aux["lower"]) - 1e-6
        return (d == 0) | (dst_ok & src_ok) \
            | (~deltas.at_src(derived.alive))

    def improvement(self, state, derived, constraint, aux, deltas):
        d = deltas.replica_delta.astype(jnp.float32)
        up, lo = deltas.at_topic(aux["upper"]), deltas.at_topic(aux["lower"])
        src_cnt = deltas.at_src_topic(aux["counts"])
        dst_cnt = deltas.at_dst_topic(aux["counts"])
        before = _band_viol(src_cnt, lo, up) + _band_viol(dst_cnt, lo, up)
        after = _band_viol(src_cnt - d, lo, up) + _band_viol(dst_cnt + d, lo, up)
        imp = before - after
        # Band-fixing tiebreak only (see ResourceDistributionGoal).
        var_gain = ((src_cnt - dst_cnt) ** 2 - (src_cnt - dst_cnt - 2 * d) ** 2) * 1e-6
        return jnp.where(deltas.valid,
                         imp + jnp.where(imp > 0, var_gain, 0.0), -jnp.inf)

    def _over_donor(self, derived, aux):
        """[T, B] — per-(topic, broker) shed pressure with donor widening."""
        return donor_widened_shed(aux["counts"], aux["lower"][:, None],
                                  aux["upper"][:, None], derived)

    def source_score(self, state, derived, constraint, aux):
        score = self._over_donor(derived, aux).sum(axis=0)
        return jnp.where(derived.alive, score, 0.0)

    def dest_score(self, state, derived, constraint, aux):
        headroom = jnp.maximum(aux["upper"][:, None] - aux["counts"], 0.0).sum(axis=0)
        return jnp.where(derived.allowed_replica_move, headroom, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        b = state.num_brokers
        t = state.topic[:, None]
        slot_b = jnp.clip(state.assignment, 0, b - 1)
        pressure = self._over_donor(derived, aux)
        w = pressure[t.repeat(state.max_replication_factor, 1), slot_b]
        return jnp.where(replica_exists(state), w, -jnp.inf)

    def target_dests(self, state, derived, constraint, aux,
                     cand_p, cand_s, src_valid, rank_stride=1,
                     rank_offset=0):
        # Per-topic deficit fill: the round-count bottleneck of the 7k/1M
        # north star (r4: ~65% of wall-clock) was this goal funneling
        # thousands of per-topic cards through ≤ num_dests shared
        # destinations — each card instead targets position rank-in-topic
        # of its topic's [deficit | headroom] profile, so a round's joint
        # assignment respects every (topic, broker) integer gap. Measured
        # at 7k (r5): the reachable fixed point deepens from residual
        # violation 1497 (r4, destination-starved) to ~53; a
        # deficit-only variant saved nothing (327 s vs 323 s) at worse
        # residual (80), so the full profile stays.
        t = state.topic[cand_p]
        deficit, headroom = _int_deficit_headroom(
            aux["counts"], aux["lower"][:, None], aux["upper"][:, None])
        rank = rank_within_group(t, src_valid) * rank_stride + rank_offset
        dst, ok = deficit_fill_dests(t, rank,
                                     deficit, headroom,
                                     derived.replica_dest_ok)
        return dst, ok & src_valid

    def direct_spec(self, state, derived, constraint, aux, num_topics):
        # Per-topic groups over the [T, B] count plane (the aux the goal
        # already maintains); every existing replica is movable, grouped
        # by its partition's topic.
        group = jnp.broadcast_to(state.topic[:, None],
                                 state.assignment.shape).astype(jnp.int32)
        return (aux["counts"], aux["lower"][:, None].astype(jnp.float32),
                aux["upper"][:, None].astype(jnp.float32), group,
                replica_exists(state))


@dataclasses.dataclass(frozen=True)
class PotentialNwOutGoal(Goal):
    """Keep potential NW-out (all replicas promoted) under the outbound
    capacity limit (PotentialNwOutGoal.java:367LoC)."""

    def _limit(self, state, constraint):
        r = int(Resource.NW_OUT)
        return constraint.capacity_threshold[r] * state.capacity[:, r]

    def broker_violations(self, state, derived, constraint, aux):
        limit = self._limit(state, constraint)
        return jnp.where(derived.alive,
                         jnp.maximum(derived.pot_nw_out - limit, 0.0), 0.0)

    def _pot_delta(self, state, deltas):
        # Moves shift the partition's full leader NW_OUT potential; pure
        # leadership moves don't change which brokers host replicas.
        nw = deltas.at_partition(state.leader_load[:, int(Resource.NW_OUT)])
        return jnp.where(deltas.replica_delta > 0, nw, 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        limit = self._limit(state, constraint)
        d = self._pot_delta(state, deltas)
        dst_after = deltas.at_dst(derived.pot_nw_out) \
            + deltas.pre0("pre_dst_pot") + d
        # Accept if destination stays within limit, or the source was
        # already violating (net improvement allowed).
        src_viol = deltas.at_src(derived.pot_nw_out) > deltas.at_src(limit)
        return (dst_after <= deltas.at_dst(limit) + 1e-6) | (d <= 0) | src_viol

    def improvement(self, state, derived, constraint, aux, deltas):
        limit = self._limit(state, constraint)

        def viol(value, at):
            return jnp.maximum(value - at(limit), 0.0)

        return pair_improvement(derived.pot_nw_out, deltas,
                                self._pot_delta(state, deltas), viol)

    def dest_score(self, state, derived, constraint, aux):
        headroom = self._limit(state, constraint) - derived.pot_nw_out
        return jnp.where(derived.allowed_replica_move & (headroom > 0),
                         headroom, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        nw = state.leader_load[:, int(Resource.NW_OUT)]
        return jnp.where(replica_exists(state), nw[:, None], -jnp.inf)


@dataclasses.dataclass(frozen=True)
class LeaderBytesInDistributionGoal(Goal):
    """Balance leader bytes-in across brokers via leadership moves
    (LeaderBytesInDistributionGoal.java:288LoC)."""

    def prepare_partial(self, state, num_topics):
        from ...model.tensors import leader_bytes_in
        return {"lbi": leader_bytes_in(state)}

    def partial_from_agg(self, agg):
        return {"lbi": agg.lbi}

    def finalize_aux(self, partial, state, derived, constraint):
        lbi = partial["lbi"]
        n = jnp.maximum(derived.allowed_leadership.sum(), 1)
        avg = (lbi * derived.allowed_leadership).sum() / n
        return {"lbi": lbi, "avg": avg}

    def _upper(self, aux, constraint):
        t = constraint.resource_balance_threshold[int(Resource.NW_IN)]
        return aux["avg"] * t

    def broker_violations(self, state, derived, constraint, aux):
        upper = self._upper(aux, constraint)
        return jnp.where(derived.alive, jnp.maximum(aux["lbi"] - upper, 0.0), 0.0)

    def _lbi_delta(self, state, deltas):
        nw_in = deltas.at_partition(state.leader_load[:, int(Resource.NW_IN)])
        return jnp.where(deltas.leader_delta > 0, nw_in, 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        upper = self._upper(aux, constraint)
        d = self._lbi_delta(state, deltas)
        dst_after = deltas.at_dst(aux["lbi"]) \
            + deltas.pre0("pre_dst_lbi") + d
        src_over = deltas.at_src(aux["lbi"]) > upper
        return (dst_after <= upper + 1e-6) | (d <= 0) | src_over

    def improvement(self, state, derived, constraint, aux, deltas):
        upper = self._upper(aux, constraint)

        def viol(value, _at):
            return jnp.maximum(value - upper, 0.0)

        imp = pair_improvement(aux["lbi"], deltas, self._lbi_delta(state, deltas), viol)
        lbi = aux["lbi"]
        d = self._lbi_delta(state, deltas)
        gap = deltas.at_src(lbi) - deltas.at_dst(lbi)
        var_gain = (gap ** 2 - (gap - 2 * d) ** 2) * 1e-6
        return jnp.where(deltas.valid, imp + var_gain, -jnp.inf)

    def dest_score(self, state, derived, constraint, aux):
        headroom = self._upper(aux, constraint) - aux["lbi"]
        return jnp.where(derived.allowed_leadership, headroom, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        nw_in = state.leader_load[:, int(Resource.NW_IN)]
        return jnp.where(is_leader_slot(state), nw_in[:, None], -jnp.inf)


@dataclasses.dataclass(frozen=True)
class PreferredLeaderElectionGoal(Goal):
    """Make the PREFERRED replica the leader everywhere — the first
    replica in list order whose broker is allowed to lead
    (PreferredLeaderElectionGoal.java:232LoC: demoted/excluded brokers are
    skipped, so demotion moves leadership to the next eligible replica,
    not merely to slot 0). Leadership-only."""

    def _preferred_slot(self, state, derived):
        """[P] int32 — first existing slot whose broker may lead;
        ``S`` (out of range) when no slot is eligible."""
        b = state.num_brokers
        exists = replica_exists(state)
        ok = exists & derived.allowed_leadership[
            jnp.clip(state.assignment, 0, b - 1)]
        s = state.max_replication_factor
        slot_ids = jnp.arange(s, dtype=jnp.int32)[None, :]
        return jnp.where(ok, slot_ids, s).min(axis=1)

    def _misled(self, state, derived):
        """[P] bool — leader differs from the preferred eligible slot."""
        pref = self._preferred_slot(state, derived)
        s = state.max_replication_factor
        return state.partition_mask & (pref < s) \
            & (state.leader_slot != pref)

    def broker_violations(self, state, derived, constraint, aux):
        misled = self._misled(state, derived)
        b = state.num_brokers
        lead_b = jnp.take_along_axis(
            state.assignment, jnp.maximum(state.leader_slot, 0)[:, None], axis=1)[:, 0]
        seg = jnp.where(misled, jnp.clip(lead_b, 0, b - 1), b)
        return jax.ops.segment_sum(misled.astype(jnp.float32), seg,
                                   num_segments=b + 1)[:b]

    def improvement(self, state, derived, constraint, aux, deltas):
        pref = deltas.at_partition(self._preferred_slot(state, derived))
        is_lead = deltas.replica_delta == 0
        fixes = (deltas.src_slot != pref) & (deltas.dst_slot == pref)
        imp = jnp.where(is_lead & fixes, 1.0, 0.0)
        return jnp.where(deltas.valid, imp, -jnp.inf)

    def dest_score(self, state, derived, constraint, aux):
        return jnp.where(derived.allowed_leadership, 0.0, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        misled = self._misled(state, derived)[:, None]
        return jnp.where(is_leader_slot(state) & misled, 1.0, -jnp.inf)

    def source_score(self, state, derived, constraint, aux):
        return jnp.ones(state.num_brokers)


@dataclasses.dataclass(frozen=True)
class MinTopicLeadersPerBrokerGoal(Goal):
    """Brokers must each host at least ``min_leaders`` leaders of every
    interested topic (MinTopicLeadersPerBrokerGoal.java:465LoC). With the
    default empty interest set this is a no-op, as in the reference."""

    min_leaders: int = 0

    def prepare_partial(self, state, num_topics):
        if self.min_leaders <= 0:
            return None
        return {"leader_counts": topic_broker_leader_counts(state, num_topics)}

    def broker_violations(self, state, derived, constraint, aux):
        if aux is None:
            return jnp.zeros(state.num_brokers)
        deficit = jnp.maximum(self.min_leaders - aux["leader_counts"], 0)
        return jnp.where(derived.alive, deficit.sum(axis=0).astype(jnp.float32), 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        if aux is None:
            return jnp.ones(deltas.valid.shape[0], dtype=bool)
        cnt = deltas.at_src_topic(aux["leader_counts"]) \
            - deltas.pre0("pre_src_topic_leaders")
        d = deltas.leader_delta
        return (d == 0) | (cnt - d >= self.min_leaders)

    def improvement(self, state, derived, constraint, aux, deltas):
        return jnp.where(deltas.valid, 0.0, -jnp.inf)

    def dest_score(self, state, derived, constraint, aux):
        return jnp.where(derived.allowed_leadership, 0.0, -jnp.inf)
