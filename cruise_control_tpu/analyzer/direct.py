"""Direct-assignment transport kernels for the count-distribution goals.

The greedy search pays for a count imbalance in ROUNDS: each round
scores a top-k grid, accepts a conflict-free batch, and re-dispatches —
at the 7k-broker/1M-partition north star TopicReplicaDistributionGoal
alone burns hundreds of acceptance-density-limited rounds shedding ~980
moves each (ROADMAP item 1). But a count goal's fixed point is KNOWN in
closed form: the per-broker (or per-topic×broker) target band is a pure
function of the counts, so the whole solve is a transport problem —
surplus replicas → deficit slots — not a search problem. This module
solves that transport as a vectorized matching in one (or a few) device
dispatches (the Podracer/Anakin "stop iterating" lever):

1. **Target counts on device**: the active goal's count plane
   ``[G, B]`` and band ``[lower, upper]`` (``G`` = 1 for the
   replica/leader goals, ``num_topics`` for the topic goal), as
   FRACTIONAL per-cell shed/fill targets resolved to integers by
   deterministic randomized rounding (round 21: one plan for every
   density regime — see ``_surplus_deficit``), with proportional donor
   widening when deficits exceed base surplus.
2. **Surplus replica selection**: ONE segmented sort of the flattened
   replica axis by ``(cell, weight)`` — cell = (group, src broker) —
   ranks every replica within its cell; the ``surplus[cell]`` lightest
   movable replicas are the movers (light-first, matching the greedy's
   ``replica_weight``).
3. **Cumsum rank-assignment**: each mover's rank within its group maps
   through the group's cumulative ``[deficit | headroom]`` profile
   (``analyzer.fill.deficit_fill_dests`` — the same kernel the targeted
   destination column uses per-card) to a destination broker, so the
   joint assignment respects every cell's integer gap by construction.
4. **Feasibility masking**: RF-sibling exclusion (destination must not
   already host the partition — nor receive two siblings in one
   sweep), rack-awareness when a rack goal is stacked prior, dead
   brokers, per-request exclusion options, the new-broker gate, and
   leadership-excluded destinations for leader movers.
5. **Prior-goal guards**: destination caps and source floors of every
   previously-optimized goal (replica-capacity / count bands / resource
   bands / capacity thresholds / potential NW-out), evaluated JOINTLY
   via dst-/src-sorted segmented exclusive cumsums — the
   ``attach_cumulative`` pre-delta contract at O(n log n) instead of
   O(m²), with the same conservative-overcount semantics (a vetoed
   earlier mover still shifts later movers' checks, which can only make
   them stricter).
6. **One-shot scatter apply**: all surviving movers land in a single
   functional scatter; a small on-device sweep loop (``max_sweeps``)
   re-runs the plan on the updated counts until nothing moves, so
   feasibility-vetoed leftovers get a second pairing without a host
   round-trip.

Anything the transport cannot place (structurally-blocked residue)
stays for the greedy polish pass that follows — the kernel REPLACES the
deficit-sized bulk rounds, not the acceptance machinery's judgment.

Safety discipline (two prior density "fixes" silently flipped the
86.0 → 82.74 CpuUsageDistribution canary and were reverted): the kernel
ships behind ``solver.direct.assignment.enabled`` (default OFF), only
activates in the wide regime (``solver.wide.batch.min.brokers``) where
deficit-sized greedy ran before, refuses chains whose prior goals it
cannot guard (``direct_eligible``), and is gated on the bench
regression sentry + full fixture matrix, never on round counts.

SPMD layout (round 21): every rank the plan assigns — within-cell
mover ranks, group fill ranks, per-destination intake positions,
per-source outflow positions — is parameterized by
``(rank_stride, block)``: a replica on block ``d`` with local rank
``r`` occupies global position ``r·stride + d``. On the partition-
sharded mesh each device passes its shard index as ``block`` and the
shard count as ``rank_stride``, so device-local sorts yield globally
unique positions without a global sort (the ``target_dests``
interleaved-fill treatment, generalized to the whole plan). Load-sum
guards cannot interleave (per-mover loads are heterogeneous), so each
block is budgeted ``1/stride`` of the remaining headroom —
conservative, never unsafe. ``rank_stride == 1`` (every single-device
caller) is byte-identical to the unparameterized plan.

Donation contract: the donated twins donate EXACTLY the strip_mutable
pair ``{assignment, leader_slot}`` (CCSA002-checked); topology tensors
are refresh-cache-shared and never donated.
"""

from __future__ import annotations

import dataclasses
import zlib
from functools import partial

import jax
import jax.numpy as jnp

from ..common.resources import Resource
from ..model.tensors import (
    ClusterTensors, is_leader_slot, replica_load_total,
    topic_broker_replica_counts,
)
from .constraint import BalancingConstraint
from .derived import compute_derived, count_limits, resource_limits
from .fill import deficit_fill_dests
from .goals.base import Goal
from .goals.capacity import ReplicaCapacityGoal, ResourceCapacityGoal
from .goals.distribution import (
    CountDistributionGoal, PotentialNwOutGoal, TopicReplicaDistributionGoal,
)
from .goals.rack import RackAwareGoal
from .search import ExclusionMasks, goal_aux

_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class DirectGuards:
    """Static (trace-time) description of the prior-goal constraints the
    transport plan must respect — computed from the chain prefix, one
    flag/tuple per constraint family the feasibility pass knows how to
    model."""

    rack: bool = False              # strict sibling-rack exclusion
    replica_cap: bool = False       # ReplicaCapacityGoal hard cap
    replica_band: bool = False      # per-broker replica-count band
    leader_band: bool = False       # per-broker leader-count band
    topic_band: bool = False        # per-(topic, broker) count band
    resources: tuple[int, ...] = ()      # distribution bands (upper+lower)
    cap_resources: tuple[int, ...] = ()  # hard capacity thresholds
    pot_nw_out: bool = False        # potential NW-out limit


def _guards_for(goals: tuple[Goal, ...], index: int) -> DirectGuards:
    priors = goals[:index]
    from .goals.distribution import ResourceDistributionGoal
    return DirectGuards(
        rack=any(isinstance(g, RackAwareGoal) for g in priors),
        replica_cap=any(isinstance(g, ReplicaCapacityGoal) for g in priors),
        replica_band=any(isinstance(g, CountDistributionGoal)
                         and not g.leaders for g in priors),
        leader_band=any(isinstance(g, CountDistributionGoal)
                        and g.leaders for g in priors),
        topic_band=any(isinstance(g, TopicReplicaDistributionGoal)
                       for g in priors),
        resources=tuple(sorted({int(g.resource) for g in priors
                                if isinstance(g, ResourceDistributionGoal)})),
        cap_resources=tuple(sorted({int(g.resource) for g in priors
                                    if isinstance(g, ResourceCapacityGoal)})),
        pot_nw_out=any(isinstance(g, PotentialNwOutGoal) for g in priors))


def direct_eligible(goals, index: int) -> bool:
    """True when ``goals[index]`` has a direct transport formulation AND
    every prior goal's acceptance is representable by the guard set —
    an unrecognized prior (broker sets, kafka-assigner variants, custom
    plugins) means the plan could silently violate a constraint the
    greedy's lexicographic stack would have vetoed, so the caller must
    keep the greedy path (the conservative fallback is the contract)."""
    from .goals.distribution import ResourceDistributionGoal
    goal = goals[index]
    if not getattr(goal, "supports_direct", False):
        return False
    recognized = (RackAwareGoal, ReplicaCapacityGoal, ResourceCapacityGoal,
                  CountDistributionGoal, TopicReplicaDistributionGoal,
                  PotentialNwOutGoal, ResourceDistributionGoal)
    return all(isinstance(g, recognized) for g in goals[:index])


# ---------------------------------------------------------------------------
# Deterministic randomized rounding (the sparse-plan PRNG, CCSA004)
# ---------------------------------------------------------------------------

#: Trace-time crc32-derived seed of the rounding PRNG — the repo's
#: approved deterministic-seeding idiom (lint CCSA004: no host RNG, no
#: clocks, no builtin hash()). Callers may override it with a crc32 of
#: ``solver.direct.sparse.rounding.salt`` so fleets can decorrelate
#: replays without breaking byte-determinism within one configuration.
SPARSE_ROUNDING_SEED = zlib.crc32(b"cruise-control:direct.sparse.rounding")
_SALT_SURPLUS = zlib.crc32(b"direct.sparse.plane:surplus")
_SALT_HEADROOM = zlib.crc32(b"direct.sparse.plane:headroom")


def sparse_rounding_seed(salt: str = "") -> int:
    """The rounding seed for a configured salt string
    (``solver.direct.sparse.rounding.salt``): empty → the module
    default; otherwise crc32 of the salt folded over it. Host-side,
    trace-time only — the value enters the kernels as a static."""
    if not salt:
        return SPARSE_ROUNDING_SEED
    return SPARSE_ROUNDING_SEED ^ zlib.crc32(salt.encode("utf-8"))


def _hash_uniform(idx: jax.Array, sweep, salt: int) -> jax.Array:
    """Deterministic per-index uniforms in [0, 1): a splitmix-style
    integer finalizer over (index, sweep, trace-time crc32 salt) — pure
    jnp on uint32, so the draw replays byte-identically on device with
    no host RNG in the loop (the CCSA004 contract)."""
    x = idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
    x = x + jnp.asarray(sweep, jnp.uint32) * jnp.uint32(0x85EBCA77)
    x = x + jnp.uint32(salt & 0xFFFFFFFF)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return jnp.minimum(x.astype(jnp.float32) * jnp.float32(2.0 ** -32),
                       jnp.float32(1.0 - 1e-7))


def _round_systematic(x: jax.Array, u: jax.Array) -> jax.Array:
    """Systematic (low-discrepancy) randomized rounding along the broker
    axis: ``x`` [G, B] non-negative fractional targets, ``u`` [G]
    uniforms. ``T[g, b] = ⌊cum[b] + u⌋ − ⌊cum[b−1] + u⌋ ∈ {⌊x⌋, ⌈x⌉}``
    with ``E[T] = x`` exactly and ``|Σ_b T − Σ_b x| < 1`` per group —
    expected counts match the fractional band math, and a group's
    realized total stays within one replica of it (independent
    per-cell Bernoulli draws would drift by O(√B)). Integral inputs
    pass through unchanged, so the dense regime keeps its exact
    plans."""
    c = jnp.cumsum(x, axis=1)
    y = jnp.floor(c + u[:, None])
    return jnp.diff(y, axis=1, prepend=0.0)


# ---------------------------------------------------------------------------
# Segmented helpers over a key-sorted axis
# ---------------------------------------------------------------------------

def _segment_starts(keys: jax.Array) -> jax.Array:
    """[N] bool — first element of each equal-key run (keys sorted)."""
    return jnp.concatenate([jnp.ones((1,), bool), keys[1:] != keys[:-1]])


def _segment_rank(keys: jax.Array) -> jax.Array:
    """[N] int32 — position within the element's equal-key run."""
    pos = jnp.arange(keys.shape[0], dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(_segment_starts(keys), pos, 0))
    return pos - start


def _segment_exclusive(keys: jax.Array, values: jax.Array) -> jax.Array:
    """Exclusive within-segment cumsum of NON-NEGATIVE ``values`` ([N] or
    [N, R]) over a key-sorted axis. Non-negativity makes the running
    total monotone, so each segment's base is recoverable by a cummax of
    the totals pinned at segment starts — no scatter, no scan."""
    cum_ex = jnp.cumsum(values, axis=0) - values
    starts = _segment_starts(keys)
    if values.ndim == 2:
        starts = starts[:, None]
    base = jax.lax.cummax(jnp.where(starts, cum_ex, jnp.zeros_like(cum_ex)),
                          axis=0)
    return cum_ex - base


# ---------------------------------------------------------------------------
# The sweep bodies (traced)
# ---------------------------------------------------------------------------

def _dst_load_caps(ds, lv_d, state, derived, constraint,
                   guards: DirectGuards, ds_b=None, share: float = 1.0):
    """Joint per-resource upper-band + hard-capacity caps at the
    destination, in the dst-sorted frame (``lv_d`` is each mover's load
    vector already masked to selected movers). Shared by BOTH transport
    modes so the prior-goal contract cannot drift between them.

    ``ds`` is the SEGMENT key (at ``rank_stride > 1`` a composite
    ``dst·stride + block``); ``ds_b`` the broker index it maps to, and
    ``share`` the stride: load sums cannot interleave like count ranks
    (per-mover loads are heterogeneous), so each block is budgeted
    ``1/stride`` of the destination's remaining headroom — a block's
    inflow scaled by ``stride`` must fit the full headroom. Conservative
    (joint overshoot impossible; unbalanced blocks under-use the cap and
    re-pair next sweep), exact at stride 1. Returns
    (okd [N] bool, pre_load [N, R])."""
    f32 = jnp.float32
    n = ds.shape[0]
    ds_b = ds if ds_b is None else ds_b
    okd = jnp.ones(n, bool)
    inf1 = jnp.full((1,), jnp.inf, f32)
    pre_load = _segment_exclusive(ds, lv_d)
    for r in guards.resources:
        _lo, up_r, _c = resource_limits(state, derived, constraint,
                                        Resource(r))
        up_pad = jnp.concatenate([up_r, inf1])
        dl_pad = jnp.concatenate([derived.broker_load[:, r],
                                  jnp.zeros((1,), f32)])
        okd &= dl_pad[ds_b] + (pre_load[:, r] + lv_d[:, r]) * share \
            <= up_pad[ds_b] + _EPS
    for r in guards.cap_resources:
        limit = constraint.capacity_threshold[r] * state.capacity[:, r]
        lim_pad = jnp.concatenate([limit, inf1])
        dl_pad = jnp.concatenate([derived.broker_load[:, r],
                                  jnp.zeros((1,), f32)])
        okd &= dl_pad[ds_b] + (pre_load[:, r] + lv_d[:, r]) * share \
            <= lim_pad[ds_b] + _EPS
    return okd, pre_load


def _src_load_floors(ss, lv_s, state, derived, constraint,
                     guards: DirectGuards, ss_b=None, share: float = 1.0):
    """Joint per-resource lower-band floors at the source, in the
    src-sorted frame (``lv_s`` is each mover's OUTBOUND load vector
    masked to selected movers): cumulative outflow must not take the
    source below a previously-optimized resource goal's lower band (the
    greedy's stays-in-band source arm). Shared by both transport modes.
    ``ss``/``ss_b``/``share`` follow the ``_dst_load_caps`` stride
    contract (each block budgeted ``1/stride`` of the floor
    headroom)."""
    f32 = jnp.float32
    n = ss.shape[0]
    ss_b = ss if ss_b is None else ss_b
    oks = jnp.ones(n, bool)
    ninf1 = jnp.full((1,), -jnp.inf, f32)
    pre_out = _segment_exclusive(ss, lv_s)
    for r in guards.resources:
        lo_r, _up, _c = resource_limits(state, derived, constraint,
                                        Resource(r))
        lo_pad = jnp.concatenate([lo_r, ninf1])
        sl_pad = jnp.concatenate([derived.broker_load[:, r],
                                  jnp.zeros((1,), f32)])
        oks &= sl_pad[ss_b] - (pre_out[:, r] + lv_s[:, r]) * share \
            >= lo_pad[ss_b] - _EPS
    return oks


def _surplus_deficit(cnt, lower, upper, alive, elig_dst, sweep=0,
                     margin_frac: float = 0.25,
                     seed: int = SPARSE_ROUNDING_SEED):
    """Integral (surplus, deficit, headroom) planes from FRACTIONAL
    per-cell targets resolved by deterministic randomized rounding —
    ONE plan for every density regime (round 21, retiring the
    ``MIN_TOPIC_CELL_DENSITY`` gate).

    The round-17 plan floored its band-edge margin and its donor room
    to integers — exact in the dense regime, but at a 1-count band
    (the sparse-cell regime: ~1.5 replicas per (topic, broker) cell at
    1k/100k and north-star scale) the floor collapsed the margin to
    zero, every touched cell landed exactly AT the band edge, donor
    widening drained in-band donors in broker-index order (packing
    low-index brokers), and the greedy polish inherited a layout it
    could not fix (measured residual ~10k vs greedy's 316). Here the
    shed target (``upper − margin``), the fill target
    (``lower + max(margin, 0.5)``) and the donor-widening shares all
    stay FRACTIONAL: a group-wide violation gap is spread across its
    in-band donors proportional to their fractional room (no
    broker-index packing), and systematic randomized rounding — one
    crc32-derived uniform per (group, plane, sweep), ``_hash_uniform``
    — resolves every fractional plane to integers with expectation
    EQUAL to the fractional band math and per-group totals within one
    replica of it. Re-drawing per sweep lets a rounding outcome that
    paired badly re-round after the counts update.

    Hard integral caps close the loop independent of the rounding: a
    source never sheds below ``lower`` (``⌊cnt − lower⌋``), a receiver
    never fills above ``upper`` (``⌊upper − cnt⌋``), so every rounding
    outcome stays inside the band by construction.

    Band-edge slack rationale (unchanged from round 17): a transport
    that parks every touched broker exactly AT a band edge leaves
    later goals zero joint slack and the greedy polish stalls in a
    worse local optimum than greedy-only (measured at 64/2048:
    TopicReplica residual 70 vs 0). Deficits are violation-sized only
    (``lower − cnt``); receivers additionally expose headroom up to
    the fill target, so inflow lands center-ward without O(B) in-band
    churn."""
    g_dim = cnt.shape[0]
    width = jnp.maximum(upper - lower, 0.0)
    margin = width * margin_frac
    hi_t = jnp.maximum(upper - margin, lower)   # fractional shed ceiling
    lo_t = jnp.minimum(lower + jnp.maximum(margin, 0.5), hi_t)  # fill target
    gidx = jnp.arange(g_dim, dtype=jnp.uint32)

    viol_dst = elig_dst[None, :] & (cnt < lower - _EPS)
    sur_f = jnp.where(alive[None, :] & (cnt > upper + _EPS),
                      jnp.maximum(cnt - hi_t, 0.0), 0.0)
    # Deficits are integral by construction (band edges and counts are
    # integers); the fractional mass lives in the shed targets and the
    # center-ward headroom below.
    defi = jnp.where(viol_dst, lower - cnt, 0.0)
    head_f = jnp.where(elig_dst[None, :],
                       jnp.maximum(lo_t - jnp.maximum(cnt, lower), 0.0), 0.0)

    # Proportional donor widening: when violation deficits exceed base
    # surplus, in-band donors cover the gap in proportion to their
    # fractional room (cnt down to the fill target) — spread across the
    # whole group instead of drained in broker-index order.
    need = jnp.maximum(defi.sum(axis=1, keepdims=True)
                       - sur_f.sum(axis=1, keepdims=True), 0.0)
    donor_room = jnp.where(alive[None, :],
                           jnp.maximum(jnp.minimum(cnt, hi_t) - lo_t, 0.0),
                           0.0)
    share = donor_room / jnp.maximum(donor_room.sum(axis=1, keepdims=True),
                                     _EPS)
    extra_f = jnp.minimum(need * share, donor_room)

    u_s = _hash_uniform(gidx, sweep, seed ^ _SALT_SURPLUS)
    u_h = _hash_uniform(gidx, sweep, seed ^ _SALT_HEADROOM)
    sur_cap = jnp.where(alive[None, :],
                        jnp.floor(jnp.maximum(cnt - lower, 0.0) + _EPS), 0.0)
    surplus = jnp.minimum(_round_systematic(sur_f + extra_f, u_s), sur_cap)
    room_cap = jnp.floor(jnp.maximum(upper - cnt, 0.0) + _EPS)
    defi = jnp.minimum(defi, room_cap)
    headr = jnp.where(elig_dst[None, :],
                      jnp.minimum(_round_systematic(head_f, u_h),
                                  jnp.maximum(room_cap - defi, 0.0)), 0.0)
    return surplus, defi, headr


def _leadership_sweep(state: ClusterTensors, goals: tuple[Goal, ...],
                      index: int, constraint: BalancingConstraint,
                      num_topics: int, masks: ExclusionMasks,
                      sweep: jax.Array | int = 0,
                      rank_stride: int = 1, block: jax.Array | int = 0,
                      psum=None, margin_frac: float = 0.25,
                      seed: int = SPARSE_ROUNDING_SEED,
                      ) -> tuple[ClusterTensors, jax.Array, jax.Array]:
    """Transport sweep for the LEADER-count goal via leadership
    TRANSFERS: after the replica goals have balanced counts, a leader
    replica move is almost always vetoed by the prior replica-count band
    — the reference (and the greedy here) rebalances leader counts by
    electing a different in-sync sibling instead. Each surplus leader's
    destination menu is its partition's own sibling replicas, so the
    plan picks the best sibling broker with leader-band room and caps
    joint intake per destination; replica placement (and every
    count/rack plane) is untouched, leaving only the resource-load
    guards (leadership carries ``leader_load − follower_load``)."""
    goal = goals[index]
    guards = _guards_for(goals, index)
    ps = psum if psum is not None else (lambda x: x)
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers, psum=psum)
    aux = goal_aux(goal, state, derived, constraint, num_topics, psum=psum)
    counts, lower, upper, _group, movable = goal.direct_spec(
        state, derived, constraint, aux, num_topics)

    p, s = state.assignment.shape
    b = state.num_brokers
    n = p * s
    f32 = jnp.float32
    stride = int(rank_stride)
    str_f = f32(stride)
    alive = derived.alive
    lead_elig = derived.allowed_leadership & alive
    cnt = counts.astype(f32)
    surplus, defi, headr = _surplus_deficit(
        cnt, lower, upper, alive, lead_elig, sweep=sweep,
        margin_frac=margin_frac, seed=seed)
    room = (defi + headr)[0]                                       # [B]

    # Movers: the surplus[src] lightest leaders per over-band broker.
    # Leadership leaving a broker removes (leader_load − follower_load)
    # from it — the same dst-independent source pre-filter as the
    # replica transport: a leader whose departure ALONE would cross a
    # prior resource goal's lower band can reach no sibling at all, so
    # it must not occupy a surplus rank (negative components clamped —
    # an outflow that RAISES the source's load cannot cross a floor).
    alive_pad = jnp.concatenate([alive, jnp.zeros((1,), bool)])
    src_plane = jnp.where(state.assignment >= 0, state.assignment, b)
    mv = movable & derived.movable_partition[:, None] & alive_pad[src_plane]
    if guards.resources:
        ninf1 = jnp.full((1,), -jnp.inf, f32)
        for r in guards.resources:
            lo_r, _up_r, _c = resource_limits(state, derived, constraint,
                                              Resource(r))
            own_r = jnp.maximum(state.leader_load[:, r]
                                - state.follower_load[:, r], 0.0)[:, None]
            load_pad = jnp.concatenate([derived.broker_load[:, r],
                                        jnp.zeros((1,), f32)])
            lo_pad = jnp.concatenate([lo_r, ninf1])
            mv &= load_pad[src_plane] - own_r >= lo_pad[src_plane] - _EPS
    cell = jnp.where(mv, src_plane, b).astype(jnp.int32)
    weight = replica_load_total(state)
    if stride > 1:
        blk_rows = jnp.broadcast_to(jnp.asarray(block, jnp.int32), (p,))
        blk_plane = jnp.broadcast_to(blk_rows[:, None], (p, s))
        key0 = cell * stride + blk_plane
    else:
        key0 = cell
    sc, _sk, si = jax.lax.sort(
        (key0.reshape(-1), weight.reshape(-1),
         jnp.arange(n, dtype=jnp.int32)), num_keys=2)
    rank_cell = _segment_rank(sc)
    cell_s = sc // stride if stride > 1 else sc
    blk_s = sc % stride if stride > 1 else jnp.zeros_like(sc)
    sur_pad = jnp.concatenate([surplus[0], jnp.zeros((1,), f32)])
    mover = (rank_cell * stride + blk_s).astype(f32) < sur_pad[cell_s]

    # Destination menu = the partition's own existing sibling replicas
    # on leadership-eligible brokers with band room; best room wins
    # (deficits before headroom), ties to the lowest slot.
    p_m = si // s
    s_m = si % s
    src = jnp.minimum((cell_s % (b + 1)).astype(jnp.int32), b - 1)
    assign_p = state.assignment[p_m]                               # [N, S]
    not_me = jnp.arange(s, dtype=jnp.int32)[None, :] != s_m[:, None]
    sib_b = jnp.clip(assign_p, 0, b - 1)
    room_pad = room
    lead_elig_sib = lead_elig[sib_b] & (assign_p >= 0) & not_me
    sib_room = jnp.where(lead_elig_sib, room_pad[sib_b], -1.0)
    sib_score = jnp.where(lead_elig_sib,
                          defi[0][sib_b] * 1e6 + headr[0][sib_b], -jnp.inf)
    best_slot = jnp.argmax(sib_score, axis=1).astype(jnp.int32)
    dst = sib_b[jnp.arange(n), best_slot]
    ok = mover & (jnp.take_along_axis(
        sib_room, best_slot[:, None], axis=1)[:, 0] >= 1.0)
    ok &= dst != src

    sel = ok
    pos = jnp.arange(n, dtype=jnp.int32)
    # Joint intake cap per destination + prior resource-band guards, in
    # one dst-sorted pass (leadership shifts leader_load − follower_load;
    # negative components are clamped to zero — ignoring an inflow that
    # REDUCES load only makes the check stricter).
    lead_vec = jnp.maximum(state.leader_load[p_m] - state.follower_load[p_m],
                           0.0)
    dkey = jnp.where(sel, dst, b)
    dkey_s = dkey * stride + blk_s if stride > 1 else dkey
    ds, _dp, d_i = jax.lax.sort((dkey_s, pos, pos), num_keys=2)
    ds_b = ds // stride if stride > 1 else ds
    blk_d = (ds % stride).astype(f32) if stride > 1 \
        else jnp.zeros((n,), f32)
    sel_d = sel[d_i]
    one_d = sel_d.astype(f32)
    pre_cnt = _segment_exclusive(ds, one_d)
    room_cap = jnp.concatenate([room, jnp.full((1,), jnp.inf, f32)])
    okd = pre_cnt * str_f + blk_d + 1.0 <= room_cap[ds_b] + _EPS
    if guards.resources or guards.cap_resources:
        okd_load, _pre = _dst_load_caps(ds, lead_vec[d_i] * sel_d[:, None],
                                        state, derived, constraint, guards,
                                        ds_b=ds_b, share=str_f)
        okd &= okd_load
    sel &= jnp.zeros(n, bool).at[d_i].set(okd)

    # Joint source-side floors (the greedy's stays-in-band src arm):
    # several leaderships leaving ONE broker in the same sweep must not
    # jointly take its load below a prior resource goal's lower band —
    # the per-mover pre-filter above only bounds a single departure.
    if guards.resources:
        skey = jnp.where(sel, src, b)
        skey_s = skey * stride + blk_s if stride > 1 else skey
        ss, _sp, s_i = jax.lax.sort((skey_s, pos, pos), num_keys=2)
        ss_b = ss // stride if stride > 1 else ss
        sel_s = sel[s_i]
        oks = _src_load_floors(ss, lead_vec[s_i] * sel_s[:, None],
                               state, derived, constraint, guards,
                               ss_b=ss_b, share=str_f)
        sel &= jnp.zeros(n, bool).at[s_i].set(oks)

    rows = jnp.where(sel, p_m, p)
    new_leader = state.leader_slot.at[rows].set(
        best_slot.astype(state.leader_slot.dtype), mode="drop")
    return (dataclasses.replace(state, leader_slot=new_leader),
            ps(sel.sum().astype(jnp.int32)),
            ps(mover.sum().astype(jnp.int32)))

def _direct_sweep(state: ClusterTensors, goals: tuple[Goal, ...], index: int,
                  constraint: BalancingConstraint, num_topics: int,
                  masks: ExclusionMasks, sweep: jax.Array | int = 0,
                  rank_stride: int = 1, block: jax.Array | int = 0,
                  psum=None, margin_frac: float = 0.25,
                  seed: int = SPARSE_ROUNDING_SEED,
                  ) -> tuple[ClusterTensors, jax.Array, jax.Array]:
    """One transport sweep for ``goals[index]``: plan the full
    surplus→deficit matching on the current counts, veto infeasible
    assignments, apply the rest in one scatter. ``sweep`` (traced)
    cyclically rotates each group's rank→profile mapping so a pairing
    vetoed by feasibility (sibling/rack collisions) is re-paired with a
    DIFFERENT destination on the next sweep even when the counts did not
    change — without it a fully-vetoed plan is a fixed point and the
    residue never re-pairs.

    ``(rank_stride, block)`` select the SPMD rank layout (module
    docstring): on the mesh each device passes its shard index and the
    shard count, and ``psum`` (the mesh collective) makes the count
    planes and the returned scalars global. The same kernel evaluated
    single-device with ``block = partition_row // shard_rows`` is the
    mesh path's byte-parity reference. Returns
    (new_state, applied, planned)."""
    goal = goals[index]
    guards = _guards_for(goals, index)
    ps = psum if psum is not None else (lambda x: x)
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers, psum=psum)
    aux = goal_aux(goal, state, derived, constraint, num_topics, psum=psum)
    counts, lower, upper, group, movable = goal.direct_spec(
        state, derived, constraint, aux, num_topics)

    p, s = state.assignment.shape
    b = state.num_brokers
    g_dim = counts.shape[0]
    n = p * s
    f32 = jnp.float32
    stride = int(rank_stride)

    alive = derived.alive
    elig_dst = derived.replica_dest_ok
    cnt = counts.astype(f32)

    # --- target distribution: integral surplus / deficit / headroom ------
    surplus, defi, headr = _surplus_deficit(
        cnt, lower, upper, alive, elig_dst, sweep=sweep,
        margin_frac=margin_frac, seed=seed)                         # [G, B]

    # --- mover selection: segmented sort by (cell, weight) ---------------
    alive_pad = jnp.concatenate([alive, jnp.zeros((1,), bool)])
    src_plane = jnp.where(state.assignment >= 0, state.assignment, b)
    mv = movable & derived.movable_partition[:, None] & alive_pad[src_plane]
    # Destination-INDEPENDENT source feasibility must be filtered out
    # BEFORE ranking: a replica whose departure alone would cross a
    # prior resource goal's lower band can reach no destination at all,
    # so letting it occupy a surplus rank wedges that rank forever (the
    # destination rotation can only re-pair, never re-select movers) —
    # measured at 64/2048: leader replicas of near-lower-band brokers
    # froze ~50 surplus ranks the greedy clears with other replicas.
    ninf1 = jnp.full((1,), -jnp.inf, f32)
    if guards.resources:
        lead_plane = is_leader_slot(state)
        for r in guards.resources:
            lo_r, _up_r, _c = resource_limits(state, derived, constraint,
                                              Resource(r))
            own_r = jnp.where(lead_plane, state.leader_load[:, r][:, None],
                              state.follower_load[:, r][:, None])
            load_pad = jnp.concatenate([derived.broker_load[:, r],
                                        jnp.zeros((1,), f32)])
            lo_pad = jnp.concatenate([lo_r, ninf1])
            mv &= load_pad[src_plane] - own_r >= lo_pad[src_plane] - _EPS
    if guards.replica_band:
        rl, _ru = count_limits(derived.avg_replicas,
                               constraint.replica_balance_threshold)
        reps_pad = jnp.concatenate([derived.broker_replicas.astype(f32),
                                    jnp.zeros((1,), f32)])
        rlo_pad = jnp.concatenate([jnp.broadcast_to(rl, (b,)), ninf1])
        mv &= reps_pad[src_plane] - 1.0 >= rlo_pad[src_plane] - _EPS
    if guards.leader_band:
        lead_plane = is_leader_slot(state)
        ll, _lu = count_limits(derived.avg_leaders,
                               constraint.leader_replica_balance_threshold)
        leads_pad = jnp.concatenate([derived.broker_leaders.astype(f32),
                                     jnp.zeros((1,), f32)])
        llo_pad = jnp.concatenate([jnp.broadcast_to(ll, (b,)), ninf1])
        mv &= (~lead_plane) \
            | (leads_pad[src_plane] - 1.0 >= llo_pad[src_plane] - _EPS)
    cell = jnp.where(mv, group * (b + 1) + src_plane,
                     g_dim * (b + 1)).astype(jnp.int32)
    weight = replica_load_total(state)
    if stride > 1:
        # Sort by (cell, block, weight): each block's rows keep their
        # local light-first order, and a device owning ONE block sees
        # the exact order of its local (cell, weight) sort — the SPMD
        # equivalence that makes single-device emulation byte-exact.
        blk_rows = jnp.broadcast_to(jnp.asarray(block, jnp.int32), (p,))
        blk_plane = jnp.broadcast_to(blk_rows[:, None], (p, s))
        key0 = cell * stride + blk_plane
    else:
        key0 = cell
    sc, _sk, si = jax.lax.sort(
        (key0.reshape(-1), weight.reshape(-1),
         jnp.arange(n, dtype=jnp.int32)), num_keys=2)
    rank_cell = _segment_rank(sc)              # within (cell, block)
    cell_s = sc // stride if stride > 1 else sc
    blk_s = sc % stride if stride > 1 else jnp.zeros_like(sc)
    sur_pad = jnp.concatenate([surplus, jnp.zeros((g_dim, 1), f32)],
                              axis=1).reshape(-1)
    sur_pad = jnp.concatenate([sur_pad, jnp.zeros((1,), f32)])
    # Interleaved global within-cell rank: local rank · stride + block.
    mover = (rank_cell * stride + blk_s).astype(f32) < sur_pad[cell_s]

    # --- cumsum rank-assignment over the [deficit | headroom] profile ----
    grp_key = cell_s // (b + 1)                 # sorted; sentinel = g_dim
    grp = jnp.minimum(grp_key, g_dim - 1)
    if stride > 1:
        # Within-(group, block) mover ordinal, interleaved to a globally
        # unique fill position (ordinal · stride + block) — computed in a
        # second sorted frame because (group, block) runs are not
        # contiguous in the (cell, block)-major frame.
        pos0 = jnp.arange(n, dtype=jnp.int32)
        gb_key = jnp.where(grp_key < g_dim, grp_key * stride + blk_s,
                           g_dim * stride).astype(jnp.int32)
        gs, _gp, g_i = jax.lax.sort((gb_key, pos0, pos0), num_keys=2)
        r_local = _segment_exclusive(gs, mover[g_i].astype(jnp.int32))
        rank_grp = jnp.zeros((n,), jnp.int32).at[g_i].set(
            r_local * stride + gs % stride)
    else:
        rank_grp = _segment_exclusive(grp_key, mover.astype(jnp.int32))
    # Per-sweep cyclic rotation within each group's position space: a
    # bijection on [0, total), so position uniqueness (and therefore every
    # cell's integer intake bound) is preserved; out-of-range ranks stay
    # put and keep their profile-overflow invalidity.
    tot_pos = (defi + headr).sum(axis=1)                           # [G]
    t_g = tot_pos[grp]
    rank_f = rank_grp.astype(f32)
    # Golden-ratio stride: consecutive profile positions usually belong
    # to the SAME broker (a deficit of d occupies d adjacent positions),
    # so a +1 rotation retries the same vetoed destination; a
    # ~0.618·total jump lands on a different broker almost every sweep.
    offs = jnp.floor(jnp.asarray(sweep, f32) * 0.6180339887 * t_g)
    rank_f = jnp.where(rank_f < t_g,
                       jnp.mod(rank_f + offs, jnp.maximum(t_g, 1.0)),
                       rank_f)
    dst, ok = deficit_fill_dests(grp, rank_f, defi, headr, elig_dst)
    ok &= mover

    # --- structural feasibility ------------------------------------------
    p_m = si // s
    s_m = si % s
    src = (cell_s % (b + 1)).astype(jnp.int32)
    ok &= dst != jnp.minimum(src, b - 1)
    assign_p = state.assignment[p_m]                           # [N, S]
    ok &= ~(assign_p == dst[:, None]).any(axis=1)
    is_lead = state.leader_slot[p_m] == s_m
    ok &= (~is_lead) | derived.allowed_leadership[dst]
    not_me = jnp.arange(s, dtype=jnp.int32)[None, :] != s_m[:, None]
    if guards.rack:
        rack_pad = jnp.concatenate([state.rack, state.rack[:1]])
        slot_racks = jnp.where(assign_p >= 0,
                               rack_pad[jnp.clip(assign_p, 0, b - 1)], -1)
        dst_rack = state.rack[dst]
        ok &= ~((slot_racks == dst_rack[:, None]) & not_me
                & (assign_p >= 0)).any(axis=1)

    # --- same-sweep sibling dedup via planned-destination planes ---------
    # ``si`` is a permutation of the replica axis, so one scatter writes
    # every slot exactly once; a mover is vetoed when an EARLIER (lower
    # sorted position) sibling of its partition claims the same broker —
    # or, under the rack guard, the same rack.
    pos = jnp.arange(n, dtype=jnp.int32)
    sel0 = mover & ok
    planned_dst = jnp.zeros((p, s), jnp.int32).at[p_m, s_m].set(
        jnp.where(sel0, dst, -1))
    planned_pri = jnp.zeros((p, s), jnp.int32).at[p_m, s_m].set(
        jnp.where(sel0, pos, n))
    others_dst = planned_dst[p_m]                              # [N, S]
    others_pri = planned_pri[p_m]
    earlier = not_me & (others_pri < pos[:, None])
    ok &= ~((others_dst == dst[:, None]) & earlier).any(axis=1)
    if guards.rack:
        others_rack = jnp.where(others_dst >= 0,
                                rack_pad[jnp.clip(others_dst, 0, b - 1)], -2)
        ok &= ~((others_rack == dst_rack[:, None]) & earlier).any(axis=1)

    sel = mover & ok
    # Per-mover load vector: a moving leader carries its leader load
    # (leadership travels with the slot), a follower its follower load.
    load_vec = jnp.where(is_lead[:, None], state.leader_load[p_m],
                         state.follower_load[p_m])              # [N, R]

    # --- prior-goal guards: dst-sorted joint caps ------------------------
    # At rank_stride > 1 the frame segments on (dst, block): COUNT caps
    # interleave (a block's k-th intake claims global position
    # k·stride + block, unique per destination, so the joint bound holds
    # across blocks with no collective); LOAD caps budget each block
    # 1/stride of the headroom (_dst_load_caps). stride == 1 reduces to
    # the exact round-17 formulas.
    str_f = f32(stride)
    dst_caps = (guards.replica_cap or guards.replica_band
                or guards.leader_band or guards.resources
                or guards.cap_resources or guards.pot_nw_out)
    if dst_caps:
        dkey = jnp.where(sel, dst, b)
        dkey_s = dkey * stride + blk_s if stride > 1 else dkey
        ds, _dp, d_i = jax.lax.sort((dkey_s, pos, pos), num_keys=2)
        ds_b = ds // stride if stride > 1 else ds
        blk_d = (ds % stride).astype(f32) if stride > 1 \
            else jnp.zeros((n,), f32)
        sel_d = sel[d_i]
        one_d = sel_d.astype(f32)
        okd = jnp.ones(n, bool)
        inf1 = jnp.full((1,), jnp.inf, f32)
        if guards.replica_cap or guards.replica_band:
            reps = derived.broker_replicas.astype(f32)
            cap_b = jnp.full((b,), jnp.inf, f32)
            if guards.replica_band:
                _rl, ru = count_limits(derived.avg_replicas,
                                       constraint.replica_balance_threshold)
                cap_b = jnp.minimum(cap_b, ru - reps)
            if guards.replica_cap:
                cap_b = jnp.minimum(
                    cap_b, constraint.max_replicas_per_broker - reps)
            pre_cnt = _segment_exclusive(ds, one_d)
            okd &= pre_cnt * str_f + blk_d + 1.0 \
                <= jnp.concatenate([cap_b, inf1])[ds_b] + _EPS
        if guards.leader_band:
            lead_d = (is_lead[d_i] & sel_d).astype(f32)
            _ll, lu = count_limits(derived.avg_leaders,
                                   constraint.leader_replica_balance_threshold)
            lcap = jnp.concatenate(
                [lu - derived.broker_leaders.astype(f32), inf1])
            pre_lead = _segment_exclusive(ds, lead_d)
            okd &= (lead_d == 0) \
                | (pre_lead * str_f + blk_d + 1.0 <= lcap[ds_b] + _EPS)
        if guards.resources or guards.cap_resources:
            okd_load, _pre = _dst_load_caps(ds, load_vec[d_i] * sel_d[:, None],
                                            state, derived, constraint,
                                            guards, ds_b=ds_b, share=str_f)
            okd &= okd_load
        if guards.pot_nw_out:
            r = int(Resource.NW_OUT)
            pot_own = state.leader_load[p_m, r][d_i] * one_d
            pre_pot = _segment_exclusive(ds, pot_own)
            limit = constraint.capacity_threshold[r] * state.capacity[:, r]
            lim_pad = jnp.concatenate([limit, inf1])
            pt_pad = jnp.concatenate([derived.pot_nw_out,
                                      jnp.zeros((1,), f32)])
            # The reference's escape hatch (PotentialNwOutGoal
            # .actionAcceptance): a move whose SOURCE already violates
            # its potential limit is tolerated — without it, a cluster
            # whose potential exceeds limits everywhere (the goal
            # violated at entry, e.g. the 1k/100k fixture at 140k
            # residual) vetoes EVERY transport move forever.
            src_pot = jnp.concatenate([derived.pot_nw_out,
                                       jnp.zeros((1,), f32)])
            src_lim = jnp.concatenate([limit, inf1])
            src_d = jnp.minimum(src[d_i], b)
            src_viol = src_pot[src_d] > src_lim[src_d] + _EPS
            okd &= (pt_pad[ds_b] + (pre_pot + pot_own) * str_f
                    <= lim_pad[ds_b] + _EPS) | src_viol
        sel &= jnp.zeros(n, bool).at[d_i].set(okd)

    # --- prior-goal guards: src-sorted joint floors ----------------------
    # Mirror of the dst caps: COUNT floors interleave outflow positions
    # (k-th departure from block d holds global position k·stride + d),
    # LOAD floors budget each block 1/stride of the slack above the band.
    src_floors = (guards.replica_band or guards.leader_band
                  or guards.resources)
    if src_floors:
        skey = jnp.where(sel, src, b)
        skey_s = skey * stride + blk_s if stride > 1 else skey
        ss, _sp, s_i = jax.lax.sort((skey_s, pos, pos), num_keys=2)
        ss_b = ss // stride if stride > 1 else ss
        blk_o = (ss % stride).astype(f32) if stride > 1 \
            else jnp.zeros((n,), f32)
        sel_s = sel[s_i]
        one_s = sel_s.astype(f32)
        oks = jnp.ones(n, bool)
        ninf1 = jnp.full((1,), -jnp.inf, f32)
        out_rank = _segment_exclusive(ss, one_s)
        if guards.replica_band:
            rl, _ru = count_limits(derived.avg_replicas,
                                   constraint.replica_balance_threshold)
            reps_pad = jnp.concatenate(
                [derived.broker_replicas.astype(f32),
                 jnp.zeros((1,), f32)])
            floor_pad = jnp.concatenate([jnp.broadcast_to(rl, (b,)), ninf1])
            oks &= reps_pad[ss_b] - (out_rank * str_f + blk_o) - 1.0 \
                >= floor_pad[ss_b] - _EPS
        if guards.leader_band:
            lead_s = (is_lead[s_i] & sel_s).astype(f32)
            ll, _lu = count_limits(derived.avg_leaders,
                                   constraint.leader_replica_balance_threshold)
            leads_pad = jnp.concatenate(
                [derived.broker_leaders.astype(f32), jnp.zeros((1,), f32)])
            lfloor = jnp.concatenate([jnp.broadcast_to(ll, (b,)), ninf1])
            pre_lead_out = _segment_exclusive(ss, lead_s)
            oks &= (lead_s == 0) \
                | (leads_pad[ss_b] - (pre_lead_out * str_f + blk_o) - 1.0
                   >= lfloor[ss_b] - _EPS)
        if guards.resources:
            oks &= _src_load_floors(ss, load_vec[s_i] * sel_s[:, None],
                                    state, derived, constraint, guards,
                                    ss_b=ss_b, share=str_f)
        sel &= jnp.zeros(n, bool).at[s_i].set(oks)

    # --- per-(topic, broker) band of a PRIOR topic goal ------------------
    if guards.topic_band and not isinstance(goal,
                                            TopicReplicaDistributionGoal):
        tb = ps(topic_broker_replica_counts(state, num_topics)).astype(f32)
        n_alive = jnp.maximum(alive.sum(), 1)
        t_avg = (tb * alive[None, :]).sum(axis=1) / n_alive
        t_up = jnp.ceil(t_avg * constraint.topic_replica_balance_threshold)
        t_lo = jnp.floor(t_avg / constraint.topic_replica_balance_threshold)
        topic_m = state.topic[p_m]
        # dst side: joint intake per (topic, dst) cell must stay under the
        # prior topic band's upper (interleaved positions at stride > 1).
        tdkey = jnp.where(sel, topic_m * (b + 1) + dst,
                          num_topics * (b + 1)).astype(jnp.int32)
        tdkey_s = tdkey * stride + blk_s if stride > 1 else tdkey
        ts, _tp, t_i = jax.lax.sort((tdkey_s, pos, pos), num_keys=2)
        ts_b = ts // stride if stride > 1 else ts
        blk_t = (ts % stride).astype(f32) if stride > 1 \
            else jnp.zeros((n,), f32)
        sel_t = sel[t_i].astype(f32)
        pre_td = _segment_exclusive(ts, sel_t)
        tb_pad = jnp.concatenate([tb, jnp.zeros((num_topics, 1), f32)],
                                 axis=1).reshape(-1)
        tb_pad = jnp.concatenate([tb_pad, jnp.zeros((1,), f32)])
        up_flat = jnp.concatenate(
            [jnp.broadcast_to(t_up[:, None], (num_topics, b + 1)).reshape(-1),
             jnp.full((1,), jnp.inf, f32)])
        okt = (sel_t == 0) \
            | (tb_pad[ts_b] + pre_td * str_f + blk_t + 1.0
               <= up_flat[ts_b] + _EPS)
        sel &= jnp.zeros(n, bool).at[t_i].set(okt)
        # src side: joint outflow per (topic, src) must stay at/above the
        # prior topic band's lower.
        tskey = jnp.where(sel, topic_m * (b + 1) + src,
                          num_topics * (b + 1)).astype(jnp.int32)
        tskey_s = tskey * stride + blk_s if stride > 1 else tskey
        ts2, _tp2, t2_i = jax.lax.sort((tskey_s, pos, pos), num_keys=2)
        ts2_b = ts2 // stride if stride > 1 else ts2
        blk_t2 = (ts2 % stride).astype(f32) if stride > 1 \
            else jnp.zeros((n,), f32)
        sel_t2 = sel[t2_i].astype(f32)
        pre_ts = _segment_exclusive(ts2, sel_t2)
        lo_flat = jnp.concatenate(
            [jnp.broadcast_to(t_lo[:, None], (num_topics, b + 1)).reshape(-1),
             jnp.full((1,), -jnp.inf, f32)])
        okt2 = (sel_t2 == 0) \
            | (tb_pad[ts2_b] - (pre_ts * str_f + blk_t2) - 1.0
               >= lo_flat[ts2_b] - _EPS)
        sel &= jnp.zeros(n, bool).at[t2_i].set(okt2)

    # --- one-shot scatter apply ------------------------------------------
    rows = jnp.where(sel, p_m, p)
    new_assignment = state.assignment.at[rows, s_m].set(
        dst.astype(state.assignment.dtype), mode="drop")
    return (dataclasses.replace(state, assignment=new_assignment),
            ps(sel.sum().astype(jnp.int32)),
            ps(mover.sum().astype(jnp.int32)))


def _sweep_fn(goals: tuple[Goal, ...], index: int):
    """Leader-count goals transport LEADERSHIP (sibling re-election);
    every other count goal transports replicas. Trace-time dispatch."""
    g = goals[index]
    if isinstance(g, CountDistributionGoal) and g.leaders:
        return _leadership_sweep
    return _direct_sweep


def _stall_limit(goals: tuple[Goal, ...], index: int) -> int:
    """Consecutive zero-apply sweeps tolerated before the loop gives the
    residue up to the greedy polish. The replica transports re-pair
    vetoed movers by rotation, so a zero-apply sweep can still unlock
    the next one — give rotation a few chances; the leadership sweep
    has no rotation (its destination menu is the partition's own
    siblings), so a zero-apply sweep would recompute a byte-identical
    plan forever — exit on the first."""
    return 1 if _sweep_fn(goals, index) is _leadership_sweep else 3


def _direct_rounds_driver(state: ClusterTensors, goals: tuple[Goal, ...],
                          index: int, constraint: BalancingConstraint,
                          num_topics: int, masks: ExclusionMasks,
                          max_sweeps: int, rank_stride: int = 1,
                          block: jax.Array | int = 0, psum=None,
                          margin_frac: float = 0.25,
                          seed: int = SPARSE_ROUNDING_SEED):
    """Sweep loop (traced): unlike the greedy megastep's zero-APPLY exit,
    the direct loop keeps sweeping while the plan still has MOVERS —
    a sweep whose every pairing was feasibility-vetoed applies nothing,
    but the next sweep's rotation can re-pair the residue. A bounded
    zero-apply STREAK (``_stall_limit``) still ends a stalled loop: a
    structurally-stuck residue must fall to the greedy polish, not burn
    the whole ``max_sweeps`` budget recomputing vetoed plans.

    A second streak watches PROGRESS: because the fractional plan keeps
    a headroom/widening tail alive until every deficit is filled, a
    wedged residue can apply a tiny trickle of moves each sweep without
    ever shrinking the plan — the zero-apply streak never fires and the
    loop burns the whole budget on a plateau (measured at 200b/10k/40t:
    all three count goals ran 13-16 of 16 sweeps for moves the polish
    replays in 2-4 rounds). A sweep must shrink ``planned`` by at least
    an EIGHTH below the best seen so far to reset the streak;
    ``_stall_limit`` consecutive non-improving sweeps end the loop. The
    geometric bar (not strict decrease) matters twice over: the
    per-sweep rounding re-draw wobbles the plan by ±1 per group per
    plane, so a plateau still "improves" by one count every few sweeps,
    and a sweep costs roughly 1.3 greedy polish rounds — progress in
    single counts per sweep is cheaper replayed by the polish, which
    the caller already sizes from the stranded residue.

    ``(rank_stride, block, psum)`` thread the SPMD layout (module
    docstring) so the mesh path can run THIS loop per shard — the
    returned scalars are already psum'd global, so the while predicate
    agrees across devices by construction."""
    if not direct_eligible(goals, index):   # trace-time guard
        raise ValueError(
            f"goal {goals[index].name} / chain prefix not direct-eligible "
            "(see direct_eligible)")
    sweep_fn = _sweep_fn(goals, index)
    stall = _stall_limit(goals, index)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)

    def cond(c):
        _st, _tot, i, planned, zeros, _best, noprog = c
        return ((planned > 0) & (i < max_sweeps) & (zeros < stall)
                & (noprog < stall))

    def body(c):
        st, tot, i, _planned, zeros, best, noprog = c
        ns, applied, planned = sweep_fn(st, goals, index, constraint,
                                        num_topics, masks, sweep=i,
                                        rank_stride=rank_stride, block=block,
                                        psum=psum, margin_frac=margin_frac,
                                        seed=seed)
        zeros = jnp.where(applied > 0, jnp.int32(0), zeros + 1)
        improved = planned < best - best // 8
        noprog = jnp.where(improved, jnp.int32(0), noprog + 1)
        return (ns, tot + applied, i + 1, planned, zeros,
                jnp.minimum(best, planned), noprog)

    final, total, sweeps, planned, _z, _b, _np = jax.lax.while_loop(
        cond, body,
        (state, jnp.int32(0), jnp.int32(0), jnp.int32(1), jnp.int32(0),
         big, jnp.int32(0)))
    # ``planned`` at exit = movers the plan still wanted but could not
    # place (0 when the transport fully converged): the caller's honest
    # residue signal for sizing the greedy polish.
    return final, total, sweeps, planned


@partial(jax.jit, static_argnames=("goals", "index", "constraint",
                                   "num_topics", "max_sweeps",
                                   "margin_frac", "seed"))
def direct_transport_rounds(state: ClusterTensors, goals: tuple[Goal, ...],
                            index: int, constraint: BalancingConstraint,
                            num_topics: int, masks: ExclusionMasks,
                            max_sweeps: int = 8, margin_frac: float = 0.25,
                            seed: int = SPARSE_ROUNDING_SEED):
    """The direct-assignment solve for ``goals[index]`` under the guards
    of ``goals[:index]``: up to ``max_sweeps`` transport sweeps inside
    ONE ``lax.while_loop`` dispatch (a stalled loop ends on device).
    Returns (final_state, moves_applied, sweeps_run, movers_stranded)."""
    return _direct_rounds_driver(state, goals, index, constraint,
                                 num_topics, masks, max_sweeps,
                                 margin_frac=margin_frac, seed=seed)


@partial(jax.jit, static_argnames=("goals", "index", "constraint",
                                   "num_topics", "max_sweeps",
                                   "margin_frac", "seed"),
         donate_argnums=(0, 1))
def direct_transport_rounds_donated(assignment: jax.Array,
                                    leader_slot: jax.Array,
                                    rest: ClusterTensors,
                                    goals: tuple[Goal, ...], index: int,
                                    constraint: BalancingConstraint,
                                    num_topics: int, masks: ExclusionMasks,
                                    max_sweeps: int = 8,
                                    margin_frac: float = 0.25,
                                    seed: int = SPARSE_ROUNDING_SEED):
    """Donated twin (identical trace): callers pass
    ``chain.strip_mutable(state)`` as ``rest`` and relinquish the two
    mutable tensors — the donation set is exactly the strip_mutable pair,
    nothing else (CCSA002)."""
    state = dataclasses.replace(rest, assignment=assignment,
                                leader_slot=leader_slot)
    final, total, sweeps, planned = _direct_rounds_driver(
        state, goals, index, constraint, num_topics, masks, max_sweeps,
        margin_frac=margin_frac, seed=seed)
    return final.assignment, final.leader_slot, total, sweeps, planned


# ---------------------------------------------------------------------------
# Megabatch twins: whole buckets of clusters, one direct program
# ---------------------------------------------------------------------------

def _megabatch_direct_driver(states: ClusterTensors, active0: jax.Array,
                             goals: tuple[Goal, ...], index: int,
                             constraint: BalancingConstraint,
                             num_topics: int, masks: ExclusionMasks,
                             max_sweeps: int, margin_frac: float = 0.25,
                             seed: int = SPARSE_ROUNDING_SEED):
    """Batched sweep loop with the megabatch freeze discipline: an
    inactive cluster's whole state is frozen by a select, so a pad slot
    (or a cluster whose plan converged) stays byte-identical while its
    batchmates keep sweeping — one compiled program per bucket shape
    serves any occupancy (occupancy is traced, never a new compile)."""
    if not direct_eligible(goals, index):   # trace-time guard
        raise ValueError(
            f"goal {goals[index].name} / chain prefix not direct-eligible "
            "(see direct_eligible)")
    c = states.assignment.shape[0]
    fields = (masks.excluded_topics, masks.excluded_replica_move_brokers,
              masks.excluded_leadership_brokers)
    ax = tuple(None if f is None else 0 for f in fields)

    sweep_fn = _sweep_fn(goals, index)
    stall = _stall_limit(goals, index)

    def per_cluster(st, tm, rm, lm, i):
        return sweep_fn(st, goals, index, constraint, num_topics,
                        ExclusionMasks(tm, rm, lm), sweep=i,
                        margin_frac=margin_frac, seed=seed)

    vsweep = jax.vmap(per_cluster, in_axes=(0,) + ax + (None,))

    def cond(carry):
        _st, _tot, _swp, i, active, _z = carry
        return active.any() & (i < max_sweeps)

    def body(carry):
        st, tot, swp, i, active, zeros = carry
        nst, applied, planned = vsweep(st, *fields, i)

        def keep(new, old):
            k = active.reshape((c,) + (1,) * (new.ndim - 1))
            return jnp.where(k, new, old)

        st = jax.tree.map(keep, nst, st)
        applied = jnp.where(active, applied, 0).astype(jnp.int32)
        zeros = jnp.where(active & (applied == 0), zeros + 1,
                          jnp.where(active, 0, zeros))
        return (st, tot + applied, swp + active.astype(jnp.int32), i + 1,
                active & (planned > 0) & (zeros < stall), zeros)

    final, total, sweeps, _i, active, _z = jax.lax.while_loop(
        cond, body,
        (states, jnp.zeros((c,), jnp.int32), jnp.zeros((c,), jnp.int32),
         jnp.int32(0), active0, jnp.zeros((c,), jnp.int32)))
    return final, total, sweeps, active


@partial(jax.jit, static_argnames=("goals", "index", "constraint",
                                   "num_topics", "max_sweeps",
                                   "margin_frac", "seed"))
def megabatch_direct_rounds(states: ClusterTensors, active0: jax.Array,
                            goals: tuple[Goal, ...], index: int,
                            constraint: BalancingConstraint,
                            num_topics: int, masks: ExclusionMasks,
                            max_sweeps: int = 8, margin_frac: float = 0.25,
                            seed: int = SPARSE_ROUNDING_SEED):
    """Batched direct solve over a leading cluster axis. Returns
    (states, moves[C], sweeps[C], active_out[C])."""
    return _megabatch_direct_driver(states, active0, goals, index,
                                    constraint, num_topics, masks,
                                    max_sweeps, margin_frac=margin_frac,
                                    seed=seed)


@partial(jax.jit, static_argnames=("goals", "index", "constraint",
                                   "num_topics", "max_sweeps",
                                   "margin_frac", "seed"),
         donate_argnums=(0, 1))
def megabatch_direct_rounds_donated(assignment: jax.Array,
                                    leader_slot: jax.Array,
                                    rest: ClusterTensors, active0: jax.Array,
                                    goals: tuple[Goal, ...], index: int,
                                    constraint: BalancingConstraint,
                                    num_topics: int, masks: ExclusionMasks,
                                    max_sweeps: int = 8,
                                    margin_frac: float = 0.25,
                                    seed: int = SPARSE_ROUNDING_SEED):
    """Donated batched twin: donation set is exactly the strip_mutable
    pair grown a cluster axis ``{assignment[C,P,S], leader_slot[C,P]}``
    (CCSA002); the stacked topology planes in ``rest`` are
    refresh-cache-shared and never donated."""
    states = dataclasses.replace(rest, assignment=assignment,
                                 leader_slot=leader_slot)
    final, total, sweeps, active = _megabatch_direct_driver(
        states, active0, goals, index, constraint, num_topics, masks,
        max_sweeps, margin_frac=margin_frac, seed=seed)
    return final.assignment, final.leader_slot, total, sweeps, active


# ---------------------------------------------------------------------------
# Host-side pass driver
# ---------------------------------------------------------------------------

def run_direct_pass(state: ClusterTensors, goals, index: int,
                    constraint: BalancingConstraint, num_topics: int,
                    masks: ExclusionMasks, megastep, max_sweeps: int,
                    stats=None, flight=None, donate_input: bool = False):
    """Fire the direct solve as ONE device dispatch and read its scalars
    back synchronously (there is nothing to pipeline behind a single
    dispatch). Donation follows the megastep discipline: the first
    mutating dispatch either consumes the caller's buffers
    (``donate_input``) or donates a device COPY of the two mutable
    tensors; the flight record and dispatch stats land under
    ``kind="direct"`` so solver_dispatches{kind="direct"} is its own
    series and the acceptance-density histogram (defined only for greedy
    move dispatches on a recorded grid) never sees these.

    Returns (state, moves, sweeps, donated, stranded) — ``stranded`` is
    the mover count the plan still wanted but could not place at exit
    (the caller's residue signal for sizing the greedy polish)."""
    import time as _time

    from ..utils.sensors import SENSORS
    from .chain import donation_enabled, strip_mutable
    goals = tuple(goals)
    donate = donation_enabled(megastep)
    margin_frac = float(getattr(megastep, "direct_sparse_margin", 0.25))
    seed = sparse_rounding_seed(getattr(megastep, "direct_sparse_salt", ""))
    # ccsa: ok[CCSA004] flight-telemetry stamp on the host driver — the
    # value never feeds the plan or the rounding seed
    t0 = _time.monotonic()
    if donate:
        if not donate_input:
            state = dataclasses.replace(
                state, assignment=jnp.copy(state.assignment),
                leader_slot=jnp.copy(state.leader_slot))
        a, l, total, sweeps, planned = direct_transport_rounds_donated(
            state.assignment, state.leader_slot, strip_mutable(state),
            goals, index, constraint, num_topics, masks, max_sweeps,
            margin_frac=margin_frac, seed=seed)
        state = dataclasses.replace(state, assignment=a, leader_slot=l)
    else:
        state, total, sweeps, planned = direct_transport_rounds(
            state, goals, index, constraint, num_topics, masks, max_sweeps,
            margin_frac=margin_frac, seed=seed)
    moves = int(total)
    sweeps_run = int(sweeps)
    stranded = int(planned)
    # ccsa: ok[CCSA004] flight-telemetry stamp on the host driver — the
    # value never feeds the plan or the rounding seed
    elapsed = _time.monotonic() - t0
    if stats is not None:
        stats.record("direct", sweeps_run, donated=donate)
    if flight is not None:
        flight.dispatch("direct", max_sweeps, sweeps_run, moves,
                        donated=donate, elapsed_s=elapsed)
    SENSORS.count("solver_direct_sweeps", sweeps_run)
    SENSORS.count("solver_direct_moves", moves)
    SENSORS.count("solver_direct_stranded", stranded)
    return state, moves, sweeps_run, donate, stranded
