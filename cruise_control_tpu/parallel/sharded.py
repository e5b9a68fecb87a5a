"""Multi-chip sharded rebalance search.

SPMD version of ``analyzer.search.optimize_round`` over a 1-D device mesh:

- the partition-indexed tensors (``assignment``, ``leader_slot``, loads,
  ``topic``, ``partition_mask``) are sharded along the mesh axis ``"p"``;
- broker-indexed tensors (capacity, rack, states) are replicated;
- per-broker aggregates (loads, replica/leader counts) are computed as local
  partial segment-sums and combined with ``psum`` — collectives ride ICI;
- every device generates candidates from ITS partitions, scores them against
  the global aggregates, and the small reduced candidate set is
  ``all_gather``-ed so all devices agree on one conflict-free batch;
- each device applies the agreed moves that land in its partition shard.

The scoring body is the SAME code as the single-device round
(search.score_round_candidates / apply_selected) with the psum hook and a
per-shard row offset plugged in — one source of truth for goal semantics.

This replaces the reference's precompute thread pool + shared mutable
ClusterModel (GoalOptimizer.java:112-119, SURVEY.md §2.11) with pure SPMD:
no locks, the "shared state" is the replicated per-broker aggregate.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analyzer.candidates import Candidates, CandidateDeltas, compute_deltas
from ..analyzer.constraint import BalancingConstraint
from ..analyzer.derived import compute_derived
from ..analyzer.search import (
    _EPS_IMPROVEMENT, ExclusionMasks, OptimizationFailureError, SearchConfig,
    _conflict_free_top_m, _per_broker_top_replicas, apply_selected, goal_aux,
    reduce_per_source, run_rounds_loop, score_round_candidates,
)
from ..model.tensors import ClusterTensors, slot_coords
from .mesh import PARTITION_AXIS


def _state_specs() -> ClusterTensors:
    """PartitionSpec pytree for ClusterTensors: partition axis sharded,
    broker axis replicated."""
    return ClusterTensors(
        assignment=P(PARTITION_AXIS), leader_slot=P(PARTITION_AXIS),
        leader_load=P(PARTITION_AXIS), follower_load=P(PARTITION_AXIS),
        capacity=P(), rack=P(), broker_state=P(), topic=P(PARTITION_AXIS),
        partition_mask=P(PARTITION_AXIS), broker_mask=P(), host=P())


def mutable_state_specs() -> tuple:
    """(assignment, leader_slot) specs — the two tensors the search
    mutates, and therefore the EXACT donation set of the donated megastep
    kernels (parallel.chain_sharded): they ride as separate donated
    arguments while everything else travels read-only through
    ``chain.strip_mutable``'s remainder."""
    return P(PARTITION_AXIS), P(PARTITION_AXIS)


def shard_cluster(state: ClusterTensors, mesh: Mesh) -> ClusterTensors:
    """Place a ClusterTensors on the mesh with the partition axis sharded.
    Partition count must divide the mesh size (pad via the builder's
    partition_bucket)."""
    n = mesh.devices.size
    if state.num_partitions % n != 0:
        raise ValueError(
            f"num_partitions {state.num_partitions} not divisible by mesh size {n}")
    specs = _state_specs()
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), state, specs)


def _psum(x):
    return jax.lax.psum(x, PARTITION_AXIS)


def _round_local(state: ClusterTensors, masks: ExclusionMasks, *, goal,
                 optimized, constraint, cfg: SearchConfig, num_topics: int,
                 num_shards: int):
    """Per-device body of one sharded search round (runs under shard_map;
    ``state`` holds this device's partition rows)."""
    shard = jax.lax.axis_index(PARTITION_AXIS)
    p_local = state.num_partitions
    p_global = p_local * num_shards
    offset = shard * p_local

    # Per-device source floor: a too-thin slice (num_sources/shards)
    # can strand the LAST violating replica below a device's top-k
    # while the global single-device search would surface it.
    k_src = max(16, cfg.num_sources // num_shards)
    cand, deltas, score, layout, _ctx = score_round_candidates(
        state, masks, goal, optimized, constraint, cfg, num_topics,
        psum=_psum, k_src=k_src)

    # Shared per-source reduction; the shard-dependent row offset makes
    # different devices lean toward different destinations among ties.
    red_idx = reduce_per_source(score, layout, row_offset=shard * k_src)

    # Gather every device's reduced candidates (global partition ids) so all
    # devices agree on one conflict-free batch.
    def gather(x):
        return jax.lax.all_gather(x, PARTITION_AXIS).reshape(
            (num_shards * x.shape[0],) + x.shape[1:])

    g_score = gather(score[red_idx])
    g_part = gather(deltas.partition[red_idx] + offset)
    g_src = gather(deltas.src_broker[red_idx])
    g_dst = gather(deltas.dst_broker[red_idx])
    g_slot = gather(deltas.src_slot[red_idx])
    g_dslot = gather(cand.dst_slot[red_idx])
    g_kind = gather(cand.kind[red_idx])

    top_idx, sel = _conflict_free_top_m(g_score, g_part, g_src, g_dst,
                                        cfg.moves_per_round, p_global,
                                        state.num_brokers)

    new_state = apply_selected(state, sel, g_part[top_idx], g_slot[top_idx],
                               g_dst[top_idx], g_kind[top_idx],
                               g_dslot[top_idx], row_offset=offset)
    return new_state, sel.sum()


@lru_cache(maxsize=256)
def _make_sharded_round(mesh: Mesh, goal, optimized, constraint,
                        cfg: SearchConfig, num_topics: int,
                        mask_presence: tuple[bool, bool, bool]):
    """Build + jit the shard_map'd round for one (mesh, goal-chain) config."""
    num_shards = mesh.devices.size
    state_specs = _state_specs()
    body = partial(_round_local, goal=goal, optimized=optimized,
                   constraint=constraint, cfg=cfg, num_topics=num_topics,
                   num_shards=num_shards)
    mapped = shard_map(body, mesh=mesh,
                       in_specs=(state_specs, _mask_specs(mask_presence)),
                       out_specs=(state_specs, P()), check_vma=False)
    return jax.jit(mapped)


def _rounds_local(state: ClusterTensors, masks: ExclusionMasks, *, goal,
                  optimized, constraint, cfg: SearchConfig, num_topics: int,
                  num_shards: int):
    """Fused multi-round driver under the mesh: `lax.while_loop` runs
    sharded search rounds (collectives and all) until convergence — ONE
    host round-trip per goal phase instead of one per round (the sharded
    analogue of search.optimize_rounds; VERDICT round 1 weak #3)."""
    return run_rounds_loop(
        lambda s: _round_local(s, masks, goal=goal, optimized=optimized,
                               constraint=constraint, cfg=cfg,
                               num_topics=num_topics, num_shards=num_shards),
        state, cfg.max_rounds)


@lru_cache(maxsize=256)
def _make_sharded_rounds(mesh: Mesh, goal, optimized, constraint,
                         cfg: SearchConfig, num_topics: int,
                         mask_presence: tuple[bool, bool, bool]):
    num_shards = mesh.devices.size
    state_specs = _state_specs()
    body = partial(_rounds_local, goal=goal, optimized=optimized,
                   constraint=constraint, cfg=cfg, num_topics=num_topics,
                   num_shards=num_shards)
    mapped = shard_map(body, mesh=mesh,
                       in_specs=(state_specs, _mask_specs(mask_presence)),
                       out_specs=(state_specs, P(), P()), check_vma=False)
    return jax.jit(mapped)


def _swap_round_local(state: ClusterTensors, masks: ExclusionMasks, *, goal,
                      optimized, constraint, num_topics: int, num_shards: int,
                      k_brokers: int = 8, j_replicas: int = 4,
                      moves: int = 8):
    """One sharded swap round (per-device body).

    The swap phase pairs a heavy replica on an overloaded broker with a
    light replica on a donor broker — the two replicas live on ARBITRARY
    partition shards, so the kernel splits the work (no global gather of
    the model):

    1. LOCAL: each device finds its top-j heaviest/lightest replicas per
       candidate broker and evaluates every prior goal's per-partition LEG
       acceptance against each possible counterparty broker
       (swap_leg_acceptance — partition state is local here).
    2. GATHER: the tiny "replica cards" (weight, load vector, leader flag,
       global id, leg-acceptance bitmaps) are all-gathered — O(K·j·K) per
       device, independent of partition count.
    3. REPLICATED: every device merges the cards (global top-j per broker),
       builds the K×K×j×j pairing grid, applies net acceptance
       (swap_net_acceptance: broker-level by contract) + the active goal's
       net improvement, and selects one conflict-free batch — identical on
       all devices.
    4. LOCAL: each device applies the legs that land in its shard.
    """
    shard = jax.lax.axis_index(PARTITION_AXIS)
    p_local = state.num_partitions
    p_global = p_local * num_shards
    offset = shard * p_local
    b = state.num_brokers
    s_dim = state.max_replication_factor
    j = j_replicas

    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers, psum=_psum)
    aux = goal_aux(goal, state, derived, constraint, num_topics, psum=_psum)
    aux_by = {g.name: goal_aux(g, state, derived, constraint, num_topics,
                               psum=_psum)
              for g in optimized}

    src_score = goal.source_score(state, derived, constraint, aux)
    if goal.partition_additive_scores:
        src_score = _psum(src_score)
    # Swap counterparties rank by swap_dest_score (broker-indexed, mesh-
    # safe) — consistent with the chain swap bodies. Leg-scored swap
    # IMPROVEMENT overrides still stay single-device (see
    # chain_sharded._chain_swap_local).
    dst_score = goal.swap_dest_score(state, derived, constraint, aux)
    weight = goal.replica_weight(state, derived, constraint, aux)

    k = min(k_brokers, b)
    src_vals, src_brokers = jax.lax.top_k(
        jnp.where(src_score > 0, src_score, -jnp.inf), k)
    dst_vals, dst_brokers = jax.lax.top_k(dst_score, k)
    src_b_ok = jnp.isfinite(src_vals)   # [k], replicated values
    dst_b_ok = jnp.isfinite(dst_vals)

    heavy_idx, heavy_ok = _per_broker_top_replicas(
        state, weight, src_brokers, j, largest=True)     # [k, j] local
    light_idx, light_ok = _per_broker_top_replicas(
        state, weight, dst_brokers, j, largest=False)

    p1, s1 = slot_coords(heavy_idx, state.num_partitions, s_dim)  # local ids [k, j]
    p2, s2 = slot_coords(light_idx, state.num_partitions, s_dim)

    def leg_masks(pp, ss, ok, counterparties):
        """[k, j, k] leg acceptance: replica (pp, ss) moved to each
        counterparty broker, judged by structural legitimacy + every prior
        goal's swap_leg_acceptance (local partition state)."""
        n = k * j * k
        cand = Candidates(
            kind=jnp.zeros(n, dtype=jnp.int8),
            partition=jnp.broadcast_to(pp[:, :, None], (k, j, k)).reshape(-1),
            src_slot=jnp.broadcast_to(ss[:, :, None], (k, j, k)).reshape(-1),
            dst_broker=jnp.broadcast_to(counterparties[None, None, :],
                                        (k, j, k)).reshape(-1),
            dst_slot=jnp.zeros(n, dtype=jnp.int32),
            valid=jnp.broadcast_to(ok[:, :, None], (k, j, k)).reshape(-1))
        d = compute_deltas(state, derived, cand)
        acc = d.valid
        for g in optimized:
            acc &= g.swap_leg_acceptance(state, derived, constraint,
                                         aux_by[g.name], d)
        return acc.reshape(k, j, k)

    leg_f = leg_masks(p1, s1, heavy_ok, dst_brokers)   # heavy → dst brokers
    leg_r = leg_masks(p2, s2, light_ok, src_brokers)   # light → src brokers

    # Replica cards. Invalid heavy cards sink (-inf), invalid light float
    # (+inf) so the global top-j merge never picks them.
    w_a = jnp.where(heavy_ok, weight[p1, s1], -jnp.inf)
    w_b = jnp.where(light_ok, weight[p2, s2], jnp.inf)
    lead1 = state.leader_slot[p1] == s1
    lead2 = state.leader_slot[p2] == s2
    load_a = jnp.where(lead1[..., None], state.leader_load[p1],
                       state.follower_load[p1])          # [k, j, R]
    load_b = jnp.where(lead2[..., None], state.leader_load[p2],
                       state.follower_load[p2])
    gp1, gp2 = p1 + offset, p2 + offset
    top1 = state.topic[p1]

    def gather_cards(x):
        """[k, j, ...] per-device → [k, num_shards·j, ...] merged."""
        y = jax.lax.all_gather(x, PARTITION_AXIS)        # [n_sh, k, j, ...]
        y = jnp.moveaxis(y, 0, 1)                        # [k, n_sh, j, ...]
        return y.reshape((k, num_shards * j) + y.shape[3:])

    g_wa = gather_cards(w_a)
    g_wb = gather_cards(w_b)
    hv, hsel = jax.lax.top_k(g_wa, j)                    # global top-j heavy
    lv, lsel = jax.lax.top_k(-g_wb, j)                   # global top-j light
    heavy_ok_g = jnp.isfinite(hv)
    light_ok_g = jnp.isfinite(lv)

    def pick(gathered, sel):
        extra = gathered.ndim - 2
        return jnp.take_along_axis(
            gathered, sel.reshape(sel.shape + (1,) * extra), axis=1)

    h_load = pick(gather_cards(load_a), hsel)            # [k, j, R]
    l_load = pick(gather_cards(load_b), lsel)
    h_lead = pick(gather_cards(lead1), hsel)
    l_lead = pick(gather_cards(lead2), lsel)
    h_gp = pick(gather_cards(gp1), hsel)
    l_gp = pick(gather_cards(gp2), lsel)
    h_s = pick(gather_cards(s1), hsel)
    l_s = pick(gather_cards(s2), lsel)
    h_topic = pick(gather_cards(top1), hsel)
    h_legs = pick(gather_cards(leg_f), hsel)             # [k, j, k]
    l_legs = pick(gather_cards(leg_r), lsel)
    h_w = hv          # top_k values of g_wa
    l_w = -lv         # top_k of -g_wb ⇒ negate back

    # Pairing grid [k_src, k_dst, j, j] — replicated, identical everywhere.
    n = k * k * j * j
    si, di, ai, bi = jnp.meshgrid(jnp.arange(k), jnp.arange(k),
                                  jnp.arange(j), jnp.arange(j), indexing="ij")
    si, di, ai, bi = (x.reshape(-1) for x in (si, di, ai, bi))
    src_b = src_brokers[si]
    dst_b = dst_brokers[di]
    wa = h_w[si, ai]
    wb = l_w[di, bi]
    sel_gp1 = h_gp[si, ai]
    sel_gp2 = l_gp[di, bi]

    base_valid = src_b_ok[si] & dst_b_ok[di] & heavy_ok_g[si, ai] \
        & light_ok_g[di, bi] & (src_b != dst_b) & (sel_gp1 != sel_gp2) \
        & (wa > wb) & h_legs[si, ai, di] & l_legs[di, bi, si]

    lead_d = h_lead[si, ai].astype(jnp.int32) - l_lead[di, bi].astype(jnp.int32)
    net_load = h_load[si, ai] - l_load[di, bi]
    net = CandidateDeltas(
        src_broker=jnp.where(base_valid, src_b, 0),
        dst_broker=jnp.where(base_valid, dst_b, 0),
        load_delta=jnp.where(base_valid[:, None], net_load, 0.0),
        replica_delta=jnp.zeros(n, dtype=jnp.int32),
        leader_delta=jnp.where(base_valid, lead_d, 0),
        partition=sel_gp1, topic=h_topic[si, ai],
        src_slot=h_s[si, ai], dst_slot=jnp.zeros(n, dtype=jnp.int32),
        valid=base_valid)

    accept = base_valid
    for g in optimized:
        accept &= g.swap_net_acceptance(state, derived, constraint,
                                        aux_by[g.name], net)
    imp = goal.improvement(state, derived, constraint, aux, net)
    score = jnp.where(accept, imp, -jnp.inf)

    # Conflict-free selection over GLOBAL partition/broker key spaces —
    # replicated and deterministic (same inputs on every device).
    k_m = min(moves, n)
    top_score, top_idx = jax.lax.top_k(score, k_m)
    ok = top_score > _EPS_IMPROVEMENT
    rank = jnp.arange(k_m, dtype=jnp.int32)
    big = jnp.int32(k_m + 1)
    rank_eff = jnp.where(ok, rank, big)
    t_gp1, t_gp2 = sel_gp1[top_idx], sel_gp2[top_idx]
    t_src, t_dst = src_b[top_idx], dst_b[top_idx]
    first_part = jnp.full(p_global, big, jnp.int32) \
        .at[t_gp1].min(rank_eff).at[t_gp2].min(rank_eff)
    first_broker = jnp.full(b, big, jnp.int32) \
        .at[t_src].min(rank_eff).at[t_dst].min(rank_eff)
    sel = ok & (first_part[t_gp1] == rank) & (first_part[t_gp2] == rank) \
        & (first_broker[t_src] == rank) & (first_broker[t_dst] == rank)

    # Apply the legs owned by this shard (OOB rows drop).
    p_pad = jnp.int32(p_local)
    row1 = t_gp1 - offset
    row2 = t_gp2 - offset
    rows1 = jnp.where(sel & (row1 >= 0) & (row1 < p_local), row1, p_pad)
    rows2 = jnp.where(sel & (row2 >= 0) & (row2 < p_local), row2, p_pad)
    new_assignment = state.assignment \
        .at[rows1, h_s[si, ai][top_idx]].set(
            t_dst.astype(state.assignment.dtype), mode="drop") \
        .at[rows2, l_s[di, bi][top_idx]].set(
            t_src.astype(state.assignment.dtype), mode="drop")
    return dataclasses.replace(state, assignment=new_assignment), sel.sum()


def _swap_rounds_local(state: ClusterTensors, masks: ExclusionMasks, *, goal,
                       optimized, constraint, num_topics: int,
                       num_shards: int, moves: int = 8, max_rounds: int = 64):
    """Fused sharded swap driver (while_loop analogue of swap_rounds)."""
    return run_rounds_loop(
        lambda s: _swap_round_local(
            s, masks, goal=goal, optimized=optimized, constraint=constraint,
            num_topics=num_topics, num_shards=num_shards, moves=moves),
        state, max_rounds)


@lru_cache(maxsize=256)
def _make_sharded_swap_rounds(mesh: Mesh, goal, optimized, constraint,
                              num_topics: int,
                              mask_presence: tuple[bool, bool, bool]):
    num_shards = mesh.devices.size
    state_specs = _state_specs()
    body = partial(_swap_rounds_local, goal=goal, optimized=optimized,
                   constraint=constraint, num_topics=num_topics,
                   num_shards=num_shards)
    mapped = shard_map(body, mesh=mesh,
                       in_specs=(state_specs, _mask_specs(mask_presence)),
                       out_specs=(state_specs, P(), P()), check_vma=False)
    return jax.jit(mapped)


def _mask_specs(mask_presence: tuple[bool, bool, bool]) -> ExclusionMasks:
    return ExclusionMasks(
        excluded_topics=P() if mask_presence[0] else None,
        excluded_replica_move_brokers=P() if mask_presence[1] else None,
        excluded_leadership_brokers=P() if mask_presence[2] else None)


@lru_cache(maxsize=256)
def _make_sharded_check(mesh: Mesh, goal, constraint,
                        num_topics: int, mask_presence: tuple[bool, bool, bool]):
    """Total goal violation computed UNDER the mesh (no host gather): psum'd
    derived state + psum'd aux partials, so [T, B]-aux goals never
    materialize on one device."""

    def body(state: ClusterTensors, masks: ExclusionMasks):
        derived = compute_derived(state, masks.excluded_topics,
                                  masks.excluded_replica_move_brokers,
                                  masks.excluded_leadership_brokers, psum=_psum)
        aux = goal_aux(goal, state, derived, constraint, num_topics, psum=_psum)
        viol = goal.broker_violations(state, derived, constraint, aux)
        if goal.partition_additive_scores:
            viol = _psum(viol)
        return viol.sum()

    mapped = shard_map(body, mesh=mesh, in_specs=(_state_specs(),
                                                  _mask_specs(mask_presence)),
                       out_specs=P(), check_vma=False)
    return jax.jit(mapped)


def sharded_optimize_round(state: ClusterTensors, goal, optimized,
                           constraint: BalancingConstraint, cfg: SearchConfig,
                           num_topics: int, masks: ExclusionMasks,
                           mesh: Mesh) -> tuple[ClusterTensors, jax.Array]:
    presence = (masks.excluded_topics is not None,
                masks.excluded_replica_move_brokers is not None,
                masks.excluded_leadership_brokers is not None)
    fn = _make_sharded_round(mesh, goal, tuple(optimized), constraint, cfg,
                             num_topics, presence)
    return fn(state, masks)


@lru_cache(maxsize=256)
def _make_sharded_swap_round(mesh: Mesh, goal, optimized, constraint,
                             num_topics: int,
                             mask_presence: tuple[bool, bool, bool]):
    num_shards = mesh.devices.size
    body = partial(_swap_round_local, goal=goal, optimized=optimized,
                   constraint=constraint, num_topics=num_topics,
                   num_shards=num_shards)
    mapped = shard_map(body, mesh=mesh,
                       in_specs=(_state_specs(), _mask_specs(mask_presence)),
                       out_specs=(_state_specs(), P()), check_vma=False)
    return jax.jit(mapped)


def sharded_swap_round(state: ClusterTensors, goal, optimized,
                       constraint: BalancingConstraint, num_topics: int,
                       masks: ExclusionMasks, mesh: Mesh,
                       ) -> tuple[ClusterTensors, jax.Array]:
    """One sharded swap round (card-gather kernel; see _swap_round_local)."""
    presence = (masks.excluded_topics is not None,
                masks.excluded_replica_move_brokers is not None,
                masks.excluded_leadership_brokers is not None)
    fn = _make_sharded_swap_round(mesh, goal, tuple(optimized), constraint,
                                  num_topics, presence)
    return fn(state, masks)


def optimize_goal_sharded(state: ClusterTensors, goal, optimized,
                          constraint: BalancingConstraint, cfg: SearchConfig,
                          num_topics: int, mesh: Mesh,
                          masks: ExclusionMasks | None = None,
                          ) -> tuple[ClusterTensors, dict]:
    """Sharded analogue of analyzer.search.optimize_goal.

    Both the move loop and the swap loop run as FUSED `lax.while_loop`
    drivers under the mesh — the host reads back one scalar per PHASE
    (``host_roundtrips`` in the info dict), not one per round, matching the
    single-chip path's dispatch profile over a high-latency device link."""
    masks = masks or ExclusionMasks()
    opt_tuple = tuple(optimized)
    presence = (masks.excluded_topics is not None,
                masks.excluded_replica_move_brokers is not None,
                masks.excluded_leadership_brokers is not None)
    fn_rounds = _make_sharded_rounds(mesh, goal, opt_tuple, constraint, cfg,
                                     num_topics, presence)
    fn_swaps = _make_sharded_swap_rounds(mesh, goal, opt_tuple, constraint,
                                         num_topics, presence) \
        if goal.supports_swap else None

    total_applied = 0
    total_swaps = 0
    rounds = 0
    roundtrips = 0
    while rounds < cfg.max_rounds:
        state, moves, r = fn_rounds(state, masks)
        roundtrips += 1
        total_applied += int(moves)
        rounds += int(r)
        if fn_swaps is None:
            break
        state, swapped, sr = fn_swaps(state, masks)
        roundtrips += 1
        swapped = int(swapped)
        total_swaps += swapped
        total_applied += swapped
        rounds += int(sr)
        if swapped == 0:
            break

    # Final violation check under the mesh — no host gather.
    check = _make_sharded_check(mesh, goal, constraint, num_topics, presence)
    total_violation = float(check(state, masks))
    roundtrips += 1
    succeeded = total_violation <= 1e-6
    if goal.is_hard and not succeeded:
        raise OptimizationFailureError(
            f"hard goal {goal.name} unsatisfied: residual violation "
            f"{total_violation:.4f} after {rounds} rounds")
    return state, {
        "goal": goal.name, "rounds": rounds, "moves_applied": total_applied,
        "swaps_applied": total_swaps,
        "residual_violation": total_violation, "succeeded": succeeded,
        "host_roundtrips": roundtrips,
    }
