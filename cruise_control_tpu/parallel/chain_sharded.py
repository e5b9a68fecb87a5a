"""Sharded chain kernels: the WHOLE goal chain, fused, under a device mesh.

Production multi-chip solver path. One ``shard_map``-wrapped, jitted kernel
runs the entire goal chain (``lax.scan`` over the goal index; the same
structure as ``analyzer.chain.chain_optimize_full``) with:

- partition-indexed tensors sharded along the mesh axis ``"p"``, broker
  aggregates psum'd (ICI collectives) — the sharding model of
  ``parallel.mesh``;
- the active goal as a TRACED index (``lax.switch``) and prior goals as a
  traced mask — ONE compilation per (mesh, chain, search config);
- one host dispatch and one stacked stats readback for the whole chain.

Collectives appear inside ``scan``/``while_loop``/``cond`` bodies; every
control-flow predicate is replicated (psum'd counters, the scanned goal
index), so all devices execute identical programs and the collectives
match.

Reference parity: GoalOptimizer.java:435-524 run under SPMD instead of a
precompute thread pool (SURVEY.md §2.11 row 1).
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..analyzer.candidates import (
    Candidates, CandidateDeltas, attach_cumulative, compute_deltas,
)
from ..analyzer.agg import (
    AggDelta, apply_deltas_to_agg, compute_agg, pot_lbi_deltas,
)
from ..analyzer.chain import (
    _chain_infos_from_stats, _chain_scores, _gated_aux, _scored_candidates,
    _switch_swap_dest_score, _switch_swap_light_weight,
    excluded_hosting_replicas, set_dispatch_rounds,
)
from ..analyzer.constraint import BalancingConstraint
from ..analyzer.derived import compute_derived
from ..analyzer.direct import (
    _direct_rounds_driver, direct_eligible, sparse_rounding_seed,
)
from ..analyzer.search import (
    _EPS_IMPROVEMENT, ExclusionMasks, SearchConfig,
    _per_broker_top_replicas, apply_selected, reduce_per_source,
    run_carry_loop, swap_brokers,
)
from ..common.resources import Resource
from ..model.tensors import ClusterTensors, offline_replicas, slot_coords
from .mesh import (
    PARTITION_AXIS, _mask_specs, _psum, _state_specs, mutable_state_specs,
)


def _chain_round_local(state: ClusterTensors, agg, masks: ExclusionMasks,
                       active_idx: jax.Array, prior_mask: jax.Array, *,
                       goals, constraint: BalancingConstraint,
                       cfg: SearchConfig, num_topics: int, num_shards: int):
    """One chain-parameterized sharded search round (per-device body): the
    scoring half of ``analyzer.chain`` (``_scored_candidates`` with the
    mesh's ``psum``), then the mesh's own selection — per-source reduction
    with a device-decorrelating rotation, the card gather, the rank-order
    cumulative recheck on the owning device, the apply by row offset.
    Every device scores a FULL ``cfg.num_sources``-wide grid over its own
    partition rows, so the union covers the global top-k and the search
    tracks the one-chip trajectory (docs/DESIGN.md "Known limits" has the
    widths and masks that measured worse). ``agg`` is the
    incrementally-maintained GLOBAL aggregate carry (replicated on every
    device; the selected batch is replicated too, so the update needs no
    further collectives). Returns (new_state, new_agg, applied)."""
    shard = jax.lax.axis_index(PARTITION_AXIS)
    p_local = state.num_partitions
    p_global = p_local * num_shards
    offset = shard * p_local

    sc = _scored_candidates(state, agg, active_idx, prior_mask, goals,
                            constraint, cfg, num_topics, masks,
                            global_partitions=p_global, psum=_psum)
    derived, aux_list, cand, deltas, score = \
        sc.derived, sc.aux_list, sc.cand, sc.deltas.without_grid(), sc.score

    # Device-decorrelating rotation offset: different devices lean toward
    # different destinations among ties.
    red_idx = reduce_per_source(
        score, sc.layout, row_offset=shard * cfg.num_sources,
        extra_last_col=sc.targets)
    k_local = red_idx.shape[0]

    def gather(x):
        return jax.lax.all_gather(x, PARTITION_AXIS).reshape(
            (num_shards * x.shape[0],) + x.shape[1:])

    # Per-candidate scalars that need LOCAL partition state are computed
    # pre-gather (global partition ids cannot be gathered against the local
    # shard); everything the joint-acceptance recheck needs travels with
    # the candidate card.
    local_sub = jax.tree.map(lambda a: a[red_idx], deltas)
    pot_local, lbi_local = pot_lbi_deltas(state, local_sub)

    g_sub = jax.tree.map(gather, local_sub)
    g_sub = dataclasses.replace(g_sub, partition=gather(
        local_sub.partition + offset))
    g_score = gather(score[red_idx])
    g_pot = gather(pot_local)
    g_lbi = gather(lbi_local)
    g_dslot = gather(cand.dst_slot[red_idx])
    g_kind = gather(cand.kind[red_idx])

    # Joint (cumulative) conflict selection, replicated: rank by score,
    # dedupe partitions, pairwise pre-deltas in RANK order over the
    # device-concatenated card array (search.cumulative_select semantics,
    # inlined because rank != array order here).
    k_global = num_shards * k_local
    k = min(max(cfg.moves_per_round, cfg.num_sources), k_global)
    top_score, order = jax.lax.top_k(g_score, k)
    ranked = jax.tree.map(lambda a: a[order], g_sub)
    ok = top_score > _EPS_IMPROVEMENT
    rank = jnp.arange(k, dtype=jnp.int32)
    big = jnp.int32(k + 1)
    rank_eff = jnp.where(ok, rank, big)
    first_p = jnp.full(p_global, big, jnp.int32) \
        .at[ranked.partition].min(rank_eff)
    part_ok = ok & (first_p[ranked.partition] == rank)
    ranked, has_earlier = attach_cumulative(ranked, part_ok, g_pot[order],
                                            g_lbi[order])

    # Acceptance recheck: per-BROKER state (derived, aux) is replicated, so
    # every device evaluates the full ranked batch identically — structural
    # per-partition terms were already folded into pass-1 acceptance (the
    # score), and per-partition scalars (pot/lbi) travel with the cards, so
    # goal.acceptance here must only touch broker-indexed state. All the
    # stacked goals' acceptance implementations satisfy that except the
    # structural ones, whose acceptance ignores the pre fields and repeats
    # the (partition-local) pass-1 verdict — evaluate those on the OWNING
    # device and gather. To keep one code path, the recheck gates on
    # ownership masks.
    own = (ranked.partition >= offset) & (ranked.partition < offset + p_local)
    local_rows = jnp.clip(ranked.partition - offset, 0, p_local - 1)
    local_view = dataclasses.replace(ranked, partition=local_rows)

    accept = jnp.ones(k, dtype=bool)
    for i, g in enumerate(goals):
        g_acc = g.acceptance(state, derived, constraint, aux_list[i],
                             local_view)
        # Rows this device does not own read clamped partition state —
        # meaningless; trust the owner: psum of (owner's verdict), since
        # exactly one device owns each row.
        g_acc_owned = _psum(jnp.where(own, g_acc, False).astype(jnp.int32)) > 0
        accept &= (~prior_mask[i]) | g_acc_owned
        accept &= (~sc.is_active[i]) | (~has_earlier) | g_acc_owned

    sel = part_ok & accept
    within_cap = jnp.cumsum(sel.astype(jnp.int32)) <= cfg.moves_per_round
    sel &= jnp.where(sc.independent, True, within_cap)

    # ``sel`` is computed from gathered, replicated data — identical on
    # every device, so its sum is already the global count, and the
    # aggregate-carry update below stays replicated device-for-device.
    if agg is not None:
        with jax.named_scope("round.apply"):
            agg = apply_deltas_to_agg(agg, ranked, sel, g_pot[order],
                                      g_lbi[order])
    new_state = apply_selected(state, sel, ranked.partition,
                               ranked.src_slot, ranked.dst_broker,
                               g_kind[order], g_dslot[order],
                               row_offset=offset)
    return new_state, agg, sel.sum()


@jax.named_scope("swap.round")
def _chain_swap_local(state: ClusterTensors, agg, masks: ExclusionMasks,
                      active_idx: jax.Array, prior_mask: jax.Array, *,
                      goals, constraint: BalancingConstraint, num_topics: int,
                      num_shards: int, k_brokers: int = 8,
                      j_replicas: int = 4, moves: int = 8):
    """Chain-parameterized sharded swap round, a card-gather kernel: the
    two replicas of a swap live on ARBITRARY partition shards, so each
    device finds its top-j heaviest / lightest replicas per candidate
    broker and judges every prior goal's per-partition LEG acceptance
    locally; the tiny replica cards are all-gathered (O(K·j·K) a device,
    independent of the partition count); every device merges them, builds
    the K x K x j x j pairing grid, applies net acceptance and the active
    goal's net improvement and selects ONE conflict-free batch, identical
    everywhere; each device applies the legs that land in its shard. The
    active goal is a traced switch, prior acceptance a traced mask.
    ``agg`` as in ``_chain_round_local``; returns (new_state, new_agg,
    applied)."""
    shard = jax.lax.axis_index(PARTITION_AXIS)
    p_local = state.num_partitions
    p_global = p_local * num_shards
    offset = shard * p_local
    b = state.num_brokers
    s_dim = state.max_replication_factor
    j = j_replicas

    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers, psum=_psum,
                              agg=agg)
    _is_active, aux_list, src_score, _dst_score, weight = _chain_scores(
        state, derived, active_idx, prior_mask, goals, constraint,
        num_topics, agg, psum=_psum)

    # Swap counterparties rank by swap_dest_score (broker-indexed, mesh-
    # safe). NOTE: swap IMPROVEMENT on the mesh stays net-transfer-based
    # (goal.improvement(net)) — leg-scored overrides (swap_improvement)
    # need the legs' partition-local state, which lives on the owning
    # device; the kafka-assigner tool mode that relies on leg scoring
    # runs single-device.
    dst_score = _switch_swap_dest_score(active_idx, goals, aux_list, state,
                                        derived, constraint)

    k = min(k_brokers, b)
    src_brokers, src_b_ok, dst_brokers, dst_b_ok = swap_brokers(
        derived, src_score, dst_score, k)

    # The heaviest by the move grid's order, the lightest and the
    # comparison by what a replica weighs in a swap, as search.swap_grid.
    light_weight = _switch_swap_light_weight(active_idx, goals, aux_list,
                                             state, derived, constraint)
    heavy_idx, heavy_ok = _per_broker_top_replicas(
        state, weight, src_brokers, j, largest=True)
    light_idx, light_ok = _per_broker_top_replicas(
        state, light_weight, dst_brokers, j, largest=False)

    p1, s1 = slot_coords(heavy_idx, state.num_partitions, s_dim)
    p2, s2 = slot_coords(light_idx, state.num_partitions, s_dim)

    def leg_masks(pp, ss, ok, counterparties):
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
        for i, g in enumerate(goals):
            acc &= (~prior_mask[i]) | g.swap_leg_acceptance(
                state, derived, constraint, aux_list[i], d)
        return acc.reshape(k, j, k)

    leg_f = leg_masks(p1, s1, heavy_ok, dst_brokers)
    leg_r = leg_masks(p2, s2, light_ok, src_brokers)

    w_a = jnp.where(heavy_ok, weight[p1, s1], -jnp.inf)
    w_b = jnp.where(light_ok, light_weight[p2, s2], jnp.inf)
    size_a = light_weight[p1, s1]
    lead1 = state.leader_slot[p1] == s1
    lead2 = state.leader_slot[p2] == s2
    load_a = jnp.where(lead1[..., None], state.leader_load[p1],
                       state.follower_load[p1])
    load_b = jnp.where(lead2[..., None], state.leader_load[p2],
                       state.follower_load[p2])
    gp1, gp2 = p1 + offset, p2 + offset
    top1 = state.topic[p1]
    top2 = state.topic[p2]
    nwout1 = state.leader_load[p1, int(Resource.NW_OUT)]
    nwout2 = state.leader_load[p2, int(Resource.NW_OUT)]
    nwin1 = state.leader_load[p1, int(Resource.NW_IN)]
    nwin2 = state.leader_load[p2, int(Resource.NW_IN)]

    def gather_cards(x):
        y = jax.lax.all_gather(x, PARTITION_AXIS)
        y = jnp.moveaxis(y, 0, 1)
        return y.reshape((k, num_shards * j) + y.shape[3:])

    g_wa = gather_cards(w_a)
    g_wb = gather_cards(w_b)
    hv, hsel = jax.lax.top_k(g_wa, j)
    lv, lsel = jax.lax.top_k(-g_wb, j)
    heavy_ok_g = jnp.isfinite(hv)
    light_ok_g = jnp.isfinite(lv)

    def pick(gathered, sel):
        extra = gathered.ndim - 2
        return jnp.take_along_axis(
            gathered, sel.reshape(sel.shape + (1,) * extra), axis=1)

    h_load = pick(gather_cards(load_a), hsel)
    l_load = pick(gather_cards(load_b), lsel)
    h_lead = pick(gather_cards(lead1), hsel)
    l_lead = pick(gather_cards(lead2), lsel)
    h_gp = pick(gather_cards(gp1), hsel)
    l_gp = pick(gather_cards(gp2), lsel)
    h_s = pick(gather_cards(s1), hsel)
    l_s = pick(gather_cards(s2), lsel)
    h_topic = pick(gather_cards(top1), hsel)
    l_topic = pick(gather_cards(top2), lsel)
    h_nwout = pick(gather_cards(nwout1), hsel)
    l_nwout = pick(gather_cards(nwout2), lsel)
    h_nwin = pick(gather_cards(nwin1), hsel)
    l_nwin = pick(gather_cards(nwin2), lsel)
    h_legs = pick(gather_cards(leg_f), hsel)
    l_legs = pick(gather_cards(leg_r), lsel)
    h_w = pick(gather_cards(size_a), hsel)
    l_w = -lv

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
    for i, g in enumerate(goals):
        accept &= (~prior_mask[i]) | g.swap_net_acceptance(
            state, derived, constraint, aux_list[i], net)

    def imp_branch(i):
        g = goals[i]

        def fn(_):
            return g.improvement(state, derived, constraint, aux_list[i],
                                 net).astype(jnp.float32)
        return fn

    imp = jax.lax.switch(active_idx,
                         [imp_branch(i) for i in range(len(goals))], 0)
    score = jnp.where(accept, imp, -jnp.inf)

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

    if agg is not None:
        # Replicated leg updates (see _chain_round_local): both directional
        # legs of each accepted swap scatter their exact effect.
        ones = jnp.ones(k_m, dtype=jnp.int32)
        h_lead_t = h_lead[si, ai][top_idx].astype(jnp.int32)
        l_lead_t = l_lead[di, bi][top_idx].astype(jnp.int32)
        fwd_leg = AggDelta(
            src_broker=t_src, dst_broker=t_dst,
            load_delta=h_load[si, ai][top_idx], replica_delta=ones,
            leader_delta=h_lead_t, topic=h_topic[si, ai][top_idx])
        rev_leg = AggDelta(
            src_broker=t_dst, dst_broker=t_src,
            load_delta=l_load[di, bi][top_idx], replica_delta=ones,
            leader_delta=l_lead_t, topic=l_topic[di, bi][top_idx])
        agg = apply_deltas_to_agg(
            agg, fwd_leg, sel, h_nwout[si, ai][top_idx],
            h_lead_t * h_nwin[si, ai][top_idx])
        agg = apply_deltas_to_agg(
            agg, rev_leg, sel, l_nwout[di, bi][top_idx],
            l_lead_t * l_nwin[di, bi][top_idx])

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
    return dataclasses.replace(state, assignment=new_assignment), agg, sel.sum()


@jax.named_scope("goal.stats")
def _chain_stats_local(state: ClusterTensors, masks: ExclusionMasks,
                       active_idx: jax.Array, *, goals,
                       constraint: BalancingConstraint, num_topics: int):
    """(viol, obj, offline) of the active goal under the mesh. Dispatches
    through ``Goal.objective`` like the single-device stats body; a goal
    with ``partition_additive_scores`` must keep any objective override
    partition-additive too (it is psum'd here)."""
    additive_f = jnp.asarray([g.partition_additive_scores for g in goals])
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers, psum=_psum)
    is_active = jnp.arange(len(goals)) == active_idx
    aux_list = [_gated_aux(is_active[i], g, state, derived, constraint,
                           num_topics, psum=_psum)
                for i, g in enumerate(goals)]

    def branch(i):
        g = goals[i]

        def fn(_):
            viol = g.broker_violations(state, derived, constraint,
                                       aux_list[i]).sum().astype(jnp.float32)
            obj = g.objective(state, derived, constraint,
                              aux_list[i]).astype(jnp.float32)
            return viol, obj
        return fn

    viol, obj = jax.lax.switch(active_idx,
                               [branch(i) for i in range(len(goals))], 0)
    viol = jnp.where(additive_f[active_idx], _psum(viol), viol)
    obj = jnp.where(additive_f[active_idx], _psum(obj), obj)
    offline = _psum(offline_replicas(state).sum())
    return viol, obj, offline


def _chain_full_local(state: ClusterTensors, masks: ExclusionMasks, *,
                      goals, constraint: BalancingConstraint,
                      cfg: SearchConfig, num_topics: int, num_shards: int,
                      swap_moves: int, swap_max_rounds: int):
    """Per-device body of the whole-chain kernel (the sharded analogue of
    ``analyzer.chain.chain_optimize_full``'s traced body)."""
    g_count = len(goals)
    supports_swap = jnp.asarray([g.supports_swap for g in goals])

    def drain_pending(s: ClusterTensors) -> jax.Array:
        if masks.excluded_replica_move_brokers is None:
            return jnp.bool_(False)
        on_excl = excluded_hosting_replicas(
            s, masks.excluded_replica_move_brokers)
        return _psum(on_excl.sum()) > 0  # replicated predicate on the mesh

    def per_goal(carry_state, g):
        prior = jnp.arange(g_count) < g
        viol0, obj0, offline0 = _chain_stats_local(
            carry_state, masks, g, goals=goals, constraint=constraint,
            num_topics=num_topics)

        def run(s):
            # Aggregate carry: psum'd -> global, replicated, threaded
            # through both phases. A cond-GATED in-loop refresh would be
            # collective-unsafe, but while_loop bodies execute collectives
            # unconditionally on every device, so an ungated recompute at
            # the top of each outer iteration is safe — it bounds f32
            # drift to one move+swap cycle instead of a full
            # cfg.max_rounds pass (ADVICE r4; counts stay exact always).
            def outer_cond(c):
                _s, _a, _m, _sw, rounds, last_swapped, first = c
                return (first | (last_swapped > 0)) & (rounds < cfg.max_rounds)

            def outer_body(c):
                s, _a, m_tot, sw_tot, rounds, _ls, _first = c
                a = compute_agg(s, num_topics, psum=_psum)

                def move_body(carry, _r):
                    st, ag = carry
                    ns, nag, applied = _chain_round_local(
                        st, ag, masks, g, prior, goals=goals,
                        constraint=constraint, cfg=cfg,
                        num_topics=num_topics, num_shards=num_shards)
                    return (ns, nag), applied

                (s, a), m, r = run_carry_loop(move_body, (s, a),
                                              cfg.max_rounds)

                def do_swap(st_ag):
                    def swap_body(carry, _r):
                        st, ag = carry
                        ns, nag, applied = _chain_swap_local(
                            st, ag, masks, g, prior, goals=goals,
                            constraint=constraint, num_topics=num_topics,
                            num_shards=num_shards, moves=swap_moves)
                        return (ns, nag), applied

                    (st, ag), sw, sr = run_carry_loop(swap_body, st_ag,
                                                      swap_max_rounds)
                    return st, ag, sw, sr

                def no_swap(st_ag):
                    st, ag = st_ag
                    return st, ag, jnp.int32(0), jnp.int32(0)

                s, a, sw, sr = jax.lax.cond(supports_swap[g], do_swap,
                                            no_swap, (s, a))
                return (s, a, m_tot + m, sw_tot + sw, rounds + r + sr, sw,
                        jnp.bool_(False))

            s, a, m, sw, rounds, _, _ = jax.lax.while_loop(
                outer_cond, outer_body,
                (s, compute_agg(s, num_topics, psum=_psum), jnp.int32(0),
                 jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.bool_(True)))
            return s, m, sw, rounds

        def skip(s):
            return s, jnp.int32(0), jnp.int32(0), jnp.int32(0)

        new_state, moves, swaps, rounds = jax.lax.cond(
            (viol0 > 0) | (offline0 > 0) | drain_pending(carry_state),
            run, skip, carry_state)
        viol1, obj1, offline1 = _chain_stats_local(
            new_state, masks, g, goals=goals, constraint=constraint,
            num_topics=num_topics)
        ys = {"viol_before": viol0, "obj_before": obj0,
              "offline_before": offline0, "viol_after": viol1,
              "obj_after": obj1, "offline_after": offline1,
              "moves": moves, "swaps": swaps, "rounds": rounds}
        return new_state, ys

    return jax.lax.scan(per_goal, state, jnp.arange(g_count, dtype=jnp.int32))


@lru_cache(maxsize=64)
def _make_chain_full(mesh: Mesh, goals, constraint, cfg: SearchConfig,
                     num_topics: int, mask_presence: tuple[bool, bool, bool],
                     swap_moves: int, swap_max_rounds: int):
    """ONE compile per (mesh, chain, search config) — the whole chain."""
    body = partial(_chain_full_local, goals=goals, constraint=constraint,
                   cfg=cfg, num_topics=num_topics,
                   num_shards=mesh.devices.size, swap_moves=swap_moves,
                   swap_max_rounds=swap_max_rounds)
    stats_specs = {k: P() for k in
                   ("viol_before", "obj_before", "offline_before",
                    "viol_after", "obj_after", "offline_after",
                    "moves", "swaps", "rounds")}
    mapped = shard_map(body, mesh=mesh,
                       in_specs=(_state_specs(), _mask_specs(mask_presence)),
                       out_specs=(_state_specs(), stats_specs),
                       check_vma=False)
    return jax.jit(mapped)


def optimize_chain_sharded(state: ClusterTensors, chain,
                           constraint: BalancingConstraint, cfg: SearchConfig,
                           num_topics: int, mesh: Mesh,
                           masks: ExclusionMasks | None = None,
                           swap_moves: int = 8, swap_max_rounds: int = 64,
                           dispatch_rounds: int = 0,
                           dispatch_target_s: float = 0.0,
                           dispatch=None, dispatch_wide=None,
                           megastep=None, stats=None,
                           donate_input: bool = False,
                           flight=None,
                           ) -> tuple[ClusterTensors, list[dict]]:
    """Sharded analogue of ``analyzer.chain.optimize_chain``: the whole
    chain in one dispatch over the mesh, same info-dict contract and error
    behavior (hard-goal failure / stats-regression raised per goal in chain
    order from the stacked stats).

    ``dispatch_rounds`` > 0 selects the bounded per-goal driver instead —
    same kernels and trajectory, ≤ that many search rounds per device
    dispatch (the large-cluster watchdog mitigation of
    ``analyzer.chain.optimize_goal_in_chain``, under the mesh), driven as
    donated megastep dispatches with asynchronous stats readback when
    ``megastep`` (chain.MegastepConfig) asks for them. ``dispatch`` /
    ``dispatch_wide`` pass the optimizer's persistent per-shape
    controllers: deficit-sized count goals run wide-cost-class rounds
    and are billed to ``dispatch_wide`` so they cannot overshoot (then
    depress) the base-width budget. ``donate_input`` declares the
    caller relinquishes ``state`` (e.g. a fresh shard_cluster
    placement) so even the first dispatch may donate. ``flight`` (a
    utils.flight_recorder pass handle) records per-goal entry/exit
    violations, sizing decisions, and per-dispatch telemetry on the
    bounded path — at DISPATCH granularity: the per-round stats ring is
    single-device machinery (its reductions would need extra collectives
    under the mesh)."""
    masks = masks or ExclusionMasks()
    goals = tuple(chain)
    if not goals:
        return state, []
    presence = (masks.excluded_topics is not None,
                masks.excluded_replica_move_brokers is not None,
                masks.excluded_leadership_brokers is not None)
    if dispatch_rounds > 0:
        return _optimize_chain_sharded_bounded(
            state, goals, constraint, cfg, num_topics, mesh, masks, presence,
            swap_moves, swap_max_rounds, dispatch_rounds, dispatch_target_s,
            dispatch=dispatch, dispatch_wide=dispatch_wide,
            megastep=megastep, stats=stats, donate_input=donate_input,
            flight=flight)
    fn = _make_chain_full(mesh, goals, constraint, cfg, num_topics, presence,
                          swap_moves, swap_max_rounds)
    from ..utils.tracing import TRACER
    with TRACER.span("solver.dispatch", route="mesh") as dispatch_span:
        with TRACER.span("solver.enqueue"):
            state, stats_dev = fn(state, masks)
        with TRACER.span("solver.wait"):
            stats_dev = {k: jax.device_get(v) for k, v in stats_dev.items()}
        infos = _chain_infos_from_stats(goals, stats_dev)
        set_dispatch_rounds(dispatch_span, infos)
    return state, infos


@lru_cache(maxsize=64)
def _make_chain_phase_kernels(mesh: Mesh, goals, constraint,
                              cfg: SearchConfig, num_topics: int,
                              mask_presence: tuple[bool, bool, bool],
                              swap_moves: int, swap_max_rounds: int):
    """Per-goal sharded kernels (move pass / swap pass / stats), each ONE
    compile for the whole chain via traced (active_idx, prior_mask) — the
    bounded-dispatch counterparts of ``_make_chain_full``."""
    shards = mesh.devices.size
    rep = P()  # replicated scalars

    def move_body(state, masks, active_idx, prior_mask, budget):
        def body(carry, _r):
            st, ag = carry
            ns, nag, applied = _chain_round_local(
                st, ag, masks, active_idx, prior_mask, goals=goals,
                constraint=constraint, cfg=cfg, num_topics=num_topics,
                num_shards=shards)
            return (ns, nag), applied

        (st, _a), total, rounds = run_carry_loop(
            body, (state, compute_agg(state, num_topics, psum=_psum)),
            cfg.max_rounds, budget=budget)
        return st, total, rounds

    def swap_body(state, masks, active_idx, prior_mask, budget):
        def body(carry, _r):
            st, ag = carry
            ns, nag, applied = _chain_swap_local(
                st, ag, masks, active_idx, prior_mask, goals=goals,
                constraint=constraint, num_topics=num_topics,
                num_shards=shards, moves=swap_moves)
            return (ns, nag), applied

        (st, _a), total, rounds = run_carry_loop(
            body, (state, compute_agg(state, num_topics, psum=_psum)),
            swap_max_rounds, budget=budget)
        return st, total, rounds

    def stats_body(state, masks, active_idx):
        return _chain_stats_local(state, masks, active_idx, goals=goals,
                                  constraint=constraint,
                                  num_topics=num_topics)

    def move_body_donated(assignment, leader_slot, rest, masks, active_idx,
                          prior_mask, budget):
        state = dataclasses.replace(rest, assignment=assignment,
                                    leader_slot=leader_slot)
        st, total, rounds = move_body(state, masks, active_idx, prior_mask,
                                      budget)
        return st.assignment, st.leader_slot, total, rounds

    def swap_body_donated(assignment, leader_slot, rest, masks, active_idx,
                          prior_mask, budget):
        state = dataclasses.replace(rest, assignment=assignment,
                                    leader_slot=leader_slot)
        st, total, rounds = swap_body(state, masks, active_idx, prior_mask,
                                      budget)
        return st.assignment, st.leader_slot, total, rounds

    mask_specs = _mask_specs(mask_presence)
    part_a, part_l = mutable_state_specs()
    move = jax.jit(shard_map(
        move_body, mesh=mesh,
        in_specs=(_state_specs(), mask_specs, rep, rep, rep),
        out_specs=(_state_specs(), rep, rep), check_vma=False))
    swap = jax.jit(shard_map(
        swap_body, mesh=mesh,
        in_specs=(_state_specs(), mask_specs, rep, rep, rep),
        out_specs=(_state_specs(), rep, rep), check_vma=False))
    # Donated megastep variants (chain.chain_optimize_rounds_donated under
    # the mesh): the two mutable tensors ride as separate donated
    # arguments so XLA rewrites the sharded assignment in place — the
    # read-only remainder (strip_mutable) keeps the topology tensors out
    # of the donation set.
    move_d = jax.jit(shard_map(
        move_body_donated, mesh=mesh,
        in_specs=(part_a, part_l, _state_specs(), mask_specs, rep, rep, rep),
        out_specs=(part_a, part_l, rep, rep), check_vma=False),
        donate_argnums=(0, 1))
    swap_d = jax.jit(shard_map(
        swap_body_donated, mesh=mesh,
        in_specs=(part_a, part_l, _state_specs(), mask_specs, rep, rep, rep),
        out_specs=(part_a, part_l, rep, rep), check_vma=False),
        donate_argnums=(0, 1))
    stats = jax.jit(shard_map(
        stats_body, mesh=mesh,
        in_specs=(_state_specs(), mask_specs, rep),
        out_specs=(rep, rep, rep), check_vma=False))
    return move, swap, stats, move_d, swap_d


@lru_cache(maxsize=64)
def _make_direct_phase_kernels(mesh: Mesh, goals, index: int, constraint,
                               num_topics: int,
                               mask_presence: tuple[bool, bool, bool],
                               max_sweeps: int, margin_frac: float,
                               seed: int):
    """Sharded direct-transport kernel pair for ONE goal index. Unlike
    the move/swap kernels (traced ``active_idx`` + prior mask, one
    compile per chain), the direct sweep bodies are selected by
    TRACE-TIME Python dispatch on the goal index (``_sweep_fn`` /
    ``_guards_for`` build the guard closure from ``goals[:index]``), so
    the mesh kernel is built per-(mesh, index) — the lru_cache bounds
    the set to the direct-eligible count goals actually reached.

    The body is the SAME sweep driver as the single-device path, run
    per-shard under the interleaved rank layout: every device ranks only
    its local replica rows but occupies global fill positions
    ``local_rank * num_shards + device`` (``rank_stride``/``block``), so
    the union of per-device movers tiles each cell's surplus exactly —
    no device claims another's positions and the joint plan equals the
    single-device plan under a row permutation. Count/load caps budget
    each device ``1/num_shards`` of every band, and the returned scalars
    are psum'd global, so the while-loop predicate agrees across devices
    by construction."""
    shards = mesh.devices.size
    rep = P()

    def direct_body(state, masks):
        return _direct_rounds_driver(
            state, goals, index, constraint, num_topics, masks, max_sweeps,
            rank_stride=shards, block=jax.lax.axis_index(PARTITION_AXIS),
            psum=_psum, margin_frac=margin_frac, seed=seed)

    def direct_body_donated(assignment, leader_slot, rest, masks):
        st = dataclasses.replace(rest, assignment=assignment,
                                 leader_slot=leader_slot)
        final, total, sweeps, planned = direct_body(st, masks)
        return final.assignment, final.leader_slot, total, sweeps, planned

    mask_specs = _mask_specs(mask_presence)
    part_a, part_l = mutable_state_specs()
    direct_k = jax.jit(shard_map(
        direct_body, mesh=mesh,
        in_specs=(_state_specs(), mask_specs),
        out_specs=(_state_specs(), rep, rep, rep), check_vma=False))
    direct_d = jax.jit(shard_map(
        direct_body_donated, mesh=mesh,
        in_specs=(part_a, part_l, _state_specs(), mask_specs),
        out_specs=(part_a, part_l, rep, rep, rep), check_vma=False),
        donate_argnums=(0, 1))
    return direct_k, direct_d


def _optimize_chain_sharded_bounded(state, goals, constraint, cfg,
                                    num_topics, mesh, masks, presence,
                                    swap_moves, swap_max_rounds,
                                    dispatch_rounds: int,
                                    dispatch_target_s: float = 0.0,
                                    dispatch=None, dispatch_wide=None,
                                    megastep=None, stats=None,
                                    donate_input: bool = False,
                                    flight=None,
                                    ) -> tuple[ClusterTensors, list[dict]]:
    """Host-looped per-goal sharded driver: the trajectory of
    ``_chain_full_local`` with every device dispatch bounded — starting at
    ``dispatch_rounds`` search rounds and adaptively resized toward
    ``dispatch_target_s`` of wall-clock per dispatch (AdaptiveDispatch;
    ``dispatch`` passes the optimizer's persistent per-shape controller
    so mesh precomputes keep their learned budget across passes), pumped
    as donated megasteps with async stats readback per ``megastep``
    (analyzer.chain machinery, shared verbatim)."""
    from ..analyzer.chain import (
        AdaptiveDispatch, deficit_sized_config, direct_path_chosen,
        donation_enabled, run_bounded_pass, strip_mutable,
    )
    from ..utils.flight_recorder import _NULL_PASS
    flight = flight if flight is not None else _NULL_PASS
    controller = dispatch if dispatch is not None \
        else AdaptiveDispatch(dispatch_rounds, dispatch_target_s)
    donate = donation_enabled(megastep)
    async_rb = bool(megastep.async_readback) if megastep is not None \
        else False
    deficit_cap = megastep.deficit_moves_cap if megastep is not None else 0
    # Direct-assignment mode on the mesh (round 21): the sweep kernels
    # carry the interleaved (rank_stride, block) layout, so each device
    # ranks its LOCAL replica rows into global fill positions
    # rank·shards + device — the per-device plans tile each cell's
    # surplus instead of jointly overshooting it, and the pre-pass runs
    # here exactly as on the single-device bounded path (one dispatch,
    # kind="direct", greedy polish after).
    direct_enabled = bool(megastep is not None
                          and megastep.direct_assignment)
    direct_sweeps_cap = (int(megastep.direct_max_sweeps)
                         if megastep is not None else 16)
    direct_margin = (float(megastep.direct_sparse_margin)
                     if megastep is not None else 0.25)
    direct_seed = sparse_rounding_seed(
        megastep.direct_sparse_salt if megastep is not None else "")
    # Deficit-sized count goals run wide-cost-class rounds (sizing can
    # multiply sources/moves 10-60x), so they get their OWN controller —
    # the single-device path's narrow/wide split: a budget learned on
    # cheap base-width rounds would overshoot the dispatch target by the
    # width ratio on the first sized dispatch, then the halvings would
    # depress the base-width budget, persisted across same-shape passes.
    controller_wide = dispatch_wide if dispatch_wide is not None \
        else (AdaptiveDispatch(dispatch_rounds, dispatch_target_s)
              if deficit_cap > 0 else controller)
    per_goal = {name: [] for name in
                ("viol_before", "obj_before", "offline_before", "viol_after",
                 "obj_after", "offline_after", "moves", "swaps", "rounds")}
    base_kernels = _make_chain_phase_kernels(
        mesh, goals, constraint, cfg, num_topics, presence, swap_moves,
        swap_max_rounds)
    stats_fn = base_kernels[2]
    can_donate = [bool(donate_input)]

    def run_pass(kernels, phase, st, idx, prior, pass_cap: int, ctl,
                 goal_flight):
        move_k, _, _stats_k, move_d, _ = kernels
        # Swap kernels always come from the BASE factory result: the swap
        # bodies close over (swap_moves, swap_max_rounds) only — cfg never
        # reaches them — so a deficit-sized width must not recompile the
        # full-chain sharded swap programs.
        _, swap_k, _, _, swap_d = base_kernels

        def enqueue(st, budget: int):
            b = jnp.int32(budget)
            if donate:
                if not can_donate[0]:
                    # Caller retains the input: donate a sharding-
                    # preserving copy of the two mutable tensors (the
                    # plain-kernel fallback would compile every shard_map
                    # program twice — see chain.optimize_goal_in_chain).
                    st = dataclasses.replace(
                        st, assignment=jnp.copy(st.assignment),
                        leader_slot=jnp.copy(st.leader_slot))
                k = move_d if phase == "move" else swap_d
                a, l, applied, r = k(st.assignment, st.leader_slot,
                                     strip_mutable(st), masks, idx, prior, b)
                st = dataclasses.replace(st, assignment=a, leader_slot=l)
            else:
                k = move_k if phase == "move" else swap_k
                st, applied, r = k(st, masks, idx, prior, b)
            can_donate[0] = True
            return st, applied, r, donate, None

        return run_bounded_pass(enqueue, st, pass_cap, ctl,
                                async_readback=async_rb, stats=stats,
                                kind=phase, flight=goal_flight,
                                grid="narrow")

    def run_direct(st, g, goal_flight):
        """Direct-transport pre-pass for goal index ``g``: one sharded
        dispatch, synchronous scalar readback (nothing to pipeline
        behind a single dispatch) — the mesh twin of
        ``direct.run_direct_pass`` with the same donation discipline
        and kind="direct" stats/flight accounting."""
        import time as _time

        from ..utils.sensors import SENSORS
        direct_k, direct_d = _make_direct_phase_kernels(
            mesh, goals, g, constraint, num_topics, presence,
            direct_sweeps_cap, direct_margin, direct_seed)
        t0 = _time.monotonic()
        if donate:
            if not can_donate[0]:
                st = dataclasses.replace(
                    st, assignment=jnp.copy(st.assignment),
                    leader_slot=jnp.copy(st.leader_slot))
            a, l, total, sweeps, planned = direct_d(
                st.assignment, st.leader_slot, strip_mutable(st), masks)
            st = dataclasses.replace(st, assignment=a, leader_slot=l)
            can_donate[0] = True
        else:
            st, total, sweeps, planned = direct_k(st, masks)
        moves = int(total)
        sweeps_run = int(sweeps)
        stranded = int(planned)
        elapsed = _time.monotonic() - t0
        if stats is not None:
            stats.record("direct", sweeps_run, donated=donate)
        goal_flight.dispatch("direct", direct_sweeps_cap, sweeps_run,
                             moves, donated=donate, elapsed_s=elapsed)
        SENSORS.count("solver_direct_sweeps", sweeps_run)
        SENSORS.count("solver_direct_moves", moves)
        SENSORS.count("solver_direct_stranded", stranded)
        return st, moves, sweeps_run, stranded

    for g, goal in enumerate(goals):
        idx = jnp.int32(g)
        prior = jnp.asarray([j < g for j in range(len(goals))])
        viol0, obj0, offline0 = stats_fn(state, masks, idx)
        per_goal["viol_before"].append(float(viol0))
        per_goal["obj_before"].append(float(obj0))
        per_goal["offline_before"].append(int(offline0))
        gf = flight.goal(goal.name)
        gf.entry(violation=float(viol0), objective=float(obj0),
                 offline=int(offline0))
        # The fused kernel's per-goal fast path: zero violations + no
        # offline replicas + no drain pending = skip entirely. Drain
        # pending mirrors _chain_full_local.drain_pending — an alive
        # excluded broker STILL HOSTING replicas, not mere mask presence
        # (presence alone would run every goal on an already-drained
        # cluster that the fused path skips).
        drain = False
        if masks.excluded_replica_move_brokers is not None:
            drain = bool(excluded_hosting_replicas(
                state, masks.excluded_replica_move_brokers).any())
        ran = float(viol0) > 0 or int(offline0) > 0 or drain
        moves_total = swaps_total = rounds = 0
        # Direct-assignment pre-pass (optimize_goal_in_chain semantics):
        # enabled kernel, guard-representable chain prefix, clean model —
        # offline replicas and drains keep the full greedy trajectory.
        use_direct = (direct_enabled and int(offline0) == 0 and not drain
                      and direct_path_chosen(megastep, goal.name)
                      and direct_eligible(goals, g))
        sizing_viol = float(viol0)
        if ran and use_direct and float(viol0) > 0:
            state, d_moves, _d_sweeps, d_stranded = run_direct(state, g, gf)
            moves_total += d_moves
            # Size the greedy POLISH from the larger of two residual
            # estimates (chain.py's post-direct re-size): entry
            # violations minus applied transport moves, and 2x the
            # movers the plan wanted but feasibility refused to place.
            sizing_viol = max(float(viol0) - float(d_moves),
                              2.0 * float(d_stranded))
        # Deficit-aware sizing for count goals (chain.deficit_sized_config
        # semantics): a sized config selects its own phase kernels — the
        # lru_cached factory bounds the compile set to the pow2-quantized
        # widths actually reached.
        cfg_g = cfg
        if deficit_cap > 0 and goal.count_based:
            cfg_g = deficit_sized_config(cfg, sizing_viol, deficit_cap)
            gf.sizing(entry_violation=sizing_viol,
                      base_moves=cfg.moves_per_round,
                      base_sources=cfg.num_sources,
                      sized_moves=cfg_g.moves_per_round,
                      sized_sources=cfg_g.num_sources, cap=deficit_cap)
        gf.grid(cfg_g.num_sources, cfg_g.num_dests, cfg_g.moves_per_round)
        kernels_g = base_kernels if cfg_g is cfg else \
            _make_chain_phase_kernels(mesh, goals, constraint, cfg_g,
                                      num_topics, presence, swap_moves,
                                      swap_max_rounds)
        # Both phases of a sized count goal bill to the wide controller
        # (mirrors the single-device per-goal dispatch= routing).
        ctl_g = controller_wide if (deficit_cap > 0 and goal.count_based) \
            else controller
        if ran:
            while rounds < cfg.max_rounds:
                state, m_, r = run_pass(kernels_g, "move", state, idx,
                                        prior, cfg.max_rounds, ctl_g, gf)
                moves_total += m_
                rounds += r
                if not goal.supports_swap:
                    break
                state, sw, sr = run_pass(kernels_g, "swap", state, idx,
                                         prior, swap_max_rounds, ctl_g, gf)
                swaps_total += sw
                rounds += sr
                if sw == 0:
                    break
            viol1, obj1, offline1 = stats_fn(state, masks, idx)
        else:
            # Skipped goal: state untouched, entry stats ARE exit stats
            # (saves the second stats dispatch per idle goal).
            viol1, obj1, offline1 = viol0, obj0, offline0
        gf.exit(violation=float(viol1), objective=float(obj1),
                offline=int(offline1))
        per_goal["viol_after"].append(float(viol1))
        per_goal["obj_after"].append(float(obj1))
        per_goal["offline_after"].append(int(offline1))
        per_goal["moves"].append(moves_total)
        per_goal["swaps"].append(swaps_total)
        per_goal["rounds"].append(rounds)
    import numpy as np
    # stats_np, not stats: the DispatchStats parameter must stay visible
    # (the unbounded sibling renamed its local to stats_dev for the same
    # reason).
    stats_np = {kname: np.asarray(v) for kname, v in per_goal.items()}
    return state, _chain_infos_from_stats(goals, stats_np)
