"""Chain-shared search kernels: one compilation for the whole goal chain.

The per-goal kernels in ``search.py`` are jitted with (goal, optimized) as
STATIC arguments, so a G-goal chain compiles G move drivers and G swap
drivers, and the g-th kernel re-traces the aux + acceptance of all g-1
prior goals — compile work grows quadratically along the chain
(VERDICT round 1, "what's weak" #2).  This module recasts the chain as
THREE compilations total:

- ``chain_optimize_rounds``: the fused ``lax.while_loop`` move driver where
  the ACTIVE goal is a traced index (``lax.switch`` over per-goal scoring
  branches) and the previously-optimized set is a traced boolean mask
  gating each goal's acceptance term.  Every goal's acceptance is traced
  ONCE; per-goal aux tensors are wrapped in ``lax.cond`` so only the active
  + prior goals' aux is actually computed at runtime.
- ``chain_swap_rounds``: same treatment for the swap phase.
- ``chain_goal_stats``: post-optimization violation/objective readback.

Host drives the chain with the SAME compiled kernels for every goal:
``optimize_goal_in_chain(state, chain, i, ...)``.

Reference semantics preserved: the lexicographic acceptance stack of
AbstractGoal.maybeApplyBalancingAction:230-272 (each candidate must be
accepted by every previously-optimized goal), SURVEY.md §A.3.
"""

from __future__ import annotations

import dataclasses
import time as _time
from functools import partial
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from ..model.tensors import ClusterTensors, offline_replicas
from .agg import (
    AggCarry, apply_deltas_to_agg, compute_agg, maybe_refresh, pot_lbi_deltas,
)
from .candidates import (
    CandidateDeltas, Candidates, compute_deltas, generate_candidates,
    flat_topk, select_sources, source_select,
)
from .fill import targets_enabled
from .constraint import BalancingConstraint
from .derived import DerivedState, compute_derived, healing
from .goals.base import Goal
from .search import (
    _EPS_IMPROVEMENT, _OFFLINE_BONUS, ExclusionMasks,
    OptimizationFailureError, SearchConfig, apply_selected,
    apply_swap_selection, cumulative_select, goal_aux, reduce_per_source,
    prior_card_dest_ok, run_carry_loop, swap_grid,
)
from ..utils.flight_recorder import NO_FLIGHT, STAT_WIDTH as _FLIGHT_STATS
from ..utils.tracing import TRACER


def _gated_aux(needed: jax.Array, goal: Goal, state, derived, constraint,
               num_topics: int, psum=None, agg=None):
    """Compute ``goal``'s aux pytree only when ``needed`` (traced bool) —
    zeros otherwise. Keeps the single chain kernel from paying every goal's
    O(P) aux reductions on every round. ``psum`` combines partition-additive
    aux partials across a mesh (the collective runs in BOTH branches — a
    ``lax.cond`` whose branches disagree on collectives would deadlock, and
    psum of the zero pytree is free). With an ``agg`` carry, agg-backed
    goals read their (already-global) partial from it — collective-free, so
    the whole aux is safely gated even under a mesh."""
    if agg is not None and goal.partial_from_agg(agg) is not None:
        def compute_from_agg(_):
            return goal.finalize_aux(goal.partial_from_agg(agg), state,
                                     derived, constraint)

        shapes = jax.eval_shape(compute_from_agg, 0)
        if not jax.tree_util.tree_leaves(shapes):
            return compute_from_agg(0)

        def zeros_from_agg(_):
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

        return jax.lax.cond(needed, compute_from_agg, zeros_from_agg, 0)

    def compute(_):
        return goal_aux(goal, state, derived, constraint, num_topics, psum)

    shapes = jax.eval_shape(compute, 0)
    if not jax.tree_util.tree_leaves(shapes):
        return compute(0)  # aux is None/empty: nothing to gate

    def zeros(_):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    if psum is None:
        return jax.lax.cond(needed, compute, zeros, 0)
    # Under a mesh the psum must execute unconditionally on every device
    # (a cond whose branches disagree on collectives would mismatch), but
    # the O(P) LOCAL partial is still gated: lax.cond around
    # prepare_partial (collective-free), psum of the (possibly zero)
    # result outside.
    partial_aux = goal.prepare_partial(state, num_topics)
    if partial_aux is not None:
        def compute_partial(_):
            return goal.prepare_partial(state, num_topics)

        def zero_partial(_):
            return jax.tree.map(jnp.zeros_like, partial_aux)

        partial_aux = jax.lax.cond(needed, compute_partial, zero_partial, 0)
        partial_aux = jax.tree.map(psum, partial_aux)
    return goal.finalize_aux(partial_aux, state, derived, constraint)


def excluded_hosting_replicas(state: ClusterTensors,
                              excluded_replica_move_brokers: jax.Array,
                              ) -> jax.Array:
    """[P, S] bool: replica sits on an ALIVE excluded-for-replica-move
    broker. Any() of this is "drain pending" — goals must keep running to
    shed replicas off excluded brokers even with zero violations
    (requireLessLoad includes excluded brokers,
    ResourceDistributionGoal.java:387). Shared by the fused and
    bounded-dispatch drivers on both the single-device and sharded paths
    so their per-goal fast-path skip conditions cannot diverge."""
    from ..model.tensors import alive_mask
    excl_alive = excluded_replica_move_brokers & alive_mask(state)
    b = state.num_brokers
    seg = jnp.where(state.assignment >= 0, state.assignment, b)
    return jnp.concatenate([excl_alive, jnp.array([False])])[seg]


def _goal_flags(goals: tuple[Goal, ...]):
    lead_only = jnp.asarray([g.leadership_only for g in goals])
    incl_lead = jnp.asarray([g.include_leadership or g.leadership_only
                             for g in goals])
    indep = jnp.asarray([g.independent_per_broker for g in goals])
    return lead_only, incl_lead, indep


def _switch_goal_fn(active_idx, goals, fn):
    """``lax.switch`` over the goal index: run ``fn(goal, i)`` for the
    ACTIVE goal only (all branches traced once, one executed). The shared
    scaffolding for every per-goal dispatch in the chain kernels."""
    def branch(i):
        def run(_):
            return fn(goals[i], i)
        return run

    return jax.lax.switch(active_idx, [branch(i) for i in range(len(goals))], 0)


def _switch_scores(active_idx, goals, aux_list, state, derived, constraint):
    """(src_score[B], dst_score[B], weight[P,S]) of the active goal."""
    return _switch_goal_fn(
        active_idx, goals,
        lambda g, i: (g.source_score(state, derived, constraint, aux_list[i])
                      .astype(jnp.float32),
                      g.dest_score(state, derived, constraint, aux_list[i])
                      .astype(jnp.float32),
                      g.replica_weight(state, derived, constraint,
                                       aux_list[i]).astype(jnp.float32)))


def _chain_scores(state, derived, active_idx, prior_mask, goals, constraint,
                  num_topics, agg, psum=None):
    """(is_active [G], aux_list, src_score [B], dst_score [B], weight [P, S])
    of the active goal: every goal's aux gated to the active and prior
    goals, then the active goal's scores. Shared by the move round's
    scoring half and the swap bodies. Under a mesh (``psum``) the sum of
    partition-additive source scores runs unconditionally
    (collective-safety) and is selected by a traced flag."""
    is_active = jnp.arange(len(goals)) == active_idx
    aux_list = [_gated_aux(prior_mask[i] | is_active[i], g, state, derived,
                           constraint, num_topics, psum=psum, agg=agg)
                for i, g in enumerate(goals)]
    src_score, dst_score, weight = _switch_scores(
        active_idx, goals, aux_list, state, derived, constraint)
    if psum is not None:
        additive_f = jnp.asarray([g.partition_additive_scores for g in goals])
        src_score = jnp.where(additive_f[active_idx], psum(src_score),
                              src_score)
    return is_active, aux_list, src_score, dst_score, weight


def _switch_swap_dest_score(active_idx, goals, aux_list, state, derived,
                            constraint):
    """[B] swap counterparty score of the active goal (shared by the
    single-device and sharded swap bodies)."""
    return _switch_goal_fn(
        active_idx, goals,
        lambda g, i: g.swap_dest_score(state, derived, constraint,
                                       aux_list[i]).astype(jnp.float32))


def _switch_swap_light_weight(active_idx, goals, aux_list, state, derived,
                              constraint):
    """[P, S] what a replica weighs in a swap under the active goal
    (``Goal.swap_light_weight``; shared by the single-device and sharded
    swap bodies, so the light side is ONE decision on every route)."""
    return _switch_goal_fn(
        active_idx, goals,
        lambda g, i: g.swap_light_weight(state, derived, constraint,
                                         aux_list[i]).astype(jnp.float32))


def _switch_target_dests(active_idx, goals, aux_list, state, derived,
                         constraint, cand_p, cand_s, src_valid):
    """The active goal's targeted-destination column (Goal.target_dests,
    analyzer.fill) — goals without a rule contribute an all-invalid
    column so every branch returns the same shapes."""

    def branch(i):
        g = goals[i]

        def fn(_):
            td = g.target_dests(state, derived, constraint, aux_list[i],
                                cand_p, cand_s, src_valid)
            if td is None:
                return (jnp.zeros_like(cand_p),
                        jnp.zeros(cand_p.shape, dtype=bool))
            return td[0].astype(jnp.int32), td[1]
        return fn

    return jax.lax.switch(active_idx, [branch(i) for i in range(len(goals))], 0)


# How the move-round body last traced in this process looks tables up for
# its candidates: "grid" (on the candidate grid's margins, CandidateGrid)
# or "flat" (one gather a candidate). The form is fixed when a program is
# traced, so it is written then, and the dispatch spans report it.
_accept_lookup_traced: str | None = None


def accept_lookup() -> str | None:
    """The table-lookup form of the last traced move-round body (None
    before any trace): the ``accept_lookup`` attribute of the
    ``solver.dispatch`` spans. One observable, ``deltas.grid``, decides
    both halves: with ``"grid"`` the goals' lookups (``round.accept``)
    AND the deltas themselves (``round.deltas``, PR 35) are built on the
    grid's margins; with ``"flat"`` both gather once a candidate."""
    return _accept_lookup_traced


def _set_traced_forms(dispatch, kind: str = "move") -> None:
    """The traced forms of the move-round body onto a dispatch span:
    ``accept_lookup``, ``source_select`` (candidates.source_select: how
    the source selection reduces the flat replica axis per broker) and
    ``flat_topk`` (candidates.flat_topk: how the round's top-k's of the
    whole flat replica axis were taken)."""
    if kind != "move":
        return
    if _accept_lookup_traced is not None:
        dispatch.set(accept_lookup=_accept_lookup_traced)
    if source_select() is not None:
        dispatch.set(source_select=source_select())
    if flat_topk() is not None:
        dispatch.set(flat_topk=flat_topk())


class ScoredCandidates(NamedTuple):
    """What ``_scored_candidates`` hands to a round's selection."""
    derived: DerivedState
    aux_list: list            # per goal; zeros unless active or prior
    cand: Candidates
    layout: tuple             # (rows, cols) of the move, the leadership block
    deltas: CandidateDeltas   # with their CandidateGrid
    accept: jax.Array         # [N] valid and accepted by every prior goal
    score: jax.Array          # [N] f32 improvement, -inf unless accepted
    is_active: jax.Array      # [G]
    independent: jax.Array    # the active goal lifts the per-round move cap
    targets: bool             # static: the move block's last column is targeted
    heals: jax.Array          # scalar: the round took the healing branch
    source_fallback: jax.Array  # scalar: select_sources reduced every row


def _self_healing(state: ClusterTensors, derived: DerivedState,
                  src_score: jax.Array, weight: jax.Array,
                  is_lead_only: jax.Array,
                  ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The self-healing priority (ClusterModel.selfHealingEligibleReplicas):
    replicas stranded on dead brokers are always sources with maximal
    weight for non-leadership goals; moving one scores a bonus in
    ``_scored_candidates`` so it wins over pure balance refinements.
    Returns (src_score, weight, offline, heals): ``offline`` is
    ``derived.healing`` (a replica is offline), ``heals`` whether this
    round took the branch that builds the per-slot mask.

    A replica is offline exactly when its broker is DEAD, so the source
    term per broker is the dead brokers' replica counts, which the carry
    already holds (global on a mesh: no collective here). The [P, S] mask
    is built only while a replica is offline (docs/DESIGN.md "The move
    round"); neither branch of the ``cond`` holds a collective, and its
    predicate is replicated on the mesh, so every device takes the same
    branch."""
    offline = healing(derived)
    heals = offline & ~is_lead_only
    offline_pb = jnp.where(derived.alive, 0.0,
                           derived.broker_replicas.astype(jnp.float32))
    src_score = src_score + jnp.where(is_lead_only, 0.0, offline_pb)
    weight = jax.lax.cond(
        heals, lambda w: jnp.where(offline_replicas(state), 1e30, w),
        lambda w: w, weight)
    return src_score, weight, offline, heals


def _scored_candidates(state: ClusterTensors, agg: "AggCarry | None",
                       active_idx: jax.Array, prior_mask: jax.Array,
                       goals: tuple[Goal, ...],
                       constraint: BalancingConstraint, cfg: SearchConfig,
                       num_topics: int, masks: ExclusionMasks, *,
                       global_partitions: int, psum=None,
                       batched: bool = False,
                       ) -> ScoredCandidates:
    """The scoring half of one move round, written ONCE for every route
    (docs/DESIGN.md "The move round"): derived state -> the goals' aux ->
    source / destination scores -> the self-healing priority -> sources ->
    candidate grid -> deltas -> the acceptance stack under ``prior_mask``
    -> the active goal's improvement -> score. ``_chain_round_body`` (one
    chip, and under ``vmap`` the megabatch) and
    ``parallel.chain_sharded._chain_round_local`` (the mesh) call it and
    differ only in the selection that follows.

    ``psum`` is the mesh, passed the way ``compute_derived`` and
    ``_gated_aux`` take it: None on one chip; under ``shard_map`` it sums
    over the partition axis, ``state`` holds this device's partition rows
    and ``global_partitions`` (``state.num_partitions`` on one chip) is
    the mesh's total. Every collective it adds runs unconditionally
    (a ``cond`` whose branches disagree on collectives would deadlock).
    ``batched``: the body runs under ``vmap`` (the megabatch), where a
    ``cond`` on a per-cluster predicate runs both branches, so the source
    selection keeps its one full reduction (``select_sources``).

    The phases carry stable ``jax.named_scope`` names (``round.score``
    and inside it ``round.score_derived``, ``round.score_goals``,
    ``round.score_offline``; ``round.source_topk``, ``round.candidates``,
    ``round.deltas``, ``round.accept``), some here and some inside the
    helpers, so a device profile names them on every route. Metadata
    only: the lowered computation is the same."""
    lead_only_f, incl_lead_f, indep_f = _goal_flags(goals)
    is_lead_only = lead_only_f[active_idx]
    has_leadership = incl_lead_f[active_idx]

    with jax.named_scope("round.score"):
        with jax.named_scope("round.score_derived"):
            derived = compute_derived(state, masks.excluded_topics,
                                      masks.excluded_replica_move_brokers,
                                      masks.excluded_leadership_brokers,
                                      psum=psum, agg=agg)
        with jax.named_scope("round.score_goals"):
            is_active, aux_list, src_score, dst_score, weight = \
                _chain_scores(state, derived, active_idx, prior_mask, goals,
                              constraint, num_topics, agg, psum=psum)

        with jax.named_scope("round.score_offline"):
            src_score, weight, offline, heals = _self_healing(
                state, derived, src_score, weight, is_lead_only)

    # UNIFORM grid layout: both the move and the leadership block always
    # exist (static shapes shared by every goal); the active goal's traced
    # flags mask out the block it doesn't use. The targeted-destination
    # column (Goal.target_dests) rides the move block: it is made from the
    # cards, so the sources are selected first and handed on. Its scale
    # gate reads the GLOBAL partition count (the threshold's measured
    # meaning is cluster scale), and it runs on one shard only: card fill
    # ranks are device-local against a replicated profile (docs/DESIGN.md
    # "Known limits").
    targets = targets_enabled(global_partitions) \
        and global_partitions == state.num_partitions
    extra = None
    sources = select_sources(state, src_score, weight, cfg.num_sources,
                             batched=batched)
    if targets:
        cand_p, cand_s, src_valid, _on_source, _fallback = sources
        # Targets pause while ANY offline replica exists, anywhere on the
        # mesh (traced scalar): targeted steering during a drain locks in
        # placements later goals cannot repair (1k drain-50: balancedness
        # 86.0 -> 82.74 with CpuUsage violated). Self-healing and the
        # drain's rebalance keep the r4 full-grid semantics; targets
        # resume once healing completes.
        t_dst, t_ok = _switch_target_dests(active_idx, goals, aux_list,
                                           state, derived, constraint,
                                           cand_p, cand_s, src_valid)
        extra = (t_dst, t_ok & ~offline)
    cand, layout = generate_candidates(state, derived, src_score, dst_score,
                                       weight, cfg.num_sources, cfg.num_dests,
                                       include_leadership=True,
                                       leadership_only=False,
                                       extra_dst=extra, sources=sources)
    (r0, c0), (r1, c1) = layout
    block_ok = jnp.concatenate([
        jnp.broadcast_to(~is_lead_only, (r0 * c0,)),
        jnp.broadcast_to(has_leadership, (r1 * c1,)),
    ])
    cand = dataclasses.replace(cand, valid=cand.valid & block_ok)
    deltas = compute_deltas(state, derived, cand, layout)
    global _accept_lookup_traced
    _accept_lookup_traced = "flat" if deltas.grid is None else "grid"

    def imp_branch(i):
        g = goals[i]

        def fn(_):
            return g.improvement(state, derived, constraint, aux_list[i],
                                 deltas).astype(jnp.float32)
        return fn

    with jax.named_scope("round.accept"):
        accept = deltas.valid
        for i, g in enumerate(goals):
            accept &= (~prior_mask[i]) | g.acceptance(
                state, derived, constraint, aux_list[i], deltas)

        moving_offline = deltas.src_offline & (deltas.replica_delta > 0)

        imp = jax.lax.switch(active_idx,
                             [imp_branch(i) for i in range(len(goals))], 0)
        imp = jnp.where(moving_offline & jnp.isfinite(imp) & deltas.valid,
                        jnp.maximum(imp, 0.0) + _OFFLINE_BONUS, imp)
        score = jnp.where(accept, imp, -jnp.inf)

    independent = indep_f[active_idx] & ~prior_mask.any()
    return ScoredCandidates(derived, aux_list, cand, layout, deltas, accept,
                            score, is_active, independent, targets, heals,
                            sources[4])


def _chain_round_body(state: ClusterTensors, agg: "AggCarry | None",
                      active_idx: jax.Array,
                      prior_mask: jax.Array, goals: tuple[Goal, ...],
                      constraint: BalancingConstraint, cfg: SearchConfig,
                      num_topics: int, masks: ExclusionMasks,
                      stats: "str | None" = None, batched: bool = False,
                      ) -> tuple[ClusterTensors, "AggCarry | None",
                                 jax.Array, "jax.Array | None", jax.Array]:
    """One search round, chain-parameterized (traced body): the scoring
    half (``_scored_candidates``), then the one-chip selection. ``agg`` is
    the incrementally-maintained aggregate carry (analyzer.agg): the round
    reads its per-broker aggregates from it instead of O(P·S) segment-sums
    and returns it updated by the applied batch (None = recompute-per-round,
    kept for the oracle paths).

    ``stats`` (trace-time): ``"row"`` additionally returns a
    ``[STAT_WIDTH]`` f32 flight-stats row for this round (utils.flight_recorder.STAT_COLUMNS:
    applied / valid / accepted / positive / winners / active-goal
    violation) — pure REDUCTIONS over tensors the round already computes
    (the duplicated ``reduce_per_source`` is structurally identical to
    the one inside ``cumulative_select``, so XLA CSE collapses the two),
    never a new selection input: the trajectory is byte-identical with
    collection on or off (pinned in tests/test_flight_recorder.py).
    ``"tally"`` (the whole-chain dispatch, which keeps no ring) returns in
    its place the row's two sums that price the acceptance stack,
    whether the round took the self-healing branch and whether its source
    selection reduced every broker's row,
    ``[valid, accepted, heals, source_fallback]``: two reductions over the
    candidate axis and two scalars. The last output is the pair of those
    two scalars ``(heals, source_fallback)`` (bools) under every
    ``stats``: the bounded route sums both into its ``PassCarry``.

    The selection's phases carry the scopes ``round.select``,
    ``round.apply`` and ``round.flight_stats`` (``round.agg_refresh`` in
    the drivers), after the scoring half's."""
    sc = _scored_candidates(state, agg, active_idx, prior_mask, goals,
                            constraint, cfg, num_topics, masks,
                            global_partitions=state.num_partitions,
                            batched=batched)
    derived, aux_list, deltas, score = \
        sc.derived, sc.aux_list, sc.deltas, sc.score
    m = max(cfg.moves_per_round, cfg.num_sources)

    def recheck(sub, has_earlier):
        """Joint acceptance with cumulative pre-deltas (cumulative_select):
        prior goals gated by the traced prior mask; the ACTIVE goal guards
        its own band for interacting candidates."""
        a = jnp.ones(sub.valid.shape[0], dtype=bool)
        for i, g in enumerate(goals):
            g_acc = g.acceptance(state, derived, constraint, aux_list[i], sub)
            a &= (~prior_mask[i]) | g_acc
            a &= (~sc.is_active[i]) | (~has_earlier) | g_acc
        return a

    top_idx, sel, sub, pot_d, lbi_d = cumulative_select(
        state, deltas, score, sc.layout, m, cfg.moves_per_round,
        sc.independent, recheck, extra_last_col=sc.targets)
    if agg is not None:
        with jax.named_scope("round.apply"):
            agg = apply_deltas_to_agg(agg, sub, sel, pot_d, lbi_d)
    # apply_selected carries the round.apply scope itself (every driver
    # calls it)
    new_state = apply_selected(
        state, sel, deltas.partition[top_idx], deltas.src_slot[top_idx],
        deltas.dst_broker[top_idx], sc.cand.kind[top_idx],
        sc.cand.dst_slot[top_idx])
    applied = sel.sum()
    assert stats in (None, "row", "tally"), stats
    stat = None
    if stats == "row":
        with jax.named_scope("round.flight_stats"):
            red_idx = reduce_per_source(score, sc.layout,
                                        extra_last_col=sc.targets)
            viol = _switch_goal_fn(
                active_idx, goals,
                lambda g, i: g.broker_violations(
                    state, derived, constraint, aux_list[i]).sum()
                .astype(jnp.float32))
            stat = jnp.stack([
                applied.astype(jnp.float32),
                deltas.valid.sum().astype(jnp.float32),
                sc.accept.sum().astype(jnp.float32),
                (score > _EPS_IMPROVEMENT).sum().astype(jnp.float32),
                (score[red_idx] > _EPS_IMPROVEMENT).sum()
                .astype(jnp.float32),
                viol,
            ])
        assert stat.shape == (_FLIGHT_STATS,)
    elif stats == "tally":
        with jax.named_scope("round.flight_stats"):
            stat = jnp.stack([deltas.valid.sum().astype(jnp.float32),
                              sc.accept.sum().astype(jnp.float32),
                              sc.heals.astype(jnp.float32),
                              sc.source_fallback.astype(jnp.float32)])
    return new_state, agg, applied, stat, (sc.heals, sc.source_fallback)


@partial(jax.tree_util.register_dataclass,
         data_fields=["agg", "rounds", "last", "source_fallbacks",
                      "healing_rounds"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class PassCarry:
    """What a bounded dispatch hands the next dispatch of the SAME pass, so
    that a pass split at any dispatch boundaries walks the unsplit loop's
    trajectory: the incrementally maintained aggregates (a fresh
    recompute at each dispatch's entry would drop the f32 drift the
    unsplit loop carries, and which rounds read fresh aggregates would
    then follow the boundaries, which the adaptive controller sets by
    wall-clock), the rounds the pass has run (the refresh cadence counts
    them) and the applied count of its last round (a pass at its fixed
    point runs no further round, so the pump's speculative successor
    runs none), and two tallies of the pass's move rounds: those whose
    source selection reduced every broker's row (``select_sources``'
    fallback) and those that built the per-slot offline mask
    (``_self_healing``); the pump reads both once the pass's last dispatch
    is read. Device scalars, chained like the state: no readback."""

    agg: AggCarry
    rounds: jax.Array       # i32: rounds the pass ran before this dispatch
    last: jax.Array         # i32: the last round's applied count
    source_fallbacks: jax.Array  # i32: fallback rounds of the pass so far
    healing_rounds: jax.Array    # i32: healing rounds of the pass so far


def _pass_start(state: ClusterTensors, num_topics: int) -> PassCarry:
    """The carry a pass starts from: aggregates computed afresh, no
    round run yet."""
    return PassCarry(compute_agg(state, num_topics), jnp.int32(0),
                     jnp.int32(1), jnp.int32(0), jnp.int32(0))


@partial(jax.jit, static_argnames=("num_topics",))
def start_pass(state: ClusterTensors, num_topics: int) -> PassCarry:
    """``_pass_start`` as its own program: the bounded route's pump makes
    one a pass and chains it through the pass's dispatches."""
    return _pass_start(state, num_topics)


def _chain_rounds_driver(state: ClusterTensors, active_idx: jax.Array,
                         prior_mask: jax.Array, goals: tuple[Goal, ...],
                         constraint: BalancingConstraint, cfg: SearchConfig,
                         num_topics: int, masks: ExclusionMasks,
                         budget: jax.Array | None = None,
                         ring_rounds: int = 0,
                         resume: PassCarry | None = None,
                         ) -> tuple[ClusterTensors, jax.Array, jax.Array,
                                    "jax.Array | None", PassCarry]:
    """Traced body of the fused move driver — the MEGASTEP: up to
    ``budget`` round-bodies under one ``lax.while_loop`` whose carry is
    ``((state, agg), moves, rounds, last_applied)`` with ``last_applied``
    as the on-device early-exit flag (a zero-apply round freezes the state
    and ends the loop — no host involvement). Shared by the plain and the
    donated jits below.

    ``ring_rounds`` > 0 (trace-time, the flight recorder's knob) adds a
    ``[ring_rounds, STAT_WIDTH]`` f32 ring to the carry: each round
    writes its flight-stats row at ``round % ring_rounds``, and the ring
    rides the dispatch's existing async readback (one more output
    tensor, ~3 KB at the default length — no extra host round-trip).

    ``resume`` (the bounded route) continues a pass from the previous
    dispatch's ``PassCarry`` instead of starting one: the aggregates are
    taken over, the refresh cadence counts the pass's rounds, and a pass
    already at its fixed point runs no round.
    Returns (final_state, total_moves, rounds_run, ring-or-None,
    PassCarry for the pass's next dispatch)."""
    collect = ring_rounds > 0
    if resume is None:
        resume = _pass_start(state, num_topics)

    def body(carry, rounds_done):
        if collect:
            s, a, fb, hl, ring = carry
        else:
            s, a, fb, hl = carry
        a = maybe_refresh(a, s, num_topics, resume.rounds + rounds_done)
        ns, na, applied, stat, (heals, fallback) = _chain_round_body(
            s, a, active_idx, prior_mask, goals, constraint, cfg,
            num_topics, masks, stats="row" if collect else None)
        fb = fb + fallback.astype(jnp.int32)
        hl = hl + heals.astype(jnp.int32)
        if collect:
            ring = ring.at[rounds_done % ring_rounds].set(stat)
            return (ns, na, fb, hl, ring), applied
        return (ns, na, fb, hl), applied

    carry0 = (state, resume.agg, resume.source_fallbacks,
              resume.healing_rounds)
    if collect:
        carry0 = carry0 + (jnp.zeros((ring_rounds, _FLIGHT_STATS),
                                     jnp.float32),)
    final_carry, total, rounds, last = run_carry_loop(
        body, carry0, cfg.max_rounds, budget=budget, last0=resume.last)
    ring = final_carry[4] if collect else None
    return (final_carry[0], total, rounds, ring,
            PassCarry(final_carry[1], resume.rounds + rounds, last,
                      final_carry[2], final_carry[3]))


@partial(jax.jit, static_argnames=("goals", "constraint", "cfg", "num_topics",
                                   "ring_rounds"))
def chain_optimize_rounds(state: ClusterTensors, active_idx: jax.Array,
                          prior_mask: jax.Array, goals: tuple[Goal, ...],
                          constraint: BalancingConstraint, cfg: SearchConfig,
                          num_topics: int, masks: ExclusionMasks,
                          budget: jax.Array | None = None,
                          ring_rounds: int = 0,
                          resume: PassCarry | None = None):
    """Fused multi-round driver for ANY goal in the chain: one compilation
    serves all G (active_idx, prior_mask) combinations. Returns
    (final_state, total_moves, rounds_run). ``budget`` (traced) further
    caps rounds without recompiling (bounded-dispatch path).

    ``ring_rounds`` > 0 (static — the flight recorder's ON switch, one
    extra compilation per process when enabled) appends the per-round
    flight-stats ring as a FOURTH output; 0 keeps the 3-tuple contract.
    ``resume`` (the bounded route's ``PassCarry``) appends the carry for
    the pass's next dispatch as the LAST output.

    Aggregates are computed once at entry and maintained incrementally
    through the loop (analyzer.agg), with a periodic fresh recompute to
    bound f32 drift."""
    final, total, rounds, ring, carry = _chain_rounds_driver(
        state, active_idx, prior_mask, goals, constraint, cfg, num_topics,
        masks, budget, ring_rounds=ring_rounds, resume=resume)
    out = (final, total, rounds) + ((ring,) if ring_rounds > 0 else ())
    return out + ((carry,) if resume is not None else ())


def strip_mutable(state: ClusterTensors) -> ClusterTensors:
    """The read-only remainder of a split state: ``assignment`` and
    ``leader_slot`` replaced by 0-row placeholders. The donated megastep
    kernels take the two mutable tensors as SEPARATE donated arguments —
    donating the whole pytree would also consume the topology tensors
    (topic/rack/capacity/...), which the incremental model pipeline
    (model/refresh.py) shares across generations from its topology cache;
    a donated shared buffer is deleted under the cache's feet."""
    s = state.max_replication_factor
    return dataclasses.replace(
        state,
        assignment=jnp.zeros((0, s), state.assignment.dtype),
        leader_slot=jnp.zeros((0,), state.leader_slot.dtype))


@partial(jax.jit, static_argnames=("goals", "constraint", "cfg",
                                   "num_topics", "ring_rounds"),
         donate_argnums=(0, 1, 2))
def chain_optimize_rounds_donated(assignment: jax.Array,
                                  leader_slot: jax.Array,
                                  resume: PassCarry,
                                  rest: ClusterTensors,
                                  active_idx: jax.Array,
                                  prior_mask: jax.Array,
                                  goals: tuple[Goal, ...],
                                  constraint: BalancingConstraint,
                                  cfg: SearchConfig, num_topics: int,
                                  masks: ExclusionMasks, budget: jax.Array,
                                  ring_rounds: int = 0):
    """The donated megastep of the bounded route: the trace of
    ``chain_optimize_rounds`` resuming ``resume``, with the two mutable
    tensors and the pass's carry donated, so XLA writes the new
    assignment into the old buffers instead of allocating a fresh
    generation per dispatch. Callers pass ``strip_mutable(state)`` as
    ``rest`` and must not touch the donated arrays afterwards. Returns
    (assignment, leader_slot, moves, rounds, PassCarry) — with the
    flight-stats ring before the carry when ``ring_rounds`` > 0
    (chain_optimize_rounds; the ring is loop-created, never part of the
    donation set)."""
    state = dataclasses.replace(rest, assignment=assignment,
                                leader_slot=leader_slot)
    final, total, rounds, ring, carry = _chain_rounds_driver(
        state, active_idx, prior_mask, goals, constraint, cfg, num_topics,
        masks, budget, ring_rounds=ring_rounds, resume=resume)
    out = (final.assignment, final.leader_slot, total, rounds)
    return out + ((ring,) if ring_rounds > 0 else ()) + (carry,)


@jax.named_scope("swap.round")
def _chain_swap_body(state: ClusterTensors, agg: "AggCarry | None",
                     active_idx: jax.Array,
                     prior_mask: jax.Array, goals: tuple[Goal, ...],
                     constraint: BalancingConstraint, num_topics: int,
                     masks: ExclusionMasks, moves: int = 8,
                     ) -> tuple[ClusterTensors, "AggCarry | None", jax.Array]:
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers, agg=agg)
    _is_active, aux_list, src_score, _dst_score, weight = _chain_scores(
        state, derived, active_idx, prior_mask, goals, constraint,
        num_topics, agg)
    dst_score = _switch_swap_dest_score(active_idx, goals, aux_list, state,
                                        derived, constraint)

    light_weight = _switch_swap_light_weight(active_idx, goals, aux_list,
                                             state, derived, constraint)

    fwd, rev, net, p1, s1, p2, s2, src_b, dst_b, base_valid = swap_grid(
        state, derived, src_score, dst_score, weight, light_weight,
        partial(prior_card_dest_ok, goals, prior_mask, state))

    accept = base_valid
    for i, g in enumerate(goals):
        accept &= (~prior_mask[i]) | g.swap_acceptance(
            state, derived, constraint, aux_list[i], fwd, rev, net)

    def imp_branch(i):
        g = goals[i]

        def fn(_):
            return g.swap_improvement(state, derived, constraint,
                                      aux_list[i], fwd, rev,
                                      net).astype(jnp.float32)
        return fn

    imp = jax.lax.switch(active_idx,
                         [imp_branch(i) for i in range(len(goals))], 0)
    score = jnp.where(accept, imp, -jnp.inf)
    new_state, applied, top_idx, sel = apply_swap_selection(
        state, score, p1, s1, p2, s2, src_b, dst_b, moves)
    if agg is not None:
        # Both directional legs of every accepted swap scatter onto the
        # carry (replica + load + leadership travel per leg).
        for leg in (fwd, rev):
            leg_sub = jax.tree.map(lambda a: a[top_idx], leg)
            pot_d, lbi_d = pot_lbi_deltas(state, leg_sub)
            agg = apply_deltas_to_agg(agg, leg_sub, sel, pot_d, lbi_d)
    return new_state, agg, applied


def _chain_swap_driver(state: ClusterTensors, active_idx: jax.Array,
                       prior_mask: jax.Array, goals: tuple[Goal, ...],
                       constraint: BalancingConstraint, num_topics: int,
                       masks: ExclusionMasks, moves: int = 8,
                       max_rounds: int = 64,
                       budget: jax.Array | None = None,
                       resume: PassCarry | None = None,
                       ) -> tuple[ClusterTensors, jax.Array, jax.Array,
                                  PassCarry]:
    """The swap twin of ``_chain_rounds_driver`` (``resume`` likewise)."""
    if resume is None:
        resume = _pass_start(state, num_topics)

    def body(carry, rounds_done):
        s, a = carry
        a = maybe_refresh(a, s, num_topics, resume.rounds + rounds_done)
        ns, na, applied = _chain_swap_body(s, a, active_idx, prior_mask,
                                           goals, constraint, num_topics,
                                           masks, moves)
        return (ns, na), applied

    (final, agg), total, rounds, last = run_carry_loop(
        body, (state, resume.agg), max_rounds, budget=budget,
        last0=resume.last)
    return final, total, rounds, dataclasses.replace(
        resume, agg=agg, rounds=resume.rounds + rounds, last=last)


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics",
                                   "moves", "max_rounds"))
def chain_swap_rounds(state: ClusterTensors, active_idx: jax.Array,
                      prior_mask: jax.Array, goals: tuple[Goal, ...],
                      constraint: BalancingConstraint, num_topics: int,
                      masks: ExclusionMasks, moves: int = 8,
                      max_rounds: int = 64,
                      budget: jax.Array | None = None,
                      resume: PassCarry | None = None):
    """Fused swap-phase driver, chain-parameterized (incremental-aggregate
    carry, as chain_optimize_rounds): (final_state, swaps, rounds), and
    the pass's next ``PassCarry`` last with ``resume``."""
    final, total, rounds, carry = _chain_swap_driver(
        state, active_idx, prior_mask, goals, constraint, num_topics, masks,
        moves, max_rounds, budget, resume)
    out = (final, total, rounds)
    return out + ((carry,) if resume is not None else ())


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics",
                                   "moves", "max_rounds"),
         donate_argnums=(0, 1, 2))
def chain_swap_rounds_donated(assignment: jax.Array, leader_slot: jax.Array,
                              resume: PassCarry,
                              rest: ClusterTensors, active_idx: jax.Array,
                              prior_mask: jax.Array, goals: tuple[Goal, ...],
                              constraint: BalancingConstraint,
                              num_topics: int, masks: ExclusionMasks,
                              moves: int, max_rounds: int,
                              budget: jax.Array,
                              ) -> tuple[jax.Array, jax.Array, jax.Array,
                                         jax.Array, PassCarry]:
    """Donated swap megastep (see chain_optimize_rounds_donated)."""
    state = dataclasses.replace(rest, assignment=assignment,
                                leader_slot=leader_slot)
    final, total, rounds, carry = _chain_swap_driver(
        state, active_idx, prior_mask, goals, constraint, num_topics, masks,
        moves, max_rounds, budget, resume)
    return final.assignment, final.leader_slot, total, rounds, carry


@jax.named_scope("goal.stats")
def _chain_goal_stats_body(state: ClusterTensors, active_idx: jax.Array,
                           goals: tuple[Goal, ...],
                           constraint: BalancingConstraint, num_topics: int,
                           masks: ExclusionMasks,
                           ) -> tuple[jax.Array, jax.Array, jax.Array]:
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)

    def branch(i):
        g = goals[i]

        def fn(_):
            aux = goal_aux(g, state, derived, constraint, num_topics)
            viol = g.broker_violations(state, derived, constraint, aux)
            obj = g.objective(state, derived, constraint, aux)
            return (viol.sum().astype(jnp.float32),
                    obj.astype(jnp.float32))
        return fn

    viol, obj = jax.lax.switch(active_idx,
                               [branch(i) for i in range(len(goals))], 0)
    return viol, obj, offline_replicas(state).sum()


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics"))
def chain_goal_stats(state: ClusterTensors, active_idx: jax.Array,
                     goals: tuple[Goal, ...],
                     constraint: BalancingConstraint, num_topics: int,
                     masks: ExclusionMasks,
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(total_violation, objective, offline_remaining) of the active goal on
    ``state`` — the post-optimization readback, on device in one call."""
    return _chain_goal_stats_body(state, active_idx, goals, constraint,
                                  num_topics, masks)


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics"))
def chain_all_violations(state: ClusterTensors, goals: tuple[Goal, ...],
                         constraint: BalancingConstraint, num_topics: int,
                         masks: ExclusionMasks) -> jax.Array:
    """[G] total violation per goal on ``state`` in ONE device call — the
    pre-optimization violation snapshot (derived state shared across
    goals)."""
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)
    totals = []
    for g in goals:
        aux = goal_aux(g, state, derived, constraint, num_topics)
        totals.append(g.broker_violations(state, derived, constraint,
                                          aux).sum().astype(jnp.float32))
    return jnp.stack(totals)


@jax.named_scope("goal.stats")
def _chain_all_goal_stats_body(state: ClusterTensors,
                               goals: tuple[Goal, ...],
                               constraint: BalancingConstraint,
                               num_topics: int, masks: ExclusionMasks,
                               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)
    viols, objs = [], []
    for g in goals:
        aux = goal_aux(g, state, derived, constraint, num_topics)
        viols.append(g.broker_violations(state, derived, constraint,
                                         aux).sum().astype(jnp.float32))
        objs.append(g.objective(state, derived, constraint,
                                aux).astype(jnp.float32))
    return (jnp.stack(viols), jnp.stack(objs),
            offline_replicas(state).sum())


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics"))
def chain_all_goal_stats(state: ClusterTensors, goals: tuple[Goal, ...],
                         constraint: BalancingConstraint, num_topics: int,
                         masks: ExclusionMasks,
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """([G] violation, [G] objective, offline) for EVERY goal on ``state``
    in ONE device call — the fingerprint-skip snapshot (round 18). The
    per-goal entry stats dispatches of the bounded path collapse into this
    one program: a goal whose snapshot shows zero violation (with zero
    offline replicas and no drain pending) applies nothing, so its
    move/swap dispatches — and its own entry/exit stats dispatches — can
    be skipped byte-identically, as long as no earlier goal has mutated
    the state since the snapshot (the hint-validity contract enforced by
    the optimizer's ``chain_owns_state`` gate)."""
    return _chain_all_goal_stats_body(state, goals, constraint, num_topics,
                                      masks)


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics"))
def megabatch_all_goal_stats(states: ClusterTensors,
                             goals: tuple[Goal, ...],
                             constraint: BalancingConstraint,
                             num_topics: int, masks: ExclusionMasks,
                             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched fingerprint-skip snapshot: ([C, G] violation, [C, G]
    objective, [C] offline) for every goal of every cluster in ONE device
    call (the ``chain_all_goal_stats`` twin on the megabatch cluster
    axis)."""
    mask_fields, mask_ax = _mask_axes(masks)

    def per_cluster(s, tm, rm, lm):
        return _chain_all_goal_stats_body(s, goals, constraint, num_topics,
                                          ExclusionMasks(tm, rm, lm))

    return jax.vmap(per_cluster, in_axes=(0,) + mask_ax)(states,
                                                         *mask_fields)


@partial(jax.jit, static_argnames=("goals", "constraint", "cfg", "num_topics",
                                   "swap_moves", "swap_max_rounds"))
def chain_optimize_full(state: ClusterTensors, goals: tuple[Goal, ...],
                        constraint: BalancingConstraint, cfg: SearchConfig,
                        num_topics: int, masks: ExclusionMasks,
                        swap_moves: int = 8, swap_max_rounds: int = 64):
    """The ENTIRE goal chain in ONE dispatch: ``lax.scan`` over the goal
    index runs each goal's fused move/swap drivers under the acceptance of
    all prior goals, collecting per-goal entry/exit stats on device.

    This is the production solver path. The per-goal kernels above cost
    ~4-6 host↔device round-trips per goal (stats, move driver, swap driver,
    stats again) — a fixed ~0.5 s/goal floor over a high-latency device
    link regardless of scale. Here the host dispatches once and reads back
    one stacked stats pytree for the whole chain.

    Per-goal fast path: a goal whose violations AND offline-replica count
    are zero on entry is skipped entirely (``lax.cond``), unless an alive
    excluded-for-replica-move broker still hosts replicas (the drain
    story). This matches the search's own fixed point — zero violations
    means either no broker has ``source_score > 0`` (goals tie sources to
    violations) or no candidate scores a positive improvement (goals whose
    improvement is the pairwise violation delta, e.g. preferred-leader) —
    and mirrors the reference, whose greedy only acts on brokers outside
    the goal's band (AbstractGoal.java:82-135).

    Returns (final_state, per_goal_stats) where per_goal_stats is a dict of
    [G]-arrays: viol_before/after, obj_before/after, offline_before,
    moves, swaps, rounds, and cand_valid / cand_accepted / healing_rounds /
    source_fallback_rounds (f32: each goal's move rounds' valid
    candidates, those of them that every earlier goal's acceptance let
    through, the move rounds that took the self-healing branch, and those
    whose source selection reduced every broker's row;
    ``_chain_round_body`` ``stats="tally"``).
    """
    g_count = len(goals)
    supports_swap = jnp.asarray([g.supports_swap for g in goals])

    def drain_pending(s: ClusterTensors) -> jax.Array:
        """True while any ALIVE excluded-for-replica-move broker still
        hosts replicas — the per-goal fast path must stay off during a
        drain (see excluded_hosting_replicas)."""
        if masks.excluded_replica_move_brokers is None:
            return jnp.bool_(False)
        return excluded_hosting_replicas(
            s, masks.excluded_replica_move_brokers).any()

    def per_goal(carry_state, g):
        prior = jnp.arange(g_count) < g
        viol0, obj0, offline0 = _chain_goal_stats_body(
            carry_state, g, goals, constraint, num_topics, masks)

        def run(s):
            # Interleave the fused move driver with the fused swap driver
            # until a swap pass applies nothing (the host loop of
            # optimize_goal_in_chain, on device). The aggregate carry is
            # computed once per goal and threaded through both phases.
            def outer_cond(c):
                _s, _a, _t, _m, _sw, rounds, last_swapped, first = c
                return (first | (last_swapped > 0)) & (rounds < cfg.max_rounds)

            def outer_body(c):
                s, a, tally, m_tot, sw_tot, rounds, _ls, _first = c

                # The refresh cadence must count ROUNDS SINCE THE LAST FULL
                # RECOMPUTE, which spans move/swap segments — each inner
                # loop's private counter restarts at 0, so it is offset by
                # the goal's cumulative round count (else a pass of many
                # short segments would never refresh).
                def move_body(carry, rounds_done):
                    st, ag, tl = carry
                    ag = maybe_refresh(ag, st, num_topics,
                                       rounds + rounds_done)
                    ns, nag, applied, stat, _flags = _chain_round_body(
                        st, ag, g, prior, goals, constraint, cfg, num_topics,
                        masks, stats="tally")
                    return (ns, nag, tl + stat), applied

                (s, a, tally), m, r = run_carry_loop(
                    move_body, (s, a, tally), cfg.max_rounds)

                def do_swap(st_ag):
                    def swap_body(carry, rounds_done):
                        st, ag = carry
                        ag = maybe_refresh(ag, st, num_topics,
                                           rounds + r + rounds_done)
                        ns, nag, applied = _chain_swap_body(
                            st, ag, g, prior, goals, constraint, num_topics,
                            masks, swap_moves)
                        return (ns, nag), applied

                    (st, ag), sw, sr = run_carry_loop(swap_body, st_ag,
                                                      swap_max_rounds)
                    return st, ag, sw, sr

                def no_swap(st_ag):
                    st, ag = st_ag
                    return st, ag, jnp.int32(0), jnp.int32(0)

                s, a, sw, sr = jax.lax.cond(supports_swap[g], do_swap,
                                            no_swap, (s, a))
                return (s, a, tally, m_tot + m, sw_tot + sw,
                        rounds + r + sr, sw, jnp.bool_(False))

            s, a, tally, m, sw, rounds, _, _ = jax.lax.while_loop(
                outer_cond, outer_body,
                (s, compute_agg(s, num_topics), jnp.zeros(4, jnp.float32),
                 jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
                 jnp.bool_(True)))
            return s, m, sw, rounds, tally

        def skip(s):
            return (s, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.zeros(4, jnp.float32))

        new_state, moves, swaps, rounds, tally = jax.lax.cond(
            (viol0 > 0) | (offline0 > 0) | drain_pending(carry_state),
            run, skip, carry_state)
        viol1, obj1, offline1 = _chain_goal_stats_body(
            new_state, g, goals, constraint, num_topics, masks)
        ys = {"viol_before": viol0, "obj_before": obj0,
              "offline_before": offline0, "viol_after": viol1,
              "obj_after": obj1, "offline_after": offline1,
              "moves": moves, "swaps": swaps, "rounds": rounds,
              "cand_valid": tally[0], "cand_accepted": tally[1],
              "healing_rounds": tally[2], "source_fallback_rounds": tally[3]}
        return new_state, ys

    final_state, stats = jax.lax.scan(
        per_goal, state, jnp.arange(g_count, dtype=jnp.int32))
    return final_state, stats


def optimize_chain(state: ClusterTensors, chain: Sequence[Goal],
                   constraint: BalancingConstraint, cfg: SearchConfig,
                   num_topics: int, masks: ExclusionMasks | None = None,
                   ) -> tuple[ClusterTensors, list[dict]]:
    """Run the whole goal chain with the single-dispatch fused kernel and
    return (final_state, [per-goal info dict in chain order]).

    Same semantics, error behavior, and info-dict shape as calling
    ``optimize_goal_in_chain`` for each goal in order (the stats-regression
    guard of AbstractGoal.java:111-119 and the hard-goal failure of
    Goal.java:53-59 are checked per goal, in chain order, from the stacked
    on-device stats), at a fraction of the host↔device round-trips.
    """
    masks = masks or ExclusionMasks()
    goals = tuple(chain)
    if not goals:
        return state, []
    with TRACER.span("solver.dispatch", route="fused") as dispatch:
        with TRACER.span("solver.enqueue"):
            state, stats = chain_optimize_full(state, goals, constraint, cfg,
                                               num_topics, masks)
        with TRACER.span("solver.wait"):
            stats = {k: jax.device_get(v) for k, v in stats.items()}
        infos = _chain_infos_from_stats(goals, stats)
        set_dispatch_rounds(dispatch, infos)
        _set_traced_forms(dispatch)
        count_source_fallbacks(
            dispatch, sum(i["source_fallback_rounds"] for i in infos),
            "fused")
        count_healing_rounds(sum(i["healing_rounds"] for i in infos), "fused")
    return state, infos


def count_source_fallbacks(dispatch, rounds: int, grid: str | None) -> None:
    """A pass's move rounds whose source selection reduced every broker's
    row (``candidates.broker_blocks``' fallback): the counter
    ``solver_source_fallback_rounds_total{grid=}`` and the attribute
    ``source_fallback_rounds`` on the pass's ``solver.dispatch`` span."""
    from ..utils.sensors import SENSORS
    SENSORS.count("solver_source_fallback_rounds", rounds,
                  labels=None if grid is None else {"grid": grid})
    dispatch.set(source_fallback_rounds=rounds)


def count_healing_rounds(rounds: int, grid: str) -> None:
    """Move rounds that built the per-slot offline mask
    (``_self_healing``), a whole chain's (``grid="fused"``) or one goal's
    on the bounded route (``narrow`` / ``wide``):
    ``solver_healing_rounds_total{grid=}``. The goals' infos carry them,
    and ``optimizer.ensure_evacuated`` puts the pass's total on its
    ``solver.dispatch`` spans."""
    from ..utils.sensors import SENSORS
    SENSORS.count("solver_healing_rounds", rounds, labels={"grid": grid})


def set_dispatch_rounds(dispatch, infos: list[dict]) -> None:
    """Rounds of a whole-chain dispatch onto its ``solver.dispatch`` span:
    the total, and each goal's count in chain order."""
    dispatch.set(rounds=sum(i["rounds"] for i in infos),
                 goal_rounds=",".join(str(i["rounds"]) for i in infos))


def _chain_infos_from_stats(goals: tuple[Goal, ...], stats: dict,
                            ) -> list[dict]:
    """Per-goal info dicts from the stacked on-device chain stats; raises
    the per-goal errors in chain order (shared by the single-device and
    sharded whole-chain kernels).

    The ``float()``/``int()`` decodes below are the INTENTIONAL readback
    of the whole-chain stats: the device sync was paid by one
    ``device_get`` upstream (optimize_chain), so each line unpacks host
    numpy scalars — annotated so CCSA001 documents, not just polices,
    the async contract."""
    infos: list[dict] = []
    for i, goal in enumerate(goals):
        # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
        obj0, obj1 = float(stats["obj_before"][i]), float(stats["obj_after"][i])
        # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
        if int(stats["offline_before"][i]) == 0:
            if obj1 > obj0 + 1e-4 * max(1.0, abs(obj0)):
                raise StatsRegressionError(
                    f"goal {goal.name} regressed its own objective during "
                    f"its optimization: {obj0:.6g} -> {obj1:.6g}")
        # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
        total_violation = float(stats["viol_after"][i])
        succeeded = total_violation <= 1e-6
        # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
        rounds = int(stats["rounds"][i])
        if goal.is_hard and not succeeded:
            raise OptimizationFailureError(
                f"hard goal {goal.name} unsatisfied: residual violation "
                f"{total_violation:.4f} after {rounds} rounds")
        # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
        swaps = int(stats["swaps"][i])
        infos.append({
            "goal": goal.name,
            "rounds": rounds,
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            "moves_applied": int(stats["moves"][i]) + swaps,
            "swaps_applied": swaps,
            "residual_violation": total_violation,
            "succeeded": succeeded,
            "objective": obj1,
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            "violation_before": float(stats["viol_before"][i]),
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            "violated_on_entry": float(stats["viol_before"][i]) > 1e-6,
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            "offline_before": int(stats["offline_before"][i]),
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            "offline_remaining": int(stats["offline_after"][i]),
        })
        if "cand_valid" in stats:
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            infos[-1]["candidates_valid"] = float(stats["cand_valid"][i])
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            infos[-1]["candidates_accepted"] = float(stats["cand_accepted"][i])
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            infos[-1]["healing_rounds"] = int(stats["healing_rounds"][i])
            # ccsa: ok[CCSA001] decode of already-fetched host stats scalars
            fallbacks = int(stats["source_fallback_rounds"][i])
            infos[-1]["source_fallback_rounds"] = fallbacks
    return infos


class StatsRegressionError(RuntimeError):
    """A goal's own objective regressed during its own optimization — the
    self-check invariant of AbstractGoal.java:111-119 (the reference throws
    IllegalStateException when a goal's ClusterModelStatsComparator prefers
    the pre-optimization stats)."""


class AdaptiveDispatch:
    """Sizes bounded dispatches by wall-clock instead of a fixed round
    count. The per-dispatch ROUND budget is the watchdog mitigation's only
    knob, but what a bound is for is seconds — and what the host pays per
    dispatch is a fixed cost (enqueue plus scalar readback; its size on a
    locally attached chip is not measured on the current machine).
    Growing the budget whenever a FULL dispatch finishes well under the
    target (and shrinking when it overshoots) amortizes that fixed cost
    while every dispatch stays bounded.

    The trajectory is dispatch-boundary-invariant (the budget is a traced
    cap on the same fixed-point loop), so adaptation never changes
    results — equivalence with the fused whole-chain kernel holds for any
    budget sequence. Shared across the goals of one optimization pass:
    per-round cost is a property of the cluster shape, not the goal."""

    MAX_ROUNDS = 1024

    def __init__(self, initial_rounds: int, target_s: float):
        self.k = max(1, initial_rounds)
        self._min = max(1, initial_rounds)
        self._target_s = target_s

    def budget(self, remaining: int) -> int:
        return min(self.k, remaining)

    def observe(self, rounds_run: int, budget: int, elapsed_s: float) -> None:
        if self._target_s <= 0 or rounds_run < budget:
            # Partial dispatch = pass fixed point reached; its duration
            # says nothing about a full budget's cost.
            return
        if elapsed_s > 2 * self._target_s:
            self.k = max(self._min, self.k // 2)
        elif elapsed_s < self._target_s / 2 and budget == self.k:
            # Grow ONLY on evidence from a full k-round dispatch — a tail
            # dispatch capped by the pass's remaining rounds also reports
            # rounds_run == budget, but its duration says nothing about
            # what k rounds would cost (doubling on it could overshoot
            # straight into execution-watchdog territory).
            self.k = min(self.k * 2, self.MAX_ROUNDS)


@dataclasses.dataclass(frozen=True)
class MegastepConfig:
    """Knobs of the bounded-dispatch megastep path (optimizer-owned; the
    chain drivers take it pre-resolved so tests can pin each switch).

    - ``donate``: request buffer donation for the mutable state tensors.
      The effective decision additionally requires a non-zero-copy backend
      (``donation_enabled``) — on CPU, ``device_put`` may alias host
      memory (model/refresh.py's snapshot rule), and a donated aliased
      buffer would let XLA scribble over the model cache.
    - ``async_readback``: enqueue dispatch N+1 before reading dispatch N's
      scalars (one-behind pipeline; AdaptiveDispatch then learns from the
      COMPLETED dispatch one step late — its documented staleness
      contract). Off = read-then-enqueue, the r9 behavior.
    - ``deficit_moves_cap``: > 0 sizes count-distribution goals'
      moves_per_round / num_sources from the measured total surplus
      (deficit_sized_config); 0 disables sizing entirely.
    - ``direct_assignment``: run the direct-assignment transport kernel
      (analyzer.direct) as a pre-pass for count-distribution goals whose
      chain prefix is guard-representable (direct_eligible): the bulk
      surplus→deficit matching lands in ONE dispatch, the greedy rounds
      only polish the structurally-blocked residue. The optimizer sets
      this from ``solver.direct.assignment.enabled`` AND the wide-regime
      gate (it replaces deficit-sized greedy; below the gate the greedy
      path is kept so the fused/bounded byte-parity pins hold).
    - ``direct_max_sweeps``: sweep budget of one direct dispatch
      (``solver.direct.max.sweeps``).
    - ``direct_sparse_margin``: fractional band-edge margin of the
      sparse-aware plan (``solver.direct.sparse.margin.frac``) — the
      shed/fill targets sit this fraction of the band width inside the
      edges; resolved per cell by deterministic randomized rounding.
    - ``direct_sparse_salt``: extra salt string folded (crc32, trace
      time) into the rounding seed (``solver.direct.sparse.rounding.salt``)
      so fleets can decorrelate rounding replays; "" keeps the module
      default seed.
    - ``direct_goals``: per-goal density-aware path CHOICE (ROADMAP 2d).
      ``None`` routes every direct-eligible goal through the transport
      kernel (today's behavior); a tuple restricts it to the NAMED goals,
      the rest taking the greedy arm even when eligible. The optimizer
      resolves this from replica density: at sparse geometry
      Replica/LeaderReplica are measurably faster under greedy while TR
      wins under direct+polish (the documented honest negative), so
      below ``solver.direct.density.sparse.threshold`` only TR keeps the
      direct arm.
    """

    donate: bool = True
    async_readback: bool = True
    deficit_moves_cap: int = 0
    direct_assignment: bool = False
    direct_max_sweeps: int = 16
    direct_sparse_margin: float = 0.25
    direct_sparse_salt: str = ""
    direct_goals: "tuple[str, ...] | None" = None


def direct_path_chosen(megastep: "MegastepConfig", goal_name: str) -> bool:
    """Whether the per-goal density-aware choice keeps the direct arm for
    this goal (None = all direct-eligible goals, the pre-choice
    behavior). The eligibility guard (``direct.direct_eligible``) still
    applies on top — this only narrows it."""
    return (megastep.direct_goals is None
            or goal_name in megastep.direct_goals)


def donation_enabled(megastep: "MegastepConfig | None") -> bool:
    """Donate only off zero-copy backends: on CPU the state tensors may
    alias host buffers owned by the incremental model pipeline
    (refresh.py ships loads zero-copy when alignment allows), and the
    topology cache shares device arrays across generations — the same
    rule refresh.py applies to its own donation decision."""
    return (megastep is not None and megastep.donate
            and jax.default_backend() != "cpu")


class DispatchStats:
    """Per-optimization-pass dispatch accounting: how many device
    dispatches the solve cost, how many rounds each carried, and how many
    were donated / speculative (the async pump's post-convergence no-op).
    Mirrored into the sensor registry via utils.xla_telemetry so the
    bench and CI can read dispatch_count / rounds_per_dispatch_p50
    without threading state through every driver."""

    def __init__(self):
        self.rounds_per_dispatch: list[int] = []     # speculative left out
        self.donated = 0
        self.speculative = 0
        self.by_kind: dict[str, int] = {}
        # Goals that consumed ZERO dispatches thanks to the
        # fingerprint-skip snapshot (round 18): their entry stats came
        # from the one batched pre-chain program and showed nothing to do.
        self.goals_skipped = 0
        # crc32 of the pass's per-goal entry-violation vector (the
        # round-18 fingerprint; None when the snapshot did not run).
        self.fingerprint = None

    def record(self, kind: str, rounds: int, donated: bool = False,
               speculative: bool = False, telemetry: bool = True,
               grid: str | None = None) -> None:
        """``telemetry=False`` keeps the tally local: the megabatch pump
        splits ONE physical dispatch into per-cluster accounting records,
        and only the physical record may hit the solver_dispatches
        sensors (a 4-cluster dispatch is one XLA execution, not four).
        ``grid`` labels the sensors (``utils.xla_telemetry.record_dispatch``).
        A speculative dispatch counts as a dispatch and stays out of
        ``rounds_per_dispatch``: it starts on the pass's fixed point."""
        if speculative:
            self.speculative += 1
        else:
            self.rounds_per_dispatch.append(int(rounds))
        self.by_kind[kind] = self.by_kind.get(kind, 0) + 1
        if donated:
            self.donated += 1
        if not telemetry:
            return
        from ..utils.xla_telemetry import record_dispatch
        record_dispatch(kind, int(rounds), donated=donated,
                        speculative=speculative, grid=grid)

    @property
    def dispatch_count(self) -> int:
        return len(self.rounds_per_dispatch) + self.speculative

    def rounds_p50(self) -> float:
        if not self.rounds_per_dispatch:
            return 0.0
        ordered = sorted(self.rounds_per_dispatch)
        return float(ordered[(len(ordered) - 1) // 2])

    def as_dict(self) -> dict:
        out = {"dispatch_count": self.dispatch_count,
               "rounds_per_dispatch_p50": self.rounds_p50(),
               "donated_dispatches": self.donated,
               "speculative_dispatches": self.speculative}
        if self.by_kind.get("direct"):
            # Present only when the direct-assignment kernel ran, so
            # pre-direct accounting consumers see an unchanged dict.
            out["direct_dispatches"] = self.by_kind["direct"]
        if self.goals_skipped:
            # Present only when the fingerprint snapshot actually skipped
            # goals (same compatibility discipline as direct_dispatches).
            out["goals_skipped"] = self.goals_skipped
        if self.fingerprint is not None:
            out["violation_fingerprint"] = self.fingerprint
        return out


def deficit_sized_config(cfg: SearchConfig, viol0: float,
                         cap: int) -> SearchConfig:
    """Deficit-aware batch sizing for the count-distribution goals: size
    the per-round move budget (and the source width that bounds how many
    moves a round can actually admit — selection takes at most one move
    per source row) from the goal's measured total band violation instead
    of the configured constant, so an O(10k)-move imbalance is not fed
    through hundreds of fixed-width rounds.

    Each move shifts one replica from an over-band broker to an
    under-band one, reducing the total violation by up to 2 — the move
    target is ``viol0 / 2``. The width is rounded UP to a power of two
    (compile-count quantization: every distinct (sources, moves) pair is
    a new XLA program) and clamped to [cfg values, cap]. Sizing depends
    only on the goal's ENTRY violations, so it is identical for any
    dispatch-budget sequence — trajectory invariance holds per sized
    config."""
    from .fill import pow2_width
    target = int(viol0) // 2
    if target <= cfg.moves_per_round:
        return cfg
    q = min(pow2_width(target), max(cap, cfg.moves_per_round))
    if q <= cfg.moves_per_round and q <= cfg.num_sources:
        return cfg
    return dataclasses.replace(
        cfg, moves_per_round=max(cfg.moves_per_round, q),
        num_sources=max(cfg.num_sources, q))


def run_bounded_pass(enqueue: Callable, st, pass_cap: int,
                     controller: AdaptiveDispatch,
                     out_of_time: Callable[[], bool] | None = None,
                     async_readback: bool = True,
                     stats: DispatchStats | None = None,
                     kind: str = "move",
                     flight=NO_FLIGHT, grid: str | None = None,
                     pass_counts: Callable | None = None):
    """Drive one logical pass (a fixed-point loop of at most ``pass_cap``
    search rounds) as a sequence of bounded megastep dispatches.

    ``enqueue(st, budget) -> (st, applied, rounds, donated, ring)`` fires
    one dispatch and returns WITHOUT reading anything back (jax async
    dispatch); the scalars are device futures and ``donated`` reports
    whether THIS dispatch ran the donated kernel (per-dispatch, so the
    donation telemetry stays exact). ``ring`` is the dispatch's per-round
    flight-stats buffer (None on paths without one); it is read — and
    handed to ``flight`` (utils.flight_recorder goal hook) together with
    the dispatch's budget/rounds/applied/controller state — exactly when
    the dispatch's scalars are read, so recording never adds a host
    round-trip. With ``async_readback`` the pump
    keeps one dispatch in flight: dispatch N+1 is enqueued — chained on
    N's output state, budgeted against the PESSIMISTIC estimate that N
    runs its full budget (the estimate can only under-budget N+1, never
    overshoot ``pass_cap``) — before N's scalars are read, so the
    host↔device link latency of the readback overlaps device compute.
    ``controller`` observes each dispatch when its scalars arrive — one
    step behind the enqueue decision it feeds (the AdaptiveDispatch
    staleness contract). In the pipelined steady state dispatch N cannot
    start on device before N−1 completes (its input is N−1's output), so
    N's own cost is measured as the delta from the PREVIOUS readback's
    return to this one — timing from enqueue would fold N−1's remaining
    execution into N and systematically ~double the observed cost,
    pinning the budget at its floor.

    A dispatch that reports fewer rounds than its budget hit the pass's
    fixed point; the speculatively-enqueued successor (if any) starts on
    that fixed point — resuming the pass (``PassCarry``, the
    single-device route) it runs no round; the mesh's kernels, which
    start a pass afresh, re-run the zero-apply round — and applies
    nothing: it is recorded in ``stats`` (speculative=True) but
    contributes neither moves nor rounds to the pass totals, so the
    round budget matches the synchronous path's exactly. Trajectory is
    invariant to all of it: same round sequence, only dispatch boundaries
    and readback timing differ.

    ``grid`` labels the dispatches in ``stats`` and the pass's
    ``solver.dispatch`` span, which also gets ``pass_rounds``,
    ``speculative`` (the pass's speculative dispatches) and
    ``budget_max`` (its largest round budget): values the pump holds
    anyway, no readback added. ``pass_counts(st, dispatch)``, where given,
    reads what the pass counted on the device from the final ``st`` once
    the pass's last dispatch has been read (the device is done with it:
    a transfer, no wait) and records it on the span.

    Returns (st, applied_total, pass_rounds)."""
    applied_total = 0
    pass_rounds = 0
    est_rounds = 0
    prev = None    # (applied, rounds, budget, t0, donated, ring) — unread
    last_read_t = None
    converged = False
    speculative = 0
    budget_max = 0
    with TRACER.span("solver.dispatch", route="bounded",
                     kind=kind) as dispatch:
        while True:
            cur = None
            may_enqueue = prev is None or async_readback
            if may_enqueue and not converged and est_rounds < pass_cap \
                    and not (out_of_time is not None and out_of_time()):
                budget = controller.budget(pass_cap - est_rounds)
                budget_max = max(budget_max, budget)
                t0 = _time.monotonic()
                with TRACER.span("solver.enqueue"):
                    st, applied, r, donated, ring = enqueue(st, budget)
                cur = (applied, r, budget, t0, donated, ring)
                est_rounds += budget
            if prev is not None:
                applied_p, r_p, budget_p, t0_p, donated_p, ring_p = prev
                with TRACER.span("solver.wait"):
                    # ccsa: ok[CCSA001] THE pump readback: dispatch N's
                    # scalars are read here exactly one enqueue behind —
                    # N+1 is already in flight, so this block overlaps
                    # device compute by design (the span opens where the
                    # pump already waits; it adds no sync)
                    r_read = int(r_p)                   # blocks on dispatch N
                now = _time.monotonic()
                start = t0_p if last_read_t is None else max(t0_p, last_read_t)
                # ccsa: ok[CCSA001] same readback point: N already synced via
                # r_read, this transfer is paid, not a new stall
                applied_total += int(applied_p)
                controller.observe(r_read, budget_p, now - start)
                last_read_t = now
                if stats is not None:
                    stats.record(kind, r_read, donated=donated_p, grid=grid)
                # ccsa: ok[CCSA001] same readback point, applied_p already read
                flight.dispatch(kind, budget_p, r_read, int(applied_p),
                                donated=donated_p, elapsed_s=now - start,
                                controller_k=controller.k, ring=ring_p)
                pass_rounds += r_read
                est_rounds -= budget_p - r_read         # correct the estimate
                if r_read < budget_p:
                    converged = True
            if converged and cur is not None:
                # Speculative post-convergence dispatch: it started on
                # the pass's fixed point and applied nothing (state
                # untouched on device; a resumed pass runs no round).
                # Nothing of it is added to the pass totals; its ring
                # rows, if any, repeat the terminal round.
                speculative += 1
                if stats is not None:
                    # ccsa: ok[CCSA001] post-convergence drain: nothing left to
                    # pipeline behind this readback — the pass is over
                    stats.record(kind, int(cur[1]), donated=cur[4],
                                 speculative=True, grid=grid)
                # ccsa: ok[CCSA001] post-convergence drain, same as above
                flight.dispatch(kind, cur[2], int(cur[1]), 0, donated=cur[4],
                                speculative=True, controller_k=controller.k)
                cur = None
            prev = cur
            if prev is None and (converged or est_rounds >= pass_cap
                                 or (out_of_time is not None and out_of_time())):
                break
        dispatch.set(rounds=pass_rounds, pass_rounds=pass_rounds,
                     speculative=speculative, budget_max=budget_max)
        if grid is not None:
            dispatch.set(grid=grid)
        _set_traced_forms(dispatch, kind)
        if pass_counts is not None:
            pass_counts(st, dispatch)
    return st, applied_total, pass_rounds


# ---------------------------------------------------------------------------
# Megabatch: whole buckets of clusters through ONE device program
# ---------------------------------------------------------------------------
#
# The fleet layer pads every cluster onto a shared bucket grid
# (fleet.bucketing), so same-bucket clusters are shape-identical pytrees.
# Stacking them along a leading cluster axis and vmapping the round body
# turns the megastep into a FLEET megastep: one donated dispatch advances
# every cluster in the batch by up to ``budget`` rounds, with a
# per-cluster early-exit mask replacing the scalar early-exit flag — a
# converged (or inert pad-slot) cluster's carry is frozen by a select, so
# its state stays byte-identical to a serial solve while its neighbors
# keep searching. Rounds run in lockstep: the batched dispatch costs
# max-over-clusters rounds instead of the serial sum — the
# Podracer/Anakin lever (compile once per bucket shape, amortize the
# whole fleet through it).


def stack_states(states: Sequence[ClusterTensors]) -> ClusterTensors:
    """Stack shape-identical cluster states along a new leading cluster
    axis (the megabatch layout). All states must share one padded bucket
    shape — the fleet assembler's grouping contract."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def unstack_state(batched: ClusterTensors, index: int) -> ClusterTensors:
    """Slice cluster ``index`` back out of a megabatch state."""
    return jax.tree.map(lambda x: x[index], batched)


def inert_state_like(state: ClusterTensors) -> ClusterTensors:
    """A zero-weight pad-slot cluster at ``state``'s shape: every broker
    DEAD/masked with zero capacity, every partition empty and masked —
    the same pad-row encoding fleet.bucketing uses for rows, applied to a
    WHOLE cluster slot. It generates no candidates, no violations, and no
    offline replicas, so the per-goal activation mask never wakes it; a
    partially-filled megabatch pads with these so one compiled program
    per bucket shape serves any occupancy."""
    from ..common.broker_state import BrokerState
    return dataclasses.replace(
        state,
        assignment=jnp.full_like(state.assignment, -1),
        leader_slot=jnp.full_like(state.leader_slot, -1),
        leader_load=jnp.zeros_like(state.leader_load),
        follower_load=jnp.zeros_like(state.follower_load),
        capacity=jnp.zeros_like(state.capacity),
        rack=jnp.zeros_like(state.rack),
        broker_state=jnp.full_like(state.broker_state,
                                   int(BrokerState.DEAD)),
        topic=jnp.zeros_like(state.topic),
        partition_mask=jnp.zeros_like(state.partition_mask),
        broker_mask=jnp.zeros_like(state.broker_mask))


def _mask_axes(masks: ExclusionMasks):
    """(fields, vmap axes) for a BATCHED ExclusionMasks: each field is
    either None for every cluster in the batch or stacked ``[C, ...]``
    (the assembler's mask-uniformity contract)."""
    fields = (masks.excluded_topics, masks.excluded_replica_move_brokers,
              masks.excluded_leadership_brokers)
    return fields, tuple(None if f is None else 0 for f in fields)


def _megabatch_rounds_driver(states: ClusterTensors, active0: jax.Array,
                             active_idx: jax.Array, prior_mask: jax.Array,
                             goals: tuple[Goal, ...],
                             constraint: BalancingConstraint,
                             cfg: SearchConfig, num_topics: int,
                             masks: ExclusionMasks, budget: jax.Array,
                             ring_rounds: int = 0):
    """Traced body of the batched move megastep: one ``lax.while_loop``
    whose body vmaps ``_chain_round_body`` over the leading cluster axis.

    ``active0[C]`` is the per-cluster early-exit mask threaded DISPATCH TO
    DISPATCH as a device value (the pump chains it like the state, so
    enqueueing the next dispatch never reads it back): a cluster runs a
    round only while active, a zero-apply round deactivates it, and an
    inactive cluster's whole carry (state, aggregate, ring) is frozen by a
    select — byte-identical to the serial megastep, which simply stops
    dispatching at that point. The loop ends when every cluster is
    inactive or the shared round budget is spent; while active, a
    cluster's within-dispatch round index equals the global one (all
    clusters start at round 0 together), so the aggregate refresh cadence
    matches the serial driver's exactly.

    ``ring_rounds`` > 0 grows the flight ring a CLUSTER axis:
    ``[C, ring_rounds, STAT_WIDTH]``, one per-round stats row per cluster,
    frozen with the rest of the carry once the cluster exits.

    Returns (states, total[C], rounds[C], active_out[C], ring-or-None)."""
    collect = ring_rounds > 0
    c = states.assignment.shape[0]
    mask_fields, mask_ax = _mask_axes(masks)

    def per_cluster(s, a, ring, tm, rm, lm, gr):
        m = ExclusionMasks(tm, rm, lm)
        a = maybe_refresh(a, s, num_topics, gr)
        ns, na, applied, stat, _flags = _chain_round_body(
            s, a, active_idx, prior_mask, goals, constraint, cfg,
            num_topics, m, stats="row" if collect else None, batched=True)
        if collect:
            ring = ring.at[gr % ring_rounds].set(stat)
        return ns, na, ring, applied

    vround = jax.vmap(per_cluster,
                      in_axes=(0, 0, 0) + mask_ax + (None,))

    def freeze(active):
        def sel(new, old):
            keep = active.reshape((c,) + (1,) * (new.ndim - 1))
            return jnp.where(keep, new, old)
        return sel

    cap = jnp.minimum(jnp.int32(cfg.max_rounds), budget.astype(jnp.int32))

    def cond(carry):
        _s, _a, _r, _tot, _rnd, gr, active = carry
        return active.any() & (gr < cap)

    def body(carry):
        st, ag, ring, tot, rnd, gr, active = carry
        nst, nag, nring, applied = vround(st, ag, ring, *mask_fields, gr)
        sel = freeze(active)
        st = jax.tree.map(sel, nst, st)
        ag = jax.tree.map(sel, nag, ag)
        ring = sel(nring, ring)
        applied = jnp.where(active, applied, 0).astype(jnp.int32)
        return (st, ag, ring, tot + applied,
                rnd + active.astype(jnp.int32), gr + 1,
                active & (applied > 0))

    agg0 = jax.vmap(lambda s: compute_agg(s, num_topics))(states)
    ring0 = jnp.zeros((c, ring_rounds if collect else 0, _FLIGHT_STATS),
                      jnp.float32)
    final, _agg, ring, total, rounds, _gr, active = jax.lax.while_loop(
        cond, body,
        (states, agg0, ring0, jnp.zeros((c,), jnp.int32),
         jnp.zeros((c,), jnp.int32), jnp.int32(0), active0))
    return final, total, rounds, active, (ring if collect else None)


@partial(jax.jit, static_argnames=("goals", "constraint", "cfg", "num_topics",
                                   "ring_rounds"))
def megabatch_optimize_rounds(states: ClusterTensors, active0: jax.Array,
                              active_idx: jax.Array, prior_mask: jax.Array,
                              goals: tuple[Goal, ...],
                              constraint: BalancingConstraint,
                              cfg: SearchConfig, num_topics: int,
                              masks: ExclusionMasks, budget: jax.Array,
                              ring_rounds: int = 0):
    """Batched fused move driver (the non-donating megabatch twin of
    ``chain_optimize_rounds``; the CPU / parity-oracle path). Occupancy is
    a traced property (``active0`` plus inert pad-slot clusters), so ONE
    compilation per bucket shape serves any fill level."""
    final, total, rounds, active, ring = _megabatch_rounds_driver(
        states, active0, active_idx, prior_mask, goals, constraint, cfg,
        num_topics, masks, budget, ring_rounds=ring_rounds)
    if ring_rounds > 0:
        return final, total, rounds, active, ring
    return final, total, rounds, active


@partial(jax.jit, static_argnames=("goals", "constraint", "cfg",
                                   "num_topics", "ring_rounds"),
         donate_argnums=(0, 1))
def megabatch_optimize_rounds_donated(assignment: jax.Array,
                                      leader_slot: jax.Array,
                                      rest: ClusterTensors,
                                      active0: jax.Array,
                                      active_idx: jax.Array,
                                      prior_mask: jax.Array,
                                      goals: tuple[Goal, ...],
                                      constraint: BalancingConstraint,
                                      cfg: SearchConfig, num_topics: int,
                                      masks: ExclusionMasks,
                                      budget: jax.Array,
                                      ring_rounds: int = 0):
    """The donated fleet megastep: identical trace to
    ``megabatch_optimize_rounds`` with the BATCHED mutable pair
    ``{assignment[C,P,S], leader_slot[C,P]}`` donated — exactly the
    strip_mutable donation set grown a cluster axis, nothing else (the
    stacked topology planes in ``rest`` are built from the refresh
    cache's shared arrays and must never be donated; CCSA002 verifies the
    batched kernel form too). Callers pass ``strip_mutable`` applied
    per cluster before stacking as ``rest``."""
    states = dataclasses.replace(rest, assignment=assignment,
                                 leader_slot=leader_slot)
    final, total, rounds, active, ring = _megabatch_rounds_driver(
        states, active0, active_idx, prior_mask, goals, constraint, cfg,
        num_topics, masks, budget, ring_rounds=ring_rounds)
    if ring_rounds > 0:
        return (final.assignment, final.leader_slot, total, rounds, active,
                ring)
    return final.assignment, final.leader_slot, total, rounds, active


def _megabatch_swap_driver(states: ClusterTensors, active0: jax.Array,
                           active_idx: jax.Array, prior_mask: jax.Array,
                           goals: tuple[Goal, ...],
                           constraint: BalancingConstraint, num_topics: int,
                           masks: ExclusionMasks, moves: int,
                           max_rounds: int, budget: jax.Array):
    """Batched swap-phase driver (same per-cluster freeze discipline as
    the move driver; swap phases carry no flight ring)."""
    c = states.assignment.shape[0]
    mask_fields, mask_ax = _mask_axes(masks)

    def per_cluster(s, a, tm, rm, lm, gr):
        m = ExclusionMasks(tm, rm, lm)
        a = maybe_refresh(a, s, num_topics, gr)
        ns, na, applied = _chain_swap_body(s, a, active_idx, prior_mask,
                                           goals, constraint, num_topics,
                                           m, moves)
        return ns, na, applied

    vround = jax.vmap(per_cluster, in_axes=(0, 0) + mask_ax + (None,))
    cap = jnp.minimum(jnp.int32(max_rounds), budget.astype(jnp.int32))

    def cond(carry):
        _s, _a, _tot, _rnd, gr, active = carry
        return active.any() & (gr < cap)

    def body(carry):
        st, ag, tot, rnd, gr, active = carry
        nst, nag, applied = vround(st, ag, *mask_fields, gr)

        def sel(new, old):
            keep = active.reshape((c,) + (1,) * (new.ndim - 1))
            return jnp.where(keep, new, old)

        st = jax.tree.map(sel, nst, st)
        ag = jax.tree.map(sel, nag, ag)
        applied = jnp.where(active, applied, 0).astype(jnp.int32)
        return (st, ag, tot + applied, rnd + active.astype(jnp.int32),
                gr + 1, active & (applied > 0))

    agg0 = jax.vmap(lambda s: compute_agg(s, num_topics))(states)
    final, _agg, total, rounds, _gr, active = jax.lax.while_loop(
        cond, body,
        (states, agg0, jnp.zeros((c,), jnp.int32),
         jnp.zeros((c,), jnp.int32), jnp.int32(0), active0))
    return final, total, rounds, active


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics",
                                   "moves", "max_rounds"))
def megabatch_swap_rounds(states: ClusterTensors, active0: jax.Array,
                          active_idx: jax.Array, prior_mask: jax.Array,
                          goals: tuple[Goal, ...],
                          constraint: BalancingConstraint, num_topics: int,
                          masks: ExclusionMasks, moves: int,
                          max_rounds: int, budget: jax.Array):
    """Batched fused swap driver (non-donating twin)."""
    return _megabatch_swap_driver(states, active0, active_idx, prior_mask,
                                  goals, constraint, num_topics, masks,
                                  moves, max_rounds, budget)


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics",
                                   "moves", "max_rounds"),
         donate_argnums=(0, 1))
def megabatch_swap_rounds_donated(assignment: jax.Array,
                                  leader_slot: jax.Array,
                                  rest: ClusterTensors, active0: jax.Array,
                                  active_idx: jax.Array,
                                  prior_mask: jax.Array,
                                  goals: tuple[Goal, ...],
                                  constraint: BalancingConstraint,
                                  num_topics: int, masks: ExclusionMasks,
                                  moves: int, max_rounds: int,
                                  budget: jax.Array):
    """Donated batched swap megastep (see
    megabatch_optimize_rounds_donated for the donation contract)."""
    states = dataclasses.replace(rest, assignment=assignment,
                                 leader_slot=leader_slot)
    final, total, rounds, active = _megabatch_swap_driver(
        states, active0, active_idx, prior_mask, goals, constraint,
        num_topics, masks, moves, max_rounds, budget)
    return final.assignment, final.leader_slot, total, rounds, active


@partial(jax.jit, static_argnames=("goals", "constraint", "num_topics"))
def megabatch_goal_stats(states: ClusterTensors, active_idx: jax.Array,
                         goals: tuple[Goal, ...],
                         constraint: BalancingConstraint, num_topics: int,
                         masks: ExclusionMasks):
    """Per-cluster (violation, objective, offline) of the active goal on a
    megabatch state — the batched twin of ``chain_goal_stats``, one device
    call for the whole bucket."""
    mask_fields, mask_ax = _mask_axes(masks)

    def per_cluster(s, tm, rm, lm):
        return _chain_goal_stats_body(s, active_idx, goals, constraint,
                                      num_topics,
                                      ExclusionMasks(tm, rm, lm))

    return jax.vmap(per_cluster, in_axes=(0,) + mask_ax)(states,
                                                         *mask_fields)


def run_megabatch_pass(enqueue: Callable, st, active0, pass_cap: int,
                       controller: AdaptiveDispatch,
                       async_readback: bool = True,
                       stats: "list[DispatchStats] | None" = None,
                       physical_stats: "DispatchStats | None" = None,
                       kind: str = "move", flights=None):
    """Drive one logical BATCHED pass as a sequence of bounded megabatch
    dispatches — the fleet twin of ``run_bounded_pass``, same one-behind
    pump. ``enqueue(st, active, budget) -> (st, active_out, applied,
    rounds, donated, ring)`` fires one batched dispatch and returns
    device futures only; the per-cluster early-exit mask ``active_out``
    chains into the next enqueue exactly like the state, so pipelining
    never waits on it. Scalars become ``[C]`` arrays: the readback
    decodes them ONCE per dispatch and splits per-cluster accounting out
    of it — ``stats[b]`` records cluster b's rounds (dispatch accounting
    split), ``flights[b]`` gets its dispatch record plus its slice of the
    cluster-axis flight ring, and ``physical_stats`` records the ONE
    actual XLA execution (the sensor-facing tally; per-cluster splits
    skip telemetry so a 4-cluster dispatch never counts as 4 device
    executions).

    The pass converges when every cluster's early-exit mask clears. The
    speculatively-enqueued successor then runs ZERO rounds (every
    cluster inactive at entry — cheaper than the serial speculative
    zero-apply round, and byte-identical since inactive clusters are
    frozen); it is recorded speculative and contributes nothing.

    Returns (st, active_final_host, applied_totals, rounds_totals) with
    the totals as per-cluster numpy int arrays."""
    import numpy as np
    c = active0.shape[0]
    applied_total = np.zeros(c, dtype=np.int64)
    rounds_total = np.zeros(c, dtype=np.int64)
    # ccsa: ok[CCSA001] pass-entry decode of the caller's activation
    # mask — nothing is in flight before the first enqueue
    active_host = np.asarray(active0).astype(bool)
    entry_active = active_host.copy()
    active_dev = active0
    est_rounds = 0
    prev = None   # (applied, rounds, active_out, budget, t0, donated, ring)
    last_read_t = None
    converged = False
    with TRACER.span("solver.dispatch", route="megabatch",
                     kind=kind) as dispatch:
        while True:
            cur = None
            may_enqueue = prev is None or async_readback
            if may_enqueue and not converged and est_rounds < pass_cap:
                budget = controller.budget(pass_cap - est_rounds)
                t0 = _time.monotonic()
                with TRACER.span("solver.enqueue"):
                    st, active_dev, applied, r, donated, ring = enqueue(
                        st, active_dev, budget)
                cur = (applied, r, active_dev, budget, t0, donated, ring)
                est_rounds += budget
            if prev is not None:
                applied_p, r_p, act_p, budget_p, t0_p, donated_p, ring_p = prev
                with TRACER.span("solver.wait"):
                    # ccsa: ok[CCSA001] THE megabatch pump readback: dispatch
                    # N's per-cluster arrays are read here exactly one
                    # enqueue behind — N+1 is already in flight chained on
                    # N's output state and early-exit mask, so this block
                    # overlaps device compute
                    rounds_np = np.asarray(r_p)         # blocks on dispatch N
                now = _time.monotonic()
                start = t0_p if last_read_t is None else max(t0_p, last_read_t)
                # ccsa: ok[CCSA001] same readback point: N already synced via
                # rounds_np, these transfers are paid, not new stalls
                applied_np = np.asarray(applied_p)
                # ccsa: ok[CCSA001] same readback point (the early-exit mask
                # the NEXT enqueue already consumed on device)
                active_host = np.asarray(act_p).astype(bool)
                # ccsa: ok[CCSA001] decode of the already-fetched host array
                global_rounds = int(rounds_np.max()) if c else 0
                applied_total += applied_np
                rounds_total += rounds_np
                controller.observe(global_rounds, budget_p, now - start)
                last_read_t = now
                if physical_stats is not None:
                    physical_stats.record(kind, global_rounds, donated=donated_p)
                for b in range(c):
                    if rounds_np[b] <= 0:
                        continue
                    if stats is not None:
                        # ccsa: ok[CCSA001] per-cluster split of the paid
                        # readback: host numpy scalar decodes only
                        stats[b].record(kind, int(rounds_np[b]),
                                        donated=donated_p, telemetry=False)
                    if flights is not None:
                        # ccsa: ok[CCSA001] same split, host numpy decodes
                        r_b, a_b = int(rounds_np[b]), int(applied_np[b])
                        flights[b].dispatch(
                            kind, budget_p, r_b, a_b, donated=donated_p,
                            elapsed_s=now - start, controller_k=controller.k,
                            ring=None if ring_p is None else ring_p[b])
                est_rounds -= budget_p - global_rounds
                if not active_host.any():
                    converged = True
            if converged and cur is not None:
                # Speculative post-convergence dispatch: every cluster entered
                # inactive, so the batched while_loop ran zero rounds and the
                # state is untouched — recorded, never counted.
                if physical_stats is not None:
                    physical_stats.record(kind, 0, donated=cur[5],
                                          speculative=True)
                if flights is not None:
                    # Only clusters that PARTICIPATED in this pass get the
                    # speculative record — a goal-satisfied (or pad-slot)
                    # cluster that never activated records no dispatch at
                    # all, exactly like its serial solve.
                    for b in range(c):
                        if entry_active[b]:
                            flights[b].dispatch(kind, cur[3], 0, 0,
                                                donated=cur[5],
                                                speculative=True,
                                                controller_k=controller.k)
                cur = None
            prev = cur
            if prev is None and (converged or est_rounds >= pass_cap):
                break
        # ccsa: ok[CCSA001] host numpy totals of reads already paid
        dispatch.set(rounds=int(rounds_total.max()) if c else 0)
        _set_traced_forms(dispatch, kind)
    return st, active_host, applied_total, rounds_total


def optimize_goal_in_chain_megabatch(states: ClusterTensors,
                                     chain: Sequence[Goal], index: int,
                                     constraint: BalancingConstraint,
                                     cfg: SearchConfig, num_topics: int,
                                     masks: ExclusionMasks,
                                     cluster_mask,
                                     dispatch_rounds: int,
                                     dispatch: AdaptiveDispatch,
                                     megastep: MegastepConfig,
                                     stats: "list[DispatchStats] | None" = None,
                                     physical_stats: "DispatchStats | None" = None,
                                     flights=None,
                                     donate_input: bool = False,
                                     entry_stats: tuple | None = None,
                                     drain_hint=None,
                                     mesh=None,
                                     ) -> tuple[ClusterTensors, list[dict]]:
    """Run goal ``chain[index]`` for EVERY cluster in a megabatch under
    the acceptance of ``chain[:index]`` — the batched twin of
    ``optimize_goal_in_chain``, bounded-dispatch path only (the megabatch
    exists to amortize dispatches; there is no batched unbounded path).

    ``cluster_mask[C]`` (host bool array) marks real cluster slots: inert
    pad slots are never activated, count no rounds, and get no info dict
    semantics beyond zeros. Per-cluster failures do NOT raise — a hard
    goal failing on cluster 2 must not abort clusters 0, 1, 3 — instead
    each returned info dict may carry ``error``/``error_type`` and the
    caller freezes that cluster for the rest of the chain (its state then
    matches the serial solve's at its raise point).

    Deficit-aware count-goal sizing is structurally OFF here: it sizes
    the search grid from ONE cluster's entry violation, and a megabatch
    shares one compiled grid across the bucket (the assembler's config
    key pins this).

    ``entry_stats`` / ``drain_hint`` (round 18): this goal's per-cluster
    ``([C] violation, [C] objective, [C] offline)`` and drain-pending
    ``[C]`` bools already computed by ONE ``megabatch_all_goal_stats``
    snapshot for the whole chain — valid only while no goal has mutated
    any cluster since the snapshot (the ``chain_owns_state`` gate). A
    goal the snapshot shows inactive for EVERY cluster consumes zero
    batched dispatches.

    ``mesh`` (round 23): a 1-D device mesh routes every batched kernel
    through its shard_map twin (parallel.megabatch_sharded) — the
    cluster axis splits ``batch_width / n_devices`` slots per device,
    everything else (this whole host loop, the pump, the donation guard)
    is unchanged because the sharded wrappers are call-compatible. The
    caller must have placed ``states``/``masks`` on the mesh and padded
    the batch to a device multiple.

    Returns (states, [per-cluster info dict])."""
    import numpy as np
    goals = tuple(chain)
    goal = goals[index]
    idx = jnp.int32(index)
    prior = jnp.asarray([j < index for j in range(len(goals))])
    c = states.assignment.shape[0]
    cluster_mask = np.asarray(cluster_mask).astype(bool)
    assert dispatch_rounds > 0, "megabatch requires the bounded path"

    # Resolve the kernel family ONCE (single-path code below): either the
    # single-device jitted megabatch kernels or their sharded twins with
    # the mesh bound in. Lazy import — analyzer must not depend on
    # parallel at module load.
    if mesh is not None:
        from ..parallel import megabatch_sharded as _mbs
        mb_stats = partial(_mbs.megabatch_goal_stats_sharded, mesh)
        mb_move = partial(_mbs.megabatch_optimize_rounds_sharded, mesh)
        mb_move_don = partial(
            _mbs.megabatch_optimize_rounds_donated_sharded, mesh)
        mb_swap = partial(_mbs.megabatch_swap_rounds_sharded, mesh)
        mb_swap_don = partial(
            _mbs.megabatch_swap_rounds_donated_sharded, mesh)
    else:
        mb_stats = megabatch_goal_stats
        mb_move = megabatch_optimize_rounds
        mb_move_don = megabatch_optimize_rounds_donated
        mb_swap = megabatch_swap_rounds
        mb_swap_don = megabatch_swap_rounds_donated

    if entry_stats is not None:
        viol0, obj0, off0 = (np.asarray(entry_stats[0]),
                             np.asarray(entry_stats[1]),
                             np.asarray(entry_stats[2]))
    else:
        viol0_d, obj0_d, off0_d = mb_stats(states, idx, goals, constraint,
                                           num_topics, masks)
        viol0 = np.asarray(viol0_d)
        obj0 = np.asarray(obj0_d)
        off0 = np.asarray(off0_d)
    if flights is not None:
        for b in range(c):
            if cluster_mask[b]:
                flights[b].entry(violation=float(viol0[b]),
                                 objective=float(obj0[b]),
                                 offline=int(off0[b]))
                flights[b].grid(cfg.num_sources, cfg.num_dests,
                                cfg.moves_per_round)
    drain = np.zeros(c, dtype=bool)
    if masks.excluded_replica_move_brokers is not None:
        drain = np.asarray(drain_hint).astype(bool) \
            if drain_hint is not None \
            else np.asarray(jax.vmap(excluded_hosting_replicas)(
                states, masks.excluded_replica_move_brokers).any(axis=(1, 2)))
    ran = cluster_mask & ((viol0 > 0) | (off0 > 0) | drain)
    if entry_stats is not None and not ran.any():
        # Whole-goal fingerprint skip: no cluster has anything to do, so
        # the goal pays zero batched dispatches (entry/exit stats both
        # come from the snapshot).
        if physical_stats is not None:
            physical_stats.goals_skipped += 1
        if stats is not None:
            for b in range(c):
                if cluster_mask[b]:
                    stats[b].goals_skipped += 1

    donate = donation_enabled(megastep)
    async_rb = bool(megastep.async_readback)
    ring_n = 0
    if flights is not None and flights and flights[0].recording:
        ring_n = flights[0].ring_rounds
    can_donate = [bool(donate_input)]

    def make_enqueue(phase: str):
        def enqueue(st, active, budget: int):
            b = jnp.int32(budget)
            ring = None
            if donate:
                if not can_donate[0]:
                    st = dataclasses.replace(
                        st, assignment=jnp.copy(st.assignment),
                        leader_slot=jnp.copy(st.leader_slot))
                rest = dataclasses.replace(
                    st,
                    assignment=jnp.zeros((c, 0, st.assignment.shape[2]),
                                         st.assignment.dtype),
                    leader_slot=jnp.zeros((c, 0), st.leader_slot.dtype))
                if phase == "move":
                    out = mb_move_don(
                        st.assignment, st.leader_slot, rest, active, idx,
                        prior, goals, constraint, cfg, num_topics, masks,
                        b, ring_rounds=ring_n)
                    a, l, applied, r, act = out[:5]
                    ring = out[5] if ring_n > 0 else None
                else:
                    a, l, applied, r, act = mb_swap_don(
                        st.assignment, st.leader_slot, rest, active, idx,
                        prior, goals, constraint, num_topics, masks, 8,
                        64, b)
                st = dataclasses.replace(st, assignment=a, leader_slot=l)
            elif phase == "move":
                out = mb_move(
                    st, active, idx, prior, goals, constraint, cfg,
                    num_topics, masks, b, ring_rounds=ring_n)
                st, applied, r, act = out[:4]
                ring = out[4] if ring_n > 0 else None
            else:
                st, applied, r, act = mb_swap(
                    st, active, idx, prior, goals, constraint, num_topics,
                    masks, 8, 64, b)
            can_donate[0] = True
            return st, act, applied, r, donate, ring
        return enqueue

    applied_total = np.zeros(c, dtype=np.int64)
    swaps_total = np.zeros(c, dtype=np.int64)
    rounds_total = np.zeros(c, dtype=np.int64)
    direct_moves = np.zeros(c, dtype=np.int64)
    direct_sweeps = np.zeros(c, dtype=np.int64)
    # Direct-assignment pre-pass, batched (analyzer.direct megabatch
    # twins): one dispatch advances EVERY participating cluster's bulk
    # transport in lockstep, with inactive clusters (pad slots, clusters
    # with offline replicas or drains — those keep the full greedy
    # semantics) frozen by the batched early-exit mask; the greedy cycle
    # below polishes the residue. Occupancy stays traced — the direct
    # program compiles once per bucket shape, like every other megabatch
    # kernel.
    use_direct = False
    if megastep.direct_assignment and direct_path_chosen(megastep,
                                                         goal.name):
        from .direct import direct_eligible
        use_direct = direct_eligible(goals, index)
    direct_active = ran & (off0 == 0) & ~drain & (viol0 > 0)
    if use_direct and direct_active.any():
        from .direct import sparse_rounding_seed
        from ..utils.sensors import SENSORS
        if mesh is not None:
            mb_direct = partial(_mbs.megabatch_direct_rounds_sharded, mesh)
            mb_direct_don = partial(
                _mbs.megabatch_direct_rounds_donated_sharded, mesh)
        else:
            from .direct import megabatch_direct_rounds as mb_direct
            from .direct import (
                megabatch_direct_rounds_donated as mb_direct_don,
            )
        active0 = jnp.asarray(direct_active)
        t0 = _time.monotonic()
        if donate:
            if not can_donate[0]:
                states = dataclasses.replace(
                    states, assignment=jnp.copy(states.assignment),
                    leader_slot=jnp.copy(states.leader_slot))
            rest = dataclasses.replace(
                states,
                assignment=jnp.zeros((c, 0, states.assignment.shape[2]),
                                     states.assignment.dtype),
                leader_slot=jnp.zeros((c, 0), states.leader_slot.dtype))
            a, l, mv, sw, _act = mb_direct_don(
                states.assignment, states.leader_slot, rest, active0,
                goals, index, constraint, num_topics, masks,
                megastep.direct_max_sweeps,
                margin_frac=megastep.direct_sparse_margin,
                seed=sparse_rounding_seed(megastep.direct_sparse_salt))
            states = dataclasses.replace(states, assignment=a,
                                         leader_slot=l)
            can_donate[0] = True
        else:
            states, mv, sw, _act = mb_direct(
                states, active0, goals, index, constraint, num_topics,
                masks, megastep.direct_max_sweeps,
                margin_frac=megastep.direct_sparse_margin,
                seed=sparse_rounding_seed(megastep.direct_sparse_salt))
        mv_np = np.asarray(mv)
        sw_np = np.asarray(sw)
        elapsed = _time.monotonic() - t0
        direct_moves += mv_np
        direct_sweeps += sw_np
        applied_total += mv_np
        # ONE physical XLA execution; per-cluster splits skip telemetry
        # (the run_megabatch_pass accounting discipline).
        if physical_stats is not None:
            physical_stats.record("direct", int(sw_np.max()),
                                  donated=donate)
        for b in range(c):
            if stats is not None and sw_np[b] > 0:
                stats[b].record("direct", int(sw_np[b]), donated=donate,
                                telemetry=False)
            if flights is not None and direct_active[b]:
                flights[b].dispatch(
                    "direct", megastep.direct_max_sweeps, int(sw_np[b]),
                    int(mv_np[b]), donated=donate, elapsed_s=elapsed)
        SENSORS.count("solver_direct_sweeps", int(sw_np.max()))
        SENSORS.count("solver_direct_moves", int(mv_np.sum()))
    alive = ran.copy()
    while True:
        # A cluster joins the next move+swap cycle exactly when the serial
        # host loop would: its last swap pass applied something (or this
        # is its first cycle) and its cumulative rounds sit below the cap.
        participate = alive & (rounds_total < cfg.max_rounds)
        if not participate.any():
            break
        active0 = jnp.asarray(participate)
        states, _act, moved, r = run_megabatch_pass(
            make_enqueue("move"), states, active0, cfg.max_rounds,
            dispatch, async_readback=async_rb, stats=stats,
            physical_stats=physical_stats, kind="move", flights=flights)
        applied_total += moved
        rounds_total += r
        if not goal.supports_swap:
            break
        states, _act, swapped, sr = run_megabatch_pass(
            make_enqueue("swap"), states, jnp.asarray(participate), 64,
            dispatch, async_readback=async_rb, stats=stats,
            physical_stats=physical_stats, kind="swap", flights=flights)
        swaps_total += swapped
        applied_total += swapped
        rounds_total += sr
        alive = participate & (swapped > 0)

    if ran.any():
        viol1_d, obj1_d, off1_d = mb_stats(
            states, idx, goals, constraint, num_topics, masks)
        viol1 = np.asarray(viol1_d)
        obj1 = np.asarray(obj1_d)
        off1 = np.asarray(off1_d)
    else:
        viol1, obj1, off1 = viol0, obj0, off0
    # Skipped clusters never ran: their entry stats ARE their exit stats
    # (the batched kernels froze them, but the goal-stats recompute on a
    # frozen state is the same value — use the entry read for exactness).
    viol1 = np.where(ran, viol1, viol0)
    obj1 = np.where(ran, obj1, obj0)
    off1 = np.where(ran, off1, off0)

    infos: list[dict] = []
    for b in range(c):
        if flights is not None and cluster_mask[b]:
            flights[b].exit(violation=float(viol1[b]),
                            objective=float(obj1[b]),
                            offline=int(off1[b]))
        total_violation = float(viol1[b])
        succeeded = total_violation <= 1e-6
        info = {
            "goal": goal.name,
            "rounds": int(rounds_total[b]),
            "moves_applied": int(applied_total[b]),
            "swaps_applied": int(swaps_total[b]),
            "residual_violation": total_violation,
            "succeeded": succeeded,
            "objective": float(obj1[b]),
            "violated_on_entry": float(viol0[b]) > 1e-6,
            "offline_before": int(off0[b]),
            "offline_remaining": int(off1[b]),
        }
        if use_direct:
            info["direct_moves"] = int(direct_moves[b])
            info["direct_sweeps"] = int(direct_sweeps[b])
        if cluster_mask[b] and int(off0[b]) == 0:
            before, after = float(obj0[b]), float(obj1[b])
            if after > before + 1e-4 * max(1.0, abs(before)):
                info["error_type"] = "StatsRegressionError"
                info["error"] = (
                    f"goal {goal.name} regressed its own objective during "
                    f"its optimization: {before:.6g} -> {after:.6g}")
        if cluster_mask[b] and goal.is_hard and not succeeded \
                and "error" not in info:
            info["error_type"] = "OptimizationFailureError"
            info["error"] = (
                f"hard goal {goal.name} unsatisfied: residual violation "
                f"{total_violation:.4f} after {int(rounds_total[b])} rounds")
        infos.append(info)
    return states, infos


def optimize_goal_in_chain(state: ClusterTensors, chain: Sequence[Goal],
                           index: int, constraint: BalancingConstraint,
                           cfg: SearchConfig, num_topics: int,
                           masks: ExclusionMasks | None = None,
                           dispatch_rounds: int = 0,
                           dispatch: AdaptiveDispatch | None = None,
                           wall_budget_s: float = 0.0,
                           megastep: MegastepConfig | None = None,
                           stats: DispatchStats | None = None,
                           donate_input: bool = False,
                           flight=NO_FLIGHT,
                           entry_stats: tuple | None = None,
                           drain_hint: bool | None = None,
                           grid: str = "narrow",
                           ) -> tuple[ClusterTensors, dict]:
    """Run goal ``chain[index]`` to convergence under the acceptance of
    ``chain[:index]``, using the chain-shared kernels (same semantics and
    info dict as ``search.optimize_goal``, one compile for the whole chain).

    ``dispatch_rounds`` > 0 caps the search rounds a SINGLE device dispatch
    may run (the host loops to the same fixed point — identical
    trajectory, more round-trips). This bounds per-dispatch wall-clock: at
    1k+ brokers the unbounded fused drivers run tens of seconds in one
    XLA program, and a bounded dispatch keeps every execution short
    enough for progress reporting and fast-mode wall budgets (whether a
    locally attached chip would tolerate the unbounded program is not
    measured on the current machine).

    Enforces the per-goal stats-regression guard (AbstractGoal.java:111-119):
    the active goal's objective on exit must not exceed its objective on
    entry. Skipped when offline replicas exist at entry — self-healing
    placement takes precedence over the goal's own balance objective
    (ClusterModel.selfHealingEligibleReplicas semantics).

    ``wall_budget_s`` > 0 (fast mode: fast.mode.per.broker.move.timeout.ms
    x num_brokers) stops dispatching further search rounds for this goal
    once its elapsed wall-clock exceeds the budget — the batch-search
    analogue of the reference's per-broker move timeout
    (ResourceDistributionGoal.java:470-475), enforceable at dispatch
    granularity on the bounded path. Hard goals still raise on residual
    violations, exactly like the reference in fast mode.

    ``megastep`` selects the bounded path's dispatch machinery (donation,
    async readback, deficit-aware count-goal sizing; see MegastepConfig);
    None keeps the r9 synchronous non-donating behavior. ``donate_input``
    declares the CALLER relinquishes ``state`` — the first dispatch then
    donates it directly; otherwise it donates a device COPY of the two
    mutable tensors (intermediate states are loop-owned and donated
    as-is). ``stats`` collects per-dispatch accounting.

    ``flight`` (utils.flight_recorder goal hook) records entry/exit
    violations, grid geometry, sizing decisions, and per-dispatch
    telemetry; when it is recording, the MOVE-phase kernels run with the
    per-round stats ring enabled (``ring_rounds``) — reductions only, so
    the trajectory is unchanged (the recorder's parity contract).

    ``entry_stats`` (round 18 fingerprint skip): the goal's
    ``(violation, objective, offline)`` ALREADY computed by the one
    batched pre-chain ``chain_all_goal_stats`` program — valid only while
    no earlier goal has mutated the state since that snapshot (the
    caller's responsibility; the optimizer gates on ``chain_owns_state``).
    With it provided, the per-goal entry stats dispatch is skipped, and a
    goal with nothing to do consumes ZERO dispatches (counted in
    ``stats.goals_skipped``) — byte-identical to the unhinted path, since
    the hint holds the exact values that dispatch would have returned.
    ``drain_hint`` is the matching precomputed drain-pending bool (drain
    is goal-independent, a function of state + masks only).

    ``grid`` names the grid ``cfg`` is, ``"narrow"`` or ``"wide"`` (the
    optimizer's widened grid): the label of the goal's dispatches in the
    ``solver_dispatch*`` series and on their ``solver.dispatch`` spans.
    """
    goal_t0 = _time.monotonic()

    def out_of_time() -> bool:
        return wall_budget_s > 0 \
            and _time.monotonic() - goal_t0 > wall_budget_s

    masks = masks or ExclusionMasks()
    goals = tuple(chain)
    goal = goals[index]
    idx = jnp.int32(index)
    prior = jnp.asarray([j < index for j in range(len(goals))])

    if entry_stats is not None:
        viol0, obj0, offline0 = entry_stats
    else:
        viol0, obj0, offline0 = chain_goal_stats(state, idx, goals,
                                                 constraint, num_topics,
                                                 masks)
    flight.entry(violation=float(viol0), objective=float(obj0),
                 offline=int(offline0))
    total_applied = 0
    total_swaps = 0
    rounds = 0
    bounded = dispatch_rounds > 0
    if bounded and dispatch is None:
        dispatch = AdaptiveDispatch(dispatch_rounds, target_s=0.0)
    donate = donation_enabled(megastep) and bounded
    async_rb = bool(megastep.async_readback) if megastep is not None \
        else False
    drain = False
    if masks.excluded_replica_move_brokers is not None:
        drain = bool(drain_hint) if drain_hint is not None \
            else bool(excluded_hosting_replicas(
                state, masks.excluded_replica_move_brokers).any())
    # Direct-assignment pre-pass eligibility (analyzer.direct): bounded
    # path, kernel enabled for this pass (the optimizer resolves the
    # config flag AND the wide-regime gate into megastep), a
    # guard-representable chain prefix, and a clean model — self-healing
    # (offline replicas) and drains keep the full greedy semantics, the
    # same pause rule as the targeted-destination column.
    use_direct = False
    if bounded and megastep is not None and megastep.direct_assignment \
            and direct_path_chosen(megastep, goal.name) \
            and int(offline0) == 0 and not drain:
        from .direct import direct_eligible
        use_direct = direct_eligible(goals, index)
    if bounded and megastep is not None and megastep.deficit_moves_cap > 0 \
            and goal.count_based and not use_direct:
        # Deficit-aware sizing from the goal's ENTRY violations — a
        # pass-level constant, so the trajectory stays invariant to the
        # dispatch-budget sequence under the sized config.
        base_cfg = cfg
        cfg = deficit_sized_config(cfg, float(viol0),
                                   megastep.deficit_moves_cap)
        flight.sizing(entry_violation=float(viol0),
                      base_moves=base_cfg.moves_per_round,
                      base_sources=base_cfg.num_sources,
                      sized_moves=cfg.moves_per_round,
                      sized_sources=cfg.num_sources,
                      cap=megastep.deficit_moves_cap)
    flight.grid(cfg.num_sources, cfg.num_dests, cfg.moves_per_round)
    # Per-round on-device flight ring: MOVE phases of the single-device
    # chain kernels only (the stats live in the round body; swap phases
    # and the sharded kernels record at dispatch granularity).
    ring_n = flight.ring_rounds if flight.recording else 0
    # Donation gate: the first dispatch consumes the caller's state —
    # donatable only on the caller's say-so; everything after consumes
    # loop-owned intermediates. With donation ON, the first dispatch
    # COPIES the two mutable tensors instead of falling back to the
    # non-donated kernel: a copy is an O(P·RF) device op, while the
    # fallback would compile the full-chain program TWICE (plain +
    # donated — minutes each at scale).
    can_donate = [bool(donate_input)]
    # The bounded move passes' healing rounds, read off each pass's carry.
    healed = [0]

    def run_pass(phase: str, st, pass_cap: int):
        """One logical pass (a single fixed-point loop of up to
        ``pass_cap`` rounds), split into bounded megastep dispatches when
        bounded (round budget sized by ``dispatch``, pumped by
        run_bounded_pass). The per-dispatch cap rides a TRACED budget (no
        recompile per value); a dispatch stopping below its budget hit a
        zero-apply round, i.e. the pass's fixed point. Identical
        trajectory either way — the round sequence is the same, only
        dispatch boundaries differ."""
        if not bounded:
            # One dispatch IS the whole pass (the kernel's static cap
            # equals pass_cap).
            ring = None
            if phase == "move":
                # 3-tuple when ring_n == 0, 4-tuple with the ring
                # appended otherwise (the kernel's static-flag contract).
                out = chain_optimize_rounds(
                    st, idx, prior, goals, constraint, cfg, num_topics,
                    masks, ring_rounds=ring_n)
                st, applied, r = out[:3]
                ring = out[3] if ring_n > 0 else None
            else:
                st, applied, r = chain_swap_rounds(
                    st, idx, prior, goals, constraint, num_topics, masks)
            if stats is not None:
                stats.record(phase, int(r), grid=grid)
            flight.dispatch(phase, pass_cap, int(r), int(applied),
                            ring=ring)
            return st, int(applied), int(r)

        def enqueue(sc, budget: int):
            st, carry = sc
            b = jnp.int32(budget)
            ring = None
            if donate:
                if not can_donate[0]:
                    # Caller retains the input: donate a copy of the two
                    # mutable tensors, never the caller's buffers.
                    st = dataclasses.replace(
                        st, assignment=jnp.copy(st.assignment),
                        leader_slot=jnp.copy(st.leader_slot))
                rest = strip_mutable(st)
                if phase == "move":
                    out = chain_optimize_rounds_donated(
                        st.assignment, st.leader_slot, carry, rest, idx,
                        prior, goals, constraint, cfg, num_topics, masks, b,
                        ring_rounds=ring_n)
                    a, l, applied, r = out[:4]
                else:
                    out = chain_swap_rounds_donated(
                        st.assignment, st.leader_slot, carry, rest, idx,
                        prior, goals, constraint, num_topics, masks, 8, 64,
                        b)
                    a, l, applied, r = out[:4]
                st = dataclasses.replace(st, assignment=a, leader_slot=l)
            elif phase == "move":
                out = chain_optimize_rounds(
                    st, idx, prior, goals, constraint, cfg, num_topics,
                    masks, budget=b, ring_rounds=ring_n, resume=carry)
                st, applied, r = out[:3]
            else:
                out = chain_swap_rounds(
                    st, idx, prior, goals, constraint, num_topics, masks,
                    budget=b, resume=carry)
                st, applied, r = out[:3]
            if phase == "move" and ring_n > 0:
                ring = out[-2]
            can_donate[0] = True
            return (st, out[-1]), applied, r, donate, ring

        def move_counts(sc, span):
            # ccsa: ok[CCSA001] after the pass's last readback: the
            # carry is that dispatch's output, so this waits on nothing
            count_source_fallbacks(span, int(sc[1].source_fallbacks), grid)
            # ccsa: ok[CCSA001] the same finished carry
            healed[0] += int(sc[1].healing_rounds)

        # One carry a pass: the pass's dispatches chain it on device, so
        # any split of the pass walks the same rounds (PassCarry).
        (st, _carry), applied, rounds_run = run_bounded_pass(
            enqueue, (st, start_pass(st, num_topics)), pass_cap, dispatch,
            out_of_time=out_of_time if wall_budget_s > 0 else None,
            async_readback=async_rb, stats=stats, kind=phase,
            flight=flight, grid=grid,
            pass_counts=move_counts if phase == "move" else None)
        return st, applied, rounds_run

    # Fast path (parity with chain_optimize_full's per-goal lax.cond skip
    # and the sharded bounded driver): nothing violated, nothing offline,
    # no drain pending = the search fixed point is immediate — skip the
    # drivers and their dispatch round-trips entirely.
    ran = float(viol0) > 0 or int(offline0) > 0 or drain
    if not ran and entry_stats is not None and stats is not None:
        # Fingerprint skip: the goal consumed ZERO dispatches — its entry
        # stats came from the batched pre-chain snapshot and its exit
        # stats ARE its entry stats (nothing ran).
        stats.goals_skipped += 1
    direct_moves = 0
    direct_sweeps = 0
    if ran and use_direct and float(viol0) > 0:
        # Direct-assignment pre-pass: the bulk transport in ONE dispatch
        # (kind="direct" in stats/flight — its own dispatch series, out
        # of the acceptance-density histogram); the greedy loop below
        # polishes whatever the feasibility masks vetoed.
        from .direct import run_direct_pass
        (state, direct_moves, direct_sweeps, d_donated,
         d_stranded) = run_direct_pass(
            state, goals, index, constraint, num_topics, masks, megastep,
            megastep.direct_max_sweeps, stats=stats, flight=flight,
            donate_input=can_donate[0])
        if d_donated:
            # The direct kernel consumed (a copy of) the mutable pair;
            # its outputs are chain-owned, so later dispatches may donate
            # them directly.
            can_donate[0] = True
        total_applied += direct_moves
        if megastep.deficit_moves_cap > 0 and goal.count_based:
            # Deficit-size the POLISH from the larger of two residual
            # estimates (no extra stats dispatch): viol0 − moves (a
            # transport move fixes at least 1 unit — but margin-depth
            # moves fix 0, so this alone can zero out) and 2× the
            # STRANDED movers the kernel reports at exit (each stranded
            # mover is up to 2 violation units feasibility refused to
            # place). When the transport left a real residue, the polish
            # must not grind it through base-width rounds.
            base_cfg = cfg
            cfg = deficit_sized_config(
                cfg, max(float(viol0) - float(direct_moves),
                         2.0 * float(d_stranded)),
                megastep.deficit_moves_cap)
            if cfg is not base_cfg:
                flight.sizing(entry_violation=float(viol0),
                              base_moves=base_cfg.moves_per_round,
                              base_sources=base_cfg.num_sources,
                              sized_moves=cfg.moves_per_round,
                              sized_sources=cfg.num_sources,
                              cap=megastep.deficit_moves_cap)
                flight.grid(cfg.num_sources, cfg.num_dests,
                            cfg.moves_per_round)
    if ran:
        while rounds < cfg.max_rounds and not out_of_time():
            state, moves, r = run_pass("move", state, cfg.max_rounds)
            total_applied += moves
            rounds += r
            if not goal.supports_swap:
                break
            state, swapped, sr = run_pass("swap", state, 64)
            total_swaps += swapped
            total_applied += swapped
            rounds += sr
            if swapped == 0:
                break

    if ran:
        viol, obj, offline = chain_goal_stats(state, idx, goals, constraint,
                                              num_topics, masks)
    else:
        # Skipped goal: the state is untouched, entry stats ARE exit stats.
        viol, obj, offline = viol0, obj0, offline0
    flight.exit(violation=float(viol), objective=float(obj),
                offline=int(offline))
    if int(offline0) == 0:
        before, after = float(obj0), float(obj)
        if after > before + 1e-4 * max(1.0, abs(before)):
            raise StatsRegressionError(
                f"goal {goal.name} regressed its own objective during its "
                f"optimization: {before:.6g} -> {after:.6g}")
    total_violation = float(viol)
    succeeded = total_violation <= 1e-6
    if goal.is_hard and not succeeded:
        raise OptimizationFailureError(
            f"hard goal {goal.name} unsatisfied: residual violation "
            f"{total_violation:.4f} after {rounds} rounds")
    info = {
        "goal": goal.name,
        "rounds": rounds,
        "moves_applied": total_applied,
        "swaps_applied": total_swaps,
        "residual_violation": total_violation,
        "succeeded": succeeded,
        "objective": float(obj),
        "violated_on_entry": float(viol0) > 1e-6,
        "offline_before": int(offline0),
        "offline_remaining": int(offline),
    }
    if bounded:
        info["healing_rounds"] = healed[0]
        count_healing_rounds(healed[0], grid)
    if use_direct:
        # Direct-pass attribution (keys present only when the direct mode
        # was in force, so the disabled path's info dict stays identical
        # to the pre-direct contract).
        info["direct_moves"] = direct_moves
        info["direct_sweeps"] = direct_sweeps
    return state, info
