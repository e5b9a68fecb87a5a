"""The batched rebalance search: the plain per-goal kernels and the
selection helpers every route shares.

TPU-native replacement for the reference's greedy inner loop
(AbstractGoal.java:82-135 optimize → rebalanceForBroker → one
maybeApplyBalancingAction at a time). Each round, ONE fused kernel:

1. recomputes derived per-broker state,
2. generates a top-k × top-k grid of candidate actions for the active goal,
3. evaluates the active goal's improvement AND every previously-optimized
   goal's acceptance for all candidates (the lexicographic-constraint stack
   of SURVEY.md §A.3 as boolean masks),
4. picks a conflict-free batch of the best improving candidates
   (rank-order cumulative selection, one move a partition), and
5. applies them functionally.

Two things live here (docs/DESIGN.md "The move round"):

- The per-goal kernels (``score_round_candidates``, ``optimize_round(s)``,
  ``swap_round(s)``, ``optimize_goal``), jitted with (goal, optimized)
  STATIC: the plain one-chip EQUIVALENCE ORACLE of the chain kernels
  (tests/test_chain.py, tests/test_analyzer.py). No served route runs
  them. They keep the full recompute of every aggregate and the flat
  per-candidate lookups on purpose and share no scoring code with
  ``analyzer.chain._scored_candidates``: an oracle that shared the
  production body would check nothing.
- The selection and apply helpers the production bodies call
  (``reduce_per_source``, ``cumulative_select``, ``apply_selected``,
  ``swap_grid``, ``apply_swap_selection``, ``run_carry_loop``).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from ..common.resources import Resource
from ..model.tensors import (
    ClusterTensors, flatten_slots, offline_per_broker, offline_replicas,
    slot_coords,
)
from .agg import pot_lbi_deltas
from .candidates import (
    KIND_MOVE, attach_cumulative, compute_deltas, generate_candidates,
    select_sources,
)
from .constraint import BalancingConstraint
from .derived import DerivedState, compute_derived
from .goals.base import Goal

_EPS_IMPROVEMENT = 1e-9
_OFFLINE_BONUS = 1e12
# Relative width of the "these scores are effectively tied" window inside
# which the destination-rotation preference may reorder choices.
_TIE_WINDOW = 0.01


class OptimizationFailureError(RuntimeError):
    """A hard goal could not be satisfied
    (OptimizationFailureException equivalent)."""


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    num_sources: int = 64
    num_dests: int = 32
    moves_per_round: int = 32
    max_rounds: int = 200


@partial(jax.tree_util.register_dataclass,
         data_fields=["excluded_topics", "excluded_replica_move_brokers",
                      "excluded_leadership_brokers"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class ExclusionMasks:
    """Traced boolean masks built from OptimizationOptions by the optimizer."""

    excluded_topics: jax.Array | None = None            # [T] bool
    excluded_replica_move_brokers: jax.Array | None = None  # [B] bool
    excluded_leadership_brokers: jax.Array | None = None    # [B] bool


def goal_aux(goal: Goal, state: ClusterTensors, derived: DerivedState,
             constraint: BalancingConstraint, num_topics: int, psum=None):
    """Per-goal aux tensors; the partition-additive partial is psum'd when a
    mesh hook is given (Goal.prepare_partial/finalize_aux contract). The
    agg-carry read path lives in chain._gated_aux — the per-goal kernels
    here stay recompute-only as the equivalence oracle."""
    partial_aux = goal.prepare_partial(state, num_topics)
    if partial_aux is not None and psum is not None:
        partial_aux = jax.tree.map(psum, partial_aux)
    return goal.finalize_aux(partial_aux, state, derived, constraint)


def reduce_per_source(score: jax.Array,
                      layout: tuple[tuple[int, int], ...],
                      row_offset: jax.Array | int = 0,
                      extra_last_col: bool = False) -> jax.Array:
    """Per-source best-destination reduction: each [rows × cols] grid block
    collapses to one candidate per source replica. Without this, equal
    scores cluster one partition's candidates at the head of the global
    sort and the conflict dedup throws most of the round away.

    Tie-breaking: among the columns whose score is within a small relative
    window of the row's best, prefer column ((row + row_offset) mod cols),
    then the next, etc. This spreads near-tied sources across DIFFERENT
    destinations — otherwise all sources chase the single most-attractive
    destination and the one-move-per-destination conflict rule caps the
    round at one move. Columns outside the tie window are never chosen, so
    a genuinely better candidate (e.g. the only one fixing a tiny capacity
    violation) cannot be displaced. ``row_offset`` decorrelates devices in
    the mesh body (parallel.chain_sharded).

    ``extra_last_col``: the FIRST block's last column is the targeted-
    destination column (generate_candidates ``extra_dst``); it is kept
    OUT of the rotation cycle (rank = cols, i.e. last among ties) so its
    mere presence cannot perturb the rotation arithmetic of the shared
    destinations — an all-invalid targeted column then selects
    bit-identically to no column at all (measured: the modulo shift
    alone flipped the 1k drain-50 fixture 86.0 → 82.74). A targeted
    destination still wins whenever it scores strictly above the tie
    window, which is what it is for."""
    red_parts = []
    offset = 0
    for block_i, (rows, cols) in enumerate(layout):
        block = score[offset:offset + rows * cols].reshape(rows, cols)
        finite = jnp.isfinite(block)
        safe = jnp.where(finite, block, -jnp.inf)
        row_max = safe.max(axis=1, keepdims=True)
        window = _TIE_WINDOW * jnp.maximum(jnp.abs(row_max), 1e-6)
        tied = finite & (safe >= row_max - window)

        rot_cols = cols - 1 if (extra_last_col and block_i == 0) else cols
        col_ids = jnp.arange(cols, dtype=jnp.int32)[None, :]
        row_ids = jnp.arange(rows, dtype=jnp.int32)[:, None] + row_offset
        # Rotation rank: 0 for the row's preferred column, increasing
        # after; the extra column (if any) ranks last among ties.
        rot = jnp.where(col_ids < rot_cols,
                        (col_ids - row_ids) % max(rot_cols, 1), cols)
        best_col = jnp.argmin(jnp.where(tied, rot, cols + 1), axis=1)
        # Rows with no tied (finite) column keep plain argmax (all -inf:
        # conflict selection drops them anyway).
        best_col = jnp.where(tied.any(axis=1), best_col, jnp.argmax(safe, axis=1))
        red_parts.append(offset + jnp.arange(rows) * cols + best_col)
        offset += rows * cols
    return jnp.concatenate(red_parts)


@jax.named_scope("round.select")
def cumulative_select(state: ClusterTensors, deltas, score: jax.Array,
                      layout, m: int, moves_cap: int,
                      independent: bool | jax.Array, recheck,
                      extra_last_col: bool = False):
    """Conflict selection with JOINT acceptance instead of broker dedupe.

    The old rule admitted at most ONE move per src/dst broker per round
    (scatter-min dedupe), because each candidate's acceptance was judged
    against round-start aggregates — sound but it serialized per-broker
    throughput (~num_dests accepted moves/round at scale). Here the top-m
    candidates (rank order, one per partition) get pairwise CUMULATIVE
    pre-deltas (attach_cumulative), and ``recheck(sub, has_earlier)``
    re-evaluates every stacked goal's acceptance with those shifts: many
    moves may share a broker as long as their joint effect stays inside
    every goal's bands/limits.

    Returns (top_idx into the full grid, sel mask, selected sub-batch,
    pot_delta, lbi_delta) — the latter three so aggregate-carrying drivers
    can scatter the batch's effect without re-deriving it."""
    red_idx = reduce_per_source(score, layout, extra_last_col=extra_last_col)
    red_score = score[red_idx]
    k = min(m, red_score.shape[0])
    top_score, top_i = jax.lax.top_k(red_score, k)
    idx = red_idx[top_i]
    ok = top_score > _EPS_IMPROVEMENT
    rank = jnp.arange(k, dtype=jnp.int32)
    big = jnp.int32(k + 1)
    rank_eff = jnp.where(ok, rank, big)
    sel_p = deltas.partition[idx]
    first_p = jnp.full(state.num_partitions, big, jnp.int32) \
        .at[sel_p].min(rank_eff)
    part_ok = ok & (first_p[sel_p] == rank)

    sub = jax.tree.map(lambda a: a[idx], deltas.without_grid())
    pot, lbi = pot_lbi_deltas(state, sub)
    sub, has_earlier = attach_cumulative(sub, part_ok, pot, lbi)
    sel = part_ok & recheck(sub, has_earlier)
    within_cap = jnp.cumsum(sel.astype(jnp.int32)) <= moves_cap
    if independent is True:
        pass
    elif independent is False:
        sel &= within_cap
    else:
        sel &= jnp.where(independent, True, within_cap)
    return idx, sel, sub, pot, lbi


def run_carry_loop(round_body, carry0, max_rounds: int, budget=None,
                   last0=None):
    """Generic fused-driver scaffold: iterate ``round_body(carry, rounds)
    -> (carry, applied)`` under ``lax.while_loop`` until a round applies
    nothing (or ``max_rounds``) entirely on device — ONE host round-trip
    for the whole loop. ``carry0`` is any pytree (the incremental-aggregate
    drivers carry (state, AggCarry)). Returns (final_carry, total_applied,
    rounds_run).

    ``budget`` (optional TRACED int) further caps the rounds this call may
    run without recompiling per value — the bounded-dispatch driver passes
    the remaining global round budget so a dispatch never overshoots
    ``cfg.max_rounds`` (the static ``max_rounds`` alone would admit up to
    a full dispatch past it).

    This loop IS the megastep (docs/DESIGN.md round 10): the while carry
    ``(carry, total, rounds, last_applied)`` keeps the early-exit flag —
    ``last_applied == 0`` — on device, so a budget-K dispatch that reaches
    its fixed point mid-budget freezes the state and stops WITHOUT a host
    round-trip; the host detects convergence purely from the returned
    ``rounds_run < budget``. That detectability is what the async
    readback pump and its speculative post-convergence dispatch rely on
    (chain.run_bounded_pass).

    ``last0`` (optional TRACED int) resumes a pass that an earlier call
    left: the applied count of its last round, so a call that starts on
    a fixed point (``last0 == 0``) runs no round at all. With it the loop
    also returns its own last applied count, the next call's ``last0``:
    (final_carry, total_applied, rounds_run, last_applied)."""
    cap = max_rounds if budget is None else jnp.minimum(
        jnp.int32(max_rounds), budget.astype(jnp.int32))

    def cond(c):
        _carry, _total, rounds, last = c
        return (last > 0) & (rounds < cap)

    def body(c):
        carry, total, rounds, _last = c
        carry, applied = round_body(carry, rounds)
        applied = applied.astype(jnp.int32)
        return carry, total + applied, rounds + 1, applied

    final, total, rounds, last = jax.lax.while_loop(
        cond, body, (carry0, jnp.int32(0), jnp.int32(0),
                     jnp.int32(1) if last0 is None
                     else last0.astype(jnp.int32)))
    if last0 is None:
        return final, total, rounds
    return final, total, rounds, last


def run_rounds_loop(round_body, state: ClusterTensors, max_rounds: int,
                    budget=None,
                    ) -> tuple[ClusterTensors, jax.Array, jax.Array]:
    """State-only wrapper of :func:`run_carry_loop` — iterate
    ``round_body(state) -> (new_state, applied)`` to its fixed point.
    Returns (final_state, total_applied, rounds_run). Used by the per-goal
    kernels (the equivalence oracles) and any driver without an aggregate
    carry."""
    return run_carry_loop(lambda s, _r: round_body(s), state, max_rounds,
                          budget=budget)


def score_round_candidates(state: ClusterTensors, masks: ExclusionMasks,
                           goal: Goal, optimized: tuple[Goal, ...],
                           constraint: BalancingConstraint, cfg: SearchConfig,
                           num_topics: int):
    """The oracle's scoring half, for ONE static goal: derived state →
    candidate grid → lexicographic acceptance stack → scored candidates.
    The production half is ``chain._scored_candidates`` (traced goal index,
    aggregate carry, grid lookups, the mesh keyword); this one recomputes
    everything and looks tables up per candidate, and must stay
    independent of it (module docstring).

    Returns (cand, deltas, score, layout, (derived, aux, aux_by_goal))."""
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)
    aux = goal_aux(goal, state, derived, constraint, num_topics)
    aux_by_goal = {g.name: goal_aux(g, state, derived, constraint, num_topics)
                   for g in optimized}

    src_score = goal.source_score(state, derived, constraint, aux)
    dst_score = goal.dest_score(state, derived, constraint, aux)
    weight = goal.replica_weight(state, derived, constraint, aux)

    # Self-healing has priority: replicas stranded on dead brokers are
    # always sources with maximal weight, and moving one scores a large
    # bonus so it wins over pure balance refinements
    # (ClusterModel.selfHealingEligibleReplicas / _fixOfflineReplicasOnly).
    off = offline_replicas(state)  # [P, S]
    if not goal.leadership_only:
        src_score = src_score + offline_per_broker(state, off)
        weight = jnp.where(off, 1e30, weight)  # finite: top-k validity uses isfinite

    # Targeted destination column (Goal.target_dests over the shared
    # source selection, analyzer.fill), scale-gated (targets_enabled).
    # Where enabled, it is appended for every non-leadership goal — goals
    # without a target rule get an all-invalid column — so the per-goal
    # and chain kernels share one move-block column count.
    from .fill import targets_enabled
    extra = sources = None
    if targets_enabled(state.num_partitions) and not goal.leadership_only:
        sources = select_sources(state, src_score, weight, cfg.num_sources)
        cand_p, cand_s, src_valid, _on_source, _fallback = sources
        extra = goal.target_dests(state, derived, constraint, aux,
                                  cand_p, cand_s, src_valid)
        if extra is None:
            extra = (jnp.zeros_like(cand_p),
                     jnp.zeros(cand_p.shape, dtype=bool))
        else:
            # Targets pause while any offline replica exists (see
            # chain._scored_candidates).
            extra = (extra[0], extra[1] & ~off.any())

    cand, layout = generate_candidates(state, derived, src_score, dst_score, weight,
                                       cfg.num_sources, cfg.num_dests,
                                       goal.include_leadership, goal.leadership_only,
                                       extra_dst=extra, sources=sources)
    deltas = compute_deltas(state, derived, cand)

    accept = deltas.valid
    for g in optimized:
        accept &= g.acceptance(state, derived, constraint,
                               aux_by_goal[g.name], deltas)

    moving_offline = deltas.at_src_slot(off) & (deltas.replica_delta > 0)
    imp = goal.improvement(state, derived, constraint, aux, deltas)
    imp = jnp.where(moving_offline & jnp.isfinite(imp) & deltas.valid,
                    jnp.maximum(imp, 0.0) + _OFFLINE_BONUS, imp)
    score = jnp.where(accept, imp, -jnp.inf)
    return cand, deltas, score, layout, (derived, aux, aux_by_goal)


@jax.named_scope("round.apply")
def apply_selected(state: ClusterTensors, sel: jax.Array, sel_p: jax.Array,
                   sel_slot: jax.Array, sel_dst_b: jax.Array,
                   sel_kind: jax.Array, sel_dst_slot: jax.Array,
                   row_offset: jax.Array | int = 0) -> ClusterTensors:
    """Apply a selected move batch functionally. ``sel_p`` holds partition
    row ids relative to ``row_offset`` + local rows (global ids in the
    sharded path); rows outside [0, P_local) and non-selected rows route out
    of bounds — JAX scatters drop OOB indices, so duplicate candidate rows
    can never overwrite an accepted move with a stale no-op value."""
    p_local = state.num_partitions
    local_row = sel_p - row_offset
    in_range = (local_row >= 0) & (local_row < p_local)
    is_move = sel_kind == KIND_MOVE
    p_pad = jnp.int32(p_local)

    move_rows = jnp.where(sel & is_move & in_range, local_row, p_pad)
    new_assignment = state.assignment.at[move_rows, sel_slot].set(
        sel_dst_b.astype(state.assignment.dtype), mode="drop")

    lead_rows = jnp.where(sel & ~is_move & in_range, local_row, p_pad)
    new_leader = state.leader_slot.at[lead_rows].set(
        sel_dst_slot.astype(state.leader_slot.dtype), mode="drop")

    return dataclasses.replace(state, assignment=new_assignment,
                               leader_slot=new_leader)


def _per_broker_top_replicas(state: ClusterTensors, weight: jax.Array,
                             brokers: jax.Array, j: int, largest: bool):
    """For each broker in ``brokers[K]``: the j best replicas it hosts by
    ``weight[P, S]`` (largest or smallest). Returns (flat_idx[K, j],
    valid[K, j]) into the ``flatten_slots`` replica axis (decode with
    ``slot_coords``)."""
    from ..model.tensors import broker_segments, replica_exists
    exists = replica_exists(state)
    seg = broker_segments(state)
    flat_w = flatten_slots(jnp.where(exists, weight, jnp.nan))

    def one(broker):
        on_b = (seg == broker) & jnp.isfinite(flat_w)
        key = jnp.where(on_b, flat_w if largest else -flat_w, -jnp.inf)
        vals, idx = jax.lax.top_k(key, j)
        return idx, jnp.isfinite(vals)

    return jax.vmap(one)(brokers)


def swap_brokers(derived: DerivedState, src_score: jax.Array,
                 dst_score: jax.Array, k: int):
    """The ``k`` overloaded brokers and the ``k`` counterparties of a swap
    round: (src_brokers, src_ok, dst_brokers, dst_ok). A swap places a
    replica on BOTH ends, so both are taken among
    ``derived.replica_dest_ok`` alone: with a NEW broker present swaps run
    between new brokers only (the scale-out's rule), and a broker excluded
    from replica moves is on neither side. Swap sources are never offline:
    no exemption here. Shared by ``swap_grid`` and the mesh's swap body."""
    ok = derived.replica_dest_ok
    src_vals, src_brokers = jax.lax.top_k(
        jnp.where(ok & (src_score > 0), src_score, -jnp.inf), k)
    dst_vals, dst_brokers = jax.lax.top_k(
        jnp.where(ok, dst_score, -jnp.inf), k)
    return (src_brokers, jnp.isfinite(src_vals),
            dst_brokers, jnp.isfinite(dst_vals))


def prior_card_dest_ok(goals: Sequence[Goal], prior_mask: jax.Array,
                       state: ClusterTensors, cand_p: jax.Array,
                       cand_s: jax.Array) -> "jax.Array | None":
    """[k, B] bool: the brokers each source card may enter under the PRIOR
    goals, as far as their acceptance depends on the card alone
    (``Goal.card_dest_ok``: a rack rule reads the racks of the partition's
    other replicas), or None where no goal of the chain has such a rule
    (trace-time). The ONE place destination construction learns of it
    (docs/DESIGN.md "Destinations under a rack rule"): whatever builds a
    destination for a card reads this, and acceptance still judges every
    candidate. A goal's rule counts once the goal is prior (traced)."""
    ok = None
    for i, g in enumerate(goals):
        rule = g.card_dest_ok(state, cand_p, cand_s)
        if rule is not None:
            rule = rule | ~prior_mask[i]
            ok = rule if ok is None else ok & rule
    return ok


def swap_counterparties(derived: DerivedState, dst_score: jax.Array,
                        src_may: jax.Array, k: int):
    """The counterparties of a swap round where the prior goals rule
    brokers out card by card (``src_may`` [K, B]: the brokers SOME heavy
    replica of each overloaded broker may enter,
    ``search.prior_card_dest_ok``): each overloaded broker's OWN ``k``
    best-scored brokers among those it may reach, where ``swap_brokers``
    takes the ``k`` best-scored overall for all of them, which may all lie
    where this broker's replicas cannot go (three zones at RF 3: in
    another zone). With nothing ruled out every row is ``swap_brokers``'
    counterparties. Returns (dst_brokers [K, k], dst_ok [K, k])."""
    key = jnp.where(derived.replica_dest_ok[None, :] & src_may,
                    dst_score[None, :], -jnp.inf)
    vals, brokers = jax.lax.top_k(key, k)
    return brokers, jnp.isfinite(vals)


def swap_grid(state: ClusterTensors, derived: DerivedState,
              src_score: jax.Array, dst_score: jax.Array, weight: jax.Array,
              light_weight: jax.Array, card_dest_ok,
              k_brokers: int = 8, j_replicas: int = 4):
    """The swap candidate grid (AbstractGoal.maybeApplySwapAction:287 + the
    swap search of ResourceDistributionGoal.java:599-687), batched:

    top-k overloaded brokers × top-k donors × (j heaviest source replicas ×
    j lightest destination replicas) → K·K·j·j swap candidates. The source
    replica must outweigh the destination replica (maxSourceReplicaLoad: a
    swap always decreases the overloaded side, :599-687): the heaviest are
    the first by ``weight`` (the move grid's order), the lightest and the
    comparison are by ``light_weight`` (``Goal.swap_light_weight``).

    ``card_dest_ok`` (``(partition [n], slot [n]) -> [n, B] bool | None``,
    the chain's ``prior_card_dest_ok``): where it rules a broker out for
    an overloaded broker's heavy replicas, every overloaded broker meets
    its OWN K donors (``swap_counterparties``), the same grid shape over
    K x K donor slots; where it rules nothing out, or answers None,
    ``swap_brokers``' one list.

    Returns (fwd, rev, net, p1, s1, p2, s2, src_b, dst_b, base_valid) where
    fwd/rev are the directional move legs and net the net transfer."""
    from .candidates import CandidateDeltas

    k = min(k_brokers, state.num_brokers)
    src_brokers, src_b_ok, dst_brokers, dst_b_ok = swap_brokers(
        derived, src_score, dst_score, k)

    heavy_idx, heavy_ok = _per_broker_top_replicas(
        state, weight, src_brokers, j_replicas, largest=True)    # [K, j]
    s_dim = state.max_replication_factor

    def lightest(brokers):
        return _per_broker_top_replicas(state, light_weight, brokers,
                                        j_replicas, largest=False)

    def one_list(_):
        """``swap_brokers``' K donors for every overloaded broker."""
        light_idx, light_ok = lightest(dst_brokers)              # [K, j]
        return tuple(jnp.broadcast_to(x[None], (k,) + x.shape) for x in
                     (dst_brokers, dst_b_ok, light_idx, light_ok))

    may_enter = card_dest_ok(
        *slot_coords(heavy_idx.reshape(-1), state.num_partitions, s_dim))
    if may_enter is None:
        dst_brokers, dst_b_ok, light_idx, light_ok = one_list(None)
    else:
        # [K, B]: the brokers some heavy replica of each overloaded broker
        # may enter; a row that offers no swap rules nothing out
        src_may = (may_enter.reshape(k, j_replicas, -1)
                   & heavy_ok[:, :, None]).any(axis=1) \
            | ~(src_b_ok & heavy_ok.any(axis=1))[:, None]

        def own_lists(_):
            brokers, ok = swap_counterparties(derived, dst_score, src_may, k)
            light_idx, light_ok = lightest(brokers.reshape(-1))
            return (brokers, ok, light_idx.reshape(k, k, j_replicas),
                    light_ok.reshape(k, k, j_replicas))

        # With nothing ruled out every own list IS the one list. The cond
        # is for the cells where the rule seldom bites: seeking the K x K
        # donors' lightest replicas on every swap round read +2.04 % on
        # ``round.ms_per_round`` at 250 brokers / 8 racks, the cond +0.64 %
        # (PERF.md section 6, PR 34).
        dst_brokers, dst_b_ok, light_idx, light_ok = jax.lax.cond(
            src_may.all(), one_list, own_lists, None)

    # Grid: [K_src, K_dst, j, j] flattened.
    n = k * k * j_replicas * j_replicas
    si, di, ai, bi = jnp.meshgrid(jnp.arange(k), jnp.arange(k),
                                  jnp.arange(j_replicas),
                                  jnp.arange(j_replicas), indexing="ij")
    si, di, ai, bi = (x.reshape(-1) for x in (si, di, ai, bi))
    src_b = src_brokers[si]
    dst_b = dst_brokers[si, di]
    a_flat = heavy_idx[si, ai]
    b_flat = light_idx[si, di, bi]
    p1, s1 = slot_coords(a_flat, state.num_partitions, s_dim)
    p2, s2 = slot_coords(b_flat, state.num_partitions, s_dim)

    base_valid = src_b_ok[si] & dst_b_ok[si, di] & heavy_ok[si, ai] \
        & light_ok[si, di, bi] & (src_b != dst_b) \
        & derived.movable_partition[p1] & derived.movable_partition[p2]
    # Distinct partitions, cross-hosting checks.
    base_valid &= p1 != p2
    base_valid &= ~(state.assignment[p1] == dst_b[:, None]).any(axis=1)
    base_valid &= ~(state.assignment[p2] == src_b[:, None]).any(axis=1)
    # The swap must shrink the overloaded side.
    w_a = light_weight[p1, s1]
    w_b = light_weight[p2, s2]
    base_valid &= w_a > w_b

    # Load vectors travel with the replicas (leadership keeps its replica).
    lead1 = (state.leader_slot[p1] == s1)
    lead2 = (state.leader_slot[p2] == s2)
    # A leader leg may not land on a leadership-excluded broker
    # (GoalUtils.eligibleReplicasForSwap:266 — swap sources are never
    # offline, so no self-healing carve-out is needed here).
    base_valid &= (~lead1) | derived.allowed_leadership[dst_b]
    base_valid &= (~lead2) | derived.allowed_leadership[src_b]
    load_a = jnp.where(lead1[:, None], state.leader_load[p1],
                       state.follower_load[p1])
    load_b = jnp.where(lead2[:, None], state.leader_load[p2],
                       state.follower_load[p2])

    def leg(partition, slot, load_vec, lead, src, dst, valid):
        return CandidateDeltas(
            src_broker=jnp.where(valid, src, 0),
            dst_broker=jnp.where(valid, dst, 0),
            load_delta=jnp.where(valid[:, None], load_vec, 0.0),
            replica_delta=valid.astype(jnp.int32),
            leader_delta=(valid & lead).astype(jnp.int32),
            partition=partition, topic=state.topic[partition],
            src_slot=jnp.where(valid, slot, 0),
            dst_slot=jnp.zeros(n, dtype=jnp.int32), valid=valid)

    fwd = leg(p1, s1, load_a, lead1, src_b, dst_b, base_valid)
    rev = leg(p2, s2, load_b, lead2, dst_b, src_b, base_valid)
    net = CandidateDeltas(
        src_broker=fwd.src_broker, dst_broker=fwd.dst_broker,
        load_delta=jnp.where(base_valid[:, None], load_a - load_b, 0.0),
        replica_delta=jnp.zeros(n, dtype=jnp.int32),
        leader_delta=jnp.where(base_valid,
                               lead1.astype(jnp.int32) - lead2.astype(jnp.int32),
                               0),
        partition=p1, topic=state.topic[p1],
        src_slot=fwd.src_slot, dst_slot=jnp.zeros(n, dtype=jnp.int32),
        valid=base_valid)
    return fwd, rev, net, p1, s1, p2, s2, src_b, dst_b, base_valid


def swap_round_candidates(state: ClusterTensors, masks: ExclusionMasks,
                          goal: Goal, optimized: tuple[Goal, ...],
                          constraint: BalancingConstraint, num_topics: int,
                          k_brokers: int = 8, j_replicas: int = 4):
    """Per-goal swap scoring: the swap grid under the active goal's scores,
    with every previously-optimized goal's swap acceptance (the
    lexicographic stack applied to both legs / the net transfer)."""
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)
    aux = goal_aux(goal, state, derived, constraint, num_topics)
    aux_by_goal = {g.name: goal_aux(g, state, derived, constraint, num_topics)
                   for g in optimized}

    src_score = goal.source_score(state, derived, constraint, aux)
    dst_score = goal.swap_dest_score(state, derived, constraint, aux)
    weight = goal.replica_weight(state, derived, constraint, aux)

    fwd, rev, net, p1, s1, p2, s2, src_b, dst_b, base_valid = swap_grid(
        state, derived, src_score, dst_score, weight,
        goal.swap_light_weight(state, derived, constraint, aux),
        partial(prior_card_dest_ok, optimized,
                jnp.ones(len(optimized), dtype=bool), state),
        k_brokers, j_replicas)
    accept = base_valid
    for g in optimized:
        accept &= g.swap_acceptance(state, derived, constraint,
                                    aux_by_goal[g.name], fwd, rev, net)
    imp = goal.swap_improvement(state, derived, constraint, aux, fwd, rev,
                                net)
    score = jnp.where(accept, imp, -jnp.inf)
    return score, p1, s1, p2, s2, src_b, dst_b


def apply_swap_selection(state: ClusterTensors, score: jax.Array,
                         p1: jax.Array, s1: jax.Array, p2: jax.Array,
                         s2: jax.Array, src_b: jax.Array, dst_b: jax.Array,
                         moves: int = 8,
                         ) -> tuple[ClusterTensors, jax.Array, jax.Array, jax.Array]:
    """Select + apply a conflict-free batch of scored swaps. Returns
    (new_state, num_applied, top_idx, sel) — the selection indices/mask so
    aggregate-carrying drivers can scatter the swap's effect onto the
    carry.

    Selection: no two accepted swaps may share ANY partition (p1 or p2,
    across roles — else one partition could gain two replicas on a broker
    or a later scatter could half-overwrite an earlier swap) nor ANY
    broker (src or dst, across roles). One scatter array per key space,
    fed from both roles."""
    k = min(moves, score.shape[0])
    top_score, top_idx = jax.lax.top_k(score, k)
    ok = top_score > _EPS_IMPROVEMENT
    rank = jnp.arange(k, dtype=jnp.int32)
    big = jnp.int32(k + 1)
    rank_eff = jnp.where(ok, rank, big)
    sel_p1, sel_p2 = p1[top_idx], p2[top_idx]
    sel_src, sel_dst = src_b[top_idx], dst_b[top_idx]
    first_part = jnp.full(state.num_partitions, big, jnp.int32) \
        .at[sel_p1].min(rank_eff).at[sel_p2].min(rank_eff)
    first_broker = jnp.full(state.num_brokers, big, jnp.int32) \
        .at[sel_src].min(rank_eff).at[sel_dst].min(rank_eff)
    sel = ok & (first_part[sel_p1] == rank) & (first_part[sel_p2] == rank) \
        & (first_broker[sel_src] == rank) & (first_broker[sel_dst] == rank)

    p_pad = jnp.int32(state.num_partitions)
    rows1 = jnp.where(sel, p1[top_idx], p_pad)
    rows2 = jnp.where(sel, p2[top_idx], p_pad)
    new_assignment = state.assignment \
        .at[rows1, s1[top_idx]].set(dst_b[top_idx].astype(state.assignment.dtype),
                                    mode="drop") \
        .at[rows2, s2[top_idx]].set(src_b[top_idx].astype(state.assignment.dtype),
                                    mode="drop")
    return (dataclasses.replace(state, assignment=new_assignment), sel.sum(),
            top_idx, sel)


def _swap_round_body(state: ClusterTensors, goal: Goal,
                     optimized: tuple[Goal, ...],
                     constraint: BalancingConstraint, num_topics: int,
                     masks: ExclusionMasks, moves: int = 8,
                     ) -> tuple[ClusterTensors, jax.Array]:
    """One batched swap round (traced body)."""
    score, p1, s1, p2, s2, src_b, dst_b = swap_round_candidates(
        state, masks, goal, optimized, constraint, num_topics)
    new_state, applied, _idx, _sel = apply_swap_selection(
        state, score, p1, s1, p2, s2, src_b, dst_b, moves)
    return new_state, applied


@partial(jax.jit, static_argnames=("goal", "optimized", "constraint",
                                   "num_topics", "moves"))
def swap_round(state: ClusterTensors, goal: Goal, optimized: tuple[Goal, ...],
               constraint: BalancingConstraint, num_topics: int,
               masks: ExclusionMasks, moves: int = 8,
               ) -> tuple[ClusterTensors, jax.Array]:
    """One batched swap round. Returns (new_state, num_swaps_applied)."""
    return _swap_round_body(state, goal, optimized, constraint, num_topics,
                            masks, moves)


def _round_body(state: ClusterTensors, goal: Goal, optimized: tuple[Goal, ...],
                constraint: BalancingConstraint, cfg: SearchConfig,
                num_topics: int, masks: ExclusionMasks,
                ) -> tuple[ClusterTensors, jax.Array]:
    """One search round (traced body shared by optimize_round and the fused
    on-device driver)."""
    cand, deltas, score, layout, (derived, aux, aux_by) = \
        score_round_candidates(state, masks, goal, optimized, constraint,
                               cfg, num_topics)

    independent = goal.independent_per_broker and not optimized
    m = max(cfg.moves_per_round, cfg.num_sources)

    def recheck(sub, has_earlier):
        """Joint acceptance of the selected batch: every stacked goal with
        cumulative pre-deltas, plus the ACTIVE goal's own acceptance for
        candidates that interact with an earlier one (guards against
        jointly overshooting its own band; the first candidate per broker
        keeps single-candidate semantics)."""
        a = jnp.ones(sub.valid.shape[0], dtype=bool)
        for g in optimized:
            a &= g.acceptance(state, derived, constraint, aux_by[g.name], sub)
        a &= (~has_earlier) | goal.acceptance(state, derived, constraint,
                                              aux, sub)
        return a

    from .fill import targets_enabled
    top_idx, sel, _sub, _pot, _lbi = cumulative_select(
        state, deltas, score, layout, m, cfg.moves_per_round, independent,
        recheck,
        extra_last_col=targets_enabled(state.num_partitions)
        and not goal.leadership_only)
    new_state = apply_selected(
        state, sel, deltas.partition[top_idx], deltas.src_slot[top_idx],
        deltas.dst_broker[top_idx], cand.kind[top_idx], cand.dst_slot[top_idx])
    return new_state, sel.sum()


@partial(jax.jit, static_argnames=("goal", "optimized", "constraint", "cfg",
                                   "num_topics"))
def optimize_round(state: ClusterTensors, goal: Goal,
                   optimized: tuple[Goal, ...], constraint: BalancingConstraint,
                   cfg: SearchConfig, num_topics: int,
                   masks: ExclusionMasks) -> tuple[ClusterTensors, jax.Array]:
    """One fused search round for ``goal``. Returns (new_state, num_applied)."""
    return _round_body(state, goal, optimized, constraint, cfg, num_topics,
                       masks)


@partial(jax.jit, static_argnames=("goal", "optimized", "constraint", "cfg",
                                   "num_topics"))
def optimize_rounds(state: ClusterTensors, goal: Goal,
                    optimized: tuple[Goal, ...],
                    constraint: BalancingConstraint, cfg: SearchConfig,
                    num_topics: int, masks: ExclusionMasks,
                    ) -> tuple[ClusterTensors, jax.Array, jax.Array]:
    """The FUSED multi-round driver: `lax.while_loop` runs search rounds
    until convergence (or cfg.max_rounds) entirely on device — ONE host
    round-trip per goal instead of one per round. This is what makes the
    solver viable over a high-latency device link (and faster everywhere:
    no per-round dispatch).

    Returns (final_state, total_moves, rounds_run)."""
    return run_rounds_loop(
        lambda s: _round_body(s, goal, optimized, constraint, cfg,
                              num_topics, masks),
        state, cfg.max_rounds)


@partial(jax.jit, static_argnames=("goal", "optimized", "constraint",
                                   "num_topics", "moves", "max_rounds"))
def swap_rounds(state: ClusterTensors, goal: Goal, optimized: tuple[Goal, ...],
                constraint: BalancingConstraint, num_topics: int,
                masks: ExclusionMasks, moves: int = 8, max_rounds: int = 64,
                ) -> tuple[ClusterTensors, jax.Array, jax.Array]:
    """Fused swap-phase driver (while_loop analogue of optimize_rounds)."""
    return run_rounds_loop(
        lambda s: _swap_round_body(s, goal, optimized, constraint,
                                   num_topics, masks, moves),
        state, max_rounds)


def optimize_goal(state: ClusterTensors, goal: Goal,
                  optimized: Sequence[Goal], constraint: BalancingConstraint,
                  cfg: SearchConfig, num_topics: int,
                  masks: ExclusionMasks | None = None,
                  ) -> tuple[ClusterTensors, dict]:
    """Run rounds for one goal until converged (no applicable improving
    action) or the round cap. Host reads one scalar per round.

    Raises OptimizationFailureError if a hard goal still has violations
    after convergence (Goal.java:53-59 semantics).
    """
    masks = masks or ExclusionMasks()
    opt_tuple = tuple(optimized)
    total_applied = 0
    total_swaps = 0
    rounds = 0
    # Fused drivers: ONE device call runs the whole move loop to
    # convergence; swap phases interleave only for swap-capable goals
    # (ResourceDistributionGoal.java:421-430: swaps after moves stall).
    while rounds < cfg.max_rounds:
        state, moves, r = optimize_rounds(
            state, goal, opt_tuple, constraint, cfg, num_topics, masks)
        total_applied += int(moves)
        rounds += int(r)
        if not goal.supports_swap:
            break
        state, swapped, sr = swap_rounds(
            state, goal, opt_tuple, constraint, num_topics, masks)
        swapped = int(swapped)
        total_swaps += swapped
        total_applied += swapped
        rounds += int(sr)
        if swapped == 0:
            break

    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)
    aux = goal.prepare(state, derived, constraint, num_topics)
    violations = goal.broker_violations(state, derived, constraint, aux)
    objective = float(goal.objective(state, derived, constraint, aux))
    total_violation = float(violations.sum())
    offline_remaining = int(offline_replicas(state).sum())
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
        "objective": objective,
        "offline_remaining": offline_remaining,
    }
    return state, info
