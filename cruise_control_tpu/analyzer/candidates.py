"""Candidate actions: batched generation and delta evaluation.

The TPU-native replacement for the reference's per-replica greedy inner loop
(AbstractGoal.rebalanceForBroker → maybeApplyBalancingAction): instead of
trying one action at a time, the solver materializes a fixed-size batch of
candidate actions each round, evaluates every goal's acceptance and the
active goal's improvement for ALL of them in one fused kernel, and applies a
conflict-free subset.

A candidate is (kind, partition, src_slot, dst_broker, dst_slot):
- kind 0 = INTER_BROKER_REPLICA_MOVEMENT: replica at (partition, src_slot)
  moves to dst_broker (keeps leadership if it was the leader).
- kind 1 = LEADERSHIP_MOVEMENT: leadership transfers from the current leader
  slot to dst_slot (dst_broker is derived = broker of dst_slot).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..model.tensors import (
    ClusterTensors, broker_best, broker_best_rows, broker_flag_at,
    broker_reduce_form, broker_segments, flat_top_k, flat_topk_form,
    flatten_slots, is_leader_slot, replica_exists, slot_coords,
)
from .derived import DerivedState, dest_columns_ok, broker_masks_at

KIND_MOVE = 0
KIND_LEADERSHIP = 1


@partial(jax.tree_util.register_dataclass,
         data_fields=["kind", "partition", "src_slot", "dst_broker", "dst_slot", "valid"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Candidates:
    kind: jax.Array        # [N] int8
    partition: jax.Array   # [N] int32
    src_slot: jax.Array    # [N] int32
    dst_broker: jax.Array  # [N] int32
    dst_slot: jax.Array    # [N] int32 (leadership only)
    valid: jax.Array       # [N] bool

    @property
    def n(self) -> int:
        return self.kind.shape[0]


def _span(x: jax.Array, start: int, stop: int, stride: int = 1) -> jax.Array:
    """``x[start:stop:stride]`` along axis 0 as a static ``lax.slice``
    (jnp indexing lowers a strided slice, and a slice beside ``None``, to
    a gather)."""
    return jax.lax.slice_in_dim(x, start, stop, stride, axis=0)


def _grid_from_rows(layout, rows: jax.Array) -> jax.Array:
    """[k_src + k_l, ...] values, one per row of the two-block grid
    ``layout`` -> [N, ...] candidates (a broadcast, no lookup)."""
    (k_src, k_cols), (k_l, s) = layout
    tail = rows.shape[1:]
    move = jnp.broadcast_to(jnp.expand_dims(_span(rows, 0, k_src), 1),
                            (k_src, k_cols) + tail)
    lead = jnp.broadcast_to(
        jnp.expand_dims(_span(rows, k_src, k_src + k_l), 1),
        (k_l, s) + tail)
    return jnp.concatenate([move.reshape((k_src * k_cols,) + tail),
                            lead.reshape((k_l * s,) + tail)])


def _grid_from_dst(layout, vals: jax.Array) -> jax.Array:
    """[k_cols - 1 | k_src | k_l * S, ...] values, one per entry of the
    grid's destination margin (shared columns, targeted column, leadership
    slots) -> [N, ...] candidates."""
    (k_src, k_cols), _ = layout
    k_shared = k_cols - 1
    tail = vals.shape[1:]
    move = jnp.concatenate(
        [jnp.broadcast_to(_span(vals, 0, k_shared)[None],
                          (k_src, k_shared) + tail),
         jnp.expand_dims(_span(vals, k_shared, k_shared + k_src), 1)],
        axis=1)
    return jnp.concatenate([move.reshape((k_src * k_cols,) + tail),
                            _span(vals, k_shared + k_src, len(vals))])


@partial(jax.tree_util.register_dataclass,
         data_fields=["row_src", "dst_margin", "row_partition", "row_topic",
                      "row_src_slot"],
         meta_fields=["layout"])
@dataclasses.dataclass(frozen=True)
class CandidateGrid:
    """The margins of ``generate_candidates``' two-block grid, so that what
    is constant along a row or a column is looked up there and broadcast,
    not gathered once per candidate (docs/DESIGN.md "Grid lookups").

    ``layout`` is ``((k_src, k_cols), (k_l, S))``: the move block, one row
    per source replica and one column per destination, then the leadership
    block, one row per leader and one column per slot. A ROW fixes the
    partition, its topic, the moving slot and the source broker in both
    blocks. A COLUMN of the move block fixes the destination broker, all
    but the last one (the targeted column is per row; a grid without one
    spends k_src lookups on a shared column, same values); a leadership
    destination is per candidate. The arrays index VALID candidates'
    values; an invalid candidate reads its row's and column's brokers
    where the flat fields read broker 0, and ``valid`` masks both."""

    layout: tuple[tuple[int, int], tuple[int, int]]
    row_src: jax.Array        # [k_src + k_l] source broker of each row
    dst_margin: jax.Array     # [k_cols - 1 | k_src | k_l * S] dest brokers
    row_partition: jax.Array  # [k_src + k_l]
    row_topic: jax.Array      # [k_src + k_l]
    row_src_slot: jax.Array   # [k_src + k_l]

    def from_rows(self, rows: jax.Array) -> jax.Array:
        """[k_src + k_l, ...] row values -> [N, ...] candidates."""
        return _grid_from_rows(self.layout, rows)

    def from_dst(self, vals: jax.Array) -> jax.Array:
        """[len(dst_margin), ...] values read at ``dst_margin`` -> [N, ...]
        candidates."""
        return _grid_from_dst(self.layout, vals)

    def at_dst_topic(self, x: jax.Array) -> jax.Array:
        """``x[topic, dst_broker]`` of a [T, B] table per candidate: the
        shared columns as k_shared columns of ``x``, then one row of them
        per source; the targeted column and the leadership slots, where
        topic and broker both vary, one element each."""
        (k_src, k_cols), (_k_l, s) = self.layout
        k_shared = k_cols - 1
        n_rows, n_dst = len(self.row_topic), len(self.dst_margin)
        t_move = _span(self.row_topic, 0, k_src)
        t_lead = _span(self.row_topic, k_src, n_rows)
        shared = jnp.take(x, _span(self.dst_margin, 0, k_shared),
                          axis=1)[t_move]
        rest = x[jnp.concatenate([t_move, jnp.repeat(t_lead, s)]),
                 _span(self.dst_margin, k_shared, n_dst)]
        move = jnp.concatenate(
            [shared, jnp.expand_dims(_span(rest, 0, k_src), 1)], axis=1)
        return jnp.concatenate([move.reshape(-1),
                                _span(rest, k_src, len(rest))])


@partial(jax.tree_util.register_dataclass,
         data_fields=["src_broker", "dst_broker", "load_delta", "replica_delta",
                      "leader_delta", "partition", "topic", "src_slot",
                      "dst_slot", "valid", "src_offline", "pre_src_load",
                      "pre_dst_load", "pre_src_count", "pre_dst_count",
                      "pre_src_leaders", "pre_dst_leaders",
                      "pre_src_topic_count", "pre_dst_topic_count",
                      "pre_src_topic_leaders", "pre_dst_pot", "pre_dst_lbi",
                      "grid"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class CandidateDeltas:
    """Per-candidate effect: src loses, dst gains.

    The optional ``pre_*`` fields carry the CUMULATIVE effect of
    higher-ranked candidates selected in the same round on this candidate's
    src/dst brokers (attach_cumulative). Goal acceptance adds them to the
    round-start aggregates so a batch of same-broker moves is judged
    jointly — the sound relaxation of one-move-per-broker-per-round.
    Directionally conservative: dst pre terms count only inflows, src pre
    terms only outflows, so a rejected earlier candidate can only make the
    check stricter, never looser. ``None`` = single-candidate semantics.

    Goals read per-broker, per-topic and per-partition TABLES through the
    ``at_*`` methods below, never by indexing with the [N] fields: with a
    ``grid`` attached (compute_deltas given the layout) the lookup runs on
    the grid's margins and is broadcast; with none it is the plain
    per-candidate gather. Same values on every valid candidate. The [N]
    fields themselves are built the same way (compute_deltas) and are
    equal on EVERY candidate."""

    src_broker: jax.Array    # [N] int32
    dst_broker: jax.Array    # [N] int32
    load_delta: jax.Array    # [N, R] — leaves src, arrives dst
    replica_delta: jax.Array  # [N] int32 (1 for moves, 0 for leadership)
    leader_delta: jax.Array   # [N] int32 (1 if leadership follows the action)
    partition: jax.Array     # [N] int32
    topic: jax.Array         # [N] int32
    src_slot: jax.Array      # [N] int32
    dst_slot: jax.Array      # [N] int32 (leadership target slot; 0 for moves)
    valid: jax.Array         # [N] bool
    # [N] bool: the moving replica is OFFLINE (its broker is dead), False
    # on invalid candidates; compute_deltas sets it, a swap's net leaves it
    src_offline: jax.Array | None = None
    pre_src_load: jax.Array | None = None        # [N, R]
    pre_dst_load: jax.Array | None = None        # [N, R]
    pre_src_count: jax.Array | None = None       # [N] f32
    pre_dst_count: jax.Array | None = None       # [N] f32
    pre_src_leaders: jax.Array | None = None     # [N] f32
    pre_dst_leaders: jax.Array | None = None     # [N] f32
    pre_src_topic_count: jax.Array | None = None   # [N] f32 (same topic)
    pre_dst_topic_count: jax.Array | None = None   # [N] f32
    pre_src_topic_leaders: jax.Array | None = None  # [N] f32
    pre_dst_pot: jax.Array | None = None         # [N] f32 potential NW-out
    pre_dst_lbi: jax.Array | None = None         # [N] f32 leader bytes-in
    grid: CandidateGrid | None = None

    def without_grid(self) -> "CandidateDeltas":
        """The same candidates as a plain batch. Anything that re-indexes
        the [N] fields (a selected sub-batch) must drop the grid first:
        its margins describe the WHOLE grid."""
        return dataclasses.replace(self, grid=None)

    # -- row view: one entry per grid row (per candidate without a grid) --
    @property
    def row_src(self) -> jax.Array:
        return self.src_broker if self.grid is None else self.grid.row_src

    @property
    def row_partition(self) -> jax.Array:
        return self.partition if self.grid is None \
            else self.grid.row_partition

    @property
    def row_topic(self) -> jax.Array:
        return self.topic if self.grid is None else self.grid.row_topic

    @property
    def row_src_slot(self) -> jax.Array:
        return self.src_slot if self.grid is None \
            else self.grid.row_src_slot

    def from_rows(self, rows: jax.Array) -> jax.Array:
        """Row-view values -> [N, ...] candidates."""
        return rows if self.grid is None else self.grid.from_rows(rows)

    # -- table lookups ---------------------------------------------------
    def at_src(self, x: jax.Array) -> jax.Array:
        """``x[src_broker]`` of a [B] or [B, k] table."""
        return self.from_rows(x[self.row_src])

    def at_dst(self, x: jax.Array) -> jax.Array:
        """``x[dst_broker]`` of a [B] or [B, k] table."""
        if self.grid is None:
            return x[self.dst_broker]
        return self.grid.from_dst(x[self.grid.dst_margin])

    def at_src_topic(self, x: jax.Array) -> jax.Array:
        """``x[topic, src_broker]`` of a [T, B] table."""
        return self.from_rows(x[self.row_topic, self.row_src])

    def at_dst_topic(self, x: jax.Array) -> jax.Array:
        """``x[topic, dst_broker]`` of a [T, B] table."""
        if self.grid is None:
            return x[self.topic, self.dst_broker]
        return self.grid.at_dst_topic(x)

    def at_topic(self, x: jax.Array) -> jax.Array:
        """``x[topic]`` of a [T] table."""
        return self.from_rows(x[self.row_topic])

    def at_partition(self, x: jax.Array) -> jax.Array:
        """``x[partition]`` of a [P] or [P, k] table."""
        return self.from_rows(x[self.row_partition])

    def at_src_slot(self, x: jax.Array) -> jax.Array:
        """``x[partition, src_slot]`` of a [P, S] table: the moving
        replica's entry."""
        return self.from_rows(x[self.row_partition, self.row_src_slot])

    def pre0(self, name: str):
        """Pre-term or 0.0 (single-candidate semantics when absent)."""
        value = getattr(self, name)
        return 0.0 if value is None else value

    def pre_load(self, name: str, r: int):
        value = getattr(self, name)
        return 0.0 if value is None else value[:, r]


def _at_slot(assign: jax.Array, slot: jax.Array) -> jax.Array:
    """``assign[i, slot[i]]`` of [n, S] assignment rows, a negative slot
    read as slot 0."""
    return jnp.take_along_axis(
        assign, jnp.maximum(slot, 0)[:, None], axis=1)[:, 0]


def _delta_frame(cand: Candidates, layout):
    """How ``compute_deltas`` lays the candidates out: ``(rows, from_rows,
    from_dst, destinations)``.

    - ``rows(flat)``: an [N] field of ``cand`` that a row fixes -> one
      value per row;
    - ``from_rows(v)``, ``from_dst(v)``: per-row / per-destination values
      -> [N, ...] candidates;
    - ``destinations(assign, is_move)``: the raw destination brokers, one
      per destination, given the rows' assignment [rows, S].

    With no layout every candidate is its own row and its own destination
    (the three maps are the identity, a leadership destination is looked
    up behind ``cand.dst_slot``). With ``generate_candidates``' two-block
    layout the rows are the grid's (each row's first candidate, static
    strided slices) and the destinations its margin ``[shared columns |
    targeted column | leadership slots]``: column j of the leadership
    block is slot j, so its destinations are the leader rows' assignment
    itself, reshaped, with no lookup."""
    if layout is None:
        def same(x):
            return x

        def destinations(assign, is_move):
            return jnp.where(is_move, cand.dst_broker,
                             _at_slot(assign, cand.dst_slot))
        return same, same, same, destinations

    if len(layout) != 2:
        raise ValueError(
            f"grid lookups need the move + leadership layout, got {layout!r}")
    (k_src, k_cols), (k_l, s_dim) = layout
    n_move, n = k_src * k_cols, cand.n

    def rows(flat):
        return jnp.concatenate([_span(flat, 0, n_move, k_cols),
                                _span(flat, n_move, n, s_dim)])

    def destinations(assign, _is_move):
        return jnp.concatenate(
            [_span(cand.dst_broker, 0, k_cols - 1),
             _span(cand.dst_broker, k_cols - 1, n_move, k_cols),
             _span(assign, k_src, k_src + k_l).reshape(-1)])
    return (rows, partial(_grid_from_rows, layout),
            partial(_grid_from_dst, layout), destinations)


@jax.named_scope("round.deltas")
def compute_deltas(state: ClusterTensors, derived: DerivedState,
                   cand: Candidates,
                   layout: "tuple[tuple[int, int], ...] | None" = None,
                   ) -> CandidateDeltas:
    """The (src, dst, Δload) tuple of every candidate; also folds the
    structural legitimacy checks (GoalUtils.legitMove: destination must not
    already host the partition, source must exist, destination must be an
    alive allowed broker, leadership destination must be a live replica).

    ``layout``: ``generate_candidates``' layout for ``cand`` when it made
    both blocks (moves, then leadership). Whatever a ROW of that grid fixes
    (the partition's assignment, leader slot, loads and topic, the moving
    slot, the source broker and its state, the deltas) is then looked up
    once per row, whatever a DESTINATION fixes (the broker's masks) once
    per entry of the destination margin, and both are broadcast; what
    mixes the two is a compare of the broadcasts. The deltas carry the
    margins (CandidateGrid) and the goals' table lookups run there too.
    Without it the same body runs with every candidate its own row and
    destination: the per-candidate gathers. Same fields either way, on
    every candidate (docs/DESIGN.md "Grid lookups")."""
    b = state.num_brokers
    rows, from_rows, from_dst, destinations = _delta_frame(cand, layout)

    # Per row ---------------------------------------------------------------
    p = rows(cand.partition)
    is_move = rows(cand.kind) == KIND_MOVE
    assign = state.assignment[p]                # [rows, S]
    leader_slot = state.leader_slot[p]          # [rows]
    # src broker: replica's broker for moves; current leader's broker for leadership.
    src_slot = jnp.where(is_move, rows(cand.src_slot), leader_slot)
    src_broker = _at_slot(assign, src_slot)

    moving_is_leader = src_slot == leader_slot
    lead = state.leader_load[p]      # [rows, R]
    foll = state.follower_load[p]    # [rows, R]
    move_vec = jnp.where(moving_is_leader[:, None], lead, foll)
    leadership_vec = lead - foll
    load_delta = jnp.where(is_move[:, None], move_vec, leadership_vec)

    replica_delta = is_move.astype(jnp.int32)
    leader_delta = (jnp.where(is_move, moving_is_leader, True)).astype(jnp.int32)
    topic = state.topic[p]

    src_exists = (src_slot >= 0) & (src_broker >= 0)
    src_safe = jnp.clip(src_broker, 0, b - 1)
    src_offline = from_rows(~derived.alive[src_safe])
    row_ok = from_rows(derived.movable_partition[p] & src_exists)
    leader_slot_n = from_rows(leader_slot)
    moving_is_leader_n = from_rows(moving_is_leader)

    # Per destination -------------------------------------------------------
    dst_broker = destinations(assign, is_move)
    dst_safe = jnp.clip(dst_broker, 0, b - 1)
    dst_alive, dst_may_lead, dst_may_receive = broker_masks_at(
        derived, dst_safe, src_offline, from_dst)
    dst_alive &= from_dst((dst_broker >= 0) & (dst_broker < b))
    dst_n = from_dst(dst_broker)

    # Structural legitimacy: a row against a destination, [N] ----------------
    # Destination must not already host the partition (moves only);
    # comparing against all S slots of the partition.
    already_hosts = (from_rows(assign) == dst_n[:, None]).any(axis=1)
    # Moving a LEADER replica transfers leadership with it, so destinations
    # excluded for leadership are ineligible for leader-replica moves
    # (GoalUtils.filterOutBrokersExcludedForLeadership:120-137: excluded
    # brokers are removed when action is LEADERSHIP_MOVEMENT or
    # replica.isLeader()). Offline replicas are exempt — self-healing
    # placement must proceed even onto leadership-excluded brokers
    # (eligibleReplicasForSwap's !isOriginalOffline carve-out).
    lead_dst_ok = (~moving_is_leader_n) | src_offline | dst_may_lead
    move_ok = (~already_hosts) & dst_may_receive \
        & (from_rows(src_broker) != dst_n) & lead_dst_ok
    # Leadership: destination slot must hold a live replica (its broker IS
    # the destination) on an allowed-for-leadership broker, and differ from
    # the current leader.
    lead_ok = (dst_n >= 0) & (cand.dst_slot != leader_slot_n) \
        & (cand.dst_slot >= 0) & dst_may_lead & (leader_slot_n >= 0)

    is_move_n = cand.kind == KIND_MOVE
    valid = cand.valid & row_ok & dst_alive \
        & jnp.where(is_move_n, move_ok, lead_ok)

    grid = None
    if layout is not None:
        grid = CandidateGrid(
            layout=tuple(layout), row_src=src_safe, dst_margin=dst_safe,
            row_partition=p, row_topic=topic,
            row_src_slot=jnp.maximum(src_slot, 0))

    return CandidateDeltas(
        src_broker=jnp.where(valid, from_rows(src_broker), 0),
        dst_broker=jnp.where(valid, from_dst(dst_safe), 0),
        load_delta=jnp.where(valid[:, None], from_rows(load_delta), 0.0),
        replica_delta=jnp.where(valid, from_rows(replica_delta), 0),
        leader_delta=jnp.where(valid, from_rows(leader_delta), 0),
        partition=cand.partition,
        topic=from_rows(topic),
        src_slot=jnp.where(valid, from_rows(src_slot), 0),
        dst_slot=jnp.where(valid & ~is_move_n, cand.dst_slot, 0),
        valid=valid,
        src_offline=valid & src_offline,
        grid=grid,
    )


# The form select_sources last took in this process ("dense", "rows" or
# "segment": model.tensors.broker_reduce_form, narrowed by source_rows):
# fixed when a program is traced, so it is written then, and the
# ``solver.dispatch`` spans report it.
_source_select_traced: str | None = None


def source_select() -> str | None:
    """How the last traced source selection reduced the flat replica axis
    per broker (None before any trace): the ``source_select`` attribute of
    the ``solver.dispatch`` spans."""
    return _source_select_traced


# The form the source selection's top-k of the whole flat replica axis last
# took in this process ("sort" or "two_level": model.tensors.flat_topk_form;
# the leadership block's top-k, whose k is the grid's k_src, takes the same
# form at every served grid), written when a program is traced, as
# ``_source_select_traced`` is.
_flat_topk_traced: str | None = None


def flat_topk() -> str | None:
    """How the last traced move round took its top-k's of the flat
    replica axis (None before any trace): the ``flat_topk`` attribute of
    the ``solver.dispatch`` spans."""
    return _flat_topk_traced


def source_rows(quarter: int, b: int, form: str) -> int | None:
    """How many candidate brokers the per-broker reductions of
    ``select_sources`` run over, from static shapes: ``quarter`` (the
    brokers the grid keeps) and a slack of a quarter of it, at least 16.
    None where every broker's row is reduced as before: the ``segment``
    form, whose cost is by the element and not by the broker, or fewer
    than twice as many brokers as rows (docs/DESIGN.md "Per-broker
    reductions of the flat replica axis")."""
    if form != "dense":
        return None
    m = quarter + max(16, quarter // 4)
    return m if 2 * m <= b else None


def _keep_brokers(score: jax.Array, w1: jax.Array, best1: jax.Array,
                  w2: jax.Array, best2: jax.Array, quarter: int, n_flat: int,
                  ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The per-broker blocks' cards: the ``quarter`` rows of highest
    ``score`` among those with a finite best (``lax.top_k``: the lower
    position first among equals), each broker's best and second-best flat
    index and their validity; ``n_flat`` where a row offers none."""
    tb_score, top = jax.lax.top_k(
        jnp.where(jnp.isfinite(w1), score, -jnp.inf), quarter)
    ok_b1 = jnp.isfinite(tb_score)
    rows_b1 = jnp.where(ok_b1, best1[top], n_flat)
    ok_b2 = ok_b1 & jnp.isfinite(w2[top])
    rows_b2 = jnp.where(ok_b2, best2[top], n_flat)
    return rows_b1, rows_b2, ok_b1, ok_b2


def broker_blocks(flat_weight: jax.Array, seg_flat: jax.Array,
                  source_score: jax.Array, quarter: int, form: str,
                  batched: bool = False) -> tuple[jax.Array, ...]:
    """The source selection's per-broker blocks: of the ``quarter``
    brokers of highest ``source_score`` that hold a replica of finite
    ``flat_weight``, the best and the second best replica each. Returns
    (rows_b1, rows_b2, ok_b1, ok_b2 [quarter], fallback): ``fallback``
    (scalar bool) says the round reduced every broker's row.

    Where ``source_rows`` gives M, the best and second best are reduced
    over the rows of the M brokers of highest ``source_score`` alone
    (``lax.top_k``: score descending, broker ascending, the order the
    full top-k breaks ties in), and the quarter is kept among them. That
    is the full selection bit for bit unless fewer than ``quarter`` of the
    M rows hold a finite best AND the M-th score is above 0, so that a
    source broker may lie outside them: only then does the ``cond`` run
    the reduction over all B rows. Under ``vmap`` (``batched``) a
    ``cond`` whose predicate is batched runs both branches, so there every
    broker's row is reduced, once, as without rows."""
    b = source_score.shape[0]
    n_flat = flat_weight.shape[0]

    def every_broker():
        w1, best1 = broker_best(flat_weight, seg_flat, b, form)
        w2, best2 = broker_best(flat_weight, seg_flat, b, form, skip=best1)
        return _keep_brokers(source_score, w1, best1, w2, best2, quarter,
                             n_flat)

    m = None if batched else source_rows(quarter, b, form)
    if m is None:
        return every_broker() + (jnp.bool_(False),)
    s_m, cand_b = jax.lax.top_k(source_score, m)
    w1, best1 = broker_best_rows(flat_weight, seg_flat, cand_b)
    w2, best2 = broker_best_rows(flat_weight, seg_flat, cand_b, skip=best1)
    fallback = (jnp.isfinite(w1).sum() < quarter) & ~(s_m[m - 1] <= 0.0)
    kept = jax.lax.cond(
        fallback, every_broker,
        lambda: _keep_brokers(s_m, w1, best1, w2, best2, quarter, n_flat))
    return tuple(kept) + (fallback,)


@jax.named_scope("round.source_topk")
def select_sources(state: ClusterTensors, source_score: jax.Array,
                   replica_weight: jax.Array, num_sources: int,
                   batched: bool = False) -> tuple[jax.Array, ...]:
    """The move grid's source-replica selection (broker-diverse top-k; see
    generate_candidates). Returns (cand_p [k], cand_s [k], src_valid [k],
    on_source [n_flat], fallback): the cards, which flat replicas sit on a
    source broker at all (the leadership block ranks the leaders among
    them), and whether the per-broker blocks reduced every broker's row
    (``broker_blocks``; a scalar the counter
    ``solver_source_fallback_rounds_total`` sums).

    A caller that needs the source list FIRST (to compute per-card
    targeted destinations, analyzer.fill) hands the whole result on as
    ``generate_candidates(..., sources=...)``: the selection is traced and
    run once a round.

    The per-broker reductions take the form ``broker_reduce_form`` gives
    for these shapes (dense compare-and-reduce, or ``segment_*``), over
    the rows ``source_rows`` gives (every row where ``batched``: under
    ``vmap``, ``broker_blocks``); the global block's top-k the form
    ``flat_topk_form`` gives (``lax.top_k``, or the two-level form); cards
    and validity are the same under every form."""
    global _source_select_traced, _flat_topk_traced
    b = state.num_brokers
    s_dim = state.max_replication_factor
    seg_flat = broker_segments(state)
    n_flat = seg_flat.shape[0]
    form = broker_reduce_form(b, n_flat)
    on_source = broker_flag_at(source_score > 0.0, seg_flat, form) \
        & flatten_slots(replica_exists(state))

    flat_weight = jnp.where(on_source, flatten_slots(replica_weight),
                            -jnp.inf)
    k_src = min(num_sources, n_flat)

    # Source rows must be BROKER-DIVERSE: conflict-free selection admits at
    # most one move per source broker per round (for totals-dependent
    # goals), so a global top-k by weight — which piles onto the few most
    # overloaded brokers — caps accepted moves per round at a handful
    # regardless of k. Mirror the reference's per-broker greedy
    # (AbstractGoal.rebalanceForBroker iterates brokersToBalance, each
    # offering its own sorted replicas): half the rows are the globally
    # heaviest replicas (preserves offline/self-healing priority), half are
    # the best (and second-best) replica of each of the top source brokers.
    quarter = min(k_src // 4, b)
    half = k_src - 2 * quarter            # exact: half + 2*quarter == k_src
    _source_select_traced = form \
        if batched or source_rows(quarter, b, form) is None else "rows"

    _flat_topk_traced = flat_topk_form(n_flat, half)
    g_w, g_idx = flat_top_k(flat_weight, half)
    # Mask the global block's rows out of the per-broker selection so the
    # broker blocks only ADD diversity (on skewed clusters the globally
    # heaviest replicas are exactly the top brokers' best replicas, and a
    # duplicate row wastes its whole k_dst grid slice).
    in_global = jnp.zeros(n_flat + 1, dtype=bool).at[
        jnp.where(jnp.isfinite(g_w), g_idx, n_flat)].set(True)[:n_flat]
    flat_weight_rest = jnp.where(in_global, -jnp.inf, flat_weight)

    rows_b1, rows_b2, broker_ok, ok_b2, fallback = broker_blocks(
        flat_weight_rest, seg_flat, source_score, quarter, form, batched)

    top_idx = jnp.concatenate([g_idx, rows_b1, rows_b2])[:k_src]
    src_valid = jnp.concatenate([jnp.isfinite(g_w), broker_ok, ok_b2])[:k_src]
    src_valid &= top_idx < n_flat
    top_idx = jnp.minimum(top_idx, n_flat - 1)
    cand_p, cand_s = slot_coords(top_idx, state.num_partitions, s_dim)
    return (cand_p.astype(jnp.int32), cand_s.astype(jnp.int32), src_valid,
            on_source, fallback)


@jax.named_scope("round.candidates")
def generate_candidates(state: ClusterTensors, derived: DerivedState,
                        source_score: jax.Array, dest_score: jax.Array,
                        replica_weight: jax.Array, num_sources: int,
                        num_dests: int, include_leadership: bool,
                        leadership_only: bool = False,
                        extra_dst: "tuple[jax.Array, jax.Array] | None" = None,
                        sources: "tuple[jax.Array, ...] | None" = None,
                        ) -> "tuple[Candidates, tuple[tuple[int, int], ...]]":
    """Top-k × top-k candidate grid.

    - ``source_score[B]``: how much each broker needs to shed (>0 = source).
    - ``dest_score[B]``: how attractive each broker is as a destination
      (-inf = not eligible). The move block's columns are taken among
      ``derived.dest_columns_ok`` alone, whatever the goal: with NEW
      brokers present the columns ARE the new brokers.
    - ``replica_weight[P, S]``: which replicas are worth moving (higher =
      try first; the per-goal analogue of SortedReplicas score functions).
    - ``extra_dst``: optional (dst [k_src], ok [k_src]) per-card TARGETED
      destination (Goal.target_dests over the select_sources card list),
      appended as one more column of the move block so each source also
      competes with a destination constructed for it.
    - ``sources``: the ``select_sources`` result for these very scores,
      weights and ``num_sources``, from a caller that already holds it
      (it made ``extra_dst`` from the cards); selected here otherwise.

    Replica moves: the ``num_sources`` highest-weight replicas living on
    positive-score source brokers × the ``num_dests`` best destinations.
    Leadership: the top leader slots on source brokers × their follower
    slots (dst_broker implied by slot).

    Returns (candidates, layout) where ``layout`` describes the grid blocks
    — [k_src × (k_dst + extra)] moves then [k_l × S] leadership — so the
    selector can do a per-source best-destination reduction before global
    ranking.
    """
    b = state.num_brokers
    s_dim = state.max_replication_factor
    if sources is None:
        sources = select_sources(state, source_score, replica_weight,
                                 num_sources)
    cand_p, cand_s, src_valid, on_source, _fallback = sources
    k_src = cand_p.shape[0]

    layout: list[tuple[int, int]] = []
    parts: list[Candidates] = []
    if not leadership_only:
        k_dst = min(num_dests, b)
        _dst_score, dst_idx = jax.lax.top_k(
            jnp.where(dest_columns_ok(derived), dest_score, -jnp.inf), k_dst)
        dst_valid = jnp.isfinite(_dst_score)
        cols_dst = jnp.broadcast_to(dst_idx.astype(jnp.int32)[None, :],
                                    (k_src, k_dst))
        cols_ok = jnp.broadcast_to(dst_valid[None, :], (k_src, k_dst))
        if extra_dst is not None:
            t_dst, t_ok = extra_dst
            cols_dst = jnp.concatenate(
                [cols_dst, t_dst.astype(jnp.int32)[:, None]], axis=1)
            cols_ok = jnp.concatenate([cols_ok, t_ok[:, None]], axis=1)
        k_cols = cols_dst.shape[1]
        n = k_src * k_cols
        grid_p = jnp.repeat(cand_p, k_cols)
        grid_s = jnp.repeat(cand_s, k_cols)
        grid_valid = jnp.repeat(src_valid, k_cols) & cols_ok.reshape(-1)
        grid_dst = cols_dst.reshape(-1)
        parts.append(Candidates(
            kind=jnp.zeros(n, dtype=jnp.int8),
            partition=grid_p, src_slot=grid_s, dst_broker=grid_dst,
            dst_slot=jnp.zeros(n, dtype=jnp.int32), valid=grid_valid))
        layout.append((k_src, k_cols))

    if include_leadership or leadership_only:
        # Leadership candidates: for each top source replica that IS a
        # leader, try every other slot.
        flat_lw = jnp.where(on_source & flatten_slots(is_leader_slot(state)),
                            flatten_slots(replica_weight), -jnp.inf)
        k_l = min(num_sources, flat_lw.shape[0])
        top_lw, top_lidx = flat_top_k(flat_lw, k_l)
        lp = slot_coords(top_lidx, state.num_partitions,
                         s_dim)[0].astype(jnp.int32)
        l_valid = jnp.isfinite(top_lw)
        n = k_l * s_dim
        grid_p = jnp.repeat(lp, s_dim)
        grid_valid = jnp.repeat(l_valid, s_dim)
        grid_dslot = jnp.tile(jnp.arange(s_dim, dtype=jnp.int32), k_l)
        parts.append(Candidates(
            kind=jnp.ones(n, dtype=jnp.int8),
            partition=grid_p,
            src_slot=jnp.zeros(n, dtype=jnp.int32),
            dst_broker=jnp.zeros(n, dtype=jnp.int32),
            dst_slot=grid_dslot, valid=grid_valid))
        layout.append((k_l, s_dim))

    return Candidates(
        kind=jnp.concatenate([c.kind for c in parts]),
        partition=jnp.concatenate([c.partition for c in parts]),
        src_slot=jnp.concatenate([c.src_slot for c in parts]),
        dst_broker=jnp.concatenate([c.dst_broker for c in parts]),
        dst_slot=jnp.concatenate([c.dst_slot for c in parts]),
        valid=jnp.concatenate([c.valid for c in parts]),
    ), tuple(layout)


def _exclusive_group_prefix(keys: "tuple[jax.Array, ...]",
                            values: jax.Array) -> jax.Array:
    """For each row i: sum of ``values[j]`` over EARLIER rows j < i whose
    key tuple equals row i's — the per-group exclusive prefix sum, by one
    lexicographic sort on (keys..., index) + a cumsum + a group-base
    gather: O(m log m) instead of the [m, m] mask matmul. Key tuples
    avoid composite-integer keys (int64 is unavailable without
    jax_enable_x64). ``values`` is [m, C]."""
    m = values.shape[0]
    # np.lexsort semantics: LAST key is primary; appending the index makes
    # the order total, so within a group rows appear in index order.
    perm = jnp.lexsort((jnp.arange(m),) + tuple(reversed(keys)))
    v_sorted = values[perm]
    cs_prev = jnp.concatenate(
        [jnp.zeros((1, values.shape[1]), values.dtype),
         jnp.cumsum(v_sorted, axis=0)[:-1]])
    is_start = jnp.zeros(m, dtype=bool).at[0].set(True)
    for k in keys:
        ks = k[perm]
        is_start = is_start | jnp.concatenate(
            [jnp.array([True]), ks[1:] != ks[:-1]])
    # lax.cummax, not jnp.maximum.accumulate: jnp ufunc objects carry no
    # .accumulate under jitted tracing on this jax line.
    start_pos = jax.lax.cummax(
        jnp.where(is_start, jnp.arange(m), 0), axis=0)
    excl = cs_prev - cs_prev[start_pos]
    return jnp.zeros_like(values).at[perm].set(excl)


def attach_cumulative_segments(sub: CandidateDeltas, considered: jax.Array,
                               pot_delta: jax.Array, lbi_delta: jax.Array,
                               ) -> tuple[CandidateDeltas, jax.Array]:
    """O(m log m) ``attach_cumulative``: per-key exclusive prefix sums via
    sorted segments instead of [m, m] mask matmuls. Numerically the sums
    run in sorted order rather than index order — equal up to f32
    reassociation — and the m² → m log m change is what makes SELECTION
    widths beyond ~2k affordable (the pairwise matmul is the width
    bottleneck of the wide-batch grids at 7k scale)."""
    f32 = jnp.float32
    m = sub.partition.shape[0]
    rep = sub.replica_delta.astype(f32)
    lead = sub.leader_delta.astype(f32)
    r = sub.load_delta.shape[1]
    src_vals = jnp.concatenate(
        [sub.load_delta, rep[:, None], lead[:, None]], axis=1)   # [m, R+2]
    dst_vals = jnp.concatenate(
        [sub.load_delta, rep[:, None], lead[:, None], pot_delta[:, None],
         lbi_delta[:, None]], axis=1)                            # [m, R+4]
    cons = considered.astype(f32)[:, None]
    src_out = _exclusive_group_prefix((sub.src_broker,), src_vals * cons)
    dst_out = _exclusive_group_prefix((sub.dst_broker,), dst_vals * cons)
    topic_vals = jnp.stack([rep, lead], axis=1) * cons
    st_out = _exclusive_group_prefix((sub.src_broker, sub.topic), topic_vals)
    dt_out = _exclusive_group_prefix((sub.dst_broker, sub.topic), topic_vals)

    # has_earlier: any earlier CONSIDERED row touching either of my
    # brokers in either role. Per-broker first-touch rank via the same
    # sorted-group machinery (a dense [B] scatter would need a traced
    # broker bound for its shape): each row contributes its (src, rank)
    # and (dst, rank) entries; within a sorted group the first entry IS
    # the min rank, broadcast group-wide through the start-position
    # gather and scattered back to entry order.
    idx = jnp.arange(m, dtype=jnp.int32)
    rank_eff = jnp.where(considered, idx, m)
    keys2 = jnp.concatenate([sub.src_broker, sub.dst_broker])
    ranks2 = jnp.concatenate([rank_eff, rank_eff])
    perm2 = jnp.lexsort((jnp.arange(2 * m), ranks2, keys2))
    k_sorted = keys2[perm2]
    is_start = jnp.concatenate(
        [jnp.array([True]), k_sorted[1:] != k_sorted[:-1]])
    start_pos = jax.lax.cummax(
        jnp.where(is_start, jnp.arange(2 * m), 0), axis=0)
    group_min = ranks2[perm2][start_pos]
    entry_min = jnp.zeros(2 * m, jnp.int32).at[perm2].set(group_min)
    has_earlier = (entry_min[:m] < idx) | (entry_min[m:] < idx)

    return dataclasses.replace(
        sub,
        pre_src_load=src_out[:, :r],
        pre_dst_load=dst_out[:, :r],
        pre_src_count=src_out[:, r],
        pre_dst_count=dst_out[:, r],
        pre_src_leaders=src_out[:, r + 1],
        pre_dst_leaders=dst_out[:, r + 1],
        pre_src_topic_count=st_out[:, 0],
        pre_dst_topic_count=dt_out[:, 0],
        pre_src_topic_leaders=st_out[:, 1],
        pre_dst_pot=dst_out[:, r + 2],
        pre_dst_lbi=dst_out[:, r + 3],
    ), has_earlier


# Cumulative pre-delta implementation: "segment" (O(m log m) sort-based)
# or "matmul" ([m, m] pairwise masks — the MXU-friendly form and the
# equivalence oracle). Default is BACKEND-AWARE, decided lazily at trace
# time (the backend is not known at import): segment on CPU (measured
# −13% TopicReplica round cost at 7k), matmul on accelerators (the MXU
# eats [m, m] matmuls; device-side sorts are comparatively slow and the
# segment form is unmeasured on the chip).
def _attach_impl() -> str:
    return "segment" if jax.default_backend() == "cpu" else "matmul"


def attach_cumulative(sub: CandidateDeltas, considered: jax.Array,
                      pot_delta: jax.Array, lbi_delta: jax.Array,
                      ) -> tuple[CandidateDeltas, jax.Array]:
    """Fill the ``pre_*`` fields of a RANK-ORDERED candidate batch: for each
    candidate i, the summed effect of every considered candidate j < i on
    i's src/dst brokers (pairwise masks + matmuls over the small selected
    batch — [m, m] with m ≤ a few hundred).

    ``considered[j]`` marks candidates whose effect must be assumed applied
    (passed scoring + partition dedupe). Including candidates that a later
    acceptance recheck rejects only OVERCOUNTS inflow/outflow — the checks
    get stricter, never looser, so the relaxation stays sound.
    ``pot_delta``/``lbi_delta`` are the per-candidate potential-NW-out and
    leader-bytes-in transfer scalars (computed by the caller so this stays
    free of per-partition state gathers — shard-safe).

    Returns (sub with pre fields, has_earlier[m]) where ``has_earlier``
    marks candidates sharing a src or dst broker with an earlier considered
    candidate (the first candidate per broker keeps single-candidate
    acceptance semantics)."""
    if _attach_impl() == "segment":
        return attach_cumulative_segments(sub, considered, pot_delta,
                                          lbi_delta)
    m = sub.partition.shape[0]
    idx = jnp.arange(m)
    earlier = (idx[:, None] > idx[None, :]) & considered[None, :]
    same_dst = earlier & (sub.dst_broker[:, None] == sub.dst_broker[None, :])
    same_src = earlier & (sub.src_broker[:, None] == sub.src_broker[None, :])
    cross_sd = earlier & (sub.src_broker[:, None] == sub.dst_broker[None, :])
    cross_ds = earlier & (sub.dst_broker[:, None] == sub.src_broker[None, :])
    same_topic = sub.topic[:, None] == sub.topic[None, :]

    f32 = jnp.float32
    rep = sub.replica_delta.astype(f32)
    lead = sub.leader_delta.astype(f32)

    # One [m, m] matmul per MASK with the value columns stacked, instead of
    # one matmul per field: at wide-batch m (~2k) the pairwise matmuls are
    # a measurable slice of a round on the host backend, and each output
    # column depends only on its own value column, so stacking is exact.
    r = sub.load_delta.shape[1]
    src_vals = jnp.concatenate(
        [sub.load_delta, rep[:, None], lead[:, None]], axis=1)   # [m, R+2]
    dst_vals = jnp.concatenate(
        [sub.load_delta, rep[:, None], lead[:, None], pot_delta[:, None],
         lbi_delta[:, None]], axis=1)                            # [m, R+4]
    src_out = same_src.astype(f32) @ src_vals
    dst_out = same_dst.astype(f32) @ dst_vals
    st_out = (same_src & same_topic).astype(f32) @ jnp.stack([rep, lead], axis=1)
    dt_count = ((same_dst & same_topic).astype(f32) @ rep[:, None])[:, 0]

    has_earlier = (same_dst | same_src | cross_sd | cross_ds).any(axis=1)
    return dataclasses.replace(
        sub,
        pre_src_load=src_out[:, :r],
        pre_dst_load=dst_out[:, :r],
        pre_src_count=src_out[:, r],
        pre_dst_count=dst_out[:, r],
        pre_src_leaders=src_out[:, r + 1],
        pre_dst_leaders=dst_out[:, r + 1],
        pre_src_topic_count=st_out[:, 0],
        pre_dst_topic_count=dt_count,
        pre_src_topic_leaders=st_out[:, 1],
        pre_dst_pot=dst_out[:, r + 2],
        pre_dst_lbi=dst_out[:, r + 3],
    ), has_earlier
