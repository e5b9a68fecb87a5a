"""The cluster model as dense device tensors.

Reference parity: model/ClusterModel.java (rack→broker→replica topology with
per-replica load), model/Load.java, model/Partition.java. Where the
reference keeps a mutable object graph and mutates it during search, this
model is a frozen pytree of arrays; "mutation" is a functional update that
XLA fuses into the search loop, and a model "generation" is simply a new
pytree value.

Array schema (P partitions × S replica slots × B brokers × R resources):

- ``assignment[P, S]`` int32 — broker index per replica slot, -1 empty.
- ``leader_slot[P]`` int32 — which slot is the leader (-1 = offline/no leader).
- ``leader_load[P, R]`` float32 — resource load a broker bears when hosting
  the leader replica (CPU=leader cpu, NW_IN=leader bytes-in, NW_OUT=leader
  bytes-out, DISK=partition size; MonitorUtils.populatePartitionLoad).
- ``follower_load[P, R]`` float32 — load when hosting a follower (follower
  cpu estimate, replication bytes-in, zero NW_OUT, same disk).
- ``capacity[B, R]`` float32 — broker capacity (BrokerCapacityConfigResolver).
- ``rack[B]`` int32 — rack index per broker (Rack.java topology flattened).
- ``broker_state[B]`` int8 — BrokerState codes (ALIVE/DEAD/NEW/DEMOTED/BAD_DISKS).
- ``topic[P]`` int32 — topic index per partition.
- ``partition_mask[P]`` / ``broker_mask[B]`` bool — padding masks (static
  shapes for XLA; clusters are padded up to bucket sizes).

Padded replica slots use broker index = B (one-past-the-end) inside kernels
so segment reductions drop them without branching.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..common.broker_state import BrokerState


@partial(jax.tree_util.register_dataclass,
         data_fields=["assignment", "leader_slot", "leader_load", "follower_load",
                      "capacity", "rack", "broker_state", "topic",
                      "partition_mask", "broker_mask", "host"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class ClusterTensors:
    assignment: jax.Array     # [P, S] int32
    leader_slot: jax.Array    # [P] int32
    leader_load: jax.Array    # [P, R] float32
    follower_load: jax.Array  # [P, R] float32
    capacity: jax.Array       # [B, R] float32
    # Fault-domain index per broker (Rack.java semantics): the builder
    # folds rack-falls-back-to-host in — a broker with no configured rack
    # gets its HOST's domain, so co-hosted brokers share one rack index
    # (ClusterModel.handleDeadBroker / Host.java level). Rack-aware goal
    # kernels therefore need no host special-casing.
    rack: jax.Array           # [B] int32
    broker_state: jax.Array   # [B] int8
    topic: jax.Array          # [P] int32
    partition_mask: jax.Array  # [P] bool
    broker_mask: jax.Array    # [B] bool
    # Physical host index per broker (model/Host.java, the level between
    # rack and broker): multiple brokers may share a host; host-level
    # stats and the rack fallback derive from it. Defaults to one host
    # per broker when topology is unknown.
    host: jax.Array = None    # [B] int32

    def __post_init__(self):
        # Default host topology = one host per broker. Guarded on capacity
        # actually being an array: pytree unflattens re-enter __init__ with
        # arbitrary leaf payloads (tree_map/broadcast_prefix pass None or
        # spec objects through), and those dummy trees must round-trip
        # untouched.
        if self.host is None and hasattr(self.capacity, "shape"):
            object.__setattr__(
                self, "host",
                jnp.arange(self.capacity.shape[0], dtype=jnp.int32))

    @property
    def num_partitions(self) -> int:
        return self.assignment.shape[0]

    @property
    def max_replication_factor(self) -> int:
        return self.assignment.shape[1]

    @property
    def num_brokers(self) -> int:
        return self.capacity.shape[0]

    @property
    def num_topics(self) -> int:
        # Static upper bound: topics are indexed densely by the builder.
        return self.num_partitions


@dataclasses.dataclass
class ClusterMeta:
    """Host-side names for the integer indices of a ClusterTensors value
    (broker ids, topic names, rack names). Not traced."""

    broker_ids: list[int]
    topic_names: list[str]
    rack_names: list[str]
    num_topics: int
    partition_index: list[tuple[str, int]]  # row → (topic, partition number)
    # Physical host names indexed by ClusterTensors.host (Host.java level);
    # empty when the builder predates host topology.
    host_names: list[str] = dataclasses.field(default_factory=list)


# ---- derived quantities (all jittable) -----------------------------------

def replica_exists(state: ClusterTensors) -> jax.Array:
    """[P, S] bool — slot holds a real replica of a real partition."""
    return (state.assignment >= 0) & state.partition_mask[:, None]


def is_leader_slot(state: ClusterTensors) -> jax.Array:
    """[P, S] bool — slot is the partition's leader."""
    s = jnp.arange(state.max_replication_factor, dtype=state.leader_slot.dtype)
    return (state.leader_slot[:, None] == s[None, :]) & replica_exists(state)


def replica_load(state: ClusterTensors) -> jax.Array:
    """[P, S, R] float32 — per-slot resource load (leader vs follower)."""
    lead = is_leader_slot(state)
    load = jnp.where(lead[:, :, None], state.leader_load[:, None, :],
                     state.follower_load[:, None, :])
    return load * replica_exists(state)[:, :, None]


def replica_load_total(state: ClusterTensors) -> jax.Array:
    """[P, S] float32 — summed-over-resources load per replica slot.
    Equivalent to ``replica_load(state).sum(axis=-1)`` without
    materializing the [P, S, R] cube: the per-partition leader/follower
    totals are loop-invariant [P] reductions (XLA hoists them out of the
    search while-loop), leaving only a [P, S] select per round."""
    lsum = state.leader_load.sum(axis=-1)
    fsum = state.follower_load.sum(axis=-1)
    lead = is_leader_slot(state)
    return jnp.where(lead, lsum[:, None], fsum[:, None]) \
        * replica_exists(state)


def replica_load_column(state: ClusterTensors, r: int) -> jax.Array:
    """[P, S] float32 — one resource column of the per-replica load,
    without the [P, S, R] materialization (see replica_load_total)."""
    lead = is_leader_slot(state)
    return jnp.where(lead, state.leader_load[:, r][:, None],
                     state.follower_load[:, r][:, None]) \
        * replica_exists(state)


def slot_major_flat() -> bool:
    """Whether the flat replica axis is laid out slot-major ([S, P] order)
    rather than partition-major ([P, S] order). XLA:TPU takes tens of
    seconds to COMPILE the merge of the huge partition axis with the
    3-wide slot axis (26 s per reshape at P=100k and growing faster than
    P, against under a second for [S, P] -> [S*P]; chip run, PR 21), and
    every solver program flattens several [P, S] arrays. The CPU backend
    keeps partition-major order: ties in the source selection break by
    flat index, and the pinned CPU trajectories were recorded under it."""
    return jax.default_backend() != "cpu"


def flatten_slots(per_slot: jax.Array) -> jax.Array:
    """[P, S, ...] -> [P*S, ...]: the flat replica axis. The element ORDER
    is backend-dependent (``slot_major_flat``); callers either do not
    care (segment reductions) or decode indices with ``slot_coords``."""
    if slot_major_flat():
        per_slot = jnp.moveaxis(per_slot, 1, 0)
    return per_slot.reshape((-1,) + per_slot.shape[2:])


def slot_coords(flat_idx: jax.Array, num_partitions: int,
                num_slots: int) -> tuple[jax.Array, jax.Array]:
    """(partition, slot) of indices into a ``flatten_slots`` axis."""
    if slot_major_flat():
        return flat_idx % num_partitions, flat_idx // num_partitions
    return flat_idx // num_slots, flat_idx % num_slots


# ---- per-broker reductions of the flat replica axis ------------------------
#
# The round body asks, every round, per-broker questions of the flat
# replica axis (each broker's best replica, its count of offline replicas)
# and per-replica questions of a [B] table (is my broker a source). Two
# forms give the same answers, bit for bit (docs/DESIGN.md "Per-broker
# reductions of the flat replica axis"):
#
# - "segment": ``jax.ops.segment_*`` keyed by broker id and ``table[seg]``.
#   On the v5e a scatter or a gather costs by the element it touches (5.5-
#   5.9 ns), whatever B is.
# - "dense": one masked reduce over the ``[B, n_flat]`` compare
#   ``arange(B)[:, None] == seg[None, :]``, which XLA fuses and never
#   materialises. It costs by the CELL, B * n_flat of them a pass.
#
# DENSE_BROKER_CELLS is the power of two above the largest ``b * n_flat``
# measured on the chip (``utils/microbench.py``, PR 28, ms an iteration of each
# broker's best and second best with the source lookup, segment / dense:
# 1.97 / 0.092 at 128 brokers x 30,000 replicas, 4.90 / 0.235 at 256 x
# 75,000, 19.3 / 1.83 at 1,024 x 300,000 = 3.1e8 cells). The forms have not
# met there; beyond it nothing is measured and a mask XLA did materialise
# would be gigabytes, so the segment form stays. XLA:CPU runs a scatter as
# a tight loop and the dense form twenty to fifty times slower at real
# sizes, so the CPU keeps "segment" (and tier-1 its running time).
DENSE_BROKER_CELLS = 1 << 29


def broker_reduce_form(num_brokers: int, n_flat: int) -> str:
    """"dense" or "segment": the form the per-broker helpers below take at
    these (static) shapes on this backend. The ONE place that chooses."""
    if jax.default_backend() == "cpu":
        return "segment"
    return "dense" if num_brokers * n_flat <= DENSE_BROKER_CELLS \
        else "segment"


def broker_segments(state: ClusterTensors) -> jax.Array:
    """[n_flat] int32 — the broker of every flat replica; empty slots go to
    the dead bucket ``num_brokers``, which no dense column matches."""
    return flatten_slots(jnp.where(state.assignment >= 0, state.assignment,
                                   state.num_brokers))


def _broker_mask(seg_flat: jax.Array, b: int) -> jax.Array:
    """[B, n_flat] bool — row i marks broker i's flat replicas."""
    return jnp.arange(b, dtype=seg_flat.dtype)[:, None] == seg_flat[None, :]


def broker_best_rows(fw: jax.Array, seg_flat: jax.Array, rows: jax.Array,
                     skip: jax.Array | None = None,
                     ) -> tuple[jax.Array, jax.Array]:
    """``broker_best``'s dense form over the brokers ``rows [M]`` alone:
    ``(w [M], idx [M])``, row i exactly what ``broker_best`` reads at broker
    ``rows[i]`` (``skip [M]`` likewise). The masked reduce runs over
    ``[M, n_flat]`` cells, so it costs M / B of the full one."""
    n_flat = fw.shape[0]
    idxs = jnp.arange(n_flat, dtype=jnp.int32)
    mask = rows.astype(seg_flat.dtype)[:, None] == seg_flat[None, :]
    if skip is not None:
        mask &= idxs[None, :] != skip[:, None]
    masked = jnp.where(mask, fw[None, :], -jnp.inf)
    w = masked.max(axis=1)
    # argmax takes the first of equals: the lowest flat index
    first = jnp.argmax(masked, axis=1).astype(jnp.int32)
    return w, jnp.where(jnp.isfinite(w), first, n_flat)


def broker_best(fw: jax.Array, seg_flat: jax.Array, b: int, form: str,
                skip: jax.Array | None = None,
                ) -> tuple[jax.Array, jax.Array]:
    """Per broker, the largest weight ``fw`` among its flat replicas and the
    LOWEST flat index that attains it: ``(w [B], idx [B])``. A broker with
    no replica, or none of finite weight, reads ``n_flat`` as its index
    (and -inf, or its non-finite maximum, as its weight). ``skip [B]``
    leaves one flat index per broker out, for the second best. Ties break
    by flat index in both forms: the CPU trajectories are pinned under it
    (``slot_major_flat``)."""
    n_flat = fw.shape[0]
    idxs = jnp.arange(n_flat, dtype=jnp.int32)
    if form == "dense":
        return broker_best_rows(fw, seg_flat,
                                jnp.arange(b, dtype=seg_flat.dtype), skip)
    if skip is not None:
        dead = jnp.array([n_flat], jnp.int32)
        fw = jnp.where(idxs == jnp.concatenate([skip, dead])[seg_flat],
                       -jnp.inf, fw)
    smax = jax.ops.segment_max(fw, seg_flat, num_segments=b + 1)
    is_best = jnp.isfinite(fw) & (fw == smax[seg_flat])
    best = jax.ops.segment_min(jnp.where(is_best, idxs, n_flat), seg_flat,
                               num_segments=b + 1)
    # an empty segment reads int32's maximum
    return smax[:b], jnp.minimum(best[:b], n_flat)


def broker_count(flags: jax.Array, seg_flat: jax.Array, b: int,
                 form: str) -> jax.Array:
    """[B] float32 — how many flat replicas of each broker carry ``flags``
    ([n_flat] bool). A count, so the order of summation cannot show (exact
    below 2**24)."""
    if form == "dense":
        return jnp.where(_broker_mask(seg_flat, b) & flags[None, :],
                         1.0, 0.0).sum(axis=1)
    return jax.ops.segment_sum(flags.astype(jnp.float32), seg_flat,
                               num_segments=b + 1)[:b]


def broker_flag_at(flag: jax.Array, seg_flat: jax.Array,
                   form: str) -> jax.Array:
    """[n_flat] bool — ``flag [B]`` of every flat replica's broker; False
    in the dead bucket."""
    if form == "dense":
        return (_broker_mask(seg_flat, flag.shape[0])
                & flag[:, None]).any(axis=0)
    return jnp.concatenate([flag, jnp.array([False])])[seg_flat]


def offline_per_broker(state: ClusterTensors, off: jax.Array) -> jax.Array:
    """[B] float32 — replicas marked in ``off`` ([P, S] bool,
    ``offline_replicas``) per broker: the self-healing term of the source
    score."""
    seg_flat = broker_segments(state)
    b = state.num_brokers
    return broker_count(flatten_slots(off), seg_flat, b,
                        broker_reduce_form(b, seg_flat.shape[0]))


# ---- top-k of the flat replica axis ----------------------------------------
#
# A move round ranks the whole flat replica axis twice: the source
# selection's global block and the leadership block take the k heaviest
# replicas. ``lax.top_k`` lowers to ONE stable sort of the whole axis (a
# comparator on the float's total order, then the index). Two forms give
# its result, values, indices and order (docs/DESIGN.md "Top-k of the flat
# replica axis"):
#
# - "sort": ``lax.top_k`` itself.
# - "two_level": view the axis as rows of L contiguous elements, rank the
#   rows by their best element, keep the k best rows in ascending order and
#   run ``lax.top_k`` over their k * L elements alone.
#
# Measured on one v5e (ms an iteration of a fused loop, the sort against the
# two-level form at the row length below; docs/DESIGN.md has the table, and
# ``utils/microbench.py`` keeps the two as the cases ``flat_sort<k>`` /
# ``flat_two<k>``): 0.38 against 0.022-0.046 ms at 307,200 flat replicas
# (k 128 to 1,024), 0.086-0.090 against 0.018-0.021 at 76,800, and level at
# 30,720 (0.020-0.021 against 0.017-0.021, k 128 / 256); no shape the grids
# use reads the sort faster. Its result is ``lax.top_k``'s, so it is taken
# wherever it keeps a small share of the axis; ``lax.top_k`` is left where
# it would not.


def flat_topk_form(n_flat: int, k: int) -> str:
    """"sort" or "two_level": the form ``flat_top_k`` takes for these
    (static) shapes. The ONE place that chooses: the two-level form where
    the elements it keeps (k * L) are a quarter of the axis or fewer."""
    return "two_level" if 4 * k * flat_topk_row_len(n_flat, k) <= n_flat \
        else "sort"


def flat_topk_row_len(n_flat: int, k: int) -> int:
    """L, the row length of the two-level form: the power of two nearest
    sqrt(2 n_flat / k), at least 8 (fit to one chip sweep over L: the
    fastest row length it read at every k from 128 to 1,024 at 76,800 and
    307,200, or within 2 % of it)."""
    target = (2.0 * n_flat / max(k, 1)) ** 0.5
    row = 8
    while row * 2 <= target * 2 ** 0.5:
        row *= 2
    return row


def _total_order_key(x: jax.Array) -> jax.Array:
    """int32 keys whose order is the float32 total order ``lax.top_k``'s
    comparator uses (-NaN < -inf < ... < -0.0 < +0.0 < ... < +inf < +NaN)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


# The lowest float32 in the total order (all bits set: a negative NaN):
# the two-level form pads the axis with it, behind every real element.
_TOTAL_ORDER_MIN = np.array(-1, np.int32).view(np.float32)


def two_level_top_k(x: jax.Array, k: int,
                    row_len: int) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k(x, k)`` of a 1-D ``x`` without sorting all of it: the
    ``k`` rows of ``row_len`` elements whose best element ranks highest
    (best first, then the lower row: the order of the elements themselves)
    hold every element of the result. Fewer than k rows rank above the
    row of the k-th element, and each row that holds a result element
    ranks no lower than that row, so the kept rows, taken in ascending
    order, rank among themselves as the whole axis does."""
    n = x.shape[0]
    rows_n = -(-n // row_len)
    kept = min(k, rows_n)
    xp = jnp.concatenate(
        [x, jnp.full(rows_n * row_len - n, _TOTAL_ORDER_MIN, x.dtype)])
    grid = xp.reshape(rows_n, row_len)
    _, rows = jax.lax.top_k(_total_order_key(grid).max(axis=1), kept)
    rows = jnp.sort(rows)
    vals, at = jax.lax.top_k(grid[rows].reshape(-1), k)
    return vals, (rows[at // row_len] * row_len + at % row_len).astype(
        jnp.int32)


def flat_top_k(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """``lax.top_k(x, k)`` over the flat replica axis ``x [n_flat]``, in
    the form ``flat_topk_form`` gives: the same values, indices and order
    under both."""
    n_flat = x.shape[-1]
    if flat_topk_form(n_flat, k) == "two_level":
        return two_level_top_k(x, k, flat_topk_row_len(n_flat, k))
    return jax.lax.top_k(x, k)


def _scatter_to_brokers(state: ClusterTensors, per_slot: jax.Array) -> jax.Array:
    """Sum a [P, S] or [P, S, R] per-replica quantity into per-broker rows
    ([B] or [B, R]). Padded slots route to a dead bucket at index B."""
    b = state.num_brokers
    out = jax.ops.segment_sum(flatten_slots(per_slot), broker_segments(state),
                              num_segments=b + 1)
    return out[:b]


def broker_load(state: ClusterTensors) -> jax.Array:
    """[B, R] float32 — total resource load per broker
    (ClusterModel load accounting; the solver's hottest reduction)."""
    return _scatter_to_brokers(state, replica_load(state))


def broker_replica_counts(state: ClusterTensors) -> jax.Array:
    """[B] int32 — replicas hosted per broker."""
    return _scatter_to_brokers(state, replica_exists(state).astype(jnp.int32))


def broker_leader_counts(state: ClusterTensors) -> jax.Array:
    """[B] int32 — leader replicas per broker."""
    return _scatter_to_brokers(state, is_leader_slot(state).astype(jnp.int32))


def _topic_broker_counts(state: ClusterTensors, num_topics: int,
                         per_slot: jax.Array) -> jax.Array:
    """[T, B] int32 — count of ``per_slot``-selected replicas per
    (topic, broker) via one flattened segment-sum; masked-out slots route to
    a one-past-the-end bucket."""
    b = state.num_brokers
    seg = jnp.where(per_slot, state.topic[:, None] * (b + 1)
                    + jnp.where(state.assignment >= 0, state.assignment, b),
                    num_topics * (b + 1))
    flat = flatten_slots(per_slot.astype(jnp.int32))
    out = jax.ops.segment_sum(flat, flatten_slots(seg),
                              num_segments=num_topics * (b + 1) + 1)
    return out[:num_topics * (b + 1)].reshape(num_topics, b + 1)[:, :b]


def topic_broker_replica_counts(state: ClusterTensors, num_topics: int) -> jax.Array:
    """[T, B] int32 — replicas per (topic, broker), for topic-replica
    distribution and min-topic-leaders goals."""
    return _topic_broker_counts(state, num_topics, replica_exists(state))


def topic_broker_leader_counts(state: ClusterTensors, num_topics: int) -> jax.Array:
    """[T, B] int32 — leaders per (topic, broker)."""
    return _topic_broker_counts(state, num_topics, is_leader_slot(state))


def potential_nw_out(state: ClusterTensors) -> jax.Array:
    """[B] float32 — potential network-outbound load per broker: the NW_OUT
    every broker would bear if all its replicas became leaders
    (ClusterModel.potentialLeadershipLoadFor; used by PotentialNwOutGoal)."""
    from ..common.resources import Resource
    nw_out = state.leader_load[:, Resource.NW_OUT]
    per_slot = jnp.broadcast_to(nw_out[:, None], state.assignment.shape) \
        * replica_exists(state)
    return _scatter_to_brokers(state, per_slot)


def leader_bytes_in(state: ClusterTensors) -> jax.Array:
    """[B] float32 — leader NW_IN per broker (the LeaderBytesInDistribution
    aggregate; also maintained incrementally by analyzer.agg)."""
    from ..common.resources import Resource
    per_slot = jnp.where(
        is_leader_slot(state),
        jnp.broadcast_to(state.leader_load[:, int(Resource.NW_IN)][:, None],
                         state.assignment.shape),
        0.0)
    return _scatter_to_brokers(state, per_slot)


def rack_partition_counts(state: ClusterTensors, num_racks: int) -> jax.Array:
    """[P, K] int32 — replicas of each partition per rack (rack-aware goals)."""
    exists = replica_exists(state)
    broker_rack = jnp.concatenate([state.rack, jnp.array([num_racks], dtype=state.rack.dtype)])
    slot_rack = broker_rack[jnp.where(state.assignment >= 0, state.assignment,
                                      state.num_brokers)]
    one_hot = jax.nn.one_hot(slot_rack, num_racks + 1, dtype=jnp.int32)
    return (one_hot * exists[:, :, None].astype(jnp.int32)).sum(axis=1)[:, :num_racks]


def alive_mask(state: ClusterTensors) -> jax.Array:
    """[B] bool — broker alive & real (Broker.State ALIVE/NEW/DEMOTED/BAD_DISKS
    count as alive for hosting; DEAD does not: Broker.java isAlive)."""
    return (state.broker_state != jnp.int8(BrokerState.DEAD)) & state.broker_mask


def new_broker_mask(state: ClusterTensors) -> jax.Array:
    return (state.broker_state == jnp.int8(BrokerState.NEW)) & state.broker_mask


def offline_replicas(state: ClusterTensors) -> jax.Array:
    """[P, S] bool — replicas on dead brokers (self-healing eligible;
    ClusterModel.selfHealingEligibleReplicas)."""
    dead = ~alive_mask(state)
    dead_pad = jnp.concatenate([dead, jnp.array([True])])
    return replica_exists(state) & dead_pad[
        jnp.where(state.assignment >= 0, state.assignment, state.num_brokers)]


# ---- functional mutations (the search's move operators) ------------------

def apply_replica_move(state: ClusterTensors, partition: jax.Array, slot: jax.Array,
                       dst_broker: jax.Array) -> ClusterTensors:
    """Move the replica at (partition, slot) to dst_broker
    (ClusterModel.relocateReplica:380, functional)."""
    new_assignment = state.assignment.at[partition, slot].set(
        dst_broker.astype(state.assignment.dtype))
    return dataclasses.replace(state, assignment=new_assignment)


def apply_leadership_move(state: ClusterTensors, partition: jax.Array,
                          new_leader_slot: jax.Array) -> ClusterTensors:
    """Transfer leadership to another in-sync slot
    (ClusterModel.relocateLeadership:409, functional)."""
    new_leader = state.leader_slot.at[partition].set(
        new_leader_slot.astype(state.leader_slot.dtype))
    return dataclasses.replace(state, leader_slot=new_leader)


def apply_swap(state: ClusterTensors, p1: jax.Array, s1: jax.Array,
               p2: jax.Array, s2: jax.Array) -> ClusterTensors:
    """Swap the broker placements of two replicas (INTER_BROKER_REPLICA_SWAP)."""
    b1 = state.assignment[p1, s1]
    b2 = state.assignment[p2, s2]
    new_assignment = state.assignment.at[p1, s1].set(b2).at[p2, s2].set(b1)
    return dataclasses.replace(state, assignment=new_assignment)


def set_broker_state(state: ClusterTensors, broker: jax.Array, code: int) -> ClusterTensors:
    """(ClusterModel.setBrokerState:297, functional)."""
    return dataclasses.replace(
        state, broker_state=state.broker_state.at[broker].set(jnp.int8(code)))
