"""Per-round derived state shared by every goal kernel.

The reference recomputes broker loads incrementally inside its object graph;
here one fused computation refreshes every derived tensor per search round
(cheap on TPU, and XLA fuses it into the round kernel).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..common.resources import Resource
from ..model.tensors import (
    ClusterTensors, alive_mask, broker_leader_counts, broker_load,
    broker_replica_counts, new_broker_mask, potential_nw_out,
)
from .constraint import BalancingConstraint


@partial(jax.tree_util.register_dataclass,
         data_fields=["broker_load", "broker_replicas", "broker_leaders",
                      "pot_nw_out", "alive", "new_brokers", "allowed_replica_move",
                      "replica_dest_ok", "allowed_leadership", "avg_util", "avg_replicas",
                      "avg_leaders", "movable_partition"],
         meta_fields=[])
@dataclasses.dataclass(frozen=True)
class DerivedState:
    broker_load: jax.Array        # [B, R]
    broker_replicas: jax.Array    # [B] int32
    broker_leaders: jax.Array     # [B] int32
    pot_nw_out: jax.Array         # [B]
    alive: jax.Array              # [B] bool
    new_brokers: jax.Array        # [B] bool
    allowed_replica_move: jax.Array  # [B] bool (alive & not excluded as dest)
    replica_dest_ok: jax.Array    # [B] bool (may RECEIVE a replica; below)
    allowed_leadership: jax.Array    # [B] bool
    avg_util: jax.Array           # [R] — Σload / Σcapacity over allowed brokers
    avg_replicas: jax.Array       # scalar f32 over alive brokers
    avg_leaders: jax.Array        # scalar f32
    movable_partition: jax.Array  # [P] bool (not in an excluded topic)


def compute_derived(state: ClusterTensors,
                    excluded_topic_mask: jax.Array | None = None,
                    excluded_replica_move_brokers: jax.Array | None = None,
                    excluded_leadership_brokers: jax.Array | None = None,
                    psum=None, agg=None) -> DerivedState:
    """All per-broker aggregates + cluster averages in one pass.

    ``excluded_*`` are boolean masks aligned with topics/brokers (host-built
    from OptimizationOptions by the optimizer). ``psum`` combines the
    partition-additive aggregates across a sharded mesh (identity when the
    whole model lives on one device). ``agg`` (an
    :class:`~cruise_control_tpu.analyzer.agg.AggCarry`) supplies the
    per-broker aggregates pre-computed — the incrementally-maintained loop
    carry — skipping the O(P·S) segment-sums (and their psums: the carry is
    already global on a mesh).
    """
    p = psum or (lambda x: x)
    alive = alive_mask(state)
    if agg is not None:
        load, reps, leads, pot = (agg.broker_load, agg.broker_replicas,
                                  agg.broker_leaders, agg.pot_nw_out)
    else:
        load = p(broker_load(state))
        reps = p(broker_replica_counts(state))
        leads = p(broker_leader_counts(state))
        pot = p(potential_nw_out(state))
    new_b = new_broker_mask(state)

    excl_rm = (jnp.zeros(state.num_brokers, dtype=bool)
               if excluded_replica_move_brokers is None else excluded_replica_move_brokers)
    excl_ld = (jnp.zeros(state.num_brokers, dtype=bool)
               if excluded_leadership_brokers is None else excluded_leadership_brokers)
    allowed_rm = alive & ~excl_rm
    allowed_ld = alive & ~excl_ld
    # The scale-out's rule, written once (docs/DESIGN.md "The scale-out's
    # rule"): while a broker is NEW, replicas move only onto NEW brokers,
    # never among the old ones (upstream's REST documentation of POST
    # /add_broker: "the replicas are only moved from the existing brokers
    # to the new brokers, not among existing brokers"; SURVEY.md Appendix
    # A.2 item 6). With no NEW broker the field IS allowed_replica_move.
    # Whatever picks or accepts a replica's destination reads this field;
    # the one exemption, an offline replica's, is ``broker_masks_at``'s.
    dest_ok = allowed_rm & (new_b | ~new_b.any())

    # avgUtilizationPercentage = Σ load / Σ capacity over brokers allowed
    # replica moves (ResourceDistributionGoal.java:245-248).
    cap_sum = jnp.maximum((state.capacity * allowed_rm[:, None]).sum(axis=0), 1e-9)
    load_sum = (load * allowed_rm[:, None]).sum(axis=0)
    avg_util = load_sum / cap_sum

    n_alive = jnp.maximum(alive.sum(), 1)
    avg_reps = (reps * alive).sum() / n_alive
    avg_leads = (leads * alive).sum() / n_alive

    if excluded_topic_mask is None:
        movable = state.partition_mask
    else:
        movable = state.partition_mask & ~excluded_topic_mask[state.topic]

    return DerivedState(
        broker_load=load, broker_replicas=reps, broker_leaders=leads,
        pot_nw_out=pot, alive=alive, new_brokers=new_b,
        allowed_replica_move=allowed_rm, replica_dest_ok=dest_ok,
        allowed_leadership=allowed_ld,
        avg_util=avg_util, avg_replicas=avg_reps, avg_leaders=avg_leads,
        movable_partition=movable,
    )


def healing(derived: DerivedState) -> jax.Array:
    """Scalar bool: a replica is offline, that is a DEAD broker still hosts
    one (``offline_replicas(state).any()``, read from the per-broker counts
    instead of every slot). Global on a mesh, as the counts are."""
    return ((derived.broker_replicas > 0) & ~derived.alive).any()


def dest_columns_ok(derived: DerivedState) -> jax.Array:
    """[B] bool: the brokers a round may offer as destination COLUMNS:
    ``replica_dest_ok``, widened to every broker allowed replica moves
    while a replica is offline (``healing``), so that self-healing is not
    held up by a scale-out. Which candidates of a widened column are
    legitimate is still ``broker_masks_at``'s to say."""
    return derived.replica_dest_ok \
        | (healing(derived) & derived.allowed_replica_move)


def broker_masks_at(derived: DerivedState, dst: jax.Array,
                    src_offline: jax.Array, from_dst=lambda x: x,
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The lookup of the per-broker masks at the destinations ``dst``
    (in-range broker indices), per candidate: ([N] alive, [N] allowed
    leadership, [N] may RECEIVE the candidate's replica). ``dst`` holds
    one broker per candidate, or one per entry of the candidate grid's
    destination margin with ``from_dst`` the margin's broadcast to the
    candidates (``candidates.compute_deltas``); ``src_offline`` is [N].
    ONE lookup a destination: the four per-broker masks ride one table of
    bits (a gather on the chip costs by the element it produces, and with
    no broker excluded XLA folded the separate lookups into one anyway;
    PERF.md, PR 32).

    An online replica is received only where ``replica_dest_ok``; an
    OFFLINE one (``src_offline``: its broker is dead) on any broker
    allowed replica moves, NEW brokers or not (assumed from upstream's
    ``GoalUtils.eligibleBrokers``, which the reference tree on this
    machine does not hold; upstream also lets a replica return to its
    original broker, which the tensors do not record: docs/DESIGN.md)."""
    bits = (derived.alive, derived.allowed_leadership,
            derived.replica_dest_ok, derived.allowed_replica_move)
    table = sum(mask.astype(jnp.int8) << i for i, mask in enumerate(bits))
    at_dst = from_dst(table[dst])
    alive, may_lead, online_ok, offline_ok = (
        (at_dst >> i) & 1 == 1 for i in range(len(bits)))
    return alive, may_lead, jnp.where(src_offline, offline_ok, online_ok)


def resource_limits(state: ClusterTensors, derived: DerivedState,
                    constraint: BalancingConstraint, resource: Resource,
                    for_detector: bool = False) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(lower[B], upper[B], capacity_limit[B]) absolute load limits per
    broker for one resource (balance band around the average utilization +
    the capacity threshold; ResourceDistributionGoal.initGoalState /
    CapacityGoal)."""
    r = int(resource)
    lo_mult, up_mult = constraint.balance_band(resource, for_detector)
    cap = state.capacity[:, r]
    lower = derived.avg_util[r] * lo_mult * cap
    upper = derived.avg_util[r] * up_mult * cap
    cap_limit = constraint.capacity_threshold[r] * cap
    return lower, upper, cap_limit


def count_limits(avg: jax.Array, threshold: float) -> tuple[jax.Array, jax.Array]:
    """(lower, upper) replica-count limits
    (ReplicaDistributionAbstractGoal.initGoalState: ceil(avg*t), floor(avg/t))."""
    upper = jnp.ceil(avg * threshold)
    lower = jnp.floor(avg / threshold)
    return lower, upper
