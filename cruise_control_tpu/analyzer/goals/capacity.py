"""Hard capacity goals.

Reference parity: analyzer/goals/CapacityGoal.java (+ the four 45-line
specializations DiskCapacityGoal / NetworkInboundCapacityGoal /
NetworkOutboundCapacityGoal / CpuCapacityGoal) and ReplicaCapacityGoal.java.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ...common.resources import Resource
from ...model.tensors import replica_load_column, replica_load_total
from ..candidates import CandidateDeltas
from .base import Goal, pair_improvement


@dataclasses.dataclass(frozen=True)
class ResourceCapacityGoal(Goal):
    """Keep every alive broker's load for one resource under
    capacity × capacity_threshold (CapacityGoal.java)."""

    resource: Resource = Resource.DISK

    def _limit(self, state, constraint):
        r = int(self.resource)
        return constraint.capacity_threshold[r] * state.capacity[:, r]

    def broker_violations(self, state, derived, constraint, aux):
        limit = self._limit(state, constraint)
        load = derived.broker_load[:, int(self.resource)]
        return jnp.where(derived.alive, jnp.maximum(load - limit, 0.0), 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        # isMovementAcceptableForCapacity: destination stays within its
        # capacity limit after receiving the load (including inflow from
        # higher-ranked candidates accepted this round).
        r = int(self.resource)
        limit = self._limit(state, constraint)
        dst_after = deltas.at_dst(derived.broker_load[:, r]) \
            + deltas.pre_load("pre_dst_load", r) + deltas.load_delta[:, r]
        return dst_after <= deltas.at_dst(limit) + 1e-6

    def improvement(self, state, derived, constraint, aux, deltas):
        r = int(self.resource)
        limit = self._limit(state, constraint)

        def viol(value, at):
            return jnp.maximum(value - at(limit), 0.0)

        return pair_improvement(derived.broker_load[:, r], deltas,
                                deltas.load_delta[:, r], viol)

    def dest_score(self, state, derived, constraint, aux):
        limit = self._limit(state, constraint)
        headroom = limit - derived.broker_load[:, int(self.resource)]
        return jnp.where(derived.allowed_replica_move, headroom, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        return replica_load_column(state, int(self.resource))

    def swap_leg_acceptance(self, state, derived, constraint, aux, leg):
        # Judged on the net transfer only — a leg-wise capacity check would
        # spuriously veto swaps whose net effect is within limits.
        return jnp.ones(leg.valid.shape[0], dtype=bool)

    def swap_net_acceptance(self, state, derived, constraint, aux, net):
        # Net transfer is SIGNED (a swap ranked on another resource can pull
        # load toward the source on this one): bound BOTH endpoints.
        r = int(self.resource)
        limit = self._limit(state, constraint)
        d = net.load_delta[:, r]
        load = derived.broker_load[:, r]
        dst_ok = net.at_dst(load) + d <= net.at_dst(limit) + 1e-6
        src_ok = net.at_src(load) - d <= net.at_src(limit) + 1e-6
        return dst_ok & src_ok


@dataclasses.dataclass(frozen=True)
class ReplicaCapacityGoal(Goal):
    """Max replicas per alive broker (ReplicaCapacityGoal.java:340LoC)."""

    def broker_violations(self, state, derived, constraint, aux):
        over = derived.broker_replicas - constraint.max_replicas_per_broker
        return jnp.where(derived.alive, jnp.maximum(over, 0).astype(jnp.float32), 0.0)

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        dst_after = deltas.at_dst(derived.broker_replicas) \
            + deltas.pre0("pre_dst_count") + deltas.replica_delta
        return dst_after <= constraint.max_replicas_per_broker

    def improvement(self, state, derived, constraint, aux, deltas):
        cap = float(constraint.max_replicas_per_broker)

        def viol(value, _at):
            return jnp.maximum(value - cap, 0.0)

        return pair_improvement(derived.broker_replicas.astype(jnp.float32), deltas,
                                deltas.replica_delta.astype(jnp.float32), viol)

    def dest_score(self, state, derived, constraint, aux):
        headroom = (constraint.max_replicas_per_broker
                    - derived.broker_replicas).astype(jnp.float32)
        return jnp.where(derived.allowed_replica_move & (headroom > 0),
                         headroom, -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        # Any replica works; prefer light ones to minimize load disturbance.
        return -replica_load_total(state)

    def swap_leg_acceptance(self, state, derived, constraint, aux, leg):
        # Swaps never change per-broker replica counts: always acceptable.
        return jnp.ones(leg.valid.shape[0], dtype=bool)


def make_capacity_goals() -> list[Goal]:
    return [
        ResourceCapacityGoal(name="DiskCapacityGoal", is_hard=True,
                             resource=Resource.DISK),
        ResourceCapacityGoal(name="NetworkInboundCapacityGoal", is_hard=True,
                             resource=Resource.NW_IN),
        ResourceCapacityGoal(name="NetworkOutboundCapacityGoal", is_hard=True,
                             include_leadership=True, resource=Resource.NW_OUT),
        ResourceCapacityGoal(name="CpuCapacityGoal", is_hard=True,
                             include_leadership=True, resource=Resource.CPU),
    ]
