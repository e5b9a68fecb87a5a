"""Broker-set awareness.

Reference parity: analyzer/goals/BrokerSetAwareGoal.java:80 (hard goal:
every topic's replicas confined to ONE broker set, where broker sets come
from brokerSets.json via a pluggable resolver) — the reference resolves a
topic's target set from its current placement and rejects any action that
crosses set boundaries.

The goal instance carries the broker→set mapping as a hashable tuple
(indexed by broker INDEX; the optimizer/facade translates broker ids via
ClusterMeta) so it remains a static jit argument like every other goal.
A topic's home set = the set hosting the majority of its replicas (ties →
lowest set id), computed as a partition-additive [T, num_sets] count so the
sharded search can psum it.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ...model.tensors import replica_exists, replica_load_total
from ..candidates import CandidateDeltas
from .base import Goal


def broker_sets_from_file(path: str, broker_ids: list[int]) -> tuple[int, ...]:
    """Parse brokerSets.json ({"brokerSets": [{"brokerSetId", "brokerIds"}]})
    into the per-broker-index set-id tuple this goal consumes. Brokers not
    named by any set share one implicit trailing set (the reference treats
    unmapped brokers as an error; the implicit set keeps dev clusters
    usable while still confining mapped topics)."""
    import json
    with open(path) as f:
        doc = json.load(f)
    set_of: dict[int, int] = {}
    for k, entry in enumerate(doc.get("brokerSets", [])):
        for bid in entry.get("brokerIds", []):
            set_of[int(bid)] = k
    implicit = len(doc.get("brokerSets", []))
    return tuple(set_of.get(bid, implicit) for bid in broker_ids)


@dataclasses.dataclass(frozen=True)
class BrokerSetAwareGoal(Goal):
    name: str = "BrokerSetAwareGoal"
    is_hard: bool = True
    partition_additive_scores: bool = True
    broker_sets: tuple[int, ...] = ()    # set id per broker index

    def _set_array(self, state) -> jax.Array:
        if self.broker_sets:
            sets = jnp.asarray(self.broker_sets, dtype=jnp.int32)
        else:
            sets = jnp.zeros(state.num_brokers, dtype=jnp.int32)
        return sets

    @property
    def _num_sets(self) -> int:
        return (max(self.broker_sets) + 1) if self.broker_sets else 1

    def _slot_sets(self, state) -> jax.Array:
        """[P, S] set id per replica slot (num_sets for empty)."""
        sets = self._set_array(state)
        pad = jnp.concatenate([sets, jnp.array([self._num_sets], jnp.int32)])
        return pad[jnp.where(state.assignment >= 0, state.assignment,
                             state.num_brokers)]

    def prepare_partial(self, state, num_topics: int):
        """[T, num_sets] replica counts (additive over partitions)."""
        k = self._num_sets
        slot_sets = self._slot_sets(state)
        exists = replica_exists(state)
        seg = jnp.where(exists, state.topic[:, None] * (k + 1)
                        + jnp.minimum(slot_sets, k), num_topics * (k + 1))
        out = jax.ops.segment_sum(exists.astype(jnp.int32).reshape(-1),
                                  seg.reshape(-1),
                                  num_segments=num_topics * (k + 1) + 1)
        return out[:num_topics * (k + 1)].reshape(num_topics, k + 1)[:, :k]

    def finalize_aux(self, partial, state, derived, constraint):
        """aux = (home_set[T], counts[T, K])."""
        return (jnp.argmax(partial, axis=1).astype(jnp.int32), partial)

    def _misplaced(self, state, aux) -> jax.Array:
        """[P, S] bool — replica outside its topic's home set."""
        home, _counts = aux
        slot_sets = self._slot_sets(state)
        topic_home = home[state.topic]          # [P]
        return replica_exists(state) & (slot_sets != topic_home[:, None])

    def broker_violations(self, state, derived, constraint, aux):
        # Excluded-topic replicas are unmovable: not counted as violations
        # (GoalUtils excluded-topic filtering semantics).
        mis = self._misplaced(state, aux) & derived.movable_partition[:, None]
        b = state.num_brokers
        seg = jnp.where(state.assignment >= 0, state.assignment, b).reshape(-1)
        out = jax.ops.segment_sum(mis.astype(jnp.float32).reshape(-1), seg,
                                  num_segments=b + 1)
        return out[:b]

    def acceptance(self, state, derived, constraint, aux, deltas: CandidateDeltas):
        home, _ = aux
        sets = self._set_array(state)
        dst_ok = deltas.at_dst(sets) == deltas.at_topic(home)
        is_move = deltas.replica_delta > 0
        return jnp.where(is_move, dst_ok, True)

    def improvement(self, state, derived, constraint, aux, deltas):
        home, _ = aux
        sets = self._set_array(state)
        home_t = deltas.at_topic(home)
        src_bad = (deltas.at_src(sets) != home_t).astype(jnp.float32)
        dst_bad = (deltas.at_dst(sets) != home_t).astype(jnp.float32)
        is_move = deltas.replica_delta > 0
        imp = jnp.where(is_move, src_bad - dst_bad, 0.0)
        return jnp.where(deltas.valid, imp, -jnp.inf)

    def dest_score(self, state, derived, constraint, aux):
        return jnp.where(derived.allowed_replica_move,
                         -derived.broker_replicas.astype(jnp.float32), -jnp.inf)

    def replica_weight(self, state, derived, constraint, aux):
        mis = self._misplaced(state, aux)
        return jnp.where(mis, 1.0 + replica_load_total(state), -jnp.inf)
