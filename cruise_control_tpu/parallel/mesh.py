"""Device-mesh helpers for the sharded solver.

The solver's scale axis is the partition dimension of the cluster load
tensors (SURVEY.md §5 "long-context" mapping: N windows × M partitions,
O(brokers × replicas) search). Multi-chip runs shard that axis over a 1-D
``jax.sharding.Mesh`` named ``"p"``; broker-indexed aggregates stay
replicated and travel through ``psum`` collectives over ICI/DCN — the
TPU-native replacement for the reference's in-JVM shared-memory threading
(GoalOptimizer.java:112-119 precompute pool; SURVEY.md §2.11).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analyzer.search import ExclusionMasks
from ..model.tensors import ClusterTensors

PARTITION_AXIS = "p"


def make_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices (all by default)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices but only {len(devices)} present")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (PARTITION_AXIS,))


def partition_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for arrays whose leading axis is the partition axis."""
    return NamedSharding(mesh, P(PARTITION_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _psum(x):
    """The mesh, as the analyzer's ``psum=`` keyword takes it."""
    return jax.lax.psum(x, PARTITION_AXIS)


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


def _mask_specs(mask_presence: tuple[bool, bool, bool]) -> ExclusionMasks:
    return ExclusionMasks(
        excluded_topics=P() if mask_presence[0] else None,
        excluded_replica_move_brokers=P() if mask_presence[1] else None,
        excluded_leadership_brokers=P() if mask_presence[2] else None)


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
