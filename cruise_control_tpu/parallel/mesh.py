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
