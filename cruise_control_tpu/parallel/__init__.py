"""Multi-chip SPMD solver: device mesh + partition-axis-sharded search.

TPU-native replacement for the reference in-JVM concurrency (precompute
thread pool, shared mutable ClusterModel -- SURVEY.md §2.11): collectives
over ICI/DCN instead of locks.
"""

from .chain_sharded import optimize_chain_sharded
from .mesh import (
    PARTITION_AXIS, make_mesh, partition_sharding, replicated_sharding,
    shard_cluster,
)

__all__ = [
    "PARTITION_AXIS", "make_mesh", "partition_sharding", "replicated_sharding",
    "optimize_chain_sharded", "shard_cluster",
]
