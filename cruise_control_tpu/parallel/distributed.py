"""Multi-host solver initialization (jax.distributed over ICI/DCN).

The reference scales its optimizer with an in-JVM thread pool
(GoalOptimizer.java:112-119) and talks to the outside world over
Kafka/ZooKeeper RPC (SURVEY.md §2.11). The TPU-native equivalent runs ONE
SPMD program over a pod slice: each host process owns its local chips,
``jax.distributed.initialize`` wires the processes into a single runtime,
and the solver mesh spans every device — collectives ride ICI within a
slice and DCN across slices. No hand-rolled RPC: the sharded kernels in
``chain_sharded.py`` are topology-agnostic (they see one mesh).

Usage (one process per host, e.g. under GKE/ray/mpi):

    from cruise_control_tpu.parallel import distributed
    distributed.initialize()            # env-driven (TPU pods auto-detect)
    mesh = distributed.global_mesh()    # 1-D mesh over ALL devices
    sharded = shard_cluster(state, mesh)  # global arrays, per-host shards

On a TPU pod slice, ``initialize()`` needs no arguments — the TPU runtime
supplies coordinator address, process count and process id. Elsewhere pass
them explicitly or via JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID.
"""

from __future__ import annotations

import os
import warnings

import jax
import numpy as np
from jax.sharding import Mesh

from .mesh import PARTITION_AXIS

_initialized = False


# Env markers a TPU pod / multislice runtime sets on worker hosts —
# checkable WITHOUT touching the XLA backend (jax.distributed.initialize
# must run before any backend use, so probing jax.devices()/process_count()
# here would make multi-host init impossible).
_POD_ENV_MARKERS = ("TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID",
                    "MEGASCALE_COORDINATOR_ADDRESS", "CLOUD_TPU_TASK_ID")


def _backend_initialized() -> bool:
    from jax._src import xla_bridge
    return bool(xla_bridge.backends_are_initialized())


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Join this process into the multi-host JAX runtime (idempotent).

    MUST run before any JAX call that initializes the XLA backend. The
    decision to join is made purely from arguments and environment
    variables for the same reason. Single-process deployments may skip
    this entirely; with no explicit configuration and no pod environment
    markers it is a no-op.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    env_pid = os.environ.get("JAX_PROCESS_ID")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None)
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None)

    explicit = coordinator_address is not None or num_processes is not None \
        or process_id is not None
    on_pod = any(os.environ.get(m) for m in _POD_ENV_MARKERS)
    if not explicit and not on_pod:
        return  # single-host run; nothing to join
    if _backend_initialized():
        if not explicit:
            # Pod env markers alone are not a request for multi-host init —
            # single-host TPU VMs carry them too. A library user who touched
            # JAX first gets a warning and a single-process runtime, not a
            # crash.
            # NOT latched as initialized: a later explicit
            # initialize(coordinator_address=...) must still raise loudly
            # rather than silently no-op on the idempotency check.
            warnings.warn(
                "parallel.distributed.initialize(): XLA backend already "
                "initialized and no explicit multi-host configuration was "
                "given — continuing single-process. To join a multi-host "
                "runtime, call initialize() before any jax computation.",
                RuntimeWarning, stacklevel=2)
            return
        raise RuntimeError(
            "parallel.distributed.initialize() called after the XLA backend "
            "was already initialized — call it before any jax computation "
            "or device query in this process.")
    if explicit:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    else:
        jax.distributed.initialize()  # TPU pod runtime auto-detects
    _initialized = True


def global_mesh() -> Mesh:
    """1-D solver mesh over every device in the (possibly multi-host)
    runtime. With ``jax.distributed`` initialized, ``jax.devices()`` lists
    ALL devices across hosts; each host addresses only its local shards and
    the sharded kernels' psum/all_gather ride ICI/DCN."""
    return Mesh(np.asarray(jax.devices()), (PARTITION_AXIS,))


def process_info() -> dict:
    """Diagnostic snapshot for the STATE endpoint / logs."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "backend": jax.default_backend(),
    }
