"""XLA / device telemetry → the sensor registry.

The two PRs before this one created exactly the blind spots this module
covers: fleet shape-bucketing exists to stop recompile churn, and the
incremental model pipeline exists to cut host→device transfer — yet
nothing measured compile events, transfer bytes, or device memory, so
neither fix could be proven live. Three surfaces, all flowing into the
same ``/metrics`` scrape (ambient per-cluster labels apply):

- **Compilation**: ``jax.monitoring`` event listeners record every XLA
  backend compile (count + seconds, histogram ``xla_compile_seconds``)
  and persistent-cache hits/misses, and the seconds jax spends BEFORE
  the backend on every first call of a shape: tracing the Python function
  to a jaxpr (``xla_trace_seconds``) and lowering the jaxpr to an MLIR
  module (``xla_lower_seconds``) — what a persistent-cache hit does not
  skip. jax reports a nested event inside its caller's duration too (a
  jit called by a traced function, the compile of a constant computed
  while tracing), so these two histograms hold each event's OWN seconds,
  its nested events taken out: trace + lower + compile add up to the
  seconds jax spent, each counted once. All are labeled with the padded
  bucket shape ambient at dispatch time (``shape_scope``), so a
  shape-flap recompile storm shows up as new ``shape=`` series — proving
  or disproving the bucket-hysteresis fix.
- **Device memory**: ``device_memory_bytes{device,kind}`` gauges from
  ``Device.memory_stats()`` (TPU/GPU allocator stats), refreshed at
  scrape time. Backends without allocator stats (CPU) fall back to the
  live jax.Array footprint so the series exists everywhere.
- **Transfers**: ``record_transfer()`` counts host↔device bytes at the
  call sites that move model data (the refresh pipeline's fused
  ``device_put``), and annotates the ambient trace span.

JAX-version caveats (documented in docs/DESIGN.md): the monitoring event
names are jax-internal strings — ``install()`` matches by suffix so a
rename degrades to missing series, never an exception; listeners cannot
be unregistered on this jax line, so install is once-per-process and
``enabled`` is checked inside the callbacks.
"""

from __future__ import annotations

import contextvars
import logging
import threading
from contextlib import contextmanager

from .sensors import SENSORS

LOG = logging.getLogger(__name__)

# Compile times span ~3 decades beyond span latencies: a warm small-shape
# compile is ~50 ms, a cold 7k-broker chain compile is minutes.
COMPILE_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 60.0,
                   150.0, 300.0, 600.0)

# The padded bucket shape whose dispatch is currently executing, e.g.
# "p102400_b1024" (set by GoalOptimizer around the solve): compiles fire
# from inside jit tracing, so a contextvar is the only way to attribute
# them to a model shape without threading labels through jax.
_SHAPE: contextvars.ContextVar[str | None] = \
    contextvars.ContextVar("xla_shape_label", default=None)

_BACKEND_COMPILE_SUFFIX = "backend_compile_duration"
_JAXPR_TRACE_SUFFIX = "jaxpr_trace_duration"
_JAXPR_LOWER_SUFFIX = "jaxpr_to_mlir_module_duration"
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "xla_compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "xla_compile_cache_misses",
}

_NESTING_SUFFIXES = (_BACKEND_COMPILE_SUFFIX, _JAXPR_TRACE_SUFFIX,
                     _JAXPR_LOWER_SUFFIX)

_install_lock = threading.Lock()
_installed = False
_enabled = True

# Per thread, the trace / lower / compile events jax has begun and not
# ended, innermost last: [event, seconds of the events nested in it].
_OPEN = threading.local()


@contextmanager
def shape_scope(num_partitions: int, num_brokers: int):
    """Label XLA compiles fired under this block with the padded model
    shape (the solver's compiled-kernel identity)."""
    token = _SHAPE.set(f"p{num_partitions}_b{num_brokers}")
    try:
        yield
    finally:
        _SHAPE.reset(token)


def _on_scalar(event: str, value: float, **kwargs) -> None:
    """jax announces the start of a timed event with a scalar of the same
    name: that opens a level of nesting on this thread."""
    if _enabled and event.endswith(_NESTING_SUFFIXES):
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        stack.append([event, 0.0])


def _own_seconds(event: str, duration_secs: float) -> float:
    """Close the innermost open ``event`` of this thread: its duration
    less what ran nested in it, and the whole of it charged to the event
    that encloses it. An event whose start was not seen keeps all."""
    stack = getattr(_OPEN, "stack", None) or []
    nested = 0.0
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] == event:
            nested = stack[i][1]
            del stack[i:]
            break
    if stack:
        stack[-1][1] += duration_secs
    return max(0.0, duration_secs - nested)


def _on_event_duration(event: str, duration_secs: float, **kwargs) -> None:
    if not _enabled:
        return
    try:
        labels = {"shape": _SHAPE.get() or "unscoped"}
        if event.endswith(_NESTING_SUFFIXES):
            own_secs = _own_seconds(event, duration_secs)
        if event.endswith(_BACKEND_COMPILE_SUFFIX):
            SENSORS.count("xla_compile_events", labels=labels)
            # Histogram ONLY — a timer named xla_compile would render the
            # same xla_compile_seconds_sum/_count family twice and
            # Prometheus rejects duplicate-sample scrapes outright.
            SENSORS.observe("xla_compile_seconds", duration_secs,
                            labels=labels, buckets=COMPILE_BUCKETS)
        elif event.endswith(_JAXPR_TRACE_SUFFIX):
            # Python traced to a jaxpr: paid on every first call of a
            # shape, persistent-cache hit or not.
            SENSORS.observe("xla_trace_seconds", own_secs,
                            labels=labels, buckets=COMPILE_BUCKETS)
        elif event.endswith(_JAXPR_LOWER_SUFFIX):
            # ... and the jaxpr lowered to an MLIR module, likewise.
            SENSORS.observe("xla_lower_seconds", own_secs,
                            labels=labels, buckets=COMPILE_BUCKETS)
        elif event.endswith("cache_retrieval_time_sec"):
            # Persistent-cache hit: the retrieval that REPLACED a compile.
            SENSORS.observe("xla_compile_cache_retrieval_seconds",
                            duration_secs, buckets=COMPILE_BUCKETS)
        elif event.endswith("compile_time_saved_sec"):
            SENSORS.count("xla_compile_seconds_saved",
                          max(0.0, duration_secs))
    except Exception:  # noqa: BLE001 — a telemetry bug must never break jit
        LOG.debug("xla telemetry listener failed", exc_info=True)


def _on_event(event: str, **kwargs) -> None:
    if not _enabled:
        return
    name = _EVENT_COUNTERS.get(event)
    if name is not None:
        SENSORS.count(name)


def install(enabled: bool = True) -> bool:
    """Register the jax.monitoring listeners (idempotent: jax keeps a
    plain listener list with no dedup, and this jax line has no public
    unregister — so install once and gate the callbacks on ``enabled``).
    Returns True when the listeners are active."""
    global _installed, _enabled
    with _install_lock:
        _enabled = bool(enabled)
        if _installed or not _enabled:
            return _installed
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_listener(_on_event)
        _installed = True
        return True


# Rounds-per-dispatch are megastep budgets: pow2-ish from 1 to the
# AdaptiveDispatch ceiling (1024).
DISPATCH_ROUND_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                          256.0, 512.0, 1024.0)


def record_dispatch(kind: str, rounds: int, donated: bool = False,
                    speculative: bool = False,
                    grid: str | None = None) -> None:
    """Account one solver device dispatch (a bounded megastep or a fused
    whole-pass execution): counters by kind (move/swap/chain), donation
    and speculative (async post-convergence no-op) tallies, and the
    rounds-per-dispatch histogram the bench reads its p50 from. ``grid``
    adds a label of that name to all four: ``narrow`` or ``wide`` on the
    bounded per-goal route (which grid the goal's rounds ran on),
    ``fused`` on the whole-chain route; routes that pass none keep the
    ``kind`` label alone. The ambient trace span (goal.solve) gets a
    dispatch tally so traces show how many XLA executions a goal cost."""
    from .tracing import TRACER
    span = TRACER.current_span()
    if span is not None:
        span.attributes["dispatches"] = \
            int(span.attributes.get("dispatches", 0)) + 1
    if not _enabled:
        return
    labels = {"kind": kind} if grid is None else {"kind": kind, "grid": grid}
    SENSORS.count("solver_dispatches", labels=labels)
    SENSORS.observe("solver_dispatch_rounds", float(rounds), labels=labels,
                    buckets=DISPATCH_ROUND_BUCKETS)
    if donated:
        SENSORS.count("solver_dispatch_donations", labels=labels)
    if speculative:
        SENSORS.count("solver_dispatch_speculative", labels=labels)


def record_transfer(nbytes: int, direction: str = "h2d",
                    source: str = "model_refresh") -> None:
    """Account one host↔device transfer: counters + the ambient span's
    ``transfer_bytes`` attribute (so a trace shows what the model refresh
    actually shipped). The span attribute belongs to the TRACING flag,
    the counters to this module's — each off switch removes its own
    surface and only that."""
    from .tracing import TRACER
    span = TRACER.current_span()
    if span is not None:
        span.attributes["transfer_bytes"] = \
            int(span.attributes.get("transfer_bytes", 0)) + int(nbytes)
    if not _enabled:
        return
    labels = {"direction": direction, "source": source}
    SENSORS.count("device_transfer_bytes", float(nbytes), labels=labels)
    SENSORS.count("device_transfers", labels=labels)


def refresh_device_gauges() -> None:
    """Refresh ``device_memory_bytes{device,kind}`` from the live backend
    (called at /metrics scrape time; gauges persist between scrapes).
    Allocator stats where the runtime provides them; otherwise the summed
    live jax.Array footprint per device, so the series is never absent
    just because the backend is host-local. No-op (no device polling, no
    live-array walk) when xla.telemetry.enabled=false."""
    if not _enabled:
        return
    try:
        import jax
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — no backend, no gauges
        return
    stats_by_device = {}
    for d in devices:
        try:
            stats_by_device[d] = d.memory_stats()
        except Exception:  # noqa: BLE001 — unsupported on this runtime
            stats_by_device[d] = None
    if any(s is None for s in stats_by_device.values()):
        live: dict = {}
        try:
            for arr in jax.live_arrays():
                for d in getattr(arr, "devices", lambda: ())() or ():
                    live[d] = live.get(d, 0) + getattr(arr, "nbytes", 0)
        except Exception:  # noqa: BLE001 — live_arrays is debug API
            live = {}
        for d, s in stats_by_device.items():
            if s is None:
                stats_by_device[d] = {"bytes_in_use": live.get(d, 0)}
    for d, stats in stats_by_device.items():
        dev = f"{d.platform}:{d.id}"
        for kind in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                     "largest_free_block_bytes"):
            if stats and kind in stats:
                SENSORS.gauge("device_memory_bytes", float(stats[kind]),
                              labels={"device": dev, "kind": kind})
