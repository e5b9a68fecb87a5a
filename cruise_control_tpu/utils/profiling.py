"""On-demand device profiling behind a single-flight gate.

``GET /kafkacruisecontrol/profile?duration_s=`` wraps
``jax.profiler.trace``: the capture window records whatever the live
process executes — in-flight solves, model refreshes, the fleet pacer's
precomputes — into a Perfetto/TensorBoard trace directory the operator
pulls off the host (or CI uploads as an artifact). This is the live
sibling of the offline marginal tools: span tracing (utils.tracing) says
WHICH stage was slow, the profiler says which op inside the XLA program.
The capture holds both on one clock (every live span is a ``cc.<name>``
host event), and the response carries their reduction under ``summary``
(utils.profile_summary): device busy / idle, device seconds by XLA
program and by named scope of the round body, idle seconds by the
program span that covers each gap — so the operator reads the answer
and need not pull the trace off the host.

Single-flight discipline: ``jax.profiler`` is process-global state — two
overlapping ``start_trace`` calls corrupt each other — so capture runs
under a non-blocking lock and a concurrent request fails fast with
``ProfilerBusyError`` carrying the remaining window, which the API layer
renders as 503 + Retry-After (the circuit-breaker response shape the
clients already understand).

The microbench surface (``?microbench=true``) shares the gate: op-class
while_loop marginals (utils.microbench) also own the device while they
run, and interleaving them with a trace capture would corrupt both
measurements.
"""

from __future__ import annotations

import logging
import os
import threading
import time

LOG = logging.getLogger(__name__)


class ProfilerBusyError(RuntimeError):
    """A capture or microbench is already running. ``retry_after_s`` is
    the remaining window of the in-flight run (API layer: 503 +
    Retry-After, the breaker-style busy response)."""

    def __init__(self, retry_after_s: float):
        retry_after_s = max(0.5, retry_after_s)
        super().__init__(
            f"device profiler busy; retry in {retry_after_s:.1f}s")
        self.retry_after_s = retry_after_s


class DeviceProfiler:
    """Process-wide profiler front-end (single-flight)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._busy_until = 0.0
        self.captures = 0
        self.microbenches = 0
        # Directory sequence, advanced for every ATTEMPT (not just
        # successes): a retry after a failed capture in the same
        # wall-clock second must not reuse the dead attempt's directory
        # and double-count its leftover files.
        self._dir_seq = 0

    def _acquire(self, window_s: float):
        if not self._lock.acquire(blocking=False):
            raise ProfilerBusyError(self._busy_until - time.monotonic())
        self._busy_until = time.monotonic() + window_s

    def capture(self, duration_s: float, trace_dir: str,
                max_duration_s: float = 60.0) -> dict:
        """Record ``duration_s`` of live device activity into a
        timestamped subdirectory of ``trace_dir``. Returns the trace
        location + captured file listing + ``summary`` (None where no
        operation ran on a device, as on the CPU backend) + ``summaryS``,
        the seconds the reduction took."""
        duration = min(max(float(duration_s), 0.05), max_duration_s)
        self._acquire(duration)
        try:
            import jax
            # Attempt counter in the name: two captures inside one
            # wall-clock second must not share a directory (the second's
            # file listing would double-count the first's output).
            self._dir_seq += 1
            out_dir = os.path.join(
                trace_dir, time.strftime("trace_%Y%m%d_%H%M%S")
                + f"_{self._dir_seq:03d}")
            os.makedirs(out_dir, exist_ok=True)
            # The interpreter's frames stay out of the capture: hooking
            # every Python call slows the host work whose share of the
            # idle time the summary reports, and the program's own spans
            # are what it is read by.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            t0 = time.monotonic()
            jax.profiler.start_trace(out_dir, profiler_options=options)
            try:
                time.sleep(duration)
            finally:
                jax.profiler.stop_trace()
            elapsed = time.monotonic() - t0
            files, total = [], 0
            for root, _dirs, names in os.walk(out_dir):
                for n in names:
                    p = os.path.join(root, n)
                    size = os.path.getsize(p)
                    total += size
                    files.append({"path": os.path.relpath(p, out_dir),
                                  "sizeBytes": size})
            self.captures += 1
            from .sensors import SENSORS
            SENSORS.count("profiling_captures")
            SENSORS.record_timer("profiling_capture", elapsed)
            # The reduction is pure Python over the whole capture, in
            # this process and under the single-flight gate: its seconds
            # are the operator's to see.
            from .profile_summary import summarize
            t1 = time.monotonic()
            try:
                summary = summarize(out_dir)
            except Exception:  # noqa: BLE001 — the capture itself stands
                LOG.exception("profile summary of %s failed", out_dir)
                summary = None
            summary_s = time.monotonic() - t1
            return {"traceDir": out_dir, "durationS": round(duration, 3),
                    "elapsedS": round(elapsed, 3),
                    "numFiles": len(files), "totalBytes": total,
                    "files": sorted(files, key=lambda f: f["path"]),
                    "summary": summary, "summaryS": round(summary_s, 3)}
        finally:
            self._lock.release()

    def microbench(self, brokers: int, partitions: int,
                   iters: int = 16, budget_s: float = 120.0) -> dict:
        """Run the in-process op-class microbench (utils.microbench)
        under the same single-flight gate. ``budget_s`` only sizes the
        Retry-After a concurrent caller sees — the bench itself runs to
        completion."""
        self._acquire(budget_s)
        try:
            from .microbench import run_microbench
            t0 = time.monotonic()
            out = run_microbench(brokers=brokers, partitions=partitions,
                                 iters=iters)
            self.microbenches += 1
            from .sensors import SENSORS
            SENSORS.count("profiling_microbenches")
            out["elapsedS"] = round(time.monotonic() - t0, 3)
            return out
        finally:
            self._lock.release()


PROFILER = DeviceProfiler()
