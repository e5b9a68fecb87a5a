"""Request-scoped span tracing for the rebalance pipeline.

Dapper-style (Sigelman et al., 2010) span trees over every operation the
service runs: a rebalance cycle becomes one trace — sample fetch →
aggregate → model assembly (cache hit/miss, transfer bytes) → per-goal
solve → proposal diff → execution — instead of forty disconnected
counters. The reference exposes ~40 JMX sensors but nothing that explains
*why* one proposal took 12 s; spans carry the causality.

Design points:

- **Contextvar propagation** (the same pattern as ``sensors.cluster_label``
  and ``progress.OperationProgress``): deep layers open child spans with
  no plumbing; a span opened on a worker thread with no ambient parent
  becomes its own trace root (the executor's run thread, the background
  sampling loop). A request keeps ONE trace across thread pools:
  ``attach(parent)`` re-establishes the ambient span inside the
  task-engine worker and the fleet worker, at the sites that re-enter
  the cluster label, the journey and the heal scope.
- **One clock with the device trace**: a live span also enters a
  ``jax.profiler.TraceAnnotation`` named ``cc.<span name>`` (and so does
  a journey segment, through ``annotation()``), so a profiler capture
  holds the program's spans beside the device's ops. Durations come from
  ``time.monotonic_ns()``; the exported ``startTimeUnixNano`` adds one
  wall-clock anchor read at import.
- **Bounded ring** of recent traces, served by ``GET
  /kafkacruisecontrol/trace`` as OTLP-compatible JSON span trees
  (traceId/spanId/parentSpanId/startTimeUnixNano/attributes key-value
  shape), filterable by cluster and operation. A trace enters it when
  it is whole: its root has closed and the work attached to it on other
  threads has ended. A tree of ``transient`` spans alone (the ``http.*``
  spans of a scrape, a UI asset or a poll) never enters it, so a scraper
  does not turn the ring over.
- **Automatic histograms**: every span close records into the
  ``trace_span_seconds`` histogram (one series per span name, ambient
  cluster label applies) so ``/metrics`` grows a ``_bucket`` latency
  distribution per pipeline stage with zero extra call sites.
- **JSONL dump** (``configure(jsonl_path=...)``): bench runs append one
  JSON line per completed trace for offline analysis / CI artifacts.
- **Zero-cost when disabled**: ``span()`` returns a shared no-op context
  manager — no allocation, no contextvar write, no clock read — so the
  config flag removes tracing from the solver hot path entirely.
- **The collector's pauses where they fall** (``watch_collector``): one
  ``gc.callbacks`` entry times every collection of Python's collector
  into plain integers; a span that a pause overlapped carries ``gcMs``
  and feeds ``trace_span_gc_seconds_total`` under its own labels, and a
  full collection is a ``cc.gc.gen2`` event of a running capture.
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import os
import sys
import threading
import time

from .sensors import SENSORS, cluster_label, current_cluster_label

import contextvars

_CURRENT: contextvars.ContextVar["Span | None"] = \
    contextvars.ContextVar("trace_current_span", default=None)

# Unix nanoseconds at monotonic zero: spans are timed on the monotonic
# clock (a stepped wall clock cannot shorten or lengthen them) and placed
# on the wall clock only for export.
_WALL_ANCHOR_NS = time.time_ns() - time.monotonic_ns()

ANNOTATION_PREFIX = "cc."
_TRACE_ANNOTATION = None


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``cc.<name>``: the one way
    the program's spans and journey segments enter a profiler capture.
    With no profiler session entering it is a branch on an atomic."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(ANNOTATION_PREFIX + name)

# Monotone span-id source; thread-safe in CPython (single bytecode next()).
_IDS = itertools.count(1)

SPAN_HISTOGRAM = "trace_span_seconds"

# -- the collector -------------------------------------------------------------
#
# What ``_on_collection`` writes. Plain integers and lists of them, and no
# lock: CPython runs one collection at a time and calls ``gc.callbacks`` on
# the collecting thread with the GIL held, and a collection can start at
# any allocation, one made inside ``SENSORS``' or ``TRACER``'s own
# ``with self._lock:`` block included. ``threading.Lock`` is not
# re-entrant, so a callback that took either lock (``SENSORS.count``)
# would stop its thread against itself. The registry reads these at
# render time (``_publish_collector``).
GENERATIONS = 3
_gc_pause_ns = 0                        # every generation, the process's life
_gc_started_ns = 0
_gc_collections = [0] * GENERATIONS     # by generation
_gc_ns = [0] * GENERATIONS
_gc_last_ns = [0] * GENERATIONS
_gc_max_ns = [0] * GENERATIONS
# sys.getallocatedblocks() at the install and at the end of every full
# collection, and at no other time: it is NOT O(1), it walks every pool of
# every arena (tens of milliseconds once the heap has passed some millions
# of blocks; read at every render it cost 5-7 % of a 0.5 s loop, PERF.md).
_gc_blocks = 0
_gc_full_annotation = None
_watch_lock = threading.Lock()          # install and removal only


def gc_pause_ns() -> int:
    """Nanoseconds the collector has paused the process for since
    ``watch_collector(True)``; 0 without the hook. A span or a journey
    segment reads it when it opens and when it closes: the difference is
    the pauses that overlapped it, on whichever thread they ran (a pause
    holds the GIL, so it stops every Python thread; for a span that waits
    in C with the GIL released, ``solver.wait``, it is overlap and not
    stall)."""
    return _gc_pause_ns


def _on_collection(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` entry. Takes no lock and calls nothing that
    does (above). A full collection is also a ``cc.gc.gen2`` event of a
    running capture; the younger ones (hundreds a request, under a
    millisecond each) are counted only."""
    global _gc_pause_ns, _gc_started_ns, _gc_blocks
    global _gc_full_annotation
    generation = info["generation"]
    if phase == "start":
        if generation == 2:
            _gc_full_annotation = annotation("gc.gen2")
            _gc_full_annotation.__enter__()
        _gc_started_ns = time.monotonic_ns()
        return
    started = _gc_started_ns
    if not started:     # installed between a collection's start and stop
        return
    pause = time.monotonic_ns() - started
    _gc_started_ns = 0
    _gc_pause_ns += pause
    _gc_collections[generation] += 1
    _gc_ns[generation] += pause
    _gc_last_ns[generation] = pause
    if pause > _gc_max_ns[generation]:
        _gc_max_ns[generation] = pause
    if generation == 2:
        _gc_blocks = sys.getallocatedblocks()
        if _gc_full_annotation is not None:
            _gc_full_annotation.__exit__(None, None, None)
            _gc_full_annotation = None


def _publish_collector() -> None:
    """The callback's integers into ``SENSORS``: run by the registry at
    the top of ``render()``, outside any collection. The series carry no
    cluster label: the collector is the process's."""
    with cluster_label(None):
        for generation in range(GENERATIONS):
            labels = {"generation": str(generation)}
            SENSORS.set_counter("python_gc_collections",
                                _gc_collections[generation], labels=labels)
            SENSORS.set_timer("python_gc_pause",
                              _gc_collections[generation],
                              _gc_ns[generation] / 1e9,
                              _gc_last_ns[generation] / 1e9,
                              _gc_max_ns[generation] / 1e9, labels=labels)
        SENSORS.gauge("python_allocated_blocks", _gc_blocks)


def watch_collector(enabled: bool) -> None:
    """Install (once a process; a second call leaves one entry) or remove
    the ``gc.callbacks`` entry and its publication. Called where
    ``TRACER.configure(enabled=...)`` is, with the same flag: with
    ``tracing.enabled=false`` the program has no entry in
    ``gc.callbacks``. The ``python_gc_*`` series exist, at 0, from the
    install on, so a reader tells "no pause" from "no hook"; after a
    removal they stand where they were."""
    global _gc_blocks
    with _watch_lock:
        installed = _on_collection in gc.callbacks
        if not enabled:
            if installed:
                gc.callbacks.remove(_on_collection)
            SENSORS.remove_refresh(_publish_collector)
        elif not installed:
            annotation("gc.gen2")   # the import, outside any collection
            _gc_blocks = sys.getallocatedblocks()
            gc.callbacks.append(_on_collection)
            SENSORS.add_refresh(_publish_collector)
            _publish_collector()


class Span:
    """One timed, attributed node of a trace tree."""

    __slots__ = ("name", "span_id", "parent", "root", "trace_id",
                 "start_ns", "end_ns", "attributes", "children",
                 "label_keys", "transient", "pending", "published",
                 "gc_ns0")

    def __init__(self, name: str, parent: "Span | None"):
        self.name = name
        self.parent = parent
        self.root = parent.root if parent is not None else self
        self.span_id = f"{next(_IDS):016x}"
        self.trace_id = parent.trace_id if parent is not None \
            else f"{next(_IDS):032x}"
        self.start_ns = time.monotonic_ns()
        # gc_pause_ns() when the span opened: read after the start and,
        # in Tracer._close, before the end, so what is counted fell inside.
        self.gc_ns0 = _gc_pause_ns
        self.end_ns = 0
        self.attributes: dict = {}
        self.children: list[Span] = []
        # Attributes that also label the span's histogram series (the
        # http.* spans' endpoint; empty for every other span).
        self.label_keys: tuple[str, ...] = ()
        # transient: this span alone does not make a trace worth keeping.
        # On a root only: pending, the work attached on other threads
        # that has not ended; published, the trace is in the ring.
        self.transient = False
        self.pending = 0
        self.published = False

    def set(self, **attributes) -> None:
        """Attach attributes (goal name, candidate count, transfer bytes…)."""
        self.attributes.update(attributes)

    @property
    def duration_s(self) -> float:
        return max(0.0, (self.end_ns - self.start_ns) / 1e9)

    def to_dict(self) -> dict:
        """OTLP-compatible field shape, nested (children inline — the
        trace endpoint serves trees, not flat span lists)."""
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentSpanId": self.parent.span_id if self.parent else "",
            "name": self.name,
            "startTimeUnixNano": str(self.start_ns + _WALL_ANCHOR_NS),
            "endTimeUnixNano": str(self.end_ns + _WALL_ANCHOR_NS),
            "durationMs": round((self.end_ns - self.start_ns) / 1e6, 3),
            "attributes": [{"key": k, "value": _otlp_value(v)}
                           for k, v in self.attributes.items()],
            "children": [c.to_dict() for c in self.children],
        }


def _otlp_value(v) -> dict:
    if isinstance(v, bool):
        return {"boolValue": v}
    if isinstance(v, int):
        return {"intValue": str(v)}  # OTLP JSON encodes int64 as string
    if isinstance(v, float):
        return {"doubleValue": v}
    return {"stringValue": str(v)}


class _NullSpan:
    """Shared no-op context manager for disabled tracing: the hot path
    pays one attribute load and one ``is None``-style branch, nothing
    else."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attributes) -> None:
        pass


_NULL = _NullSpan()


class _SpanScope:
    """Live span context manager: opens on enter, closes (histogram +
    trace completion) on exit. Exceptions mark the span and propagate.
    The span is also a ``cc.<name>`` event of a running profiler capture."""

    __slots__ = ("_tracer", "_span", "_token", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attributes: dict,
                 label_keys: tuple[str, ...] = (), transient: bool = False):
        self._tracer = tracer
        self._span = Span(name, _CURRENT.get())
        self._span.label_keys = label_keys
        self._span.transient = transient
        if attributes:
            self._span.attributes.update(attributes)

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self._span)
        self._annotation = annotation(self._span.name)
        self._annotation.__enter__()
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._annotation.__exit__(exc_type, exc, tb)
        _CURRENT.reset(self._token)
        if exc_type is not None:
            self._span.attributes.setdefault("error", exc_type.__name__)
        self._tracer._close(self._span)
        return False


class _Attach:
    """Re-establishes ``parent`` as the ambient span on another thread:
    ContextVars do not cross a thread pool, so the work callable carries
    the span that caused it and re-enters it where it runs. While it is
    entered the trace is not complete, whether or not its root has
    closed (the task of a request answered 202)."""

    __slots__ = ("_tracer", "_parent", "_token")

    def __init__(self, tracer: "Tracer", parent: Span):
        self._tracer = tracer
        self._parent = parent

    def __enter__(self) -> Span:
        self._tracer._pending(self._parent.root, 1)
        self._token = _CURRENT.set(self._parent)
        return self._parent

    def __exit__(self, *exc) -> bool:
        _CURRENT.reset(self._token)
        self._tracer._pending(self._parent.root, -1)
        return False


class Trace:
    """A completed span tree plus its routing metadata."""

    __slots__ = ("root", "operation", "cluster")

    def __init__(self, root: Span):
        self.root = root
        self.operation = str(root.attributes.get("operation", root.name))
        # The root's own attribute: a served request's root closes on the
        # handler thread, outside the label its work ran under, so the
        # front door writes the cluster it routed to (annotate_root);
        # every other root takes the ambient label when it closes.
        self.cluster = root.attributes.get("cluster")

    @property
    def operations(self) -> frozenset:
        """EVERY operation attribute in the tree, for filtering: a served
        request's root is ``http.request`` (a precompute's, the
        scheduler's ``fleet.job``) with the actual runnable ("rebalance")
        nested below — ?operation=rebalance must still find it. Read when
        asked: the task of a request answered 202 closes its spans into
        the tree after the root has closed."""
        ops = {self.operation}
        stack = [self.root]
        while stack:
            s = stack.pop()
            op = s.attributes.get("operation")
            if op is not None:
                ops.add(str(op))
            stack.extend(s.children)
        return frozenset(ops)

    @property
    def span_count(self) -> int:
        return _count_spans(self.root)

    def to_dict(self) -> dict:
        return {
            "traceId": self.root.trace_id,
            "operation": self.operation,
            "operations": sorted(self.operations),
            "cluster": self.cluster,
            "startTimeUnixNano": str(self.root.start_ns + _WALL_ANCHOR_NS),
            "durationMs": round(
                (self.root.end_ns - self.root.start_ns) / 1e6, 3),
            "spanCount": self.span_count,
            "root": self.root.to_dict(),
        }


class Tracer:
    """Process-wide tracer: span factory + bounded trace ring + exports."""

    def __init__(self, max_traces: int = 256):
        self._lock = threading.Lock()
        # JSONL appends serialize on their own lock: a multi-KB trace line
        # is bigger than any atomic-append guarantee, and two threads
        # closing root spans concurrently must not interleave bytes in
        # the dump — but the ring lock must not be held across file I/O.
        self._dump_lock = threading.Lock()
        self._enabled = True
        self._ring: collections.deque[Trace] = \
            collections.deque(maxlen=max_traces)
        self._jsonl_path: str | None = None
        self._jsonl_max_bytes = 0
        self._jsonl_max_files = 1
        self.spans_closed = 0
        self.traces_completed = 0
        self.jsonl_rotations = 0

    # -- configuration -----------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def configure(self, enabled: bool | None = None,
                  max_traces: int | None = None,
                  jsonl_path: str | None = ...,
                  jsonl_max_bytes: int | None = None,
                  jsonl_max_files: int | None = None) -> None:
        """Apply the config surface (tracing.enabled / tracing.max.traces /
        tracing.jsonl.path / tracing.jsonl.max.bytes /
        tracing.jsonl.max.files). ``jsonl_path``: ``...`` = leave
        unchanged, None/"" = off, a path = append one JSON line per
        trace. ``jsonl_max_bytes``: rotate the dump before an append
        would push it past this size (0 = unlimited).
        ``jsonl_max_files``: rotated generations kept — the cascade
        renames ``.1→.2→…→.N`` and drops ``.N`` (default 1, today's
        single-``.1`` behavior)."""
        with self._lock:
            if enabled is not None:
                self._enabled = bool(enabled)
            if max_traces is not None and max_traces != self._ring.maxlen:
                self._ring = collections.deque(self._ring,
                                               maxlen=max(1, max_traces))
            if jsonl_path is not ...:
                self._jsonl_path = jsonl_path or None
            if jsonl_max_bytes is not None:
                self._jsonl_max_bytes = max(0, int(jsonl_max_bytes))
            if jsonl_max_files is not None:
                self._jsonl_max_files = max(1, int(jsonl_max_files))

    # -- recording ---------------------------------------------------------
    def span(self, name: str, label_keys: tuple[str, ...] = (),
             transient: bool = False, **attributes):
        """Open a child span of the ambient span (or a new trace root).
        Returns a context manager yielding the Span (``.set(**attrs)``).
        ``label_keys`` names attributes that also label the span's
        ``trace_span_seconds`` series. A trace whose spans are all
        ``transient`` (the ``http.*`` spans of a scrape, a UI asset or a
        poll, under which the program did nothing it times) feeds the
        histogram and stays out of the ring and the dump."""
        if not self._enabled:
            return _NULL
        return _SpanScope(self, name, attributes, label_keys, transient)

    def attach(self, parent: "Span | None"):
        """Context manager that makes ``parent`` (a span captured on the
        thread that caused the work) the ambient span of this thread."""
        if parent is None or not self._enabled:
            return _NULL
        return _Attach(self, parent)

    def annotate(self, **attributes) -> None:
        """Attach attributes to the ambient span; no-op outside one (deep
        layers can report cache hits / byte counts without plumbing)."""
        if not self._enabled:
            return
        span = _CURRENT.get()
        if span is not None:
            span.attributes.update(attributes)

    def annotate_root(self, **attributes) -> None:
        """Attach attributes to the root of the ambient span's trace."""
        if not self._enabled:
            return
        span = _CURRENT.get()
        if span is not None:
            span.root.attributes.update(attributes)

    def current_span(self) -> Span | None:
        return _CURRENT.get()

    def _close(self, span: Span) -> None:
        paused_ns = _gc_pause_ns - span.gc_ns0
        span.end_ns = time.monotonic_ns()
        labels = {"span": span.name}
        for key in span.label_keys:
            labels[key] = str(span.attributes.get(key, ""))
        SENSORS.observe(SPAN_HISTOGRAM, span.duration_s, labels=labels)
        if paused_ns > 0:
            span.attributes["gcMs"] = round(paused_ns / 1e6, 3)
            SENSORS.count("trace_span_gc_seconds", paused_ns / 1e9,
                          labels=labels)
        with self._lock:
            self.spans_closed += 1
        parent = span.parent
        if parent is not None:
            parent.children.append(span)
            return
        cluster = current_cluster_label()
        if cluster is not None:
            span.attributes.setdefault("cluster", cluster)
        self._publish(span)

    def _pending(self, root: Span, step: int) -> None:
        with self._lock:
            root.pending += step
        if step < 0:
            self._publish(root)

    def _publish(self, root: Span) -> None:
        """Ring and dump a trace, once, when it is whole: its root has
        closed and no attached work is still running (so the dump's line
        of a request answered 202 holds its task's spans too)."""
        with self._lock:
            if root.published or root.pending or not root.end_ns:
                return
            if root.transient and _all_transient(root):
                return
            root.published = True
            trace = Trace(root)
            self.traces_completed += 1
            self._ring.append(trace)
            path = self._jsonl_path
            max_bytes = self._jsonl_max_bytes
            max_files = self._jsonl_max_files
        if path:
            try:
                line = json.dumps(trace.to_dict()) + "\n"
                with self._dump_lock:
                    self._maybe_rotate_jsonl(path, len(line), max_bytes,
                                             max_files)
                    with open(path, "a") as f:
                        f.write(line)
            except OSError:  # pragma: no cover — dump is best-effort
                pass

    def _maybe_rotate_jsonl(self, path: str, incoming: int,
                            max_bytes: int, max_files: int = 1) -> None:
        """Size-capped rotation (tracing.jsonl.max.bytes): when the next
        append would push the dump past the cap, the generation cascade
        runs — ``.{N-1}→.N`` down to ``path→.1`` — keeping
        ``max_files`` rotated generations (tracing.jsonl.max.files;
        bounded total footprint of ~(max_files+1)× the cap).
        ``jsonl_rotations`` counts per generation MOVED, so a deep
        cascade is visible as more than one rotation. Called under
        ``_dump_lock``. A single line larger than the cap still lands
        (in an otherwise-empty file): dropping traces silently would
        defeat the dump's whole purpose."""
        if max_bytes <= 0:
            return
        try:
            size = os.path.getsize(path)
        except OSError:
            return  # no file yet — nothing to rotate
        if size and size + incoming > max_bytes:
            for gen in range(max(1, max_files), 1, -1):
                older = f"{path}.{gen - 1}"
                if os.path.exists(older):
                    os.replace(older, f"{path}.{gen}")
                    self.jsonl_rotations += 1
            os.replace(path, path + ".1")
            self.jsonl_rotations += 1

    # -- export ------------------------------------------------------------
    def traces(self, cluster: str | None = None,
               operation: str | None = None,
               limit: int | None = None) -> list[dict]:
        """Recent traces, newest first, optionally filtered by the cluster
        label they ran under and/or operation name."""
        with self._lock:
            snapshot = list(self._ring)
        out: list[dict] = []
        if limit is not None and limit <= 0:
            return out
        for t in reversed(snapshot):
            if cluster is not None and t.cluster != cluster:
                continue
            if operation is not None and operation not in t.operations:
                continue
            out.append(t.to_dict())
            if limit is not None and len(out) >= limit:
                break
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


def _all_transient(root: Span) -> bool:
    stack = [root]
    while stack:
        s = stack.pop()
        if not s.transient:
            return False
        stack.extend(s.children)
    return True


def _count_spans(span: Span) -> int:
    n = 1
    stack = list(span.children)
    while stack:
        s = stack.pop()
        n += 1
        stack.extend(s.children)
    return n


def span_names(trace_dict: dict) -> list[str]:
    """Flat pre-order span-name list of a ``Trace.to_dict()`` payload
    (test/assertion helper)."""
    out: list[str] = []

    def walk(node: dict) -> None:
        out.append(node["name"])
        for c in node["children"]:
            walk(c)

    walk(trace_dict["root"])
    return out


TRACER = Tracer()
