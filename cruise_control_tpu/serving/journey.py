"""Per-request journeys: latency attribution for the serving front door.

The round-20 serving layer reports only machine-shaped END-TO-END
latency: nothing attributes a slow PROPOSALS response to admission vs
queue wait vs model build vs solve vs render. A journey is the ambient
per-request record (the ``sensors.cluster_label`` / heal-ledger
``heal_scope`` ContextVar pattern) opened in ``api.server._dispatch``
and stamped at every stage the request already passes through:

- ``admission`` — the admission-controller verdict,
- ``cache_lookup`` — response-cache identity + probe (hit/miss attr),
- ``queue_wait`` — task-engine queue time, per class (VIEWER/SOLVER),
- ``sched_wait`` — fleet-scheduler wait before the device turn,
- ``model_build`` — monitor cluster-model assembly,
- ``solve`` — the optimizer pass, linked to the flight recorder's
  ``passSeqs`` / warm-start attrs and the ambient heal chain id,
- ``proposal_diff`` / ``render`` — response assembly,
- ``cache_store`` — response-cache fill,

plus a ``coalesce`` note (leader vs follower). Completed journeys land
in a bounded lock-guarded ring per facade, served on
``GET /kafkacruisecontrol/journeys`` and mirrored into the
``journey_segment_seconds{endpoint,segment}`` histograms so the loadgen
report can say WHERE time went — and how much of the wall is
unattributed (reported, never hidden).

Deterministic machinery (CCSA004): every timestamp rides the injected
``monotonic``/``clock`` seams — the digital twin runs journeys on its
sim clock. Off-means-off: ``open()`` on a disabled log returns the
shared ``NO_JOURNEY`` null handle (``recording=False``, every method a
no-op), so observation never changes behavior and the disabled path is
ns-scale (benched as ``journey_noop_overhead``).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import threading
import time
from typing import Callable

from ..utils.sensors import SENSORS
from ..utils.tracing import annotation, gc_pause_ns

_AMBIENT: contextvars.ContextVar["Journey | None"] = \
    contextvars.ContextVar("journey_current", default=None)


class _NullSegment:
    """Shared no-op segment scope for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_NULL_SEGMENT = _NullSegment()


class _NullJourney:
    """Shared null journey (the heal ledger's ``NO_HEAL`` discipline):
    every stamp site calls through unconditionally; the disabled path
    pays one attribute load and a method call, nothing else."""

    __slots__ = ()
    recording = False

    def now(self) -> float:
        return 0.0

    def add(self, name: str, duration_s: float, **attrs) -> None:
        pass

    def seg(self, name: str, **attrs):
        return _NULL_SEGMENT

    def note(self, **attrs) -> None:
        pass


NO_JOURNEY = _NullJourney()


class _SegmentScope:
    """Times a ``with`` block into one journey segment. ``set()``
    attaches attrs before close (cache hit, verdict, pass ids). The
    block is also a ``cc.<segment>`` event of a running profiler capture
    (the spans' helper), so the segment IS the measurement of its
    boundary on the device trace's clock too. The collector's pauses that
    overlapped the block are kept beside the segment (``Journey.paused``),
    not in it: a pause is real time, a journey's clock may be the
    twin's."""

    __slots__ = ("_journey", "_name", "_attrs", "_t0", "_annotation",
                 "_gc_ns0")

    def __init__(self, journey: "Journey", name: str, attrs: dict):
        self._journey = journey
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> "_SegmentScope":
        self._t0 = self._journey.now()
        self._gc_ns0 = gc_pause_ns()
        self._annotation = annotation(self._name)
        self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self._attrs.setdefault("error", exc_type.__name__)
        paused_ns = gc_pause_ns() - self._gc_ns0
        self._journey.add(self._name,
                          max(0.0, self._journey.now() - self._t0),
                          **self._attrs)
        if paused_ns > 0:
            self._journey.paused(self._name, paused_ns)
        return False

    def set(self, **attrs) -> None:
        self._attrs.update(attrs)


class Journey:
    """One request's attribution record. Segments are stamped from
    MULTIPLE threads (HTTP handler, engine worker, fleet worker), so
    appends are lock-guarded; stamps after close are dropped — a
    202-returned request's journey records what happened within its
    dispatch wall, not the solve that finishes after it."""

    recording = True

    __slots__ = ("endpoint", "cluster", "opened_unix_s", "status",
                 "attrs", "segments", "gc_ns", "total_s", "unattributed_s",
                 "_t0", "_monotonic", "_lock", "_closed")

    def __init__(self, endpoint: str, cluster: str | None,
                 monotonic: Callable[[], float],
                 clock: Callable[[], float]):
        self.endpoint = endpoint
        self.cluster = cluster
        self.opened_unix_s = clock()
        self.status = "open"
        self.attrs: dict = {}
        self.segments: list[tuple[str, float, dict]] = []
        # segment -> nanoseconds of the collector's pauses that overlapped
        # its blocks; feeds a counter at close and is not exported.
        self.gc_ns: dict[str, int] = {}
        self.total_s = 0.0
        self.unattributed_s = 0.0
        self._monotonic = monotonic
        self._t0 = monotonic()
        self._lock = threading.Lock()
        self._closed = False

    def now(self) -> float:
        return self._monotonic()

    def add(self, name: str, duration_s: float, **attrs) -> None:
        """Append one already-timed segment (the fleet/engine waits are
        measured across threads and stamped at work start)."""
        with self._lock:
            if self._closed:
                return
            self.segments.append((name, max(0.0, float(duration_s)),
                                  attrs))

    def paused(self, name: str, pause_ns: int) -> None:
        with self._lock:
            if not self._closed:
                self.gc_ns[name] = self.gc_ns.get(name, 0) + pause_ns

    def seg(self, name: str, **attrs) -> _SegmentScope:
        """Context manager timing a block into one segment."""
        return _SegmentScope(self, name, dict(attrs))

    def note(self, **attrs) -> None:
        """Journey-level attributes (coalesce role, outcome, error)."""
        with self._lock:
            if not self._closed:
                self.attrs.update(attrs)

    def _finalize(self, status: str) -> bool:
        with self._lock:
            if self._closed:
                return False
            self._closed = True
            self.status = status
            self.total_s = max(0.0, self._monotonic() - self._t0)
            attributed = sum(d for _n, d, _a in self.segments)
            self.unattributed_s = max(0.0, self.total_s - attributed)
            return True

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "endpoint": self.endpoint,
                "cluster": self.cluster,
                "openedTimeUnixMs": int(self.opened_unix_s * 1000),
                "status": self.status,
                "totalS": round(self.total_s, 6),
                "unattributedS": round(self.unattributed_s, 6),
                "attributes": dict(self.attrs),
                "segments": [
                    {"segment": n, "seconds": round(d, 6), **a}
                    for n, d, a in self.segments],
            }


class JourneyLog:
    """Per-facade bounded ring of completed journeys + the open seam.

    ``open()`` is the ONLY branch point: disabled → ``NO_JOURNEY`` and
    every downstream stamp no-ops. ``close()`` finalizes the record,
    appends it to the ring, and mirrors each segment into the
    ``journey_segment_seconds{endpoint,segment}`` histogram (ambient
    cluster label applies, exactly like every other sensor) and the
    collector's pauses inside it into
    ``journey_segment_gc_seconds_total`` under the same labels."""

    def __init__(self, enabled: bool = True, max_entries: int = 256,
                 monotonic: Callable[[], float] = time.monotonic,
                 clock: Callable[[], float] = time.time):
        self._enabled = bool(enabled)
        self._monotonic = monotonic
        self._clock = clock
        self._lock = threading.Lock()
        self._ring: collections.deque[Journey] = \
            collections.deque(maxlen=max(1, int(max_entries)))
        self.journeys_opened = 0
        self.journeys_closed = 0

    @property
    def enabled(self) -> bool:
        return self._enabled

    def open(self, endpoint: str,
             cluster: str | None = None) -> Journey | _NullJourney:
        if not self._enabled:
            return NO_JOURNEY
        journey = Journey(endpoint, cluster, self._monotonic, self._clock)
        with self._lock:
            self.journeys_opened += 1
        return journey

    def close(self, journey: Journey | _NullJourney,
              status: str = "ok") -> None:
        if not journey.recording:
            return
        if not journey._finalize(status):
            return
        with self._lock:
            self._ring.append(journey)
            self.journeys_closed += 1
        for name, duration_s, _attrs in journey.segments:
            SENSORS.observe("journey_segment_seconds", duration_s,
                            labels={"endpoint": journey.endpoint,
                                    "segment": name})
        for name, pause_ns in journey.gc_ns.items():
            SENSORS.count("journey_segment_gc_seconds", pause_ns / 1e9,
                          labels={"endpoint": journey.endpoint,
                                  "segment": name})

    # -- export ------------------------------------------------------------
    def entries(self, endpoint: str | None = None,
                limit: int | None = None) -> list[dict]:
        """Completed journeys, newest first, optionally filtered by
        endpoint name."""
        with self._lock:
            snapshot = list(self._ring)
        out: list[dict] = []
        if limit is not None and limit <= 0:
            return out
        for j in reversed(snapshot):
            if endpoint is not None and j.endpoint != endpoint:
                continue
            out.append(j.to_dict())
            if limit is not None and len(out) >= limit:
                break
        return out

    def dump_json(self, path: str) -> int:
        """Write the ring (newest first) as a JSON document — the bench
        stage's ``BENCH_JOURNEY_FILE`` CI artifact."""
        entries = self.entries()
        with open(path, "w") as f:
            json.dump({"numJourneys": len(entries),
                       "journeys": entries}, f, indent=2)
        return len(entries)

    def stats(self) -> dict:
        with self._lock:
            return {"journeysEnabled": self._enabled,
                    "journeysOpened": self.journeys_opened,
                    "journeysClosed": self.journeys_closed,
                    "ringSize": len(self._ring)}


def current_journey() -> Journey | _NullJourney:
    """The ambient journey (``NO_JOURNEY`` outside any request scope):
    deep layers — the monitor's model build, the facade's solve — stamp
    segments with no plumbing, exactly like ``sensors.cluster_label``."""
    journey = _AMBIENT.get()
    return journey if journey is not None else NO_JOURNEY


@contextlib.contextmanager
def journey_scope(journey: Journey | _NullJourney):
    """Establish ``journey`` as the ambient record. ContextVars do NOT
    cross thread pools: the api layer re-enters this scope inside the
    engine-worker closure and again inside fleet-scheduled work (the
    ``cluster_label`` rewrap discipline)."""
    token = _AMBIENT.set(journey if journey.recording else None)
    try:
        yield journey
    finally:
        _AMBIENT.reset(token)


def segment_attribution(entries: list[dict]) -> dict:
    """Aggregate completed journeys into the per-segment attribution
    table the loadgen report carries: per-segment count/total/p50/p99
    plus the attributed-fraction of total wall (unattributed remainder
    REPORTED, not hidden)."""
    per_seg: dict[str, list[float]] = {}
    total = attributed = 0.0
    for e in entries:
        total += e.get("totalS", 0.0)
        for seg in e.get("segments", ()):
            d = float(seg.get("seconds", 0.0))
            attributed += d
            per_seg.setdefault(seg["segment"], []).append(d)
    table = {}
    for name in sorted(per_seg):
        vals = sorted(per_seg[name])
        n = len(vals)
        table[name] = {
            "count": n,
            "total_s": round(sum(vals), 6),
            "p50_s": round(vals[min(n - 1, int(0.50 * n))], 6),
            "p99_s": round(vals[min(n - 1, int(0.99 * n))], 6),
        }
    return {
        "journeys": len(entries),
        "wall_s": round(total, 6),
        "attributed_s": round(attributed, 6),
        "unattributed_s": round(max(0.0, total - attributed), 6),
        "attributed_fraction": round(attributed / total, 4)
        if total > 0 else 0.0,
        "segments": table,
    }
