"""Self-instrumentation sensors → Prometheus text exposition.

Reference parity: the Dropwizard MetricRegistry → JMX domain
``kafka.cruisecontrol`` (KafkaCruiseControlApp.java:29-32) with ~40
operational sensors (docs/wiki/User Guide/Sensors.md: valid-windows,
monitored-partitions-percentage, balancedness-score,
proposal-computation-timer GoalOptimizer.java:128,
cluster-model-creation-timer LoadMonitor.java:177, execution
counts/timers Executor.java:145-148,346). JMX is a JVM-ism; the TPU-era
export surface is a Prometheus ``/metrics`` endpoint fed by the same
sensor registry.

Four metric kinds: counters, gauges, timers (count/sum/last/max — the
Dropwizard shape), and histograms (``observe``): log-spaced buckets
rendered as cumulative ``_bucket{le=...}`` series so latency
DISTRIBUTIONS survive aggregation — the timer shape collapses to
count/sum/last/max and no p99 can be recovered from it. The span tracer
(utils.tracing) feeds one histogram series per span name automatically.

Hot-path cost is one dict write per record — no locks on read-modify of
floats beyond a plain mutex, nothing device-side.
"""

from __future__ import annotations

import bisect
import contextvars
import logging
import threading
from contextlib import contextmanager

LOG = logging.getLogger(__name__)

_PREFIX = "kafka_cruisecontrol"

# Log-spaced default histogram buckets (seconds): the 1-2.5-5 decade
# ladder from 1 ms to 60 s, covering everything from a span around a
# single device dispatch to a full 7k-broker chain solve.
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 60.0)

# Ambient per-cluster label (fleet federation): work executed on behalf of
# a registered cluster — a scheduler job, a ?cluster=-routed API request —
# runs inside ``cluster_label(cid)``, and every sensor written underneath
# picks up the ``cluster`` label without touching the call sites. Scoped
# via ContextVar so concurrent per-cluster work cannot mislabel each other.
_CLUSTER: contextvars.ContextVar[str | None] = \
    contextvars.ContextVar("sensor_cluster_label", default=None)


@contextmanager
def cluster_label(cluster_id: str | None):
    """Attribute all sensors recorded inside the block to ``cluster_id``
    (None = no-op, so call sites need no branching)."""
    token = _CLUSTER.set(cluster_id)
    try:
        yield
    finally:
        _CLUSTER.reset(token)


def current_cluster_label() -> str | None:
    return _CLUSTER.get()


def escape_label_value(value) -> str:
    """Prometheus text-format label escaping: backslash, double quote and
    newline must be escaped or the scrape line is syntactically broken
    (a single quoted value with an embedded ``"`` truncates the label
    set and corrupts every sample after it)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


class _Histogram:
    """Per-series bucket counts. ``counts[i]`` is the NON-cumulative count
    of observations ≤ ``buckets[i]`` and > the previous bound;
    ``counts[-1]`` is the +Inf overflow. Cumulated at render time."""

    __slots__ = ("buckets", "counts", "total", "count")

    def __init__(self, buckets: tuple):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        self.total = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.buckets, value)] += 1
        self.total += value
        self.count += 1

    def quantile(self, q: float) -> float:
        return bucket_quantile(self.buckets, self.counts, q)


def bucket_quantile(buckets: tuple, counts: list, q: float) -> float:
    """Estimated q-quantile (0..1) over NON-cumulative bucket counts
    (+Inf overflow last), with linear interpolation inside the landing
    bucket (the Prometheus histogram_quantile estimate). The +Inf bucket
    clamps to the top finite bound. Edge cases are PINNED, never
    None/NaN: an empty window (all-zero counts, or no finite bounds)
    is 0.0; a single-bucket layout answers its one bound — the SLO
    engine's latency objectives call this hot and must get a number.
    Exposed standalone so callers holding snapshot DIFFS (per-stage
    bench windows) reuse the same math."""
    total = sum(counts)
    if total == 0 or not buckets:
        return 0.0
    if len(buckets) == 1:
        return float(buckets[0])
    rank = q * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= rank and c:
            if i >= len(buckets):
                return float(buckets[-1])
            lo = buckets[i - 1] if i else 0.0
            hi = buckets[i]
            return float(lo + (hi - lo) * (rank - (cum - c)) / c)
    return float(buckets[-1])


class SensorRegistry:
    """Counters, gauges, timers and histograms keyed by (name, labels)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[tuple[str, tuple], float] = {}
        self._gauges: dict[tuple[str, tuple], float] = {}
        # name -> (count, total_seconds, last_seconds, max_seconds)
        self._timers: dict[tuple[str, tuple], tuple[int, float, float, float]] = {}
        self._histograms: dict[tuple[str, tuple], _Histogram] = {}
        # Run at the top of render(): what publishes totals kept elsewhere.
        self._refreshes: list = []

    @staticmethod
    def _key(name: str, labels: dict | None) -> tuple[str, tuple]:
        cluster = _CLUSTER.get()
        if cluster is not None and "cluster" not in (labels or {}):
            labels = {**(labels or {}), "cluster": cluster}
        return name, tuple(sorted((labels or {}).items()))

    def count(self, name: str, value: float = 1.0,
              labels: dict | None = None) -> None:
        k = self._key(name, labels)
        with self._lock:
            self._counters[k] = self._counters.get(k, 0.0) + value

    def gauge(self, name: str, value: float, labels: dict | None = None) -> None:
        with self._lock:
            self._gauges[self._key(name, labels)] = float(value)

    def set_counter(self, name: str, total: float,
                    labels: dict | None = None) -> None:
        """A counter whose running total is kept elsewhere (by code that
        may not take this lock: ``tracing._on_collection``) and published
        from a refresh; the total only ever grows."""
        with self._lock:
            self._counters[self._key(name, labels)] = float(total)

    def set_timer(self, name: str, count: int, total: float, last: float,
                  mx: float, labels: dict | None = None) -> None:
        """``set_counter``'s like for the timer shape."""
        with self._lock:
            self._timers[self._key(name, labels)] = (
                int(count), float(total), float(last), float(mx))

    def add_refresh(self, refresh) -> None:
        """``refresh()`` runs at the top of every ``render()``, before
        the snapshot is taken, so that a scrape and a direct ``render()``
        both see what it publishes. Adding one twice keeps one."""
        with self._lock:
            if refresh not in self._refreshes:
                self._refreshes.append(refresh)

    def remove_refresh(self, refresh) -> None:
        with self._lock:
            if refresh in self._refreshes:
                self._refreshes.remove(refresh)

    def record_timer(self, name: str, seconds: float,
                     labels: dict | None = None) -> None:
        k = self._key(name, labels)
        with self._lock:
            count, total, _last, mx = self._timers.get(k, (0, 0.0, 0.0, 0.0))
            self._timers[k] = (count + 1, total + seconds, seconds,
                              max(mx, seconds))

    def observe(self, name: str, value: float, labels: dict | None = None,
                buckets: tuple | None = None) -> None:
        """Record into the histogram series ``(name, labels)``. The bucket
        layout is fixed by the FIRST observation of a series (Prometheus
        semantics: bucket bounds of a live series never change)."""
        k = self._key(name, labels)
        with self._lock:
            h = self._histograms.get(k)
            if h is None:
                h = self._histograms[k] = _Histogram(
                    tuple(buckets) if buckets else DEFAULT_BUCKETS)
            h.observe(value)

    def quantile(self, name: str, q: float,
                 labels: dict | None = None) -> float | None:
        """Estimated q-quantile of a histogram series (None ONLY when
        the series does not exist; an existing-but-empty window pins to
        0.0 via bucket_quantile) — the bench/CI summary hook for
        p50/p99 columns."""
        k = self._key(name, labels)
        with self._lock:
            h = self._histograms.get(k)
            return h.quantile(q) if h is not None else None

    def counter_total(self, name: str) -> float:
        """Sum of a counter over every label set (introspection surface:
        ``chip_smoke.py`` brackets requests with the compile counters)."""
        with self._lock:
            return sum(v for (n, _labels), v in self._counters.items()
                       if n == name)

    def histogram_sum(self, name: str) -> float:
        """Sum of all observations of a histogram over every label set
        (same surface: seconds spent in backend compiles)."""
        with self._lock:
            return sum(h.total for (n, _labels), h in self._histograms.items()
                       if n == name)

    def histogram_snapshot(self, name: str, labels: dict | None = None,
                           ) -> dict | None:
        """{buckets, counts (non-cumulative, +Inf last), sum, count} of a
        series, or None (test/introspection surface)."""
        k = self._key(name, labels)
        with self._lock:
            h = self._histograms.get(k)
            if h is None:
                return None
            return {"buckets": h.buckets, "counts": list(h.counts),
                    "sum": h.total, "count": h.count}

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._timers.clear()
            self._histograms.clear()

    def remove_labeled(self, label: str, value: str) -> int:
        """Drop every series carrying ``label=value`` (fleet deregister:
        a removed cluster's series must disappear from the export, not
        freeze at their last values). Returns the number removed."""
        pair = (label, value)
        removed = 0
        with self._lock:
            for store in (self._counters, self._gauges, self._timers,
                          self._histograms):
                stale = [k for k in store if pair in k[1]]
                for k in stale:
                    del store[k]
                removed += len(stale)
        return removed

    # -- exposition --------------------------------------------------------
    @staticmethod
    def _labels_str(labels: tuple, extra: tuple = ()) -> str:
        pairs = labels + extra
        if not pairs:
            return ""
        inner = ",".join(f'{k}="{escape_label_value(v)}"' for k, v in pairs)
        return "{" + inner + "}"

    @classmethod
    def _fmt(cls, name: str, labels: tuple, value: float) -> str:
        return f"{_PREFIX}_{name}{cls._labels_str(labels)} {value}"

    @staticmethod
    def _type_line(lines: list[str], seen: set, family: str,
                   kind: str) -> None:
        if family not in seen:
            seen.add(family)
            lines.append(f"# TYPE {_PREFIX}_{family} {kind}")

    def render(self, extra_gauges: dict | None = None) -> str:
        """Prometheus text format. ``extra_gauges`` lets the scrape handler
        mix in live values (name -> value or (value, labels))."""
        with self._lock:
            refreshes = list(self._refreshes)
        for refresh in refreshes:
            try:
                refresh()
            except Exception:  # noqa: BLE001 — a scrape must not fail
                LOG.warning("sensor refresh %r failed", refresh,
                            exc_info=True)
        lines: list[str] = []
        typed: set[str] = set()
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            timers = dict(self._timers)
            histograms = {k: (h.buckets, list(h.counts), h.total, h.count)
                          for k, h in self._histograms.items()}
        for name, value in (extra_gauges or {}).items():
            labels: dict | None = None
            if isinstance(value, tuple):
                value, labels = value
            gauges[self._key(name, labels)] = float(value)
        for (name, labels), v in sorted(counters.items()):
            self._type_line(lines, typed, name + "_total", "counter")
            lines.append(self._fmt(name + "_total", labels, v))
        for (name, labels), v in sorted(gauges.items()):
            self._type_line(lines, typed, name, "gauge")
            lines.append(self._fmt(name, labels, v))
        for (name, labels), (count, total, last, mx) in sorted(timers.items()):
            lines.append(self._fmt(name + "_seconds_count", labels, count))
            lines.append(self._fmt(name + "_seconds_sum", labels, total))
            lines.append(self._fmt(name + "_seconds_last", labels, last))
            lines.append(self._fmt(name + "_seconds_max", labels, mx))
        for (name, labels), (buckets, counts, total, count) in sorted(
                histograms.items()):
            self._type_line(lines, typed, name, "histogram")
            full = f"{_PREFIX}_{name}_bucket"
            cum = 0
            for bound, c in zip(buckets, counts):
                cum += c
                lines.append(full + self._labels_str(
                    labels, (("le", repr(float(bound))),)) + f" {cum}")
            lines.append(full + self._labels_str(
                labels, (("le", "+Inf"),)) + f" {count}")
            lines.append(self._fmt(name + "_sum", labels, total))
            lines.append(self._fmt(name + "_count", labels, count))
        return "\n".join(lines) + "\n"


SENSORS = SensorRegistry()
