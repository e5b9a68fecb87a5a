"""Metric fetch fan-out.

Reference parity: monitor/sampling/MetricFetcherManager.java:37-174 (N
fetcher threads over a pluggable MetricSamplerPartitionAssignor) and
SamplingFetcher.java (feeds aggregators + sample store).
"""

from __future__ import annotations

import logging
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Mapping

import numpy as np

from ...executor.admin import PartitionState
from ...utils.resilience import RetryPolicy, call_with_resilience
from .sampler import MetricSampler, SamplerResult
from .sample_store import SampleStore
from .samples import samples_to_matrix

LOG = logging.getLogger(__name__)


class PartialWindowError(RuntimeError):
    """The sampling interval fetched less than the configured
    completeness floor — the window is rejected rather than ingested
    (the task runner logs and the next interval retries)."""


def default_partition_assignor(partitions: Mapping[tuple[str, int], PartitionState],
                               num_fetchers: int) -> list[dict]:
    """DefaultMetricSamplerPartitionAssignor: deterministic spread of the
    partition universe across fetchers at TOPIC granularity. Keeping a
    topic's partitions in one bucket is load-bearing: the processor derives
    per-partition rates from topic-level rates using share weights over the
    partitions it sees, so splitting a topic across fetchers would make each
    fetcher attribute the full topic rate to its subset.

    The topic hash is ``crc32`` (NOT builtin ``hash``, which varies per
    process under PYTHONHASHSEED): topic→fetcher placement must survive
    restarts so per-fetcher sample stores and caches stay warm."""
    buckets: list[dict] = [{} for _ in range(num_fetchers)]
    for (topic, part), st in partitions.items():
        idx = zlib.crc32(topic.encode("utf-8")) % num_fetchers
        buckets[idx][(topic, part)] = st
    return buckets


class MetricFetcherManager:
    """Fans a sampling interval out over samplers and routes the returned
    samples into the two aggregators + the sample store."""

    def __init__(self, samplers: list[MetricSampler],
                 partition_aggregator, broker_aggregator,
                 sample_store: SampleStore,
                 assignor: Callable = default_partition_assignor,
                 num_fetchers: int | None = None,
                 retry_policy: RetryPolicy | None = None,
                 min_completeness: float = 0.0):
        if not samplers:
            raise ValueError("at least one sampler required")
        # Resilience (round 9): each fetcher retries its sampler under
        # the policy; a fetcher that still fails costs only ITS bucket.
        # The merged interval is accepted as a PARTIAL window while the
        # fetched fraction stays at or above ``min_completeness``
        # (reference parity: sampling completeness) and rejected with
        # PartialWindowError below it — degraded data beats no data,
        # but a mostly-empty window would poison the aggregates.
        self._retry_policy = retry_policy
        self._min_completeness = min_completeness
        # num.metric.fetchers fan-out (MetricFetcherManager.java:37-110):
        # the reference runs N fetcher threads each with its own sampler
        # instance. With one configured sampler and N > 1, clone it per
        # fetcher when it supports clone(); a sampler without clone() is
        # shared across threads (must then be thread-safe, like the
        # synthetic and noop samplers).
        n = num_fetchers or len(samplers)
        if len(samplers) == 1 and n > 1:
            base = samplers[0]
            clone = getattr(base, "clone", None)
            samplers = [base] + [clone() if clone else base
                                 for _ in range(n - 1)]
        self._samplers = samplers
        self._partition_agg = partition_aggregator
        self._broker_agg = broker_aggregator
        self._store = sample_store
        self._assignor = assignor
        self._pool = ThreadPoolExecutor(max_workers=len(samplers),
                                        thread_name_prefix="metric-fetcher")
        self._lock = threading.Lock()

    def fetch_metric_samples(
            self,
            describe: Callable[[], Mapping[tuple[str, int], PartitionState]],
            start_ms: int, end_ms: int,
            store: bool = True) -> SamplerResult:
        """One sampling round under span ``monitor.sample_fetch``, split
        where the work happens: ``sampling.describe`` (``describe()``, the
        metadata read that names the partitions, so the round's span
        covers it), ``sampling.get_samples`` (the sampler plug-ins'
        fan-out, until the last fetcher returns) and ``sampling.ingest``
        (aggregators and sample store)."""
        from ...utils.tracing import TRACER
        with TRACER.span("monitor.sample_fetch", operation="sampling",
                         num_fetchers=len(self._samplers)) as sp:
            with TRACER.span("sampling.describe"):
                partitions = describe()
            sp.set(num_partitions=len(partitions))
            with TRACER.span("sampling.get_samples"):
                buckets = self._assignor(partitions, len(self._samplers))
                futures = [self._pool.submit(self._fetch_one, s, b,
                                             start_ms, end_ms)
                           for s, b in zip(self._samplers, buckets)]
                merged = SamplerResult([], [], 0)
                for f in futures:
                    r = f.result()
                    merged.partition_samples.extend(r.partition_samples)
                    merged.broker_samples.extend(r.broker_samples)
                    merged.skipped_partitions += r.skipped_partitions
            total = len(partitions)
            completeness = 1.0 if total == 0 \
                else 1.0 - merged.skipped_partitions / total
            if total and completeness < self._min_completeness:
                from ...utils.sensors import SENSORS
                SENSORS.count("monitor_windows_rejected")
                sp.set(completeness=round(completeness, 4), rejected=True)
                raise PartialWindowError(
                    f"sampling interval [{start_ms}, {end_ms}) fetched "
                    f"{completeness:.1%} of {total} partitions, below the "
                    f"{self._min_completeness:.1%} completeness floor")
            if merged.skipped_partitions:
                # Degraded but above the floor: accept the partial window
                # (the reference's sampling-completeness semantics) and
                # make the degradation visible.
                from ...utils.sensors import SENSORS
                SENSORS.count("monitor_partial_windows")
                sp.set(partial=True)
            with TRACER.span("sampling.ingest"):
                self._ingest(merged, end_ms, store)
            sp.set(partition_samples=len(merged.partition_samples),
                   broker_samples=len(merged.broker_samples),
                   skipped_partitions=merged.skipped_partitions,
                   completeness=round(completeness, 4))
            return merged

    def _fetch_one(self, sampler: MetricSampler, bucket, start_ms, end_ms):
        try:
            return call_with_resilience(
                "sampler.get_samples",
                lambda: sampler.get_samples(bucket, start_ms, end_ms),
                policy=self._retry_policy)
        except Exception:
            LOG.exception("metric sampler failed for interval [%s, %s)",
                          start_ms, end_ms)
            # sampling-fetch failure rate (LoadMonitorTaskRunner sensors).
            # Per-fetcher degradation: this bucket's partitions count as
            # skipped; the other fetchers' samples still land.
            from ...utils.sensors import SENSORS
            SENSORS.count("monitor_sampling_fetch_failures")
            return SamplerResult([], [], len(bucket))

    def _ingest(self, result: SamplerResult, time_ms: int, store: bool) -> None:
        with self._lock:
            ents, vals = samples_to_matrix(result.partition_samples)
            if ents:
                self._partition_agg.add_samples_batch(ents, time_ms, vals)
            ents, vals = samples_to_matrix(result.broker_samples)
            if ents:
                self._broker_agg.add_samples_batch(ents, time_ms, vals)
        if store:
            self._store.store_samples(result)

    def clear(self) -> None:
        """Drop all aggregated windows (bootstrap with clear-metrics)."""
        with self._lock:
            self._partition_agg.clear()
            self._broker_agg.clear()

    def replay(self, result: SamplerResult) -> int:
        """Load store-replayed samples into the aggregators at their original
        timestamps (KafkaSampleStore.loadSamples warm-start path)."""
        count = 0
        with self._lock:
            for s in result.partition_samples:
                self._partition_agg.add_sample(s.entity, s.time_ms,
                                               np.asarray(s.values, dtype=np.float32))
                count += 1
            for s in result.broker_samples:
                self._broker_agg.add_sample(s.entity, s.time_ms,
                                            np.asarray(s.values, dtype=np.float32))
                count += 1
        return count

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)
        for s in self._samplers:
            s.close()
