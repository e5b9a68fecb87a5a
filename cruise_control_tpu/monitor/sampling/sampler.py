"""MetricSampler SPI + bundled implementations.

Reference parity: monitor/sampling/MetricSampler.java plugin SPI with
CruiseControlMetricsReporterSampler (consumes the reporter's metrics topic),
PrometheusMetricSampler (PromQL over HTTP), and NoopSampler.

Redesign: the Kafka consumer is abstracted behind ``MetricsTransport`` (an
in-memory queue in this image — the wire binding (kafka.transport.KafkaMetricsTransport) implements
the same two methods against the real ``__CruiseControlMetrics`` topic).
The Prometheus sampler maps PromQL queries onto raw metric types like the
reference's PrometheusAdapter but is gated on an injectable ``http_get``
so tests run without a server and the image needs no client library.
"""

from __future__ import annotations

import dataclasses
import math
import time
import zlib
from typing import Callable, Mapping, Protocol

from ...executor.admin import PartitionState
from ...metricdef.raw_metric_type import RawMetricType as R
from ...model.cpu_estimation import CpuEstimator
from ...reporter.metrics import CruiseControlMetric, deserialize
from .processor import CruiseControlMetricsProcessor, ProcessorResult
from .samples import BrokerMetricSample, PartitionMetricSample


@dataclasses.dataclass
class SamplerResult:
    partition_samples: list[PartitionMetricSample]
    broker_samples: list[BrokerMetricSample]
    skipped_partitions: int = 0


class MetricSampler(Protocol):
    """getSamples(cluster, assigned partitions, [start, end)) → samples."""

    def get_samples(self, partitions: Mapping[tuple[str, int], PartitionState],
                    start_ms: int, end_ms: int) -> SamplerResult: ...

    def close(self) -> None: ...


class NoopSampler:
    def get_samples(self, partitions, start_ms, end_ms) -> SamplerResult:
        return SamplerResult([], [], 0)

    def close(self) -> None:
        pass


class MetricsTransport(Protocol):
    """Minimal consumer view of the metrics topic."""

    def poll(self, start_ms: int, end_ms: int) -> list[bytes]: ...

    def produce(self, payload: bytes) -> None: ...


class InMemoryMetricsTransport:
    """Test/simulation transport holding serialized metric records."""

    def __init__(self):
        self._records: list[tuple[int, bytes]] = []

    def produce(self, payload: bytes) -> None:
        m = deserialize(payload)
        self._records.append((m.time_ms, payload))

    def produce_metric(self, metric: CruiseControlMetric) -> None:
        from ...reporter.metrics import serialize
        self._records.append((metric.time_ms, serialize(metric)))

    def poll(self, start_ms: int, end_ms: int) -> list[bytes]:
        return [b for ts, b in self._records if start_ms <= ts < end_ms]


class CruiseControlMetricsReporterSampler:
    """Consumes reporter records from the transport and runs the processor
    (CruiseControlMetricsReporterSampler.java + MetricsProcessor)."""

    def __init__(self, transport: MetricsTransport,
                 cpu_estimator: CpuEstimator | None = None):
        self._transport = transport
        self._processor = CruiseControlMetricsProcessor(cpu_estimator)

    def get_samples(self, partitions, start_ms: int, end_ms: int) -> SamplerResult:
        res = self._columnar_samples(partitions, start_ms, end_ms)
        if res is None:
            raw = [deserialize(b) for b in self._transport.poll(start_ms, end_ms)]
            if partitions:
                assigned = set(partitions)
                raw = [m for m in raw
                       if m.topic is None or m.partition < 0
                       or (m.topic, m.partition) in assigned]
            res = self._processor.process(raw, partitions, end_ms)
        return SamplerResult(res.partition_samples, res.broker_samples,
                             res.skipped_partitions)

    def _columnar_samples(self, partitions, start_ms: int,
                          end_ms: int) -> "ProcessorResult | None":
        """The vectorized ingest path: raw record-set bytes → native span
        index → one columnar serde parse → batched BrokerLoads. Falls back
        to the per-record path when the transport cannot serve spans (the
        in-memory test transport, or no C compiler)."""
        poll_columns = getattr(self._transport, "poll_columns", None)
        if poll_columns is None:
            return None
        got = poll_columns(start_ms, end_ms)
        if got is None:
            return None
        import numpy as np

        from ...monitor.sampling.holder import broker_loads_from_columns
        from ...reporter.metrics import deserialize_columns

        data, spans = got
        cols = deserialize_columns(data, spans)
        if partitions and len(cols):
            # Assigned-partition filter (scalar path parity): only
            # partition-scope rows are filtered; broker/topic scope passes.
            tid_of = {t: i for i, t in enumerate(cols.topics)}
            assigned = np.array(
                [(tid_of[t] << 32) | p for (t, p) in partitions
                 if t in tid_of], dtype=np.int64)
            keys = (cols.topic_id.astype(np.int64) << 32) \
                | (cols.partition.astype(np.int64) & 0xFFFFFFFF)
            ok = (cols.scope != 2) | np.isin(keys, assigned)
            if not ok.all():
                cols = cols.take(ok)
        loads = broker_loads_from_columns(cols)
        return self._processor.process((), partitions, end_ms, loads=loads)

    def close(self) -> None:
        pass


# -- Prometheus ------------------------------------------------------------

# PromQL per raw metric (PrometheusMetricSampler.java DEFAULT_QUERY_MAP).
DEFAULT_PROMETHEUS_QUERIES: dict[R, str] = {
    R.ALL_TOPIC_BYTES_IN: "sum(rate(kafka_server_BrokerTopicMetrics_BytesInPerSec[1m])) by (instance)",
    R.ALL_TOPIC_BYTES_OUT: "sum(rate(kafka_server_BrokerTopicMetrics_BytesOutPerSec[1m])) by (instance)",
    R.BROKER_CPU_UTIL: "1 - avg(rate(node_cpu_seconds_total{mode='idle'}[1m])) by (instance)",
    R.TOPIC_BYTES_IN: "sum(rate(kafka_server_BrokerTopicMetrics_BytesInPerSec[1m])) by (instance, topic)",
    R.TOPIC_BYTES_OUT: "sum(rate(kafka_server_BrokerTopicMetrics_BytesOutPerSec[1m])) by (instance, topic)",
    R.PARTITION_SIZE: "kafka_log_Log_Size",
}


def prometheus_http_get(endpoint: str, timeout_s: float = 10.0,
                        ) -> "Callable[[str, float], list[tuple[dict, float]]]":
    """Production ``http_get`` for ``PrometheusMetricSampler``: an instant
    query against ``{endpoint}/api/v1/query`` via stdlib urllib
    (prometheus/PrometheusAdapter.java:queryMetric). Returns
    [(labels, value)] rows; non-success statuses raise."""
    import json as _json
    import urllib.parse
    import urllib.request

    base = endpoint.rstrip("/")

    def http_get(query: str, time_s: float) -> list[tuple[dict, float]]:
        import urllib.error

        url = (f"{base}/api/v1/query?"
               + urllib.parse.urlencode({"query": query, "time": time_s}))
        try:
            with urllib.request.urlopen(url, timeout=timeout_s) as resp:
                payload = _json.load(resp)
        except urllib.error.HTTPError as e:
            # Prometheus reports query errors (e.g. bad PromQL) as non-2xx
            # WITH a JSON body — surface its detail, not a bare 400.
            try:
                payload = _json.load(e)
            except Exception:  # noqa: BLE001 — body was not JSON
                raise RuntimeError(
                    f"prometheus query failed: HTTP {e.code}") from e
        if payload.get("status") != "success":
            raise RuntimeError(f"prometheus query failed: "
                               f"{payload.get('error', payload)}")
        out = []
        for row in payload.get("data", {}).get("result", []):
            value = row.get("value", [None, "nan"])[1]
            out.append((row.get("metric", {}), float(value)))
        return out

    return http_get


class PrometheusMetricSampler:
    """PromQL-backed sampler. ``http_get(query, time_s) -> [(labels, value)]``
    is injected for tests; production uses ``from_endpoint`` (the stdlib
    urllib client against ``/api/v1/query``, with the server URL from the
    ``prometheus.server.endpoint`` config key)."""

    @classmethod
    def from_endpoint(cls, endpoint: str,
                      broker_of_instance: Callable[[str], int | None],
                      queries: Mapping[R, str] | None = None,
                      cpu_estimator: CpuEstimator | None = None,
                      ) -> "PrometheusMetricSampler":
        return cls(prometheus_http_get(endpoint), broker_of_instance,
                   queries, cpu_estimator)

    def __init__(self, http_get: Callable[[str, float], list[tuple[dict, float]]],
                 broker_of_instance: Callable[[str], int | None],
                 queries: Mapping[R, str] | None = None,
                 cpu_estimator: CpuEstimator | None = None):
        self._http_get = http_get
        self._broker_of = broker_of_instance
        self._queries = dict(queries or DEFAULT_PROMETHEUS_QUERIES)
        self._processor = CruiseControlMetricsProcessor(cpu_estimator)

    def get_samples(self, partitions, start_ms: int, end_ms: int) -> SamplerResult:
        raw: list[CruiseControlMetric] = []
        t = end_ms / 1000.0
        for rtype, q in self._queries.items():
            for labels, value in self._http_get(q, t):
                broker = self._broker_of(labels.get("instance", ""))
                if broker is None or not math.isfinite(value):
                    continue
                topic = labels.get("topic")
                part = int(labels.get("partition", -1))
                raw.append(CruiseControlMetric(rtype, end_ms, broker, value,
                                               topic=topic, partition=part))
        res = self._processor.process(raw, partitions, end_ms)
        return SamplerResult(res.partition_samples, res.broker_samples,
                             res.skipped_partitions)

    def close(self) -> None:
        pass


class SyntheticSampler:
    """Deterministic load generator for demos and tests: stable per-partition
    rates derived from a crc32 of (seed, topic, partition) so windows are
    self-consistent across intervals AND across processes (builtin
    ``hash()`` is PYTHONHASHSEED-randomized for the topic string — the
    same trap PR 4 fixed in the partition assignor; CCSA004 now polices
    it). ``skew`` > 1 raises the uniform draw to that power: most
    partitions light, a few heavy (1.0 keeps the uniform spread)."""

    def __init__(self, seed: int = 0, cpu_per_kb: float = 2e-4,
                 skew: float = 1.0):
        self._seed = seed
        self._cpu_per_kb = cpu_per_kb
        self._skew = skew

    def get_samples(self, partitions, start_ms, end_ms) -> SamplerResult:
        from ...metricdef.kafka_metric_def import CommonMetric as CM
        psamples = []
        per_broker: dict[int, float] = {}
        for (topic, part), st in partitions.items():
            if st.leader < 0:
                continue
            h = ((zlib.crc32(f"{self._seed}:{topic}:{part}".encode())
                  % 1000) / 1000.0) ** self._skew
            bytes_in = 50.0 + 950.0 * h
            bytes_out = 2.0 * bytes_in
            psamples.append(PartitionMetricSample.make(topic, part, end_ms, {
                CM.CPU_USAGE: self._cpu_per_kb * bytes_in,
                CM.DISK_USAGE: 10_000.0 * h + 100.0,
                CM.LEADER_BYTES_IN: bytes_in,
                CM.LEADER_BYTES_OUT: bytes_out,
                CM.REPLICATION_BYTES_IN_RATE: bytes_in,
                CM.MESSAGE_IN_RATE: bytes_in / 2,
            }))
            per_broker[st.leader] = per_broker.get(st.leader, 0.0) + bytes_in
        bsamples = [BrokerMetricSample.make(b, end_ms, {
            CM.CPU_USAGE.name: min(1.0, self._cpu_per_kb * v),
            CM.LEADER_BYTES_IN.name: v, CM.LEADER_BYTES_OUT.name: 2 * v,
        }) for b, v in per_broker.items()]
        return SamplerResult(psamples, bsamples, 0)

    def close(self) -> None:
        pass


def now_ms() -> int:
    return int(time.time() * 1000)
