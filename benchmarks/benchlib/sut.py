"""The system under test, brought up the way ``api/app.py:main`` does it.

The only file of the benchmark that imports the program. It hands the
deployment's arrays to the program's own stand-ins for Kafka
(``InMemoryAdminBackend``; a sampler that reports the deployment's loads),
wires monitor -> optimizer -> facade -> REST server exactly as
``chip_smoke.run`` does (``serve``: compile cache, ``start_up``, HTTP
thread; mesh chosen by ``entry_point_optimizer``), and exposes what the
harness reads: the port, one sampling round, and the program's counters in
their Prometheus text form.
"""

from __future__ import annotations

import re

import numpy as np

from .deployment import CPU, DISK, NW_IN, NW_OUT, Deployment

_QUIET_MS = 3_600_000
# chip_smoke.SMOKE_CONFIG: one-second windows filled by the harness at
# fixed end_ms; time-driven background work (sampling thread, detectors)
# held beyond the run so that no background solve lands in the window;
# nothing written into the checkout.
BASE_CONFIG = {
    "partition.metrics.window.ms": 1000,
    "broker.metrics.window.ms": 1000,
    "metric.sampling.interval.ms": _QUIET_MS,
    "anomaly.detection.interval.ms": _QUIET_MS,
    "failed.brokers.file.path": "",
}

_SERIES = re.compile(r"^kafka_cruisecontrol_(\w+?)(\{[^}]*\})? (\S+)$")


class DeploymentSampler:
    """Reports the deployment's per-partition loads, the same in every
    round (as ``SyntheticSampler`` does with its own numbers)."""

    def __init__(self, dep: Deployment):
        from cruise_control_tpu.metricdef.kafka_metric_def import (
            CommonMetric as CM,
        )
        from cruise_control_tpu.monitor.sampling.samples import (
            PartitionEntity, PartitionMetricSample,
        )
        self._dep = dep
        self._rows = {}
        ll = dep.leader_load
        for i in range(dep.partitions):
            topic, part = dep.topic_partition(i)
            s = PartitionMetricSample.make(topic, part, 0, {
                CM.CPU_USAGE: ll[i, CPU], CM.DISK_USAGE: ll[i, DISK],
                CM.LEADER_BYTES_IN: ll[i, NW_IN],
                CM.LEADER_BYTES_OUT: ll[i, NW_OUT],
                CM.REPLICATION_BYTES_IN_RATE: ll[i, NW_IN],
                CM.MESSAGE_IN_RATE: ll[i, NW_IN] / 2,
            })
            self._rows[(topic, part)] = (PartitionEntity(topic, part),
                                         s.values, i)

    def get_samples(self, partitions, start_ms, end_ms):
        from cruise_control_tpu.metricdef.kafka_metric_def import (
            CommonMetric as CM,
        )
        from cruise_control_tpu.monitor.sampling.sampler import SamplerResult
        from cruise_control_tpu.monitor.sampling.samples import (
            BrokerMetricSample, PartitionMetricSample,
        )
        psamples = []
        leader_in = np.zeros(self._dep.brokers)
        ll = self._dep.leader_load
        for key, st in partitions.items():
            if st.leader < 0:
                continue
            entity, values, i = self._rows[key]
            psamples.append(PartitionMetricSample(entity, end_ms, values))
            leader_in[st.leader] += ll[i, NW_IN]
        bsamples = [BrokerMetricSample.make(int(b), end_ms, {
            CM.CPU_USAGE.name: min(1.0, 2e-4 * v),
            CM.LEADER_BYTES_IN.name: v, CM.LEADER_BYTES_OUT.name: 2 * v,
        }) for b, v in enumerate(leader_in) if v > 0]
        return SamplerResult(psamples, bsamples, 0)

    def close(self) -> None:
        pass


def parse_exposition(text: str) -> dict:
    """Prometheus text -> {(name, labels_string): value}."""
    out = {}
    for line in text.splitlines():
        m = _SERIES.match(line)
        if m:
            out[(m.group(1), m.group(2) or "")] = float(m.group(3))
    return out


def series_total(series: dict, name: str, **labels) -> float:
    """Sum of a series over its label sets, optionally only those that
    carry every given ``label="value"``."""
    want = [f'{k}="{v}"' for k, v in labels.items()]
    return sum(v for (n, lab), v in series.items()
               if n == name and all(w in lab for w in want))


class Served:
    """One deployment behind the REST server, on loopback."""

    def __init__(self, cfg: dict, dep: Deployment):
        from cruise_control_tpu.api.app import entry_point_optimizer, serve
        from cruise_control_tpu.common.resources import Resource
        from cruise_control_tpu.config.cruise_control_config import (
            CruiseControlConfig,
        )
        from cruise_control_tpu.executor.admin import (
            InMemoryAdminBackend, PartitionState,
        )
        from cruise_control_tpu.facade import CruiseControl
        from cruise_control_tpu.monitor import (
            LoadMonitor, StaticCapacityResolver,
        )

        prefix = "cruise_control_tpu.analyzer.goals."
        goals = [g if "." in g else prefix + g for g in cfg["goals"]]
        hard = [g if "." in g else prefix + g for g in cfg["hard_goals"]]
        settings = dict(BASE_CONFIG)
        settings.update({
            "goals": goals, "hard.goals": hard,
            "anomaly.detection.goals": [g for g in goals if g in hard][:3]
            or goals[:1],
            "cpu.capacity.threshold":
                cfg["guarantees"]["capacity_threshold"]["cpu"],
            "disk.capacity.threshold":
                cfg["guarantees"]["capacity_threshold"]["disk"],
            "network.inbound.capacity.threshold":
                cfg["guarantees"]["capacity_threshold"]["nw_in"],
            "network.outbound.capacity.threshold":
                cfg["guarantees"]["capacity_threshold"]["nw_out"],
            "max.replicas.per.broker":
                cfg["guarantees"]["max_replicas_per_broker"],
        })
        settings.update(cfg.get("overrides", {}))
        self.config = CruiseControlConfig(settings)
        self._request_parameters = cfg.get("request_parameters", {})

        states = []
        for i, reps in enumerate(dep.assignment.tolist()):
            topic, part = dep.topic_partition(i)
            reps = tuple(reps)
            states.append(PartitionState(topic, part, reps, reps[0],
                                         isr=reps))
        backend = InMemoryAdminBackend(states)
        for b in range(dep.brokers):    # a new broker hosts nothing yet
            backend.revive_broker(b)
        caps = StaticCapacityResolver({}, {
            Resource.CPU: dep.capacity[CPU], Resource.DISK: dep.capacity[DISK],
            Resource.NW_IN: dep.capacity[NW_IN],
            Resource.NW_OUT: dep.capacity[NW_OUT]})
        self._monitor = LoadMonitor(
            self.config, backend, samplers=[DeploymentSampler(dep)],
            capacity_resolver=caps,
            broker_racks={b: f"rack{r}"
                          for b, r in enumerate(dep.broker_rack.tolist())})
        self.optimizer = entry_point_optimizer(self.config)
        self._cc = CruiseControl(self.config, backend,
                                 load_monitor=self._monitor,
                                 optimizer=self.optimizer)
        self._server, self._api, _thread = serve(
            self._cc, host="127.0.0.1", port=0, start_precompute=False)
        self.port = self._server.server_address[1]
        self._end_ms = 0

    def fill_windows(self) -> int:
        rounds = self.config.get_int("num.partition.metrics.windows") + 1
        for _ in range(rounds):
            self.sampling_round()
        return rounds

    def sampling_round(self) -> None:
        """One more sampling round at a fixed ``end_ms``: host-side numpy,
        no device work. It moves the model generation, so the facade
        cannot replay its cached proposal."""
        gen = self._monitor.model_generation
        self._end_ms += 1000
        self._monitor.task_runner.run_sampling_once(end_ms=self._end_ms)
        if self._monitor.model_generation == gen:
            raise RuntimeError("sampling round did not move the model "
                               "generation")

    def counters(self) -> dict:
        from cruise_control_tpu.utils.sensors import SENSORS
        series = parse_exposition(SENSORS.render())
        series[("pass_seq", "")] = float(self.optimizer.pass_seq())
        return series

    def request(self, dep: Deployment) -> tuple[str, str, dict]:
        """(method, endpoint, parameters) of the deployment's operation."""
        params = dict(self._request_parameters)
        if dep.operation == "proposals":
            return "GET", "proposals", params
        params["dryrun"] = "true"
        if dep.operation != "rebalance":
            params["brokerid"] = ",".join(map(str, dep.operation_brokers))
        return "POST", dep.operation, params

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._api.shutdown()
        self._cc.shutdown()
