#!/usr/bin/env python3
"""Chip smoke: one pass of the served path on the accelerator.

    python3 chip_smoke.py [--brokers N --partitions P --seed S]

Builds BASELINE.json config 3 (1,000 brokers / 100,000 partitions / RF 3 /
8 racks / topics = brokers/10, skewed placement and load from ``--seed``)
behind the wiring ``api/app.py:main`` uses (compile cache -> ``start_up``
-> REST server, mesh chosen at the entry point), with
``InMemoryAdminBackend`` and ``SyntheticSampler`` standing in for Kafka,
and answers, over loopback HTTP from a thread that never touches JAX:
``GET /state``, ``GET /load``, ``GET /proposals`` (cold; compile is
set-up), one more sampling round, ``GET /proposals`` (steady), ``POST
/rebalance?dryrun=true``. Exits non-zero unless the platform is the
required one (``tpu``), every request returned 200 with a body, the
proposals hold up (non-empty, no hard goal violated, balancedness not
worse), the solver donated buffers and used every visible device, and the
steady requests solved without compiling. Prints smoke readings (one
host-clock reading each, not benchmark numbers) and ends with one JSON
line naming the device.

One process holds the chip: everything runs here, no child is started.
The run is deterministic in what it asks of the device — the sampler
thread, the detectors and the proposal precompute loop are held off (see
``SMOKE_CONFIG`` and ``serve(start_precompute=False)``) so that no
background solve lands inside a bracketed request.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# Same imbalance recipe as the solver bench's random_cluster call: broker
# placement weights exp(-2 i/(B-1)) (first broker ~7x the last) and a
# heavy-tailed partition load, sized so the cluster AVERAGE sits at half
# of capacity while the crowded brokers start over their hard limits.
PLACEMENT_SKEW = 2.0
LOAD_SKEW = 3.0
TARGET_UTILIZATION = 0.5

_QUIET_MS = 3_600_000
SMOKE_CONFIG = {
    # The window keys a short run needs (api/app.py _DEMO_DEFAULTS does the
    # same): one-second windows, filled by the smoke at fixed end_ms.
    "partition.metrics.window.ms": 1000,
    "broker.metrics.window.ms": 1000,
    # Time-driven background work is held beyond the run: the smoke drives
    # sampling itself, and a detector tick must not solve (or compile)
    # inside a bracketed request.
    "metric.sampling.interval.ms": _QUIET_MS,
    "anomaly.detection.interval.ms": _QUIET_MS,
    # Nothing is written into the checkout.
    "failed.brokers.file.path": "",
}

# Counters bracketing each request: a solve ran iff pass_seq advanced;
# nothing compiled iff all three xla counters stood still (a jit-cache
# miss shows as a backend compile or as a persistent-cache hit/miss).
_COMPILE_COUNTERS = ("xla_compile_events", "xla_compile_cache_misses",
                     "xla_compile_cache_hits")


class SmokeFailure(Exception):
    """A check of the smoke's contract did not hold."""


def _say(msg: str) -> None:
    print(msg, flush=True)


def device_report(required_platform: str) -> dict:
    """The device as JAX reports it; refuses to go on (before anything is
    built or solved) on any platform but the required one."""
    import jax
    devices = jax.devices()
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if report["platform"] != required_platform:
        raise SmokeFailure(
            f"platform is {report['platform']!r} "
            f"({report['count']} x {report['kind']}), required "
            f"{required_platform!r}: not solving")
    return report


def build_cluster(brokers: int, partitions: int, rf: int, racks: int,
                  seed: int):
    """(partition states, broker -> rack) of the skewed deployment."""
    import numpy as np

    from cruise_control_tpu.executor.admin import PartitionState

    if partitions < brokers:
        raise ValueError("need at least one partition per broker")
    rng = np.random.default_rng(seed)
    rf = min(rf, brokers)
    topics = max(1, brokers // 10)
    weights = np.exp(-PLACEMENT_SKEW * np.arange(brokers)
                     / max(1, brokers - 1))
    cdf = np.cumsum(weights)

    def draw(n):
        return np.minimum(np.searchsorted(cdf, rng.random((n, rf)) * cdf[-1]),
                          brokers - 1)

    replicas = draw(partitions)
    while True:    # re-draw only the rows that drew one broker twice
        srt = np.sort(replicas, axis=1)
        dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
        if not dup.any():
            break
        replicas[dup] = draw(int(dup.sum()))
    # The first B partitions sit on a ring, so every broker hosts
    # something and the backend knows it is alive.
    replicas[:brokers] = (np.arange(brokers)[:, None] + np.arange(rf)) % brokers
    states = []
    for i, reps in enumerate(replicas.tolist()):
        reps = tuple(reps)
        states.append(PartitionState(f"topic{i % topics}", i // topics, reps,
                                     reps[0], isr=reps))
    return states, {b: f"rack{b % racks}" for b in range(brokers)}


def capacities(sampler, states, brokers: int, rf: int) -> dict:
    """Homogeneous broker capacity putting the cluster-average
    utilization of every resource at TARGET_UTILIZATION of what the
    sampler will report (followers replicate NW_IN and DISK; NW_OUT is
    leader-only; CPU takes the generous all-replicas bound)."""
    from cruise_control_tpu.common.resources import Resource
    from cruise_control_tpu.metricdef.kafka_metric_def import CommonMetric

    sample = sampler.get_samples(
        {(s.topic, s.partition): s for s in states}, 0, 1)
    total = {m: sum(p.metric_value(m) for p in sample.partition_samples)
             for m in (CommonMetric.CPU_USAGE, CommonMetric.DISK_USAGE,
                       CommonMetric.LEADER_BYTES_IN,
                       CommonMetric.LEADER_BYTES_OUT)}
    per_broker = 1.0 / (brokers * TARGET_UTILIZATION)
    return {
        Resource.CPU: rf * total[CommonMetric.CPU_USAGE] * per_broker,
        Resource.DISK: rf * total[CommonMetric.DISK_USAGE] * per_broker,
        Resource.NW_IN: rf * total[CommonMetric.LEADER_BYTES_IN] * per_broker,
        Resource.NW_OUT: total[CommonMetric.LEADER_BYTES_OUT] * per_broker,
    }


class _Client(threading.Thread):
    """The operator's side: the repo's own REST client
    (``client.Responder``: issue, then resume by User-Task-ID until the
    operation completes) over loopback HTTP, never JAX. ``probe`` reads
    plain Python counters; ``bump`` asks the monitor for one more sampling
    round (host-side numpy, no device work)."""

    def __init__(self, responder, probe, bump):
        super().__init__(name="smoke-client", daemon=True)
        self._responder = responder
        self._probe = probe
        self._bump = bump
        self.readings: dict[str, dict] = {}
        self.error: BaseException | None = None

    def call(self, label: str, method: str, endpoint: str,
             **params) -> dict:
        """One operation to completion; anything but a 200 with a body is
        a failure."""
        from cruise_control_tpu.client.responder import (
            CruiseControlClientError,
        )
        before = self._probe()
        t0 = time.monotonic()
        try:
            body = self._responder.retrieve_response(method, endpoint, params)
        except CruiseControlClientError as e:
            raise SmokeFailure(f"{label}: {e}") from e
        if not body:
            raise SmokeFailure(f"{label}: HTTP 200 with an empty body")
        after = self._probe()
        delta = {k: after[k] - before[k] for k in after}
        reading = {"seconds": time.monotonic() - t0, "body": body,
                   "delta": delta}
        self.readings[label] = reading
        query = "&".join(f"{k}={str(v).lower()}" for k, v in params.items())
        _say(f"smoke reading: {label:<16} {method} /{endpoint}"
             f"{'?' + query if query else ''} -> 200, "
             f"{reading['seconds']:.3f} s, "
             f"passes +{delta['pass_seq']}, compile events "
             f"+{sum(delta[c] for c in _COMPILE_COUNTERS):g} "
             f"({delta['compile_seconds']:.1f} s in backend compiles)")
        return reading

    def run(self) -> None:
        try:
            self.call("state", "GET", "state")
            self.call("load", "GET", "load")
            self.call("proposals_cold", "GET", "proposals")
            self._bump()
            self.call("proposals_steady", "GET", "proposals")
            self.call("rebalance_dryrun", "POST", "rebalance", dryrun=True)
            self.call("solver", "GET", "solver", entries=3)
        except BaseException as e:  # noqa: BLE001 — re-raised by run()
            self.error = e


def _check_proposals(label: str, reading: dict, hard_goals: set) -> None:
    body = reading["body"]
    if body.get("stale"):
        raise SmokeFailure(f"{label}: served a STALE cached body: "
                           f"{body.get('message')}")
    summary = body.get("summary")
    if not summary or body.get("numProposals", 0) <= 0:
        raise SmokeFailure(f"{label}: empty proposal set")
    violated_hard = hard_goals & set(summary["violated_goals_after"])
    if violated_hard:
        raise SmokeFailure(f"{label}: hard goals violated after "
                           f"optimization: {sorted(violated_hard)}")
    if summary["balancedness_after"] < summary["balancedness_before"]:
        raise SmokeFailure(
            f"{label}: balancedness fell "
            f"{summary['balancedness_before']} -> "
            f"{summary['balancedness_after']}")
    _say(f"smoke reading: {label:<16} {body['numProposals']} proposals, "
         f"balancedness {summary['balancedness_before']} -> "
         f"{summary['balancedness_after']}, violated after: "
         f"{summary['violated_goals_after']}")
    _say(f"smoke reading: {label:<16} per goal (rounds, s): " + ", ".join(
        f"{g['goal'].replace('Goal', '')} "
        f"{summary['goals'][g['goal']]['rounds']}/"
        f"{g['optimizationTimeMs'] / 1000:.1f}"
        for g in body["goalSummary"]))


def _check_steady(label: str, reading: dict) -> None:
    delta = reading["delta"]
    if delta["pass_seq"] != 1:
        # 0 = a cache replay; more = the polls were not resumed as one
        # task (or background work solved inside the bracket).
        raise SmokeFailure(f"{label}: {delta['pass_seq']:g} solves ran, "
                           "expected exactly one")
    compiled = {c: delta[c] for c in _COMPILE_COUNTERS if delta[c]}
    if compiled:
        raise SmokeFailure(f"{label}: compiled in the steady window: "
                           f"{compiled}")


def _check_devices(device: dict, optimizer) -> None:
    import jax
    used = optimizer.solver_devices()
    if used != device["count"]:
        raise SmokeFailure(
            f"solver ran on {used} device(s) of {device['count']} visible "
            "(mesh dropped to one device? see the optimizer's warning)")
    live = jax.live_arrays()
    off = [a for a in live
           if any(d.platform != device["platform"] for d in a.devices())]
    if not live or off:
        raise SmokeFailure(
            f"model arrays not on {device['platform']}: {len(live)} live, "
            f"{len(off)} elsewhere")
    for d in jax.devices():
        stats = d.memory_stats()
        if stats is None:       # the CPU backend keeps no allocator stats
            continue
        _say(f"smoke reading: device {d.id} bytes_in_use "
             f"{stats['bytes_in_use']}, peak {stats['peak_bytes_in_use']}")
        # The sharded model lives on the mesh only while a pass runs, so
        # the peak is what shows that every device took part.
        if stats["peak_bytes_in_use"] <= 0:
            raise SmokeFailure(f"device {d.id} never held anything "
                               "(peak_bytes_in_use == 0)")


def run(brokers: int = 1000, partitions: int = 100_000, rf: int = 3,
        racks: int = 8, seed: int = 0, platform: str = "tpu",
        timeout_s: float = 1150.0) -> dict:
    """Drive the served path once; raises ``SmokeFailure`` on the first
    check that does not hold, returns the result object otherwise."""
    t_start = time.monotonic()
    device = device_report(platform)

    import jax
    import jaxlib

    from cruise_control_tpu.analyzer.optimizer import goals_by_priority
    from cruise_control_tpu.api.app import entry_point_optimizer, serve
    from cruise_control_tpu.client.responder import Responder
    from cruise_control_tpu.config.cruise_control_config import (
        CruiseControlConfig,
    )
    from cruise_control_tpu.executor.admin import InMemoryAdminBackend
    from cruise_control_tpu.facade import CruiseControl
    from cruise_control_tpu.monitor import LoadMonitor, StaticCapacityResolver
    from cruise_control_tpu.monitor.sampling import SyntheticSampler
    from cruise_control_tpu.utils.sensors import SENSORS

    cfg = CruiseControlConfig(dict(SMOKE_CONFIG))
    states, broker_racks = build_cluster(brokers, partitions, rf, racks, seed)
    backend = InMemoryAdminBackend(states)
    sampler = SyntheticSampler(seed=seed, skew=LOAD_SKEW)
    caps = StaticCapacityResolver(
        {}, capacities(sampler, states, brokers, min(rf, brokers)))
    monitor = LoadMonitor(cfg, backend, samplers=[sampler],
                          capacity_resolver=caps, broker_racks=broker_racks)
    optimizer = entry_point_optimizer(cfg)
    cc = CruiseControl(cfg, backend, load_monitor=monitor,
                       optimizer=optimizer)
    server, api, _thread = serve(cc, host="127.0.0.1", port=0,
                                 start_precompute=False)
    try:
        from cruise_control_tpu import native
        _say(f"smoke ran on: platform={device['platform']} "
             f"device_kind={device['kind']} devices={device['count']} "
             f"mesh_devices={optimizer.mesh.devices.size if optimizer.mesh else 1} "
             f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
             f"compile_cache={jax.config.jax_compilation_cache_dir} "
             f"ccnative_loaded={native.lib() is not None}")
        _say(f"smoke deployment: {brokers} brokers, {partitions} partitions, "
             f"rf {rf}, {racks} racks, {max(1, brokers // 10)} topics, "
             f"seed {seed}, goals {len(goals_by_priority(cfg))}")
        windows = cfg.get_int("num.partition.metrics.windows")
        end_ms = 0
        t0 = time.monotonic()
        for _ in range(windows + 1):
            end_ms += 1000
            monitor.task_runner.run_sampling_once(end_ms=end_ms)
        _say(f"smoke reading: set-up    {windows + 1} sampling rounds "
             f"{time.monotonic() - t0:.3f} s (cluster build + wiring "
             f"{t0 - t_start:.3f} s)")

        def probe() -> dict:
            out = {c: SENSORS.counter_total(c) for c in _COMPILE_COUNTERS}
            out["compile_seconds"] = SENSORS.histogram_sum(
                "xla_compile_seconds")
            out["pass_seq"] = optimizer.pass_seq()
            out["donations"] = SENSORS.counter_total(
                "solver_dispatch_donations")
            return out

        def bump() -> None:
            nonlocal end_ms
            gen = monitor.model_generation
            end_ms += 1000
            monitor.task_runner.run_sampling_once(end_ms=end_ms)
            if monitor.model_generation == gen:
                raise SmokeFailure("sampling round did not bump the model "
                                   "generation")

        client = _Client(Responder(
            f"http://127.0.0.1:{server.server_address[1]}/kafkacruisecontrol",
            poll_interval_s=0.0,
            timeout_s=timeout_s - (time.monotonic() - t_start)), probe, bump)
        client.start()
        client.join(timeout_s)
        if client.is_alive():
            raise SmokeFailure(f"requests not answered in {timeout_s:.0f} s")
        if client.error is not None:
            raise client.error
        r = client.readings

        hard = {g.name for g in goals_by_priority(cfg) if g.is_hard}
        for label in ("proposals_cold", "proposals_steady",
                      "rebalance_dryrun"):
            _check_proposals(label, r[label], hard)
        for label in ("proposals_steady", "rebalance_dryrun"):
            _check_steady(label, r[label])
        if r["proposals_cold"]["delta"]["pass_seq"] < 1:
            raise SmokeFailure("proposals_cold: no solve ran")
        donated = sum(x["delta"]["donations"] for x in r.values())
        routes = {(p["path"], bool(p["attributes"].get("bounded")))
                  for p in r["solver"]["body"]["passes"]}
        if len(routes) != 1:
            raise SmokeFailure(f"the three solves took different routes: "
                               f"{sorted(routes)}")
        route, mesh_bounded = routes.pop()
        # Above solver.fused.chain.max.brokers the chain runs as bounded
        # dispatches, and those donate the mutable pair wherever the
        # backend cannot alias host memory (the CPU backend can, so the
        # chain never donates there: analyzer.chain.donation_enabled).
        if platform != "cpu" and (route == "bounded" or mesh_bounded) \
                and donated <= 0:
            raise SmokeFailure("donated_dispatches == 0 on the bounded "
                               "route: the solve did not donate")
        _check_devices(device, optimizer)
        cold = r["proposals_cold"]
        _say(f"smoke reading: set-up    proposals_cold {cold['seconds']:.3f} s"
             f" incl. compile (backend compiles "
             f"{cold['delta']['xla_compile_events']:g}, persistent-cache "
             f"hits {cold['delta']['xla_compile_cache_hits']:g}, misses "
             f"{cold['delta']['xla_compile_cache_misses']:g}); steady "
             f"{r['proposals_steady']['seconds']:.3f} s, dry-run "
             f"{r['rebalance_dryrun']['seconds']:.3f} s, donated "
             f"dispatches {donated:g}, route {route}"
             f"{' (bounded)' if mesh_bounded else ''}, solver_devices "
             f"{optimizer.solver_devices()}")
        return {"ok": True, "device": device}
    finally:
        server.shutdown()
        server.server_close()
        api.shutdown()
        cc.shutdown()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--brokers", type=int, default=1000)
    ap.add_argument("--partitions", type=int, default=100_000)
    ap.add_argument("--rf", type=int, default=3)
    ap.add_argument("--racks", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout-s", type=float, default=1150.0,
                    help="give up (non-zero) when the requests are not "
                    "answered within this many seconds")
    args = ap.parse_args(argv)
    try:
        result = run(args.brokers, args.partitions, args.rf, args.racks,
                     args.seed, timeout_s=args.timeout_s)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.stdout.flush()
        # A failed run may leave a solve in flight on a server thread;
        # interpreter teardown under a running XLA execution aborts.
        os._exit(1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
