"""Application bootstrap: ``python -m cruise_control_tpu.api.app``.

Reference parity: KafkaCruiseControlMain.java:26 (main(config,[port],[host]))
+ KafkaCruiseControlApp/KafkaCruiseControlServletApp — build the facade from
a properties file, start monitor + detectors, serve REST until interrupted.

Without --properties the app runs against a synthetic in-memory cluster
(the demo/dev mode; the reference needs a live Kafka for the same tour).
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys

from ..config.cruise_control_config import CruiseControlConfig
from ..facade import CruiseControl
from .server import make_server, serve_forever_in_thread

LOG = logging.getLogger(__name__)


def load_properties(path: str) -> dict:
    """Java .properties subset: key=value lines, # comments."""
    out: dict[str, str] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith(("#", "!")):
                continue
            key, _, value = line.partition("=")
            out[key.strip()] = value.strip()
    return out


def entry_point_optimizer(cfg: CruiseControlConfig):
    """The serving process solves on every device it can see: the mesh is
    chosen HERE, at the entry point (``mesh="auto"`` = all visible devices
    when more than one), and handed to the facade through its
    ``optimizer=`` seam. Library defaults stay single-device."""
    from ..analyzer.optimizer import GoalOptimizer
    return GoalOptimizer(cfg, mesh="auto")


def build_demo_cruise_control(cfg: CruiseControlConfig) -> CruiseControl:
    from ..common.resources import Resource
    from ..executor.admin import InMemoryAdminBackend, PartitionState
    from ..monitor import LoadMonitor, StaticCapacityResolver
    from ..monitor.sampling import SyntheticSampler

    parts = {}
    for t in range(4):
        for p in range(8):
            reps = (0, 1 + (t + p) % 3)
            parts[(f"demo{t}", p)] = PartitionState(f"demo{t}", p, reps,
                                                    reps[0], isr=reps)
    backend = InMemoryAdminBackend(parts.values())
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6, Resource.NW_OUT: 1e6})
    monitor = LoadMonitor(cfg, backend, samplers=[SyntheticSampler()],
                          capacity_resolver=caps)
    return CruiseControl(cfg, backend, load_monitor=monitor,
                         optimizer=entry_point_optimizer(cfg))


def _configured_sample_store(cfg: CruiseControlConfig, bootstrap: str):
    """sample.store.class resolution for live mode: the Kafka store gets
    the bootstrap servers, the file store its configured path, a custom
    class a bare constructor. The configured store must actually be built
    — silently dropping it would cold-start the load model on every
    restart (no warm-window replay)."""
    from ..config.abstract_config import resolve_class
    from ..kafka import KafkaSampleStore
    from ..monitor.sampling.sample_store import FileSampleStore

    spec = cfg.get("sample.store.class")
    cls = resolve_class(spec) if isinstance(spec, str) else spec
    if cls is KafkaSampleStore:
        return KafkaSampleStore(bootstrap)
    if cls is FileSampleStore or cls is None:
        return FileSampleStore(cfg.get("sample.store.path"))
    return cls()


def _configured_capacity_resolver(cfg: CruiseControlConfig):
    """broker.capacity.config.resolver.class resolution (the
    getConfiguredInstance path): hardcoding a default here would feed the
    goals fictitious capacities on heterogeneous clusters."""
    from ..config.abstract_config import resolve_class
    from ..monitor.capacity import FileCapacityResolver

    spec = cfg.get("broker.capacity.config.resolver.class")
    cls = resolve_class(spec) if isinstance(spec, str) else spec
    if cls is FileCapacityResolver or cls is None:
        return FileCapacityResolver(cfg.get("capacity.config.file"))
    return cls()


def build_live_cruise_control(cfg: CruiseControlConfig) -> CruiseControl:
    """Wire the full stack against a LIVE Kafka cluster through the
    framework's own wire-protocol client (kafka/): admin ops, the
    __CruiseControlMetrics reporter-topic sampler, the configured sample
    store and capacity resolver, and broker racks from cluster metadata
    (refreshed per model build for late-joining brokers)."""
    from ..kafka import KafkaAdminBackend, KafkaMetricsTransport
    from ..monitor import LoadMonitor
    from ..monitor.sampling.sampler import CruiseControlMetricsReporterSampler
    from ..utils.resilience import RetryPolicy

    bootstrap = ",".join(cfg.get_list("bootstrap.servers"))
    admin = KafkaAdminBackend(bootstrap,
                              retry_policy=RetryPolicy.from_config(cfg))
    transport = KafkaMetricsTransport(bootstrap)
    sampler = CruiseControlMetricsReporterSampler(transport)
    if cfg.get_boolean("chaos.enabled"):
        # Game-day drill wiring: wrap BEFORE the monitor is built so the
        # sampling fetch and monitor metadata paths see injected faults
        # too (the facade's own wrap is idempotent and shares this
        # schedule — wrapping only there would leave the monitor clean
        # and report resilience as proven without exercising it).
        from ..testing.chaos import ChaosAdminBackend, ChaosSampler
        admin = ChaosAdminBackend.from_config(admin, cfg)
        sampler = ChaosSampler(sampler, schedule=admin.schedule)
    monitor = LoadMonitor(
        cfg, admin, samplers=[sampler],
        sample_store=_configured_sample_store(cfg, bootstrap),
        capacity_resolver=_configured_capacity_resolver(cfg))
    return CruiseControl(cfg, admin, load_monitor=monitor,
                         optimizer=entry_point_optimizer(cfg))


# Demo-mode tunables: a fresh operator should see a working rebalance in
# seconds, not after the production 5-minute window fills (the reference
# demo tour has the same cold-start, but it needs a live cluster anyway).
_DEMO_DEFAULTS = {
    "metric.sampling.interval.ms": 2_000,
    "partition.metrics.window.ms": 5_000,
    "broker.metrics.window.ms": 5_000,
    "min.valid.partition.ratio": 0.0,
}


def serve(cc: CruiseControl, host: str | None = None,
          port: int | None = None, start_precompute: bool = True):
    """Bring a wired facade up behind the REST server: compile cache
    first (so even monitor-warmup jits land in it), then ``start_up``
    (monitor, detectors, prewarm), then the HTTP thread. Returns
    ``(server, api, thread)``; the caller shuts down ``server``, ``api``
    and ``cc`` in that order. ``main`` and ``chip_smoke.py`` both come
    through here."""
    from ..warmstart import configure_compile_cache
    configure_compile_cache(cc.config)
    cc.start_up(block_on_load=False, start_precompute=start_precompute)
    server, api = make_server(cc, host=host, port=port)
    return server, api, serve_forever_in_thread(server)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="cruise-control-tpu")
    parser.add_argument("--properties", help="config properties file")
    parser.add_argument("--port", type=int, help="REST port override")
    parser.add_argument("--host", help="bind address override")
    parser.add_argument("--demo", action="store_true",
                        help="synthetic in-memory cluster (default when no "
                        "--properties is given)")
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s "
                        "%(levelname)s %(message)s")
    overrides = load_properties(args.properties) if args.properties else {}
    if overrides.get("bootstrap.servers") and not args.demo:
        # Live mode: the wire-protocol client manages the real cluster.
        cc = build_live_cruise_control(CruiseControlConfig(overrides))
    else:
        demo_cfg = dict(_DEMO_DEFAULTS)
        demo_cfg.update(overrides)
        cc = build_demo_cruise_control(CruiseControlConfig(demo_cfg))
    server, api, thread = serve(cc, host=args.host, port=args.port)
    host, port = server.server_address[:2]
    LOG.info("cruise-control-tpu listening on http://%s:%s/kafkacruisecontrol/state",
             host, port)

    stop = {"flag": False}

    def _sigterm(_sig, _frm):
        stop["flag"] = True

    signal.signal(signal.SIGINT, _sigterm)
    signal.signal(signal.SIGTERM, _sigterm)
    try:
        while not stop["flag"] and thread.is_alive():
            thread.join(timeout=0.5)
    finally:
        server.shutdown()
        api.shutdown()
        cc.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
