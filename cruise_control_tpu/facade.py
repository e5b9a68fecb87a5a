"""The orchestration facade: the single object wiring monitor, analyzer,
executor, and anomaly detection.

Reference parity: KafkaCruiseControl.java:78 (constructor wiring :112-129,
startUp:221, proposal/execute delegation) plus the operation runnables
(servlet/handler/async/runnable/: RebalanceRunnable:115,
AddBrokersRunnable, RemoveBrokersRunnable, DemoteBrokerRunnable,
FixOfflineReplicasRunnable, UpdateTopicConfigurationRunnable,
ProposalsRunnable) — here each runnable body is a facade method; the async
wrapper lives in api/user_tasks.py.

Broker-scoped operations are expressed as state edits on the tensor model
(set_broker_state — NEW for additions, DEAD for removals, DEMOTED for
demotions) followed by the same batched goal chain; the reference does the
identical thing on its object graph before optimizing.
"""

from __future__ import annotations

import contextvars
import dataclasses
import logging
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Sequence

import numpy as np

from .analyzer.constraint import OptimizationOptions
from .analyzer.optimizer import (
    GoalOptimizer, OptimizerResult, goals_by_priority,
)
from .analyzer.proposals import ExecutionProposal
from .common.broker_state import BrokerState
from .config.cruise_control_config import CruiseControlConfig
from .detector.broker_failure import BrokerFailureDetector
from .detector.disk_failure import DiskFailureDetector
from .detector.goal_violation import GoalViolationDetector
from .detector.maintenance import (
    InMemoryMaintenanceEventReader, MaintenanceEventDetector,
)
from .detector.manager import AnomalyDetectorManager
from .detector.metric_anomaly import MetricAnomalyDetector
from .detector.notifier import AnomalyNotifier, SelfHealingNotifier
from .detector.topic_anomaly import TopicAnomalyDetector
from .executor.admin import AdminBackend
from .executor.concurrency import ConcurrencyAdjusterConfig, ConcurrencyCaps
from .executor.executor import Executor
from .model.tensors import ClusterMeta, ClusterTensors, set_broker_state
from .monitor.load_monitor import (
    LoadMonitor, ModelCompletenessRequirements, NotEnoughValidWindowsError,
)
from .monitor.task_runner import SamplingMode

LOG = logging.getLogger(__name__)
OPERATION_LOG = logging.getLogger("cruise_control_tpu.operation")

# Per-request execution overrides (strategy, concurrency dict, extras dict)
# — thread/task scoped via ContextVar; see CruiseControl.execution_overrides.
# extras keys: progress_check_interval_s, replication_throttle,
# throttle_excluded_brokers, stop_ongoing_execution.
_EXECUTION_OVERRIDES: contextvars.ContextVar[tuple] = \
    contextvars.ContextVar("execution_overrides", default=(None, {}, {}))


def _traced_op(name: str):
    """Span wrapper for the operation runnables (operation attribute =
    runnable name; cluster attribution comes from the ambient sensor
    label): a child of the served request's ``http.handle`` where the
    api layer re-entered the request's span on this worker, the root of
    its own trace otherwise (library callers, detectors). Child spans —
    aggregate, model assembly, solver dispatch, execution — open
    contextvar-deep with no plumbing."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            from .utils.tracing import TRACER
            with TRACER.span(name, operation=name):
                return fn(self, *args, **kwargs)
        return wrapper
    return deco


@dataclass
class OperationResult:
    """What every operation returns (the runnable's computeResult)."""

    operation: str
    dryrun: bool
    optimizer_result: OptimizerResult | None = None
    proposals: tuple[ExecutionProposal, ...] = ()
    executed: bool = False
    reason: str = ""
    extra: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        d = {"operation": self.operation, "dryrun": self.dryrun,
             "executed": self.executed, "reason": self.reason,
             "numProposals": len(self.proposals)}
        if self.optimizer_result is not None:
            d["summary"] = self.optimizer_result.summary()
        d.update(self.extra)
        return d


class CruiseControl:
    """The KafkaCruiseControl facade for the TPU framework."""

    def __init__(self, config: CruiseControlConfig, admin: AdminBackend,
                 load_monitor: LoadMonitor | None = None,
                 executor: Executor | None = None,
                 notifier: AnomalyNotifier | None = None,
                 optimizer: GoalOptimizer | None = None,
                 clock: "Callable[[], float] | None" = None,
                 configure_observability: bool = True):
        self._config = config
        # Injectable clock (round 11): when given, simulated time drives
        # every detector-pipeline time comparison — anomaly tick
        # scheduling, broker-failure escalation thresholds, maintenance
        # idempotence windows, and the model breaker's recovery window —
        # so the digital-twin simulator replays hours of cluster drift
        # wall-clock-free. None (production) keeps wall time everywhere.
        self._clock = clock
        self._now_ms = (lambda: int(clock() * 1000)) \
            if clock is not None else None
        # Chaos harness (round 9): ``chaos.enabled=true`` wraps the admin
        # backend in the deterministic fault injector — game-day drills
        # run the REAL pipeline against injected timeouts/transients/
        # partial metadata, exercising the same resilience layer the
        # chaos suite pins.
        if config.get_boolean("chaos.enabled"):
            from .testing.chaos import ChaosAdminBackend
            if not isinstance(admin, ChaosAdminBackend):
                # Idempotent: a builder that already wrapped (so its
                # monitor/sampler share the SAME fault schedule — see
                # api/app.build_live_cruise_control) is left alone.
                admin = ChaosAdminBackend.from_config(admin, config)
        self._admin = admin
        # Resilience (round 9): one retry policy + one breaker per
        # facade, shared by the executor's admin calls and the proposal
        # path's stale-cache fallback below.
        from .utils.resilience import CircuitBreaker, RetryPolicy
        self._retry_policy = RetryPolicy.from_config(config)
        self._model_breaker = CircuitBreaker.from_config(
            config, name="model",
            clock=clock if clock is not None else time.monotonic)
        # Observability wiring (round 8): one process-wide tracer,
        # (re)configured from each facade's config — fleet overlays
        # inherit the tracing.* keys from the base config, and per-cluster
        # attribution comes from the ambient cluster label, not from
        # per-facade tracers. XLA telemetry hooks jax.monitoring once.
        # ``configure_observability=False`` (digital-twin simulators,
        # other EMBEDDED facades) leaves the process-wide tracer/telemetry
        # exactly as the HOST configured them: a ?what_if= replay must not
        # rewrite the serving process's tracing settings, and bench
        # --scenarios must keep its own JSONL dump path.
        if configure_observability:
            from .utils import xla_telemetry
            from .utils.flight_recorder import FLIGHT
            from .utils.tracing import TRACER, watch_collector
            TRACER.configure(
                enabled=config.get_boolean("tracing.enabled"),
                max_traces=config.get_int("tracing.max.traces"),
                jsonl_path=config.get("tracing.jsonl.path") or None,
                jsonl_max_bytes=config.get_long("tracing.jsonl.max.bytes"),
                jsonl_max_files=config.get_int("tracing.jsonl.max.files"))
            watch_collector(config.get_boolean("tracing.enabled"))
            FLIGHT.configure(
                enabled=config.get_boolean("solver.flight.recorder.enabled"),
                max_passes=config.get_int("solver.flight.recorder.max.passes"),
                ring_rounds=config.get_int(
                    "solver.flight.recorder.ring.rounds"))
            xla_telemetry.install(
                enabled=config.get_boolean("xla.telemetry.enabled"))
        self._load_monitor = load_monitor or LoadMonitor(config, admin)
        self._executor = executor or Executor(
            admin,
            caps=ConcurrencyCaps(
                inter_broker_per_broker=config.get_int(
                    "num.concurrent.partition.movements.per.broker"),
                cluster_inter_broker=config.get_int(
                    "max.num.cluster.partition.movements"),
                intra_broker_per_broker=config.get_int(
                    "num.concurrent.intra.broker.partition.movements"),
                leadership_cluster=config.get_int(
                    "num.concurrent.leader.movements"),
            ),
            replication_throttle=config.get("default.replication.throttle"),
            on_sampling_mode_change=self._on_execution_sampling_change,
            adjuster_enabled=config.get_boolean("concurrency.adjuster.enabled"),
            adjuster_interval_s=config.get_long(
                "concurrency.adjuster.interval.ms") / 1000.0,
            adjuster_config=ConcurrencyAdjusterConfig.from_config(config),
            broker_metrics_supplier=lambda: (
                self._load_monitor.latest_broker_metrics(
                    [n for n, _f in ConcurrencyAdjusterConfig.LIMIT_METRICS])),
            inter_rate_alert_mb_s=config.get_double(
                "inter.broker.replica.movement.rate.alerting.threshold"),
            intra_rate_alert_mb_s=config.get_double(
                "intra.broker.replica.movement.rate.alerting.threshold"),
            retry_policy=self._retry_policy,
            dead_letter_attempts=config.get_int(
                "resilience.executor.dead.letter.attempts"))
        # ``optimizer`` injection is the fleet's solver-sharing seam
        # (fleet.registry): every cluster facade in a federated process
        # runs the SAME GoalOptimizer (and device/mesh), so bucketed
        # shapes land in one compiled-kernel set.
        self._optimizer = optimizer or GoalOptimizer(config)
        self._notifier = notifier or SelfHealingNotifier(
            config, now_ms=self._now_ms)
        # Heal ledger (round 16): the anomaly-lifecycle journal. One
        # PER FACADE — a fleet's clusters and an embedded digital twin
        # each journal on their own (possibly simulated) clock, the same
        # isolation discipline as configure_observability. Served as
        # GET /heals; the detector manager opens chains at detection and
        # the facade/scheduler/executor phases attach ambiently.
        from .utils.heal_ledger import HealLedger
        self.heal_ledger = HealLedger(
            enabled=config.get_boolean("heal.ledger.enabled"),
            max_chains=config.get_int("heal.ledger.max.chains"),
            max_phases=config.get_int("heal.ledger.max.phases"),
            clock=clock if clock is not None else time.time)
        # Request journeys + SLO engine (round 21): per-facade like the
        # heal ledger — a fleet's clusters and an embedded twin each
        # keep their own ring and their own objective windows, on their
        # own (possibly simulated) clock.
        from .serving.journey import JourneyLog
        self.journeys = JourneyLog(
            enabled=config.get_boolean("journey.enabled"),
            max_entries=config.get_int("journey.max.entries"),
            monotonic=clock if clock is not None else time.monotonic,
            clock=clock if clock is not None else time.time)
        from .utils.slo import SloRegistry
        self.slo = SloRegistry.from_config(
            config, clock=clock if clock is not None else time.time)
        self._anomaly_detector = AnomalyDetectorManager(
            config, self._notifier, facade=self, clock=self._clock,
            ledger=self.heal_ledger)
        self.maintenance_reader = self._configured_maintenance_reader(config)
        # Executor.java demotion/removal history consumed by the
        # exclude_recently_* request parameters and the ADMIN drop_* params;
        # initialized BEFORE detector wiring, which shares the live
        # history. Entries are TIMESTAMPED and expire after
        # *.history.retention.time.ms on the injected clock (reference
        # parity: Executor.java removalHistory/demotionHistory retention).
        # The digital-twin multi_az_failure scenario surfaced why a bare
        # set is wrong: a self-healed broker removal excluded the broker
        # from replica moves FOREVER, so after the failed AZ revived,
        # goal-violation detection reported "unfixable
        # ReplicaDistributionGoal" endlessly instead of rebalancing onto
        # the recovered brokers.
        self._removal_history: dict[int, int] = {}   # broker -> stamp ms
        self._demotion_history: dict[int, int] = {}
        self._removal_retention_ms = config.get_long(
            "removal.history.retention.time.ms")
        self._demotion_retention_ms = config.get_long(
            "demotion.history.retention.time.ms")
        # Guards ALL reads/writes of the two histories above (API threads
        # mutate them; the detection thread snapshots them). Taken INSIDE
        # the recently_*_brokers properties — callers must not hold it.
        self.excluded_sets_lock = threading.Lock()
        from .analyzer.plugins import (
            compile_excluded_topics_pattern, options_generator_from_config,
        )
        self._options_generator = options_generator_from_config(config)
        # Fallback for CUSTOM generators that lack merged_excluded_topics:
        # the config's never-move contract must hold regardless of which
        # generator is plugged in.
        self._excluded_topics_rx = compile_excluded_topics_pattern(config)
        # Predictive rebalancing (round 19): one forecast engine per
        # facade (the heal-ledger isolation discipline — a fleet's
        # clusters and an embedded twin each forecast their OWN
        # monitor's history). Off-means-off: with forecast.enabled=false
        # the engine and its detector cost one config read per tick and
        # serving behavior is byte-identical.
        from .forecast import ForecastEngine
        self.forecast_engine = ForecastEngine(config, self._load_monitor)
        # Pacer promotion flag: a predicted violation's precompute marks
        # this cluster due for an immediate paced cache fill regardless
        # of its cadence (fleet/scheduler.pace_once consumes + clears).
        self.predicted_precompute_pending = False
        self._wire_detectors()

        self._proposal_cache: tuple[int, float, OptimizerResult] | None = None
        self._proposal_lock = threading.Lock()
        # Serializes the EXPENSIVE proposal computation (the reference's
        # in-progress coordination, GoalOptimizer.java:152-203): the
        # precompute loop and an API request must not run two identical
        # optimization passes concurrently.
        self._proposal_compute_lock = threading.Lock()
        self._stop_precompute: threading.Event | None = None
        self._precompute_thread: threading.Thread | None = None
        self._started = False
        # Fleet seam (ROADMAP item 3c tail, round 15): when the registry
        # wires a nonzero width, goal-chain solves — self-healing fixes
        # and on-demand operations included — run through the BATCHED
        # megabatch kernels at occupancy 1 instead of compiling the solo
        # chain programs: one compiled program per bucket shape serves
        # precompute fills, fixes, and futures alike, and per-request
        # exclusion options ride the batched mask assembler.
        self.megabatch_solve_width = 0
        # Always-hot solver (round 18): the last ACCEPTED (assignment,
        # leader_slot) seeds the next default-chain solve — under
        # sustained drift most goals are already satisfied at the
        # previous target, so rounds-to-convergence collapses. The
        # quality fallback (_warm_quality_ok) re-solves cold whenever a
        # warm result falls below the sentry band, so warm starts can
        # never silently degrade proposals. One store per facade = one
        # per cluster (the heal-ledger isolation discipline).
        from .warmstart import WarmSeedStore
        self._warm_enabled = config.get_boolean("solver.warm.start.enabled")
        self._warm_band = config.get_double("solver.warm.start.quality.band")
        # Warm-band pre-check (round 19, ROADMAP 3a tail): score the
        # seed against the CURRENT loads in one batched stats program
        # before committing to the full warm chain — a seed that
        # drifted band-worse is skipped without paying attempt+fallback.
        self._warm_precheck = config.get_boolean(
            "solver.warm.start.precheck.enabled")
        self._warm_seeds = WarmSeedStore()
        # Pending warm context across the precompute seams (set by
        # precompute_inputs, consumed by store_precomputed on the SAME
        # worker thread — the megabatch runner's prepare/complete both
        # run inside one scheduler turn).
        self._tls_warm = threading.local()
        from .detector.provisioner import BasicProvisioner
        self.provisioner = BasicProvisioner()

    # -- wiring ------------------------------------------------------------
    @staticmethod
    def _configured_maintenance_reader(config: CruiseControlConfig):
        """maintenance.event.reader.class plugin resolution
        (AnomalyDetectorConfig.MAINTENANCE_EVENT_READER_CLASS_CONFIG). The
        default in-memory reader takes no arguments; custom readers are
        instantiated bare and may read their own config via attributes."""
        from .config.abstract_config import resolve_class
        from .detector.maintenance_serde import TopicMaintenanceEventReader
        spec = config.get("maintenance.event.reader.class")
        cls = resolve_class(spec) if isinstance(spec, str) else spec
        if cls is InMemoryMaintenanceEventReader or cls is None:
            return InMemoryMaintenanceEventReader()
        if cls is TopicMaintenanceEventReader:
            # Live Kafka binding (MaintenanceEventTopicReader.java:350):
            # consume plans an ops pipeline produces to
            # ``maintenance.event.topic`` over the wire client.
            bootstrap = config.get("bootstrap.servers")
            if not bootstrap:
                LOG.warning("maintenance.event.reader.class is the topic "
                            "reader but bootstrap.servers is unset; using "
                            "the in-memory reader")
                return InMemoryMaintenanceEventReader()
            from .kafka.transport import KafkaMetricsTransport
            transport = KafkaMetricsTransport(
                bootstrap, topic=config.get("maintenance.event.topic"),
                num_partitions=1)
            return TopicMaintenanceEventReader(transport)
        try:
            return cls()
        except TypeError:
            # Reader needs deployment wiring (e.g. a Kafka transport):
            # leave construction to the embedder, fall back in-memory.
            LOG.warning("maintenance reader %s needs explicit construction; "
                        "using the in-memory reader", spec)
            return InMemoryMaintenanceEventReader()

    def _wire_detectors(self) -> None:
        cfg, report = self._config, self._anomaly_detector.report
        interval = cfg.get_long("anomaly.detection.interval.ms")
        mgr = self._anomaly_detector
        self.goal_violation_detector = GoalViolationDetector(
            cfg, self._load_monitor, self._optimizer, report)

        # Detection excludes the same recently-removed/demoted brokers the
        # user-facing operations do — the history properties snapshot
        # under the facade's lock, so the detection thread never iterates
        # a dict an API thread is mutating.
        def _excluded_snapshot():
            return (tuple(sorted(self.recently_demoted_brokers)),
                    tuple(sorted(self.recently_removed_brokers)))

        self.goal_violation_detector.excluded_brokers_supplier = \
            _excluded_snapshot
        mgr.add_detector(self.goal_violation_detector, interval)
        # Predictive twin of the goal-violation detector (round 19):
        # scores the forecaster's projected model through the same
        # batched goal-stats program and reports predicted violations as
        # first-class anomalies. Registered unconditionally — a disabled
        # engine makes its tick a single config read (the noop-overhead
        # guard family).
        from .detector.predictive import PredictiveViolationDetector
        self.predictive_detector = PredictiveViolationDetector(
            cfg, self.forecast_engine, self._optimizer, report,
            ledger=self.heal_ledger,
            clock=self._clock if self._clock is not None else time.time)
        self.predictive_detector.excluded_brokers_supplier = \
            _excluded_snapshot
        mgr.add_detector(self.predictive_detector, interval)
        # SLO burn detector (round 21): evaluates the facade's objective
        # registry's multi-window burn rule and raises SLO_BURN anomalies
        # through the same manager/ledger path. Registered
        # unconditionally — a disabled registry makes its tick one
        # attribute read (the noop-overhead guard family).
        from .detector.slo_burn import SloBurnDetector
        self.slo_burn_detector = SloBurnDetector(
            self.slo, report, ledger=self.heal_ledger)
        mgr.add_detector(self.slo_burn_detector, interval)
        mgr.add_detector(BrokerFailureDetector(
            self._admin, report,
            failed_brokers_file_path=cfg.get("failed.brokers.file.path"),
            now_ms=self._now_ms),
            interval)
        mgr.add_detector(DiskFailureDetector(self._admin, report), interval)
        mgr.add_detector(MetricAnomalyDetector(
            self._load_monitor.broker_aggregator, report, config=cfg),
            cfg.get("metric.anomaly.detection.interval.ms") or interval)
        target_rf = cfg.get("self.healing.target.topic.replication.factor")
        if target_rf:
            mgr.add_detector(TopicAnomalyDetector(
                self._admin, report, cfg, desired_rf=int(target_rf),
                topic_pattern=cfg.get("topic.anomaly.topic.pattern")), interval)
        idem_retention = cfg.get_long("maintenance.event.idempotence."
                                      "retention.ms")
        if not cfg.get_boolean("maintenance.event.enable.idempotence"):
            idem_retention = 0  # zero-retention cache never matches
        mgr.add_detector(MaintenanceEventDetector(
            self.maintenance_reader, report,
            idempotence_retention_ms=idem_retention,
            now_ms=self._now_ms), interval)

    def _on_execution_sampling_change(self, executing: bool) -> None:
        """Executor.java:1408-1424 — reduce sampling scope during moves and
        RESTORE the prior mode afterwards (a user-initiated pause must
        survive an execution that completes meanwhile)."""
        runner = self._load_monitor.task_runner
        try:
            if executing:
                self._sampling_mode_before_execution = runner.sampling_mode
                runner.set_mode(SamplingMode.ONGOING_EXECUTION,
                                reason="proposal execution")
            elif runner.sampling_mode is SamplingMode.ONGOING_EXECUTION:
                restore = getattr(self, "_sampling_mode_before_execution",
                                  SamplingMode.RUNNING)
                if restore is SamplingMode.ONGOING_EXECUTION:
                    restore = SamplingMode.RUNNING
                runner.set_mode(restore, reason="execution finished")
        except Exception:
            LOG.exception("could not flip sampling mode")

    # -- lifecycle (KafkaCruiseControl.startUp:221) ------------------------
    def start_up(self, block_on_load: bool = True,
                 start_precompute: bool = True) -> None:
        """``start_precompute=False`` leaves the facade's own proposal
        precompute loop off — fleet deployments route precompute through
        the FleetScheduler's pacer instead (one device, many clusters:
        per-facade loops would contend for it unscheduled)."""
        # Always-hot solver (round 18): point XLA's persistent compile
        # cache at the configured dir (serving processes get it without
        # wrapper scripts — idempotent, safest before the first solve
        # jit), then prewarm the known bucket-shape set in a background
        # thread so a fresh replica serves its first rebalance in
        # seconds. Both no-op when their config switches are off; the
        # prewarm manager is per-optimizer, so fleet clusters sharing
        # one solver prewarm exactly once.
        from .warmstart import configure_compile_cache, ensure_prewarm
        configure_compile_cache(self._config)
        ensure_prewarm(self._optimizer, self._config)
        self._load_monitor.start_up(block_on_load=block_on_load)
        self._anomaly_detector.start_detection()
        self._started = True
        if start_precompute and (self._precompute_thread is None
                                 or not self._precompute_thread.is_alive()):
            self._stop_precompute = threading.Event()
            self._precompute_thread = threading.Thread(
                target=self._proposal_precompute_loop, daemon=True,
                name="proposal-precompute")
            self._precompute_thread.start()

    def _proposal_precompute_loop(self) -> None:
        """GoalOptimizer.run (GoalOptimizer.java:152-203): keep the cached
        proposals fresh in the background so a PROPOSALS/REBALANCE request
        hits a warm cache. Refresh-ahead: an entry with less than one
        wake interval of budget left is recomputed NOW, so requests never
        find the cache expired between wakes. Tolerates a not-ready load
        model."""
        expiration_s = self._config.get_long("proposal.expiration.ms") / 1000.0
        interval_s = max(1.0, expiration_s / 2.0)
        # Refresh-ahead headroom: 1.5 wake intervals so an entry never
        # expires between one wake deciding "fresh" and the next wake's
        # recompute finishing — clamped for pathologically short budgets
        # (expiration < interval), where some inline computes are what the
        # operator's config demands.
        margin_s = min(1.5 * interval_s, 0.75 * expiration_s)
        stop = self._stop_precompute
        while not stop.wait(interval_s):
            try:
                gen = self._load_monitor.model_generation
                if self._cached_proposals_fresh(gen, margin_s=margin_s):
                    continue
                self.proposals(_freshness_margin_s=margin_s)
                from .utils.sensors import SENSORS
                SENSORS.count("analyzer_proposal_precompute_runs")
            except Exception:  # noqa: BLE001 — model may not be ready yet
                LOG.debug("proposal precompute skipped", exc_info=True)

    def shutdown(self) -> None:
        if self._stop_precompute is not None:
            self._stop_precompute.set()
        if self._precompute_thread is not None \
                and self._precompute_thread.is_alive():
            # Join BEFORE tearing down the monitor/executor: an in-flight
            # precompute must not race a half-shut-down load monitor.
            self._precompute_thread.join(timeout=30.0)
        # Forget the thread either way — a later start_up() must spawn a
        # fresh loop even if this join timed out (the old thread exits on
        # its own already-set stop event).
        self._precompute_thread = None
        self._anomaly_detector.shutdown()
        self._executor.stop_execution()
        self._load_monitor.shutdown()
        self._started = False

    # -- collaborators -----------------------------------------------------
    @property
    def config(self) -> CruiseControlConfig:
        return self._config

    @property
    def load_monitor(self) -> LoadMonitor:
        return self._load_monitor

    @property
    def executor(self) -> Executor:
        return self._executor

    @property
    def optimizer(self) -> GoalOptimizer:
        return self._optimizer

    @property
    def anomaly_detector(self) -> AnomalyDetectorManager:
        return self._anomaly_detector

    # -- model helpers -----------------------------------------------------
    def _model(self, requirements: ModelCompletenessRequirements | None = None,
               allow_capacity_estimation: bool = True,
               ) -> tuple[ClusterTensors, ClusterMeta]:
        return self._load_monitor.cluster_model(
            requirements, allow_capacity_estimation=allow_capacity_estimation)

    def _chain_and_model(self, goals, use_ready_default_goals: bool,
                         data_from: str | None,
                         allow_capacity_estimation: bool):
        """Shared preamble of every goal-based operation: resolve the goal
        chain (ready-filtered when asked), then build the model under the
        chain's data_from-derived completeness requirements."""
        chain = self._goal_chain(goals, use_ready_default_goals)
        state, meta = self._model(
            self._requirements_for(data_from, chain),
            allow_capacity_estimation=allow_capacity_estimation)
        # Heal ledger: a fix operation's model build is a phase on its
        # correlation chain (NO_HEAL no-op outside a heal scope).
        from .utils.heal_ledger import current_heal
        current_heal().phase("model_built",
                             brokers=len(meta.broker_ids),
                             partitions=len(meta.partition_index))
        return chain, state, meta

    def _requirements_for(self, data_from: str | None, chain,
                          ) -> ModelCompletenessRequirements | None:
        """data_from request param → model completeness requirements
        (GoalBasedOptimizationParameters.getRequirements:93 merged weaker
        with the chain's own requirements): valid_windows weakens the
        window count to 1; valid_partitions keeps the chain's window
        requirement but drops the partition-coverage floor."""
        if not data_from:
            return None
        df = data_from.lower()
        nw = self._config.get_int("num.partition.metrics.windows")
        ratio = self._config.get_double("min.valid.partition.ratio")
        if df == "valid_windows":
            return ModelCompletenessRequirements(1, ratio)
        if df == "valid_partitions":
            goal_windows = max(
                (g.completeness_requirements(nw, ratio)[0] for g in chain),
                default=1)
            return ModelCompletenessRequirements(goal_windows, 0.0)
        raise ValueError(f"unknown data_from {data_from!r} "
                         "(valid_windows | valid_partitions)")

    def _admin_call(self, op: str, fn):
        """Admin-backend read under the facade's retry policy (bare when
        resilience is disabled)."""
        from .utils.resilience import call_with_resilience
        return call_with_resilience(op, fn, policy=self._retry_policy)

    def alive_brokers(self) -> set[int]:
        """Live broker set (anomaly re-validation + dashboards)."""
        return self._admin_call("admin.alive_brokers",
                                self._admin.alive_brokers)

    def ready_for_self_healing(self) -> bool:
        """Completeness gate consulted before anomaly fixes
        (AnomalyDetectorManager.java:513)."""
        try:
            state = self._load_monitor.state()
        except Exception:
            return False
        return state.num_valid_windows >= 1

    def _broker_indices(self, meta: ClusterMeta, broker_ids: Sequence[int],
                        ) -> list[int]:
        idx = {bid: i for i, bid in enumerate(meta.broker_ids)}
        missing = [b for b in broker_ids if b not in idx]
        if missing:
            raise ValueError(f"brokers not in cluster model: {missing}")
        return [idx[b] for b in broker_ids]

    def _mark_brokers(self, state: ClusterTensors, meta: ClusterMeta,
                      broker_ids: Sequence[int], code: BrokerState,
                      ) -> ClusterTensors:
        for i in self._broker_indices(meta, broker_ids):
            state = set_broker_state(state, np.int32(i), int(code))
        return state

    def _goal_chain(self, goals: Sequence[str] | None,
                    use_ready_default_goals: bool = False):
        names = list(goals) if goals else None
        chain = goals_by_priority(self._config, names)
        if names is None and use_ready_default_goals:
            ready = self.ready_goals(chain)
            if not ready:
                raise ValueError(
                    "use_ready_default_goals: no default goal's model-"
                    "completeness requirement is currently met")
            chain = ready
        return chain

    def ready_goals(self, chain=None, monitor_state=None) -> list:
        """The subset of ``chain`` (default: the configured goal chain)
        whose model-completeness requirements the monitor currently meets
        (Goal.clusterModelCompletenessRequirements × the monitor's valid
        windows/coverage; the ``use_ready_default_goals`` request param and
        the STATE AnalyzerState.readyGoals field). Pass ``monitor_state``
        when one is already computed — LoadMonitor.state() walks the whole
        partition metadata, too expensive to repeat per request."""
        if chain is None:
            chain = goals_by_priority(self._config)
        try:
            ms = monitor_state or self._load_monitor.state()
            windows, coverage = ms.num_valid_windows, \
                ms.monitored_partitions_percentage
        except Exception:  # noqa: BLE001 — monitor not started yet
            return []
        num_windows = self._config.get_int("num.partition.metrics.windows")
        min_ratio = self._config.get_double("min.valid.partition.ratio")
        out = []
        for g in chain:
            need_w, need_ratio = g.completeness_requirements(
                num_windows, min_ratio)
            if windows >= need_w and coverage >= need_ratio:
                out.append(g)
        return out

    @contextmanager
    def execution_overrides(self,
                            replica_movement_strategies: Sequence[str] = (),
                            concurrency: Mapping[str, int] | None = None,
                            extras: Mapping[str, Any] | None = None):
        """Per-request execution overrides (ParameterUtils), scoped to the
        operation run inside the ``with`` block. Carried in a ContextVar:
        each request thread (ThreadingHTTPServer / user-task pool) sees only
        ITS overrides — concurrent requests cannot clobber or clear each
        other's — and exit always restores, so a dry run, zero-proposal
        result, or optimizer exception never leaks them.

        ``extras``: progress_check_interval_s (float),
        replication_throttle (int rate override),
        throttle_excluded_brokers (broker ids to leave unthrottled),
        stop_ongoing_execution (bool: gracefully stop + wait before this
        execution, RunnableUtils.maybeStopOngoingExecutionToModifyAndWait)."""
        strategy = None
        if replica_movement_strategies:
            from .executor.strategy import strategy_chain
            strategy = strategy_chain(list(replica_movement_strategies))
        token = _EXECUTION_OVERRIDES.set(
            (strategy, dict(concurrency or {}), dict(extras or {})))
        try:
            yield
        finally:
            _EXECUTION_OVERRIDES.reset(token)

    def _maybe_execute(self, result: OptimizerResult, dryrun: bool,
                       operation: str, reason: str, uuid: str = "") -> bool:
        if dryrun or not result.proposals:
            return False
        OPERATION_LOG.info("%s executing %d proposals (reason: %s)",
                           operation, len(result.proposals), reason)
        strategy, concurrency, extras = _EXECUTION_OVERRIDES.get()
        if extras.get("stop_ongoing_execution") \
                and self._executor.has_ongoing_execution():
            # maybeStopOngoingExecutionToModifyAndWait (RunnableUtils.java):
            # gracefully stop the current execution, wait for it to wind
            # down, then start this one.
            OPERATION_LOG.info("%s stopping ongoing execution first", operation)
            self._executor.stop_execution()
            deadline = time.time() + 60.0
            while self._executor.has_ongoing_execution() \
                    and time.time() < deadline:
                time.sleep(0.05)
        self._executor.execute_proposals(
            result.proposals, uuid=uuid, strategy=strategy,
            concurrency_overrides=concurrency or None,
            progress_check_interval_s=extras.get("progress_check_interval_s"),
            replication_throttle=extras.get("replication_throttle"),
            throttle_excluded_brokers=extras.get(
                "throttle_excluded_brokers", ()))
        return True

    def _config_excluded_topics(self, topic_names,
                                explicit=()) -> tuple[str, ...]:
        """Explicit exclusions ∪ config-regex matches. Delegates to the
        generator's single merge implementation; a custom generator
        without the helper falls back to the facade's own compiled
        pattern — the config's never-move contract must hold regardless
        of which generator is plugged in."""
        merge = getattr(self._options_generator, "merged_excluded_topics",
                        None)
        if merge is not None:
            return merge(topic_names, explicit)
        merged = set(explicit)
        if self._excluded_topics_rx is not None:
            merged.update(t for t in topic_names
                          if self._excluded_topics_rx.fullmatch(t))
        return tuple(sorted(merged))

    def _with_config_excluded_topics(self, meta,
                                     options: OptimizationOptions,
                                     ) -> OptimizationOptions:
        """Merge ``topics.excluded.from.partition.movement`` matches into
        the options of EVERY operation that may move partitions — the
        config contract ('never moved') must hold on the execution paths,
        not just the dryrun/detection previews."""
        merged = self._config_excluded_topics(meta.topic_names,
                                              options.excluded_topics)
        if merged == options.excluded_topics:
            return options
        import dataclasses as _dc
        return _dc.replace(options, excluded_topics=merged)

    def _movable_partition_mask(self, state, meta):
        """[P] bool (True = movable) from the merged excluded topics, or
        None when nothing is excluded — the intra-broker disk kernels'
        view of the same never-move contract."""
        excluded = set(self._config_excluded_topics(meta.topic_names))
        if not excluded:
            return None
        import jax.numpy as jnp
        bad_ids = np.asarray(
            [i for i, t in enumerate(meta.topic_names) if t in excluded])
        mask = ~np.isin(np.asarray(state.topic), bad_ids)
        return jnp.asarray(mask)

    # -- operations (the runnables) ----------------------------------------
    def _cached_proposals_fresh(self, gen: int, margin_s: float = 0.0):
        """The ONE validCachedProposal predicate
        (GoalOptimizer.validCachedProposal:232): cache entry if it matches
        the model generation and has more than ``margin_s`` of its
        expiration budget left, else None. The precompute loop passes its
        own interval as margin (refresh-ahead: the cache must never be
        found expired by a request between two wakes)."""
        expiration_s = self._config.get_long("proposal.expiration.ms") / 1000.0
        with self._proposal_lock:
            cached = self._proposal_cache
        if cached is not None and cached[0] == gen \
                and time.time() - cached[1] < expiration_s - margin_s:
            return cached
        return None

    @_traced_op("proposals")
    def proposals(self, goals: Sequence[str] | None = None,
                  ignore_proposal_cache: bool = False,
                  use_ready_default_goals: bool = False,
                  fast_mode: bool = False,
                  data_from: str | None = None,
                  allow_capacity_estimation: bool = True,
                  _freshness_margin_s: float = 0.0) -> OperationResult:
        """ProposalsRunnable — cached when the model generation and the
        expiration budget allow (GoalOptimizer.validCachedProposal:232).
        The expensive computation is serialized: a loser of the compute
        lock re-checks the cache so two callers never run the identical
        optimization concurrently (``_freshness_margin_s`` is the
        precompute loop's refresh-ahead knob)."""
        # A ready-filtered chain is a custom chain for caching purposes:
        # the cache holds full-default-chain results; a data_from override
        # is a weaker-requirement model (hasWeakerRequirement,
        # KafkaCruiseControl.ignoreProposalCache:565-583).
        # fast_mode results are quality-degraded: they must neither be
        # served from nor stored into the default-chain cache.
        use_cache = goals is None and not ignore_proposal_cache \
            and not use_ready_default_goals and data_from is None \
            and not fast_mode

        def cached_result():
            # Generation read fresh at check time: a stale pre-lock value
            # would mislabel the cache entry and defeat the dedup.
            gen = self._load_monitor.model_generation
            cached = self._cached_proposals_fresh(gen, _freshness_margin_s)
            if cached is None:
                return None
            return OperationResult(
                "proposals", dryrun=True, optimizer_result=cached[2],
                proposals=cached[2].proposals, reason="cached")

        if use_cache:
            out = cached_result()
            if out is not None:
                return out

        def compute():
            chain, state, meta = self._chain_and_model(
                goals, use_ready_default_goals, data_from,
                allow_capacity_estimation)
            options = self._options_generator.for_cached_proposal_calculation(
                meta.topic_names, ())
            if fast_mode:
                options = dataclasses.replace(options, fast_mode=True)
            # Through the shared solve seam (round 18): proposal
            # computes get warm seeding + the quality fallback, and on a
            # fleet-wired facade ride the same batched kernels as fixes
            # (occupancy-1 parity is pinned in test_fleet). Only the
            # CANONICAL default-chain compute is warm-eligible — custom
            # chains / weakened models are incomparable solve classes.
            _final, result = self._optimize(
                state, meta, chain, options,
                warm_eligible=goals is None and not use_ready_default_goals
                and data_from is None and not fast_mode)
            return result

        if goals is not None or use_ready_default_goals or fast_mode \
                or data_from is not None:
            # Custom-goal / fast-mode / weakened-model requests are never
            # cached (neither served nor STORED — a degraded result must
            # not become the canonical default-chain cache entry) and share
            # nothing with the default-chain computation — no reason to
            # serialize them behind a long-running precompute pass.
            result = compute()
        else:
            # Graceful degradation (round 9): when the model build /
            # optimization fails, serve the LAST GOOD cached proposal set
            # — any age, any generation — clearly marked stale=true,
            # instead of a hard error. Repeated failures trip the model
            # breaker (keyed by the ambient cluster label), and an OPEN
            # breaker fails fast with BreakerOpenError, which the API
            # layer renders as 503 + Retry-After.
            from .utils.sensors import current_cluster_label
            breaker = self._model_breaker
            target = current_cluster_label() or "default"
            if breaker is not None:
                breaker.guard(target)
            with self._proposal_compute_lock:
                if use_cache:
                    out = cached_result()  # a concurrent compute finished
                    if out is not None:
                        return out
                gen = self._load_monitor.model_generation
                try:
                    result = compute()
                except NotEnoughValidWindowsError:
                    # Model not ready (warmup) is not a dependency fault:
                    # feeding it to the breaker would trip 503s that
                    # outlive the warmup and mask the real diagnostic.
                    raise
                except Exception as e:
                    if breaker is not None:
                        breaker.record_failure(target)
                    with self._proposal_lock:
                        cached = self._proposal_cache
                    if cached is None or ignore_proposal_cache:
                        # No fallback to serve — or the caller EXPLICITLY
                        # refused cached answers (ignore_proposal_cache):
                        # serving stale would override their contract.
                        raise
                    LOG.warning("proposal computation failed; serving the "
                                "last good cached proposals as STALE",
                                exc_info=True)
                    # staleness_s: age of the entry being served degraded
                    # (cache stamps are wall time regardless of the sim
                    # clock — the cache itself lives on wall time). The
                    # SLO scorer and clients both read it: degraded
                    # serving is only an SLO if its DURATION is visible.
                    staleness_s = round(time.time() - cached[1], 3)
                    from .utils.sensors import SENSORS
                    SENSORS.count("proposals_stale_served")
                    SENSORS.gauge("proposals_stale_age_seconds", staleness_s)
                    # Stale-serving window correlation: any heal in
                    # flight carries the evidence that serving degraded
                    # during its window.
                    self.heal_ledger.note_stale(staleness_s)
                    # Staleness-age SLO objective: a degraded serve is
                    # one classified event (bad past the threshold).
                    self.slo.observe_staleness(staleness_s)
                    from .utils.tracing import TRACER
                    TRACER.annotate(stale=True, staleness_s=staleness_s)
                    return OperationResult(
                        "proposals", dryrun=True, optimizer_result=cached[2],
                        proposals=cached[2].proposals,
                        reason="stale cache fallback "
                               f"({type(e).__name__}: {e})",
                        extra={"stale": True, "staleness_s": staleness_s})
                if breaker is not None:
                    breaker.record_success(target)
                with self._proposal_lock:
                    self._proposal_cache = (gen, time.time(), result)
        return OperationResult("proposals", dryrun=True,
                               optimizer_result=result,
                               proposals=result.proposals)

    def _optimize(self, state, meta, chain, options: OptimizationOptions,
                  warm_eligible: bool = False,
                  ) -> tuple[Any, OptimizerResult]:
        """The single-cluster solve seam for the goal-chain operations.
        With a fleet-wired ``megabatch_solve_width`` the solve routes
        through ``optimizations_megabatch`` at occupancy 1 — the same
        compiled batched program (and the same per-cluster exclusion-mask
        assembly) the fleet's coalesced precompute fills use, so fix and
        on-demand solves pay zero extra compilations on a megabatching
        deployment. Per-cluster failures surface as the exact exception
        a serial solve would raise. Fast mode and mesh solvers keep the
        serial path (the megabatch supports neither), and so does the
        deficit-sizing regime: the batched path structurally disables
        deficit-aware count-goal sizing, and a fleet-wired deployment
        must not return different proposals than a standalone one for
        the same cluster state."""
        from .serving.journey import current_journey
        from .utils.heal_ledger import current_heal
        from .utils.sensors import SENSORS
        heal = current_heal()
        jny = current_journey()
        jny_t0 = jny.now()
        width = self.megabatch_solve_width
        batched = bool(width and not options.fast_mode
                       and self._optimizer.mesh is None
                       and not self._optimizer.deficit_sizing_active(
                           state.num_brokers))
        # Warm start (round 18): seed the search from the last accepted
        # target when one is valid for this model's index space. The
        # solve still diffs against the TRUE current ``state`` (the
        # optimizer's initial_state seam), so proposals always encode
        # moves from reality. ``warm_eligible`` scopes seeding to the
        # CANONICAL default-chain solve class (proposals/precompute):
        # broker-scoped operations, custom chains, and per-request
        # exclusion sets are incomparable solve classes — their results
        # must neither consume nor become seeds, or the single-slot
        # store's quality reference cross-contaminates (a drained
        # remove_brokers result as the gate reference would let a
        # degraded warm default solve pass; the default reference would
        # spuriously fail legitimate constrained solves).
        warm = warm_eligible and self._warm_enabled \
            and not options.fast_mode
        warm_seed = None
        warm_state = state
        if warm:
            from .warmstart import apply_seed
            warm_seed = self._warm_seeds.match(state, meta)
            if warm_seed is not None:
                warm_state = apply_seed(state, warm_seed)
            if warm_seed is not None and self._warm_precheck:
                # Warm-band pre-check (ROADMAP 3a tail): score the seed
                # against the CURRENT (drifted) loads in ONE batched
                # goal-stats program. A seed whose entry picture already
                # breaches the sentry band — a violated goal its
                # accepted solve did not have (the band rule collapses
                # to that on the 0-100 scale) — would fail the quality
                # gate after the full chain anyway; skipping here saves
                # the doomed attempt+fallback double solve. SERVED
                # results stay byte-equal: the skip path runs exactly
                # the cold solve the fallback would have (pinned in
                # tests/test_warmstart.py).
                from .warmstart import seed_band_ok
                try:
                    pre_chain, pv, _po, _poff = \
                        self._optimizer.goal_entry_stats(
                            warm_state, meta, chain, options)
                    pre_violated = {g.name for g, v in zip(pre_chain, pv)
                                    if float(v) > 1e-6}
                    pre_bal = self._optimizer.balancedness_of(
                        pre_chain, pre_violated)
                except Exception:  # noqa: BLE001 — pre-check is an
                    # optimization; a failure falls through to the
                    # gate-protected warm attempt
                    LOG.debug("warm pre-check failed; attempting warm",
                              exc_info=True)
                else:
                    if not seed_band_ok(pre_bal, pre_violated, warm_seed,
                                        self._warm_band):
                        LOG.info(
                            "warm seed band-worse on entry (balancedness "
                            "%.3f vs accepted %.3f, violated %s); "
                            "skipping the warm attempt", pre_bal,
                            warm_seed.balancedness_after,
                            sorted(pre_violated))
                        SENSORS.count("solver_warm_precheck_skips")
                        self._warm_seeds.clear()
                        warm_seed = None
                        warm_state = state
            if warm_seed is not None:
                # Counted AFTER the pre-check: a skipped seed is a cold
                # solve, and solver_warm_seeded must mean "this solve
                # actually rode a warm seed" (the warm-adoption ruler).
                SENSORS.count("solver_warm_seeded")
        # Heal-correlated solves link the flight recorder's pass ids:
        # the chain's solve_completed phase names the passSeq values that
        # resolve in GET /solver (best-effort window — a concurrent
        # solve from another thread can land inside it, so the ids are
        # filtered by this solve's ambient cluster label).
        marker = None
        if heal.recording or jny.recording:
            from .utils.flight_recorder import FLIGHT
            if FLIGHT.enabled:
                marker = FLIGHT.marker()
        if heal.recording:
            heal.phase("solve_dispatched",
                       path="megabatch" if batched else "serial",
                       warmStart=warm_seed is not None)

        def run(solve_state, initial):
            if batched:
                from .utils.sensors import current_cluster_label
                cid = current_cluster_label() or "default"
                out = self._optimizer.optimizations_megabatch(
                    [(solve_state, meta, cid, options, initial)],
                    goals=list(chain), width=width)
                r = out[0]
                if isinstance(r, Exception):
                    raise r
                return r
            return self._optimizer.optimizations(
                solve_state, meta, chain, options, initial_state=initial)

        warm_fallback = False
        if warm_seed is not None:
            try:
                res = run(warm_state, state)
            except Exception:  # noqa: BLE001 — warm failure falls back cold
                LOG.warning("warm-seeded solve failed; re-solving cold",
                            exc_info=True)
                res = None
            if res is not None and not self._warm_quality_ok(res[1],
                                                             warm_seed):
                LOG.info(
                    "warm-seeded solve below the sentry band "
                    "(balancedness %.3f vs accepted %.3f, violated %s); "
                    "re-solving cold", res[1].balancedness_after,
                    warm_seed.balancedness_after,
                    res[1].violated_goals_after)
                res = None
            if res is None:
                # The fallback contract: a warm start may cost an extra
                # solve, but can never degrade what gets served.
                warm_fallback = True
                SENSORS.count("solver_warm_fallbacks")
                self._warm_seeds.clear()
                res = run(state, None)
        else:
            res = run(state, None)
        if warm:
            self._warm_store(res[0], meta, res[1], seed=warm_seed,
                             warm_accepted=warm_seed is not None
                             and not warm_fallback)
        pass_seqs = None
        if marker is not None:
            from .utils.flight_recorder import FLIGHT
            from .utils.sensors import current_cluster_label
            # The batched path records its flight pass under the
            # same "default" fallback it solved under — the filter
            # label must match or the /solver link comes back empty
            # exactly on the megabatch path.
            label = current_cluster_label() \
                or ("default" if batched else None)
            pass_seqs = [
                p["passSeq"] for p in FLIGHT.passes_since(marker)
                if p.get("cluster") == label]
        if jny.recording:
            # The request's solve segment, linked to the same flight
            # recorder passes and (when ambient) the heal chain the
            # solve ran on account of.
            attrs: dict = {"path": "megabatch" if batched else "serial",
                           "warmStart": warm_seed is not None}
            if warm_fallback:
                attrs["warmFallback"] = True
            if pass_seqs:
                attrs["passSeqs"] = pass_seqs
            if heal.recording:
                attrs["healChainId"] = heal.chain_id
            jny.add("solve", jny.now() - jny_t0, **attrs)
        if heal.recording:
            detail: dict = {}
            if pass_seqs is not None:
                detail["passSeqs"] = pass_seqs
            if batched:
                # The fleet-wired solve rode the batched kernels at
                # occupancy 1 (one compiled program per bucket shape
                # serves fixes and precomputes alike).
                detail["batchWidth"] = width
            # Warm-path adoption attrs (round 18): GET /heals can
            # distinguish warm from cold heals, and the fingerprint
            # skip's dispatch savings are attributable per chain.
            detail["warmStart"] = warm_seed is not None
            if warm_fallback:
                detail["warmFallback"] = True
            skipped = self._optimizer.thread_dispatch_stats().get(
                "goals_skipped", 0)
            if skipped:
                detail["goalsSkipped"] = skipped
            heal.phase("solve_completed", **detail)
            heal.phase("proposal_ready", numProposals=len(res[1].proposals))
        return res

    def _warm_quality_ok(self, result, seed) -> bool:
        """The warm-start sentry band: no violated goal the seed's own
        accepted solve did not have, and balancedness within
        ``solver.warm.start.quality.band`` of the seed's (the shared
        warmstart.warm_quality_ok predicate — bench measures SERVED
        semantics with the same function)."""
        from .warmstart import warm_quality_ok
        return warm_quality_ok(result, seed.balancedness_after,
                               seed.violated_after, self._warm_band)

    def _warm_store(self, final_state, meta, result, seed=None,
                    warm_accepted: bool = False) -> None:
        """Store an accepted solve as the next seed. ``warm_accepted``
        marks a gate-passing WARM result: its reference is sticky —
        max(seed reference, own balancedness) with its own (gate-bounded)
        violated set — so only cold solves re-anchor the gate (see
        WarmSeedStore.store). ONE implementation for the serial solve
        and the fleet-precompute write-back, so the never-degrade
        contract cannot diverge between the two paths."""
        if warm_accepted and seed is not None:
            self._warm_seeds.store(final_state, meta, result, reference=(
                max(seed.balancedness_after, result.balancedness_after),
                frozenset(result.violated_goals_after)))
        else:
            self._warm_seeds.store(final_state, meta, result)

    # -- megabatch precompute seams (fleet.megabatch) ----------------------
    def precompute_inputs(self):
        """(chain, state, meta, options, generation, initial_state) for a
        DEFAULT-chain cached-proposal computation — the megabatch
        runner's model-build seam. Mirrors ``proposals()``'s compute
        preamble exactly (same chain resolution, model requirements, and
        options generator), so a batched precompute stores a cache entry
        indistinguishable from a solo one. The generation is read BEFORE
        the build, like the serial path, so a mid-build metadata bump
        invalidates the entry rather than mislabeling it.

        Warm starts (round 18): with a valid seed, ``state`` is the
        warm-seeded search start and ``initial_state`` the TRUE current
        model the batched solve must diff against; the pending seed is
        held for ``store_precomputed``'s quality gate on the same worker
        thread. ``initial_state`` is None on cold computes."""
        gen = self._load_monitor.model_generation
        chain, state, meta = self._chain_and_model(None, False, None, True)
        options = self._options_generator.for_cached_proposal_calculation(
            meta.topic_names, ())
        initial = None
        self._tls_warm.ctx = None
        if self._warm_enabled:
            from .utils.sensors import SENSORS
            from .warmstart import apply_seed
            seed = self._warm_seeds.match(state, meta)
            self._tls_warm.ctx = (seed, state, meta, chain, options)
            if seed is not None:
                SENSORS.count("solver_warm_seeded")
                initial = state
                state = apply_seed(state, seed)
        return chain, state, meta, options, gen, initial

    def store_precomputed(self, generation: int, result,
                          final_state=None) -> None:
        """Write an externally computed default-chain OptimizerResult
        into the proposal cache (the megabatch runner's write-back seam —
        the batched twin of the cache store at the end of
        ``proposals()``). A warm-seeded precompute that falls below the
        sentry band is NOT stored: the seed is dropped, the fallback
        counted, and the cluster re-solved cold inline (on the runner's
        worker thread) — the same never-degrade contract as the serial
        warm path."""
        ctx = getattr(self._tls_warm, "ctx", None)
        self._tls_warm.ctx = None
        if ctx is not None:
            seed, initial, meta, chain, options = ctx
            warm_ok = seed is not None
            if seed is not None and not self._warm_quality_ok(result, seed):
                from .utils.sensors import SENSORS
                warm_ok = False
                SENSORS.count("solver_warm_fallbacks")
                self._warm_seeds.clear()
                LOG.info("warm-seeded precompute below the sentry band; "
                         "re-solving cold")
                final_state, result = self._optimizer.optimizations(
                    initial, meta, chain, options)
            if final_state is not None:
                self._warm_store(final_state, meta, result, seed=seed,
                                 warm_accepted=warm_ok)
        with self._proposal_lock:
            self._proposal_cache = (generation, time.time(), result)

    # -- predictive rebalancing (round 19) ---------------------------------
    def fix_predicted_violation(self, execute: bool = False,
                                reason: str = "",
                                anomaly_id: str | None = None) -> bool:
        """The PREDICTED_GOAL_VIOLATION fix: solve the forecaster's
        PROJECTED model — the current assignment under the horizon-peak
        loads, so proposals diff against the TRUE current state and are
        executable on the real cluster.

        ``execute=False`` (the default precompute mode) never moves
        anything:

        - the solve's compiled programs land on the exact jit cache keys
          the real fix will hit (same shape, same chain),
        - the predicted TARGET seeds the warm-seed store, so the real
          solve warm-starts from it (``solver.warm.start.enabled``
          consumes it; the store is written regardless so flipping warm
          on mid-incident still finds the seed), and
        - the fleet pacer is flagged (``predicted_precompute_pending``)
          to refresh this cluster's REAL proposal cache on its next
          sweep instead of waiting out the cadence.

        ``execute=True`` (the ``anomaly.detection.predictive.fix.enabled``
        opt-in) additionally EXECUTES the projected-model proposals —
        the proactive rebalance that heals before the violation.
        Returns True when a fix/precompute ran (the anomaly fix-started
        contract)."""
        from .utils.heal_ledger import current_heal
        from .utils.sensors import SENSORS
        last = self.forecast_engine.last_result
        if last is None:
            return False
        chain = self._goal_chain(None)
        # Same exclusion contract as the reactive goal-violation fix:
        # the self.healing.exclude.recently.* configs and the config's
        # never-move topics hold on the predictive path too.
        no_leadership = tuple(sorted(self.recently_demoted_brokers)) \
            if self._config.get_boolean(
                "self.healing.exclude.recently.demoted.brokers") else ()
        no_replicas = tuple(sorted(self.recently_removed_brokers)) \
            if self._config.get_boolean(
                "self.healing.exclude.recently.removed.brokers") else ()
        options = OptimizationOptions(
            excluded_brokers_for_leadership=no_leadership,
            excluded_brokers_for_replica_move=no_replicas,
            is_triggered_by_goal_violation=True)
        options = self._with_config_excluded_topics(last.meta, options)
        heal = current_heal()
        heal.phase("predictive_solve", horizonS=round(last.horizon_s, 3),
                   execute=bool(execute))
        final, result = self._optimize(last.projected_state, last.meta,
                                       chain, options)
        # The predicted target is the next solve's warm seed — but its
        # quality gate reference must describe REALITY, not the
        # projected model: a projected-model score can be optimistic
        # (warm attempts would spuriously fall back — one wasted solve)
        # or PESSIMISTIC (a too-low reference would let a degraded warm
        # result pass the sentry band — the round-18 cross-contamination
        # the incomparable-solve-class rule exists to prevent). Score
        # the predicted target against the CURRENT loads in one batched
        # entry snapshot and anchor the gate there.
        try:
            ref_state = dataclasses.replace(
                final, leader_load=last.state.leader_load,
                follower_load=last.state.follower_load)
            ref_chain, rv, _ro, _roff = self._optimizer.goal_entry_stats(
                ref_state, last.meta, chain, options)
            ref_violated = frozenset(
                g.name for g, v in zip(ref_chain, rv) if float(v) > 1e-6)
            reference = (self._optimizer.balancedness_of(ref_chain,
                                                         ref_violated),
                         ref_violated)
            self._warm_seeds.store(final, last.meta, result,
                                   reference=reference)
        except Exception:  # noqa: BLE001 — reference scoring is an
            # accuracy refinement; fall back to the solve's own quality
            LOG.debug("predicted-seed reference scoring failed",
                      exc_info=True)
            self._warm_seeds.store(final, last.meta, result)
        heal.phase("proposal_ready", predicted=True,
                   numProposals=len(result.proposals))
        if execute:
            executed = self._maybe_execute(
                result, dryrun=False, operation="predictive_rebalance",
                reason=reason or "proactive predicted-violation fix")
            if executed:
                SENSORS.count("anomaly_predicted_fixes")
                if anomaly_id is not None:
                    # The detector's settle pass distinguishes a
                    # prediction AVERTED by its own proactive fix
                    # (cleared) from one that plainly missed
                    # (self_cleared).
                    det = getattr(self, "predictive_detector", None)
                    if det is not None:
                        det.note_proactive_fix(anomaly_id)
                return True
            # Execution refused (executor busy / stop requested / zero
            # proposals): fall back to the precompute contract — the
            # prediction still leaves a hot answer and a pacer flag,
            # and the averted bookkeeping is correctly NOT marked.
        self.predicted_precompute_pending = True
        SENSORS.count("anomaly_predicted_precomputes")
        return True

    # Backwards-compatible precompute entry (the anomaly's default fix).
    def precompute_predicted(self) -> bool:
        return self.fix_predicted_violation(execute=False)

    def fix_slo_burn(self, objective: str = "", reason: str = "",
                     anomaly_id: str | None = None) -> bool:
        """The SLO_BURN fix: no rebalance to run — the burn is a serving
        condition, not an assignment problem — but the chain must reach
        FIX_STARTED and stay OPEN until the detector's budget-recovered
        terminal (returning False would close it ``fix_failed_to_start``
        and the clear would have no chain to land on). Mitigation is the
        precompute pacer flag: a hot proposal cache removes solve time
        from the request path, the one lever self-healing owns against a
        latency/shed burn. Returns True (the fix-started contract)."""
        from .utils.heal_ledger import current_heal
        from .utils.sensors import SENSORS
        current_heal().phase("mitigation_started", objective=objective,
                             reason=reason or "slo burn",
                             action="precompute_refresh")
        # Same lever as the predictive fix's precompute mode: the fleet
        # pacer refreshes this cluster's proposal cache on its next
        # sweep instead of waiting out the cadence.
        self.predicted_precompute_pending = True
        SENSORS.count("slo_burn_mitigations")
        return True

    def forecast_state(self, refresh: bool = False) -> dict:
        """GET /forecast body: the engine's last projection (per-broker
        current-vs-projected loads + confidence band) and the predictive
        detector's lifecycle counters. ``refresh=True`` fits a fresh
        forecast inline (device work — the param is explicit opt-in)."""
        eng = self.forecast_engine
        body: dict[str, Any] = {
            "forecastEnabled": eng.enabled,
            "horizonWindows": self._config.get_int(
                "forecast.horizon.windows"),
            "fitWindows": self._config.get_int("forecast.fit.windows"),
            "seasonalPeriodWindows": self._config.get_int(
                "forecast.seasonal.period.windows"),
            "predictiveFixEnabled": self._config.get_boolean(
                "anomaly.detection.predictive.fix.enabled"),
        }
        result = None
        if eng.enabled:
            # A refresh whose fresh fit is not ready yet (monitor short
            # of stable windows) falls back to the cached projection —
            # refresh means "at least as fresh as the cache", never
            # worse. A DISABLED engine serves null even if a pre-flip
            # fit is still cached (off means off).
            result = eng.forecast() if refresh else eng.last_result
            if result is None:
                result = eng.last_result
        body["forecast"] = result.to_dict() if result is not None else None
        det = getattr(self, "predictive_detector", None)
        body["detector"] = det.state() if det is not None else None
        return body

    # -- removal/demotion history (Executor.java retention parity) ---------
    def _history_now_ms(self) -> int:
        return self._now_ms() if self._now_ms is not None \
            else int(time.time() * 1000)

    def _history_active(self, hist: dict[int, int],
                        retention_ms: int) -> set[int]:
        """Prune expired entries and return the still-active broker ids."""
        now = self._history_now_ms()
        with self.excluded_sets_lock:
            for b in [b for b, ts in hist.items()
                      if now - ts > retention_ms]:
                del hist[b]
            return set(hist)

    def _history_record(self, hist: dict[int, int],
                        broker_ids: Sequence[int]) -> None:
        now = self._history_now_ms()
        with self.excluded_sets_lock:
            for b in broker_ids:
                hist[int(b)] = now

    @property
    def recently_removed_brokers(self) -> set[int]:
        """Brokers removed by an executed remove_brokers within the
        removal-history retention window — excluded as replica-move
        destinations by detection and exclude_recently_removed_brokers
        requests until the window (on the injected clock) lapses."""
        return self._history_active(self._removal_history,
                                    self._removal_retention_ms)

    @property
    def recently_demoted_brokers(self) -> set[int]:
        return self._history_active(self._demotion_history,
                                    self._demotion_retention_ms)

    def drop_recently_removed_brokers(self, broker_ids: Sequence[int]) -> None:
        with self.excluded_sets_lock:
            for b in broker_ids:
                self._removal_history.pop(int(b), None)

    def drop_recently_demoted_brokers(self, broker_ids: Sequence[int]) -> None:
        with self.excluded_sets_lock:
            for b in broker_ids:
                self._demotion_history.pop(int(b), None)

    @_traced_op("rebalance")
    def rebalance(self, goals: Sequence[str] | None = None, dryrun: bool = True,
                  ignore_proposal_cache: bool = False,
                  excluded_topics: Sequence[str] = (),
                  destination_broker_ids: Sequence[int] = (),
                  exclude_recently_demoted_brokers: bool = False,
                  exclude_recently_removed_brokers: bool = False,
                  is_triggered_by_user_request: bool = True,
                  use_ready_default_goals: bool = False,
                  fast_mode: bool = False,
                  data_from: str | None = None,
                  allow_capacity_estimation: bool = True,
                  reason: str = "", uuid: str = "") -> OperationResult:
        """RebalanceRunnable.workWithoutClusterModel:115."""
        del ignore_proposal_cache  # explicit model pass below is always fresh
        chain, state, meta = self._chain_and_model(
            goals, use_ready_default_goals, data_from,
            allow_capacity_estimation)
        # The history properties snapshot under the facade's lock.
        no_leadership = tuple(sorted(self.recently_demoted_brokers)) \
            if exclude_recently_demoted_brokers else ()
        no_replicas = tuple(sorted(self.recently_removed_brokers)) \
            if exclude_recently_removed_brokers else ()
        options = OptimizationOptions(
            excluded_topics=tuple(excluded_topics),
            excluded_brokers_for_leadership=no_leadership,
            excluded_brokers_for_replica_move=no_replicas,
            requested_destination_broker_ids=tuple(destination_broker_ids),
            is_triggered_by_goal_violation=not is_triggered_by_user_request,
            fast_mode=fast_mode)
        options = self._with_config_excluded_topics(meta, options)
        _final, result = self._optimize(state, meta, chain, options)
        executed = self._maybe_execute(result, dryrun, "rebalance", reason, uuid)
        return OperationResult("rebalance", dryrun, result, result.proposals,
                               executed, reason)

    @_traced_op("add_broker")
    def add_brokers(self, broker_ids: Sequence[int], dryrun: bool = True,
                    goals: Sequence[str] | None = None,
                    is_triggered_by_user_request: bool = True,
                    use_ready_default_goals: bool = False,
                    fast_mode: bool = False,
                    data_from: str | None = None,
                    allow_capacity_estimation: bool = True,
                    reason: str = "", uuid: str = "") -> OperationResult:
        """AddBrokersRunnable — mark NEW and run the chain: while a broker
        is NEW, replicas move only onto NEW brokers, in every goal, swap
        and transport (``analyzer.derived.DerivedState.replica_dest_ok``,
        docs/DESIGN.md "The scale-out's rule"); a plan that breaks this
        raises (``optimizer.ensure_only_new_brokers_receive``)."""
        chain, state, meta = self._chain_and_model(
            goals, use_ready_default_goals, data_from,
            allow_capacity_estimation)
        state = self._mark_brokers(state, meta, broker_ids, BrokerState.NEW)
        options = self._with_config_excluded_topics(
            meta, OptimizationOptions(fast_mode=fast_mode))
        _final, result = self._optimize(state, meta, chain, options)
        executed = self._maybe_execute(result, dryrun, "add_broker", reason, uuid)
        if executed:
            # An added broker is a live destination again: clear any
            # removal-history entry so detection and
            # exclude_recently_removed_brokers requests stop excluding it
            # (AddBrokersRunnable drops re-added brokers from the
            # Executor's removal history).
            self.drop_recently_removed_brokers(broker_ids)
        return OperationResult("add_broker", dryrun, result, result.proposals,
                               executed, reason)

    @_traced_op("remove_broker")
    def remove_brokers(self, broker_ids: Sequence[int], dryrun: bool = True,
                       goals: Sequence[str] | None = None,
                       is_triggered_by_user_request: bool = True,
                       use_ready_default_goals: bool = False,
                       fast_mode: bool = False,
                       data_from: str | None = None,
                       allow_capacity_estimation: bool = True,
                       reason: str = "", uuid: str = "") -> OperationResult:
        """RemoveBrokersRunnable — mark DEAD so every replica they host
        becomes self-healing-eligible and must be relocated."""
        chain, state, meta = self._chain_and_model(
            goals, use_ready_default_goals, data_from,
            allow_capacity_estimation)
        state = self._mark_brokers(state, meta, broker_ids, BrokerState.DEAD)
        options = self._with_config_excluded_topics(
            meta, OptimizationOptions(
                excluded_brokers_for_replica_move=tuple(broker_ids),
                excluded_brokers_for_leadership=tuple(broker_ids),
                fast_mode=fast_mode))
        _final, result = self._optimize(state, meta, chain, options)
        executed = self._maybe_execute(result, dryrun, "remove_broker", reason, uuid)
        if executed:
            self._history_record(self._removal_history, broker_ids)
        return OperationResult("remove_broker", dryrun, result,
                               result.proposals, executed, reason)

    @_traced_op("demote_broker")
    def demote_brokers(self, broker_ids: Sequence[int], dryrun: bool = True,
                       is_triggered_by_user_request: bool = True,
                       skip_urp_demotion: bool = True,
                       exclude_follower_demotion: bool = False,
                       reason: str = "", uuid: str = "") -> OperationResult:
        """DemoteBrokerRunnable — PreferredLeaderElectionGoal with the
        demoted brokers excluded from leadership.

        ``skip_urp_demotion`` (default true, DemoteBrokerRunnable
        SKIP_URP_DEMOTION): partitions currently under-replicated are left
        alone. ``exclude_follower_demotion=False`` (the default) also
        reorders each affected partition's replica list so the demoted
        brokers' replicas come last (the reference's follower demotion);
        true limits the operation to leadership transfers."""
        from .analyzer.goals import PreferredLeaderElectionGoal
        state, meta = self._model()
        state = self._mark_brokers(state, meta, broker_ids, BrokerState.DEMOTED)
        options = OptimizationOptions(
            excluded_brokers_for_leadership=tuple(broker_ids))
        _final, result = self._optimizer.optimizations(
            state, meta, [PreferredLeaderElectionGoal()], options)
        proposals = list(result.proposals)
        parts = self._admin_call("admin.describe_partitions",
                                 self._admin.describe_partitions)
        if skip_urp_demotion:
            urp = {key for key, st in parts.items()
                   if set(st.replicas) - set(st.isr)}
            proposals = [p for p in proposals
                         if (p.topic, p.partition) not in urp]
        if not exclude_follower_demotion:
            demoted = set(broker_ids)
            covered = {(p.topic, p.partition): i
                       for i, p in enumerate(proposals)}
            for (topic, part), st in sorted(parts.items()):
                if skip_urp_demotion and set(st.replicas) - set(st.isr):
                    continue
                hit = [b for b in st.replicas if b in demoted]
                if not hit:
                    continue
                keep = [b for b in st.replicas if b not in demoted]
                reordered = tuple(keep + hit)
                idx = covered.get((topic, part))
                if idx is not None:
                    p0 = proposals[idx]
                    keep2 = [b for b in p0.new_replicas if b not in demoted]
                    hit2 = [b for b in p0.new_replicas if b in demoted]
                    proposals[idx] = dataclasses.replace(
                        p0, new_replicas=tuple(keep2 + hit2))
                elif reordered != tuple(st.replicas):
                    proposals.append(ExecutionProposal(
                        topic=topic, partition=part, old_leader=st.leader,
                        old_replicas=tuple(st.replicas),
                        new_replicas=reordered, new_leader=st.leader))
        result = dataclasses.replace(result, proposals=proposals)
        executed = self._maybe_execute(result, dryrun, "demote_broker", reason, uuid)
        if executed:
            self._history_record(self._demotion_history, broker_ids)
        return OperationResult("demote_broker", dryrun, result,
                               result.proposals, executed, reason)

    @_traced_op("fix_offline_replicas")
    def fix_offline_replicas(self, dryrun: bool = True,
                             goals: Sequence[str] | None = None,
                             is_triggered_by_user_request: bool = True,
                             use_ready_default_goals: bool = False,
                             fast_mode: bool = False,
                             data_from: str | None = None,
                             allow_capacity_estimation: bool = True,
                             reason: str = "", uuid: str = "") -> OperationResult:
        """FixOfflineReplicasRunnable — the model already marks replicas on
        dead brokers offline; the goal chain must relocate them."""
        chain, state, meta = self._chain_and_model(
            goals, use_ready_default_goals, data_from,
            allow_capacity_estimation)
        options = self._with_config_excluded_topics(
            meta, OptimizationOptions(only_move_immigrant_replicas=False,
                                      fast_mode=fast_mode))
        _final, result = self._optimize(state, meta, chain, options)
        executed = self._maybe_execute(result, dryrun, "fix_offline_replicas",
                                       reason, uuid)
        return OperationResult("fix_offline_replicas", dryrun, result,
                               result.proposals, executed, reason)

    @_traced_op("topic_configuration")
    def update_topic_replication_factor(self, topics: Sequence[str],
                                        replication_factor: int,
                                        dryrun: bool = True,
                                        is_triggered_by_user_request: bool = True,
                                        reason: str = "", uuid: str = "",
                                        skip_rack_awareness_check: bool = False,
                                        ) -> OperationResult:
        """UpdateTopicConfigurationRunnable — grow/shrink each partition's
        replica list to the target RF (rack-diverse, least-loaded brokers
        first for growth; drop the most-loaded non-leader for shrink)."""
        state, meta = self._model()
        want = set(topics)
        partitions = self._admin_call("admin.describe_partitions",
                                      self._admin.describe_partitions)
        alive = self._admin_call("admin.alive_brokers",
                                 self._admin.alive_brokers)
        racks = {bid: meta.rack_names[int(r)]
                 for bid, r in zip(meta.broker_ids, np.asarray(state.rack))}
        # populateRackInfoForReplicationFactorChange (RunnableUtils.java:74):
        # RF above the alive-broker count is always impossible; RF above the
        # rack count breaks one-replica-per-rack and needs the explicit
        # skip_rack_awareness_check opt-in.
        if replication_factor > len(alive):
            raise ValueError(
                f"replication factor {replication_factor} exceeds the "
                f"{len(alive)} alive broker(s)")
        if not skip_rack_awareness_check:
            num_racks = len({racks[b] for b in alive if b in racks})
            if replication_factor > max(num_racks, 1):
                raise ValueError(
                    f"replication factor {replication_factor} exceeds the "
                    f"{num_racks} distinct alive rack(s); pass "
                    "skip_rack_awareness_check=true to override")
        counts: dict[int, int] = {b: 0 for b in alive}
        for st in partitions.values():
            for b in st.replicas:
                counts[b] = counts.get(b, 0) + 1
        proposals: list[ExecutionProposal] = []
        for (topic, part), st in sorted(partitions.items()):
            if topic not in want or len(st.replicas) == replication_factor:
                continue
            old = tuple(st.replicas)
            leader = st.leader if st.leader is not None and st.leader >= 0 \
                else (old[0] if old else -1)
            new = list(old)
            while len(new) > replication_factor and len(new) > 1:
                victims = [b for b in new if b != leader] or new[1:]
                victim = max(victims, key=lambda b: counts.get(b, 0))
                new.remove(victim)
                counts[victim] = counts.get(victim, 0) - 1
            while len(new) < replication_factor:
                used_racks = {racks.get(b) for b in new}
                # Growth targets must be alive (a dead broker can appear in
                # stale replica lists and would otherwise win on count).
                candidates = [b for b in alive if b not in new]
                if not candidates:
                    break
                fresh = [b for b in candidates if racks.get(b) not in used_racks]
                pick = min(fresh or candidates, key=lambda b: counts.get(b, 0))
                new.append(pick)
                counts[pick] = counts.get(pick, 0) + 1
            if tuple(new) != old:
                proposals.append(ExecutionProposal(
                    topic=topic, partition=part, old_leader=leader,
                    old_replicas=old, new_replicas=tuple(new),
                    new_leader=leader))
        executed = False
        if proposals and not dryrun:
            self._executor.execute_proposals(proposals, uuid=uuid)
            executed = True
        return OperationResult("topic_configuration", dryrun, None,
                               tuple(proposals), executed, reason,
                               extra={"replicationFactor": replication_factor,
                                      "topics": sorted(want)})

    def _disk_model(self, state, meta):
        """(DiskTensors, DiskMeta) from the backend's JBOD surface, or raise
        when the backend is not JBOD-capable."""
        from .model.disks import build_disk_tensors
        replica_dirs_fn = getattr(self._admin, "replica_logdirs", None)
        logdirs_fn = getattr(self._admin, "describe_logdirs", None)
        if replica_dirs_fn is None or logdirs_fn is None or not logdirs_fn():
            raise ValueError(
                "operation requires a JBOD-capable admin backend "
                "(replica_logdirs/describe_logdirs)")
        return build_disk_tensors(state, meta, logdirs_fn(), replica_dirs_fn())

    def _intra_broker_result(self, operation, state, meta, disks0, disks1,
                             disk_meta, dryrun, reason) -> OperationResult:
        from .analyzer.proposals import ExecutionProposal
        from .model.disks import diff_intra_broker_moves
        moves = diff_intra_broker_moves(disks0, disks1, state, meta, disk_meta)
        executed = False
        if moves and not dryrun:
            # Submit through the Executor (intra-broker phase: per-broker
            # caps, completion polling, dead-task handling — Executor.java
            # :1672), NOT by calling the admin directly.
            from .common.resources import Resource
            disk_mb = np.asarray(state.leader_load[:, int(Resource.DISK)])
            row_of = {tp: i for i, tp in enumerate(meta.partition_index)}
            proposals = [ExecutionProposal(
                topic=m.topic, partition=m.partition, old_leader=-1,
                old_replicas=(), new_replicas=(), new_leader=-1,
                logdir_broker=m.broker_id, source_logdir=m.source_logdir,
                destination_logdir=m.destination_logdir,
                data_to_move_mb=float(disk_mb[row_of[(m.topic, m.partition)]])
                ) for m in moves]
            OPERATION_LOG.info("%s executing %d intra-broker moves "
                               "(reason: %s)", operation, len(moves), reason)
            self._executor.execute_proposals(proposals, uuid=operation)
            executed = True
        return OperationResult(
            operation, dryrun, executed=executed, reason=reason,
            extra={"intraBrokerMoves": [
                {"topic": m.topic, "partition": m.partition,
                 "broker": m.broker_id, "sourceLogdir": m.source_logdir,
                 "destinationLogdir": m.destination_logdir} for m in moves]})

    @_traced_op("remove_disks")
    def remove_disks(self, broker_logdirs: Mapping[int, Sequence[str]],
                     dryrun: bool = True, reason: str = "",
                     uuid: str = "") -> OperationResult:
        """RemoveDisksRunnable — mark the named log dirs dead in the disk
        model and drain them with the [B]-parallel intra-broker kernel
        (heaviest replicas first onto the least-utilized remaining dirs)."""
        import dataclasses as dc

        import jax.numpy as jnp

        from .analyzer.goals.intra_broker import IntraBrokerDiskCapacityGoal
        state, meta = self._model()
        disks, disk_meta = self._disk_model(state, meta)
        dead = np.asarray(disks.disk_alive).copy()
        requested = np.zeros_like(dead)  # dirs named in THIS request
        idx = {bid: i for i, bid in enumerate(meta.broker_ids)}
        for broker, dirs in broker_logdirs.items():
            if broker not in idx:
                raise ValueError(f"unknown broker {broker}")
            i = idx[broker]
            for d in dirs:
                if d not in disk_meta.dir_names[i]:
                    raise ValueError(f"broker {broker} has no log dir {d!r}")
                slot = disk_meta.dir_names[i].index(d)
                dead[i, slot] = False
                requested[i, slot] = True
            if not dead[i].any():
                raise ValueError(f"broker {broker}: no remaining alive log dirs")
        marked = dc.replace(disks, disk_alive=jnp.asarray(dead))
        movable = self._movable_partition_mask(state, meta)
        if movable is not None:
            # A pinned (never-move) replica on a dir being REMOVED BY THIS
            # REQUEST is an unresolvable conflict between the two
            # contracts: draining it violates the exclusion, leaving it
            # silently loses the replica when the operator pulls the disk.
            # Refuse loudly. Only dirs NAMED IN THIS REQUEST count — a
            # long-offline dir elsewhere must not block this operation
            # (and a named dir that was already offline still counts: the
            # operator is about to pull that disk).
            assign = np.asarray(disks.disk_assignment)
            broker_of = np.asarray(state.assignment)
            pinned = ~np.asarray(movable)
            removed_now = requested
            valid = (broker_of >= 0) & (assign >= 0)
            hit = pinned[:, None] & valid & removed_now[
                np.clip(broker_of, 0, None), np.clip(assign, 0, None)]
            stuck_rows = np.nonzero(hit.any(axis=1))[0]
            if stuck_rows.size:
                names = [meta.partition_index[p] if
                         p < len(meta.partition_index) else int(p)
                         for p in stuck_rows[:10]]
                raise ValueError(
                    f"excluded-topic replicas live on the removed log dirs "
                    f"and may not be moved "
                    f"(topics.excluded.from.partition.movement): {names}")
        balanced = IntraBrokerDiskCapacityGoal().optimize(
            state, marked, movable=movable)
        return self._intra_broker_result("remove_disks", state, meta, marked,
                                         balanced, disk_meta, dryrun, reason)

    @_traced_op("rebalance_disk")
    def rebalance_disk(self, dryrun: bool = True, reason: str = "",
                       uuid: str = "") -> OperationResult:
        """REBALANCE?rebalance_disk=true — intra-broker disk-usage balance
        (IntraBrokerDiskUsageDistributionGoal over every broker at once)."""
        from .analyzer.goals.intra_broker import (
            IntraBrokerDiskUsageDistributionGoal,
        )
        state, meta = self._model()
        disks, disk_meta = self._disk_model(state, meta)
        balanced = IntraBrokerDiskUsageDistributionGoal().optimize(
            state, disks, movable=self._movable_partition_mask(state, meta))
        return self._intra_broker_result("rebalance_disk", state, meta, disks,
                                         balanced, disk_meta, dryrun, reason)

    def rightsize(self, num_brokers_to_add: int = 0, partition_count: int = 0,
                  topic: str | None = None) -> OperationResult:
        """RightsizeRunnable — hand a ProvisionRecommendation to the
        configured Provisioner."""
        if not self._config.get_boolean("provisioner.enable"):
            raise ValueError(
                "provisioner is disabled (provisioner.enable=false)")
        from .detector.provisioner import ProvisionRecommendation, ProvisionStatus
        rec = ProvisionRecommendation(
            status=ProvisionStatus.UNDER_PROVISIONED,
            num_brokers=num_brokers_to_add, num_partitions=partition_count,
            topic=topic)
        state = self.provisioner.rightsize([rec])
        return OperationResult("rightsize", dryrun=False,
                               extra={"provisionerState": state.value,
                                      "recommendation": rec.to_dict()})

    # -- admin toggles ------------------------------------------------------
    def set_concurrency(self, inter_broker_per_broker: int | None = None,
                        intra_broker_per_broker: int | None = None,
                        leadership_cluster: int | None = None) -> dict:
        """ADMIN endpoint concurrency overrides."""
        return self._executor.set_requested_concurrency(
            inter_broker_per_broker=inter_broker_per_broker,
            intra_broker_per_broker=intra_broker_per_broker,
            leadership_cluster=leadership_cluster)

    def pause_metric_sampling(self, reason: str = "") -> None:
        self._load_monitor.pause_metric_sampling(reason)

    def resume_metric_sampling(self, reason: str = "") -> None:
        self._load_monitor.resume_metric_sampling(reason)

    def stop_proposal_execution(self, force_stop: bool = False,
                                stop_external_agent: bool = False) -> None:
        """STOP_PROPOSAL_EXECUTION (Executor.userTriggeredStopExecution:1139).
        ``force_stop`` is accepted for parameter parity — with the
        AdminClient (KIP-455) cancellation path both modes cancel in-flight
        reassignments, the old soft/force split only existed for ZK-based
        stops. ``stop_external_agent`` additionally cancels reassignments
        this executor did not start (maybeStopExternalAgent:1261) when no
        internal execution is running."""
        del force_stop
        self._executor.stop_execution()
        if stop_external_agent:
            cancelled = self._executor.stop_external_reassignments()
            if cancelled:
                OPERATION_LOG.info(
                    "stop_proposal_execution cancelled %d external "
                    "reassignment(s)", cancelled)

    # -- state (the STATE endpoint dashboard) -------------------------------
    def state(self, substates: Sequence[str] = (),
              super_verbose: bool = False) -> dict:
        """STATE body; ``super_verbose`` adds the per-window detail the
        reference's CruiseControlState verbose/super_verbose flags expose
        (monitored window timestamps, executor history)."""
        want = {s.lower() for s in substates} or \
            {"monitor", "executor", "analyzer", "anomaly_detector"}
        out: dict[str, Any] = {}
        # LoadMonitor.state() walks full partition metadata + completeness:
        # compute at most once per request (shared by monitor + analyzer).
        _ms_cache: list = []

        def monitor_state():
            if not _ms_cache:
                _ms_cache.append(self._load_monitor.state())
            return _ms_cache[0]

        def _ready_names():
            # Guarded: a not-yet-started monitor degrades readyGoals to []
            # instead of failing the whole STATE request.
            try:
                return self.ready_goals(monitor_state=monitor_state())
            except Exception:  # noqa: BLE001 — monitor not started yet
                return []

        if "monitor" in want:
            ms = monitor_state()
            out["MonitorState"] = {
                "state": ms.runner_state,
                "numValidWindows": ms.num_valid_windows,
                "monitoredWindows": ms.num_valid_windows,
                "monitoringCoveragePct": round(
                    100.0 * ms.monitored_partitions_percentage, 3),
                "totalNumPartitions": ms.total_num_partitions,
                "numPartitionSamples": ms.num_partition_samples,
                "modelGeneration": ms.model_generation,
            }
            if super_verbose:
                try:
                    out["MonitorState"]["windowTimestampsMs"] = \
                        self._load_monitor.window_times()
                except Exception:  # noqa: BLE001 — detail only
                    out["MonitorState"]["windowTimestampsMs"] = []
        if "executor" in want:
            out["ExecutorState"] = self._executor.execution_state(
                history_limit=20 if super_verbose else 5)
        if "analyzer" in want:
            with self._proposal_lock:
                cached = self._proposal_cache
            out["AnalyzerState"] = {
                "isProposalReady": cached is not None,
                "readyGoals": [g.name for g in _ready_names()],
                "balancednessScore":
                    self.goal_violation_detector.balancedness_score,
            }
            # Prewarm progress (round 18): how far the background
            # known-shape compile sweep has come — the signal a fresh
            # replica's readiness probe should watch before admitting
            # solver traffic. Absent when prewarm is disabled.
            from .warmstart import prewarm_status
            pw = prewarm_status(self._optimizer)
            if pw is not None:
                out["AnalyzerState"]["prewarm"] = pw
        if "anomaly_detector" in want:
            out["AnomalyDetectorState"] = self._anomaly_detector.state()
        return out
