"""The REST server: stdlib threaded HTTP front-end over the facade.

Reference parity: servlet/KafkaCruiseControlServletApp (Jetty) +
KafkaCruiseControlRequestHandler (dispatch, :~40) +
KafkaCruiseControlEndPoints — collapsed onto ThreadingHTTPServer. Request
flow mirrors the reference: resolve endpoint → authenticate/authorize →
two-step purgatory gate → parse parameters → sync handler or async
user-task submission (202 + ``User-Task-ID`` when still running).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import urllib.parse
from concurrent.futures import TimeoutError as FuturesTimeoutError
from functools import lru_cache
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

from ..config.cruise_control_config import CruiseControlConfig
from ..facade import CruiseControl
from ..fleet.registry import ClusterPausedError, UnknownClusterError
from ..monitor.load_monitor import NotEnoughValidWindowsError
from ..serving import (
    AdmissionController, AdmissionShedError, AsyncTaskEngine, ResponseCache,
    TaskQueueFullError, canonical_params, task_class_of,
)
from ..serving.cache import CACHEABLE_ENDPOINTS, COALESCIBLE_ENDPOINTS
from ..utils.resilience import BreakerOpenError
from . import responses
from .endpoints import REVIEWABLE_ENDPOINTS, EndPoint, endpoint_for_path
from .parameters import ParameterParseError, parse_parameters
from .purgatory import Purgatory
from .security import (
    AuthenticationError, AuthorizationError, NoopSecurityProvider, Principal,
    SecurityProvider,
)
from .user_tasks import (
    USER_TASK_HEADER, TaskOwnershipError, TooManyUserTasksError,
    UserTaskManager,
)

LOG = logging.getLogger(__name__)

URL_PREFIX = "/kafkacruisecontrol"

# Endpoints answered inline; everything else runs as an async user task
# (handler/sync vs handler/async split in the reference).
_SYNC_ENDPOINTS = {
    EndPoint.STATE, EndPoint.KAFKA_CLUSTER_STATE, EndPoint.USER_TASKS,
    EndPoint.REVIEW_BOARD, EndPoint.PERMISSIONS, EndPoint.REVIEW,
    EndPoint.PAUSE_SAMPLING, EndPoint.RESUME_SAMPLING,
    EndPoint.STOP_PROPOSAL_EXECUTION, EndPoint.ADMIN, EndPoint.BOOTSTRAP,
    EndPoint.TRAIN, EndPoint.RIGHTSIZE, EndPoint.FLEET, EndPoint.HEALS,
    EndPoint.FORECAST, EndPoint.JOURNEYS, EndPoint.SLO, EndPoint.REDTEAM,
}

# Endpoints that consume solver time. In fleet mode these (a) are refused
# for paused clusters and (b) run through the FleetScheduler as ON_DEMAND
# jobs, so one cluster's requests share the device fairly with every
# other cluster's precompute and self-healing (fleet.scheduler).
# RIGHTSIZE is deliberately absent: it hands a recommendation to the
# provisioner without touching the solver (and is answered inline).
_SOLVER_ENDPOINTS = {
    EndPoint.PROPOSALS, EndPoint.REBALANCE, EndPoint.ADD_BROKER,
    EndPoint.REMOVE_BROKER, EndPoint.DEMOTE_BROKER,
    EndPoint.FIX_OFFLINE_REPLICAS, EndPoint.TOPIC_CONFIGURATION,
    EndPoint.REMOVE_DISKS, EndPoint.COMPARE_FUTURES,
}

# Async endpoints whose work is a cluster-model BUILD (device transfers +
# stats kernels, no solver search). In fleet mode these run through the
# FleetScheduler too (round 20, ROADMAP item 4 tail) so the handler layer
# never touches the device directly — but they stay outside
# _SOLVER_ENDPOINTS: reads keep working against a PAUSED cluster, and the
# breaker treats them as monitor traffic.
_MODEL_BUILD_ENDPOINTS = {EndPoint.LOAD, EndPoint.PARTITION_LOAD}


# Proposal-executing endpoints gated by request.reason.required (the
# parameter classes that consult REQUEST_REASON_REQUIRED_CONFIG:
# Rebalance/AddedOrRemovedBroker/DemoteBroker/FixOfflineReplicas/
# TopicConfiguration/RemoveDisks Parameters.java).
_REASON_REQUIRED_ENDPOINTS = {
    EndPoint.REBALANCE, EndPoint.ADD_BROKER, EndPoint.REMOVE_BROKER,
    EndPoint.DEMOTE_BROKER, EndPoint.FIX_OFFLINE_REPLICAS,
    EndPoint.TOPIC_CONFIGURATION, EndPoint.REMOVE_DISKS,
}

# The two-goal chain kafka_assigner mode swaps in
# (ParameterUtils.getGoals:755-771, RunnableUtils.KAFKA_ASSIGNER_GOALS).
_KAFKA_ASSIGNER_GOALS = ["KafkaAssignerEvenRackAwareGoal",
                         "KafkaAssignerDiskUsageDistributionGoal"]

# Endpoints whose EXPLICIT goal lists must contain the configured hard
# goals (GoalBasedOperationRunnable.init → sanityCheckGoals; PROPOSALS is
# dryrun-only and exempt, as in ProposalsParameters).
_HARD_GOAL_CHECKED_ENDPOINTS = {
    EndPoint.REBALANCE, EndPoint.ADD_BROKER, EndPoint.REMOVE_BROKER,
    EndPoint.FIX_OFFLINE_REPLICAS, EndPoint.TOPIC_CONFIGURATION,
}


def _resolve_goal_names(p: dict) -> list[str] | None:
    """Request goal list after mode switches (ParameterUtils.getGoals:755):
    kafka_assigner mode uses exactly the two assigner goals and conflicts
    with both explicit goals and rebalance-disk mode; rebalance-disk mode
    picks its intra-broker chain in the facade."""
    explicit = list(p["goals"]) if "goals" in p else None
    if p.get("kafka_assigner"):
        if p.get("rebalance_disk"):
            raise ParameterParseError(
                "Kafka assigner mode and rebalance disk mode cannot be set "
                "at the same time.")
        if explicit:
            raise ParameterParseError(
                "Kafka assigner mode does not support explicitly specifying "
                "goals in request.")
        if p.get("use_ready_default_goals"):
            raise ParameterParseError(
                "use_ready_default_goals is about the DEFAULT goal chain; "
                "it cannot be combined with kafka_assigner mode.")
        return list(_KAFKA_ASSIGNER_GOALS)
    if p.get("rebalance_disk") and explicit:
        raise ParameterParseError(
            "Rebalance disk mode does not support explicitly specifying "
            "goals in request.")
    if explicit and p.get("use_ready_default_goals"):
        raise ParameterParseError(
            "use_ready_default_goals cannot be combined with explicitly "
            "specified goals.")
    return explicit


class ApiError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status


class CruiseControlApi:
    """Transport-independent request handling (so tests can drive it
    without sockets, like the reference's servlet unit tests)."""

    def __init__(self, cc: CruiseControl,
                 security_provider: SecurityProvider | None = None,
                 config: CruiseControlConfig | None = None,
                 fleet=None):
        self._cc = cc
        # Optional fleet.FleetRegistry: enables ?cluster= routing on every
        # endpoint plus the FLEET dashboard. The default (no ?cluster=)
        # path always serves ``cc`` — single-cluster deployments are
        # byte-for-byte unchanged.
        self._fleet = fleet
        cfg = config or cc.config
        self._config = cfg
        self._security = security_provider or (
            self._configured_security(cfg) if cfg.get_boolean("webserver.security.enable")
            else NoopSecurityProvider())
        self._two_step = cfg.get_boolean("two.step.verification.enabled")
        self._purgatory = Purgatory(
            retention_ms=cfg.get_long("two.step.purgatory.retention.time.ms"))
        from .user_tasks import CC_ADMIN, CC_MONITOR, KAFKA_ADMIN, KAFKA_MONITOR
        retention_overrides = {
            cls: cfg.get_long(key)
            for cls, key in (
                (KAFKA_MONITOR, "completed.kafka.monitor.user.task.retention.time.ms"),
                (KAFKA_ADMIN, "completed.kafka.admin.user.task.retention.time.ms"),
                (CC_MONITOR, "completed.cruise.control.monitor.user.task.retention.time.ms"),
                (CC_ADMIN, "completed.cruise.control.admin.user.task.retention.time.ms"))
            if cfg.get(key) is not None}
        # Serving front door (round 20): the unified async task engine
        # (bounded per-class queues), the model-generation response
        # cache, cross-user coalescing, and queue-depth admission.
        self._engine = AsyncTaskEngine(
            viewer_capacity=cfg.get_int("serving.task.queue.viewer.capacity"),
            solver_capacity=cfg.get_int("serving.task.queue.solver.capacity"),
            viewer_threads=cfg.get_int("serving.task.viewer.threads"),
            solver_threads=cfg.get_int("serving.task.solver.threads"))
        self._response_cache = ResponseCache(
            max_entries=cfg.get_int("serving.cache.max.entries"),
            enabled=cfg.get_boolean("serving.cache.enabled"),
            cache_state=cfg.get_boolean("serving.cache.state.enabled"))
        self._coalesce_enabled = cfg.get_boolean("serving.coalesce.enabled")
        self._admission = AdmissionController(
            viewer_max=cfg.get_int("serving.admission.queue.viewer.max"),
            solver_max=cfg.get_int("serving.admission.queue.solver.max"),
            enabled=cfg.get_boolean("serving.admission.enabled"))
        self._tasks = UserTaskManager(
            max_active_tasks=cfg.get_int("max.active.user.tasks"),
            completed_retention_ms=cfg.get_long(
                "completed.user.task.retention.time.ms"),
            max_cached_completed_monitor_tasks=cfg.get_int(
                "max.cached.completed.kafka.monitor.user.tasks"),
            max_cached_completed_admin_tasks=cfg.get_int(
                "max.cached.completed.kafka.admin.user.tasks"),
            max_cached_completed_tasks=cfg.get_int(
                "max.cached.completed.user.tasks"),
            max_cached_completed_cc_monitor_tasks=cfg.get_int(
                "max.cached.completed.cruise.control.monitor.user.tasks"),
            max_cached_completed_cc_admin_tasks=cfg.get_int(
                "max.cached.completed.cruise.control.admin.user.tasks"),
            retention_ms_by_class=retention_overrides,
            engine=self._engine)
        self._async_wait_s = cfg.get_long(
            "webserver.request.maxBlockTimeMs") / 1000.0
        self._reason_required = cfg.get_boolean("request.reason.required")

    @staticmethod
    def _configured_security(cfg: CruiseControlConfig) -> SecurityProvider:
        from .security import BasicSecurityProvider, SpnegoSecurityProvider
        cls_name = cfg.get("webserver.security.provider")
        if cls_name.endswith("BasicSecurityProvider"):
            return BasicSecurityProvider(
                credentials_file=cfg.get("webserver.auth.credentials.file") or "")
        if cls_name.endswith("SpnegoSecurityProvider"):
            return SpnegoSecurityProvider.from_config(cfg)
        if cls_name.endswith("JwtSecurityProvider"):
            from .security import JwtSecurityProvider
            return JwtSecurityProvider.from_config(cfg)
        import importlib
        module, _, name = cls_name.rpartition(".")
        return getattr(importlib.import_module(module), name)()

    def authenticate_readonly(self, headers: dict[str, str],
                              remote_addr: str = "") -> None:
        """Auth gate for the non-endpoint GET surfaces (/metrics, /openapi):
        any authenticated principal may read them; raises AuthenticationError
        when security is enabled and credentials are missing/invalid."""
        self._security.authenticate(headers, remote_addr)

    def metrics_text(self) -> str:
        """Prometheus exposition of the sensor registry + live state gauges
        (the JMX sensor surface of Sensors.md as a /metrics scrape)."""
        from ..utils.sensors import SENSORS
        extra: dict = {}
        try:
            # Live device-side telemetry (utils.xla_telemetry): memory
            # gauges refreshed at scrape time so the series track the
            # allocator, not the last model build.
            from ..utils import xla_telemetry
            xla_telemetry.refresh_device_gauges()
        except Exception:  # noqa: BLE001 — a scrape must not 500
            LOG.warning("device telemetry refresh failed", exc_info=True)
        try:
            st = self._cc.state()
            ms = st.get("MonitorState", {})
            extra["monitor_num_valid_windows"] = ms.get("numValidWindows", 0)
            extra["monitor_monitored_partitions_percentage"] = \
                ms.get("monitoringCoveragePct", 0.0)
            extra["monitor_total_num_partitions"] = \
                ms.get("totalNumPartitions", 0)
            extra["analyzer_balancedness_score"] = \
                st.get("AnalyzerState", {}).get("balancednessScore") or 0.0
            ex = st.get("ExecutorState", {})
            extra["executor_in_execution"] = \
                0.0 if ex.get("state") == "NO_TASK_IN_PROGRESS" else 1.0
            ad = st.get("AnomalyDetectorState", {})
            # selfHealing(Enabled|Disabled) are LISTS of type names
            # (AnomalyDetectorManager.state).
            for a_type in ad.get("selfHealingEnabled") or ():
                SENSORS.gauge("anomaly_detector_self_healing_enabled", 1.0,
                              labels={"anomaly_type": str(a_type)})
            for a_type in ad.get("selfHealingDisabled") or ():
                SENSORS.gauge("anomaly_detector_self_healing_enabled", 0.0,
                              labels={"anomaly_type": str(a_type)})
        except Exception:  # noqa: BLE001 — a scrape must not 500 on state
            LOG.warning("metrics state snapshot failed", exc_info=True)
        if self._fleet is not None:
            # Per-cluster fleet gauges (explicit labels; the ambient
            # cluster_label context covers per-cluster WORK, a scrape is
            # fleet-wide).
            for e in self._fleet.entries():
                labels = {"cluster": e.cluster_id}
                SENSORS.gauge("fleet_cluster_paused",
                              1.0 if e.paused else 0.0, labels=labels)
                if e.shape is not None:
                    SENSORS.gauge("fleet_cluster_brokers", e.shape[0],
                                  labels=labels)
                    SENSORS.gauge("fleet_cluster_partitions", e.shape[1],
                                  labels=labels)
        return SENSORS.render(extra)

    @property
    def purgatory(self) -> Purgatory:
        return self._purgatory

    @property
    def user_tasks(self) -> UserTaskManager:
        return self._tasks

    def shutdown(self) -> None:
        self._tasks.shutdown()
        self._engine.shutdown()

    @property
    def task_engine(self) -> AsyncTaskEngine:
        return self._engine

    @property
    def response_cache(self) -> ResponseCache:
        return self._response_cache

    @property
    def admission(self) -> AdmissionController:
        return self._admission

    def serving_stats(self) -> dict:
        """One snapshot of the serving front door's counters — what the
        load harness reads before/after a run (engine queues and service
        rates, cache hits/misses, coalesced joins, per-class sheds)."""
        return {"engine": self._engine.stats(),
                "cache": self._response_cache.stats(),
                "coalesced": self._tasks.coalesced,
                "admission": self._admission.stats()}

    # -- the dispatch pipeline ---------------------------------------------
    def handle(self, method: str, path: str, query_string: str = "",
               headers: dict[str, str] | None = None,
               remote_addr: str = "") -> tuple[int, dict, dict[str, str]]:
        """→ (http status, json body, extra response headers). Wraps the
        pipeline with the SLO registry's request classification
        (utils/slo.py): every front-door response — sheds and errors
        included — is one latency/error/shed event. Off means off: a
        disabled or absent registry costs one attribute read."""
        slo = getattr(self._cc, "slo", None)
        if slo is None or not slo.enabled:
            return self._handle_inner(method, path, query_string, headers,
                                      remote_addr)
        t0 = time.monotonic()
        status, body, out_headers = self._handle_inner(
            method, path, query_string, headers, remote_addr)
        slo.record_request(time.monotonic() - t0, status)
        return status, body, out_headers

    def _handle_inner(self, method: str, path: str, query_string: str = "",
                      headers: dict[str, str] | None = None,
                      remote_addr: str = "",
                      ) -> tuple[int, dict, dict[str, str]]:
        """→ (http status, json body, extra response headers)."""
        headers = headers or {}
        out_headers: dict[str, str] = {}
        try:
            endpoint = self._resolve(method, path)
            # The doas request param (ParameterUtils DO_AS_PARAM) is the
            # query-string form of trusted-proxy delegation: surface it to
            # the provider as the X-Do-As header when none is present.
            if "doas=" in query_string and "X-Do-As" not in headers:
                qs = urllib.parse.parse_qs(query_string)
                if qs.get("doas"):
                    headers = {**headers, "X-Do-As": qs["doas"][-1]}
            principal = self._security.authenticate(headers, remote_addr)
            self._security.authorize(principal, endpoint)
            query = urllib.parse.parse_qs(query_string, keep_blank_values=True)
            params = self._parse(endpoint, query)
            if self._reason_required and endpoint in _REASON_REQUIRED_ENDPOINTS \
                    and not params.get("reason"):
                raise ParameterParseError(
                    f"{endpoint.name} requires a reason parameter "
                    "(request.reason.required=true)")
            review_id = params.pop("review_id", None)
            if self._two_step and endpoint in REVIEWABLE_ENDPOINTS:
                if review_id is None:
                    info = self._purgatory.add(endpoint.name, query_string,
                                               principal.name)
                    return 200, responses.envelope(
                        {"reviewResult": info.to_dict(),
                         "message": "request parked for review"}), out_headers
                info = self._purgatory.submit(review_id, endpoint.name)
                # Execute EXACTLY what was reviewed: replay the parked query,
                # not whatever came with the resubmission (otherwise an
                # approved dry-run could smuggle in dryrun=false).
                query_string = info.query
                params = self._parse(endpoint, urllib.parse.parse_qs(
                    query_string, keep_blank_values=True))
                params.pop("review_id", None)
            # Fleet routing: ?cluster= selects the registered cluster's
            # facade (popped AFTER the purgatory replay so the reviewed
            # query's cluster wins over the resubmission's). A request
            # WITHOUT the parameter against a default facade that is
            # itself fleet-registered is that cluster's request too —
            # its solver work must share the device under the scheduler
            # and respect the pause state, not sneak around both.
            cluster_id = params.pop("cluster", None)
            if endpoint in (EndPoint.TRACE, EndPoint.SOLVER,
                            EndPoint.PROFILE):
                # Observability endpoints: cluster FILTERS recorded
                # traces/passes (it is a label on the record, not a
                # route) — valid without a fleet, and never subject to
                # the pause gate; PROFILE is process-wide by nature (one
                # device, one profiler gate). The request-class plugin
                # seam still applies (these bypass _dispatch, where other
                # endpoints' plugins are resolved).
                handler = self._request_plugin(endpoint)
                if handler is not None:
                    body = handler.handle(
                        self._cc, {**params, "cluster": cluster_id},
                        principal)
                elif endpoint is EndPoint.TRACE:
                    body = self._trace_handler(params, cluster_id)
                elif endpoint is EndPoint.SOLVER:
                    body = self._solver_handler(params, cluster_id)
                else:
                    body = self._profile_handler(params, out_headers)
            else:
                if cluster_id is None and self._fleet is not None:
                    cluster_id = self._fleet.cluster_id_of(self._cc)
                cc = self._route_cluster(endpoint, cluster_id)
                from ..utils.sensors import cluster_label
                if cluster_id is not None:
                    # The request's root span closes on the handler
                    # thread, after this label has been left: the trace
                    # takes its cluster from the root's attribute.
                    from ..utils.tracing import TRACER
                    TRACER.annotate_root(cluster=cluster_id)
                with cluster_label(cluster_id):
                    body = self._dispatch(endpoint, params, principal,
                                          query_string, headers, out_headers,
                                          cc=cc, cluster_id=cluster_id)
            if params.get("get_response_schema"):
                body = {**body, "responseSchema": _schema_of(body)}
            if params.get("json") is False:
                # json=false plaintext rendering (ParameterUtils wantJSON;
                # the reference writes text tables).
                out_headers["Content-Type"] = "text/plain; charset=utf-8"
                body = {"__text__": _as_text(body)}
            return 200, body, out_headers
        except ParameterParseError as e:
            return 400, self._error(str(e)), out_headers
        except UnknownClusterError as e:
            return 404, self._error(
                f"unknown cluster {e.args[0]!r}"), out_headers
        except ClusterPausedError as e:
            return 409, self._error(str(e)), out_headers
        except AuthenticationError as e:
            out_headers["WWW-Authenticate"] = self._security.challenge()
            return 401, self._error(str(e)), out_headers
        except AuthorizationError as e:
            return 403, self._error(str(e)), out_headers
        except ApiError as e:
            return e.status, self._error(str(e)), out_headers
        except TooManyUserTasksError as e:
            return 429, self._error(str(e)), out_headers
        except (AdmissionShedError, TaskQueueFullError) as e:
            # Serving admission (round 20): overload sheds BEFORE a task
            # exists, with a Retry-After derived from the observed
            # per-class service rate.
            out_headers["Retry-After"] = str(max(1, int(e.retry_after_s + 0.5)))
            return 429, self._error(str(e)), out_headers
        except TaskOwnershipError as e:
            return 403, self._error(str(e)), out_headers
        except NotEnoughValidWindowsError as e:
            return 503, self._error(f"load model not ready: {e}"), out_headers
        except BreakerOpenError as e:
            # Resilience layer (round 9): an open circuit breaker fails
            # fast and tells the client exactly when to come back.
            out_headers["Retry-After"] = str(max(1, int(e.retry_after_s + 0.5)))
            return 503, self._error(str(e)), out_headers
        except (KeyError, ValueError) as e:
            return 400, self._error(str(e)), out_headers
        except Exception as e:
            LOG.exception("internal error handling %s %s", method, path)
            return 500, self._error(f"{type(e).__name__}: {e}"), out_headers

    def _trace_handler(self, p: dict, cluster_id: str | None) -> dict:
        """GET /trace: recent span trees (newest first) from the tracer's
        ring, as OTLP-shaped JSON. ``?cluster=`` / ``?operation=`` filter;
        ``?entries=`` bounds the response."""
        from ..utils.tracing import TRACER
        traces = TRACER.traces(cluster=cluster_id,
                               operation=p.get("operation"),
                               limit=p.get("entries", 50))
        return responses.envelope({
            "tracingEnabled": TRACER.enabled,
            "numTraces": len(traces),
            "spansClosed": TRACER.spans_closed,
            "traces": traces})

    def _solver_handler(self, p: dict, cluster_id: str | None) -> dict:
        """GET /solver: recent recorded optimization passes (newest first)
        from the flight recorder's ring — per-goal acceptance density,
        candidate-kill attribution, per-round violation trajectories,
        deficit-sizing decisions, and per-dispatch controller state.
        ``?cluster=`` / ``?goal=`` filter; ``?entries=`` bounds the
        response."""
        from ..utils.flight_recorder import FLIGHT
        passes = FLIGHT.passes(cluster=cluster_id, goal=p.get("goal"),
                               limit=p.get("entries", 20))
        return responses.envelope({
            "flightRecorderEnabled": FLIGHT.enabled,
            "ringRounds": FLIGHT.ring_rounds,
            "numPasses": len(passes),
            "passesClosed": FLIGHT.passes_closed,
            "dispatchesRecorded": FLIGHT.dispatches_recorded,
            "passes": passes})

    def _profile_handler(self, p: dict,
                         out_headers: dict[str, str]) -> dict:
        """GET /profile: on-demand device profiling (utils.profiling).
        ``?duration_s=`` captures a jax.profiler (Perfetto/TensorBoard)
        trace of whatever the live process executes during the window;
        ``?microbench=true`` runs the in-process op-class while_loop
        marginals instead. Both hold the single-flight profiler gate — a
        concurrent request gets 503 + Retry-After (the breaker response
        shape)."""
        from ..utils.profiling import PROFILER, ProfilerBusyError
        if not self._config.get_boolean("profiling.enabled"):
            raise ApiError(403, "profiling is disabled "
                                "(profiling.enabled=false)")
        try:
            if p.get("microbench"):
                result = PROFILER.microbench(
                    brokers=p.get("brokers", 1000),
                    partitions=p.get("partitions", 100_000),
                    iters=p.get("iters", 16))
                return responses.envelope(
                    {"profile": "microbench", **result})
            if "duration_s" not in p:
                raise ParameterParseError(
                    "PROFILE requires duration_s (seconds to capture) or "
                    "microbench=true")
            result = PROFILER.capture(
                p["duration_s"],
                trace_dir=self._config.get("profiling.trace.dir"),
                max_duration_s=self._config.get_double(
                    "profiling.max.duration.seconds"))
            return responses.envelope({"profile": "trace", **result})
        except ProfilerBusyError as e:
            out_headers["Retry-After"] = str(
                max(1, int(e.retry_after_s + 0.5)))
            raise ApiError(503, str(e)) from None

    def _route_cluster(self, endpoint: EndPoint,
                       cluster_id: str | None) -> CruiseControl:
        """?cluster= → the registered cluster's facade. No parameter =
        the default facade (single-cluster deployments unchanged); solver
        endpoints are refused for paused clusters."""
        if cluster_id is None:
            return self._cc
        if self._fleet is None:
            raise ParameterParseError(
                "cluster parameter given but this server is not running "
                "a fleet (no FleetRegistry configured)")
        return self._fleet.get(
            cluster_id, for_operation=endpoint in _SOLVER_ENDPOINTS)

    # Reference plugin-key spelling for each endpoint
    # (CruiseControlParametersConfig / CruiseControlRequestConfig).
    _PLUGIN_KEY = {EndPoint.STOP_PROPOSAL_EXECUTION: "stop.proposal"}

    def _plugin(self, endpoint: EndPoint, suffix: str):
        key = self._PLUGIN_KEY.get(endpoint,
                                   endpoint.name.lower().replace("_", "."))
        spec = self._config.get(f"{key}.{suffix}.class")
        if not spec:
            return None
        from ..config.abstract_config import resolve_class
        return resolve_class(spec) if isinstance(spec, str) else spec

    def _request_plugin(self, endpoint: EndPoint):
        """Resolved ``<endpoint>.request.class`` handler instance or None
        — the ONE plugin seam, shared by _dispatch and the TRACE branch
        (which bypasses _dispatch for its no-route cluster semantics)."""
        custom = self._plugin(endpoint, "request")
        if custom is None:
            return None
        return custom() if isinstance(custom, type) else custom

    def _parse(self, endpoint: EndPoint, query: dict) -> dict:
        """Config-swappable parameter parsing
        (CruiseControlParametersConfig reflection): a configured
        ``<endpoint>.parameters.class`` replaces the built-in schema."""
        custom = self._plugin(endpoint, "parameters")
        if custom is not None:
            return custom()(query) if isinstance(custom, type) else custom(query)
        return parse_parameters(endpoint, query)

    def _resolve(self, method: str, path: str) -> EndPoint:
        if not path.startswith(URL_PREFIX):
            raise ApiError(404, f"unknown path {path!r}; expected {URL_PREFIX}/*")
        endpoint = endpoint_for_path(path[len(URL_PREFIX):])
        if endpoint is None:
            raise ApiError(404, f"unknown endpoint {path!r}")
        if method != endpoint.method:
            raise ApiError(405, f"{endpoint.name} requires {endpoint.method}")
        return endpoint

    @staticmethod
    def _error(message: str) -> dict:
        return responses.envelope({"errorMessage": message})

    # -- handlers ----------------------------------------------------------
    def _dispatch(self, endpoint: EndPoint, params: dict, principal: Principal,
                  query_string: str, headers: dict[str, str],
                  out_headers: dict[str, str],
                  cc: CruiseControl | None = None,
                  cluster_id: str | None = None) -> dict:
        """Journey shell around the pipeline (serving/journey.py): open
        the ambient per-request record, run the real dispatch under its
        scope, close it with the outcome. Off means off: a disabled or
        absent journey log falls straight through to the inner
        pipeline."""
        journeys = getattr(cc or self._cc, "journeys", None)
        if journeys is None or not journeys.enabled:
            return self._dispatch_inner(endpoint, params, principal,
                                        query_string, headers, out_headers,
                                        cc=cc, cluster_id=cluster_id)
        from ..serving.journey import journey_scope
        jny = journeys.open(endpoint.name, cluster=cluster_id)
        with journey_scope(jny):
            try:
                body = self._dispatch_inner(endpoint, params, principal,
                                            query_string, headers,
                                            out_headers, cc=cc,
                                            cluster_id=cluster_id)
            except BaseException as e:
                jny.note(error=type(e).__name__)
                journeys.close(jny, status="error")
                raise
        journeys.close(jny, status=jny.attrs.get("outcome", "ok"))
        return body

    def _dispatch_inner(self, endpoint: EndPoint, params: dict,
                        principal: Principal, query_string: str,
                        headers: dict[str, str],
                        out_headers: dict[str, str],
                        cc: CruiseControl | None = None,
                        cluster_id: str | None = None) -> dict:
        cc = cc or self._cc
        p = params
        handler = self._request_plugin(endpoint)
        if handler is not None:
            # CruiseControlRequestConfig reflection: the configured request
            # class handles the endpoint end to end.
            return handler.handle(cc, p, principal)
        from ..serving.journey import current_journey
        jny = current_journey()
        if endpoint in _SYNC_ENDPOINTS:
            # One segment for inline endpoints: their wall IS response
            # assembly (STATE is the loadgen mix's heaviest read).
            with jny.seg("render"):
                return self._sync_handler(endpoint, p, principal, cc)
        # Async (model-building) endpoints run as user tasks. The
        # cluster label must be re-established INSIDE the work callable:
        # ContextVars do not cross into the user-task thread pool, so the
        # handle()-level context alone would label nothing async.
        # COMPARE_FUTURES validation runs ONCE here — a template typo
        # 400s before a user task is ever created — but the live-seed
        # MODEL BUILD is deferred into a lazy once-supplier shared by
        # the work closure AND the fleet-coalesced payload path:
        # _dispatch runs on the HTTP handler thread on EVERY request,
        # including each poll of an in-flight task, and must not pay a
        # cluster-model build the task dedup would discard.
        futures_req = futures_live = None
        if endpoint is EndPoint.COMPARE_FUTURES:
            futures_req = self._futures_request(cc, p)

            @lru_cache(maxsize=1)
            def futures_live():
                from ..futures.evaluator import live_seed_from
                return live_seed_from(cc)
        # Serving front door (round 20): on a NEW request (no User-Task-ID
        # presented), try the generation-keyed response cache, build the
        # coalescing key, and run admission — in that order, so a cache
        # hit or a coalesced join is never shed (neither consumes solver
        # capacity). Polls of existing tasks skip all three.
        # Header names are case-insensitive on the wire, and urllib (the
        # repo's own client.Responder) sends this one as "User-task-id":
        # an exact-case lookup turned every poll of a non-coalescible
        # operation into a NEW task until admission shed them.
        resume_id = next((v for k, v in headers.items()
                          if k.lower() == USER_TASK_HEADER.lower()), None)
        store_key = coalesce_key = None
        if resume_id is None:
            with jny.seg("cache_lookup") as cache_seg:
                identity = self._response_identity(cc, cluster_id)
                if identity is not None:
                    generation, fingerprint = identity
                    pkey = canonical_params(endpoint.name, p,
                                            allowed=CACHEABLE_ENDPOINTS)
                    if pkey is not None:
                        store_key = (cluster_id, endpoint.name, pkey,
                                     generation, fingerprint)
                        cached = self._response_cache.get(store_key)
                        if cached is not None:
                            cache_seg.set(result="hit")
                            jny.note(outcome="cache_hit")
                            out_headers["X-Serving-Cache"] = "hit"
                            return cached
                    cache_seg.set(result="miss")
                    if self._coalesce_enabled:
                        ckey_params = canonical_params(
                            endpoint.name, p, allowed=COALESCIBLE_ENDPOINTS)
                        if ckey_params is not None:
                            coalesce_key = (cluster_id, endpoint.name,
                                            ckey_params, generation,
                                            fingerprint)
            if not self._tasks.has_inflight(coalesce_key):
                klass = task_class_of(endpoint.name)
                with jny.seg("admission", **{"class": klass.value}):
                    self._admission.admit(
                        klass, self._engine.queue_depth(klass),
                        self._engine.service_time_s(klass))
        work = self._async_work(endpoint, p, cc, futures_req=futures_req,
                                futures_live=futures_live)
        if cluster_id is not None:
            inner_work = work

            def work(inner=inner_work, cid=cluster_id):
                from ..utils.sensors import cluster_label
                with cluster_label(cid):
                    return inner()

        if jny.recording:
            # Same rewrap discipline as the cluster label just above:
            # ContextVars do not cross into the worker pools, so the
            # journey scope is re-established inside the work callable —
            # the model-build/solve stamps land on THIS request's record
            # whichever thread runs them.
            journey_inner = work

            def work(inner=journey_inner, j=jny):
                from ..serving.journey import journey_scope
                with journey_scope(j):
                    return inner()

        work = self._schedule_fleet_work(endpoint, cluster_id, work, cc, p,
                                         futures_req=futures_req,
                                         futures_live=futures_live)
        if store_key is not None:
            # Outermost wrapper (outside the fleet scheduling) so the
            # cached body is the FINAL envelope whichever path produced
            # it — solo work, scheduled job, or coalesced futures payload.
            caching_inner = work

            def work(inner=caching_inner, key=store_key, j=jny):
                body = inner()
                with j.seg("cache_store"):
                    self._response_cache.put(key, body)
                return body

        from ..utils.tracing import TRACER
        parent = TRACER.current_span()
        if parent is not None:
            # The trace context crosses the pool the way the cluster label
            # and the journey do, by re-entry inside the work callable:
            # the task's spans keep this request's trace id on whichever
            # thread runs them, also after a 202 has closed the root (a
            # poll is its own http.request with the same userTaskId).
            traced_inner = work

            def work(inner=traced_inner, parent=parent):
                with TRACER.attach(parent):
                    return inner()

        info = self._tasks.get_or_create_task(
            endpoint.name, query_string, work,
            task_id=resume_id, client=principal.name,
            coalesce_key=coalesce_key)
        out_headers[USER_TASK_HEADER] = info.task_id
        engine_task = getattr(info, "engine_task", None)
        # Follower ⟺ the user task rides another task's engine record
        # (user_tasks.get_or_create_task coalescing). A follower's wall
        # is spent WAITING on the leader's future — its own journey has
        # no work segments, so the wait itself is the named segment.
        follower = engine_task is not None \
            and engine_task.task_id != info.task_id
        if jny.recording and coalesce_key is not None \
                and engine_task is not None:
            jny.note(coalesce="follower" if follower else "leader")
        wait_t0 = jny.now() if follower else 0.0
        try:
            exc = info.future.exception(timeout=self._async_wait_s)
        except FuturesTimeoutError:
            if follower:
                jny.add("coalesce_wait", jny.now() - wait_t0)
            else:
                self._stamp_queue_wait(jny, engine_task)
            jny.note(outcome="in_progress")
            progress = info.progress.to_list() if info.progress else []
            return responses.envelope({
                "progress": [{"operation": endpoint.name, **p}
                             for p in progress],
                "message": f"operation still running; poll with "
                           f"{USER_TASK_HEADER} {info.task_id}"})
        if follower:
            jny.add("coalesce_wait", jny.now() - wait_t0)
        else:
            self._stamp_queue_wait(jny, engine_task)
        if exc is not None:
            if isinstance(exc, ApiError):
                raise exc
            if isinstance(exc, BreakerOpenError):
                raise exc  # handle() renders 503 + Retry-After
            if isinstance(exc, (ParameterParseError, ValueError, KeyError)):
                raise ApiError(400, str(exc))
            if isinstance(exc, NotEnoughValidWindowsError):
                raise ApiError(503, f"load model not ready: {exc}")
            raise ApiError(500, f"{type(exc).__name__}: {exc}")
        return info.future.result()

    @staticmethod
    def _stamp_queue_wait(jny, engine_task) -> None:
        """One ``queue_wait`` segment from the engine's lifecycle record
        (started − enqueued on the engine's monotonic seam) — stamped
        once the task left its class queue; a still-queued 202 has no
        wait to report yet (its poll will)."""
        if not jny.recording or engine_task is None \
                or engine_task.started_s <= 0.0:
            return
        jny.add("queue_wait",
                engine_task.started_s - engine_task.enqueued_s,
                **{"class": engine_task.klass.value})

    @staticmethod
    def _response_identity(cc: CruiseControl,
                           cluster_id: str | None) -> tuple | None:
        """(load-model generation, goal-chain fingerprint) — the serving
        cache/coalescing identity (round 20) — or None when the facade
        cannot provide one (a plugin facade without a monitor, say):
        without an identity nothing is cached or coalesced, never the
        other way around."""
        try:
            generation = int(cc.load_monitor.model_generation)
            from ..fleet.megabatch import solver_config_fingerprint
            fingerprint = solver_config_fingerprint(cc.config)
        except Exception:  # noqa: BLE001 — identity is best-effort
            return None
        return generation, fingerprint

    def _schedule_fleet_work(self, endpoint: EndPoint,
                             cluster_id: str | None, work,
                             cc: CruiseControl | None = None,
                             p: dict | None = None,
                             futures_req: dict | None = None,
                             futures_live=None):
        """Wrap a fleet-routed solver work callable so it runs as an
        ON_DEMAND FleetScheduler job: the user-task thread submits and
        blocks on the future (202-poll behavior unchanged), while the
        device itself is shared under the scheduler's priorities and
        starvation bound. Inline when no worker is draining (embedded or
        test schedulers) — blocking on a future nobody serves would hang
        the task forever. Model-build reads (_MODEL_BUILD_ENDPOINTS,
        round 20) schedule too — the handler layer no longer touches the
        device at all — but keep their monitor-class semantics (no pause
        gate, no breaker accounting as solver traffic)."""
        if cluster_id is None or self._fleet is None \
                or (endpoint not in _SOLVER_ENDPOINTS
                    and endpoint not in _MODEL_BUILD_ENDPOINTS):
            return work
        sched = self._fleet.scheduler
        if sched is None or not sched.running:
            return work
        if endpoint is EndPoint.PROPOSALS and cc is not None \
                and p is not None and not any(
                    p.get(k) for k in ("goals", "ignore_proposal_cache",
                                       "use_ready_default_goals",
                                       "fast_mode", "data_from")):
            # A default-chain PROPOSALS request with a fresh cache needs
            # NO solver time — answering inline keeps the pre-fleet
            # instant-cached-read behavior instead of parking a zero-work
            # request behind another cluster's multi-second solve.
            try:
                if cc._cached_proposals_fresh(
                        cc._load_monitor.model_generation):
                    return work
            except Exception:  # noqa: BLE001 — fall through to the queue
                pass
        from ..fleet.scheduler import JobKind

        batch_key = payload = None
        if endpoint is EndPoint.COMPARE_FUTURES and sched.coalescing \
                and p is not None:
            # Futures coalesce with precomputes (round 15): the request
            # submits under its cluster's precompute batch key plus a
            # runner payload, so a scheduler turn that picks either
            # drains both — the futures' decision solves and the paced
            # cache fills share one worker turn (and, when compatible,
            # one batched program). Solo fallback (``work``) covers
            # shutdown/inline execution unchanged.
            try:
                batch_key = \
                    self._precompute_key_for(cluster_id)
            except Exception:  # noqa: BLE001 — hint only; run solo
                batch_key = None
            if batch_key is not None and futures_req is not None:
                from ..futures.evaluator import FuturesPayload
                req = futures_req
                payload = FuturesPayload(
                    cluster_id, req["templates"], req["num_futures"],
                    req["seed"], req["ticks"],
                    include_present=req["include_present"],
                    wrap=responses.envelope,
                    # _dispatch's lazy once-supplier: the live seed
                    # builds at most ONE cluster model per request, on
                    # the worker thread, shared with the solo work path.
                    live_supplier=futures_live)
            if payload is None:
                # No payload to drain under the key: submit as a plain
                # solo job rather than a batch-keyed job with nothing
                # coalescible behind it.
                batch_key = None

        # Captured on the handler thread (the journey scope does not
        # cross into the engine worker that runs ``scheduled``): the
        # sched_wait segment is submit → the scheduler's device turn.
        from ..serving.journey import current_journey
        jny = current_journey()

        def scheduled():
            from concurrent.futures import CancelledError
            job = work
            if jny.recording:
                t0 = jny.now()

                def job(inner=work, j=jny, t0=t0):
                    j.add("sched_wait", j.now() - t0)
                    return inner()

            try:
                return sched.submit(cluster_id, JobKind.ON_DEMAND,
                                    job, batch_key=batch_key,
                                    payload=payload).result()
            except CancelledError:
                # Scheduler shut down before the job ran: a meaningful
                # 503 beats an opaque "CancelledError:" 500.
                raise ApiError(
                    503, "fleet scheduler shut down before the request "
                    "could run; retry once the fleet is back up")

        return scheduled

    def _precompute_key_for(self, cluster_id: str) -> tuple | None:
        """The cluster's precompute coalescing key (None when it has no
        recorded bucket yet)."""
        from ..fleet.megabatch import precompute_batch_key
        return precompute_batch_key(self._fleet.entry(cluster_id))

    def _futures_request(self, cc: CruiseControl, p: dict) -> dict:
        """Resolve + validate a COMPARE_FUTURES request against the
        cluster's config caps (shared by the direct work path and the
        fleet-coalesced payload path; template typos 400 up front)."""
        from ..futures.generator import FUTURE_TEMPLATES
        cfg = cc.config
        templates = [t for t in p.get("templates", ()) if t]
        live_templates = []
        for t in templates:
            if t not in FUTURE_TEMPLATES:
                raise ParameterParseError(
                    f"unknown futures template {t!r}; expected one of "
                    f"{', '.join(sorted(FUTURE_TEMPLATES))}")
            if FUTURE_TEMPLATES[t].requires_live:
                live_templates.append(t)
        if live_templates:
            # Validated ONCE outside the template loop. Only CHEAP
            # checks run here — this executes on the HTTP handler
            # thread for every request, including task polls; the
            # cluster-model build itself is deferred to _dispatch's
            # lazy once-supplier on the worker thread.
            t = live_templates[0]
            if not cfg.get_boolean("futures.live.seed.enabled"):
                raise ParameterParseError(
                    f"template {t!r} requires the live-cluster seam "
                    "(futures.live.seed.enabled=true)")
            if not cc.load_monitor.window_times():
                # Eager 400 with the REAL cause for the common case
                # (no stable windows yet — probe is a list read, no
                # model build); a build failure past this probe still
                # surfaces as the worker path's 400/503.
                raise ParameterParseError(
                    f"template {t!r} requires the live cluster model, "
                    "which is not ready yet (monitor still warming)")
        n = p.get("num_futures", cfg.get_int("futures.default.count"))
        n = max(1, min(int(n), cfg.get_int("futures.max.count")))
        ticks = p.get("ticks", cfg.get_int("futures.default.ticks"))
        ticks = max(1, min(int(ticks), cfg.get_int("futures.max.ticks")))
        return {"templates": templates or None, "num_futures": n,
                "seed": p.get("seed", 0), "ticks": ticks,
                "include_present": p.get("include_present", True)}

    def _sync_handler(self, endpoint: EndPoint, p: dict,
                      principal: Principal,
                      cc: CruiseControl | None = None) -> dict:
        cc = cc or self._cc
        if endpoint is EndPoint.FLEET:
            if self._fleet is None:
                return responses.envelope(
                    {"numClusters": 0, "clusters": {},
                     "message": "fleet mode not enabled"})
            return responses.envelope(self._fleet.state())
        if endpoint is EndPoint.HEALS:
            # GET /heals: correlated anomaly-lifecycle chains from the
            # routed facade's heal ledger (per-facade journals — a
            # fleet's ?cluster= routes, a twin's ledger stays its own).
            ledger = cc.heal_ledger
            chains = ledger.chains(anomaly_type=p.get("anomaly_type"),
                                   limit=p.get("entries", 20))
            return responses.envelope({
                "healLedgerEnabled": ledger.enabled,
                "numChains": len(chains),
                "chainsOpened": ledger.chains_opened,
                "chainsResolved": ledger.chains_resolved,
                "healsOpen": ledger.open_count(),
                "meanTimeToStartFixMs": ledger.mean_time_to_start_fix_ms(),
                "chains": chains})
        if endpoint is EndPoint.FORECAST:
            # GET /forecast: the routed facade's forecast engine —
            # per-broker current-vs-projected loads, horizon geometry,
            # and the predictive detector's hit-rate counters.
            refresh = bool(p.get("refresh", False))

            def _forecast_work():
                return responses.envelope(
                    cc.forecast_state(refresh=refresh))

            if refresh and self._fleet is not None:
                # refresh=true runs the jitted fit — device work, maybe
                # a first-shape compile. In fleet mode it shares the
                # device under the scheduler like every other
                # solver-time request instead of contending from the
                # HTTP handler thread mid-solve (the _SOLVER_ENDPOINTS
                # discipline; the cached read stays inline).
                sched = self._fleet.scheduler
                cid = self._fleet.cluster_id_of(cc)
                if sched is not None and sched.running \
                        and cid is not None:
                    from concurrent.futures import CancelledError

                    from ..fleet.scheduler import JobKind
                    try:
                        return sched.submit(
                            cid, JobKind.ON_DEMAND,
                            _forecast_work).result()
                    except CancelledError:
                        raise ApiError(
                            503, "fleet scheduler shut down before the "
                            "forecast refresh could run; retry once the "
                            "fleet is back up")
            return _forecast_work()
        if endpoint is EndPoint.JOURNEYS:
            # GET /journeys: the routed facade's completed-request ring
            # (serving/journey.py) — per-request latency attribution,
            # newest first. ``?endpoint=`` / ``?entries=`` filter.
            journeys = getattr(cc, "journeys", None)
            if journeys is None:
                return responses.envelope({
                    "journeysEnabled": False, "numJourneys": 0,
                    "journeys": []})
            entries = journeys.entries(endpoint=p.get("endpoint"),
                                       limit=p.get("entries", 50))
            return responses.envelope({
                **journeys.stats(),
                "numJourneys": len(entries),
                "journeys": entries})
        if endpoint is EndPoint.SLO:
            # GET /slo: the routed facade's objective registry
            # (utils/slo.py) — per-window burn rates, remaining budget,
            # burning verdicts — plus the burn detector's lifecycle.
            slo = getattr(cc, "slo", None)
            if slo is None:
                return responses.envelope(
                    {"sloEnabled": False, "objectives": {}})
            body = slo.state()
            objective = p.get("objective")
            if objective:
                body["objectives"] = {
                    name: entry
                    for name, entry in body["objectives"].items()
                    if name == objective}
            detector = getattr(cc, "slo_burn_detector", None)
            if detector is not None:
                body["burnDetector"] = detector.state()
            return responses.envelope(body)
        if endpoint is EndPoint.REDTEAM:
            # GET /redteam: the mined worst-case regression frontier
            # (redteam/, round 22) — per-entry SLO margins, verdict
            # strings, replay recipes, the forecaster blind-spot
            # report, and the canonical library's margin bar. Serves
            # the COMMITTED frontier file; mining never runs on the
            # request path.
            if not cc.config.get_boolean("redteam.enabled"):
                raise ParameterParseError(
                    "redteam.enabled=false: the mined frontier surface "
                    "is disabled on this cluster")
            from ..redteam.frontier import load_frontier
            path = cc.config.get_string("redteam.frontier.path")
            frontier = load_frontier(path)
            if frontier is None:
                return responses.envelope({
                    "redteamEnabled": True, "frontierPath": path,
                    "frontierFound": False, "numEntries": 0,
                    "frontier": [],
                    "hint": "no frontier file; run the miner — "
                            "python bench.py --redteam"})
            entries = list(frontier.get("frontier") or [])
            limit = p.get("entries")
            if limit is not None:
                entries = entries[:max(0, int(limit))]
            if not p.get("blind_spots", True):
                entries = [{k: v for k, v in e.items()
                            if k != "blindSpot"} for e in entries]
            return responses.envelope({
                "redteamEnabled": True, "frontierPath": path,
                "frontierFound": True,
                "sweepSeed": frontier.get("sweepSeed"),
                "generationsRun": frontier.get("generationsRun"),
                "evals": frontier.get("evals"),
                "replays": frontier.get("replays"),
                "partial": frontier.get("partial"),
                "partialReason": frontier.get("partialReason"),
                "library": frontier.get("library"),
                "foundBelowLibrary": frontier.get("foundBelowLibrary"),
                "blindSpotCount": frontier.get("blindSpotCount"),
                "numEntries": len(entries),
                "frontier": entries})
        if endpoint is EndPoint.STATE:
            key = None
            if self._response_cache.cache_state:
                # Opt-in only (serving.cache.state.enabled): /state is
                # NOT generation-pure — executor progress and anomaly
                # state move without a model-generation bump, so this
                # trades freshness for poll throughput, explicitly.
                cid = self._fleet.cluster_id_of(cc) \
                    if self._fleet is not None else None
                identity = self._response_identity(cc, cid)
                if identity is not None:
                    key = (cid, "STATE",
                           tuple(sorted((k, repr(v))
                                        for k, v in p.items())),
                           *identity)
                    cached = self._response_cache.get(key)
                    if cached is not None:
                        return cached
            body = responses.envelope(cc.state(
                p.get("substates", ()),
                super_verbose=p.get("super_verbose", False)))
            self._response_cache.put(key, body)
            return body
        if endpoint is EndPoint.KAFKA_CLUSTER_STATE:
            return responses.kafka_cluster_state(cc._admin, p.get("topic", ""))
        if endpoint is EndPoint.USER_TASKS:
            tasks = self._tasks.all_tasks()
            ids = set(p.get("user_task_ids", ()))
            if ids:
                tasks = [t for t in tasks if t.task_id in ids]
            eps = set(p.get("endpoints", ()))
            if eps:
                tasks = [t for t in tasks if t.endpoint in eps]
            clients = set(p.get("client_ids", ()))
            if clients:
                tasks = [t for t in tasks if t.client in clients]
            # types filter: task state names, e.g. Active / Completed /
            # CompletedWithError (UserTaskManager.TaskState).
            states = {s.lower() for s in p.get("types", ())}
            if states:
                tasks = [t for t in tasks
                         if t.to_dict()["Status"].lower() in states]
            tasks = tasks[: p.get("entries", len(tasks))]
            if p.get("fetch_completed_task"):
                # Return the stored final response of each completed task
                # instead of the summary row (FETCH_COMPLETED_TASK_PARAM).
                out = []
                for t in tasks:
                    body = None
                    if t.future is not None and t.future.done() \
                            and not t.future.exception():
                        body = t.future.result()
                    out.append({**t.to_dict(), "originalResponse": body})
                return responses.envelope({"userTasks": out})
            return responses.envelope(
                {"userTasks": [t.to_dict() for t in tasks]})
        if endpoint is EndPoint.REVIEW_BOARD:
            board = self._purgatory.review_board()
            ids = set(p.get("review_ids", ()))
            if ids:
                board = [r for r in board if r["Id"] in ids]
            return responses.envelope({"requestInfo": board})
        if endpoint is EndPoint.PERMISSIONS:
            return responses.envelope(
                {"user": principal.name, "role": principal.role.name})
        if endpoint is EndPoint.REVIEW:
            out = []
            for rid in p.get("approve", ()):
                out.append(self._purgatory.approve(rid, p.get("reason", "")).to_dict())
            for rid in p.get("discard", ()):
                out.append(self._purgatory.discard(rid, p.get("reason", "")).to_dict())
            return responses.envelope({"requestInfo": out})
        if endpoint is EndPoint.PAUSE_SAMPLING:
            cc.pause_metric_sampling(p.get("reason", ""))
            return responses.envelope({"message": "metric sampling paused"})
        if endpoint is EndPoint.RESUME_SAMPLING:
            cc.resume_metric_sampling(p.get("reason", ""))
            return responses.envelope({"message": "metric sampling resumed"})
        if endpoint is EndPoint.STOP_PROPOSAL_EXECUTION:
            cc.stop_proposal_execution(
                force_stop=p.get("force_stop", False),
                stop_external_agent=p.get("stop_external_agent", False))
            return responses.envelope({"message": "execution stop requested"})
        if endpoint is EndPoint.BOOTSTRAP:
            if not p.get("developer_mode", False):
                # BootstrapRequest.java:29: without developer_mode=true the
                # endpoint does nothing but say so.
                return responses.envelope({
                    "message": "This endpoint is used only for development "
                               "purposes in developer_mode=true."})
            start = p.get("start")
            if start is None:
                raise ParameterParseError("bootstrap requires start")
            cc.load_monitor.bootstrap(start, p.get("end", int(time.time() * 1000)),
                                      p.get("clearmetrics", True))
            return responses.envelope({"message": "bootstrap started"})
        if endpoint is EndPoint.TRAIN:
            start = p.get("start", 0)
            end = p.get("end", int(time.time() * 1000))
            return responses.envelope(
                {"message": "training pass completed",
                 **cc.load_monitor.train(start, end)})
        if endpoint is EndPoint.RIGHTSIZE:
            res = cc.rightsize(p.get("numbrokerstoadd", 0),
                               p.get("partition_count", 0), p.get("topic"))
            return responses.optimization_result(res)
        if endpoint is EndPoint.ADMIN:
            return self._admin_handler(p, cc)
        raise ApiError(500, f"no sync handler for {endpoint.name}")

    def _admin_handler(self, p: dict,
                       cc: CruiseControl | None = None) -> dict:
        from ..detector.anomaly import AnomalyType
        from ..executor.concurrency import ExecutionConcurrencyManager
        cc = cc or self._cc
        # Validate EVERY name-typed argument before applying ANY mutation:
        # a typo anywhere must 400 the whole request, not leave the earlier
        # toggles silently applied under an error response.
        healing_toggles = [(n, False) for n in
                           p.get("disable_self_healing_for", ())] + \
                          [(n, True) for n in
                           p.get("enable_self_healing_for", ())]
        for name, _e in healing_toggles:
            if name.upper() not in AnomalyType.__members__:
                raise ParameterParseError(
                    f"unknown anomaly type {name!r}; expected one of "
                    f"{', '.join(AnomalyType.__members__)}")
        adjuster_toggles = [(n, False) for n in
                            p.get("disable_concurrency_adjuster_for", ())] + \
                           [(n, True) for n in
                            p.get("enable_concurrency_adjuster_for", ())]
        for name, _e in adjuster_toggles:
            if name.upper() not in ExecutionConcurrencyManager.ADJUSTER_TYPES:
                raise ParameterParseError(
                    f"unknown concurrency type {name!r}; expected one of "
                    f"{', '.join(ExecutionConcurrencyManager.ADJUSTER_TYPES)}")
        changed: dict[str, Any] = {}
        for name, enabled in healing_toggles:
            old = cc.anomaly_detector.set_self_healing_for(
                AnomalyType[name.upper()], enabled)
            changed.setdefault("selfHealingEnabledBefore" if enabled
                               else "selfHealingDisabledBefore", {})[name] = old
        conc = {k: p[k] for k in
                ("concurrent_partition_movements_per_broker",
                 "concurrent_intra_broker_partition_movements",
                 "concurrent_leader_movements") if k in p}
        if conc:
            changed["concurrency"] = cc.set_concurrency(
                inter_broker_per_broker=conc.get(
                    "concurrent_partition_movements_per_broker"),
                intra_broker_per_broker=conc.get(
                    "concurrent_intra_broker_partition_movements"),
                leadership_cluster=conc.get("concurrent_leader_movements"))
        for name, enabled in adjuster_toggles:
            old = cc.executor.set_concurrency_adjuster_for(name, enabled)
            changed.setdefault("concurrencyAdjusterEnabledBefore", {})[name] = old
        if "min_isr_based_concurrency_adjustment" in p:
            changed["minIsrBasedAdjustmentBefore"] = \
                cc.executor.set_min_isr_based_adjustment(
                    p["min_isr_based_concurrency_adjustment"])
        dropped_removed = p.get("drop_recently_removed_brokers", ())
        if dropped_removed:
            cc.drop_recently_removed_brokers(dropped_removed)
            changed["droppedRecentlyRemoved"] = sorted(dropped_removed)
        dropped_demoted = p.get("drop_recently_demoted_brokers", ())
        if dropped_demoted:
            cc.drop_recently_demoted_brokers(dropped_demoted)
            changed["droppedRecentlyDemoted"] = sorted(dropped_demoted)
        return responses.envelope(changed or {"message": "no admin action given"})

    def _what_if_handler(self, cc: CruiseControl, p: dict) -> dict:
        """PROPOSALS ``?what_if=<scenario>``: replay a canonical scenario
        on the digital twin (testing/simulator.py) and return the scored
        trajectory — the time-dimension extension of the proposals dry
        run. ``what_if=random:<template>:<seed>`` replays a
        generator-sampled scenario (futures/generator.py) instead —
        every sampled row of a COMPARE_FUTURES answer is replayable this
        way — and ``what_if=mined:<frontier-id>`` replays a mined
        red-team frontier entry (redteam/, round 22) from its recorded
        recipe. The simulator wires its OWN backend/executor, so this
        cluster's executor state is never touched; tick counts are capped
        by ``scenario.what.if.max.ticks`` since a replay is real solver
        work."""
        from ..testing.simulator import CANONICAL_SCENARIOS, run_scenario
        name = p["what_if"]
        default_seed = 0
        if name.startswith("mined:"):
            # Mined frontier replay (redteam/, round 22): the entry's
            # recipe rebuilds the exact perturbed spec; the default sim
            # seed is the entry's recorded replaySeed so a bare
            # what_if=mined:<id> reproduces the mined score byte-for-
            # byte (what_if_seed still overrides for exploration).
            from ..redteam.frontier import entry_spec, load_frontier
            if not cc.config.get_boolean("redteam.enabled"):
                raise ParameterParseError(
                    "redteam.enabled=false: mined frontier replays are "
                    "disabled on this cluster")
            path = cc.config.get_string("redteam.frontier.path")
            frontier = load_frontier(path)
            entries = (frontier or {}).get("frontier") or []
            if not entries:
                raise ParameterParseError(
                    f"mined frontier is empty (no frontier file at "
                    f"{path!r}); run the miner — python bench.py "
                    "--redteam — to populate it")
            by_id = {e["id"]: e for e in entries}
            wanted = name[len("mined:"):]
            entry = by_id.get(wanted)
            if entry is None:
                raise ParameterParseError(
                    f"unknown mined frontier id {wanted!r}; known ids: "
                    f"{', '.join(sorted(by_id))}")
            spec = entry_spec(entry)
            default_seed = int(entry.get("replaySeed", 0))
        elif name.startswith("random:"):
            from ..futures.generator import FUTURE_TEMPLATES, sample_scenario
            parts = name.split(":")
            template = parts[1] if len(parts) >= 2 else ""
            if len(parts) not in (2, 3) or template not in FUTURE_TEMPLATES:
                raise ParameterParseError(
                    f"unknown futures template {template!r} in "
                    f"what_if={name!r}; expected "
                    "random:<template>[:<seed>] with a template from: "
                    f"{', '.join(sorted(FUTURE_TEMPLATES))}")
            if FUTURE_TEMPLATES[template].requires_live:
                # A requires_live template's standalone spec is a bare
                # renamed BASE_SPEC (its content lives in the
                # evaluator's live seam): replaying it here would serve
                # a meaningless synthetic trajectory under the
                # template's name. COMPARE_FUTURES is the surface that
                # answers it — same 400 discipline as there.
                raise ParameterParseError(
                    f"template {template!r} requires the live-cluster "
                    "seam and has no standalone replay; request it via "
                    "COMPARE_FUTURES (templates parameter) instead")
            try:
                gen_seed = int(parts[2]) if len(parts) == 3 else 0
            except ValueError:
                raise ParameterParseError(
                    f"bad generator seed in what_if={name!r}: "
                    f"{parts[2]!r} is not an integer")
            spec = sample_scenario(template, gen_seed)
        else:
            if name not in CANONICAL_SCENARIOS:
                raise ParameterParseError(
                    f"unknown what_if scenario {name!r}; expected one of "
                    f"{', '.join(sorted(CANONICAL_SCENARIOS))} or "
                    "random:<template>:<seed>")
            spec = CANONICAL_SCENARIOS[name]
        cap = cc.config.get_int("scenario.what.if.max.ticks")
        ticks = p.get("what_if_ticks")
        ticks = min(spec.ticks, cap) if ticks is None \
            else max(1, min(int(ticks), cap))
        seed = p.get("what_if_seed", default_seed)
        result = run_scenario(spec, seed=seed, ticks=ticks)
        return responses.envelope({
            "operation": "what_if", "dryrun": True, "executed": False,
            "scenario": spec.name, "seed": seed, "ticks": ticks,
            "score": result.score.as_dict(),
            "finalAssignmentDigest": result.assignment_digest,
            "events": result.events})

    def _sanity_check_hard_goals(self, endpoint: EndPoint, p: dict,
                                 cc: CruiseControl | None = None) -> None:
        """Explicitly requested goals must include every configured hard
        goal unless skip_hard_goal_check=true
        (KafkaCruiseControlUtils.sanityCheckGoals:426-437; a sole
        PreferredLeaderElectionGoal is exempt). Mode-derived chains
        (kafka_assigner, rebalance_disk) are not user goal lists and skip
        the check."""
        explicit = p.get("goals")
        if endpoint not in _HARD_GOAL_CHECKED_ENDPOINTS or not explicit \
                or p.get("skip_hard_goal_check", False):
            return
        short = [g.rsplit(".", 1)[-1] for g in explicit]
        if short == ["PreferredLeaderElectionGoal"]:
            return
        hard = {g.rsplit(".", 1)[-1]
                for g in (cc or self._cc)._config.get_list("hard.goals")}
        missing = sorted(hard - set(short))
        if missing:
            raise ParameterParseError(
                f"Missing hard goals {missing} in the provided goals: "
                f"{short}. Add skip_hard_goal_check=true parameter to "
                "ignore this sanity check.")

    def _async_work(self, endpoint: EndPoint, p: dict,
                    cc: CruiseControl | None = None,
                    futures_req: dict | None = None,
                    futures_live=None):
        cc = cc or self._cc
        dryrun = p.get("dryrun", True)
        goals = _resolve_goal_names(p)
        self._sanity_check_hard_goals(endpoint, p, cc)
        use_ready = p.get("use_ready_default_goals", False)
        fast_mode = p.get("fast_mode", False)
        reason = p.get("reason", "")
        verbose = p.get("verbose", False)

        def exec_scope():
            """Per-request execution overrides (ParameterUtils): scoped to
            the operation via the facade's context manager, so a dry run,
            an empty result, or an exception never leaks them into a later
            execution."""
            import contextlib
            if dryrun:
                return contextlib.nullcontext()
            conc = {}
            if "concurrent_partition_movements_per_broker" in p:
                conc["inter_broker_per_broker"] = \
                    p["concurrent_partition_movements_per_broker"]
            if "concurrent_intra_broker_partition_movements" in p:
                conc["intra_broker_per_broker"] = \
                    p["concurrent_intra_broker_partition_movements"]
            if "concurrent_leader_movements" in p:
                conc["leadership_cluster"] = p["concurrent_leader_movements"]
            if "max_partition_movements_in_cluster" in p:
                conc["cluster_inter_broker"] = \
                    p["max_partition_movements_in_cluster"]
            if "broker_concurrent_leader_movements" in p:
                conc["leadership_per_broker"] = \
                    p["broker_concurrent_leader_movements"]
            strategies = p.get("replica_movement_strategies", ())
            extras = {}
            if "execution_progress_check_interval_ms" in p:
                extras["progress_check_interval_s"] = \
                    p["execution_progress_check_interval_ms"] / 1000.0
            if "replication_throttle" in p:
                extras["replication_throttle"] = p["replication_throttle"]
            if p.get("stop_ongoing_execution"):
                extras["stop_ongoing_execution"] = True
            # throttle_added_broker / throttle_removed_broker = false:
            # leave the brokers being added/removed unthrottled
            # (AddedOrRemovedBrokerParameters.java).
            if (endpoint is EndPoint.ADD_BROKER
                    and not p.get("throttle_added_broker", True)) \
                    or (endpoint is EndPoint.REMOVE_BROKER
                        and not p.get("throttle_removed_broker", True)):
                extras["throttle_excluded_brokers"] = \
                    tuple(p.get("brokerid", ()))
            if conc or strategies or extras:
                return cc.execution_overrides(strategies, conc, extras)
            return contextlib.nullcontext()

        def load():
            if p.get("capacity_only"):
                # capacity_only=true answers from the capacity config alone
                # — no metric completeness needed (ParameterUtils
                # capacityOnly, excludes the time-range params).
                return responses.broker_capacities(
                    cc._admin, cc.load_monitor.capacity_resolver)
            state, meta = cc.load_monitor.cluster_model(
                allow_capacity_estimation=p.get("allow_capacity_estimation",
                                                True),
                start_ms=p.get("start", -1),
                end_ms=p.get("time", p.get("end", -1)))
            disk_info = None
            if p.get("populate_disk_info"):
                disk_info = (getattr(cc._admin, "describe_logdirs",
                                     lambda: {})(),
                             cc.load_monitor.capacity_resolver)
            return responses.broker_stats(state, meta, disk_info=disk_info)

        def partition_load():
            # max_load/avg_load pick the window reduction at model build
            # (Load.expectedUtilizationFor wantMaxLoad).
            reduction = "max" if p.get("max_load") \
                else ("avg" if p.get("avg_load") else "default")
            state, meta = cc.load_monitor.cluster_model(
                allow_capacity_estimation=p.get("allow_capacity_estimation",
                                                True),
                start_ms=p.get("start", -1), end_ms=p.get("end", -1),
                min_valid_partition_ratio=p.get("min_valid_partition_ratio"),
                reduction=reduction)
            return responses.partition_load(
                state, meta, p.get("resource", "DISK"), p.get("entries"),
                topic_rx=p.get("topic"), partition_range=p.get("partition"),
                brokerids=p.get("brokerid", ()))

        data_from = p.get("data_from")
        allow_cap = p.get("allow_capacity_estimation", True)

        # futures_req arrives pre-validated from _dispatch; the live
        # seed builds here on the WORKER thread via _dispatch's lazy
        # once-supplier (shared with the fleet payload path).

        def compare_futures():
            from ..futures.evaluator import compare_futures as _compare
            body = _compare(
                optimizer=cc.optimizer,
                width=cc.config.get_int("futures.batch.width"),
                live=futures_live() if futures_live is not None else None,
                **futures_req)
            return responses.envelope(body)

        def proposals():
            if p.get("what_if"):
                return self._what_if_handler(cc, p)
            return responses.optimization_result(cc.proposals(
                goals, p.get("ignore_proposal_cache", False),
                use_ready_default_goals=use_ready, fast_mode=fast_mode,
                data_from=data_from, allow_capacity_estimation=allow_cap),
                verbose)

        def rebalance():
            with exec_scope():
                if p.get("rebalance_disk"):
                    return responses.optimization_result(
                        cc.rebalance_disk(dryrun, reason=reason), verbose)
                return responses.optimization_result(cc.rebalance(
                    goals, dryrun,
                    excluded_topics=p.get("excluded_topics", ()),
                    destination_broker_ids=p.get("destination_broker_ids", ()),
                    exclude_recently_demoted_brokers=p.get(
                        "exclude_recently_demoted_brokers", False),
                    exclude_recently_removed_brokers=p.get(
                        "exclude_recently_removed_brokers", False),
                    use_ready_default_goals=use_ready, fast_mode=fast_mode,
                    data_from=data_from, allow_capacity_estimation=allow_cap,
                    reason=reason), verbose)

        def add_broker():
            with exec_scope():
                return responses.optimization_result(cc.add_brokers(
                    list(p.get("brokerid", ())), dryrun, goals,
                    use_ready_default_goals=use_ready, fast_mode=fast_mode,
                    data_from=data_from, allow_capacity_estimation=allow_cap,
                    reason=reason), verbose)

        def remove_broker():
            with exec_scope():
                return responses.optimization_result(cc.remove_brokers(
                    list(p.get("brokerid", ())), dryrun, goals,
                    use_ready_default_goals=use_ready, fast_mode=fast_mode,
                    data_from=data_from, allow_capacity_estimation=allow_cap,
                    reason=reason), verbose)

        def demote_broker():
            with exec_scope():
                return responses.optimization_result(cc.demote_brokers(
                    list(p.get("brokerid", ())), dryrun,
                    skip_urp_demotion=p.get("skip_urp_demotion", True),
                    exclude_follower_demotion=p.get(
                        "exclude_follower_demotion", False),
                    reason=reason), verbose)

        def fix_offline_replicas():
            with exec_scope():
                return responses.optimization_result(cc.fix_offline_replicas(
                    dryrun, goals, use_ready_default_goals=use_ready,
                    fast_mode=fast_mode, data_from=data_from,
                    allow_capacity_estimation=allow_cap,
                    reason=reason), verbose)

        def topic_configuration():
            topic = p.get("topic")
            rf = p.get("replication_factor")
            if not topic or rf is None:
                raise ParameterParseError(
                    "topic_configuration requires topic and replication_factor")
            with exec_scope():
                return responses.optimization_result(
                    cc.update_topic_replication_factor(
                        [topic], rf, dryrun, reason=reason,
                        skip_rack_awareness_check=p.get(
                            "skip_rack_awareness_check", False)), verbose)

        def remove_disks():
            mapping = p.get("brokerid_and_logdirs")
            if not mapping:
                raise ParameterParseError(
                    "remove_disks requires brokerid_and_logdirs")
            with exec_scope():
                return responses.optimization_result(
                    cc.remove_disks(mapping, dryrun, reason=reason), verbose)

        table = {EndPoint.LOAD: load, EndPoint.PARTITION_LOAD: partition_load,
                 EndPoint.PROPOSALS: proposals, EndPoint.REBALANCE: rebalance,
                 EndPoint.ADD_BROKER: add_broker,
                 EndPoint.REMOVE_BROKER: remove_broker,
                 EndPoint.DEMOTE_BROKER: demote_broker,
                 EndPoint.FIX_OFFLINE_REPLICAS: fix_offline_replicas,
                 EndPoint.TOPIC_CONFIGURATION: topic_configuration,
                 EndPoint.REMOVE_DISKS: remove_disks,
                 EndPoint.COMPARE_FUTURES: compare_futures}
        return table[endpoint]


def _schema_of(value: Any) -> Any:
    """Response-shape description for get_response_schema=true (the
    reference serves JSON schemas generated from its response classes)."""
    if isinstance(value, dict):
        return {k: _schema_of(v) for k, v in value.items()}
    if isinstance(value, list):
        return [_schema_of(value[0])] if value else []
    if isinstance(value, bool):
        return "boolean"
    if isinstance(value, (int, float)):
        return "number"
    return "string"


def _as_text(value: Any, indent: int = 0) -> str:
    """Plaintext rendering for json=false (key: value lines, nested
    structures indented — the text-table role of the reference's
    plaintext writers)."""
    pad = " " * indent
    if isinstance(value, dict):
        lines = []
        for k, v in value.items():
            if isinstance(v, (dict, list)):
                lines.append(f"{pad}{k}:")
                lines.append(_as_text(v, indent + 2))
            else:
                lines.append(f"{pad}{k}: {v}")
        return "\n".join(lines)
    if isinstance(value, list):
        return "\n".join(_as_text(v, indent) if isinstance(v, (dict, list))
                         else f"{pad}- {v}" for v in value)
    return f"{pad}{value}"


_SCRAPE_PATHS = {"/metrics": "metrics", URL_PREFIX + "/metrics": "metrics",
                 "/openapi": "openapi", URL_PREFIX + "/openapi": "openapi"}

def _endpoint_label(method: str, path: str) -> str:
    """The request's endpoint for the http.* spans: the endpoint's name,
    the scrape surface, or OTHER (UI assets, unknown paths)."""
    kind = _SCRAPE_PATHS.get(path) if method == "GET" else None
    if kind is not None:
        return kind.upper()
    if path.startswith(URL_PREFIX):
        endpoint = endpoint_for_path(path[len(URL_PREFIX):])
        if endpoint is not None:
            return endpoint.name
    return "OTHER"


class _Handler(BaseHTTPRequestHandler):
    api: CruiseControlApi  # set by make_server

    _UI_TYPES = {".html": "text/html; charset=utf-8",
                 ".js": "text/javascript", ".css": "text/css",
                 ".json": "application/json", ".svg": "image/svg+xml",
                 ".png": "image/png", ".ico": "image/x-icon",
                 ".woff2": "font/woff2", ".map": "application/json"}

    def _send(self, method: str, t0: float, status: int, data: bytes,
              content_type: str, extra: dict[str, str] | None = None) -> None:
        """The single response writer: every surface (API, scrapes, UI,
        errors) goes through here so HSTS, CORS, and the access log apply
        uniformly. Span ``http.write``: headers and body onto the socket;
        status and bytes go onto the request's root span."""
        from ..utils.tracing import TRACER
        TRACER.annotate(status=status, bytes=len(data))
        with self._http_span("http.write"):
            self._write(method, t0, status, data, content_type, extra)

    def _write(self, method: str, t0: float, status: int, data: bytes,
               content_type: str, extra: dict[str, str] | None) -> None:
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        cfg = self.api._config
        if cfg.get_boolean("webserver.ssl.enable") and \
                cfg.get_boolean("webserver.ssl.sts.enabled"):
            # webserver.ssl.sts.* (WebServerConfig HSTS surface).
            sts = f"max-age={cfg.get_long('webserver.ssl.sts.max.age')}"
            if cfg.get_boolean("webserver.ssl.sts.include.subdomains"):
                sts += "; includeSubDomains"
            self.send_header("Strict-Transport-Security", sts)
        if cfg.get_boolean("webserver.http.cors.enabled"):
            # webserver.http.cors.* (WebServerConfig CORS surface).
            self.send_header("Access-Control-Allow-Origin",
                             cfg.get("webserver.http.cors.origin"))
            self.send_header("Access-Control-Allow-Methods",
                             cfg.get("webserver.http.cors.allowmethods"))
            self.send_header("Access-Control-Expose-Headers",
                             cfg.get("webserver.http.cors.exposeheaders"))
        for k, v in (extra or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)
        if cfg.get_boolean("webserver.accesslog.enabled"):
            LOG.info('access %s "%s %s" %d %dB %.1fms',
                     self.client_address[0], method, self.path, status,
                     len(data), 1000 * (time.time() - t0))

    def _ui_lookup(self, path: str) -> tuple[bytes, str] | None:
        """(content, content-type) for the static Web-UI surface
        (KafkaCruiseControlServletApp serves the webroot at
        webserver.ui.diskpath): the configured directory when set, else the
        bundled single-file dashboard. Assets only — all DATA flows through
        the API endpoints."""
        if path.startswith(URL_PREFIX):
            return None
        cfg = self.api._config
        base = cfg.get("webserver.ui.diskpath")
        bundled = not base
        if bundled:
            import cruise_control_tpu.webui as webui
            base = os.path.dirname(webui.__file__)
        rel = path.lstrip("/") or "index.html"
        full = os.path.realpath(os.path.join(base, rel))
        # Traversal guard: the resolved file must stay inside the UI dir.
        if not full.startswith(os.path.realpath(base) + os.sep):
            return None
        ext = os.path.splitext(full)[1].lower()
        if bundled and ext not in self._UI_TYPES:
            # The bundled dir is a Python package: only recognized asset
            # types are public (never __init__.py / __pycache__ bytecode).
            return None
        if not os.path.isfile(full):
            return None
        with open(full, "rb") as f:
            return f.read(), self._UI_TYPES.get(ext,
                                                "application/octet-stream")

    # The request's endpoint; a class default so that ``_send`` works
    # before ``_serve`` has set it.
    _endpoint_label = "OTHER"

    def _http_span(self, name: str, **attributes):
        """An ``http.*`` span: the only spans that label their histogram
        series with the endpoint, so that one endpoint's requests can be
        read alone, and ``transient``: a request under which the program
        opened no other span (a scrape, a UI asset, a poll) leaves its
        histogram samples and no trace."""
        from ..utils.tracing import TRACER
        return TRACER.span(name, label_keys=("endpoint",), transient=True,
                           endpoint=self._endpoint_label, **attributes)

    def _serve(self, method: str) -> None:
        """One request, one trace: root span ``http.request`` from the
        parsed request line to the last byte written, with children
        ``http.handle`` (the pipeline), ``http.serialize`` (the body's
        JSON text) and ``http.write`` (``_send``)."""
        parsed = urllib.parse.urlparse(self.path)
        self._endpoint_label = _endpoint_label(method, parsed.path)
        with self._http_span("http.request", method=method) as root:
            self._serve_traced(method, parsed, root)

    def _serve_traced(self, method: str, parsed, root) -> None:
        t0 = time.time()
        cfg = self.api._config
        header_bytes = sum(len(k) + len(v) for k, v in self.headers.items())
        if header_bytes > cfg.get_int("webserver.http.header.size"):
            self._send(method, t0, 431, json.dumps(
                {"errorMessage": "request headers too large"}).encode(),
                "application/json")
            return
        kind = _SCRAPE_PATHS.get(parsed.path) if method == "GET" else None
        ui = None
        if method == "GET" and kind is None:
            ui = self._ui_lookup(parsed.path)
        if kind is not None or ui is not None:
            # These surfaces sit outside the endpoint enum but NOT outside
            # security: operational state — and operator-configured disk
            # content — must not leak unauthenticated.
            from .security import AuthenticationError
            try:
                self.api.authenticate_readonly(dict(self.headers),
                                               self.client_address[0])
            except AuthenticationError as e:
                self._send(method, t0, 401, json.dumps(
                    {"errorMessage": str(e)}).encode(), "application/json",
                    {"WWW-Authenticate": self.api._security.challenge()})
                return
            if ui is not None:
                self._send(method, t0, 200, ui[0], ui[1])
            elif kind == "metrics":
                self._send(method, t0, 200, self.api.metrics_text().encode(),
                           "text/plain; version=0.0.4; charset=utf-8")
            else:
                from .openapi import openapi_yaml
                self._send(method, t0, 200, openapi_yaml().encode(),
                           "application/yaml")
            return
        with self._http_span("http.handle"):
            status, body, extra = self.api.handle(
                method, parsed.path, parsed.query, dict(self.headers),
                self.client_address[0])
        if USER_TASK_HEADER in extra:
            root.set(userTaskId=extra[USER_TASK_HEADER])
        with self._http_span("http.serialize"):
            if isinstance(body, dict) and "__text__" in body:
                data = (body["__text__"] + "\n").encode()
                content_type = extra.pop("Content-Type",
                                         "text/plain; charset=utf-8")
            else:
                data = responses.body_bytes(body)
                content_type = extra.pop("Content-Type", "application/json")
        self._send(method, t0, status, data, content_type, extra)

    def do_GET(self) -> None:  # noqa: N802 (http.server API)
        self._serve("GET")

    def do_POST(self) -> None:  # noqa: N802
        self._serve("POST")

    def log_message(self, fmt: str, *args) -> None:
        LOG.debug("http: " + fmt, *args)


def make_server(cc: CruiseControl, host: str | None = None,
                port: int | None = None,
                security_provider: SecurityProvider | None = None,
                fleet=None) -> tuple[ThreadingHTTPServer, CruiseControlApi]:
    cfg = cc.config
    api = CruiseControlApi(cc, security_provider, fleet=fleet)
    handler = type("BoundHandler", (_Handler,), {"api": api})
    server = ThreadingHTTPServer(
        (host or cfg.get("webserver.http.address"),
         port if port is not None else cfg.get_int("webserver.http.port")),
        handler)
    if cfg.get_boolean("webserver.ssl.enable"):
        # webserver.ssl.* (WebServerConfig): PEM cert+key via stdlib ssl.
        import ssl
        pem = cfg.get("webserver.ssl.keystore.location")
        if not pem:
            raise ValueError("webserver.ssl.enable requires "
                             "webserver.ssl.keystore.location (PEM file)")
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        password = cfg.get("webserver.ssl.keystore.password")
        ctx.load_cert_chain(pem, password=str(password) if password else None)
        include = cfg.get_list("webserver.ssl.include.ciphers")
        if include:
            ctx.set_ciphers(":".join(include))
        server.socket = ctx.wrap_socket(server.socket, server_side=True)
    return server, api


def serve_forever_in_thread(server: ThreadingHTTPServer) -> threading.Thread:
    t = threading.Thread(target=server.serve_forever, daemon=True,
                         name="cruise-control-http")
    t.start()
    return t
