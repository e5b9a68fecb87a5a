"""REST API layer: endpoint dispatch, parameters, responses, user tasks,
two-step review, security (reference parity: servlet/ test ideas —
KafkaCruiseControlServletEndpointTest, UserTaskManagerTest, purgatory and
security suites — against the stdlib server)."""

import http.client
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from cruise_control_tpu.api import (
    EndPoint, Purgatory, ReviewStatus, Role, UserTaskManager,
)
from cruise_control_tpu.api.parameters import (
    ParameterParseError, parse_parameters,
)
from cruise_control_tpu.api.security import (
    AuthenticationError, BasicSecurityProvider, JwtSecurityProvider,
    Principal, TrustedProxySecurityProvider, encode_jwt,
    parse_credentials_file,
)
from cruise_control_tpu.api.server import CruiseControlApi, make_server
from cruise_control_tpu.common.resources import Resource
from cruise_control_tpu.config.cruise_control_config import CruiseControlConfig
from cruise_control_tpu.executor.admin import InMemoryAdminBackend, PartitionState
from cruise_control_tpu.executor.executor import Executor
from cruise_control_tpu.facade import CruiseControl
from cruise_control_tpu.monitor import LoadMonitor, StaticCapacityResolver
from cruise_control_tpu.monitor.sampling import SyntheticSampler


def _partitions(brokers=(0, 1, 2, 3), topics=2, parts=4):
    out = {}
    for t in range(topics):
        for p in range(parts):
            reps = (brokers[0], brokers[1 + (t + p) % (len(brokers) - 1)])
            out[(f"t{t}", p)] = PartitionState(f"t{t}", p, reps, reps[0],
                                               isr=reps)
    return out


@pytest.fixture(scope="module")
def cc():
    partitions = _partitions()
    backend = InMemoryAdminBackend(partitions.values())
    cfg = CruiseControlConfig({
        "partition.metrics.window.ms": 1000,
        "num.partition.metrics.windows": 3,
        "min.valid.partition.ratio": 0.0,
        "max.solver.rounds": 30,
        "failed.brokers.file.path": ""})
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6, Resource.NW_OUT: 1e6})
    monitor = LoadMonitor(cfg, backend, samplers=[SyntheticSampler()],
                          capacity_resolver=caps)
    cc = CruiseControl(cfg, backend, load_monitor=monitor,
                       executor=Executor(backend, synchronous=True))
    for k in range(1, 4):
        monitor.task_runner.run_sampling_once(end_ms=k * 1000)
    return cc


@pytest.fixture()
def api(cc):
    api = CruiseControlApi(cc)
    api._async_wait_s = 180       # cover first-compile of the solver kernels
    yield api
    api.shutdown()


# ---- parameters ----------------------------------------------------------

def test_parameter_parsing_types_and_unknown_rejection():
    q = {"brokerid": ["1,2,3"], "dryrun": ["false"], "reason": ["test"]}
    p = parse_parameters(EndPoint.REMOVE_BROKER, q)
    assert p == {"brokerid": (1, 2, 3), "dryrun": False, "reason": "test"}
    with pytest.raises(ParameterParseError, match="unknown parameter"):
        parse_parameters(EndPoint.REBALANCE, {"tyop": ["x"]})
    with pytest.raises(ParameterParseError, match="not a boolean"):
        parse_parameters(EndPoint.REBALANCE, {"dryrun": ["maybe"]})


def test_remove_disks_parameter_pairs():
    p = parse_parameters(EndPoint.REMOVE_DISKS,
                         {"brokerid_and_logdirs": ["0-/d1,0-/d2,1-/d1"]})
    assert p["brokerid_and_logdirs"] == {0: ("/d1", "/d2"), 1: ("/d1",)}


# ---- endpoint dispatch ---------------------------------------------------

def test_state_endpoint(api):
    status, body, _ = api.handle("GET", "/kafkacruisecontrol/state")
    assert status == 200
    assert {"MonitorState", "ExecutorState", "AnalyzerState",
            "AnomalyDetectorState"} <= set(body)


def test_unknown_endpoint_and_method_mismatch(api):
    assert api.handle("GET", "/kafkacruisecontrol/nope")[0] == 404
    assert api.handle("GET", "/other/state")[0] == 404
    assert api.handle("GET", "/kafkacruisecontrol/rebalance")[0] == 405


def test_kafka_cluster_state(api):
    status, body, _ = api.handle("GET", "/kafkacruisecontrol/kafka_cluster_state")
    assert status == 200
    counts = body["KafkaBrokerState"]["ReplicaCountByBrokerId"]
    assert sum(counts.values()) == 16      # 8 partitions × RF 2


def test_load_and_partition_load(api):
    status, body, _ = api.handle("GET", "/kafkacruisecontrol/load")
    assert status == 200
    assert len(body["brokers"]) == 4
    assert all("DiskMB" in b and "CpuPct" in b for b in body["brokers"])
    # Host-level rows (BrokerStats.java host section): default topology is
    # one host per broker, so sums must match broker-for-broker.
    assert len(body["hosts"]) == 4
    assert all("Host" in h and "Replicas" in h and "DiskMB" in h
               for h in body["hosts"])
    assert sum(h["Replicas"] for h in body["hosts"]) \
        == sum(b["Replicas"] for b in body["brokers"])
    status, body, _ = api.handle(
        "GET", "/kafkacruisecontrol/partition_load",
        "resource=network_outbound&entries=5")
    assert status == 200
    assert len(body["records"]) == 5
    status, _body, _ = api.handle("GET", "/kafkacruisecontrol/partition_load",
                                  "resource=warp_drive")
    assert status == 400


def test_load_host_rows_rack_falls_back_to_host():
    """Rack-falls-back-to-host end-to-end through the LOAD body
    (ClusterModel.createBroker: rack == null ? host : rack +
    model/Host.java:275 host aggregation): two rackless brokers sharing a
    host collapse to one fault domain AND one aggregated host row."""
    from cruise_control_tpu.api.responses import broker_stats
    from cruise_control_tpu.model.builder import ClusterModelBuilder

    cap = {Resource.CPU: 100.0, Resource.NW_IN: 1e5, Resource.NW_OUT: 1e5,
           Resource.DISK: 1e6}
    load = {Resource.CPU: 1.0, Resource.NW_IN: 10.0, Resource.NW_OUT: 10.0,
            Resource.DISK: 100.0}
    b = ClusterModelBuilder()
    b.add_broker(0, "", cap, host="shared-host")
    b.add_broker(1, "", cap, host="shared-host")
    b.add_broker(2, "rackA", cap, host="solo-host")
    b.add_partition("t", 0, [0, 2], leader_load=load)
    b.add_partition("t", 1, [1, 2], leader_load=load)
    state, meta = b.build()
    body = broker_stats(state, meta)

    by_host = {h["Host"]: h for h in body["hosts"]}
    assert set(by_host) == {"shared-host", "solo-host"}
    assert by_host["shared-host"]["Replicas"] == 2   # brokers 0 + 1
    assert by_host["solo-host"]["Replicas"] == 2     # broker 2's two
    assert by_host["shared-host"]["DiskMB"] == pytest.approx(200.0)
    rows = {r["Broker"]: r for r in body["brokers"]}
    # Rackless brokers inherit their host as the fault domain.
    assert rows[0]["Rack"] == rows[1]["Rack"] == "shared-host"
    assert rows[0]["Host"] == rows[1]["Host"] == "shared-host"
    assert rows[2]["Rack"] == "rackA" and rows[2]["Host"] == "solo-host"


def test_proposals_and_rebalance_dryrun(api):
    status, body, headers = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                       "dryrun=true")
    assert status == 200
    assert body["proposals"], "skewed fixture must produce proposals"
    assert "User-Task-ID" in headers
    status, body2, _ = api.handle("GET", "/kafkacruisecontrol/proposals")
    assert status == 200 and "summary" in body2


def test_user_tasks_listing(api):
    api.handle("POST", "/kafkacruisecontrol/rebalance", "dryrun=true")
    status, body, _ = api.handle("GET", "/kafkacruisecontrol/user_tasks")
    assert status == 200
    assert body["userTasks"]
    assert {"UserTaskId", "Status", "RequestURL"} <= set(body["userTasks"][0])


def test_user_task_id_resume(api):
    _s, _b, headers = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                 "dryrun=true")
    tid = headers["User-Task-ID"]
    _s2, _b2, headers2 = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                    "dryrun=true", {"User-Task-ID": tid})
    assert headers2["User-Task-ID"] == tid
    # As urllib (client.Responder) spells it on the wire.
    _s3, _b3, headers3 = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                    "dryrun=true", {"User-task-id": tid})
    assert headers3["User-Task-ID"] == tid


def test_admin_self_healing_toggle(api, cc):
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/admin",
        "enable_self_healing_for=broker_failure")
    assert status == 200
    st = cc.anomaly_detector.state()
    assert "BROKER_FAILURE" in st["selfHealingEnabled"]
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/admin",
        "disable_self_healing_for=broker_failure")
    assert status == 200
    assert body["selfHealingDisabledBefore"] == {"broker_failure": True}


def test_admin_concurrency_override(api, cc):
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/admin",
        "concurrent_partition_movements_per_broker=3")
    assert status == 200
    assert cc.executor._concurrency._caps.inter_broker_per_broker == 3


def test_admin_concurrency_adjuster_toggles(api, cc):
    mgr = cc.executor._concurrency
    base = mgr.snapshot()
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/admin",
        "disable_concurrency_adjuster_for=leadership"
        "&min_isr_based_concurrency_adjustment=false")
    assert status == 200
    assert body["concurrencyAdjusterEnabledBefore"] == {"leadership": True}
    # Seeded from concurrency.adjuster.min.isr.check.enabled, which
    # defaults FALSE (ExecutorConfig.java:583).
    assert body["minIsrBasedAdjustmentBefore"] is False
    # LEADERSHIP adjuster off + min-ISR-based adjustment off: an
    # under-min-ISR tick changes neither cap.
    mgr.adjust(cluster_healthy=False, has_under_min_isr=True)
    after = mgr.snapshot()
    assert after.leadership_cluster == base.leadership_cluster
    assert after.inter_broker_per_broker == base.inter_broker_per_broker
    # Re-enable: the same tick now halves the inter-broker cap again.
    assert api.handle("POST", "/kafkacruisecontrol/admin",
                      "enable_concurrency_adjuster_for=leadership"
                      "&min_isr_based_concurrency_adjustment=true")[0] == 200
    mgr.adjust(cluster_healthy=False, has_under_min_isr=True)
    adj = mgr.adjuster_config
    assert mgr.snapshot().inter_broker_per_broker == \
        max(adj.min_partition_movements_per_broker,
            int(base.inter_broker_per_broker
                / adj.multiplicative_decrease_inter_broker))
    cc.executor.set_requested_concurrency(
        inter_broker_per_broker=base.inter_broker_per_broker,
        leadership_cluster=base.leadership_cluster)
    # A typo'd concurrency type must 400, not silently no-op.
    assert api.handle("POST", "/kafkacruisecontrol/admin",
                      "disable_concurrency_adjuster_for=warp_drive")[0] == 400


def test_admin_rejects_whole_request_on_any_bad_name(api, cc):
    """A typo anywhere in an ADMIN request must 400 WITHOUT applying the
    valid toggles that preceded it (no partial mutation under an error)."""
    st_before = cc.anomaly_detector.state()["selfHealingEnabled"]
    status, _b, _ = api.handle(
        "POST", "/kafkacruisecontrol/admin",
        "disable_self_healing_for=broker_failure"
        "&disable_concurrency_adjuster_for=warp_drive")
    assert status == 400
    assert cc.anomaly_detector.state()["selfHealingEnabled"] == st_before
    assert api.handle("POST", "/kafkacruisecontrol/admin",
                      "enable_self_healing_for=warp_core")[0] == 400


def test_stop_execution_stop_external_agent(api, cc):
    backend = cc._admin
    # An "external agent" reassignment: destination broker 9 is dead, so the
    # fake cluster's tick never completes it.
    backend.alter_partition_reassignments({("t0", 0): (0, 9)})
    assert backend.list_reassigning_partitions()
    # A plain stop leaves the external reassignment alone ...
    assert api.handle("POST",
                      "/kafkacruisecontrol/stop_proposal_execution")[0] == 200
    assert backend.list_reassigning_partitions()
    # ... stop_external_agent=true cancels it (maybeStopExternalAgent:1261).
    assert api.handle("POST", "/kafkacruisecontrol/stop_proposal_execution",
                      "stop_external_agent=true&force_stop=true")[0] == 200
    assert not backend.list_reassigning_partitions()


def test_execution_param_surface_parses():
    p = parse_parameters(EndPoint.REBALANCE, {
        "max_partition_movements_in_cluster": ["600"],
        "broker_concurrent_leader_movements": ["50"],
        "dryrun": ["false"]})
    assert p["max_partition_movements_in_cluster"] == 600
    assert p["broker_concurrent_leader_movements"] == 50
    p = parse_parameters(EndPoint.TOPIC_CONFIGURATION,
                         {"skip_rack_awareness_check": ["true"],
                          "topic": ["t0"], "replication_factor": ["3"]})
    assert p["skip_rack_awareness_check"] is True
    p = parse_parameters(EndPoint.BOOTSTRAP, {"developer_mode": ["true"],
                                              "start": ["0"]})
    assert p["developer_mode"] is True


def test_pause_resume_and_stop(api, cc):
    assert api.handle("POST", "/kafkacruisecontrol/pause_sampling",
                      "reason=maintenance")[0] == 200
    assert cc.load_monitor.task_runner.sampling_mode.name == "PAUSED"
    assert api.handle("POST", "/kafkacruisecontrol/resume_sampling")[0] == 200
    assert cc.load_monitor.task_runner.sampling_mode.name == "RUNNING"
    assert api.handle("POST",
                      "/kafkacruisecontrol/stop_proposal_execution")[0] == 200


def test_remove_disks_requires_jbod_backend(api):
    status, body, _ = api.handle("POST", "/kafkacruisecontrol/remove_disks",
                                 "brokerid_and_logdirs=0-/d1")
    assert status == 400
    assert "JBOD" in body["errorMessage"]


# ---- two-step review -----------------------------------------------------

def test_two_step_review_flow(cc):
    api = CruiseControlApi(cc, config=None)
    api._two_step = True
    try:
        status, body, _ = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                     "dryrun=true")
        assert status == 200
        rid = body["reviewResult"]["Id"]
        assert body["reviewResult"]["Status"] == "PENDING_REVIEW"
        # Un-approved submission is rejected.
        status, body2, _ = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                      f"dryrun=true&review_id={rid}")
        assert status == 400
        # Approve via REVIEW, then submit.
        status, body3, _ = api.handle("POST", "/kafkacruisecontrol/review",
                                      f"approve={rid}")
        assert status == 200
        assert body3["requestInfo"][0]["Status"] == "APPROVED"
        # Submission replays the REVIEWED query: smuggled parameter changes
        # (dryrun=false here) are discarded in favor of what was approved.
        status, body4, _ = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                      f"dryrun=false&review_id={rid}")
        assert status == 200 and body4["proposals"]
        assert body4["dryrun"] is True and body4["executed"] is False
        status, board, _ = api.handle("GET", "/kafkacruisecontrol/review_board")
        assert board["requestInfo"][0]["Status"] == "SUBMITTED"
    finally:
        api.shutdown()


def test_purgatory_transitions():
    purgatory = Purgatory()
    info = purgatory.add("REBALANCE", "dryrun=true", "alice")
    with pytest.raises(ValueError):
        purgatory.submit(info.review_id, "REBALANCE")   # not approved yet
    purgatory.approve(info.review_id)
    with pytest.raises(ValueError):
        purgatory.submit(info.review_id, "ADD_BROKER")  # endpoint mismatch
    assert purgatory.submit(info.review_id, "REBALANCE").status \
        is ReviewStatus.SUBMITTED
    info2 = purgatory.add("REBALANCE", "", "bob")
    purgatory.discard(info2.review_id, "nope")
    with pytest.raises(ValueError):
        purgatory.approve(info2.review_id)


# ---- security ------------------------------------------------------------

def test_basic_security_provider_and_roles(cc):
    users = parse_credentials_file(
        "viewer: vpass, VIEWER\nadmin: apass, ADMIN\n")
    api = CruiseControlApi(cc, BasicSecurityProvider(users=users))
    try:
        import base64

        def basic(u, p):
            return {"Authorization": "Basic "
                    + base64.b64encode(f"{u}:{p}".encode()).decode()}

        assert api.handle("GET", "/kafkacruisecontrol/state")[0] == 401
        assert api.handle("GET", "/kafkacruisecontrol/state",
                          headers=basic("viewer", "wrong"))[0] == 401
        assert api.handle("GET", "/kafkacruisecontrol/state",
                          headers=basic("viewer", "vpass"))[0] == 200
        # VIEWER may not POST rebalance (requires ADMIN).
        assert api.handle("POST", "/kafkacruisecontrol/rebalance", "dryrun=true",
                          headers=basic("viewer", "vpass"))[0] == 403
        assert api.handle("POST", "/kafkacruisecontrol/pause_sampling", "",
                          headers=basic("admin", "apass"))[0] == 200
        api.handle("POST", "/kafkacruisecontrol/resume_sampling", "",
                   headers=basic("admin", "apass"))
    finally:
        api.shutdown()


def test_jwt_security_provider():
    secret = b"s3cret"
    provider = JwtSecurityProvider(secret)
    token = encode_jwt({"sub": "ops", "roles": ["ADMIN"],
                        "exp": time.time() + 60}, secret)
    principal = provider.authenticate({"Authorization": f"Bearer {token}"})
    assert principal == Principal("ops", Role.ADMIN)
    expired = encode_jwt({"sub": "ops", "exp": time.time() - 1}, secret)
    with pytest.raises(AuthenticationError, match="expired"):
        provider.authenticate({"Authorization": f"Bearer {expired}"})
    forged = token[:-2] + "xx"
    with pytest.raises(AuthenticationError, match="signature"):
        provider.authenticate({"Authorization": f"Bearer {forged}"})


def test_trusted_proxy_provider():
    provider = TrustedProxySecurityProvider({"10.0.0.1"},
                                            {"alice": Role.ADMIN})
    p = provider.authenticate({"X-Do-As": "alice"}, remote_addr="10.0.0.1")
    assert p.role is Role.ADMIN
    with pytest.raises(AuthenticationError):
        provider.authenticate({"X-Do-As": "alice"}, remote_addr="10.9.9.9")
    with pytest.raises(AuthenticationError):
        provider.authenticate({}, remote_addr="10.0.0.1")


# ---- user task manager ---------------------------------------------------

def test_user_task_manager_caps_active_tasks():
    utm = UserTaskManager(max_active_tasks=1)
    try:
        gate = threading.Event()
        utm.get_or_create_task("STATE", "", gate.wait)
        with pytest.raises(RuntimeError, match="max active"):
            utm.get_or_create_task("STATE", "", lambda: None)
        gate.set()
    finally:
        utm.shutdown()


# ---- real HTTP round-trip ------------------------------------------------

def test_http_server_round_trip(cc):
    server, api = make_server(cc, host="127.0.0.1", port=0)
    from cruise_control_tpu.api.server import serve_forever_in_thread
    serve_forever_in_thread(server)
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/kafkacruisecontrol/state") as r:
            assert r.status == 200
            body = json.loads(r.read())
            assert "MonitorState" in body
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/kafkacruisecontrol/rebalance?dryrun=true",
            method="POST")
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
            body = json.loads(r.read())
            assert body["proposals"]
    finally:
        server.shutdown()
        api.shutdown()


# ---- the body's JSON text on the wire (ISSUE 30) ---------------------------

@pytest.fixture(scope="module")
def wire(cc):
    server, api = make_server(cc, host="127.0.0.1", port=0)
    api._async_wait_s = 180
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield server.server_address[1], api
    server.shutdown()
    server.server_close()
    api.shutdown()


def _raw(port, method, path):
    """(status, headers, raw bytes) of one request over a real socket."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(method, f"/kafkacruisecontrol/{path}")
        resp = conn.getresponse()
        return resp.status, resp.headers, resp.read()
    finally:
        conn.close()


@pytest.mark.parametrize("method,path,expected", [
    ("GET", "proposals?verbose=true", 200),
    ("POST", "remove_broker?brokerid=3&dryrun=true&verbose=true", 200),
    ("GET", "state", 200),
    ("GET", "load", 200),
    ("GET", "partition_load", 200),
    ("GET", "kafka_cluster_state", 200),
    ("GET", "user_tasks", 200),
    ("GET", "partition_load?resource=warp", 400),
], ids=["proposals", "remove_broker", "state", "load", "partition_load",
        "kafka_cluster_state", "user_tasks", "error"])
def test_json_bodies_are_compact_and_parse_to_the_same_document(
        wire, method, path, expected):
    """Every JSON response is the C encoder's compact text (what
    upstream's Gson writes): nothing between tokens, key order and number
    text the encoder's own; the document a client parses is the one the
    indented text held."""
    port, api = wire
    status, headers, raw = _raw(port, method, path)
    assert status == expected, raw[:400]
    assert headers["Content-Type"] == "application/json"
    parsed = json.loads(raw)
    assert raw == json.dumps(parsed, separators=(",", ":")).encode()
    assert b"\n " not in raw
    assert int(headers["Content-Length"]) == len(raw)
    if path.startswith("proposals"):
        # same model generation: the facade answers with the same plan
        _status, body, _extra = api.handle(
            "GET", "/kafkacruisecontrol/proposals", "verbose=true")
        assert len(body["proposals"]) > 0
        assert parsed == json.loads(json.dumps(body, indent=2))


def test_text_bodies_pass_the_encoder_by(wire):
    """``json=false`` tables are written as they are: the text and one
    newline, byte for byte."""
    port, api = wire
    _status, body, _extra = api.handle(
        "GET", "/kafkacruisecontrol/kafka_cluster_state", "json=false")
    status, headers, raw = _raw(port, "GET", "kafka_cluster_state?json=false")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")
    assert raw == (body["__text__"] + "\n").encode()
    assert int(headers["Content-Length"]) == len(raw)


# ---- console client ------------------------------------------------------

def test_cccli_against_live_server(cc, capsys):
    from cruise_control_tpu.client import main as cccli_main
    server, api = make_server(cc, host="127.0.0.1", port=0)
    from cruise_control_tpu.api.server import serve_forever_in_thread
    serve_forever_in_thread(server)
    try:
        port = server.server_address[1]
        rc = cccli_main(["-a", f"http://127.0.0.1:{port}", "state"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert "MonitorState" in out
        rc = cccli_main(["-a", f"http://127.0.0.1:{port}", "rebalance",
                         "--dryrun", "true"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["proposals"]
        # Server-side parameter rejection propagates as a client error.
        rc = cccli_main(["-a", f"http://127.0.0.1:{port}", "partition_load",
                         "--resource", "warp"])
        assert rc == 1
        assert "unknown resource" in capsys.readouterr().err
    finally:
        server.shutdown()
        api.shutdown()


def test_metrics_endpoint_renders_prometheus(api):
    """/metrics publishes the headline sensors (Sensors.md): valid windows,
    monitored-partitions pct, balancedness, proposal-computation timer,
    executor task counters."""
    from cruise_control_tpu.utils.sensors import SENSORS

    SENSORS.clear()  # the registry is process-global; isolate the scrape
    SENSORS.record_timer("analyzer_proposal_computation", 1.25)
    SENSORS.count("executor_tasks", 3, labels={
        "type": "inter_broker_replica_action", "state": "completed"})
    text = api.metrics_text()
    assert "kafka_cruisecontrol_monitor_num_valid_windows" in text
    assert "kafka_cruisecontrol_monitor_monitored_partitions_percentage" in text
    assert "kafka_cruisecontrol_analyzer_balancedness_score" in text
    assert "kafka_cruisecontrol_analyzer_proposal_computation_seconds_count" in text
    assert 'kafka_cruisecontrol_executor_tasks_total{state="completed"' \
           ',type="inter_broker_replica_action"} 3' in text


def test_forecast_endpoint_serves_state(api):
    """GET /forecast (round 19): VIEWER-safe engine + detector state;
    disabled by default (off means off) with the config geometry still
    reported so an operator can see what flipping it on would do."""
    status, body, _ = api.handle("GET", "/kafkacruisecontrol/forecast", "")
    assert status == 200
    assert body["forecastEnabled"] is False
    assert body["forecast"] is None
    assert body["detector"]["predictionsMade"] == 0
    assert body["horizonWindows"] >= 1 and body["fitWindows"] >= 4
    # Unknown params still 400 (the shared parameter discipline).
    status, body, _ = api.handle("GET", "/kafkacruisecontrol/forecast",
                                 "bogus=1")
    assert status == 400


def test_openapi_spec_covers_all_endpoints():
    import yaml

    from cruise_control_tpu.api.endpoints import EndPoint
    from cruise_control_tpu.api.openapi import openapi_yaml

    spec = yaml.safe_load(openapi_yaml())
    assert spec["openapi"].startswith("3.")
    for e in EndPoint:
        path = f"/kafkacruisecontrol/{e.name.lower()}"
        assert path in spec["paths"], path
        assert e.method.lower() in spec["paths"][path]
    # Parameters derive from the live schemas.
    rb = spec["paths"]["/kafkacruisecontrol/rebalance"]["post"]["parameters"]
    names = {p["name"] for p in rb}
    assert {"dryrun", "goals", "verbose", "json",
            "replica_movement_strategies"} <= names


def test_json_false_renders_plaintext(api):
    status, body, headers = api.handle(
        "GET", "/kafkacruisecontrol/state", "json=false")
    assert status == 200
    assert "__text__" in body
    assert "MonitorState" in body["__text__"]
    assert headers["Content-Type"].startswith("text/plain")


def test_get_response_schema_included(api):
    status, body, _ = api.handle(
        "GET", "/kafkacruisecontrol/state", "get_response_schema=true")
    assert status == 200
    assert body["responseSchema"]["version"] == "number"


def test_verbose_adds_stats_and_caps_proposals(api):
    status, body, _ = api.handle("GET", "/kafkacruisecontrol/proposals",
                                 "verbose=true")
    assert status == 200
    assert "loadBeforeOptimization" in body
    assert body["numProposals"] == len(body["proposals"])


def test_endpoint_request_class_is_config_swappable(cc):
    """CruiseControlRequestConfig reflection parity: a configured
    <endpoint>.request.class takes over the endpoint end to end."""

    class CustomStateHandler:
        def handle(self, facade, params, principal):
            return {"version": 1, "custom": True,
                    "caller": principal.name}

    import cruise_control_tpu.api.server as server_mod
    cfg = CruiseControlConfig({
        "state.request.class":
            f"{__name__}.CustomStateHandler",
        "failed.brokers.file.path": ""})
    # Resolution goes through resolve_class on a dotted path; register the
    # class where that path can find it.
    import sys
    setattr(sys.modules[__name__], "CustomStateHandler", CustomStateHandler)
    api = server_mod.CruiseControlApi(cc, config=cfg)
    try:
        status, body, _ = api.handle("GET", "/kafkacruisecontrol/state")
        assert status == 200
        assert body == {"version": 1, "custom": True, "caller": "anonymous"}
    finally:
        api.shutdown()


def test_user_task_manager_max_active_maps_to_429(cc):
    import threading

    from cruise_control_tpu.api.user_tasks import UserTaskManager

    api = CruiseControlApi(cc)
    api._async_wait_s = 0.01
    gate = threading.Event()
    api._tasks = UserTaskManager(max_active_tasks=1)
    api._tasks.get_or_create_task("REBALANCE", "", gate.wait)
    try:
        status, body, _ = api.handle("POST", "/kafkacruisecontrol/rebalance",
                                     "dryrun=true")
        assert status == 429
        assert "max active user tasks" in body["errorMessage"]
    finally:
        gate.set()
        api.shutdown()


def test_user_task_per_class_completed_retention():
    from cruise_control_tpu.api.user_tasks import UserTaskManager

    m = UserTaskManager(max_active_tasks=50,
                        max_cached_completed_monitor_tasks=2,
                        max_cached_completed_admin_tasks=3)
    try:
        for i in range(5):
            m.get_or_create_task("PROPOSALS", f"q={i}", lambda: 1).future.result()
        for i in range(5):
            m.get_or_create_task("REBALANCE", f"q={i}", lambda: 1).future.result()
        tasks = m.all_tasks()
        monitor = [t for t in tasks if t.endpoint == "PROPOSALS"]
        admin = [t for t in tasks if t.endpoint == "REBALANCE"]
        assert len(monitor) == 2     # newest 2 monitor-type kept
        assert len(admin) == 3       # newest 3 admin-type kept
    finally:
        m.shutdown()


def test_async_task_reports_typed_progress(api):
    """OperationProgress parity: a completed model-building task records
    the typed steps (AggregatingMetrics → GeneratingClusterModel → ...)."""
    api.handle("POST", "/kafkacruisecontrol/rebalance", "dryrun=true")
    tasks = [t for t in api.user_tasks.all_tasks()
             if t.endpoint == "REBALANCE"]
    assert tasks
    steps = [p["step"] for p in tasks[0].progress.to_list()]
    assert "GeneratingClusterModel" in steps
    assert "OptimizationForGoalChain" in steps


def test_jwt_rs256_round_trip():
    """RS256 JWT verification against a public key (JwtAuthenticator.java
    parity via the cryptography package), including audience checks."""
    import base64
    import json as json_mod
    import time as time_mod

    # Optional dependency: tier-1 must stay green on images without it
    # (the provider itself degrades the same way at runtime).
    pytest.importorskip("cryptography")
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import padding, rsa

    from cruise_control_tpu.api.security import (
        AuthenticationError, JwtSecurityProvider, Role,
    )

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    pem = key.public_key().public_bytes(
        serialization.Encoding.PEM,
        serialization.PublicFormat.SubjectPublicKeyInfo)

    def b64url(data: bytes) -> str:
        return base64.urlsafe_b64encode(data).rstrip(b"=").decode()

    def sign(claims: dict) -> str:
        header = b64url(json_mod.dumps({"alg": "RS256",
                                        "typ": "JWT"}).encode())
        payload = b64url(json_mod.dumps(claims).encode())
        sig = key.sign(f"{header}.{payload}".encode(), padding.PKCS1v15(),
                       hashes.SHA256())
        return f"{header}.{payload}.{b64url(sig)}"

    provider = JwtSecurityProvider(public_key_pem=pem,
                                   expected_audiences=("cruise-control",))
    token = sign({"sub": "alice", "roles": ["ADMIN"],
                  "aud": "cruise-control",
                  "exp": time_mod.time() + 60})
    principal = provider.authenticate({"Authorization": f"Bearer {token}"})
    assert principal.name == "alice" and principal.role is Role.ADMIN

    import pytest as pytest_mod
    with pytest_mod.raises(AuthenticationError, match="audience"):
        provider.authenticate({"Authorization": "Bearer " + sign(
            {"sub": "alice", "aud": "other", "exp": time_mod.time() + 60})})
    # Tampered payload: signature must fail.
    head, payload, sig = token.split(".")
    evil = b64url(json_mod.dumps({"sub": "mallory", "roles": ["ADMIN"],
                                  "aud": "cruise-control"}).encode())
    with pytest_mod.raises(AuthenticationError, match="signature"):
        provider.authenticate(
            {"Authorization": f"Bearer {head}.{evil}.{sig}"})


def test_user_task_id_bound_to_client():
    """A User-Task-ID is a capability scoped to its creator: another
    client presenting the id gets 403, not the first client's result
    (UserTaskManager.java session binding)."""
    from cruise_control_tpu.api.user_tasks import (
        TaskOwnershipError, UserTaskManager,
    )

    mgr = UserTaskManager()
    info = mgr.get_or_create_task("PROPOSALS", "", lambda: 42,
                                  client="alice")
    assert info.future.result(timeout=5) == 42
    # same client resumes fine
    again = mgr.get_or_create_task("PROPOSALS", "", lambda: 43,
                                   task_id=info.task_id, client="alice")
    assert again.task_id == info.task_id
    with pytest.raises(TaskOwnershipError):
        mgr.get_or_create_task("PROPOSALS", "", lambda: 44,
                               task_id=info.task_id, client="mallory")
    mgr.shutdown()


def test_unknown_user_task_id_is_rejected_not_squatted():
    """An unknown/expired User-Task-ID must 400, never create a task
    under the client-chosen id (id squatting would 403 the legitimate
    owner's next poll after cache eviction)."""
    from cruise_control_tpu.api.user_tasks import UserTaskManager

    mgr = UserTaskManager()
    with pytest.raises(ValueError, match="unknown or expired"):
        mgr.get_or_create_task("PROPOSALS", "", lambda: 1,
                               task_id="11111111-2222-3333-4444-555555555555",
                               client="mallory")
    assert mgr.all_tasks() == []
    mgr.shutdown()


def test_request_reason_required(cc):
    api2 = CruiseControlApi(cc)
    api2._reason_required = True
    try:
        status, body, _ = api2.handle("POST", "/kafkacruisecontrol/rebalance",
                                      "dryrun=true")
        assert status == 400 and "reason" in body["errorMessage"]
        # Non-executing POSTs stay exempt (ParameterUtils scopes the flag to
        # the proposal-executing parameter classes).
        assert api2.handle("POST",
                           "/kafkacruisecontrol/pause_sampling")[0] == 200
        assert api2.handle("POST", "/kafkacruisecontrol/resume_sampling",
                           "reason=x")[0] == 200
    finally:
        api2.shutdown()


def test_provisioner_disabled_refuses_rightsize():
    partitions = _partitions()
    backend = InMemoryAdminBackend(partitions.values())
    cfg = CruiseControlConfig({
        "partition.metrics.window.ms": 1000,
        "num.partition.metrics.windows": 3,
        "min.valid.partition.ratio": 0.0,
        "provisioner.enable": False,
        "failed.brokers.file.path": ""})
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6, Resource.NW_OUT: 1e6})
    monitor = LoadMonitor(cfg, backend, samplers=[SyntheticSampler()],
                          capacity_resolver=caps)
    cc2 = CruiseControl(cfg, backend, load_monitor=monitor,
                        executor=Executor(backend, synchronous=True))
    api2 = CruiseControlApi(cc2)
    try:
        status, body, _ = api2.handle("POST", "/kafkacruisecontrol/rightsize",
                                      "numbrokerstoadd=2")
        assert status == 400
        assert "provisioner" in body["errorMessage"]
    finally:
        api2.shutdown()


def test_user_task_manager_four_retention_classes():
    from cruise_control_tpu.api.user_tasks import task_class

    assert task_class("LOAD") == "KAFKA_MONITOR"
    assert task_class("REBALANCE") == "KAFKA_ADMIN"
    assert task_class("STATE") == "CC_MONITOR"
    assert task_class("ADMIN") == "CC_ADMIN"
    mgr = UserTaskManager(max_cached_completed_monitor_tasks=2,
                          max_cached_completed_admin_tasks=5,
                          max_cached_completed_cc_monitor_tasks=1)
    try:
        for i in range(4):
            mgr.get_or_create_task("LOAD", f"q{i}", lambda: 1).future.result()
        for i in range(3):
            mgr.get_or_create_task("STATE", f"q{i}", lambda: 1).future.result()
        tasks = mgr.all_tasks()
        assert sum(1 for t in tasks if t.endpoint == "LOAD") == 2
        assert sum(1 for t in tasks if t.endpoint == "STATE") == 1
    finally:
        mgr.shutdown()


def test_web_ui_served_with_traversal_guard(cc):
    server, api2 = make_server(cc, host="127.0.0.1", port=0)
    try:
        threading.Thread(target=server.serve_forever, daemon=True).start()
        port = server.server_address[1]
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/") as r:
            body = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/html")
            assert "cruise-control-tpu" in body
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/index.html") as r:
            assert r.status == 200
        # Traversal attempts must not escape the UI directory.
        for evil in ("/../facade.py", "/..%2f..%2fetc%2fpasswd",
                     "/nonexistent.js"):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{evil}") as r:
                    assert r.status == 404, evil
            except urllib.error.HTTPError as e:
                assert e.code == 404, evil
    finally:
        server.shutdown()
        api2.shutdown()


def test_web_ui_bundled_package_files_not_served(cc):
    server, api2 = make_server(cc, host="127.0.0.1", port=0)
    try:
        threading.Thread(target=server.serve_forever, daemon=True).start()
        port = server.server_address[1]
        # Only recognized asset types are public from the bundled package.
        for hidden in ("/__init__.py", "/__pycache__/__init__.cpython-311.pyc"):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{hidden}") as r:
                    assert r.status == 404, hidden
            except urllib.error.HTTPError as e:
                assert e.code == 404, hidden
    finally:
        server.shutdown()
        api2.shutdown()


def test_web_ui_requires_auth_when_security_enabled(cc):
    from cruise_control_tpu.api.security import BasicSecurityProvider, Role
    import base64 as b64
    provider = BasicSecurityProvider(users={"ops": ("pw", Role.VIEWER)})
    server, api2 = make_server(cc, host="127.0.0.1", port=0,
                               security_provider=provider)
    try:
        threading.Thread(target=server.serve_forever, daemon=True).start()
        port = server.server_address[1]
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/")
            raise AssertionError("expected 401")
        except urllib.error.HTTPError as e:
            assert e.code == 401
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/",
            headers={"Authorization": "Basic "
                     + b64.b64encode(b"ops:pw").decode()})
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
            assert "cruise-control-tpu" in r.read().decode()
    finally:
        server.shutdown()
        api2.shutdown()


# ---- request-parameter conformance (VERDICT r3 weak #4) ------------------

def test_kafka_assigner_mode_switches_chain(api):
    """rebalance?kafka_assigner=true runs EXACTLY the two assigner goals
    (ParameterUtils.getGoals:755-771)."""
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/rebalance",
        "kafka_assigner=true&dryrun=true")
    assert status == 200, body
    names = [g["goal"] for g in body["goalSummary"]]
    assert names == ["KafkaAssignerEvenRackAwareGoal",
                     "KafkaAssignerDiskUsageDistributionGoal"]


def test_kafka_assigner_mode_conflicts_are_400(api):
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/rebalance",
        "kafka_assigner=true&goals=RackAwareGoal&dryrun=true")
    assert status == 400 and "explicitly specifying" in body["errorMessage"]
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/rebalance",
        "kafka_assigner=true&rebalance_disk=true&dryrun=true")
    assert status == 400


def test_use_ready_default_goals_filters_chain(api, cc):
    """With full monitor readiness the ready chain IS the default chain;
    with explicit goals the combination is a 400
    (ParameterUtils.getBooleanExcludeGiven:323-334)."""
    ready = [g.name for g in cc.ready_goals()]
    default_chain = [s.rsplit(".", 1)[-1]
                     for s in cc._config.get_list("goals")]
    assert ready == default_chain  # fixture monitor is fully caught up
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/rebalance",
        "use_ready_default_goals=true&goals=RackAwareGoal&dryrun=true")
    assert status == 400
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/rebalance",
        "use_ready_default_goals=true&dryrun=true")
    assert status == 200, body
    assert [g["goal"] for g in body["goalSummary"]] == default_chain


def test_ready_goals_tracks_monitor_completeness(cc):
    """Resource-metric goals need num_windows//2 valid windows; structural
    goals need one (Goal.clusterModelCompletenessRequirements)."""
    from cruise_control_tpu.analyzer.optimizer import goals_by_priority
    chain = goals_by_priority(cc._config)
    windows = cc._config.get_int("num.partition.metrics.windows")
    for g in chain:
        need_w, _need_r = g.completeness_requirements(windows, 0.95)
        assert need_w == (max(1, windows // 2)
                          if g.uses_resource_metrics else 1)


def test_fast_mode_caps_goal_wall_clock(api):
    """fast_mode=true completes and reports per-goal durations bounded by
    the fast.mode.per.broker.move.timeout.ms x B budget (trivially
    satisfied at this scale — the assertion is that the parameter reaches
    the optimizer and the run still balances)."""
    status, body, _ = api.handle(
        "POST", "/kafkacruisecontrol/rebalance", "fast_mode=true&dryrun=true")
    assert status == 200, body
    assert body["goalSummary"]


def test_every_schema_param_has_a_consumer():
    """Tripwire for accepted-but-dead request parameters (the class of bug
    VERDICT r3 found for kafka_assigner/fast_mode/use_ready_default_goals):
    every parameter name in SCHEMAS must appear in at least one consuming
    module outside parameters.py."""
    import os

    import cruise_control_tpu.api.parameters as params_mod

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(params_mod.__file__)))
    consumers = [
        os.path.join(root, "api", "server.py"),
        os.path.join(root, "api", "responses.py"),
        os.path.join(root, "api", "user_tasks.py"),
        os.path.join(root, "api", "security.py"),
        os.path.join(root, "facade.py"),
        os.path.join(root, "monitor", "load_monitor.py"),
    ]
    blob = "".join(open(f).read() for f in consumers)
    from cruise_control_tpu.api.parameters import _COMMON, SCHEMAS
    all_params = set(_COMMON)
    for schema in SCHEMAS.values():
        all_params |= set(schema)
    dead = sorted(p for p in all_params if f'"{p}"' not in blob)
    assert not dead, f"accepted-but-unused request parameters: {dead}"


def test_spnego_negotiate_with_stub_gssapi(monkeypatch):
    """SPNEGO completes a real accept-side GSS handshake when gssapi is
    importable (stubbed here — the package is not in this image), and
    fails LOUDLY without it (VERDICT r3 #8: no silent shim).
    Reference: security/spnego/SpnegoSecurityProvider.java:21."""
    import base64
    import sys
    import types

    from cruise_control_tpu.api.security import SpnegoSecurityProvider

    calls = {}

    class _Name:
        def __init__(self, name, name_type=None):
            self.name = name

        def __str__(self):
            return self.name

    class _Creds:
        def __init__(self, name=None, usage=None, store=None):
            calls["cred_name"] = str(name) if name else None
            calls["store"] = store

    class _Ctx:
        def __init__(self, creds=None, usage=None):
            calls["usage"] = usage

        def step(self, token):
            calls["token"] = token
            if token == b"bad":
                raise RuntimeError("defective token")

        @property
        def initiator_name(self):
            return _Name("alice/host@EXAMPLE.COM")

    stub = types.ModuleType("gssapi")
    stub.Name = _Name
    stub.NameType = types.SimpleNamespace(kerberos_principal="krb5")
    stub.Credentials = _Creds
    stub.SecurityContext = _Ctx
    monkeypatch.setitem(sys.modules, "gssapi", stub)

    provider = SpnegoSecurityProvider(
        principal="HTTP/cc.example.com@EXAMPLE.COM",
        keytab_file="/etc/krb5.keytab")
    token = base64.b64encode(b"gss-blob").decode()
    principal = provider.authenticate(
        {"Authorization": f"Negotiate {token}"})
    # Kerberos principal shortened to the bare user (principal shortening
    # of the reference provider) + keytab store threaded through.
    assert principal.name == "alice"
    assert calls["token"] == b"gss-blob"
    assert calls["store"] == {"keytab": "/etc/krb5.keytab"}
    assert calls["cred_name"] == "HTTP/cc.example.com@EXAMPLE.COM"

    # A defective token is a 401-class failure.
    bad = base64.b64encode(b"bad").decode()
    with pytest.raises(AuthenticationError, match="negotiation failed"):
        provider.authenticate({"Authorization": f"Negotiate {bad}"})

    # Without the gssapi package: loud server-side failure, never open.
    monkeypatch.delitem(sys.modules, "gssapi")
    import builtins
    real_import = builtins.__import__

    def no_gssapi(name, *a, **k):
        if name == "gssapi":
            raise ImportError("No module named 'gssapi'")
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_gssapi)
    with pytest.raises(AuthenticationError, match="python-gssapi"):
        provider.authenticate({"Authorization": f"Negotiate {token}"})
