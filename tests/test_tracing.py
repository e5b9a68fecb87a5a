"""Pipeline tracing + XLA/device telemetry (round 8).

Unit coverage of the span tracer (nesting, ring bound, disabled no-op,
JSONL dump), the OperationProgress fixes (idempotent done, live
completion estimate), and the end-to-end acceptance bar: one rebalance
dry-run against the in-memory fixture yields ONE trace tree —
aggregate → model (cache hit/miss + transfer bytes) → per-goal solve →
proposal diff — retrievable from GET /kafkacruisecontrol/trace, with
well-formed per-stage ``_bucket`` histograms plus ``xla_compile_seconds``
and ``device_memory_bytes`` series on /metrics."""

import json
import threading
import time

import pytest

from cruise_control_tpu.api.server import CruiseControlApi
from cruise_control_tpu.common.resources import Resource
from cruise_control_tpu.config.cruise_control_config import CruiseControlConfig
from cruise_control_tpu.executor.admin import InMemoryAdminBackend, PartitionState
from cruise_control_tpu.executor.executor import Executor
from cruise_control_tpu.facade import CruiseControl
from cruise_control_tpu.monitor import LoadMonitor, StaticCapacityResolver
from cruise_control_tpu.monitor.sampling import SyntheticSampler
from cruise_control_tpu.utils.progress import OperationProgress
from cruise_control_tpu.utils.tracing import TRACER, Tracer, span_names


# ---- tracer unit behavior ------------------------------------------------

def test_span_nesting_and_attributes():
    tracer = Tracer(max_traces=8)
    with tracer.span("root", operation="op") as r:
        with tracer.span("child") as c:
            c.set(k=1)
            with tracer.span("grandchild"):
                tracer.annotate(deep=True)
        r.set(done=True)
    traces = tracer.traces()
    assert len(traces) == 1
    t = traces[0]
    assert t["operation"] == "op"
    assert t["spanCount"] == 3
    assert span_names(t) == ["root", "child", "grandchild"]
    child = t["root"]["children"][0]
    assert {"key": "k", "value": {"intValue": "1"}} in child["attributes"]
    grand = child["children"][0]
    assert {"key": "deep", "value": {"boolValue": True}} in grand["attributes"]
    # OTLP-compatible ids: 32-hex trace id shared, distinct 16-hex span ids
    assert len(t["traceId"]) == 32
    ids = {t["root"]["spanId"], child["spanId"], grand["spanId"]}
    assert len(ids) == 3 and all(len(i) == 16 for i in ids)
    assert child["parentSpanId"] == t["root"]["spanId"]


def test_ring_bound_and_filters():
    tracer = Tracer(max_traces=2)
    for i in range(4):
        with tracer.span(f"op{i}", operation=f"op{i}"):
            pass
    traces = tracer.traces()
    assert [t["operation"] for t in traces] == ["op3", "op2"]
    assert tracer.traces(operation="op3")[0]["operation"] == "op3"
    assert tracer.traces(operation="op0") == []
    assert tracer.traces(limit=1)[0]["operation"] == "op3"
    assert tracer.traces(limit=0) == []


def test_disabled_records_nothing_and_is_reentrant():
    tracer = Tracer()
    tracer.configure(enabled=False)
    with tracer.span("a") as s:
        s.set(x=1)  # the null span accepts set()
        with tracer.span("b"):
            tracer.annotate(y=2)
    assert tracer.traces() == []
    assert tracer.spans_closed == 0
    # the disabled path hands back one shared object — no per-call alloc
    assert tracer.span("a") is tracer.span("b")


def test_exception_marks_span_and_propagates():
    tracer = Tracer()
    with pytest.raises(ValueError):
        with tracer.span("boom", operation="x"):
            raise ValueError("nope")
    t = tracer.traces()[0]
    assert {"key": "error", "value": {"stringValue": "ValueError"}} \
        in t["root"]["attributes"]


def test_jsonl_dump(tmp_path):
    path = tmp_path / "trace.jsonl"
    tracer = Tracer()
    tracer.configure(jsonl_path=str(path))
    with tracer.span("a", operation="bench"):
        with tracer.span("b"):
            pass
    with tracer.span("c", operation="bench"):
        pass
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["spanCount"] == 2
    assert span_names(lines[0]) == ["a", "b"]


def test_operation_filter_matches_nested_operations():
    # Fleet mode: the scheduler's fleet.job wrapper is the trace ROOT and
    # the routed runnable ("rebalance") nests under it — the operation
    # filter must still find the trace by the nested runnable name.
    tracer = Tracer()
    with tracer.span("fleet.job", operation="fleet.on_demand",
                     cluster="alpha"):
        with tracer.span("rebalance", operation="rebalance"):
            pass
    assert tracer.traces(operation="rebalance"), \
        "fleet-wrapped operations must stay filterable by runnable name"
    assert tracer.traces(operation="fleet.on_demand")
    t = tracer.traces()[0]
    assert t["operation"] == "fleet.on_demand"  # the root stays primary
    assert set(t["operations"]) == {"fleet.on_demand", "rebalance"}


def test_cross_thread_spans_become_roots():
    tracer = Tracer()
    done = threading.Event()

    def worker():
        with tracer.span("worker.job", operation="background"):
            pass
        done.set()

    with tracer.span("main.op", operation="main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert done.wait(1)
    ops = {t["operation"] for t in tracer.traces()}
    assert ops == {"main", "background"}


# ---- OperationProgress satellites ---------------------------------------

def test_progress_done_idempotent():
    p = OperationProgress("op")
    p.start_step("A")
    time.sleep(0.01)
    p.done()
    first = p.to_list()[0]["durationS"]
    time.sleep(0.02)
    p.done()  # re-entered done() must not overwrite the duration
    assert p.to_list()[0]["durationS"] == first
    assert p.to_list()[0]["completionPercentage"] == 100.0


def test_progress_live_completion_estimate():
    p = OperationProgress("op")
    p.start_step("Model", estimate_s=0.05)
    time.sleep(0.02)
    live = p.to_list()[0]["completionPercentage"]
    assert 10.0 <= live < 100.0, \
        f"in-flight step with an estimate must report progress, got {live}"
    time.sleep(0.06)
    assert p.to_list()[0]["completionPercentage"] == 99.0  # clamped
    p.done()
    assert p.to_list()[0]["completionPercentage"] == 100.0


def test_progress_without_estimate_stays_zero():
    p = OperationProgress("op")
    p.start_step("NoEstimate")
    assert p.to_list()[0]["completionPercentage"] == 0.0


# ---- end-to-end: rebalance trace + telemetry exposition ------------------

def _partitions(brokers=(0, 1, 2, 3), topics=2, parts=4):
    out = {}
    for t in range(topics):
        for p in range(parts):
            reps = (brokers[0], brokers[1 + (t + p) % (len(brokers) - 1)])
            out[(f"t{t}", p)] = PartitionState(f"t{t}", p, reps, reps[0],
                                               isr=reps)
    return out


@pytest.fixture(scope="module")
def traced_api():
    partitions = _partitions()
    backend = InMemoryAdminBackend(partitions.values())
    cfg = CruiseControlConfig({
        "partition.metrics.window.ms": 1000,
        "num.partition.metrics.windows": 3,
        "min.valid.partition.ratio": 0.0,
        "max.solver.rounds": 30,
        "failed.brokers.file.path": ""})
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6,
                                       Resource.NW_OUT: 1e6})
    monitor = LoadMonitor(cfg, backend, samplers=[SyntheticSampler()],
                          capacity_resolver=caps)
    cc = CruiseControl(cfg, backend, load_monitor=monitor,
                       executor=Executor(backend, synchronous=True))
    for k in range(1, 4):
        monitor.task_runner.run_sampling_once(end_ms=k * 1000)
    api = CruiseControlApi(cc)
    api._async_wait_s = 180
    yield api
    api.shutdown()
    TRACER.configure(enabled=True, jsonl_path=None)


def test_rebalance_dryrun_yields_full_trace_tree(traced_api):
    assert TRACER.enabled  # facade wired tracing.enabled from config
    status, body, _ = traced_api.handle(
        "POST", "/kafkacruisecontrol/rebalance", "dryrun=true")
    assert status == 200, body
    status, body, _ = traced_api.handle(
        "GET", "/kafkacruisecontrol/trace", "operation=rebalance&entries=1")
    assert status == 200, body
    assert body["tracingEnabled"] is True
    assert body["numTraces"] == 1
    trace = body["traces"][0]
    names = span_names(trace)
    assert names[0] == "rebalance"
    for expected in ("monitor.cluster_model", "monitor.aggregate",
                     "model.assemble", "analyzer.optimize", "solver.dispatch",
                     "solver.enqueue", "solver.wait",
                     "analyzer.proposal_diff", "diff.stats", "diff.fetch",
                     "diff.compare"):
        assert expected in names, f"missing {expected} in {names}"
    # The fused route is ONE dispatch: a span is a measured interval, so
    # no per-goal span is made up from the round counts (the bounded
    # route's live goal.solve spans: test_bounded_route_goal_spans_are_live).
    assert "goal.solve" not in names

    def find(node, name):
        if node["name"] == name:
            return node
        for c in node["children"]:
            hit = find(c, name)
            if hit is not None:
                return hit
        return None

    assemble = find(trace["root"], "model.assemble")
    attrs = {a["key"]: a["value"] for a in assemble["attributes"]}
    assert "topology_hit" in attrs, "cache hit/miss must be attributed"
    assert "transfer_bytes" in attrs
    assert int(attrs["transfer_bytes"]["intValue"]) > 0
    dispatch = find(trace["root"], "solver.dispatch")
    dattrs = {a["key"]: a["value"] for a in dispatch["attributes"]}
    assert dattrs["route"] == {"stringValue": "fused"}
    # the round body looks tables up on the candidate grid's margins
    assert dattrs["accept_lookup"] == {"stringValue": "grid"}
    # and reduces the flat replica axis per broker as the CPU does
    assert dattrs["source_select"] == {"stringValue": "segment"}
    # and ranks the whole flat replica axis with lax.top_k: on a cluster
    # this small the two-level form's rows would be most of the axis
    assert dattrs["flat_topk"] == {"stringValue": "sort"}
    per_goal = [int(r) for r in
                dattrs["goal_rounds"]["stringValue"].split(",")]
    assert sum(per_goal) == int(dattrs["rounds"]["intValue"])
    assert [c["name"] for c in dispatch["children"]] == \
        ["solver.enqueue", "solver.wait"]
    fetch = find(trace["root"], "diff.fetch")
    fattrs = {a["key"]: a["value"] for a in fetch["attributes"]}
    assert int(fattrs["transfer_bytes"]["intValue"]) > 0


def test_sampling_fetch_traces_recorded(traced_api):
    traces = TRACER.traces(operation="sampling")
    assert traces, "each sampling cycle should record its own fetch trace"
    # the round is split where the work happens
    assert span_names(traces[0]) == [
        "monitor.sample_fetch", "sampling.describe", "sampling.get_samples",
        "sampling.ingest"]


def test_metrics_expose_histograms_and_device_telemetry(traced_api):
    # Run at least one traced operation first (module fixture already did).
    text = traced_api.metrics_text()
    # per-stage span histograms, well-formed
    for stage in ("monitor.aggregate", "model.assemble", "solver.dispatch",
                  "analyzer.optimize"):
        assert (f'kafka_cruisecontrol_trace_span_seconds_bucket'
                f'{{span="{stage}",le="+Inf"}}') in text, stage
    assert "# TYPE kafka_cruisecontrol_trace_span_seconds histogram" in text
    # XLA compile telemetry (per padded-shape labels)
    assert "kafka_cruisecontrol_xla_compile_seconds_bucket" in text
    assert 'shape="' in text
    # device memory gauges exist on every backend (CPU falls back to the
    # live-array footprint)
    assert "kafka_cruisecontrol_device_memory_bytes{" in text
    # transfer accounting from the model pipeline
    assert "kafka_cruisecontrol_device_transfer_bytes_total" in text
    # No duplicate sample lines anywhere: Prometheus rejects the whole
    # scrape if one series (name + label set) appears twice.
    samples = [ln.split(" ")[0] for ln in text.splitlines()
               if ln and not ln.startswith("#")]
    dupes = {s for s in samples if samples.count(s) > 1}
    assert not dupes, f"duplicate series in /metrics: {sorted(dupes)[:5]}"


def test_trace_endpoint_cluster_filter_no_fleet(traced_api):
    # ?cluster= on /trace FILTERS by recorded label (no fleet required;
    # nothing in this fixture ran under a cluster label).
    status, body, _ = traced_api.handle(
        "GET", "/kafkacruisecontrol/trace", "cluster=nosuch")
    assert status == 200
    assert body["numTraces"] == 0


def test_tracing_disabled_no_new_traces(traced_api):
    TRACER.configure(enabled=False)
    try:
        before = TRACER.spans_closed
        status, _body, _ = traced_api.handle(
            "POST", "/kafkacruisecontrol/rebalance", "dryrun=true")
        assert status == 200
        assert TRACER.spans_closed == before
        status, body, _ = traced_api.handle(
            "GET", "/kafkacruisecontrol/trace", "")
        assert status == 200 and body["tracingEnabled"] is False
    finally:
        TRACER.configure(enabled=True)


def test_jsonl_rotation_caps_file_size(tmp_path):
    """tracing.jsonl.max.bytes: an append that would push the dump past
    the cap rotates the file to <path>.1 first (one rotated generation
    kept — total footprint bounded at ~2x the cap); an unlimited cap (0)
    never rotates."""
    path = tmp_path / "trace.jsonl"
    tracer = Tracer()
    tracer.configure(jsonl_path=str(path))
    with tracer.span("sizer", operation="bench"):
        pass
    line_size = len(path.read_text())
    # Cap at ~2.5 lines: the 3rd close must rotate.
    tracer.configure(jsonl_max_bytes=int(2.5 * line_size))
    path.write_text("")  # restart the dump empty
    for _ in range(3):
        with tracer.span("sizer", operation="bench"):
            pass
    rotated = tmp_path / "trace.jsonl.1"
    assert rotated.exists(), "rotation did not happen"
    assert tracer.jsonl_rotations == 1
    assert len((rotated).read_text().splitlines()) == 2
    assert len(path.read_text().splitlines()) == 1
    # Every line in both generations is still valid JSON.
    for f in (path, rotated):
        for ln in f.read_text().splitlines():
            json.loads(ln)
    # A second overflow replaces the rotated generation (bounded at one).
    for _ in range(2):
        with tracer.span("sizer", operation="bench"):
            pass
    assert tracer.jsonl_rotations == 2
    assert len(rotated.read_text().splitlines()) == 2


def test_jsonl_no_rotation_when_unlimited(tmp_path):
    path = tmp_path / "trace.jsonl"
    tracer = Tracer()
    tracer.configure(jsonl_path=str(path), jsonl_max_bytes=0)
    for _ in range(5):
        with tracer.span("a", operation="bench"):
            pass
    assert not (tmp_path / "trace.jsonl.1").exists()
    assert len(path.read_text().splitlines()) == 5


# ---- xla_telemetry unit coverage (round 12 satellite) --------------------

def test_device_memory_bytes_cpu_live_array_fallback():
    """CPU backends have no allocator stats: refresh_device_gauges must
    fall back to the summed live jax.Array footprint so the
    device_memory_bytes series exists everywhere."""
    import jax.numpy as jnp

    from cruise_control_tpu.utils import xla_telemetry
    from cruise_control_tpu.utils.sensors import SENSORS

    keep = jnp.ones((256, 4), jnp.float32)  # ≥ 4 KB live on the device
    xla_telemetry.refresh_device_gauges()
    gauges = {k: v for k, v in SENSORS._gauges.items()
              if k[0] == "device_memory_bytes"}
    assert gauges, "no device_memory_bytes series on CPU"
    cpu_in_use = [(k, v) for k, v in gauges.items()
                  if ("kind", "bytes_in_use") in k[1]
                  and any(lk == "device" and lv.startswith("cpu")
                          for lk, lv in k[1])]
    assert cpu_in_use, f"no cpu bytes_in_use gauge in {list(gauges)}"
    assert max(v for _k, v in cpu_in_use) >= keep.nbytes


def test_record_dispatch_counter_and_histogram_labels():
    from cruise_control_tpu.utils import xla_telemetry
    from cruise_control_tpu.utils.sensors import SENSORS

    def counter(name, kind):
        return SENSORS._counters.get((name, (("kind", kind),)), 0.0)

    base = counter("solver_dispatches", "move")
    base_don = counter("solver_dispatch_donations", "move")
    base_spec = counter("solver_dispatch_speculative", "move")
    snap0 = SENSORS.histogram_snapshot("solver_dispatch_rounds",
                                       labels={"kind": "move"})
    count0 = snap0["count"] if snap0 else 0
    xla_telemetry.record_dispatch("move", rounds=12, donated=True)
    xla_telemetry.record_dispatch("move", rounds=3, speculative=True)
    assert counter("solver_dispatches", "move") == base + 2
    assert counter("solver_dispatch_donations", "move") == base_don + 1
    assert counter("solver_dispatch_speculative", "move") == base_spec + 1
    snap = SENSORS.histogram_snapshot("solver_dispatch_rounds",
                                      labels={"kind": "move"})
    assert snap["count"] == count0 + 2
    assert snap["buckets"] == xla_telemetry.DISPATCH_ROUND_BUCKETS
    # swap dispatches land in their OWN labeled series
    swap_base = counter("solver_dispatches", "swap")
    xla_telemetry.record_dispatch("swap", rounds=1)
    assert counter("solver_dispatches", "swap") == swap_base + 1


def test_record_dispatch_annotates_ambient_span():
    from cruise_control_tpu.utils import xla_telemetry
    tracer_was = TRACER.enabled
    TRACER.configure(enabled=True)
    try:
        with TRACER.span("goal.solve") as sp:
            xla_telemetry.record_dispatch("move", rounds=4)
            xla_telemetry.record_dispatch("move", rounds=4)
            assert sp.attributes["dispatches"] == 2
    finally:
        TRACER.configure(enabled=tracer_was)


def test_jsonl_rotation_cascade_keeps_max_files_generations(tmp_path):
    """tracing.jsonl.max.files: each overflow cascades .{N-1}->.N down to
    path->.1, keeping exactly max_files rotated generations (total
    footprint ~(max_files+1)x the cap); jsonl_rotations counts every
    generation MOVED, so a deep cascade is more than one per overflow."""
    path = tmp_path / "trace.jsonl"
    tracer = Tracer()
    tracer.configure(jsonl_path=str(path))
    with tracer.span("sizer", operation="bench"):
        pass
    line_size = len(path.read_text())
    tracer.configure(jsonl_max_bytes=int(1.5 * line_size),
                     jsonl_max_files=2)
    path.write_text("")  # restart the dump empty
    # Overflow #1: path -> .1 (one move).
    for _ in range(2):
        with tracer.span("sizer", operation="bench"):
            pass
    assert (tmp_path / "trace.jsonl.1").exists()
    assert not (tmp_path / "trace.jsonl.2").exists()
    assert tracer.jsonl_rotations == 1
    # Overflow #2 cascades: .1 -> .2, then path -> .1 (two moves).
    with tracer.span("sizer", operation="bench"):
        pass
    assert (tmp_path / "trace.jsonl.2").exists()
    assert tracer.jsonl_rotations == 3
    # Overflow #3: .2 is replaced (the ring is bounded at max_files);
    # every surviving generation holds exactly one valid-JSON line.
    with tracer.span("sizer", operation="bench"):
        pass
    assert tracer.jsonl_rotations == 5
    assert not (tmp_path / "trace.jsonl.3").exists()
    for f in (path, tmp_path / "trace.jsonl.1", tmp_path / "trace.jsonl.2"):
        lines = f.read_text().splitlines()
        assert len(lines) == 1
        json.loads(lines[0])


# ---- one trace a request, on the profiler's clock (ISSUE 25) -------------

def _walk(node):
    yield node
    for c in node["children"]:
        yield from _walk(c)


def _find(node, name):
    return next((n for n in _walk(node) if n["name"] == name), None)


def _attrs(node):
    return {a["key"]: next(iter(a["value"].values()))
            for a in node["attributes"]}


def _http_get(port, path, headers=None):
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/kafkacruisecontrol/{path}",
        headers=headers or {})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def _request_traces(endpoint="PROPOSALS"):
    return [t for t in TRACER.traces()
            if t["root"]["name"] == "http.request"
            and _attrs(t["root"]).get("endpoint") == endpoint]


def _await_request_traces():
    """The request's traces, once its root has closed (after the write)."""
    deadline = time.monotonic() + 5
    while not _request_traces() and time.monotonic() < deadline:
        time.sleep(0.01)
    return _request_traces()


_SERVED_TREE = (
    "http.handle", "http.serialize", "http.write", "monitor.cluster_model",
    "analyzer.optimize", "solver.dispatch", "solver.enqueue", "solver.wait",
    "analyzer.proposal_diff", "diff.stats", "diff.fetch", "diff.compare")


def _served(fleet: bool):
    """A deployment behind the real HTTP server on loopback, alone or as
    one cluster of a fleet whose scheduler's worker drains the solves."""
    from cruise_control_tpu.api.server import (
        make_server, serve_forever_in_thread,
    )
    cfg = CruiseControlConfig({
        "partition.metrics.window.ms": 1000,
        "num.partition.metrics.windows": 3,
        "min.valid.partition.ratio": 0.0,
        "max.solver.rounds": 30,
        "failed.brokers.file.path": "",
        # the fleet's solves take the serial route too, so that both
        # parameters of the fixture serve the same tree (the megabatch
        # route at occupancy 1 keeps the trace id as well, under
        # analyzer.megabatch)
        "fleet.megabatch.enabled": False})
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6,
                                       Resource.NW_OUT: 1e6})
    backend = InMemoryAdminBackend(_partitions().values())
    monitor = LoadMonitor(cfg, backend, samplers=[SyntheticSampler()],
                          capacity_resolver=caps)
    registry = scheduler = None
    if fleet:
        from cruise_control_tpu.fleet import FleetRegistry, FleetScheduler
        scheduler = FleetScheduler()
        registry = FleetRegistry(base_config=cfg, scheduler=scheduler)
    cc = CruiseControl(cfg, backend, load_monitor=monitor,
                       executor=Executor(backend, synchronous=True),
                       optimizer=registry.optimizer if fleet else None)
    for k in range(1, 4):
        monitor.task_runner.run_sampling_once(end_ms=k * 1000)
    if fleet:
        registry.register("alpha", cc=cc)
        scheduler.start(pacer=False)
    server, api = make_server(cc, host="127.0.0.1", port=0, fleet=registry)
    serve_forever_in_thread(server)
    return server, api, scheduler


@pytest.fixture(scope="module", params=["engine", "fleet"])
def served(request):
    server, api, scheduler = _served(fleet=request.param == "fleet")
    api._async_wait_s = 180
    yield server.server_address[1], api, request.param
    server.shutdown()
    server.server_close()
    api.shutdown()
    if scheduler is not None:
        scheduler.shutdown()
    TRACER.configure(enabled=True, jsonl_path=None)


def test_served_proposals_is_one_trace_rooted_at_the_request(served):
    """Through the task engine, and through the fleet worker: the spans
    of one request share the root's trace id."""
    port, _api, route = served
    TRACER.clear()
    status, headers, body = _http_get(
        port, "proposals?verbose=true&ignore_proposal_cache=true")
    assert status == 200 and "summary" in body
    traces = _await_request_traces()
    assert len(traces) == 1
    root = traces[0]["root"]
    names = [n["name"] for n in _walk(root)]
    for expected in _SERVED_TREE:
        assert expected in names, f"missing {expected} in {names}"
    assert ("fleet.job" in names) == (route == "fleet")
    assert {n["traceId"] for n in _walk(root)} == {traces[0]["traceId"]}
    # nothing of the request is left as a trace of its own
    others = [t["root"]["name"] for t in TRACER.traces()
              if t["traceId"] != traces[0]["traceId"]]
    assert not {"proposals", "fleet.job", "analyzer.optimize"} & set(others)
    dispatch = _find(root, "solver.dispatch")
    assert [c["name"] for c in dispatch["children"]] == \
        ["solver.enqueue", "solver.wait"]
    diff = _find(root, "analyzer.proposal_diff")
    assert [c["name"] for c in diff["children"]] == \
        ["diff.stats", "diff.fetch", "diff.compare"]
    attrs = _attrs(root)
    assert attrs["method"] == "GET" and attrs["endpoint"] == "PROPOSALS"
    assert attrs["status"] == "200" and int(attrs["bytes"]) > 0
    assert attrs["userTaskId"] == headers["User-Task-ID"]
    assert traces[0]["operations"] == ["http.request", "proposals"] \
        or "proposals" in traces[0]["operations"]


def test_children_of_the_request_cover_it(served):
    port, _api, _route = served
    TRACER.clear()
    _http_get(port, "proposals?verbose=true&ignore_proposal_cache=true")
    root = _await_request_traces()[0]["root"]
    direct = [c for c in root["children"]
              if c["name"] in ("http.handle", "http.serialize", "http.write")]
    assert [c["name"] for c in direct] == \
        ["http.handle", "http.serialize", "http.write"]
    covered = sum(c["durationMs"] for c in direct)
    assert covered >= 0.95 * root["durationMs"]
    assert covered <= root["durationMs"] + 1e-6


def test_root_span_bytes_is_the_compact_texts_length(served):
    """What ``GET /trace`` shows of a body's size is the wire's: the
    length of the compact JSON text of the document (ISSUE 30)."""
    import urllib.request
    port, _api, _route = served
    TRACER.clear()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/kafkacruisecontrol/proposals"
            "?verbose=true&ignore_proposal_cache=true", timeout=300) as resp:
        raw = resp.read()
    body = json.loads(raw)
    assert body["proposals"]
    attrs = _attrs(_await_request_traces()[0]["root"])
    assert int(attrs["bytes"]) == len(raw) \
        == len(json.dumps(body, separators=(",", ":")).encode())


def test_202_and_its_poll_share_the_task_and_the_first_trace(
        served, monkeypatch, tmp_path):
    """A task that outlives its first response keeps the trace id of the
    request that created it; each poll is its own http.request with the
    same userTaskId, and leaves no trace of its own. The trace is rung
    and dumped when the task has ended, so the dump's line holds the
    task's spans."""
    port, api, _route = served
    TRACER.clear()
    dump = tmp_path / "traces.jsonl"
    TRACER.configure(jsonl_path=str(dump))
    roots = []
    real = TRACER._close
    monkeypatch.setattr(
        TRACER, "_close",
        lambda span: (roots.append(span) if span.parent is None else None,
                      real(span))[1])
    api._async_wait_s = 0.0          # answer "in progress" at once
    try:
        status, headers, body = _http_get(
            port, "proposals?verbose=true&ignore_proposal_cache=true")
        assert status == 200 and "progress" in body
        task_id = headers["User-Task-ID"]
    finally:
        api._async_wait_s = 180
    status, _h, body = _http_get(port, "proposals?verbose=true"
                                 "&ignore_proposal_cache=true",
                                 headers={"User-Task-ID": task_id})
    assert status == 200 and "summary" in body
    deadline = time.monotonic() + 5
    while (not _request_traces() or len(roots) < 2) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    TRACER.configure(jsonl_path=None)
    requests = [r for r in roots if r.name == "http.request"]
    assert [r.attributes["userTaskId"] for r in requests] == \
        [task_id, task_id]
    assert requests[0].trace_id != requests[1].trace_id
    # the poll ran no operation: its histogram sample stands, no trace
    traces = _request_traces()
    assert len(traces) == 1
    first = traces[0]
    assert first["traceId"] == requests[0].trace_id
    # the task's spans closed into the FIRST request's tree
    task = _find(first["root"], "analyzer.optimize")
    assert task is not None and task["traceId"] == first["traceId"]
    assert "proposals" in first["operations"]
    assert first["spanCount"] == len(list(_walk(first["root"])))
    lines = [json.loads(ln) for ln in dump.read_text().splitlines()]
    assert [ln["traceId"] for ln in lines] == [first["traceId"]]
    assert _find(lines[0]["root"], "analyzer.optimize") is not None


def test_a_served_request_keeps_its_cluster(served):
    """The root closes on the handler thread, outside the cluster label:
    the trace still belongs to the cluster the front door routed to."""
    port, _api, route = served
    TRACER.clear()
    _http_get(port, "proposals?verbose=true&ignore_proposal_cache=true")
    trace = _await_request_traces()[0]
    if route == "fleet":
        assert trace["cluster"] == "alpha"
        assert [t["traceId"] for t in TRACER.traces(cluster="alpha")] == \
            [trace["traceId"]]
        assert TRACER.traces(cluster="beta") == []
        _s, _h, body = _http_get(port, "trace?cluster=alpha")
        assert [t["traceId"] for t in body["traces"]] == [trace["traceId"]]
    else:
        assert trace["cluster"] is None
        assert TRACER.traces(cluster="alpha") == []


def test_requests_that_ran_nothing_leave_no_trace(served):
    """Scrapes, reads of the trace ring and unknown paths feed the
    histogram and stay out of the ring."""
    import urllib.request
    from cruise_control_tpu.utils.sensors import SENSORS
    port, _api, _route = served

    def requests_seen():
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in SENSORS.render().splitlines()
                   if "trace_span_seconds_count{" in ln
                   and 'span="http.request"' in ln)

    # a request's root span closes after its body is written, so the last
    # request of the test before may not be counted yet: let it land
    before = requests_seen()
    while True:
        time.sleep(0.05)
        if requests_seen() == before:
            break
        before = requests_seen()
    TRACER.clear()
    completed = TRACER.traces_completed
    for _ in range(3):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=60) as resp:
            resp.read()
    _http_get(port, "trace")
    deadline = time.monotonic() + 5
    while requests_seen() < before + 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert requests_seen() == before + 4
    assert TRACER.traces() == [] and TRACER.traces_completed == completed


def test_http_spans_alone_label_their_histogram_with_the_endpoint(served):
    from cruise_control_tpu.utils.sensors import SENSORS
    port, _api, _route = served
    _http_get(port, "state?substates=monitor")
    text = SENSORS.render()
    for span in ("http.request", "http.handle", "http.serialize",
                 "http.write"):
        assert (f'trace_span_seconds_count{{endpoint="STATE",'
                f'span="{span}"}}') in text, span
    labelled = [ln for ln in text.splitlines()
                if "trace_span_seconds_count{" in ln and "endpoint=" in ln]
    assert labelled and all('span="http.' in ln for ln in labelled)


def test_profiler_capture_holds_the_request_on_the_host_plane(
        served, tmp_path):
    """A live span is a ``cc.<name>`` event of a running profiler session
    (and so is a journey segment): program and device share one clock."""
    import jax
    from cruise_control_tpu.utils.profile_summary import load_events
    port, _api, _route = served
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _http_get(port, "proposals?verbose=true&ignore_proposal_cache=true")
        time.sleep(0.2)      # the root closes after the client has the body
    finally:
        jax.profiler.stop_trace()
    host = {name for name, _s, _d in load_events(str(tmp_path))["host"]}
    for name in ("cc.http.request", "cc.http.handle", "cc.analyzer.optimize",
                 "cc.solver.wait", "cc.diff.compare", "cc.render"):
        assert name in host, f"{name} not in {sorted(host)}"


def test_tracing_disabled_no_span_no_annotation_no_histogram(
        served, monkeypatch):
    from cruise_control_tpu.utils import tracing
    from cruise_control_tpu.utils.sensors import SENSORS
    port, _api, _route = served

    def samples():
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in SENSORS.render().splitlines()
                   if "trace_span_seconds_count" in ln)

    entered = []
    real = tracing.annotation
    monkeypatch.setattr(tracing, "annotation",
                        lambda name: entered.append(name) or real(name))
    TRACER.configure(enabled=False)
    try:
        closed, before = TRACER.spans_closed, samples()
        TRACER.clear()
        status, _h, _b = _http_get(
            port, "proposals?verbose=true&ignore_proposal_cache=true")
        assert status == 200
        assert TRACER.spans_closed == closed and samples() == before
        assert TRACER.traces() == [] and entered == []
    finally:
        TRACER.configure(enabled=True)


def test_attach_carries_the_parent_across_a_thread():
    tracer = Tracer()
    with tracer.span("request") as parent:
        def work():
            with tracer.attach(parent):
                with tracer.span("task"):
                    pass
            assert tracer.current_span() is None
        t = threading.Thread(target=work)
        t.start()
        t.join(5)
        assert not t.is_alive()
    (trace,) = tracer.traces()
    assert span_names(trace) == ["request", "task"]
    assert trace["root"]["children"][0]["traceId"] == trace["traceId"]
    # nothing to attach: the block runs as it would have
    with tracer.attach(None):
        assert tracer.current_span() is None


def test_no_negative_duration_when_the_wall_clock_steps_back(monkeypatch):
    """Durations come from the monotonic clock; the wall clock only
    places the export, through one anchor read at import."""
    wall = iter(range(10**9, 0, -10**6))
    monkeypatch.setattr(time, "time", lambda: next(wall))
    monkeypatch.setattr(time, "time_ns", lambda: next(wall) * 10**9)
    tracer = Tracer()
    with tracer.span("outer"):
        for _ in range(3):
            with tracer.span("inner"):
                time.sleep(0.001)
    (trace,) = tracer.traces()
    nodes = list(_walk(trace["root"]))
    assert len(nodes) == 4
    for n in nodes:
        assert n["durationMs"] > 0
        assert int(n["endTimeUnixNano"]) > int(n["startTimeUnixNano"])
    starts = [int(n["startTimeUnixNano"]) for n in nodes]
    assert starts == sorted(starts)


def test_bounded_route_goal_spans_are_live():
    """Above solver.fused.chain.max.brokers every goal is a live
    ``goal.solve`` span over the pump's passes; none is apportioned."""
    from cruise_control_tpu.analyzer.optimizer import GoalOptimizer
    from cruise_control_tpu.model.fixtures import random_cluster
    state, meta = random_cluster(8, 2, 96, seed=5, skew_to_first=0.6)
    opt = GoalOptimizer(CruiseControlConfig(
        {"solver.fused.chain.max.brokers": 4, "max.solver.rounds": 30}))
    TRACER.clear()
    opt.optimizations(state, meta)
    (trace,) = [t for t in TRACER.traces()
                if t["root"]["name"] == "analyzer.optimize"]
    goals = [n for n in _walk(trace["root"]) if n["name"] == "goal.solve"]
    assert len(goals) == 15          # the default chain, goal by goal
    assert all("apportioned" not in _attrs(g) for g in goals)
    dispatches = [n for g in goals for n in _walk(g)
                  if n["name"] == "solver.dispatch"]
    assert dispatches, "a goal with work runs passes of the pump"
    for d in dispatches:
        assert _attrs(d)["route"] == "bounded"
        kinds = {c["name"] for c in d["children"]}
        assert kinds <= {"solver.enqueue", "solver.wait"} and kinds


def test_trace_and_lower_seconds_move_on_a_first_jit_call_only():
    import jax
    import jax.numpy as jnp
    from cruise_control_tpu.utils import xla_telemetry
    from cruise_control_tpu.utils.sensors import SENSORS
    xla_telemetry.install(enabled=True)

    def counts():
        text = SENSORS.render()
        return [sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                    if ln.startswith(f"kafka_cruisecontrol_{name}_count"))
                for name in ("xla_trace_seconds", "xla_lower_seconds")]

    @jax.jit
    def fresh(x):
        return (x * 3 + 1).sum()

    before = counts()
    with xla_telemetry.shape_scope(48, 6):
        fresh(jnp.arange(7.0)).block_until_ready()
    first = counts()
    assert first[0] > before[0] and first[1] > before[1]
    assert 'xla_trace_seconds_count{shape="p48_b6"}' in SENSORS.render()
    fresh(jnp.arange(7.0)).block_until_ready()
    assert counts() == first


def test_a_nested_trace_is_counted_once():
    """jax reports a nested jit's trace inside its caller's duration too:
    the histograms hold each event's own seconds, so trace + lower +
    compile of a first call do not pass the seconds the call took."""
    import jax
    import jax.numpy as jnp
    from cruise_control_tpu.utils import xla_telemetry
    from cruise_control_tpu.utils.sensors import SENSORS
    xla_telemetry.install(enabled=True)

    def seconds():
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in SENSORS.render().splitlines()
                   if ln.startswith(("kafka_cruisecontrol_xla_trace_seconds_sum",
                                     "kafka_cruisecontrol_xla_lower_seconds_sum",
                                     "kafka_cruisecontrol_xla_compile_seconds_sum")))

    @jax.jit
    def inner(x):
        time.sleep(0.05)             # the inner trace takes 50 ms
        return x * 2

    @jax.jit
    def middle(x):
        return inner(x) + inner(x + 1)

    @jax.jit
    def outer(x):
        return middle(x).sum() + jnp.arange(3.0).sum()

    before = seconds()
    t0 = time.monotonic()
    outer(jnp.arange(5.0)).block_until_ready()
    wall = time.monotonic() - t0
    spent = seconds() - before
    # inclusive durations would give over 3 x 50 ms more than the wall
    assert 0.05 <= spent <= wall
    assert not getattr(xla_telemetry._OPEN, "stack", None)


_ROUND_SCOPES = ("round.agg_refresh", "round.score", "round.score_derived",
                 "round.score_goals", "round.score_offline",
                 "round.source_topk",
                 "round.candidates", "round.deltas", "round.accept",
                 "round.select", "round.apply", "round.flight_stats",
                 "swap.round", "goal.stats", "goal.agg")


def test_lowered_chain_names_every_scope_and_solves_as_the_parent_did():
    """The scopes are metadata: the lowered fused chain names each, and
    rounds, moves and balancedness are bit for bit what the commit before
    them gave on CPU (pinned from a run of that commit)."""
    import re

    from cruise_control_tpu.analyzer import optimizer as opt_mod
    from cruise_control_tpu.analyzer.chain import chain_optimize_full
    from cruise_control_tpu.analyzer.search import ExclusionMasks
    from cruise_control_tpu.model.fixtures import random_cluster
    state, meta = random_cluster(16, 4, 512, seed=3, skew_to_first=0.6)
    cfg = CruiseControlConfig({})
    opt = opt_mod.GoalOptimizer(cfg)
    lowered = chain_optimize_full.lower(
        state, tuple(opt_mod.goals_by_priority(cfg)), opt._constraint,
        opt.search_config(state), meta.num_topics, ExclusionMasks())
    named = set(re.findall(r"(?:round|swap|goal)\.[a-z_]+",
                           lowered.as_text(debug_info=True)))
    assert named == set(_ROUND_SCOPES)
    _final, result = opt.optimizations(state, meta)
    assert [g.rounds for g in result.goal_results] == \
        [4, 0, 0, 0, 0, 2, 3, 1, 15, 0, 4, 0, 9, 2, 0]
    assert [g.moves_applied for g in result.goal_results] == \
        [289, 0, 0, 0, 0, 64, 91, 0, 24, 0, 37, 0, 70, 2, 0]
    assert len(result.proposals) == 373
    assert result.balancedness_after == 89.57958658572481


# -- the collector: every pause counted where it falls (PR 36) ---------------

import gc  # noqa: E402

from cruise_control_tpu.utils import tracing  # noqa: E402
from cruise_control_tpu.utils.sensors import SENSORS, SensorRegistry  # noqa: E402


@pytest.fixture
def collector(monkeypatch):
    """The hook installed over integers of the test's own and a registry
    of its own, with the automatic collections off: what is counted is
    what the test forces."""
    registry = SensorRegistry()
    monkeypatch.setattr(tracing, "SENSORS", registry)
    for name in ("_gc_collections", "_gc_ns", "_gc_last_ns", "_gc_max_ns"):
        monkeypatch.setattr(tracing, name, [0] * tracing.GENERATIONS)
    monkeypatch.setattr(tracing, "_gc_pause_ns", 0)
    was_installed = tracing._on_collection in gc.callbacks
    tracing.watch_collector(False)
    gc.collect()
    gc.disable()
    try:
        tracing.watch_collector(True)
        yield registry
    finally:
        tracing.watch_collector(False)
        gc.enable()
        monkeypatch.undo()
        tracing.watch_collector(was_installed)


def _sample(registry, series: str) -> float | None:
    for line in registry.render().splitlines():
        if line.startswith("kafka_cruisecontrol_" + series + " "):
            return float(line.rsplit(" ", 1)[1])
    return None


def test_the_three_series_exist_at_zero_once_the_hook_is_installed(collector):
    for generation in "012":
        label = f'{{generation="{generation}"}}'
        assert _sample(collector, "python_gc_collections_total" + label) == 0
        assert _sample(collector, "python_gc_pause_seconds_sum" + label) == 0
        assert _sample(collector, "python_gc_pause_seconds_count" + label) == 0
    assert _sample(collector, "python_allocated_blocks") > 0
    assert tracing.gc_pause_ns() == 0


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_the_hook_counts_a_forced_collection_by_generation(collector,
                                                           generation):
    blocks_at_install = _sample(collector, "python_allocated_blocks")
    gc.collect(generation)
    assert tracing._gc_collections == [int(g == generation)
                                       for g in range(3)]
    pause_ns = tracing._gc_ns[generation]
    assert pause_ns > 0 and tracing.gc_pause_ns() == pause_ns
    label = f'{{generation="{generation}"}}'
    assert _sample(collector, "python_gc_collections_total" + label) == 1
    assert _sample(collector, "python_gc_pause_seconds_count" + label) == 1
    assert _sample(collector, "python_gc_pause_seconds_sum" + label) == \
        pytest.approx(pause_ns / 1e9)
    assert _sample(collector, "python_gc_pause_seconds_max" + label) == \
        pytest.approx(pause_ns / 1e9)
    # the heap's blocks are read at the install and at the end of a FULL
    # collection only: the reading walks every pool of the heap
    keep = [[i] for i in range(5000)]
    gc.collect(generation)
    grew = _sample(collector, "python_allocated_blocks") - blocks_at_install
    assert (grew >= len(keep)) == (generation == 2)


def test_installing_twice_leaves_one_entry_and_removal_none(collector):
    tracing.watch_collector(True)
    assert gc.callbacks.count(tracing._on_collection) == 1
    assert collector._refreshes.count(tracing._publish_collector) == 1
    tracing.watch_collector(False)
    assert tracing._on_collection not in gc.callbacks
    assert tracing._publish_collector not in collector._refreshes
    gc.collect()
    assert tracing.gc_pause_ns() == 0


def test_a_span_a_collection_falls_inside_gets_gcms_and_its_own_sample(
        collector):
    tracer = Tracer()
    with tracer.span("http.request", label_keys=("endpoint",),
                     endpoint="PROPOSALS") as outer:
        with tracer.span("render") as inside:
            gc.collect()
        with tracer.span("http.write") as beside:
            pass
    with tracer.span("http.request", label_keys=("endpoint",),
                     endpoint="STATE") as after:
        pass
    pause_ms = tracing.gc_pause_ns() / 1e6
    assert inside.attributes["gcMs"] == pytest.approx(pause_ms, abs=1e-3)
    assert outer.attributes["gcMs"] == inside.attributes["gcMs"]
    assert "gcMs" not in beside.attributes and "gcMs" not in after.attributes
    # under the labels of the span's own trace_span_seconds series
    counter = "trace_span_gc_seconds_total"
    assert _sample(collector, counter + '{span="render"}') == \
        pytest.approx(pause_ms / 1e3)
    assert _sample(
        collector, counter + '{endpoint="PROPOSALS",span="http.request"}') \
        == pytest.approx(pause_ms / 1e3)
    assert _sample(collector, "trace_span_seconds_count"
                   '{endpoint="PROPOSALS",span="http.request"}') == 1
    assert _sample(collector, counter + '{span="http.write"}') is None
    assert _sample(
        collector, counter + '{endpoint="STATE",span="http.request"}') is None
    # GET /trace's shape carries the attribute
    exported = tracer.traces()[1]["root"]
    assert {"key": "gcMs", "value": {"doubleValue": outer.attributes["gcMs"]}} \
        in exported["attributes"]


def test_a_journey_segment_a_collection_falls_inside_feeds_its_counter(
        collector, monkeypatch):
    from cruise_control_tpu.serving import journey as journey_mod
    monkeypatch.setattr(journey_mod, "SENSORS", collector)
    log = journey_mod.JourneyLog()
    journey = log.open("PROPOSALS")
    with journey.seg("render"):
        gc.collect()
    with journey.seg("proposal_diff"):
        pass
    log.close(journey)
    pause_s = tracing.gc_pause_ns() / 1e9
    counter = "journey_segment_gc_seconds_total"
    assert _sample(collector, counter
                   + '{endpoint="PROPOSALS",segment="render"}') == \
        pytest.approx(pause_s)
    assert _sample(collector, counter
                   + '{endpoint="PROPOSALS",segment="proposal_diff"}') is None
    assert _sample(collector, "journey_segment_seconds_count"
                   '{endpoint="PROPOSALS",segment="render"}') == 1
    # a pause is real time and a journey's clock may be the twin's: the
    # exported record stays what the injected clock made it
    assert "gc" not in json.dumps(log.entries()).lower()


@pytest.mark.parametrize("holder", ["SENSORS", "TRACER"])
def test_a_collection_under_a_held_lock_returns(collector, holder):
    """The callback takes no lock: a collection that starts inside the
    registry's or the tracer's own ``with self._lock:`` block (an
    allocation there is enough) must not stop its thread against itself."""
    lock = collector._lock if holder == "SENSORS" else TRACER._lock
    done = []

    def collect_under_the_lock():
        with lock:
            gc.collect()
        done.append(True)

    thread = threading.Thread(target=collect_under_the_lock, daemon=True)
    thread.start()
    thread.join(5)
    assert not thread.is_alive() and done == [True]
    assert tracing._gc_collections[2] == 1


def test_a_full_collection_is_one_gc_gen2_annotation(collector, monkeypatch):
    events = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    monkeypatch.setattr(tracing, "_TRACE_ANNOTATION", Recorder)
    gc.collect(0)
    gc.collect(1)
    assert events == []      # the young generations are counted only
    gc.collect()
    assert events == [("enter", "cc.gc.gen2"), ("exit", "cc.gc.gen2")]


def test_the_hook_follows_tracing_enabled(collector):
    """``facade.py`` installs the hook where it configures the tracer: with
    ``tracing.enabled=false`` the program has no entry in ``gc.callbacks``
    and no ``python_gc_*`` series."""
    tracing.watch_collector(False)
    collector.clear()
    backend = InMemoryAdminBackend(_partitions().values())

    def facade(enabled):
        cc = CruiseControl(CruiseControlConfig({
            "tracing.enabled": enabled, "failed.brokers.file.path": ""}),
            backend)
        cc.shutdown()

    try:
        facade("false")
        assert not TRACER.enabled
        assert tracing._on_collection not in gc.callbacks
        assert "python_gc" not in collector.render()
        facade("true")
        assert gc.callbacks.count(tracing._on_collection) == 1
        assert _sample(collector, "python_gc_collections_total"
                       '{generation="2"}') is not None
        facade("false")
        assert tracing._on_collection not in gc.callbacks
    finally:
        TRACER.configure(enabled=True)


def test_get_trace_shows_gcms_on_the_request_that_paid_the_pause(
        traced_api, collector, monkeypatch):
    monitor = traced_api._cc._load_monitor
    real = monitor.cluster_model

    def collect_then_build(*args, **kwargs):
        gc.collect()
        return real(*args, **kwargs)

    def rebalance_trace():
        status, body, _ = traced_api.handle(
            "POST", "/kafkacruisecontrol/rebalance", "dryrun=true")
        assert status == 200, body
        status, body, _ = traced_api.handle(
            "GET", "/kafkacruisecontrol/trace",
            "operation=rebalance&entries=1")
        return list(_walk(body["traces"][0]["root"]))

    monkeypatch.setattr(monitor, "cluster_model", collect_then_build)
    paid = {node["name"]: _attrs(node) for node in rebalance_trace()}
    assert paid["rebalance"]["gcMs"] > 0
    # the pause fell before the model build opened its span
    assert "gcMs" not in paid["monitor.cluster_model"]
    monkeypatch.setattr(monitor, "cluster_model", real)
    assert all("gcMs" not in _attrs(node) for node in rebalance_trace())
