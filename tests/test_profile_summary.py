"""``utils/profile_summary.py``: from a profiler capture to the summary
``GET /profile`` returns. The arithmetic by hand on small lists, on an
excerpt recorded on the chip (6.5 ms of a 16-broker served solve on a TPU
v5e: ``fixtures/profile_excerpt.json``, what ``load_events`` gave), and
the loader on an ``.xplane.pb`` written here in the wire format. No test
needs a chip, and none reports a device number."""

import json
import os

import pytest

from cruise_control_tpu.utils import profile_summary as ps

EXCERPT = os.path.join(os.path.dirname(__file__), "fixtures",
                       "profile_excerpt.json")
DEV = "/device:TPU:0"


def _events(ops, modules=(), host=()):
    return {"devices": {DEV: {"ops": [list(o) for o in ops],
                              "modules": [list(m) for m in modules]}},
            "host": [list(h) for h in host]}


def test_reduce_by_hand_idle_by_span_and_seconds_by_scope():
    # 10 us window. Device ops (ns): a while [1000, 7000) spanning two
    # body ops, then one unscoped op [8000, 9000).
    ops = [("while.1", "(unscoped)", 1000.0, 6000.0),
           ("fusion.1", "round.accept", 1500.0, 2000.0),
           ("fusion.2", "round.select", 4000.0, 1000.0),
           ("copy.3", "(unscoped)", 8000.0, 1000.0)]
    modules = [("jit_chain_optimize_full", 1000.0, 6000.0),
               ("jit_cluster_stats", 8000.0, 1000.0)]
    # Host spans of two threads: the request [0, 10000) with a child
    # [7000, 7600); nothing covers [9500, 10000) but the request.
    host = [("cc.http.request", 0.0, 10000.0),
            ("cc.diff.fetch", 7000.0, 600.0)]
    got = ps.reduce(_events(ops, modules, host))
    assert got["windowS"] == pytest.approx(10e-6)
    assert got["busyS"] == pytest.approx(7e-6)
    assert got["idlePct"] == pytest.approx(30.0)
    assert got["deviceSecondsByProgram"] == {
        "jit_chain_optimize_full": 6e-06, "jit_cluster_stats": 1e-06}
    # the while's own time is what its body does not cover
    assert got["deviceSecondsByScope"] == {
        "(unscoped)": 4e-06, "round.accept": 2e-06, "round.select": 1e-06}
    assert got["unscopedSecondsByProgram"] == {
        "jit_chain_optimize_full": 3e-06, "jit_cluster_stats": 1e-06}
    # gaps [0,1000) [7000,8000) [9000,10000): the innermost covering span
    assert got["idleSecondsBySpan"] == {
        "http.request": pytest.approx(2.4e-06),
        "diff.fetch": pytest.approx(0.6e-06)}


def test_reduce_by_hand_names_the_largest_operations():
    # Two requests of one program: the while's own time is what its body
    # does not cover, an operation's events are counted, and fusion.1 of
    # another program is another operation.
    ops = [("while.1", "(unscoped)", 1000.0, 6000.0),
           ("fusion.1", "round.accept", 1500.0, 2000.0),
           ("fusion.2", "round.score_goals", 4000.0, 1000.0),
           ("fusion.1", "(unscoped)", 8000.0, 1000.0),
           ("while.1", "(unscoped)", 11000.0, 6000.0),
           ("fusion.1", "round.accept", 11500.0, 2500.0),
           ("copy.9", "(unscoped)", 19000.0, 100.0)]
    modules = [("jit_chain_optimize_full", 1000.0, 6000.0),
               ("jit_cluster_stats", 8000.0, 1000.0),
               ("jit_chain_optimize_full", 11000.0, 6000.0)]
    got = ps.reduce(_events(ops, modules))
    chain, stats = "jit_chain_optimize_full", "jit_cluster_stats"
    assert got["deviceSecondsByOperation"] == [
        {"operation": "while.1", "program": chain, "scope": "(unscoped)",
         "seconds": 6.5e-06, "events": 2.0},
        {"operation": "fusion.1", "program": chain, "scope": "round.accept",
         "seconds": 4.5e-06, "events": 2.0},
        {"operation": "fusion.2", "program": chain,
         "scope": "round.score_goals", "seconds": 1e-06, "events": 1.0},
        {"operation": "fusion.1", "program": stats, "scope": "(unscoped)",
         "seconds": 1e-06, "events": 1.0},
        {"operation": "copy.9", "program": "(no program)",
         "scope": "(unscoped)", "seconds": 1e-07, "events": 1.0}]
    assert sum(o["seconds"] for o in got["deviceSecondsByOperation"]) == \
        pytest.approx(got["busyS"])


def test_reduce_keeps_the_ten_largest_operations_as_means_over_devices():
    ops = [(f"fusion.{i}", "round.accept", 100.0 * i, 10.0 + i)
           for i in range(12)]
    events = _events(ops, [("jit_f", 0.0, 2000.0)])
    events["devices"]["/device:TPU:1"] = {
        "ops": [list(o) for o in ops[6:]], "modules": [["jit_f", 0.0, 2000.0]]}
    got = ps.reduce(events)["deviceSecondsByOperation"]
    assert len(got) == ps.LARGEST_OPERATIONS == 10
    assert [o["operation"] for o in got[:6]] == \
        [f"fusion.{i}" for i in range(11, 5, -1)]
    assert got[0] == {"operation": "fusion.11", "program": "jit_f",
                      "scope": "round.accept", "seconds": 2.1e-08,
                      "events": 1.0}
    # on one device of two: half an event, half its seconds
    assert got[-1] == {"operation": "fusion.2", "program": "jit_f",
                       "scope": "round.accept", "seconds": 6e-09,
                       "events": 0.5}


def test_reduce_on_the_recorded_excerpt_names_its_operations():
    with open(EXCERPT) as f:
        got = ps.reduce(json.load(f))
    largest = got["deviceSecondsByOperation"]
    assert len(largest) == 10
    assert largest[0] == {
        "operation": "select_select_fusion.345",
        "program": "jit_chain_optimize_full", "scope": "round.accept",
        "seconds": pytest.approx(0.000112672, abs=1e-9), "events": 2.0}
    assert [o["seconds"] for o in largest] == \
        sorted((o["seconds"] for o in largest), reverse=True)
    assert all(o["program"] == "jit_chain_optimize_full" for o in largest)
    # the ten are a part of the scopes' seconds, not more
    by_scope: dict = {}
    for o in largest:
        by_scope[o["scope"]] = by_scope.get(o["scope"], 0.0) + o["seconds"]
    assert all(seconds <= got["deviceSecondsByScope"][scope] + 1e-9
               for scope, seconds in by_scope.items())


def test_reduce_labels_uncovered_idle_no_span():
    ops = [("fusion.1", "round.accept", 0.0, 1000.0),
           ("fusion.2", "round.accept", 5000.0, 1000.0)]
    host = [("cc.solver.wait", 2000.0, 1000.0)]
    got = ps.reduce(_events(ops, host=host))
    assert got["idleSecondsBySpan"] == {
        "(no span)": pytest.approx(3e-06),
        "solver.wait": pytest.approx(1e-06)}
    # no host span at all: every gap is (no span)
    got = ps.reduce(_events(ops))
    assert got["idleSecondsBySpan"] == {"(no span)": pytest.approx(4e-06)}


def test_reduce_innermost_span_is_the_latest_started_across_threads():
    ops = [("fusion.1", "round.accept", 0.0, 100.0),
           ("fusion.2", "round.accept", 900.0, 100.0)]
    host = [("cc.http.request", 50.0, 900.0),        # handler thread
            ("cc.proposals", 100.0, 700.0),           # worker thread
            ("cc.monitor.cluster_model", 200.0, 100.0),
            ("cc.render", 850.0, 40.0)]               # after proposals
    got = ps.reduce(_events(ops, host=host))
    assert got["idleSecondsBySpan"] == {
        "proposals": pytest.approx(600e-9),
        "monitor.cluster_model": pytest.approx(100e-9),
        "http.request": pytest.approx(60e-9),
        "render": pytest.approx(40e-9)}


def test_a_full_collection_is_the_innermost_span_of_its_idle():
    """``cc.gc.gen2`` starts after every span that is open when the
    collection runs, so the timeline picks it: idle under a pause reads
    ``gc.gen2``, on whichever thread the pause ran."""
    ops = [("fusion.1", "round.accept", 0.0, 100.0),
           ("fusion.2", "round.accept", 900.0, 100.0)]
    host = [("cc.http.request", 50.0, 900.0),
            ("cc.render", 200.0, 600.0),
            ("cc.gc.gen2", 300.0, 250.0),             # inside the render
            ("cc.bench.client", 0.0, 1000.0)]         # another thread
    got = ps.reduce(_events(ops, host=host))
    assert got["idleSecondsBySpan"] == {
        "render": pytest.approx(350e-9),
        "gc.gen2": pytest.approx(250e-9),
        "http.request": pytest.approx(200e-9)}


def test_reduce_without_device_ops_is_none():
    assert ps.reduce({"devices": {}, "host": [["cc.http.request", 0, 5]]}) \
        is None
    assert ps.reduce(_events([])) is None


@pytest.mark.parametrize("text,scope", [
    ("jit(chain_optimize_full)/while/body/round.accept/reduce_or:",
     "round.accept"),
    ("jit(f)/while/body/round.candidates/round.source_topk/top_k:",
     "round.source_topk"),
    ("jit(f)/cond/branch_1_fun/while/body/swap.round/gather:", "swap.round"),
    ("jit(f)/while/body/goal.stats/jit(_where)/select_n:", "goal.stats"),
    ("jit(f)/while/body/round.score/round.score_derived/reduce_sum:",
     "round.score_derived"),
    ("jit(f)/while/body/round.score/round.score_goals/goal.agg/add:",
     "goal.agg"),
    ("jit(f)/while/body/round.score/round.score_goals/select_n:",
     "round.score_goals"),
    ("jit(f)/while/body/round.score/round.score_offline/scatter-add:",
     "round.score_offline"),
    ("jit(cluster_stats)/reduce_sum:", "(unscoped)"),
    ("jit(f)/my_round.accepted/add:", "(unscoped)"),
    ("", "(unscoped)"),
])
def test_scope_of(text, scope):
    assert ps.scope_of(text) == scope


def test_op_name_is_the_instruction_not_its_hlo_line():
    assert ps.op_name("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), "
                      "kind=kLoop") == "fusion.7"


def test_reduce_on_the_recorded_excerpt():
    with open(EXCERPT) as f:
        events = json.load(f)
    assert len(events["devices"][DEV]["ops"]) == 1096
    got = ps.reduce(events)
    assert got["numDevices"] == 1
    assert got["windowS"] == pytest.approx(0.0065)
    assert got["busyS"] == pytest.approx(0.003996, abs=1e-6)
    assert got["idlePct"] == pytest.approx(38.524, abs=0.01)
    assert got["deviceSecondsByProgram"]["jit_chain_optimize_full"] == \
        pytest.approx(0.004)
    scopes = got["deviceSecondsByScope"]
    assert list(scopes)[0] == "round.accept"
    assert scopes["round.accept"] == pytest.approx(0.002328, abs=1e-6)
    assert scopes["(unscoped)"] == pytest.approx(0.000136, abs=1e-6)
    # every device second is in exactly one scope
    assert sum(scopes.values()) == pytest.approx(got["busyS"], abs=2e-5)
    assert sum(got["unscopedSecondsByProgram"].values()) == \
        pytest.approx(scopes["(unscoped)"], abs=2e-6)
    idle = got["idleSecondsBySpan"]
    assert "(no span)" not in idle
    assert list(idle)[:2] == ["model.assemble", "analyzer.optimize"]
    assert sum(idle.values()) == pytest.approx(
        got["windowS"] - got["busyS"], abs=2e-6)


# -- the loader, on a capture written here in the protobuf wire format ------

def _varint(n):
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _map(number, key, message):
    return _field(number, _field(1, key) + _field(2, message))


def _plane(name, stat_names, event_meta, lines):
    """event_meta: {id: (name, {stat id: text})}; lines: [(name, t0_ns,
    [(meta id, offset_ps, duration_ps)])]."""
    body = _field(2, name)
    for line_name, t0_ns, events in lines:
        line = _field(2, line_name) + _field(3, t0_ns)
        for meta, offset_ps, duration_ps in events:
            line += _field(4, _field(1, meta) + _field(2, offset_ps)
                           + _field(3, duration_ps))
        body += _field(3, line)
    for key, (event_name, stats) in event_meta.items():
        meta = _field(1, key) + _field(2, event_name)
        for stat_id, text in stats.items():
            meta += _field(5, _field(1, stat_id) + _field(5, text))
        body += _map(4, key, meta)
    for key, stat_name in stat_names.items():
        body += _map(5, key, _field(1, key) + _field(2, stat_name))
    return _field(1, body)


def test_load_events_reads_the_wire_format(tmp_path):
    device = _plane(
        DEV, {7: "tf_op", 8: "hlo_category"},
        {1: ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop",
             {8: "loop fusion",
              7: "jit(chain_optimize_full)/while/body/round.select/top_k:"}),
         2: ("%copy.1 = f32[8]{0} copy(f32[8]{0} %p1)", {}),
         3: ("jit_chain_optimize_full(123)", {})},
        [("XLA Ops", 1_000, [(1, 2_000_000, 500_000),
                             (2, 3_000_000, 250_000)]),
         ("XLA Modules", 1_000, [(3, 2_000_000, 1_250_000)]),
         ("Steps", 1_000, [(3, 0, 1)])])
    host = _plane(
        "/host:CPU", {}, {1: ("cc.http.request", {}), 2: ("PjitFunction", {})},
        [("python3", 500, [(1, 1_000_000, 9_000_000), (2, 0, 5)])])
    other = _plane("#Chip0 Misc", {}, {1: ("cc.ignored", {})},
                   [("x", 0, [(1, 0, 1)])])
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(device + host + other)
    events = ps.load_events(str(tmp_path))
    assert events["host"] == [["cc.http.request", 1500.0, 9000.0]]
    assert events["devices"] == {DEV: {
        "ops": [["fusion.5", "round.select", 3000.0, 500.0],
                ["copy.1", "(unscoped)", 4000.0, 250.0]],
        "modules": [["jit_chain_optimize_full", 3000.0, 1250.0]]}}
    got = ps.summarize(str(tmp_path))
    assert got["deviceSecondsByScope"] == {"round.select": 5e-07,
                                           "(unscoped)": 2.5e-07}
    assert got["idleSecondsBySpan"] == {"http.request":
                                        pytest.approx(8.25e-06)}


def test_load_events_without_a_capture_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ps.load_events(str(tmp_path))
