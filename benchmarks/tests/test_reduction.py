"""The trace reduction, the bytes-per-proposal function and the traffic
generator give the expected numbers."""

import json
import os

import numpy as np
import pytest

from benchlib import bytes_model, trace
from benchlib.deployment import build
from benchlib.traffic import read_schedule
from conftest import BENCH

MS = 1e6    # nanoseconds


def events(ops, modules, host):
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_reduce_by_hand():
    # window 0..100 ms: a 10 ms sampling round, then a request 10..100 ms
    # whose program runs 30..80 ms: a while (30..80) around two fusions.
    ev = events(
        ops=[["while.1", 30 * MS, 50 * MS], ["fusion.1", 30 * MS, 20 * MS],
             ["fusion.2", 55 * MS, 20 * MS]],
        modules=[["jit_chain_optimize_full(123)", 30 * MS, 50 * MS],
                 ["jit_cluster_stats(9)", 85 * MS, 5 * MS]],
        host=[["bench.sampling_round", 0, 10 * MS],
              ["bench.request", 10 * MS, 90 * MS]])
    ev["devices"]["/device:TPU:0"]["ops"].append(["copy.1", 85 * MS, 5 * MS])
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["requests"] == 1
    assert r["modules"] == {"jit_chain_optimize_full": pytest.approx(0.050),
                            "jit_cluster_stats": pytest.approx(0.005)}
    assert dict(map(tuple, r["device_ops"])) == {
        "fusion.1": pytest.approx(0.020), "fusion.2": pytest.approx(0.020),
        "while.1": pytest.approx(0.010), "copy.1": pytest.approx(0.005)}
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps == {
        "sampling round": pytest.approx(0.010),
        "request: before first device op (refresh, dispatch)":
            pytest.approx(0.020),
        "request: between device ops": pytest.approx(0.005),
        "request: after last device op (diff, render, HTTP)":
            pytest.approx(0.010)}


def test_reduce_finds_nothing_without_device_ops():
    assert trace.reduce(events([], [], [["bench.request", 0, MS]])) is None
    assert trace.reduce({"devices": {}, "host": []}) is None


def test_recorded_excerpt():
    """Part of a trace recorded on the v5e (PR 24, chip call 1): one
    sampling round and one request of kafka-100b-10kp.rebalance."""
    with open(os.path.join(BENCH, "tests", "trace_excerpt.json")) as f:
        r = trace.reduce(json.load(f))
    assert r["window_s"] == pytest.approx(1.376507025)
    assert r["busy_s"] == pytest.approx(1.216419084)
    assert r["modules"]["jit_chain_optimize_full"] == \
        pytest.approx(1.21539134)
    assert r["idle_gaps"][0][0].startswith("request: after last device op")


@pytest.mark.parametrize("program_s,reported", [
    (2.814, True),      # 0.995 of busy: PR 28's capture at 100 brokers
    (1.4, False),       # half of busy: the odd line of PR 29
])
def test_program_seconds_far_short_of_busy_are_not_reported(
        program_s, reported, capsys):
    """``round.ms_per_round`` and ``solver_roofline`` divide by the
    seconds of the solver's programs: where the trace lost module events
    those fall far short of the seconds in which the device ran an
    operation, and both readers give nothing and say why."""
    from benchlib.metrics import Context, read_metric
    with open(os.path.join(BENCH, "configs", "kafka-100b-10kp.json")) as f:
        cfg = json.load(f)

    class Solve:
        body = {"summary": {"goals": {"a": {"rounds": 200},
                                      "b": {"rounds": 6}}}}

    ctx = Context(
        cfg=cfg, mix={}, seconds=45.0, setup_s=50.0, t0=0.0, at_setup={},
        at_close={}, solves=[Solve()] * 7, reads=[],
        device={"kind": "TPU v5 lite"}, traced_solves=[Solve()] * 7,
        trace={"modules": {"jit_chain_optimize_full": program_s,
                           "jit_cluster_stats": 0.01},
               "busy_s": 2.828, "window_s": 4.997, "requests": 7})
    ms, share = (read_metric(name, ctx) for name in
                 ("round.ms_per_round", "solver_roofline"))
    said = capsys.readouterr().out
    if reported:
        assert ms == pytest.approx(1000 * 2.814 / (7 * 206))
        assert share == pytest.approx(
            100 * (15 * 162_100 * 4 / 819e9) / (2.814 / 7))
        assert said == ""
    else:
        assert ms is None and share is None
        assert said.count("not reported") == 2
        assert "1.400000 s of the device's 2.828000 busy seconds" in said


def test_op_name():
    assert trace.op_name("%fusion.7 = f32[8]{0} fusion(f32[8] %p), "
                         "kind=kLoop") == "fusion.7"
    assert trace.op_name("while.3") == "while.3"


def test_proposal_bytes(benchmark_file):
    cfg = {"partitions": 10, "brokers": 4, "replication_factor": 3,
           "topics": 2, "goals": ["a", "b"]}
    # read 30 + 10 + 80 + 32 + 12 + 8 = 172 words, write 30 + 10 = 40
    assert bytes_model.proposal_bytes(cfg) == 2 * (172 + 40) * 4
    with open(os.path.join(BENCH, "configs", "kafka-100b-10kp.json")) as f:
        assert bytes_model.proposal_bytes(json.load(f)) == 15 * (122_100 + 40_000) * 4
    assert bytes_model.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bytes_model.peak("TPU v9")


@pytest.mark.parametrize("arrivals", ["poisson", "uniform", "burst"])
def test_read_schedule_same_set_every_seed(arrivals):
    reads = {"rate_per_s": 20, "arrivals": arrivals, "burst_size": 5,
             "mix": [{"endpoint": "state", "weight": 0.5},
                     {"endpoint": "load", "weight": 0.3},
                     {"endpoint": "kafka_cluster_state", "weight": 0.2}]}
    a, b = read_schedule(reads, 1, 10.0), read_schedule(reads, 2**31 + 9, 10.0)
    assert len(a) == len(b) == 200
    assert a[0].due == 0.0 and 9.0 < a[-1].due <= 10.0
    gaps = lambda s: sorted(np.round(np.diff([r.due for r in s]), 9))  # noqa
    assert gaps(a) == gaps(b)
    count = lambda s, e: sum(r.endpoint == e for r in s)  # noqa: E731
    assert [count(a, e) for e in ("state", "load", "kafka_cluster_state")] \
        == [count(b, e) for e in ("state", "load", "kafka_cluster_state")] \
        == [100, 60, 40]
    assert [r.due for r in a] == [r.due for r in read_schedule(reads, 1, 10.0)]
    if arrivals == "poisson":
        assert [r.due for r in a] != [r.due for r in b]
    assert sum(r.keep for r in a) == 24


def test_deployment_is_the_configurations(tiny):
    with open(os.path.join(BENCH, "tests", "tiny-16b-512p.json")) as f:
        cfg = json.load(f)
    a, b = build(cfg), build(cfg)
    assert np.array_equal(a.assignment, b.assignment)
    assert a.assignment.shape == (512, 3)
    assert (np.sort(a.assignment, axis=1)[:, 1:]
            != np.sort(a.assignment, axis=1)[:, :-1]).all()
    assert set(np.unique(a.assignment)) == set(range(16))
    # the cluster average of every resource sits at half of capacity
    from benchlib.reference import broker_loads
    loads = broker_loads(a, a.assignment, np.zeros(512, dtype=np.int64))
    mean = loads.mean(axis=0) / a.capacity
    assert mean[1:] == pytest.approx([0.5, 0.5, 0.5])
    assert mean[0] < 0.5        # CPU: the generous all-replicas bound
    assert a.index_of(*a.topic_partition(77)) == 77
    assert a.index_of("topic0", 512) == a.index_of("nope", 0) == -1
    new = build({**cfg, "operation": "add_broker",
                 "operation_brokers": [14, 15]})
    assert not np.isin(new.assignment, [14, 15]).any()
