"""The seven per-layer metrics that read the program's own spans, rehearsed
at 16 brokers / 512 partitions on the CPU with a benchmark file of their
own (``BENCHMARK.spans.json``): the command prints each over 0, and the
pieces add up to the request's root span by construction. Nothing here is
a device number."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import BENCH

SPANS_FILE = os.path.join(BENCH, "tests", "BENCHMARK.spans.json")
REQUEST_METRICS = ["http.serialize_write_ms", "http.unattributed_ms",
                   "host.refresh_ms", "host.diff_ms", "host.render_ms",
                   "monitor.sampling_round_ms"]


def test_the_command_prints_the_seven_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         SPANS_FILE, "--workload", "tiny.rebalance", "--seed",
         str(2**31 + 25), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    for name in REQUEST_METRICS + ["xla.trace_lower_s"]:
        assert result["metrics"][name]["value"] > 0, name
    assert result["metrics"]["xla.trace_lower_s"]["unit"] == "s"


@pytest.fixture(scope="module")
def run_and_context(cpu_device):
    """One traced rehearsal in this process, with the context its readers
    were given."""
    import run
    with open(SPANS_FILE) as f:
        benchmark = json.load(f)
    seen = {}

    class Spy(run.Context):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            seen.setdefault("ctx", self)

    run.Context, original = Spy, run.Context
    try:
        result = run.run_cell(benchmark, "tiny.rebalance", 25, 2.0, True,
                              cpu_device, time.monotonic())
    finally:
        run.Context = original
    return result, seen["ctx"]


def mean_span_ms(ctx, span, **labels):
    from benchlib.spans import ms_per_solve, span_seconds
    return ms_per_solve(ctx, span_seconds(ctx, [span], **labels))


def test_the_pieces_add_up_to_the_root_span(run_and_context):
    result, ctx = run_and_context
    value = {k: v["value"] for k, v in result["metrics"].items()}
    pieces = value["host.refresh_ms"] \
        + mean_span_ms(ctx, "analyzer.optimize") \
        + value["host.render_ms"] + value["http.serialize_write_ms"] \
        + value["http.unattributed_ms"]
    root = mean_span_ms(ctx, "http.request", endpoint="PROPOSALS")
    assert root > 0
    assert pieces == pytest.approx(root, rel=1e-9)


def test_diff_and_dispatch_stay_inside_the_optimizer(run_and_context):
    result, ctx = run_and_context
    inside = result["metrics"]["host.diff_ms"]["value"] \
        + mean_span_ms(ctx, "solver.dispatch")
    assert 0 < inside < mean_span_ms(ctx, "analyzer.optimize")


def test_the_root_span_is_the_request_the_client_timed(run_and_context):
    result, ctx = run_and_context
    requests = result["workload"]["request_s"]
    # the record lists every request of the window, the readers' context
    # the completed ones: the same here, since none failed
    assert len(requests) == len(ctx.solves)
    client_ms = 1000.0 * sum(requests) / len(requests)
    root = mean_span_ms(ctx, "http.request", endpoint="PROPOSALS")
    # Within 5 %: the rest is the client's connect, read and parse. The
    # client is a thread of the same process, so the server's thread may
    # wait one switch interval of the interpreter for the lock before it
    # closes its span, after the client has its last byte: at this size
    # (a request of 50 ms) that is allowed for in absolute terms.
    handoff_ms = 1000.0 * sys.getswitchinterval()
    assert abs(root - client_ms) <= 0.05 * client_ms + handoff_ms


def test_a_program_without_the_spans_reports_nothing():
    """What the parent commit gives: no such series, so no metric."""
    from benchlib.metrics import Context, read_metric
    ctx = Context(cfg={}, mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                  at_setup={}, at_close={}, solves=[object()], reads=[],
                  device={})
    for name in REQUEST_METRICS + ["xla.trace_lower_s"]:
        assert read_metric(name, ctx) is None, name
