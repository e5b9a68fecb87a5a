"""The five per-layer metrics that read the program's count of its own
collector (PR 36), rehearsed at 16 brokers / 512 partitions on the CPU
with a benchmark file of their own (``BENCHMARK.collector.json``): the
command prints each, the program's count of the full collections agrees
with the harness's outside reading of the same run, and a span priced
without its pauses is no longer than the span. Nothing here is a device
number."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH

COLLECTOR_FILE = os.path.join(BENCH, "tests", "BENCHMARK.collector.json")
NEW = ["host.gc_ms", "host.gc_full_pause_ms", "host.heap_growth_blocks",
       "host.render_own_ms", "monitor.sampling_round_own_ms"]


@pytest.fixture(scope="module")
def run_and_context(cpu_device):
    """One traced rehearsal in this process, with the context its readers
    were given."""
    import time

    import run
    with open(COLLECTOR_FILE) as f:
        benchmark = json.load(f)
    seen = {}

    class Spy(run.Context):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            seen.setdefault("ctx", self)

    run.Context, original = Spy, run.Context
    try:
        result = run.run_cell(benchmark, "tiny.rebalance", 2**31 + 36, 4.0,
                              True, cpu_device, time.monotonic())
    finally:
        run.Context = original
    return result, seen["ctx"]


def test_the_command_prints_the_five_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         COLLECTOR_FILE, "--workload", "tiny.rebalance", "--seed",
         str(2**31 + 36), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    for name in NEW:
        assert name in metrics, name
    assert metrics["host.gc_ms"]["value"] > 0
    assert metrics["host.heap_growth_blocks"]["unit"] == "blocks/proposal"
    assert 0 < metrics["host.render_own_ms"]["value"] \
        <= metrics["host.render_ms"]["value"]
    assert 0 < metrics["monitor.sampling_round_own_ms"]["value"] \
        <= metrics["monitor.sampling_round_ms"]["value"]


def test_the_programs_count_agrees_with_the_harness_outside_reading(
        run_and_context):
    """``host.gc_ms`` is the harness's generation-2 reading of the same
    window (``workload.full_collections_s``) plus the young generations'
    share, which only the program's counters read."""
    from benchlib.collector import FULL, pause_seconds, pauses
    result, ctx = run_and_context
    proposals = result["workload"]["proposals"]
    outside = result["workload"]["full_collections_s"]
    assert pauses(ctx, generation=FULL) == len(outside)
    full_ms = 1000.0 * pause_seconds(ctx, generation=FULL) / proposals
    assert full_ms == pytest.approx(1000.0 * sum(outside) / proposals,
                                    rel=0.10, abs=0.05)
    young_ms = 1000.0 * (pause_seconds(ctx, generation="0")
                         + pause_seconds(ctx, generation="1")) / proposals
    assert young_ms > 0
    gc_ms = result["metrics"]["host.gc_ms"]["value"]
    assert gc_ms == pytest.approx(full_ms + young_ms, rel=1e-9)
    assert gc_ms == pytest.approx(
        1000.0 * sum(outside) / proposals + young_ms, rel=0.10, abs=0.05)
    if outside:
        assert result["metrics"]["host.gc_full_pause_ms"]["value"] == \
            pytest.approx(1000.0 * sum(outside) / len(outside),
                          rel=0.10, abs=0.05)
    else:
        assert result["metrics"]["host.gc_full_pause_ms"]["value"] == 0.0


def test_a_span_without_its_pauses_is_no_longer_than_the_span(
        run_and_context):
    result, _ctx = run_and_context
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < value["host.render_own_ms"] <= value["host.render_ms"]
    assert 0 < value["monitor.sampling_round_own_ms"] \
        <= value["monitor.sampling_round_ms"]


def test_a_program_without_the_hook_reports_nothing():
    """What the parent commit gives: spans and no ``python_gc_*`` series,
    so no metric, and no reader raises."""
    from benchlib.metrics import Context, read_metric
    spans = {("trace_span_seconds_count", '{span="monitor.sample_fetch"}'): 3,
             ("trace_span_seconds_sum", '{span="monitor.sample_fetch"}'): 0.3,
             ("journey_segment_seconds_count",
              '{endpoint="PROPOSALS",segment="render"}'): 3,
             ("journey_segment_seconds_sum",
              '{endpoint="PROPOSALS",segment="render"}'): 0.06}
    ctx = Context(cfg={}, mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                  at_setup={}, at_close=spans, solves=[object()] * 3,
                  reads=[], device={})
    for name in NEW:
        assert read_metric(name, ctx) is None, name
    assert read_metric("monitor.sampling_round_ms", ctx) == \
        pytest.approx(100.0)


def test_a_program_with_the_hook_and_no_pause_reports_numbers():
    from benchlib.metrics import Context, read_metric
    at_setup = {("python_gc_collections_total", '{generation="2"}'): 4,
                ("python_gc_pause_seconds_sum", '{generation="2"}'): 0.4,
                ("python_gc_pause_seconds_count", '{generation="2"}'): 4,
                ("python_allocated_blocks", ""): 1000.0}
    at_close = {**at_setup,
                ("python_allocated_blocks", ""): 1600.0,
                ("trace_span_seconds_count",
                 '{span="monitor.sample_fetch"}'): 3,
                ("trace_span_seconds_sum",
                 '{span="monitor.sample_fetch"}'): 0.3,
                ("trace_span_gc_seconds_total",
                 '{span="monitor.sample_fetch"}'): 0.06,
                ("journey_segment_seconds_count",
                 '{endpoint="PROPOSALS",segment="render"}'): 3,
                ("journey_segment_seconds_sum",
                 '{endpoint="PROPOSALS",segment="render"}'): 0.06}
    ctx = Context(cfg={}, mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                  at_setup=at_setup, at_close=at_close,
                  solves=[object()] * 3, reads=[], device={})
    assert read_metric("host.gc_ms", ctx) == 0.0
    assert read_metric("host.gc_full_pause_ms", ctx) == 0.0
    assert read_metric("host.heap_growth_blocks", ctx) == 200.0
    assert read_metric("host.render_own_ms", ctx) == pytest.approx(20.0)
    assert read_metric("monitor.sampling_round_own_ms", ctx) == \
        pytest.approx(80.0)
