"""The 1,000-broker cell: its configuration's file is the 250-broker
sibling's recipe at the source's own scale with no route switch, its four
``bounded.*`` metrics read the program's ``grid``-labelled dispatch series
(and give nothing on a program without them), and at 24 / 960 by the same
recipe, with both 512-broker switches lowered through the patch so the
small cluster takes the bounded route with the wide grids as the full one
does, the program's own answer is ``correct`` while both controls come
out as not correct, through the command's own ``run_cell``."""

import json
import os
import time

import pytest

from conftest import BENCH

NAME = "kafka-1000b-100kp"
CELL = NAME + ".rebalance"
METRICS = ("bounded.rounds_per_proposal", "bounded.wide_round_share_pct",
           "bounded.rounds_per_dispatch", "bounded.ms_per_round")
# 24 / 960, topics = brokers / 10; the switches under the size, as 512 is
# under 1,024
SWITCHES = {"solver.fused.chain.max.brokers": 16,
            "solver.wide.batch.min.brokers": 16}
# control -> the count that its ``about`` says it breaks, and the only one
CONTROLS = {"no_hard_goals": "rack_violations", "rack_only": "over_capacity"}


def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def patch(**more):
    return {"brokers": 24, "partitions": 960, "topics": 2,
            "overrides": {**config()["overrides"], **SWITCHES}, **more}


@pytest.fixture(scope="module")
def large(benchmark_file, cpu_device):
    """One traced rehearsal of the cell at 24 / 960 on the bounded route."""
    import run
    return run.run_cell(benchmark_file, CELL, 2**31 + 38, 2.0, True,
                        cpu_device, time.monotonic(), cfg_patch=patch())


def test_the_programs_own_answer_is_correct_on_the_bounded_route(large):
    assert large["correct"] is True, large["compared"]
    assert large["failed"] == 0
    assert large["workload"]["proposals"] > 0
    metrics = {k: v["value"] for k, v in large["metrics"].items()}
    # the rehearsal prints no device metric; the three counters read
    assert set(metrics) == {"bounded.rounds_per_proposal",
                            "bounded.wide_round_share_pct",
                            "bounded.rounds_per_dispatch",
                            "host.outside_solver_ms", "xla.compile_s"}
    assert 0 < metrics["bounded.wide_round_share_pct"] < 100
    # a pass is split: fewer rounds a dispatch than a proposal searches
    assert 1 <= metrics["bounded.rounds_per_dispatch"] \
        < metrics["bounded.rounds_per_proposal"]


def test_the_counted_rounds_are_the_bodies_rounds(benchmark_file,
                                                  cpu_device):
    """``bounded.rounds_per_proposal`` from the program's counters equals
    the rounds the served bodies report (speculative dispatches add none),
    and every body of a window is the same plan."""
    import run
    from benchlib.metrics import rounds
    seen = []
    real = run.compare

    def compare(dep, cfg, mix, window, good, *a, **k):
        seen.extend(good)
        return real(dep, cfg, mix, window, good, *a, **k)

    run.compare = compare
    try:
        result = run.run_cell(benchmark_file, CELL, 2**31 + 39, 1.5, False,
                              cpu_device, time.monotonic(),
                              cfg_patch=patch())
    finally:
        run.compare = real
    assert result["correct"] is True and len(seen) >= 2
    counted = {k: v["value"] for k, v in result["metrics"].items()}
    assert "bounded.rounds_per_proposal" not in counted  # per-layer: traced
    from benchlib.bounded import ROUNDS, moved
    per_solve = [moved(s.before, s.after, ROUNDS) for s in seen]
    assert per_solve == [rounds(s) for s in seen]
    plans = {json.dumps(s.body["proposals"], sort_keys=True) for s in seen}
    assert len(plans) == 1


@pytest.mark.parametrize("control,number", sorted(CONTROLS.items()))
def test_the_control_is_not_correct_on_the_bounded_route(
        benchmark_file, cpu_device, control, number):
    """The configuration's controls at 24 / 960: each comes out as not
    correct by the count its ``about`` names and by no other."""
    import run
    entry = config()["controls"][control]
    result = run.run_cell(benchmark_file, CELL, 2**31 + 40, 1.0, False,
                          cpu_device, time.monotonic(),
                          cfg_patch={**patch(), **entry["patch"]})
    breached = {k for k, v in result["compared"].items() if v[0]}
    assert result["correct"] is False and breached == {number}


def series(rounds, dispatches, wide_share=0.25):
    """Counters as the program exposes them, after ``rounds`` bounded
    rounds in ``dispatches`` dispatches, a share of the rounds wide."""
    wide = rounds * wide_share
    return {
        ("solver_dispatch_rounds_sum", '{grid="narrow",kind="move"}'):
            (rounds - wide) * 0.75,
        ("solver_dispatch_rounds_sum", '{grid="narrow",kind="swap"}'):
            (rounds - wide) * 0.25,
        ("solver_dispatch_rounds_sum", '{grid="wide",kind="move"}'): wide,
        ("solver_dispatch_rounds_sum", '{grid="fused",kind="chain"}'): 999.0,
        ("solver_dispatches_total", '{grid="narrow",kind="move"}'):
            dispatches * 0.5,
        ("solver_dispatches_total", '{grid="wide",kind="move"}'):
            dispatches * 0.5,
        ("solver_dispatches_total", '{grid="fused",kind="chain"}'): 7.0,
        ("pass_seq", ""): 1.0,
    }


class Solve:
    def __init__(self, before, after):
        self.before, self.after = before, after


def context(at_setup, at_close, solves=2, trace=None, traced=()):
    from benchlib.metrics import Context
    return Context(cfg=config(), mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                   at_setup=at_setup, at_close=at_close,
                   solves=[object()] * solves, reads=[], device={},
                   trace=trace, traced_solves=list(traced))


@pytest.mark.parametrize("name,expected", [
    ("bounded.rounds_per_proposal", 600.0),
    ("bounded.wide_round_share_pct", 25.0),
    ("bounded.rounds_per_dispatch", 1200.0 / 240.0),
    ("bounded.ms_per_round", 1000.0 * 4.5 / 1200.0)])
def test_the_readers_on_hand_made_counters(name, expected):
    """Two proposals of 600 bounded rounds each, a quarter of them on the
    wide grid, in 240 dispatches; the fused route's series are left out.
    The traced window holds the two, and the megastep programs took 4.5 of
    the device's 4.8 busy seconds."""
    from benchlib.metrics import read_metric
    at_setup, at_close = series(400.0, 80.0), series(1600.0, 320.0)
    trace = {"modules": {"jit_chain_optimize_rounds_donated": 3.5,
                         "jit_chain_swap_rounds_donated": 1.0,
                         "jit_chain_goal_stats": 0.3},
             "busy_s": 4.8}
    traced = [Solve(at_setup, series(1000.0, 200.0)),
              Solve(series(1000.0, 200.0), at_close)]
    assert read_metric(name, context(at_setup, at_close, trace=trace,
                                     traced=traced)) \
        == pytest.approx(expected)
    # a window that completed nothing
    assert read_metric(name, context(at_setup, at_close, solves=0,
                                     trace=trace)) is None
    # a window that ran no bounded round: nothing to read
    assert read_metric(name, context(at_setup, {**at_setup}, trace=trace,
                                     traced=[Solve(at_setup, at_setup)])) \
        is None


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_grid_label_gives_nothing_to_read(name):
    """A program whose dispatch series carry no ``grid`` label (the parent
    of the change that added it): the reader returns None and does not
    raise, so the line leaves the metric out."""
    from benchlib.metrics import read_metric
    before = {("solver_dispatch_rounds_sum", '{kind="move"}'): 10.0,
              ("solver_dispatches_total", '{kind="move"}'): 2.0}
    after = {("solver_dispatch_rounds_sum", '{kind="move"}'): 90.0,
             ("solver_dispatches_total", '{kind="move"}'): 12.0}
    trace = {"modules": {"jit_chain_optimize_rounds_donated": 1.0},
             "busy_s": 1.0}
    assert read_metric(name, context(before, after, trace=trace,
                                     traced=[Solve(before, after)])) is None


def test_the_configurations_file_keeps_to_the_contract(benchmark_file):
    entry = {c["name"]: c for c in benchmark_file["configs"]}[NAME]
    cfg = config()
    with open(os.path.join(BENCH, "configs", "kafka-250b-25kp.json")) as f:
        sibling = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert entry["reduced"] == [] and len(entry["why"]) <= 200
    assert (cfg["brokers"], cfg["partitions"], cfg["topics"]) \
        == tuple(cfg["source_scale"][k]
                 for k in ("brokers", "partitions", "topics")) \
        == (1000, 100000, 100)
    # the sibling's recipe at the source's own scale, and no route switch:
    # the size alone takes the bounded route and the wide grids
    same = set(sibling) - {"name", "deployment", "brokers", "partitions",
                           "topics"}
    assert set(cfg) == set(sibling)
    assert all(cfg[k] == sibling[k] for k in same)
    assert not set(SWITCHES) & set(cfg["overrides"])
    assert "benchlib/reference.py" in cfg["reference"]
    assert set(cfg["controls"]) == set(CONTROLS)
    cell = {w["name"]: w for w in benchmark_file["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "rebalance", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"]: m for m in benchmark_file["per_layer"]}
    for name in METRICS:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "proposal_s"
    # appended: the accepted entries stand first, in their order
    assert [c["name"] for c in benchmark_file["configs"]][-1] == NAME
    assert [w["name"] for w in benchmark_file["workloads"]][-1] == CELL
    assert [m["name"] for m in benchmark_file["per_layer"]][-4:] \
        == list(METRICS)
