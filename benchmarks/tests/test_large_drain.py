"""The decommission at its published size, ``kafka-1000b-100kp.drain``: its
metric ``bounded.healing_round_share_pct`` reads the bounded route's
``grid``-labelled healing rounds (and gives nothing on a program that
counts none there), and at 24 / 960 by the same recipe, with both
512-broker switches lowered through the patch so that the small cluster
takes the bounded route with the wide grids as the full one does, the
program's own answer is ``correct`` with the metric above 0, while both
controls come out as not correct, through the command's own
``run_cell``."""

import json
import os
import time

import pytest

from conftest import BENCH

NAME = "kafka-1000b-100kp-drain"
CELL = "kafka-1000b-100kp.drain"
METRIC = "bounded.healing_round_share_pct"
SWITCHES = {"solver.fused.chain.max.brokers": 16,
            "solver.wide.batch.min.brokers": 16}
CONTROLS = {"no_hard_goals": "rack_violations", "rack_only": "over_capacity"}


def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def patch(**more):
    """24 / 960 with the file's brokers that lie below 24 drained (2, 23)."""
    cfg = config()
    return {"brokers": 24, "partitions": 960, "topics": 2,
            "operation_brokers": [b for b in cfg["operation_brokers"]
                                  if b < 24],
            "overrides": {**cfg["overrides"], **SWITCHES}, **more}


@pytest.fixture(scope="module")
def drained(benchmark_file, cpu_device):
    """One traced rehearsal of the cell at 24 / 960 on the bounded route."""
    import run
    return run.run_cell(benchmark_file, CELL, 2**31 + 41, 2.0, True,
                        cpu_device, time.monotonic(), cfg_patch=patch())


def test_the_programs_own_drain_is_correct_on_the_bounded_route(drained):
    assert drained["correct"] is True, drained["compared"]
    assert drained["compared"]["on_removed_broker"] == [0, 0]
    assert drained["failed"] == 0 and drained["workload"]["proposals"] > 0
    metrics = {k: v["value"] for k, v in drained["metrics"].items()}
    # the rehearsal prints no device metric; the counters read
    assert set(metrics) == {METRIC, "host.outside_solver_ms", "xla.compile_s"}
    assert 0 < metrics[METRIC] < 100


@pytest.mark.parametrize("control,number", sorted(CONTROLS.items()))
def test_the_control_is_not_correct_in_the_drain(benchmark_file, cpu_device,
                                                 control, number):
    """The configuration's controls at 24 / 960: each comes out as not
    correct by the count its ``about`` names."""
    import run
    entry = config()["controls"][control]
    result = run.run_cell(benchmark_file, CELL, 2**31 + 42, 1.0, False,
                          cpu_device, time.monotonic(),
                          cfg_patch={**patch(), **entry["patch"]})
    assert result["correct"] is False and result["compared"][number][0] > 0


def series(rounds, healing, label=True):
    """Counters as the program exposes them after ``rounds`` bounded rounds
    (a quarter wide), ``healing`` of them healing; the fused chain's series
    beside them. ``label=False``: the healing counter as a program exposes
    it that counts the fused route's alone, with no grid label."""
    wide = rounds / 4
    out = {
        ("solver_dispatch_rounds_sum", '{grid="narrow",kind="move"}'):
            (rounds - wide) * 0.75,
        ("solver_dispatch_rounds_sum", '{grid="narrow",kind="swap"}'):
            (rounds - wide) * 0.25,
        ("solver_dispatch_rounds_sum", '{grid="wide",kind="move"}'): wide,
        ("solver_dispatch_rounds_sum", '{grid="fused",kind="chain"}'): 999.0,
        ("solver_healing_rounds_total", '{grid="fused"}'): 99.0,
    }
    if label:
        out[("solver_healing_rounds_total", '{grid="narrow"}')] = healing / 2
        out[("solver_healing_rounds_total", '{grid="wide"}')] = healing / 2
    else:
        out[("solver_healing_rounds_total", "")] = healing
    return out


def context(at_setup, at_close, solves=2):
    from benchlib.metrics import Context
    return Context(cfg=config(), mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                   at_setup=at_setup, at_close=at_close,
                   solves=[object()] * solves, reads=[], device={})


def test_the_reader_on_hand_made_counters():
    """Two drains of 600 bounded rounds each, 90 of them healing: 15 %;
    the fused chain's rounds and heals are left out."""
    from benchlib.metrics import read_metric
    at_setup, at_close = series(400.0, 60.0), series(1600.0, 240.0)
    assert read_metric(METRIC, context(at_setup, at_close)) \
        == pytest.approx(15.0)
    # a window that completed nothing, or ran no bounded round
    assert read_metric(METRIC, context(at_setup, at_close, solves=0)) is None
    assert read_metric(METRIC, context(at_setup, {**at_setup})) is None


def test_a_program_whose_bounded_route_counts_no_heals_gives_nothing():
    """The parent of the change that counts them: the healing counter is
    the fused route's, unlabelled, or missing; the reader returns None and
    does not raise, so the line leaves the metric out."""
    from benchlib.metrics import read_metric
    assert read_metric(METRIC, context(series(400.0, 60.0, label=False),
                                       series(1600.0, 240.0, label=False))) \
        is None
    bare = {k: v for k, v in series(1600.0, 0.0, label=False).items()
            if k[0] != "solver_healing_rounds_total"}
    assert read_metric(METRIC, context(bare, bare)) is None


def test_the_configurations_file_and_entries(benchmark_file):
    entry = {c["name"]: c for c in benchmark_file["configs"]}[NAME]
    cfg = config()
    with open(os.path.join(BENCH, "configs", "kafka-1000b-100kp.json")) as f:
        sibling = json.load(f)
    assert entry["reduced"] == [] and entry["file"].endswith(NAME + ".json")
    # the rebalance cell's cluster, key for key, and no route switch
    for key in ("brokers", "partitions", "topics", "replication_factor",
                "racks", "placement_skew", "load_skew", "instance_seed",
                "target_utilization", "goals", "hard_goals", "overrides",
                "controls", "request_parameters", "chips"):
        assert cfg[key] == sibling[key], key
    assert not set(SWITCHES) & set(cfg["overrides"])
    assert cfg["operation"] == "remove_broker"
    assert "benchlib/drain_reference.py" in cfg["reference"]
    cell = {w["name"]: w for w in benchmark_file["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "rebalance", 1)
    metric = {m["name"]: m for m in benchmark_file["per_layer"]}[METRIC]
    with open(os.path.join(BENCH, "metrics", METRIC + ".json")) as f:
        assert json.load(f) == metric
    assert metric["workloads"] == [CELL] and metric["moves"] == "proposal_s"
