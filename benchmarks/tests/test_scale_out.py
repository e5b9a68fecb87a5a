"""The scale-out's cell: its configuration's file keeps to what
``test_contract.py`` asks of one, its two metrics read the program's
counters (and give nothing on a program without them, as the parent commit
is), and the program's own answer at 16 / 512 places nothing on an old
broker and fills the new ones, through the command's own ``run_cell``."""

import copy
import json
import os
import time

import pytest

from conftest import BENCH, ROOT

NEW = [14, 15]
METRICS = ("scaleout.fill_pct", "scaleout.placed_per_round")
PATCH = {"placement": "kafka_rack_aware", "operation": "add_broker",
         "operation_brokers": NEW}


@pytest.fixture(scope="module")
def scaled_out(tiny, cpu_device):
    """One traced rehearsal of the scale-out at 16 / 512 (14 + 2), with the
    cell's two metrics listed for it."""
    import run
    benchmark = copy.deepcopy(tiny)
    for name in METRICS:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            benchmark["per_layer"].append(
                {**json.load(f), "workloads": ["tiny.rebalance"]})
    return run.run_cell(benchmark, "tiny.rebalance", 2**31 + 32, 1.5, True,
                        cpu_device, time.monotonic(), cfg_patch=PATCH,
                        faults=True)


def test_the_programs_own_answer_fills_the_new_brokers_alone(scaled_out):
    assert scaled_out["correct"] is True, scaled_out["compared"]
    assert scaled_out["compared"]["onto_old_broker"] == [0, 0]
    assert scaled_out["failed"] == 0
    assert scaled_out["workload"]["proposals"] > 0
    assert scaled_out["faulted"]["onto_old"] == {"onto_old_broker": 1}
    metrics = scaled_out["metrics"]
    # 2 x 512 x 3 / 16 = 192 replicas are the new brokers' even share; the
    # band of ReplicaDistributionGoal is 87-106 a broker, 90.6-110.4 %
    assert metrics["scaleout.fill_pct"]["unit"] == "%"
    assert 100 * 87 / 96 <= metrics["scaleout.fill_pct"]["value"] \
        <= 100 * 106 / 96
    assert metrics["scaleout.placed_per_round"]["value"] > 1


def context(at_setup, at_close, solves=2):
    from benchlib.metrics import Context
    with open(os.path.join(BENCH, "configs",
                           "kafka-250b-25kp-scaleout.json")) as f:
        cfg = json.load(f)
    return Context(cfg=cfg, mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                   at_setup=at_setup, at_close=at_close,
                   solves=[object()] * solves, reads=[], device={})


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_the_counters_gives_nothing_to_read(name):
    """The parent commit has no such counter: the reader returns None and
    does not raise, so the traced line leaves the metric out."""
    from benchlib.metrics import read_metric
    assert read_metric(name, context({("pass_seq", ""): 3.0},
                                     {("pass_seq", ""): 5.0})) is None


@pytest.mark.parametrize("name,expected", [
    ("scaleout.fill_pct", 110.0), ("scaleout.placed_per_round", 3300 / 233)])
def test_the_readers_on_hand_made_counters(name, expected):
    """Two proposals of a window, each 3,300 replicas onto the ten new
    brokers in 233 rounds (the parent's reading, PERF.md PR 31): 110 % of
    10 x 25,000 x 3 / 250 = 3,000, and 14.2 a round."""
    from benchlib.metrics import read_metric
    new = ("solver_scale_out_replicas_total", '{onto="new"}')
    old = ("solver_scale_out_replicas_total", '{onto="old"}')
    rounds = ("solver_scale_out_rounds_total", "")
    at_setup = {new: 3300.0, old: 0.0, rounds: 233.0}
    at_close = {new: 9900.0, old: 0.0, rounds: 699.0}
    assert read_metric(name, context(at_setup, at_close)) \
        == pytest.approx(expected)
    # a window that completed nothing, and one whose passes ran no round
    assert read_metric(name, context(at_setup, at_close, solves=0)) is None
    empty = read_metric(name, context(at_setup, {**at_setup}))
    assert empty == (0.0 if name == "scaleout.fill_pct" else None)


def test_the_configurations_file_keeps_to_the_contract(benchmark_file):
    entry = {c["name"]: c for c in benchmark_file["configs"]}[
        "kafka-250b-25kp-scaleout"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "kafka-250b-25kp.json")) as f:
        sibling = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == list(cfg["source_scale"])
    for key in entry["reduced"]:    # a quarter of the source, as its sibling
        assert cfg[key] * 4 == cfg["source_scale"][key]
    assert cfg["operation"] == "add_broker"
    assert cfg["placement"] == "kafka_rack_aware"
    assert cfg["operation_brokers"] == list(range(240, 250))
    assert "new_brokers_alone_receive_replicas" in cfg["guarantees"]
    for part in ("benchlib/reference.py", "guarantees/add_broker.py",
                 "benchlib/scaleout_reference.py"):
        assert part in cfg["reference"]
        assert os.path.isfile(os.path.join(BENCH, part))
    # the sibling's deployment under another placement and operation
    same = set(sibling) - {"name", "source", "deployment", "operation",
                           "operation_brokers", "guarantees", "reference",
                           "assumed", "controls"}
    assert all(cfg[k] == sibling[k] for k in same)
    assert set(cfg) - set(sibling) == {"placement"}
    assert {k: v for k, v in cfg["guarantees"].items()
            if k != "new_brokers_alone_receive_replicas"} \
        == sibling["guarantees"]
    # the sibling's rack_only makes an empty plan from a healthy start,
    # which is correct: only a control that has to fail HERE is kept
    assert cfg["controls"]["no_hard_goals"] \
        == {**sibling["controls"]["no_hard_goals"],
            "about": cfg["controls"]["no_hard_goals"]["about"]}
    assert set(cfg["controls"]) == set(CONTROLS)
    cell = {w["name"]: w for w in benchmark_file["workloads"]}[
        "kafka-250b-25kp.scale-out"]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (entry["name"], "rebalance", 1)
    assert len(cell["why"]) <= 200
    for name in METRICS:
        m = {m["name"]: m for m in benchmark_file["per_layer"]}[name]
        assert m["workloads"] == [cell["name"]]
        assert (m["source"], m["layer"], m["moves"]) \
            == ("program_counter", "round body", "proposal_s")


# control -> the count that its ``about`` says it breaks, and the only one
CONTROLS = {"no_hard_goals": "rack_violations",
            "rack_and_potential_nw_out": "over_capacity"}


@pytest.mark.parametrize("control,number",
                         [*sorted(CONTROLS.items()), ("rack_only", None)])
def test_the_control_is_not_correct_on_a_scale_out(tiny, cpu_device,
                                                   control, number):
    """The configuration's controls at 16 / 512 (14 + 2), on the healthy
    start the cell has: each comes out as not correct by the count its
    ``about`` names and by no other (the rule does not depend on the
    goals: nothing lands on an old broker). The sibling's ``rack_only`` is
    not among them because it guards nothing here: its plan is empty and
    ``correct``."""
    import run
    name = "kafka-250b-25kp" + ("-scaleout" if number else "")
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        entry = json.load(f)["controls"][control]
    result = run.run_cell(tiny, "tiny.rebalance", 2**31 + 33, 1.0, False,
                          cpu_device, time.monotonic(),
                          cfg_patch={**PATCH, **entry["patch"]})
    breached = {k for k, v in result["compared"].items() if v[0]}
    if number is None:
        assert result["correct"] is True and not breached
        assert result["workload"]["proposals"] > 0
    else:
        assert number in entry["about"]
        assert result["correct"] is False and breached == {number}
