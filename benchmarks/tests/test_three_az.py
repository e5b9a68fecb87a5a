"""The three-AZ cell: its placement rule draws what its docstring says, the
plain per-zone greedy balances what it can, the configuration's file keeps
to what ``test_contract.py`` asks of one, its two metrics read the
program's counters (and give nothing on a program without them, as the
parent commit is), and at 18 / 576 on 3 racks the program's own answer is
``correct`` while both controls come out as not correct, through the
command's own ``run_cell``."""

import copy
import json
import os
import time

import numpy as np
import pytest

from conftest import BENCH

NAME = "kafka-252b-25kp-3az"
METRICS = ("threeaz.violated_goals", "threeaz.prior_veto_pct")
PATCH = {"brokers": 18, "partitions": 576, "racks": 3,
         "placement": "skewed_rack_aware"}
# control -> the count that its ``about`` says it breaks, and the only one
CONTROLS = {"no_hard_goals": "rack_violations", "rack_only": "over_capacity"}


def config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def small(**patch):
    from benchlib.deployment import build
    return build({**config(), **PATCH, "topics": 2, **patch})


@pytest.mark.parametrize("racks", [3, 9])
def test_the_rule_draws_a_skewed_rack_aware_cluster(racks):
    from benchlib import reference
    dep = small(brokers=99, partitions=9900, topics=9, racks=racks)
    need = min(dep.rf, racks)
    rows = np.sort(dep.broker_rack[dep.assignment], axis=1)
    assert ((rows[:, 1:] != rows[:, :-1]).sum(axis=1) + 1 == need).all()
    assert reference.rack_violations(dep, dep.assignment) == 0
    srt = np.sort(dep.assignment, axis=1)
    assert (srt[:, 1:] != srt[:, :-1]).all()
    counts = np.bincount(dep.assignment.ravel(), minlength=dep.brokers)
    # the skew: exp(-2 i / (n - 1)), the first third of the brokers holds
    # well over the last third's replicas, and every broker hosts some
    third = dep.brokers // 3
    assert counts[:third].sum() > 2.5 * counts[-third:].sum()
    assert counts.min() > 0
    if racks == 3:      # one replica a zone: every zone holds a third
        assert (np.bincount(dep.broker_rack[dep.assignment].ravel())
                == dep.partitions).all()
    start = reference.evaluate(dep, config()["guarantees"], [])
    assert start["numbers"]["rack_violations"] == 0
    assert start["numbers"]["over_capacity"] > 0    # a cluster to repair
    assert start["info"]["capacity_worst"] > 1.3


def test_the_ring_is_kept_only_where_it_is_rack_distinct():
    """18 hosts on 4 racks: the ring's rows (i, i+1, i+2) are rack-distinct
    but for the two that wrap past the end (racks 0, 1, 0 and 1, 0, 1),
    which keep their rack-aware draw."""
    from benchlib.deployment import load_module
    rule = load_module("placements", "skewed_rack_aware")
    hosts = np.arange(18)
    racks = hosts % 4
    cfg = {"partitions": 40, "replication_factor": 3, "placement_skew": 2.0}
    rows = rule.place(cfg, hosts, racks, np.random.default_rng(3))
    ring = (np.arange(18)[:, None] + np.arange(3)) % 18
    kept = (rows[:18] == ring).all(axis=1)
    assert kept[:16].all() and not kept[16:].any()
    srt = np.sort(racks[rows], axis=1)
    assert (srt[:, 1:] != srt[:, :-1]).all()


def test_the_greedy_balances_inside_the_zones():
    from benchlib import reference, threeaz_reference as ref
    cfg = config()
    dep = small()
    start = ref.out_of_band(dep, dep.assignment)
    after = ref.rebalance(dep, cfg["guarantees"], cfg["goals"])
    left = ref.out_of_band(dep, after)
    # 80 broker-goal pairs out of band at the start (15 to 17 of 18 brokers
    # a goal); the greedy leaves 7 on NW_OUT and 5 on CPU, which leadership
    # would balance and it does not move, and none on the three it can
    assert sum(start.values()) == 80 and min(start.values()) >= 15
    assert left == {"ReplicaDistributionGoal": 0,
                    "DiskUsageDistributionGoal": 0,
                    "NetworkInboundUsageDistributionGoal": 0,
                    "NetworkOutboundUsageDistributionGoal": 7,
                    "CpuUsageDistributionGoal": 5}
    assert (dep.broker_rack[after] == dep.broker_rack[dep.assignment]).all()
    moves = [{"topicPartition": dict(zip(("topic", "partition"),
                                         dep.topic_partition(i))),
              "oldReplicas": dep.assignment[i].tolist(),
              "oldLeader": int(dep.assignment[i, 0]),
              "newReplicas": after[i].tolist(),
              "newLeader": int(after[i, 0])}
             for i in np.flatnonzero((after != dep.assignment).any(axis=1))]
    numbers = reference.evaluate(dep, cfg["guarantees"], moves)["numbers"]
    assert not any(numbers.values()), numbers
    # ``applied`` reads a body back to the assignment it was made from
    again, leader_col = ref.applied(dep, moves)
    assert (again == after).all() and not leader_col.any()
    # a chain without the goals, or an assignment inside every band: no move
    assert (ref.rebalance(dep, cfg["guarantees"], ["RackAwareGoal"])
            == dep.assignment).all()


@pytest.fixture(scope="module")
def three_az(tiny, cpu_device):
    """One traced rehearsal of the three-AZ cluster at 18 / 576, with the
    cell's two metrics listed for it and every planted fault read."""
    import run
    benchmark = copy.deepcopy(tiny)
    for name in METRICS:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            benchmark["per_layer"].append(
                {**json.load(f), "workloads": ["tiny.rebalance"]})
    return run.run_cell(benchmark, "tiny.rebalance", 2**31 + 34, 1.5, True,
                        cpu_device, time.monotonic(), cfg_patch=PATCH,
                        faults=True)


def test_the_programs_own_answer_is_correct_on_three_racks(three_az):
    assert three_az["correct"] is True, three_az["compared"]
    assert three_az["failed"] == 0
    assert three_az["workload"]["proposals"] > 0
    metrics = three_az["metrics"]
    # PotentialNwOutGoal stays violated at this size (one topic here; with
    # two TopicReplicaDistributionGoal does too, tests/test_three_az.py);
    # about two candidates of three are vetoed by an earlier goal, the
    # rack rule's share
    assert metrics["threeaz.violated_goals"] == {"value": 1.0,
                                                 "unit": "goals"}
    assert metrics["threeaz.prior_veto_pct"]["unit"] == "%"
    assert 50.0 < metrics["threeaz.prior_veto_pct"]["value"] < 90.0


def test_the_planted_faults_from_a_rack_aware_start(three_az):
    """Each fault reads above 0 on the number ``faults.FAULTS`` names,
    but for ``no_moves`` and ``half_moves``: from a start that is
    rack-aware already a plan left out breaks no rack rule (the start's
    breach is ``over_capacity``, which ``rack_only`` shows), as in the
    scale-out's cell (PERF.md section 7)."""
    from benchlib import faults
    faulted = three_az["faulted"]
    assert set(faulted) == {f.__name__ for f in faults.FAULTS}
    for fault, number in faults.FAULTS.items():
        reading = faulted[fault.__name__]
        assert list(reading) == [number]
        if fault in (faults.no_moves, faults.half_moves):
            assert reading[number] == 0
        else:
            assert reading[number] >= 1, fault.__name__


@pytest.mark.parametrize("control,number", sorted(CONTROLS.items()))
def test_the_control_is_not_correct_on_three_racks(tiny, cpu_device, control,
                                                   number):
    """The configuration's controls at 18 / 576 on the cell's start: each
    comes out as not correct by the count its ``about`` names and by no
    other."""
    import run
    entry = config()["controls"][control]
    assert number in entry["about"]
    result = run.run_cell(tiny, "tiny.rebalance", 2**31 + 35, 1.0, False,
                          cpu_device, time.monotonic(),
                          cfg_patch={**PATCH, **entry["patch"]})
    breached = {k for k, v in result["compared"].items() if v[0]}
    assert result["correct"] is False and breached == {number}


def context(at_setup, at_close, solves=2):
    from benchlib.metrics import Context
    return Context(cfg=config(), mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
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
    ("threeaz.violated_goals", 5.0), ("threeaz.prior_veto_pct", 70.0)])
def test_the_readers_on_hand_made_counters(name, expected):
    """Two proposals of a window, each leaving five goals violated and
    each vetoing 700 of 1,000 valid candidates."""
    from benchlib.metrics import read_metric
    violated = [("solver_goals_violated_after_total", f'{{goal="g{i}"}}')
                for i in range(7)]
    valid = ("solver_round_candidates_total", '{goal="g6",stage="valid"}')
    accepted = ("solver_round_candidates_total",
                '{goal="g6",stage="accepted"}')
    passes = ("pass_seq", "")
    at_setup = {**{k: (1.0 if i < 5 else 0.0)
                   for i, k in enumerate(violated)},
                valid: 1000.0, accepted: 300.0, passes: 4.0}
    at_close = {**{k: (3.0 if i < 5 else 0.0)
                   for i, k in enumerate(violated)},
                valid: 3000.0, accepted: 900.0, passes: 6.0}
    assert read_metric(name, context(at_setup, at_close)) \
        == pytest.approx(expected)
    # a window that completed nothing
    assert read_metric(name, context(at_setup, at_close, solves=0)) is None
    # a window that ran no pass: nothing to read
    assert read_metric(name, context(at_setup, {**at_setup})) is None


def test_the_configurations_file_keeps_to_the_contract(benchmark_file):
    entry = {c["name"]: c for c in benchmark_file["configs"]}[NAME]
    cfg = config()
    with open(os.path.join(BENCH, "configs", "kafka-250b-25kp.json")) as f:
        sibling = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == list(cfg["source_scale"])
    assert (cfg["brokers"], cfg["partitions"], cfg["topics"], cfg["racks"],
            cfg["replication_factor"]) == (252, 25200, 25, 3, 3)
    assert cfg["brokers"] % cfg["racks"] == 0
    assert cfg["placement"] == "skewed_rack_aware"
    assert cfg["operation"] == "proposals"
    for part in ("benchlib/reference.py", "benchlib/threeaz_reference.py"):
        assert part in cfg["reference"]
        assert os.path.isfile(os.path.join(BENCH, part))
    # the sibling's deployment on three racks, rounded up to a multiple of
    # three, under another placement rule
    same = set(sibling) - {"name", "source", "deployment", "brokers",
                           "partitions", "racks", "guarantees", "reference",
                           "assumed", "controls"}
    assert all(cfg[k] == sibling[k] for k in same)
    assert set(cfg) - set(sibling) == {"placement"}
    assert {k: v for k, v in cfg["guarantees"].items()
            if k != "rack_awareness"} \
        == {k: v for k, v in sibling["guarantees"].items()
            if k != "rack_awareness"}
    assert cfg["guarantees"]["rack_awareness"].startswith(
        sibling["guarantees"]["rack_awareness"])
    assert set(cfg["controls"]) == set(CONTROLS)
    for name, control in cfg["controls"].items():
        assert control["patch"] == sibling["controls"][name]["patch"]
    cell = {w["name"]: w for w in benchmark_file["workloads"]}[
        NAME + ".rebalance"]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (entry["name"], "rebalance", 1)
    assert len(cell["why"]) <= 200
    for name in METRICS:
        m = {m["name"]: m for m in benchmark_file["per_layer"]}[name]
        assert m["workloads"] == [cell["name"]]
        assert (m["source"], m["layer"], m["moves"], m["better"]) \
            == ("program_counter", "round body", "proposal_s", "lower")
    # appended: the accepted entries stand first, in their order
    assert [c["name"] for c in benchmark_file["configs"]][-1] == NAME
    assert [w["name"] for w in benchmark_file["workloads"]][-1] \
        == cell["name"]
    assert [m["name"] for m in benchmark_file["per_layer"]][-2:] \
        == list(METRICS)
