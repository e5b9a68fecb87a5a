"""The drain's cell: ``on_removed_broker`` reads what is left on the removed
brokers (it had never been shown above 0: ``faults.py`` plants no fault for
it), the program's own answer reads 0 in a rehearsal at 16 / 512 with the
cell's two metrics in the traced line, and the new configuration's file
keeps to what ``test_contract.py`` asks of one."""

import copy
import json
import os
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT

REMOVED = [3, 7]


@pytest.fixture(scope="module")
def tiny_cfg():
    with open(os.path.join(BENCH, "tests", "tiny-16b-512p.json")) as f:
        return {**json.load(f), "operation": "remove_broker",
                "operation_brokers": REMOVED}


@pytest.fixture(scope="module")
def drained(tiny, cpu_device):
    """One traced rehearsal of the drain at 16 / 512 through the command's
    own ``run_cell``, with the cell's two metrics listed for it."""
    import run
    benchmark = copy.deepcopy(tiny)
    for name in ("drain.evacuated_replicas", "drain.evacuation_rounds"):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            benchmark["per_layer"].append(
                {**json.load(f), "workloads": ["tiny.rebalance"]})
    return run.run_cell(
        benchmark, "tiny.rebalance", 2**31 + 27, 1.5, True, cpu_device,
        time.monotonic(), cfg_patch={"operation": "remove_broker",
                                     "operation_brokers": REMOVED})


def test_on_removed_broker_counts_what_is_left(tiny_cfg):
    from benchlib import drain_reference, reference
    from benchlib.deployment import build
    dep = build(tiny_cfg)
    stranded = int(np.isin(dep.assignment, REMOVED).sum())
    assert stranded == len(drain_reference.must_move(dep, REMOVED)) > 0

    def left(proposals):
        return reference.evaluate(dep, tiny_cfg["guarantees"],
                                  proposals)["numbers"]["on_removed_broker"]

    assert left([]) == stranded
    plan = drain_reference.as_proposals(dep, drain_reference.drain(
        dep, REMOVED, tiny_cfg["guarantees"]))
    assert left(plan) == 0
    # one move of a partition the drain did not touch, retargeted onto a
    # removed broker: 1 more than nothing, and 1 more than an empty plan
    i = next(i for i in range(dep.partitions)
             if not np.isin(dep.assignment[i], REMOVED).any())
    topic, part = dep.topic_partition(i)
    old = dep.assignment[i].tolist()
    stray = {"topicPartition": {"topic": topic, "partition": part},
             "oldLeader": old[0], "oldReplicas": old,
             "newLeader": old[0], "newReplicas": old[:-1] + [REMOVED[0]]}
    assert left(plan + [stray]) == 1
    assert left([stray]) == stranded + 1


def test_the_programs_own_answer_leaves_nothing(drained, tiny_cfg):
    from benchlib.deployment import build
    assert drained["correct"] is True, drained["compared"]
    assert drained["compared"]["on_removed_broker"] == [0, 0]
    assert drained["failed"] == 0 and drained["workload"]["proposals"] > 0
    stranded = int(np.isin(build(tiny_cfg).assignment, REMOVED).sum())
    metrics = drained["metrics"]
    assert metrics["drain.evacuated_replicas"] == {"value": float(stranded),
                                                   "unit": "replicas"}
    assert metrics["drain.evacuation_rounds"]["value"] > 0


@pytest.mark.parametrize("name", ["drain.evacuated_replicas",
                                  "drain.evacuation_rounds"])
def test_a_program_without_the_counters_gives_nothing_to_read(name):
    """The parent commit has no such counter: the reader returns None and
    does not raise, so the traced line leaves the metric out."""
    from benchlib.metrics import Context, read_metric
    ctx = Context(cfg={}, mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                  at_setup={("pass_seq", ""): 3.0},
                  at_close={("pass_seq", ""): 5.0}, solves=[object()],
                  reads=[], device={})
    assert read_metric(name, ctx) is None


def test_the_configurations_file_keeps_to_the_contract(benchmark_file):
    entry = {c["name"]: c for c in benchmark_file["configs"]}[
        "kafka-100b-10kp-drain"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs", "kafka-100b-10kp.json")) as f:
        sibling = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == list(cfg["source_scale"])
    for key in entry["reduced"]:
        assert cfg[key] != cfg["source_scale"][key]
        # a tenth of the source along its own recipe
        assert cfg[key] * 10 == cfg["source_scale"][key]
    assert cfg["operation"] == "remove_broker"
    assert len(cfg["operation_brokers"]) == cfg["drained_brokers"] \
        == len(set(cfg["operation_brokers"]))
    assert "removed_brokers_hold_nothing" in cfg["guarantees"]
    # the sibling's deployment under another operation: nothing else moved
    same = set(sibling) - {"name", "source", "deployment", "operation",
                           "operation_brokers", "guarantees", "reference",
                           "source_scale", "assumed"}
    assert all(cfg[k] == sibling[k] for k in same)
    assert {k: v for k, v in cfg["guarantees"].items()
            if k != "removed_brokers_hold_nothing"} == sibling["guarantees"]
    assert cfg["assumed"][:len(sibling["assumed"])] == sibling["assumed"]
    cell = {w["name"]: w for w in benchmark_file["workloads"]}[
        "kafka-100b-10kp.drain"]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (entry["name"], "rebalance", 1)
