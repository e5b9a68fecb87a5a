"""The two seams a configuration brings files through: its ``placement``
rule (``placements/<name>.py``) and its operation's rule
(``guarantees/<operation>.py``), both found by name and neither needing an
edit to a file that is there. The accepted cells' clusters are the
parent's, array for array; Kafka's own assignor draws the start a scale-out
is asked from; ``onto_old_broker`` counts what ``add_broker`` may not do.
Nothing here asserts what the program answers to ``add_broker``."""

import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

from benchlib import deployment, faults, reference
from benchlib.deployment import build
from conftest import BENCH

# sha256 (first 16 hex digits) of assignment, leader_load, follower_load
# and capacity as build() gave them at the parent commit (e22622a, PR 30),
# taken before deployment.py was touched.
PARENT = {
    "kafka-100b-10kp": ("a1f6f55b3e00dfbf", "55e61da6001f7445",
                        "193b23f5be7335b4", "b903cda785c8ac85"),
    "kafka-250b-25kp": ("54921690c2886e87", "4d54af44cca6ebf7",
                        "6909999faa1494b3", "336272f9c037d7c0"),
    "kafka-100b-10kp-drain": ("a1f6f55b3e00dfbf", "55e61da6001f7445",
                              "193b23f5be7335b4", "b903cda785c8ac85"),
}
ARRAYS = ("assignment", "leader_load", "follower_load", "capacity")


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_cfg():
    with open(os.path.join(BENCH, "tests", "tiny-16b-512p.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_accepted_clusters_are_the_parents(name):
    cfg = config(name)
    assert "placement" not in cfg    # skewed_random is a missing key's rule
    dep = build(cfg)
    assert tuple(hashlib.sha256(getattr(dep, a).tobytes()).hexdigest()[:16]
                 for a in ARRAYS) == PARENT[name]
    assert dep.assignment.dtype == np.int64


def scale_out(cfg, new):
    return {**cfg, "placement": "kafka_rack_aware",
            "operation": "add_broker" if new else cfg["operation"],
            "operation_brokers": new}


# (configuration, new brokers, replicas within, leaders within) of the
# hosting brokers' mean. With brokers 14 and 15 new, racks 6 and 7 keep ONE
# broker each and the other six two: KIP-36 says of racks of unequal size
# that the brokers of the smaller ones take more replicas (7.0 % here).
STARTS = {
    "16b-512p": ("tiny", [], 0.05, 0.10),
    "16b-512p-2-new": ("tiny", [14, 15], 0.10, 0.10),
    "250b-25kp-10-new": ("kafka-250b-25kp", list(range(240, 250)),
                         0.05, 0.10),
}


@pytest.mark.parametrize("case", sorted(STARTS))
def test_kafkas_assignor_draws_a_cluster_a_scale_out_starts_from(
        case, tiny_cfg):
    name, new, replicas_within, leaders_within = STARTS[case]
    cfg = scale_out(tiny_cfg if name == "tiny" else config(name), new)
    dep = build(cfg)
    a = dep.assignment
    assert a.shape == (cfg["partitions"], 3)
    assert reference.rack_violations(dep, a) == 0
    srt = np.sort(a, axis=1)
    assert (srt[:, 1:] != srt[:, :-1]).all()        # no broker twice
    hosts = np.setdiff1d(np.arange(dep.brokers), new)
    replicas = np.bincount(a.ravel(), minlength=dep.brokers)
    leaders = np.bincount(a[:, 0], minlength=dep.brokers)
    assert replicas[new].sum() == 0 == leaders[new].sum()
    for counts, within in ((replicas, replicas_within),
                           (leaders, leaders_within)):
        mean = counts[hosts].mean()
        assert np.abs(counts[hosts] / mean - 1).max() <= within
    # under every capacity limit at target_utilization 0.5, before a move
    sound = reference.evaluate(dep, cfg["guarantees"], [])
    assert sound["numbers"]["over_capacity"] == 0
    assert sound["info"]["capacity_worst_before"] < 1.0
    # the same file and instance_seed: the same arrays; another: another
    again = build(cfg)
    assert all(np.array_equal(getattr(dep, k), getattr(again, k))
               for k in ARRAYS)
    other = build({**cfg, "instance_seed": cfg["instance_seed"] + 1})
    assert not np.array_equal(other.leader_load, dep.leader_load)


def test_kafkas_assignor_on_kip_36s_example():
    """KIP-36, "Proposed Changes": six brokers on three racks (0 and 5 on
    rack 1, 3 and 4 on rack 2, 1 and 2 on rack 3), start index and shift
    0: the alternated list is 0, 3, 1, 5, 4, 2, and partition 6 takes a
    new shift where it would repeat partition 0."""
    rule = deployment.load_module("placements", "kafka_rack_aware")
    host_rack = np.array([1, 3, 3, 2, 2, 1])
    assert rule.rack_alternated(host_rack) == [0, 3, 1, 5, 4, 2]

    class Zeros:
        def integers(self, low, high, size):
            return np.zeros(size, dtype=np.int64)

    got = rule.place({"partitions": 8, "topics": 1, "replication_factor": 3},
                     np.arange(6), host_rack, Zeros())
    assert got.tolist() == [[0, 3, 1], [3, 1, 5], [1, 5, 4], [5, 4, 2],
                            [4, 2, 0], [2, 0, 3], [0, 4, 2], [3, 2, 0]]


def move(dep, i, new, leader=None):
    topic, part = dep.topic_partition(i)
    old = dep.assignment[i].tolist()
    return {"topicPartition": {"topic": topic, "partition": part},
            "oldLeader": old[0], "oldReplicas": old,
            "newLeader": new[0] if leader is None else leader,
            "newReplicas": new}


@pytest.fixture(scope="module")
def scaled_out(tiny_cfg):
    cfg = scale_out(tiny_cfg, [14, 15])
    return cfg, build(cfg)


def an_old_broker(dep, row):
    """An old broker that does not hold the row, on a rack it does not
    use."""
    used = set(dep.broker_rack[dep.assignment[row]])
    return next(b for b in range(14) if b not in dep.assignment[row]
                and dep.broker_rack[b] not in used)


@pytest.mark.parametrize("plan,expected", [
    ("all_onto_new", 0), ("one_onto_old", 1), ("leadership_only", 0),
    ("back_onto_an_old_replica", 0)])
def test_onto_old_broker_on_hand_made_plans(scaled_out, plan, expected):
    cfg, dep = scaled_out
    a = dep.assignment.tolist()
    plans = {
        "all_onto_new": [move(dep, 0, a[0][:2] + [14]),
                         move(dep, 1, [15] + a[1][1:])],
        "one_onto_old": [move(dep, 0, a[0][:2] + [14]),
                         move(dep, 1, a[1][:2] + [an_old_broker(dep, 1)])],
        "leadership_only": [move(dep, 0, a[0], leader=a[0][1]),
                            move(dep, 1, a[1][::-1])],
        # the replica on a[0][2] goes to 14 and its place in the list to
        # a[0][0], which held the partition before the plan
        "back_onto_an_old_replica": [move(dep, 0, [14, a[0][1], a[0][0]])],
    }
    numbers = reference.evaluate(dep, cfg["guarantees"],
                                 plans[plan])["numbers"]
    assert numbers["onto_old_broker"] == expected
    assert sum(numbers.values()) == expected    # and no other number moves


def test_the_scale_outs_fault_reads_one_on_a_plan_that_read_nought(
        scaled_out):
    cfg, dep = scaled_out
    a = dep.assignment.tolist()
    plan = [move(dep, 0, a[0], leader=a[0][1]),
            move(dep, 1, a[1][:2] + [14]), move(dep, 2, [15] + a[2][1:])]
    (fault, number), = [(f, n) for f, n in faults.planted("add_broker")
                        .items() if f not in faults.FAULTS]
    assert number == "onto_old_broker"
    before = reference.evaluate(dep, cfg["guarantees"], plan)["numbers"]
    broken = fault(plan, dep)
    after = reference.evaluate(dep, cfg["guarantees"], broken)["numbers"]
    assert sum(before.values()) == 0
    assert after == {**before, "onto_old_broker": 1}
    assert plan[1]["newReplicas"][2] == 14      # the input is not altered
    with pytest.raises(ValueError):
        fault(plan[:1], dep)        # no move onto a new broker to alter


def test_the_drains_fault_reads_one_on_a_plan_that_read_nought(tiny_cfg):
    from benchlib import drain_reference
    cfg = {**tiny_cfg, "operation": "remove_broker",
           "operation_brokers": [3, 7]}
    dep = build(cfg)
    plan = drain_reference.as_proposals(dep, drain_reference.drain(
        dep, [3, 7], cfg["guarantees"]))
    (fault, number), = [(f, n) for f, n in faults.planted("remove_broker")
                        .items() if f not in faults.FAULTS]
    assert number == "on_removed_broker"
    before = reference.evaluate(dep, cfg["guarantees"], plan)["numbers"]
    after = reference.evaluate(dep, cfg["guarantees"],
                               fault(plan, dep))["numbers"]
    assert before["on_removed_broker"] == 0
    assert after == {**before, "on_removed_broker": 1}
    assert len(fault(plan, dep)) == len(plan) + 1


def test_compared_takes_its_names_from_the_operation():
    assert reference.numbers_of("proposals") == reference.NUMBERS \
        == reference.numbers_of("remove_broker")
    assert reference.numbers_of("add_broker") \
        == reference.NUMBERS + ("onto_old_broker",)
    # a window that completed no body still prints the operation's number
    assert reference.worst([], reference.numbers_of("add_broker"))[
        "onto_old_broker"] == 0
    assert set(faults.planted("proposals")) == set(faults.FAULTS)


RING_ONLY = '''
"""Every partition on the ring: row i on brokers i, i + 1, i + 2."""
import numpy as np


def place(cfg, hosts, host_rack, rng):
    return (np.arange(int(cfg["partitions"]))[:, None]
            + np.arange(3)) % len(hosts)
'''

ROW_NOUGHT = '''
"""A rule for ``rebalance``, which has none: row 0 stays where it is."""
import numpy as np

NUMBERS = ("row_nought_moved",)


def count(dep, assignment, leader_col, proposals):
    return {"row_nought_moved": int(
        not np.array_equal(assignment[0], dep.assignment[0]))}


def move_row_nought(proposals, dep):
    topic, part = dep.topic_partition(0)
    old = dep.assignment[0].tolist()
    rest = [p for p in proposals if p["topicPartition"]
            != {"topic": topic, "partition": part}]
    return rest + [{"topicPartition": {"topic": topic, "partition": part},
                    "oldLeader": old[0], "oldReplicas": old,
                    "newLeader": old[0], "newReplicas": old[:2] + [11]}]


FAULTS = {move_row_nought: "row_nought_moved"}
'''


def test_a_rule_and_a_guarantee_are_files(tmp_path, monkeypatch, tiny,
                                          cpu_device):
    """A placement rule and an operation's rule added as files to a copy
    of the harness's directories, which ``deployment.HERE`` alone points
    at: a rehearsal finds both, and no file under ``benchmarks/`` is
    edited."""
    import run
    for kind in ("placements", "guarantees", "metrics", "traffic"):
        shutil.copytree(os.path.join(BENCH, kind), tmp_path / kind)
    (tmp_path / "placements" / "ring_only.py").write_text(RING_ONLY)
    (tmp_path / "guarantees" / "rebalance.py").write_text(ROW_NOUGHT)
    monkeypatch.setattr(deployment, "HERE", str(tmp_path))
    result = run.run_cell(
        tiny, "tiny.rebalance", 2**31 + 31, 1.0, False, cpu_device,
        time.monotonic(), faults=True,
        cfg_patch={"placement": "ring_only", "operation": "rebalance"})
    assert result["failed"] == 0 and result["workload"]["proposals"] > 0
    assert result["compared"]["row_nought_moved"][1] == 0
    assert result["faulted"]["move_row_nought"] == {"row_nought_moved": 1}
    assert set(result["metrics"]) == {"proposal_s", "balancedness_after",
                                      "setup_s"}
    ring = build({**config("kafka-100b-10kp"), "placement": "ring_only"})
    assert ring.assignment[:3].tolist() == [[0, 1, 2], [1, 2, 3], [2, 3, 4]]
    monkeypatch.undo()
    with pytest.raises(FileNotFoundError):
        build({**config("kafka-100b-10kp"), "placement": "ring_only"})
    assert reference.numbers_of("rebalance") == reference.NUMBERS


def spans_context(cfg, endpoint):
    """A window in which one request of ``endpoint`` closed its spans."""
    from benchlib.metrics import Context
    at_close = {}
    for span, seconds in (("http.request", 0.5), ("http.serialize", 0.016),
                          ("http.write", 0.001)):
        labels = f'{{span="{span}",endpoint="{endpoint}"}}'
        at_close[("trace_span_seconds_sum", labels)] = seconds
        at_close[("trace_span_seconds_count", labels)] = 1.0
    for segment, seconds in (("render", 0.018), ("proposal_diff", 0.002)):
        labels = f'{{endpoint="{endpoint}",segment="{segment}"}}'
        at_close[("journey_segment_seconds_sum", labels)] = seconds
        at_close[("journey_segment_seconds_count", labels)] = 1.0
    return Context(cfg=cfg, mix={}, seconds=1.0, setup_s=1.0, t0=0.0,
                   at_setup={}, at_close=at_close, solves=[object()],
                   reads=[], device={})


@pytest.mark.parametrize("operation,endpoint", [
    ("proposals", "PROPOSALS"), ("remove_broker", "REMOVE_BROKER"),
    ("add_broker", "ADD_BROKER")])
def test_the_span_metrics_read_the_cells_own_endpoint(operation, endpoint):
    from benchlib.metrics import read_metric
    ctx = spans_context({"operation": operation}, endpoint)
    assert read_metric("http.serialize_write_ms", ctx) \
        == pytest.approx(17.0)
    assert read_metric("host.render_ms", ctx) == pytest.approx(20.0)
    assert read_metric("http.unattributed_ms", ctx) \
        == pytest.approx(500.0 - 17.0 - 20.0)
    # another operation's requests are not this cell's
    other = spans_context({"operation": "rebalance"}, endpoint)
    for name in ("http.serialize_write_ms", "host.render_ms",
                 "http.unattributed_ms"):
        assert read_metric(name, other) is None
