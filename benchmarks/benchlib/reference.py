"""The plain reference: what a served proposal set has to satisfy, in numpy.

It imports nothing of the program and reads nothing the program made: the
deployment (``deployment.py``, from the configuration's file and the seed)
and the proposal list of one response body are all it sees. It applies the
moves to the deployment and counts every breach of the guarantees the
configuration's file states. Every number is a count, compared with the
limit 0: sound runs read 0 on every one, and the control (the program with
its hard goals switched off) and the planted faults read well above it.

Semantics follow Cruise Control's: a partition's leader replica carries the
leader load, the others the follower load (no NW_OUT, the follower share
of CPU); RackAwareGoal wants min(RF, racks) distinct racks per partition;
a capacity goal wants every alive broker's summed load of a resource at or
under ``capacity * threshold``; ReplicaCapacityGoal wants at most
``max_replicas_per_broker`` replicas on a broker.

What an OPERATION promises beyond the goals is a file of its own,
``guarantees/<operation>.py``, found by the name the configuration gives
(``operation_rule``): its counts join ``NUMBERS`` and its planted faults
``faults.FAULTS``, so that a deployment under a new operation brings its
rule and edits nothing here.
"""

from __future__ import annotations

import os

import numpy as np

from . import deployment
from .deployment import RESOURCES, Deployment

# The program sums a broker's load in float32; the reference in float64.
# A broker is over a limit only beyond what that rounding can explain.
_ROUNDING = 1e-5

NUMBERS = ("unknown_partition", "stale_old", "rf_broken", "dup_broker",
           "dead_broker", "leader_not_replica", "on_removed_broker",
           "rack_violations", "over_capacity")


def operation_rule(operation: str):
    """``guarantees/<operation>.py`` where the operation has a rule of its
    own, else None."""
    if not os.path.isfile(deployment.path_of("guarantees",
                                             f"{operation}.py")):
        return None
    return deployment.load_module("guarantees", operation)


def numbers_of(operation: str) -> tuple[str, ...]:
    """The names a cell under ``operation`` compares: ``NUMBERS`` and its
    rule's."""
    rule = operation_rule(operation)
    return NUMBERS + (tuple(rule.NUMBERS) if rule else ())


def broker_loads(dep: Deployment, assignment: np.ndarray,
                 leader_col: np.ndarray) -> np.ndarray:
    """[B, 4] summed load; ``leader_col[p]`` is the column of row p that
    leads."""
    rf = assignment.shape[1]
    out = np.zeros((dep.brokers, 4))
    for s in range(rf):
        leads = (leader_col == s)[:, None]
        load = np.where(leads, dep.leader_load, dep.follower_load)
        for r in range(4):
            out[:, r] += np.bincount(assignment[:, s], weights=load[:, r],
                                     minlength=dep.brokers)
    return out


def rack_violations(dep: Deployment, assignment: np.ndarray) -> int:
    racks = np.sort(dep.broker_rack[assignment], axis=1)
    distinct = 1 + (racks[:, 1:] != racks[:, :-1]).sum(axis=1)
    return int((distinct < min(dep.rf, dep.racks)).sum())


def placed(dep: Deployment, assignment: np.ndarray) -> np.ndarray:
    """[P, RF] bool: the replicas of ``assignment`` on a broker that did
    not hold their partition in the deployment as built."""
    return ~(assignment[:, :, None] == dep.assignment[:, None, :]).any(axis=2)


def evaluate(dep: Deployment, guarantees: dict, proposals: list) -> dict:
    """Counts of breached guarantees (``numbers_of`` the deployment's
    operation) after the proposals are applied to the deployment, plus
    ``info``: readings that are printed but not compared."""
    n = dict.fromkeys(NUMBERS, 0)
    assignment = dep.assignment.copy()
    leader_col = np.zeros(dep.partitions, dtype=np.int64)
    seen: set[int] = set()
    for p in proposals:
        tp = p["topicPartition"]
        i = dep.index_of(str(tp["topic"]), int(tp["partition"]))
        if i < 0:
            n["unknown_partition"] += 1
            continue
        old = [int(b) for b in p["oldReplicas"]]
        if i in seen or old != dep.assignment[i].tolist() \
                or int(p["oldLeader"]) != old[0]:
            n["stale_old"] += 1
            continue
        seen.add(i)
        new = [int(b) for b in p["newReplicas"]]
        if len(new) != dep.rf:
            n["rf_broken"] += 1
            continue
        if len(set(new)) != len(new):
            n["dup_broker"] += 1
        if any(not (0 <= b < dep.brokers and dep.alive[b]) for b in new):
            n["dead_broker"] += 1
            continue
        if int(p["newLeader"]) not in new:
            n["leader_not_replica"] += 1
            continue
        assignment[i] = new
        leader_col[i] = new.index(int(p["newLeader"]))

    n["rack_violations"] = rack_violations(dep, assignment)
    loads = broker_loads(dep, assignment, leader_col)
    thresholds = np.array([guarantees["capacity_threshold"][r]
                           for r in RESOURCES])
    ratio = loads / (dep.capacity * thresholds)
    ratio[~dep.alive] = 0.0
    n["over_capacity"] = int((ratio > 1.0 + _ROUNDING).sum())
    counts = np.bincount(assignment.ravel(), minlength=dep.brokers)
    if dep.operation == "remove_broker":
        n["on_removed_broker"] = int(
            np.isin(assignment, dep.operation_brokers).sum())
    rule = operation_rule(dep.operation)
    if rule is not None:
        n.update(rule.count(dep, assignment, leader_col, proposals))
    before = broker_loads(dep, dep.assignment,
                          np.zeros(dep.partitions, dtype=np.int64))
    new_here = placed(dep, assignment)
    touched = (assignment != dep.assignment).any(axis=1) | (leader_col != 0)
    info = {
        # Printed, not compared: at 100 partitions a broker no answer comes
        # near the ceiling, so no control or fault gives an upper reading.
        "over_replica_capacity": int(
            (counts > int(guarantees["max_replicas_per_broker"])).sum()),
        "capacity_worst": float(ratio.max()),
        "capacity_worst_before": float(
            (before / (dep.capacity * thresholds)).max()),
        "rack_violations_before": rack_violations(dep, dep.assignment),
        "moved_partitions": len(seen),
        "leadership_only": int((touched & ~new_here.any(axis=1)).sum()),
        "replicas_placed": int(new_here.sum()),
    }
    return {"numbers": n, "info": info}


def read_mismatch(dep: Deployment, endpoint: str, body: dict | None) -> bool:
    """Whether a read's answer disagrees with the deployment: exact
    comparisons only (counts, ids, replica lists). No proposal is executed
    (every operation is a dry run), so the cluster a read describes is the
    deployment as built."""
    replicas = np.bincount(dep.assignment.ravel(), minlength=dep.brokers)
    leaders = np.bincount(dep.assignment[:, 0], minlength=dep.brokers)
    try:
        if endpoint == "state":
            return int(body["MonitorState"]["totalNumPartitions"]) \
                != dep.partitions
        if endpoint == "load":
            rows = {int(b["Broker"]): b for b in body["brokers"]}
            return not (sorted(rows) == list(range(dep.brokers)) and all(
                int(rows[b]["Replicas"]) == replicas[b]
                and int(rows[b]["Leaders"]) == leaders[b]
                and rows[b]["Rack"] == f"rack{dep.broker_rack[b]}"
                for b in range(dep.brokers)))
        if endpoint == "kafka_cluster_state":
            state = body["KafkaBrokerState"]
            got = np.full((dep.partitions, dep.rf), -1, dtype=np.int64)
            for p in body["partitions"]:
                i = dep.index_of(p["topic"], int(p["partition"]))
                if i < 0 or len(p["replicas"]) != dep.rf \
                        or int(p["leader"]) != int(p["replicas"][0]):
                    return True
                got[i] = p["replicas"]
            return not (
                np.array_equal(got, dep.assignment)
                and all(int(state["ReplicaCountByBrokerId"].get(str(b), 0))
                        == replicas[b]
                        and int(state["LeaderCountByBrokerId"].get(str(b), 0))
                        == leaders[b] for b in range(dep.brokers)))
    except (KeyError, TypeError, ValueError):
        return True
    raise ValueError(f"no reference for the read {endpoint!r}")


def worst(evaluations: list[dict], names=NUMBERS) -> dict:
    """The largest reading of each number over a window's bodies, under
    whatever names the evaluations carry; a window that completed no body
    reads 0 under ``names`` (and fails ``no_proposal``)."""
    names = list(evaluations[0]["numbers"]) if evaluations else names
    return {k: max((e["numbers"][k] for e in evaluations), default=0)
            for k in names}
