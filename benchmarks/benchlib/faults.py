"""Planted faults: the answers of a sound run, altered where they are
produced, one guarantee at a time. Each has to read above the limit on the
number named beside it; ``readings.py --faults`` reads them on the chip at
the cell's own size, ``tests/test_run.py`` and ``tests/test_seams.py`` at a
small size.

A fault is ``f(proposals, dep) -> proposals`` over one body's list of
moves (``READ_FAULTS``: over one read's body). None alters its input. The
faults of an operation's own guarantee sit with its rule,
``guarantees/<operation>.py``; ``planted`` gives both.
"""

from __future__ import annotations

import copy

from .reference import operation_rule


def _alter(proposals: list, change) -> list:
    """The list with ``change`` applied to a copy of its first move that
    moves a replica (a leadership-only move keeps its brokers)."""
    for i, p in enumerate(proposals):
        if sorted(p["newReplicas"]) != sorted(p["oldReplicas"]):
            q = copy.deepcopy(p)
            change(q)
            return proposals[:i] + [q] + proposals[i + 1:]
    raise ValueError("no move to alter")


def no_moves(proposals, dep):
    """A step that returns its state unchanged."""
    return []


def half_moves(proposals, dep):
    """Half of the batch left out."""
    return proposals[::2]


def drop_replica(proposals, dep):
    return _alter(proposals, lambda q: q["newReplicas"].pop())


def dup_replica(proposals, dep):
    def change(q):
        q["newReplicas"][-1] = q["newReplicas"][0]
    return _alter(proposals, change)


def dead_broker(proposals, dep):
    def change(q):
        q["newReplicas"][-1] = dep.brokers + 7
    return _alter(proposals, change)


def wrong_leader(proposals, dep):
    def change(q):
        q["newLeader"] = next(b for b in range(dep.brokers)
                              if b not in q["newReplicas"])
    return _alter(proposals, change)


def stale_old(proposals, dep):
    return _alter(proposals, lambda q: q["oldReplicas"].reverse())


def unknown_partition(proposals, dep):
    def change(q):
        q["topicPartition"]["partition"] = dep.partitions
    return _alter(proposals, change)


def same_rack(proposals, dep):
    """One replica moved onto the rack of the partition's leader."""
    def change(q):
        lead = q["newReplicas"][0]
        q["newReplicas"][-1] = next(
            b for b in range(dep.brokers)
            if dep.broker_rack[b] == dep.broker_rack[lead]
            and b not in q["newReplicas"])
    return _alter(proposals, change)


def pile_up(proposals, dep):
    """Every move's last replica sent to one broker."""
    out = copy.deepcopy(proposals)
    for q in out:
        if 0 not in q["newReplicas"]:
            q["newReplicas"][-1] = 0
    return out


# fault -> the number it has to push over its limit
FAULTS = {
    no_moves: "rack_violations", half_moves: "rack_violations",
    drop_replica: "rf_broken", dup_replica: "dup_broker",
    dead_broker: "dead_broker", wrong_leader: "leader_not_replica",
    stale_old: "stale_old", unknown_partition: "unknown_partition",
    same_rack: "rack_violations", pile_up: "over_capacity",
}


def planted(operation: str) -> dict:
    """``FAULTS`` and those of the operation's own rule."""
    rule = operation_rule(operation)
    return {**FAULTS, **(rule.FAULTS if rule else {})}


def stale_read(body, dep):
    """A read that answers from another cluster picture: one replica
    fewer on a broker."""
    out = copy.deepcopy(body)
    if "brokers" in out:
        out["brokers"][0]["Replicas"] -= 1
    elif "KafkaBrokerState" in out:
        counts = out["KafkaBrokerState"]["ReplicaCountByBrokerId"]
        counts[next(iter(counts))] -= 1
    else:
        out["MonitorState"]["totalNumPartitions"] -= 1
    return out


READ_FAULTS = {stale_read: "read_mismatch"}
