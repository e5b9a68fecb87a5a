"""What ``POST /add_broker`` promises beyond the goals: replicas move only
from the brokers that were there onto the new ones, never among the old.

Source: upstream's REST documentation of
``POST /kafkacruisecontrol/add_broker`` (Cruise Control wiki, "REST APIs",
"Add a list of new brokers to Kafka Cluster": "the replicas are only moved
from the existing brokers to the new brokers, not among existing
brokers"); ``SURVEY.md`` Appendix A.2 item 6 ("when new brokers exist,
only they may receive load").

``onto_old_broker``: the replicas that the plan places on a broker that is
neither new (``operation_brokers``) nor already a replica of that
partition before the plan, summed over the partitions
(``set(newReplicas) - set(oldReplicas) - set(operation_brokers)``), read on
the assignment the reference applied the moves to. Limit 0.

``assumed``: leadership may move among the old brokers. A move that
reorders a partition's replicas, or hands leadership to another of them,
places no replica and counts nothing; the documentation speaks of
replicas only.

An operation's rule is this file's three names: ``NUMBERS``,
``count(dep, assignment, leader_col, proposals) -> {number: breaches}``
(the deployment, the assignment and leader columns after the moves, the
body's moves) and ``FAULTS`` (as ``benchlib/faults.py:FAULTS``: fault ->
the number it has to push over its limit).
"""

import copy

import numpy as np
from benchlib.reference import placed

NUMBERS = ("onto_old_broker",)


def count(dep, assignment, leader_col, proposals):
    onto_new = np.isin(assignment, dep.operation_brokers)
    return {"onto_old_broker":
            int((placed(dep, assignment) & ~onto_new).sum())}


def onto_old(proposals, dep):
    """The first move that places a replica on a new broker, retargeted
    onto an old broker that does not hold the partition, on a rack the
    partition does not use: no other number moves."""
    for i, p in enumerate(proposals):
        placed = [b for b in p["newReplicas"] if b not in p["oldReplicas"]
                  and b in dep.operation_brokers]
        if not placed:
            continue
        held = set(p["newReplicas"]) | set(p["oldReplicas"])
        used = {dep.broker_rack[b] for b in held}
        q = copy.deepcopy(p)
        q["newReplicas"][q["newReplicas"].index(placed[0])] = next(
            b for b in range(dep.brokers)
            if b not in dep.operation_brokers and b not in held
            and dep.broker_rack[b] not in used)
        if q["newLeader"] == placed[0]:
            q["newLeader"] = q["newReplicas"][0]
        return proposals[:i] + [q] + proposals[i + 1:]
    raise ValueError("no move onto a new broker to alter")


FAULTS = {onto_old: "onto_old_broker"}
