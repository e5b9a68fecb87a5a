"""What ``POST /remove_broker`` promises beyond the goals: after the moves
nothing is left on the removed brokers (``operation_brokers``).

The COUNT, ``on_removed_broker``, is not here: it stays in
``benchlib/reference.py:NUMBERS`` and every cell prints it as before (0
where the operation removes nothing), because the accepted cells'
``compared`` keeps the names it has. This file brings the planted fault
the count never had (PERF.md, PR 27: "``faults.py`` plants no fault for
it"); ``benchmarks/tests/test_drain.py`` builds the same move by hand.
"""

import numpy as np

NUMBERS = ()


def count(dep, assignment, leader_col, proposals):
    return {}


def stray_onto_removed(proposals, dep):
    """One more move: the last replica of a partition that the plan does
    not touch and that no removed broker hosts, sent to a removed broker."""
    moved = {(p["topicPartition"]["topic"], p["topicPartition"]["partition"])
             for p in proposals}
    removed = dep.operation_brokers
    for i in range(dep.partitions):
        topic, part = dep.topic_partition(i)
        old = dep.assignment[i].tolist()
        if (topic, part) not in moved and not np.isin(old, removed).any():
            return proposals + [{
                "topicPartition": {"topic": topic, "partition": part},
                "oldLeader": old[0], "oldReplicas": old,
                "newLeader": old[0], "newReplicas": old[:-1] + [removed[0]]}]
    raise ValueError("no untouched partition to send onto a removed broker")


FAULTS = {stray_onto_removed: "on_removed_broker"}
