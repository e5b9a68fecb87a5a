"""The plain reference of a scale-out: a sequential fill in numpy.

It imports nothing of the program. Given a deployment (``deployment.py``)
whose ``operation_brokers`` are new and empty, and the guarantees of the
configuration's file, it moves replicas one at a time from the old brokers
onto the new ones, and onto nothing else, until every new broker holds at
least the lower edge of ``ReplicaDistributionGoal``'s band, and returns the
final assignment, or ``None`` where some new broker cannot be brought
there.

The band (``lower_edge``): ``floor(mean / t)`` replicas, where ``mean`` is
the cluster's replicas over its alive brokers, the new ones included, and
``t`` is ``replica.count.balance.threshold`` (1.1 by default; the upper
edge is ``ceil(mean * t)``). Upstream:
``ReplicaDistributionAbstractGoal.initGoalState``.

One step: the new broker that holds fewest (lowest id first among equals)
takes a replica from the old broker that holds most (lowest id first);
of that broker's replicas, in a fixed order (partition-major, slot-minor),
the first that the new broker does not hold already, that leaves the
partition on ``min(RF, racks)`` distinct racks, and that keeps the new
broker under every capacity threshold and the replica ceiling. Where the
fullest old broker has none, the next fullest is asked, and so on. A move
keeps its slot, so a leader replica takes leadership with it (column 0
leads, as in the deployment).

It is a greedy and no search: where it finds a fill one exists; it stops at
the band's lower edge, where the served chain goes on to balance load, so
the program places more (``tests/test_scale_out.py`` states the factor).
"""

from __future__ import annotations

import math

import numpy as np

from .deployment import RESOURCES, Deployment
from .reference import broker_loads


def lower_edge(dep: Deployment, threshold: float = 1.1) -> int:
    """The lower edge of ``ReplicaDistributionGoal``'s band, in replicas a
    broker (module docstring)."""
    mean = dep.assignment.size / int(dep.alive.sum())
    return math.floor(mean / threshold)


def scale_out(dep: Deployment, guarantees: dict,
              threshold: float = 1.1) -> np.ndarray | None:
    """The assignment after the fill ([P, RF], column 0 still leads), or
    None where a new broker under the lower edge can take no replica."""
    new = sorted(int(b) for b in dep.operation_brokers)
    old = np.array([dep.alive[b] and b not in new
                    for b in range(dep.brokers)])
    want = lower_edge(dep, threshold)
    limit = dep.capacity * np.array([guarantees["capacity_threshold"][r]
                                     for r in RESOURCES])
    ceiling = int(guarantees["max_replicas_per_broker"])
    need_racks = min(dep.rf, dep.racks)
    assignment = dep.assignment.copy()
    loads = broker_loads(dep, assignment,
                         np.zeros(dep.partitions, dtype=np.int64))
    counts = np.bincount(assignment.ravel(), minlength=dep.brokers)
    while True:
        short = [b for b in new if counts[b] < min(want, ceiling)]
        if not short:
            return assignment
        dest = min(short, key=lambda b: (counts[b], b))
        for src in sorted(np.flatnonzero(old), key=lambda b: (-counts[b], b)):
            pick = _first_that_fits(dep, assignment, int(src), dest, loads,
                                    limit, need_racks)
            if pick is not None:
                break
        else:
            return None
        p, s = pick
        load = dep.leader_load[p] if s == 0 else dep.follower_load[p]
        loads[src] -= load
        loads[dest] += load
        counts[src] -= 1
        counts[dest] += 1
        assignment[p, s] = dest


def _first_that_fits(dep, assignment, src, dest, loads, limit, need_racks):
    """(partition, slot) of ``src``'s first replica that may go to
    ``dest``, or None."""
    rows, slots = np.nonzero(assignment == src)     # partition-major
    if not len(rows):
        return None
    held = assignment[rows]
    fits = ~(held == dest).any(axis=1)
    moved = held.copy()
    moved[np.arange(len(rows)), slots] = dest
    racks = np.sort(dep.broker_rack[moved], axis=1)
    fits &= 1 + (racks[:, 1:] != racks[:, :-1]).sum(axis=1) >= need_racks
    load = np.where((slots == 0)[:, None], dep.leader_load[rows],
                    dep.follower_load[rows])
    fits &= ((loads[dest] + load) <= limit).all(axis=1)
    first = np.flatnonzero(fits)
    if not len(first):
        return None
    return int(rows[first[0]]), int(slots[first[0]])
