"""The plain reference of a decommission: a sequential drain in numpy.

It imports nothing of the program. Given a deployment
(``deployment.py``), the brokers to remove and the guarantees of the
configuration's file, it places every replica that has to move, one at a
time in a fixed order (partition-major, slot-minor), on the eligible
broker with the lowest disk utilisation, and returns the final assignment,
or ``None`` where a replica has no eligible broker: the drain cannot
finish. Eligible means alive, not removed, not already holding the
partition, on a rack the partition's kept replicas do not use (while an
unused rack is left among the brokers that stay), and under every capacity
threshold once the replica's load is added.

A replica has to move if it sits on a removed broker (``must_move``: what
the drain itself forces, and what the program's plan is compared on), or
if it repeats the rack of an earlier kept replica of its partition: the
deployments are drawn without regard to racks, and the served chain repairs
those too, so the reference does, to be held to the same counts of
``reference.py`` with the limit 0. It is a greedy and no search: where it
finds a placement one exists; where it finds none the tests use it only on
cases that provably have none (too few brokers left for RF, or more load
than the brokers that stay may hold).
"""

from __future__ import annotations

import numpy as np

from .deployment import DISK, RESOURCES, Deployment
from .reference import broker_loads


def must_move(dep: Deployment, removed) -> set[tuple[int, int]]:
    """The (partition, slot) pairs the drain forces: replicas that start on
    a removed broker."""
    on = np.isin(dep.assignment, list(removed))
    return {(int(p), int(s)) for p, s in zip(*np.nonzero(on))}


def drain(dep: Deployment, removed, guarantees: dict) -> np.ndarray | None:
    """The assignment after the drain ([P, RF], column 0 still leads), or
    None where some replica has no eligible broker."""
    removed = set(int(b) for b in removed)
    stays = np.array([dep.alive[b] and b not in removed
                      for b in range(dep.brokers)])
    racks_left = len(set(dep.broker_rack[stays].tolist()))
    limit = dep.capacity * np.array([guarantees["capacity_threshold"][r]
                                     for r in RESOURCES])
    assignment = dep.assignment.copy()
    loads = broker_loads(dep, assignment,
                         np.zeros(dep.partitions, dtype=np.int64))
    for p in range(dep.partitions):
        kept_racks: set[int] = set()
        moving = []
        for s in range(dep.rf):
            b = int(assignment[p, s])
            rack = int(dep.broker_rack[b])
            if stays[b] and rack not in kept_racks:
                kept_racks.add(rack)
            else:
                moving.append(s)
        for s in moving:
            load = dep.leader_load[p] if s == 0 else dep.follower_load[p]
            eligible = stays.copy()
            eligible[assignment[p]] = False
            if len(kept_racks) < racks_left:
                eligible &= ~np.isin(dep.broker_rack, list(kept_racks))
            eligible &= ((loads + load) <= limit).all(axis=1)
            if not eligible.any():
                return None
            disk = np.where(eligible, loads[:, DISK], np.inf)
            dest = int(np.argmin(disk))
            loads[assignment[p, s]] -= load
            loads[dest] += load
            assignment[p, s] = dest
            kept_racks.add(int(dep.broker_rack[dest]))
    return assignment


def as_proposals(dep: Deployment, assignment: np.ndarray) -> list[dict]:
    """The moves from the deployment to ``assignment`` in the shape of a
    served body's ``proposals``, for ``reference.evaluate``."""
    out = []
    for p in np.nonzero((assignment != dep.assignment).any(axis=1))[0]:
        topic, part = dep.topic_partition(int(p))
        old, new = dep.assignment[p].tolist(), assignment[p].tolist()
        out.append({"topicPartition": {"topic": topic, "partition": part},
                    "oldLeader": old[0], "oldReplicas": old,
                    "newLeader": new[0], "newReplicas": new})
    return out
