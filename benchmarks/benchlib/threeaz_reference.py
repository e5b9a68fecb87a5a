"""The plain reference of a three-AZ rebalance: a sequential per-zone
greedy in numpy.

It imports nothing of the program. Where every partition has exactly one
replica in each zone (``racks`` = RF, ``broker.rack`` = the zone), a replica
can only ever move to a broker of ITS OWN zone, and every zone carries the
same replica count and the same total NW_IN and DISK (followers replicate
both), so balance inside the zones is balance of the cluster and moves
inside a zone suffice to reach it. The greedy makes only such moves.

For each goal of ``BANDS`` that the chain names, in the chain's order:
while a broker is over the upper edge of the goal's band, the broker
furthest over it gives its largest replica that fits to the least-loaded
broker of the same zone that fits it; stop when no broker is over or none
can give. A replica FITS a broker that does not hold its partition, stays
under every capacity threshold and the replica ceiling with it, stays at
or under this band's upper edge and every earlier band's with it, while
its source stays at or over this band's lower edge and every earlier
band's without it. A move keeps its slot, so a leader replica takes
leadership with it (column 0 leads, as in the deployment). The destination
is in the source's zone, so the partition's racks do not change.

The bands (upstream's, SURVEY.md Appendix A.1; the thresholds are the
program's defaults, which are ``config/cruisecontrol.properties``'):

- a resource: ``mean * (1 -/+ (BALANCE_THRESHOLD - 1) * BALANCE_MARGIN)``
  of a broker's capacity, ``mean`` the cluster's load over its capacity
  (``ResourceDistributionGoal.initGoalState``, ``:235-278``;
  ``GoalUtils.computeResourceUtilizationBalanceThreshold``;
  ``BALANCE_MARGIN`` = 0.9, ``ResourceDistributionGoal.java:57``;
  ``*.balance.threshold`` = 1.1);
- the replica count: ``floor(mean / t)`` to ``ceil(mean * t)`` replicas,
  ``t`` = ``replica.count.balance.threshold`` = 1.1
  (``ReplicaDistributionAbstractGoal.initGoalState``).

``out_of_band`` counts, goal by goal, the alive brokers left outside the
band: it reads an assignment, the greedy's or the program's plan
(``applied``), so both are counted by the same code.

It is a greedy and no search. What it cannot do: it never moves
leadership alone (the served chain balances NW_OUT and CPU by leadership
first), never swaps, never fills a broker UNDER a band once none is over
it, and it knows nothing of the chain's other goals (topic and leader
counts, potential NW_OUT, leader bytes in), which the served chain has to
keep while it balances: where racks outnumber RF it still stays inside a
zone, which the rack rule does not ask for.
"""

from __future__ import annotations

import math

import numpy as np

from .deployment import CPU, DISK, NW_IN, NW_OUT, RESOURCES, Deployment
from .reference import broker_loads

BALANCE_THRESHOLD = 1.1     # *.balance.threshold, replica.count.balance.threshold
BALANCE_MARGIN = 0.9        # ResourceDistributionGoal.java:57
# The goals the greedy balances, by the short names a configuration's
# ``goals`` gives: the resource column, or None for the replica count.
BANDS = {"ReplicaDistributionGoal": None,
         "DiskUsageDistributionGoal": DISK,
         "NetworkInboundUsageDistributionGoal": NW_IN,
         "NetworkOutboundUsageDistributionGoal": NW_OUT,
         "CpuUsageDistributionGoal": CPU}
_ROUNDING = 1e-9            # of a band's edge: sums in another order


def band(dep: Deployment, loads: np.ndarray, counts: np.ndarray,
         goal: str) -> tuple[np.ndarray, float, float]:
    """([B] the goal's value a broker, lower edge, upper edge)."""
    alive = int(dep.alive.sum())
    r = BANDS[goal]
    if r is None:
        mean = counts[dep.alive].sum() / alive
        return (counts.astype(float), math.floor(mean / BALANCE_THRESHOLD),
                math.ceil(mean * BALANCE_THRESHOLD))
    mean = loads[dep.alive, r].sum() / (alive * dep.capacity[r])
    spread = (BALANCE_THRESHOLD - 1.0) * BALANCE_MARGIN
    return (loads[:, r], mean * (1.0 - spread) * dep.capacity[r],
            mean * (1.0 + spread) * dep.capacity[r])


def applied(dep: Deployment, proposals: list,
            ) -> tuple[np.ndarray, np.ndarray]:
    """(assignment [P, RF], leading column [P]) after a served body's
    moves; the body is taken as sound (``reference.evaluate`` says)."""
    assignment = dep.assignment.copy()
    leader_col = np.zeros(dep.partitions, dtype=np.int64)
    for p in proposals:
        tp = p["topicPartition"]
        i = dep.index_of(str(tp["topic"]), int(tp["partition"]))
        new = [int(b) for b in p["newReplicas"]]
        assignment[i] = new
        leader_col[i] = new.index(int(p["newLeader"]))
    return assignment, leader_col


def out_of_band(dep: Deployment, assignment: np.ndarray,
                leader_col: np.ndarray | None = None) -> dict:
    """{goal: alive brokers outside the goal's band} for every goal of
    ``BANDS`` (the bands are upstream's defaults, module docstring)."""
    if leader_col is None:
        leader_col = np.zeros(dep.partitions, dtype=np.int64)
    loads = broker_loads(dep, assignment, leader_col)
    counts = np.bincount(assignment.ravel(), minlength=dep.brokers)
    out = {}
    for goal in BANDS:
        value, lower, upper = band(dep, loads, counts, goal)
        slack = _ROUNDING * max(1.0, abs(upper))
        outside = (value > upper + slack) | (value < lower - slack)
        out[goal] = int((outside & dep.alive).sum())
    return out


def rebalance(dep: Deployment, guarantees: dict, goals) -> np.ndarray:
    """The assignment after the greedy ([P, RF], column 0 still leads).
    ``goals`` is the configuration's chain (short names); those of
    ``BANDS`` are balanced, in its order."""
    limit = dep.capacity * np.array([guarantees["capacity_threshold"][r]
                                     for r in RESOURCES])
    ceiling = int(guarantees["max_replicas_per_broker"])
    assignment = dep.assignment.copy()
    loads = broker_loads(dep, assignment,
                         np.zeros(dep.partitions, dtype=np.int64))
    counts = np.bincount(assignment.ravel(), minlength=dep.brokers)
    zones = [np.flatnonzero((dep.broker_rack == z) & dep.alive)
             for z in range(dep.racks)]
    chain = [g.rsplit(".", 1)[-1] for g in goals]
    done: list[str] = []
    for goal in (g for g in chain if g in BANDS):
        done.append(goal)
        while _one_move(dep, assignment, loads, counts, zones, done, limit,
                        ceiling):
            pass
    return assignment


def _one_move(dep, assignment, loads, counts, zones, done, limit, ceiling,
              ) -> bool:
    """One move for the goal ``done[-1]`` under the bands of ``done``;
    False where no broker is over its band or none can give."""
    bands = [band(dep, loads, counts, g) for g in done]
    value, _lower, upper = bands[-1]
    slack = _ROUNDING * max(1.0, abs(upper))
    over = np.flatnonzero((value > upper + slack) & dep.alive)
    for src in over[np.argsort(-value[over], kind="stable")]:
        rows, slots = np.nonzero(assignment == src)     # partition-major
        load = np.where((slots == 0)[:, None], dep.leader_load[rows],
                        dep.follower_load[rows])         # [n, 4]
        sizes = _sizes(load, done[-1])
        order = np.argsort(-sizes, kind="stable")
        order = order[sizes[order] > 0]
        if not len(order):
            continue
        rows, slots, load = rows[order], slots[order], load[order]
        zone = zones[int(dep.broker_rack[src])]
        zone = zone[zone != src]
        zone = zone[np.argsort(value[zone], kind="stable")]  # least first
        fits = ~(assignment[rows][:, :, None] == zone[None, None, :]) \
            .any(axis=1)                                     # [n, z]
        fits &= ((loads[zone][None] + load[:, None, :]) <= limit) \
            .all(axis=2)
        fits &= (counts[zone] + 1 <= ceiling)[None, :]
        for g, (val, lo, up) in zip(done, bands):
            size = _sizes(load, g)[:, None]
            eps = _ROUNDING * max(1.0, abs(up))
            fits &= val[zone][None, :] + size <= up + eps
            fits &= (val[src] - size >= lo - eps)
        can = np.flatnonzero(fits.any(axis=1))
        if not len(can):
            continue
        i = int(can[0])
        dest = int(zone[int(np.argmax(fits[i]))])
        p, s = int(rows[i]), int(slots[i])
        loads[src] -= load[i]
        loads[dest] += load[i]
        counts[src] -= 1
        counts[dest] += 1
        assignment[p, s] = dest
        return True
    return False


def _sizes(load: np.ndarray, goal: str) -> np.ndarray:
    """[n] what each replica weighs in ``goal``'s band."""
    r = BANDS[goal]
    return np.ones(len(load)) if r is None else load[:, r]
