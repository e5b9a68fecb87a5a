"""The deployment a cell runs on, made from its configuration file and
``--seed`` in numpy alone. Nothing here imports the program: the same
arrays feed the program (through ``sut.py``) and the plain reference
(``reference.py``), so the reference takes nothing the program has made.

The configuration's ``instance_seed`` draws the cluster, and ``--seed``
leaves it alone: the solver's trajectory depends on how brokers and
partitions are numbered, so that even a seed that only renumbered the same
cluster moved the served score between 78.4 and 88.7 and the seconds a
proposal by 8 % (PERF.md, PR 24). A seed that changes the work cannot hold
a bound, so the seed orders the traffic (the reads' arrivals and which of
them are checked) and nothing else.

Recipe (copied from ``chip_smoke.build_cluster`` / ``capacities`` and
``SyntheticSampler``; the originals are listed in PERF.md for deletion):
where the replicas start is the configuration's ``placement`` rule
(``placements/<name>.py``, found by name as a metric's reader is;
``skewed_random`` where the file names none), partition load a uniform
draw raised to ``load_skew``, homogeneous capacity putting the
cluster-average utilisation of every resource at ``target_utilization``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

import numpy as np

# Resource columns of every load / capacity array here.
CPU, NW_IN, NW_OUT, DISK = 0, 1, 2, 3
RESOURCES = ("cpu", "nw_in", "nw_out", "disk")

# Cruise Control's static CPU attribution (ModelParameters.java:23-31):
# a follower's CPU is the follower-bytes-in share of its leader's.
_CPU_LEADER_IN, _CPU_LEADER_OUT, _CPU_FOLLOWER_IN = 0.7, 0.15, 0.15

OPERATIONS = ("proposals", "rebalance", "add_broker", "remove_broker")

# The one place the harness takes its directories from: every file found
# by name is sought under here, when it is sought (a test points it at a
# directory of its own).
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def path_of(*parts: str) -> str:
    return os.path.join(HERE, *parts)


def load_json(kind: str, name: str) -> dict:
    """``benchmarks/<kind>/<name>.json``: configurations, traffic mixes and
    metrics are found by the name ``BENCHMARK.json`` gives."""
    with open(path_of(kind, f"{name}.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, run anew: a metric's reader, a
    placement rule or an operation's rule, found by the name a file gives."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name),
        path_of(kind, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True)
class Deployment:
    brokers: int
    racks: int
    topics: int
    rf: int
    assignment: np.ndarray      # [P, RF] broker ids, column 0 leads
    broker_rack: np.ndarray     # [B] rack index
    alive: np.ndarray           # [B] bool
    leader_load: np.ndarray     # [P, 4] float64
    follower_load: np.ndarray   # [P, 4] float64
    capacity: np.ndarray        # [4] per broker
    operation: str
    operation_brokers: tuple[int, ...]

    @property
    def partitions(self) -> int:
        return self.assignment.shape[0]

    def topic_partition(self, i: int) -> tuple[str, int]:
        return f"topic{i % self.topics}", i // self.topics

    def index_of(self, topic: str, partition: int) -> int:
        """Row of ``(topic, partition)``, or -1 for a name the deployment
        does not have."""
        if not topic.startswith("topic") or not topic[5:].isdigit():
            return -1
        t = int(topic[5:])
        i = partition * self.topics + t
        if t >= self.topics or partition < 0 or i >= self.partitions:
            return -1
        return i


def build(cfg: dict) -> Deployment:
    brokers, partitions = int(cfg["brokers"]), int(cfg["partitions"])
    racks, topics = int(cfg["racks"]), int(cfg["topics"])
    rf = min(int(cfg["replication_factor"]), brokers)
    operation = cfg.get("operation", "proposals")
    if operation not in OPERATIONS:
        raise ValueError(f"operation {operation!r}: one of {OPERATIONS}")
    op_brokers = tuple(int(b) for b in cfg.get("operation_brokers", ()))
    if any(not 0 <= b < brokers for b in op_brokers):
        raise ValueError("operation_brokers outside 0..brokers-1")
    # add_broker: the named brokers are new, so they host nothing yet.
    hosts = np.array([b for b in range(brokers)
                      if not (operation == "add_broker" and b in op_brokers)])
    if partitions < len(hosts) or len(hosts) < rf:
        raise ValueError("need at least one partition per hosting broker "
                         "and RF hosting brokers")
    rng = np.random.default_rng(int(cfg.get("instance_seed", 0)))
    broker_rack = np.arange(brokers) % racks
    rule = cfg.get("placement", "skewed_random")
    replicas = np.asarray(load_module("placements", rule).place(
        cfg, hosts, broker_rack[hosts], rng))
    if replicas.shape != (partitions, rf):
        raise ValueError(f"placement {rule!r} gave {replicas.shape}, not "
                         f"{(partitions, rf)}")
    assignment = hosts[replicas]

    h = rng.random(partitions) ** float(cfg["load_skew"])
    bytes_in = 50.0 + 950.0 * h
    bytes_out = 2.0 * bytes_in
    leader = np.zeros((partitions, 4))
    leader[:, CPU] = 2e-4 * bytes_in
    leader[:, NW_IN] = bytes_in
    leader[:, NW_OUT] = bytes_out
    leader[:, DISK] = 10_000.0 * h + 100.0
    follower = leader.copy()
    follower[:, NW_OUT] = 0.0
    follower[:, CPU] = leader[:, CPU] * (_CPU_FOLLOWER_IN * bytes_in) / (
        _CPU_LEADER_IN * bytes_in + _CPU_LEADER_OUT * bytes_out)

    # Followers replicate NW_IN and DISK; NW_OUT is leader-only; CPU takes
    # the generous all-replicas bound (chip_smoke.capacities).
    per_broker = 1.0 / (brokers * float(cfg["target_utilization"]))
    total = leader.sum(axis=0)
    capacity = np.array([rf * total[CPU], rf * total[NW_IN], total[NW_OUT],
                         rf * total[DISK]]) * per_broker
    return Deployment(
        brokers=brokers, racks=racks, topics=topics, rf=rf,
        assignment=assignment.astype(np.int64),
        broker_rack=broker_rack,
        alive=np.ones(brokers, dtype=bool),
        leader_load=leader, follower_load=follower, capacity=capacity,
        operation=operation, operation_brokers=op_brokers)
