"""The placement of a file that names none: each replica on a broker drawn
with the weight ``exp(-placement_skew * i / (n - 1))`` of its place ``i``
among the ``n`` hosting brokers (``bench.py random_cluster
skew_to_first``), no broker twice in a row, and the first ``n`` partitions
on a ring so that every broker hosts something. It knows nothing of racks:
``kafka-100b-10kp`` starts with 3,154 partitions that break rack awareness
and its worst broker at 1.58x a capacity limit (``reference.evaluate`` of
an empty plan): the cluster a rebalance or a drain is asked to repair.

A placement rule is ``place(cfg, hosts, host_rack, rng) -> [P, RF]``:
indices into ``hosts`` (the brokers that host replicas when the cluster is
drawn, in id order; ``host_rack`` their racks), column 0 leads. ``rng`` is
the deployment's generator, seeded by ``instance_seed``: the loads are
drawn from it next, so a rule's draws are part of the cluster.
"""

import numpy as np


def place(cfg, hosts, host_rack, rng):
    n, partitions = len(hosts), int(cfg["partitions"])
    rf = min(int(cfg["replication_factor"]), n)
    weights = np.exp(-float(cfg["placement_skew"]) * np.arange(n)
                     / max(1, n - 1))
    cdf = np.cumsum(weights)

    def draw(rows):
        return np.minimum(
            np.searchsorted(cdf, rng.random((rows, rf)) * cdf[-1]), n - 1)

    replicas = draw(partitions)
    while True:     # re-draw only the rows that drew one broker twice
        srt = np.sort(replicas, axis=1)
        dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
        if not dup.any():
            break
        replicas[dup] = draw(int(dup.sum()))
    ring = (np.arange(n)[:, None] + np.arange(rf)) % n
    replicas[:n] = ring
    return replicas
