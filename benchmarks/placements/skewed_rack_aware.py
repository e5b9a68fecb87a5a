"""A cluster that grew in steps and was never rebalanced, on racks that are
availability zones: old brokers full and new ones light, and rack-aware all
along, because Kafka's own assignor always is (KIP-36: one replica a rack
while racks last). It is ``skewed_random``'s draw held to that rule: each
replica on a broker drawn with the weight ``exp(-placement_skew * i /
(n - 1))`` of its place ``i`` among the ``n`` hosting brokers, column 0
leads, and a row is drawn again, whole, while two of its replicas share a
broker or a rack and ``min(RF, racks)`` distinct racks are possible. The
first ``n`` partitions lie on ``skewed_random``'s ring (so that every broker
hosts something) only where the ring's row is rack-distinct itself; the
others keep their draw.

Source: the layout is Kafka's ``broker.rack`` (KIP-36) with the Amazon MSK
Developer Guide's rule for it (brokers a multiple of the zones, three
zones, replication factor 3), so that with ``racks: 3`` every partition
has exactly ONE replica a zone and a replica can only ever move inside its
own zone. Departures: no source fixes how uneven a grown cluster is; the
skew is ``kafka-250b-25kp``'s (``placement_skew`` 2.0, the first broker
about 7x the last), and the zones are interleaved by broker id
(``deployment.build``: ``arange(B) % racks``), so each zone has the same
age profile.

``kafka_rack_aware`` with ``racks: 3`` is NOT this start: what Kafka's
assignor places is even in count from the first day, and a rebalance of it
is 15 rounds and 137 moved partitions at 99 / 9,900 (ISSUE 34's CPU
rehearsal): it measures the host. This is the three-AZ cluster to REPAIR.

Readings at the cell's size (252 brokers / 25,200 partitions, RF 3, 3
racks, ``instance_seed`` 0; ``reference.evaluate`` of an empty plan, my
numpy reading, PR 34): 0 rack violations, the worst broker at 1.6009 of a
capacity limit, 139 broker-resource pairs over a limit; the fullest broker
holds 696 replicas and the lightest 91 against a mean of 300 (242 and 29
leaders), and each zone holds 25,200.
"""

import numpy as np


def place(cfg, hosts, host_rack, rng):
    n, partitions = len(hosts), int(cfg["partitions"])
    rf = min(int(cfg["replication_factor"]), n)
    host_rack = np.asarray(host_rack)
    need = min(rf, len(np.unique(host_rack)))
    weights = np.exp(-float(cfg["placement_skew"]) * np.arange(n)
                     / max(1, n - 1))
    cdf = np.cumsum(weights)

    def draw(rows):
        return np.minimum(
            np.searchsorted(cdf, rng.random((rows, rf)) * cdf[-1]), n - 1)

    def unsound(rows):
        """Rows that hold a broker twice or lie on fewer than ``need``
        racks."""
        srt = np.sort(rows, axis=1)
        racks = np.sort(host_rack[rows], axis=1)
        distinct = 1 + (racks[:, 1:] != racks[:, :-1]).sum(axis=1)
        return (srt[:, 1:] == srt[:, :-1]).any(axis=1) | (distinct < need)

    replicas = draw(partitions)
    while True:     # re-draw only the rows that break the rule
        bad = unsound(replicas)
        if not bad.any():
            break
        replicas[bad] = draw(int(bad.sum()))
    ring = (np.arange(n)[:, None] + np.arange(rf)) % n
    sound = ~unsound(ring)
    replicas[:n][sound] = ring[sound]
    return replicas
