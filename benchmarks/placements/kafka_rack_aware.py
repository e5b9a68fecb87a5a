"""The cluster as Kafka itself would have placed it when its topics were
created: ``AdminUtils.assignReplicasToBrokersRackAware`` (Kafka's
``kafka.admin.AdminUtils``, KIP-36 "Rack aware replica assignment"), topic
by topic. A cluster that is scaled out started like this (rack-aware, even
in COUNT) and is uneven in LOAD, which the deployment draws afterwards.

The published algorithm, for one topic of ``p`` partitions on ``n`` brokers
of ``r`` racks:

- the rack-alternated broker list: racks in order, one broker of each in
  turn (each rack's brokers in id order) until every broker is listed;
- ``start`` and ``shift`` are drawn once for the topic, each below ``n``;
- partition ``j`` puts its first replica (the preferred leader) on
  ``list[(j + start) % n]``; ``shift`` grows by one whenever ``j`` is a
  multiple of ``n`` above 0;
- its further replicas try ``list[(first + 1 + (shift * r + k) % (n - 1))
  % n]`` for ``k`` = 0, 1, ... (``k`` runs on across the partition's
  replicas), skipping a broker while its rack already holds a replica and
  some rack holds none, or while it already holds one and some broker
  holds none.

Departures from it:

- racks are ordered by their index (Kafka sorts rack NAMES: the same order
  up to 10 racks named ``rack<i>``);
- ``start`` and ``shift`` come from the deployment's numpy generator, not
  from ``java.util.Random``;
- every topic is created once with all its partitions (``startPartitionId``
  0; no ``--alter`` that adds partitions later, no broker that joined
  between two topics' creations);
- a topic owns the rows ``i % topics == t`` as partition ``i // topics``
  (``Deployment.topic_partition``), so its partition count is the number of
  such rows;
- with ``n`` = 1 the formula divides by zero in Kafka too: refused here.
"""

import numpy as np


def rack_alternated(host_rack):
    """Indices into the hosting brokers, one of each rack in turn."""
    by_rack = [list(np.flatnonzero(host_rack == r))
               for r in np.unique(host_rack)]
    out = []
    while any(by_rack):
        out += [rack.pop(0) for rack in by_rack if rack]
    return out


def place(cfg, hosts, host_rack, rng):
    n, partitions = len(hosts), int(cfg["partitions"])
    topics = int(cfg["topics"])
    rf = min(int(cfg["replication_factor"]), n)
    if n < 2:
        raise ValueError("kafka_rack_aware needs two hosting brokers")
    order = rack_alternated(host_rack)
    rack_of = [int(host_rack[b]) for b in order]
    racks = len(set(rack_of))
    replicas = np.empty((partitions, rf), dtype=np.int64)
    for t in range(topics):
        start, shift = (int(v) for v in rng.integers(0, n, size=2))
        for j, row in enumerate(range(t, partitions, topics)):
            if j > 0 and j % n == 0:
                shift += 1
            first = (j + start) % n
            chosen, used_racks = [first], {rack_of[first]}
            k = 0
            while len(chosen) < rf:
                at = (first + 1 + (shift * racks + k) % (n - 1)) % n
                k += 1
                if (rack_of[at] not in used_racks
                        or len(used_racks) == racks) \
                        and (at not in chosen or len(chosen) == n):
                    chosen.append(at)
                    used_racks.add(rack_of[at])
            replicas[row] = [order[at] for at in chosen]
    return replicas
