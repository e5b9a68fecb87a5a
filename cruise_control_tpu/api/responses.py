"""Response writers.

Reference parity: servlet/response/ (ResponseUtils version envelope,
BrokerStats for LOAD, PartitionLoadState for PARTITION_LOAD,
ClusterBrokerState for KAFKA_CLUSTER_STATE, OptimizationResult for
proposal-bearing endpoints). All JSON; the reference's plaintext variants
are served by the same dicts pretty-printed.
"""

from __future__ import annotations

import json
import threading

import numpy as np

from ..analyzer.optimizer import OptimizerResult
from ..analyzer.proposals import ProposalColumns, proposal_rows
from ..common.resources import Resource
from ..executor.admin import AdminBackend
from ..facade import OperationResult
from ..model.tensors import (
    ClusterMeta, ClusterTensors, broker_leader_counts, broker_load,
    broker_replica_counts, leader_bytes_in, potential_nw_out, replica_load,
)
from ..utils.sensors import SENSORS


def _num_cores(cpu_capacity_pct: float) -> int:
    """NumCore from the CPU capacity column. The reference carries an
    explicit core count from its BrokerCapacityConfigResolver; this model
    expresses CPU capacity in percent-of-machine (100.0 = the whole
    broker), so cores are DERIVED as capacity/100 — see docs/DESIGN.md
    ("LOAD response wire-format notes"). Zero capacity = zero cores (the
    floor of 1 applies only to brokers with SOME capacity, so dead-weight
    rows cannot inflate a mixed host's total)."""
    if cpu_capacity_pct <= 0:
        return 0
    return max(1, int(round(cpu_capacity_pct / 100.0)))

JSON_VERSION = 1
# Bodies are written compact, as upstream's Gson writes them; any
# ``indent`` takes CPython off its C encoder (docs/DESIGN.md).
_COMPACT = (",", ":")


def envelope(payload: dict) -> dict:
    return {"version": JSON_VERSION, **payload}


def broker_capacities(admin, capacity_resolver) -> dict:
    """LOAD?capacity_only=true body: per-broker capacities straight from
    the capacity config — no metric model required (ParameterUtils
    capacityOnly excludes the time/model params)."""
    rows = []
    for bid in sorted(admin.alive_brokers()):
        caps = capacity_resolver.capacity_for(bid)
        rows.append({
            "Broker": bid,
            "DiskMB": round(float(caps[Resource.DISK]), 3),
            "CpuPct": round(float(caps[Resource.CPU]), 3),
            "NwInRate": round(float(caps[Resource.NW_IN]), 3),
            "NwOutRate": round(float(caps[Resource.NW_OUT]), 3),
            "DiskCapacityByLogdir":
                capacity_resolver.disk_capacity_by_logdir(bid),
            "Estimated": bool(getattr(capacity_resolver, "is_estimated",
                                      lambda _b: False)(bid)),
        })
    # capacity_only bypasses the model entirely (admin + capacity config
    # only), and the admin surface carries no host topology — host rows
    # exist on the model-backed LOAD path (broker_stats below).
    return envelope({"brokers": rows, "hosts": []})


def _host_name(meta: ClusterMeta, h: int) -> str:
    if 0 <= h < len(meta.host_names):
        return meta.host_names[h]
    return f"host-{h}"  # builder predates host topology / fixture default


def _host_rows(state: ClusterTensors, meta: ClusterMeta, loads, caps,
               replicas, leaders, pnw, lead_in, mask) -> list[dict]:
    """Per-host aggregate rows (BrokerStats.java host section /
    model/Host.java:275): every stat summed over the host's brokers,
    utilization pct over the host's summed capacity."""
    hosts = np.asarray(state.host)[mask]
    uniq, inv = np.unique(hosts, return_inverse=True)
    n = len(uniq)

    def by_host(col):
        return np.bincount(inv, weights=col, minlength=n)

    load = {r: by_host(loads[mask, int(r)]) for r in
            (Resource.DISK, Resource.CPU, Resource.NW_IN, Resource.NW_OUT)}
    disk_cap = by_host(caps[mask, int(Resource.DISK)])
    nw_in_cap = by_host(caps[mask, int(Resource.NW_IN)])
    nw_out_cap = by_host(caps[mask, int(Resource.NW_OUT)])
    h_pnw = by_host(np.asarray(pnw, dtype=np.float64)[mask])
    h_lead_in = by_host(np.asarray(lead_in, dtype=np.float64)[mask])
    h_replicas = by_host(np.asarray(replicas, dtype=np.float64)[mask])
    h_leaders = by_host(np.asarray(leaders, dtype=np.float64)[mask])
    with np.errstate(divide="ignore", invalid="ignore"):
        disk_pct = np.where(disk_cap > 0,
                            100.0 * load[Resource.DISK] / disk_cap, 0.0)
    return [{
        "Host": _host_name(meta, int(uniq[i])),
        "DiskMB": round(float(load[Resource.DISK][i]), 3),
        "DiskPct": round(float(disk_pct[i]), 3),
        "CpuPct": round(float(load[Resource.CPU][i]), 3),
        "LeaderNwInRate": round(float(h_lead_in[i]), 3),
        "FollowerNwInRate": round(
            float(load[Resource.NW_IN][i] - h_lead_in[i]), 3),
        "NwOutRate": round(float(load[Resource.NW_OUT][i]), 3),
        "PnwOutRate": round(float(h_pnw[i]), 3),
        "Replicas": int(h_replicas[i]),
        "Leaders": int(h_leaders[i]),
        "DiskCapacityMB": round(float(disk_cap[i]), 3),
        "NetworkInCapacity": round(float(nw_in_cap[i]), 3),
        "NetworkOutCapacity": round(float(nw_out_cap[i]), 3),
        "NumCore": sum(_num_cores(float(c))
                       for c in caps[mask, int(Resource.CPU)][inv == i]),
    } for i in range(n)]


def broker_stats(state: ClusterTensors, meta: ClusterMeta,
                 disk_info=None) -> dict:
    """LOAD endpoint body (response/stats/BrokerStats.java).
    ``disk_info`` = (logdirs_by_broker, capacity_resolver) adds per-logdir
    capacity + liveness per broker (populate_disk_info=true)."""
    from ..serving.journey import current_journey
    jny = current_journey()
    t0 = jny.now()
    loads = np.asarray(broker_load(state), dtype=np.float64)       # [B, R]
    caps = np.asarray(state.capacity, dtype=np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        pct = np.where(caps > 0, 100.0 * loads / caps, 0.0)
    replicas = np.asarray(broker_replica_counts(state))
    leaders = np.asarray(broker_leader_counts(state))
    pnw = np.asarray(potential_nw_out(state))
    lead_in = np.asarray(leader_bytes_in(state), dtype=np.float64)
    states = np.asarray(state.broker_state)
    racks = np.asarray(state.rack)
    hosts = np.asarray(state.host)
    mask = np.asarray(state.broker_mask)
    from ..common.broker_state import BrokerState
    rows = []
    for i, bid in enumerate(meta.broker_ids):
        if not mask[i]:
            continue
        row = {
            "Broker": bid,
            "BrokerState": BrokerState(int(states[i])).name,
            "Rack": meta.rack_names[int(racks[i])],
            "Host": _host_name(meta, int(hosts[i])),
            "DiskMB": round(float(loads[i, Resource.DISK]), 3),
            "DiskPct": round(float(pct[i, Resource.DISK]), 3),
            "CpuPct": round(float(loads[i, Resource.CPU]), 3),
            # Reference wire format (BrokerStats.java): NW_IN is reported
            # split by replica role, not combined.
            "LeaderNwInRate": round(float(lead_in[i]), 3),
            "FollowerNwInRate": round(
                float(loads[i, Resource.NW_IN] - lead_in[i]), 3),
            "NwOutRate": round(float(loads[i, Resource.NW_OUT]), 3),
            "PnwOutRate": round(float(pnw[i]), 3),
            "Replicas": int(replicas[i]),
            "Leaders": int(leaders[i]),
            "DiskCapacityMB": round(float(caps[i, Resource.DISK]), 3),
            "NetworkInCapacity": round(float(caps[i, Resource.NW_IN]), 3),
            "NetworkOutCapacity": round(float(caps[i, Resource.NW_OUT]), 3),
            "NumCore": _num_cores(float(caps[i, Resource.CPU])),
        }
        if disk_info is not None:
            logdirs_by_broker, resolver = disk_info
            caps_by_dir = resolver.disk_capacity_by_logdir(bid) or {}
            alive_dirs = logdirs_by_broker.get(bid, {})
            row["DiskState"] = {
                d: {"DiskMB": round(float(caps_by_dir.get(d, 0.0)), 3),
                    "alive": bool(alive)}
                for d, alive in sorted(alive_dirs.items())} or {
                d: {"DiskMB": round(float(c), 3), "alive": True}
                for d, c in sorted(caps_by_dir.items())}
        rows.append(row)
    body = envelope({"brokers": rows,
                     "hosts": _host_rows(state, meta, loads, caps, replicas,
                                         leaders, pnw, lead_in, mask)})
    jny.add("render", jny.now() - t0, brokers=len(rows))
    return body


def partition_load(state: ClusterTensors, meta: ClusterMeta,
                   resource: str = "DISK", entries: int | None = None,
                   topic_rx: str | None = None,
                   partition_range: str | None = None,
                   brokerids: tuple[int, ...] = ()) -> dict:
    """PARTITION_LOAD body: partitions sorted by the requested resource,
    heaviest first (PartitionLoadState.java). ``topic_rx`` is a topic
    regex, ``partition_range`` a partition id or "start-end" range, and
    ``brokerids`` keeps only partitions with a replica on one of the
    brokers (ParameterUtils TOPIC/PARTITION/BROKER_ID params)."""
    from ..serving.journey import current_journey
    jny = current_journey()
    t0 = jny.now()
    aliases = {"NETWORK_INBOUND": "NW_IN", "NETWORK_OUTBOUND": "NW_OUT"}
    name = resource.upper()
    try:
        res = Resource[aliases.get(name, name)]
    except KeyError:
        from .parameters import ParameterParseError
        raise ParameterParseError(f"unknown resource {resource!r}")
    from .parameters import ParameterParseError
    rx = None
    if topic_rx:
        import re
        try:
            rx = re.compile(topic_rx)
        except re.error as e:
            raise ParameterParseError(f"bad topic regex {topic_rx!r}: {e}")
    p_lo = p_hi = None
    if partition_range:
        lo, sep, hi = partition_range.partition("-")
        try:
            p_lo = int(lo)
            p_hi = int(hi) if sep else p_lo
        except ValueError:
            raise ParameterParseError(
                f"bad partition range {partition_range!r} (want N or N-M)")
    want_brokers = {int(b) for b in brokerids}
    id_of = {bid: i for i, bid in enumerate(meta.broker_ids)}
    want_idx = {id_of[b] for b in want_brokers if b in id_of}
    per_slot = np.asarray(replica_load(state))          # [P, S, R]
    mask = np.asarray(state.partition_mask)
    leader_loads = np.asarray(state.leader_load)
    order = np.argsort(-leader_loads[:, res] * mask)
    assignment = np.asarray(state.assignment)
    leader_slot = np.asarray(state.leader_slot)
    records = []
    for p in order:
        if entries is not None and len(records) >= entries:
            break
        if not mask[p]:
            continue
        topic, part = meta.partition_index[int(p)]
        if rx is not None and not rx.fullmatch(topic):
            continue
        if p_lo is not None and not (p_lo <= part <= p_hi):
            continue
        if want_brokers and not any(int(b) in want_idx for b in assignment[p]
                                    if b >= 0):
            # Guard on the REQUESTED set: ids that don't resolve to model
            # brokers must filter everything out, not disable the filter.
            continue
        ls = int(leader_slot[p])
        leader_b = int(assignment[p, ls]) if 0 <= ls < assignment.shape[1] else -1
        followers = [int(meta.broker_ids[b]) for s, b in enumerate(assignment[p])
                     if b >= 0 and s != ls]
        records.append({
            "topic": topic, "partition": part,
            "leader": meta.broker_ids[leader_b] if leader_b >= 0 else -1,
            "followers": followers,
            "cpu": round(float(per_slot[p, :, Resource.CPU].sum()), 5),
            "disk": round(float(per_slot[p, :, Resource.DISK].sum()), 3),
            "networkInbound": round(float(per_slot[p, :, Resource.NW_IN].sum()), 3),
            "networkOutbound": round(float(per_slot[p, :, Resource.NW_OUT].sum()), 3),
        })
    body = envelope({"records": records})
    jny.add("render", jny.now() - t0, records=len(records))
    return body


def kafka_cluster_state(admin: AdminBackend, topic_filter: str = "") -> dict:
    """KAFKA_CLUSTER_STATE body (response/ClusterBrokerState.java): replica
    counts per broker + per-partition detail with URP/offline accounting."""
    parts = admin.describe_partitions()
    alive = admin.alive_brokers()
    replica_count: dict[int, int] = {}
    leader_count: dict[int, int] = {}
    out_of_sync: dict[str, list[int]] = {}
    offline: dict[str, list[int]] = {}
    partitions = []
    for (topic, p), st in sorted(parts.items()):
        if topic_filter and topic != topic_filter:
            continue
        for b in st.replicas:
            replica_count[b] = replica_count.get(b, 0) + 1
        if st.leader >= 0:
            leader_count[st.leader] = leader_count.get(st.leader, 0) + 1
        osr = [b for b in st.replicas if b not in st.isr]
        off = [b for b in st.replicas if b not in alive]
        key = f"{topic}-{p}"
        if osr:
            out_of_sync[key] = osr
        if off:
            offline[key] = off
        partitions.append({"topic": topic, "partition": p,
                           "leader": st.leader, "replicas": list(st.replicas),
                           "in-sync": list(st.isr), "out-of-sync": osr,
                           "offline": off})
    return envelope({
        "KafkaBrokerState": {
            "ReplicaCountByBrokerId": {str(b): c for b, c in sorted(replica_count.items())},
            "LeaderCountByBrokerId": {str(b): c for b, c in sorted(leader_count.items())},
            "OfflineReplicaCountByBrokerId": {},
            "IsController": {},
        },
        "KafkaPartitionState": {
            "offline": offline, "urp": out_of_sync,
            "with-offline-replicas": sorted(offline),
            "under-min-isr": [],
        },
        "partitions": partitions,
    })


_NON_VERBOSE_PROPOSAL_CAP = 1000


def _stats_dict(stats) -> dict:
    """ClusterModelStats → JSON (response/stats semantics)."""
    import numpy as np

    from ..common.resources import Resource
    util = {}
    for r in Resource:
        util[r.name] = {
            "avg": float(np.asarray(stats.utilization_avg)[int(r)]),
            "max": float(np.asarray(stats.utilization_max)[int(r)]),
            "min": float(np.asarray(stats.utilization_min)[int(r)]),
            "stdDev": float(np.asarray(stats.utilization_std)[int(r)]),
        }

    def four(a):
        avg, mx, mn, std = (float(x) for x in np.asarray(a))
        return {"avg": avg, "max": mx, "min": mn, "stdDev": std}

    return {"utilization": util,
            "potentialNwOut": four(stats.potential_nw_out_stats),
            "replicaCount": four(stats.replica_count_stats),
            "leaderCount": four(stats.leader_count_stats),
            "numAliveBrokers": int(stats.num_alive_brokers)}


def optimization_result(op: OperationResult, verbose: bool = False) -> dict:
    """Proposal-bearing POST/GET body (response/OptimizationResult.java:191).
    ``verbose`` lifts the proposal-list cap and adds before/after cluster
    stats (ParameterUtils verbose semantics)."""
    from ..serving.journey import current_journey
    jny = current_journey()
    body: dict = {"operation": op.operation, "dryrun": op.dryrun,
                  "executed": op.executed}
    with jny.seg("render"):
        r: OptimizerResult | None = op.optimizer_result
        if r is not None:
            s = r.summary()
            body["summary"] = s
            body["goalSummary"] = [
                {"goal": g.name,
                 "status": "FIXED" if g.succeeded else "VIOLATED",
                 "optimizationTimeMs": round(1000 * g.duration_s, 1)}
                for g in r.goal_results]
            if verbose:
                body["loadBeforeOptimization"] = _stats_dict(r.stats_before)
                body["loadAfterOptimization"] = _stats_dict(r.stats_after)
    with jny.seg("proposal_diff") as seg:
        proposals = op.proposals
        body["numProposals"] = len(proposals)
        if not verbose and len(proposals) > _NON_VERBOSE_PROPOSAL_CAP:
            body["proposalsTruncated"] = True
            proposals = proposals[:_NON_VERBOSE_PROPOSAL_CAP]
        if isinstance(proposals, ProposalColumns):
            body["proposals"] = ProposalsJson(proposals.to_json(),
                                              len(proposals))
            form = "text"
        else:
            body["proposals"] = proposal_dicts(proposals)
            form = "objects"
        SENSORS.count("proposal_rows_encoded", len(proposals), {"form": form})
        seg.set(numProposals=len(proposals), encoded=form)
    body.update(op.extra)
    return envelope(body)


def proposal_dicts(proposals) -> list[dict]:
    """The body's ``"proposals"``, a dict a move: what a plain list of
    ``ExecutionProposal``s is written from (and, read from columns, what
    ``ProposalColumns.to_json`` writes the text of)."""
    return [
        {"topicPartition": {"topic": topic, "partition": partition},
         "oldLeader": old_leader,
         "oldReplicas": old_replicas,
         "newReplicas": new_replicas,
         "newLeader": new_leader}
        for (topic, partition), old_leader, old_replicas, new_replicas,
        new_leader in proposal_rows(proposals)]


class ProposalsJson(list):
    """``body["proposals"]`` of a plan in columns: its JSON text
    (``ProposalColumns.to_json``), which ``body_bytes`` writes as it is,
    and for every reader in the process the list of dicts that the text
    parses to. ``len`` answers from the row count; any other read parses
    the text once, fills the list and counts the rows in
    ``proposal_rows_encoded_total{form="objects"}``. It is a ``list``, so
    ``json.dumps`` (which iterates a list's subclass), ``_as_text`` and
    ``==`` against a plain list read it as one. Read it; do not edit it:
    the text is what is served."""

    __slots__ = ("text", "_unread")

    def __init__(self, text: bytes, rows: int):
        super().__init__()
        self.text = text
        self._unread = rows       # None once the list holds the dicts

    def _read(self) -> "ProposalsJson":
        if self._unread is not None:
            with _READING:        # a stored body has readers on many threads
                if self._unread is not None:
                    rows = json.loads(self.text)
                    SENSORS.count("proposal_rows_encoded", len(rows),
                                  {"form": "objects"})
                    list.__setitem__(self, slice(None), rows)
                    self._unread = None
        return self

    def __len__(self) -> int:
        return list.__len__(self) if self._unread is None else self._unread


_READING = threading.Lock()


def _reading(method):
    def read(self, *args):
        return method(self._read(), *args)
    read.__name__ = method.__name__
    return read


for _method in ("__iter__", "__reversed__", "__getitem__", "__contains__",
                "__eq__", "__ne__", "__repr__", "__add__", "__mul__",
                "copy", "index", "count"):
    setattr(ProposalsJson, _method, _reading(getattr(list, _method)))


def body_bytes(body) -> bytes:
    """``json.dumps(body, separators=(",", ":")).encode()``: a
    ``ProposalsJson`` under ``body["proposals"]`` is written as its text,
    in its place among the keys, and the rest of the body is dumped as
    ever (a dict's text is its items' joined, so it splits at a key)."""
    if not (isinstance(body, dict)
            and isinstance(body.get("proposals"), ProposalsJson)):
        return json.dumps(body, separators=_COMPACT).encode()
    keys = list(body)
    at = keys.index("proposals")
    head = json.dumps({k: body[k] for k in keys[:at]},
                      separators=_COMPACT)[:-1]
    tail = json.dumps({k: body[k] for k in keys[at + 1:]},
                      separators=_COMPACT)[1:]
    return b"".join((head.encode(), b',"proposals":' if at else
                     b'"proposals":', body["proposals"].text,
                     b"," if tail != "}" else b"", tail.encode()))
