"""Proposal extraction: diff of assignment arrays.

Reference parity: AnalyzerUtils.getDiff:47-130 + ExecutionProposal.java —
proposals are NOT accumulated during search; they are the diff between the
initial and final (replica list, leader) state, so transient intra-search
shuffles cost nothing (SURVEY.md §A.5). The tensor model gets this for free
by comparing assignment/leader arrays, and the comparison is one of arrays
end to end: ``compare_diff`` orders, maps and filters the changed rows in
numpy and returns them as ``ProposalColumns``, a
``Sequence[ExecutionProposal]`` that builds an object only for a reader
that asks for one (the executor; not the served dry-run body), and writes
itself as the served body's JSON text (``ProposalColumns.to_json``).
"""

from __future__ import annotations

import dataclasses
import json
from collections.abc import Iterator, Sequence
from operator import itemgetter

import numpy as np

from ..model.tensors import ClusterMeta, ClusterTensors


@dataclasses.dataclass(frozen=True)
class ExecutionProposal:
    """One partition's reassignment (ExecutionProposal.java:309LoC):
    broker ids (not indices), new replica order leader-first.

    A proposal may additionally (or only) carry an intra-broker JBOD leg:
    the replica on ``logdir_broker`` moves ``source_logdir`` →
    ``destination_logdir`` (ReplicaPlacementInfo logdir semantics; executed
    via alterReplicaLogDirs, Executor.java:1672)."""

    topic: str
    partition: int
    old_leader: int
    old_replicas: tuple[int, ...]
    new_replicas: tuple[int, ...]
    new_leader: int
    logdir_broker: int = -1
    source_logdir: str | None = None
    destination_logdir: str | None = None
    # Partition size (ExecutionProposal.dataToMoveInMB): what each new
    # replica must copy; feeds throttling decisions and the executor's
    # movement-rate alerting.
    data_to_move_mb: float = 0.0

    @property
    def is_leadership_only(self) -> bool:
        return set(self.old_replicas) == set(self.new_replicas) \
            and self.old_leader != self.new_leader

    @property
    def has_logdir_move(self) -> bool:
        return self.logdir_broker >= 0 and self.destination_logdir is not None

    @property
    def replicas_to_add(self) -> tuple[int, ...]:
        return tuple(sorted(set(self.new_replicas) - set(self.old_replicas)))

    @property
    def replicas_to_remove(self) -> tuple[int, ...]:
        return tuple(sorted(set(self.old_replicas) - set(self.new_replicas)))


@dataclasses.dataclass(frozen=True, eq=False)
class ProposalColumns(Sequence):
    """A plan as columns, one row a changed partition in ascending partition
    index: what ``compare_diff`` returns. It IS a
    ``Sequence[ExecutionProposal]``: indexing and iteration build the
    objects on demand (counter ``proposal_objects_materialized_total``), a
    slice is columns again. The readers of the served dry-run path
    (``count_leadership_only``, ``to_json``) read the columns and build
    none."""

    partition_index: Sequence[tuple[str, int]]  # ClusterMeta's, not copied
    rows: np.ndarray             # [n] partition indices
    old_replicas: np.ndarray     # [n, S] broker ids leader-first, -1 pads right
    new_replicas: np.ndarray     # [n, S]
    old_leader: np.ndarray       # [n] broker id, -1 for none
    new_leader: np.ndarray       # [n]
    data_to_move_mb: np.ndarray  # [n]

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, i):
        if not isinstance(i, slice):
            i = range(len(self))[i]
            return next(iter(self[i:i + 1]))
        # Every field but the first is a column.
        return dataclasses.replace(
            self, **{f.name: getattr(self, f.name)[i]
                     for f in dataclasses.fields(self)[1:]})

    def __iter__(self) -> Iterator[ExecutionProposal]:
        from ..utils.sensors import SENSORS
        SENSORS.count("proposal_objects_materialized", len(self))
        return iter([
            ExecutionProposal(
                topic=topic, partition=pnum, old_leader=old_leader,
                old_replicas=tuple(old), new_replicas=tuple(new),
                new_leader=new_leader, data_to_move_mb=mb)
            for ((topic, pnum), old_leader, old, new, new_leader), mb
            in zip(self.row_values(), self.data_to_move_mb.tolist())])

    def row_values(self) -> Iterator[tuple]:
        """((topic, partition), old leader, old replicas, new replicas, new
        leader) a row, as Python values; the replicas are fresh lists."""
        index = self.partition_index
        return zip([index[p] for p in self.rows.tolist()],
                   self.old_leader.tolist(), _unpadded(self.old_replicas),
                   _unpadded(self.new_replicas), self.new_leader.tolist())

    def to_json(self) -> bytes:
        """The plan as the served body's ``"proposals"`` array: exactly
        ``json.dumps`` of a dict a row, ``{"topicPartition": {"topic",
        "partition"}, "oldLeader", "oldReplicas", "newReplicas",
        "newLeader"}``, pads dropped, with ``separators=(",", ":")``, built
        from the columns with no Python object a row. Each row is laid out
        in one ``uint8`` matrix row, every field at its widest with 0
        bytes where a value is shorter; JSON text never holds a 0 byte
        (``ensure_ascii`` escapes control characters), so deleting them
        leaves the text."""
        n = len(self)
        if n == 0:
            return b"[]"
        names = [self.partition_index[p] for p in self.rows.tolist()]
        code = {t: i for i, t in enumerate(
            dict.fromkeys(map(itemgetter(0), names)))}
        topics = _ascii_rows([json.dumps(t).encode() for t in code])
        topic = np.fromiter(map(code.__getitem__, map(itemgetter(0), names)),
                            np.intp, n)
        partition = np.fromiter(map(itemgetter(1), names), np.int64, n)
        s = self.old_replicas.shape[1]
        brokers = np.concatenate(
            [self.old_leader[:, None], self.old_replicas, self.new_replicas,
             self.new_leader[:, None]], axis=1)
        digits = _decimal(brokers)
        replicas = digits[:, 1:-1]
        replicas[..., 0] = ord(",")          # the sign's byte: no -1 listed
        replicas[:, [0, s], 0] = 0           # nothing before a list's first
        replicas[brokers[:, 1:-1] < 0] = 0   # a pad writes nothing

        def text(b: bytes) -> np.ndarray:
            return np.broadcast_to(np.frombuffer(b, np.uint8), (n, len(b)))

        rows = np.concatenate([
            text(b'{"topicPartition":{"topic":'), topics[topic],
            text(b',"partition":'), _decimal(partition),
            text(b'},"oldLeader":'), digits[:, 0],
            text(b',"oldReplicas":['), replicas[:, :s].reshape(n, -1),
            text(b'],"newReplicas":['), replicas[:, s:].reshape(n, -1),
            text(b'],"newLeader":'), digits[:, -1], text(b'},')], axis=1)
        body = rows.tobytes().translate(None, b"\0")
        return b"".join((b"[", memoryview(body)[:-1], b"]"))


def _decimal(values: np.ndarray) -> np.ndarray:
    """``values`` (integers, any shape) as ASCII decimal, one row of bytes
    a value: ``-`` or 0 first, then the digits right-aligned, 0 bytes
    before the first."""
    magnitude = np.abs(values)
    top = int(magnitude.max(initial=0))
    width = len(str(top))
    magnitude = magnitude.astype(np.min_scalar_type(top))   # faster divides
    out = np.zeros(values.shape + (width + 1,), np.uint8)
    out[..., 0] = np.where(values < 0, ord("-"), 0)
    out[..., width] = magnitude % 10 + ord("0")
    for k in range(1, width):
        above = magnitude // 10 ** k
        out[..., width - k] = np.where(above > 0, above % 10 + ord("0"), 0)
    return out


def _ascii_rows(texts: list[bytes]) -> np.ndarray:
    """``[len(texts), widest]`` ``uint8``, each text left-aligned, 0 bytes
    after it."""
    out = np.zeros((len(texts), max(map(len, texts))), np.uint8)
    for i, t in enumerate(texts):
        out[i, :len(t)] = np.frombuffer(t, np.uint8)
    return out


def _unpadded(replicas: np.ndarray) -> list[list[int]]:
    """The rows of a right-padded ``[n, S]`` id array as lists, the -1 pads
    trimmed where a row has one."""
    lists = replicas.tolist()
    present = replicas >= 0
    if not present.all():
        lengths = present.sum(axis=1)
        for i in np.nonzero(lengths < replicas.shape[1])[0].tolist():
            del lists[i][lengths[i]:]
    return lists


def proposal_rows(proposals: Sequence[ExecutionProposal]) -> Iterator[tuple]:
    """``ProposalColumns.row_values`` of any plan: columns are read as
    columns, a plain list of objects through its attributes."""
    if isinstance(proposals, ProposalColumns):
        return proposals.row_values()
    return (((p.topic, p.partition), p.old_leader, list(p.old_replicas),
             list(p.new_replicas), p.new_leader) for p in proposals)


def count_leadership_only(proposals: Sequence[ExecutionProposal]) -> int:
    """Proposals with the same replica SET under another leader
    (``ExecutionProposal.is_leadership_only``); columns all rows at once."""
    if not isinstance(proposals, ProposalColumns):
        return sum(p.is_leadership_only for p in proposals)
    old, new = proposals.old_replicas, proposals.new_replicas
    both = old[:, :, None] == new[:, None, :]
    same_set = ((both.any(axis=2) | (old < 0)).all(axis=1)
                & (both.any(axis=1) | (new < 0)).all(axis=1))
    return int((same_set
                & (proposals.old_leader != proposals.new_leader)).sum())


def _leader_first(assignment: np.ndarray, leader_slot: np.ndarray,
                  broker_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Replica broker ids ``[n, S]`` with the leader first
    (ExecutionProposal convention) and the others in slot order, -1 slots
    dropped (the pads end up on the right), and the leader's id ``[n]``. A
    leader slot out of range or on a -1 slot gives leader -1 and plain slot
    order; a second slot on the leader's broker is dropped."""
    n, s = assignment.shape
    slot_ok = (leader_slot >= 0) & (leader_slot < s)
    leader = np.maximum(-1, np.where(
        slot_ok, assignment[np.arange(n), np.where(slot_ok, leader_slot, 0)],
        -1))
    at_leader_slot = (np.arange(s) == leader_slot[:, None]) \
        & (leader >= 0)[:, None]
    dropped = (assignment < 0) \
        | ((assignment == leader[:, None]) & ~at_leader_slot)
    order = np.argsort(np.where(at_leader_slot, 0, 1 + dropped), axis=1,
                       kind="stable")
    ordered = np.take_along_axis(np.where(dropped, -1, assignment), order,
                                 axis=1)
    return (np.where(ordered >= 0, broker_ids[ordered], -1),
            np.where(leader >= 0, broker_ids[leader], -1))


@dataclasses.dataclass(frozen=True)
class FetchedDiff:
    """What ``fetch_diff`` brought to the host: the initial and the final
    placement, and the little of the initial model that reading a plan off
    them takes."""

    a0: np.ndarray            # [P, S] initial assignment (broker indices)
    a1: np.ndarray            # [P, S] final assignment
    l0: np.ndarray            # [P] initial leader slot
    l1: np.ndarray            # [P] final leader slot
    mask: np.ndarray          # [P] bool real partitions
    disk_mb: np.ndarray       # [P] leader disk load
    broker_state: np.ndarray  # [B] int8 BrokerState codes of the initial


def fetch_diff(initial: ClusterTensors, final: ClusterTensors) -> FetchedDiff:
    """The one device read of the proposal diff (span ``diff.fetch``)."""
    from ..common.resources import Resource
    from ..utils.tracing import TRACER
    from ..utils.xla_telemetry import record_transfer

    with TRACER.span("diff.fetch"):
        fetched = FetchedDiff(
            a0=np.asarray(initial.assignment),
            a1=np.asarray(final.assignment),
            l0=np.asarray(initial.leader_slot),
            l1=np.asarray(final.leader_slot),
            mask=np.asarray(initial.partition_mask),
            disk_mb=np.asarray(initial.leader_load[:, int(Resource.DISK)]),
            broker_state=np.asarray(initial.broker_state))
        record_transfer(sum(x.nbytes for x in vars(fetched).values()),
                        direction="d2h", source="proposal_diff")
    return fetched


def compare_diff(fetched: FetchedDiff, meta: ClusterMeta) -> ProposalColumns:
    """The partitions whose replica set, order, or leader changed
    (AnalyzerUtils.getDiff), from the fetched arrays, as columns (span
    ``diff.compare``): a comparison of arrays end to end, no Python object
    a move. Rows outside ``partition_mask`` never appear; a changed row
    whose leader-first order and leader are what they were (the leader's
    broker changed slots with slot 0's, say) is no proposal."""
    from ..utils.tracing import TRACER

    with TRACER.span("diff.compare") as span:
        changed = np.nonzero(((fetched.a0 != fetched.a1).any(axis=1)
                              | (fetched.l0 != fetched.l1)) & fetched.mask)[0]
        broker_ids = np.asarray(meta.broker_ids, dtype=np.int64)
        old, old_leader = _leader_first(fetched.a0[changed],
                                        fetched.l0[changed], broker_ids)
        new, new_leader = _leader_first(fetched.a1[changed],
                                        fetched.l1[changed], broker_ids)
        differs = (old != new).any(axis=1) | (old_leader != new_leader)
        rows = changed[differs]
        span.set(changed_rows=len(changed), proposals=len(rows))
        return ProposalColumns(
            partition_index=meta.partition_index, rows=rows,
            old_replicas=old[differs], new_replicas=new[differs],
            old_leader=old_leader[differs], new_leader=new_leader[differs],
            data_to_move_mb=fetched.disk_mb[rows])


def diff_proposals(initial: ClusterTensors, final: ClusterTensors,
                   meta: ClusterMeta) -> ProposalColumns:
    """``compare_diff`` of ``fetch_diff``."""
    return compare_diff(fetch_diff(initial, final), meta)
