"""Proposal extraction: diff of assignment arrays.

Reference parity: AnalyzerUtils.getDiff:47-130 + ExecutionProposal.java —
proposals are NOT accumulated during search; they are the diff between the
initial and final (replica list, leader) state, so transient intra-search
shuffles cost nothing (SURVEY.md §A.5). The tensor model gets this for free
by comparing assignment/leader arrays.
"""

from __future__ import annotations

import dataclasses

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


def _ordered_replicas(assignment_row: np.ndarray, leader_slot: int,
                      broker_ids: list[int]) -> tuple[tuple[int, ...], int]:
    """Replica broker ids with the leader first (ExecutionProposal
    convention), -1-padded slots dropped."""
    slots = [s for s, b in enumerate(assignment_row) if b >= 0]
    if not slots:
        return (), -1
    leader_b = int(assignment_row[leader_slot]) if 0 <= leader_slot < len(assignment_row) \
        and assignment_row[leader_slot] >= 0 else -1
    ordered = []
    if leader_b >= 0:
        ordered.append(leader_b)
    for s in slots:
        b = int(assignment_row[s])
        if b != leader_b:
            ordered.append(b)
    ids = tuple(broker_ids[b] for b in ordered)
    leader_id = broker_ids[leader_b] if leader_b >= 0 else -1
    return ids, leader_id


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


def compare_diff(fetched: FetchedDiff,
                 meta: ClusterMeta) -> list[ExecutionProposal]:
    """Set of ExecutionProposals for partitions whose replica set, order, or
    leader changed (AnalyzerUtils.getDiff), from the fetched arrays (span
    ``diff.compare``)."""
    from ..utils.tracing import TRACER

    a0, a1, l0, l1 = fetched.a0, fetched.a1, fetched.l0, fetched.l1
    disk_mb = fetched.disk_mb
    with TRACER.span("diff.compare"):
        changed = ((a0 != a1).any(axis=1) | (l0 != l1)) & fetched.mask
        proposals: list[ExecutionProposal] = []
        for p in np.nonzero(changed)[0]:
            old_reps, old_leader = _ordered_replicas(a0[p], int(l0[p]),
                                                     meta.broker_ids)
            new_reps, new_leader = _ordered_replicas(a1[p], int(l1[p]),
                                                     meta.broker_ids)
            if old_reps == new_reps and old_leader == new_leader:
                continue
            topic, pnum = meta.partition_index[p]
            proposals.append(ExecutionProposal(
                topic=topic, partition=pnum, old_leader=old_leader,
                old_replicas=old_reps, new_replicas=new_reps,
                new_leader=new_leader, data_to_move_mb=float(disk_mb[p])))
    return proposals


def diff_proposals(initial: ClusterTensors, final: ClusterTensors,
                   meta: ClusterMeta) -> list[ExecutionProposal]:
    """``compare_diff`` of ``fetch_diff``."""
    return compare_diff(fetch_diff(initial, final), meta)
