"""The plan in columns (`analyzer/proposals.py:ProposalColumns`) against
the loop it replaced, which lives on here as the oracle: one Python object
a move, `_ordered_replicas` twice a move. Equal results, row for row."""

import dataclasses
import types

import numpy as np
import pytest

from cruise_control_tpu.analyzer.proposals import (
    ExecutionProposal, FetchedDiff, ProposalColumns, compare_diff,
    count_leadership_only,
)
from cruise_control_tpu.api import responses
from cruise_control_tpu.facade import OperationResult
from cruise_control_tpu.utils.sensors import SENSORS
from cruise_control_tpu.utils.tracing import TRACER

from .test_facade import _cruise_control, _partitions
from .test_tracing import _attrs


# ---- the oracle: the served path's loop until PR 33 ------------------------

def _ordered_replicas(assignment_row, leader_slot, broker_ids):
    """Replica broker ids with the leader first (ExecutionProposal
    convention), -1-padded slots dropped."""
    slots = [s for s, b in enumerate(assignment_row) if b >= 0]
    if not slots:
        return (), -1
    leader_b = int(assignment_row[leader_slot]) \
        if 0 <= leader_slot < len(assignment_row) \
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


def compare_diff_loop(fetched, meta):
    a0, a1, l0, l1 = fetched.a0, fetched.a1, fetched.l0, fetched.l1
    changed = ((a0 != a1).any(axis=1) | (l0 != l1)) & fetched.mask
    proposals = []
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
            new_leader=new_leader,
            data_to_move_mb=float(fetched.disk_mb[p])))
    return proposals


# ---- drawn arrays ----------------------------------------------------------

def _meta(partitions, broker_ids):
    return types.SimpleNamespace(
        broker_ids=list(broker_ids),
        partition_index=[(f"t{p // 7}", p % 7) for p in range(partitions)])


def _fetched(a0, a1, l0, l1, mask=None, brokers=16, seed=0):
    p = len(a0)
    disk = np.random.default_rng(seed).random(p).astype(np.float32) * 1e4
    return FetchedDiff(
        a0=np.asarray(a0, np.int32), a1=np.asarray(a1, np.int32),
        l0=np.asarray(l0, np.int32), l1=np.asarray(l1, np.int32),
        mask=np.ones(p, bool) if mask is None else np.asarray(mask, bool),
        disk_mb=disk, broker_state=np.zeros(brokers, np.int8))


def _placement(rng, partitions, brokers, s):
    return np.stack([rng.permutation(brokers)[:s]
                     for _ in range(partitions)]).astype(np.int32)


def _moved(rng, a0, l0, brokers, share=0.6):
    """Some rows get another broker in one slot, some another leader slot,
    some both."""
    p, s = a0.shape
    a1, l1 = a0.copy(), l0.copy()
    rows = rng.choice(p, int(share * p), replace=False)
    a1[rows, rng.integers(0, s, len(rows))] = rng.integers(0, brokers,
                                                           len(rows))
    rows = rng.choice(p, int(share * p / 2), replace=False)
    l1[rows] = rng.integers(0, s, len(rows))
    return a1, l1


def _rf3_dense(rng, masked=False, broker_ids=range(16)):
    a0 = _placement(rng, 400, 16, 3)
    l0 = rng.integers(0, 3, 400)
    a1, l1 = _moved(rng, a0, l0, 16)
    mask = rng.random(400) < 0.5 if masked else None
    return _fetched(a0, a1, l0, l1, mask), _meta(400, broker_ids)


def _mixed_rf_pads(rng):
    """RF 1-4 in four slots, the pads in left, middle and right slots."""
    a0 = _placement(rng, 300, 16, 4)
    l0 = rng.integers(0, 4, 300)
    a1, l1 = _moved(rng, a0, l0, 16)
    for a in (a0, a1):
        pads = rng.random(a.shape) < 0.3
        a[pads] = -1
    a0[0], a1[0] = (-1, 3, -1, 5), (-1, 3, 6, -1)        # left + middle
    a0[1], a1[1] = (-1, -1, -1, -1), (2, -1, -1, 4)      # from nothing
    a0[2], a1[2] = (7, -1, 8, -1), (-1, -1, -1, -1)      # to nothing
    l0[:3], l1[:3] = (1, 0, 0), (2, 3, 0)
    return _fetched(a0, a1, l0, l1), _meta(300, range(16))


def _leader_on_pad_or_out_of_range(rng):
    a0 = _placement(rng, 200, 16, 3)
    a0[rng.random(a0.shape) < 0.2] = -1
    a1 = a0.copy()
    a1[::3, 1] = rng.integers(0, 16, len(a1[::3]))
    l0 = rng.integers(-2, 6, 200)
    l1 = rng.integers(-2, 6, 200)
    return _fetched(a0, a1, l0, l1), _meta(200, range(16))


def _followers_swapped_same_leader(rng):
    """Another follower ORDER is another replica list: a proposal, in the
    loop too."""
    a0 = _placement(rng, 100, 16, 3)
    a1 = a0[:, [0, 2, 1]]
    leader = np.zeros(100, np.int32)
    return _fetched(a0, a1, leader, leader), _meta(100, range(16))


def _slots_swapped_same_leader_first_order(rng):
    """The leader's broker changes slot with slot 0's and stays the leader:
    the arrays differ, the leader-first list does not. No proposal."""
    a0 = _placement(rng, 100, 16, 3)
    a1 = a0[:, [1, 0, 2]]
    return _fetched(a0, a1, np.ones(100, np.int32),
                    np.zeros(100, np.int32)), _meta(100, range(16))


def _leadership_only(rng):
    a0 = _placement(rng, 100, 16, 3)
    l0 = rng.integers(0, 3, 100)
    return _fetched(a0, a0, l0, (l0 + 1) % 3), _meta(100, range(16))


def _rows_outside_mask(rng):
    return _rf3_dense(rng, masked=True)


def _no_change(rng):
    a0 = _placement(rng, 50, 16, 3)
    l0 = rng.integers(0, 3, 50)
    return _fetched(a0, a0, l0, l0), _meta(50, range(16))


def _broker_ids_not_a_range(rng):
    return _rf3_dense(rng,
                      broker_ids=[1001 + 7 * b for b in reversed(range(16))])


def _second_slot_on_the_leaders_broker(rng):
    """A placement no solver makes; the loop dropped the second slot."""
    a0 = _placement(rng, 50, 16, 3)
    a1 = a0.copy()
    a1[:, 2] = a1[:, 0]
    leader = np.zeros(50, np.int32)
    return _fetched(a0, a1, leader, leader), _meta(50, range(16))


CASES = {
    "rf3_dense": (_rf3_dense, None),
    "mixed_rf_pads": (_mixed_rf_pads, None),
    "leader_on_pad_or_out_of_range": (_leader_on_pad_or_out_of_range, None),
    "followers_swapped_same_leader": (_followers_swapped_same_leader, 100),
    "slots_swapped_same_leader_first_order":
        (_slots_swapped_same_leader_first_order, 0),
    "leadership_only": (_leadership_only, 100),
    "rows_outside_mask": (_rows_outside_mask, None),
    "no_change": (_no_change, 0),
    "broker_ids_not_a_range": (_broker_ids_not_a_range, None),
    "second_slot_on_the_leaders_broker":
        (_second_slot_on_the_leaders_broker, 50),
}


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_columns_equal_the_loop(case, seed):
    draw, expected_len = CASES[case]
    fetched, meta = draw(np.random.default_rng(seed))
    oracle = compare_diff_loop(fetched, meta)
    columns = compare_diff(fetched, meta)

    assert isinstance(columns, ProposalColumns)
    assert len(columns) == len(oracle)
    assert bool(columns) == bool(oracle)
    if expected_len is not None:
        assert len(columns) == expected_len
    else:
        assert oracle, "the draw must change something"
    assert list(columns) == oracle
    assert columns.partition_index is meta.partition_index
    assert count_leadership_only(columns) \
        == sum(p.is_leadership_only for p in oracle)
    if case == "leadership_only":
        assert count_leadership_only(columns) == 100
    if case == "rows_outside_mask":
        real = {meta.partition_index[p]
                for p in np.nonzero(fetched.mask)[0]}
        assert {(p.topic, p.partition) for p in columns} <= real
    # Columns in, columns out; an index builds the one object.
    head = columns[:7]
    assert isinstance(head, ProposalColumns) and list(head) == oracle[:7]
    assert list(columns[3::2]) == oracle[3::2]
    if oracle:
        assert columns[0] == oracle[0] and columns[-1] == oracle[-1]
        assert columns[len(oracle) // 2] == oracle[len(oracle) // 2]
    with pytest.raises(IndexError):
        columns[len(oracle)]


def _materialized() -> float:
    return SENSORS.counter_total("proposal_objects_materialized")


def _large_plan(partitions=1500):
    rng = np.random.default_rng(33)
    a0 = _placement(rng, partitions, 16, 3)
    a0[rng.random(partitions) < 0.1, 2] = -1
    a1 = a0.copy()
    a1[:, 0] = (a0[:, 0] + 1 + rng.integers(0, 14, partitions)) % 16
    l0 = rng.integers(0, 2, partitions)
    l1 = rng.integers(0, 2, partitions)
    return compare_diff(_fetched(a0, a1, l0, l1),
                        _meta(partitions, range(100, 116)))


@pytest.mark.parametrize("verbose", [True, False])
def test_body_from_columns_equals_body_from_objects(verbose):
    """`optimization_result` reads a plan in columns without building an
    object, and writes what it writes for the plain list of objects."""
    columns = _large_plan()
    assert len(columns) > responses._NON_VERBOSE_PROPOSAL_CAP
    cc, _backend = _cruise_control(_partitions())
    solved = cc.rebalance(dryrun=True).optimizer_result

    def body(proposals):
        result = dataclasses.replace(solved, proposals=proposals)
        return responses.optimization_result(
            OperationResult("rebalance", True, result, proposals),
            verbose=verbose)

    objects = list(columns)
    before = _materialized()
    from_columns = body(columns)
    assert _materialized() == before
    from_objects = body(objects)

    assert from_columns == from_objects
    assert list(from_columns) == list(from_objects)     # key order
    assert [list(p) for p in from_columns["proposals"]] \
        == [list(p) for p in from_objects["proposals"]]
    assert from_columns["numProposals"] == len(objects)
    assert from_columns["summary"]["num_leadership_only"] \
        == sum(p.is_leadership_only for p in objects)
    if verbose:
        assert len(from_columns["proposals"]) == len(objects)
        assert "proposalsTruncated" not in from_columns
    else:
        assert from_columns["proposalsTruncated"] is True
        assert len(from_columns["proposals"]) \
            == responses._NON_VERBOSE_PROPOSAL_CAP
    first = from_columns["proposals"][0]
    assert first["oldReplicas"] == list(objects[0].old_replicas)
    assert all(-1 not in p["oldReplicas"] and -1 not in p["newReplicas"]
               for p in from_columns["proposals"])


def test_objects_are_built_for_an_execution_and_not_for_a_dry_run():
    """`proposal_objects_materialized_total`: 0 over a dry run and its
    body, the plan's length over an execution (the executor reads
    objects); `diff.compare` says what it compared."""
    cc, _backend = _cruise_control(_partitions())
    before = _materialized()
    dry = cc.rebalance(dryrun=True)
    assert isinstance(dry.proposals, ProposalColumns) and dry.proposals
    body = responses.optimization_result(dry, verbose=True)
    assert body["numProposals"] == len(dry.proposals)
    assert _materialized() == before

    assert len(list(dry.proposals)) == len(dry.proposals)
    assert _materialized() == before + len(dry.proposals)

    before = _materialized()
    done = cc.rebalance(dryrun=False)
    assert done.executed
    responses.optimization_result(done, verbose=True)
    assert _materialized() == before + len(done.proposals)

    compared = [_attrs(s) for t in TRACER.traces(operation="rebalance")
                for s in _walk(t["root"]) if s["name"] == "diff.compare"]
    assert compared[0]["proposals"] == str(len(done.proposals))
    assert int(compared[0]["changed_rows"]) >= len(done.proposals)


def _walk(span):
    yield span
    for child in span["children"]:
        yield from _walk(child)
