"""The served plan's text (`ProposalColumns.to_json`,
`responses.ProposalsJson`, `responses.body_bytes`) against the dict a move
it replaced: the bytes of a body are the compact `json.dumps` of the body
that `proposal_dicts` writes, and every reader in the process sees that
body."""

import copy
import dataclasses
import json
import pickle
import urllib.request

import numpy as np
import pytest

from cruise_control_tpu.analyzer.proposals import (
    ExecutionProposal, ProposalColumns,
)
from cruise_control_tpu.api import responses
from cruise_control_tpu.api.server import _as_text
from cruise_control_tpu.facade import OperationResult
from cruise_control_tpu.serving.journey import JourneyLog, journey_scope
from cruise_control_tpu.utils.sensors import SENSORS

from .test_facade import _cruise_control, _partitions


def _columns(rows, old, new, old_leader, new_leader, topics=None):
    """A plan in columns over a partition index of ``max(rows) + 1`` rows:
    topic ``t<p // 7>`` (or ``topics[p % len(topics)]``), partition
    ``p % 7``."""
    rows = np.asarray(rows, np.int64)
    count = int(rows.max()) + 1 if len(rows) else 1
    names = topics or [f"t{p // 7}" for p in range(count)]
    index = [(names[p % len(names)], p % 7) for p in range(count)]
    ids = lambda a, shape: np.asarray(a, np.int64).reshape(shape)  # noqa: E731
    s = np.asarray(old).shape[-1] if len(rows) else 3
    return ProposalColumns(
        partition_index=index, rows=rows,
        old_replicas=ids(old, (len(rows), s)),
        new_replicas=ids(new, (len(rows), s)),
        old_leader=ids(old_leader, (len(rows),)),
        new_leader=ids(new_leader, (len(rows),)),
        data_to_move_mb=np.zeros(len(rows)))


def _rf3(n, seed=42, brokers=1000):
    rng = np.random.default_rng(seed)
    old = np.stack([rng.permutation(brokers)[:3] for _ in range(n)])
    new = old.copy()
    new[:, 2] = (old[:, 2] + 1 + rng.integers(0, brokers - 3, n)) % brokers
    return _columns(np.sort(rng.choice(10 * n, n, replace=False)), old, new,
                    old[:, 0], new[:, 0])


def _mixed_rf_pads():
    """RF 1-4 in four slots, the pads on the right as `compare_diff` puts
    them: rows from nothing, to nothing, and of one replica."""
    rng = np.random.default_rng(7)
    n = 300
    lengths = rng.integers(0, 5, (2, n))
    old, new = (np.where(np.arange(4) < lengths[i][:, None],
                         rng.integers(0, 120, (n, 4)), -1) for i in (0, 1))
    lead = [np.where(lengths[i] > 0, a[:, 0], -1)
            for i, a in enumerate((old, new))]
    return _columns(np.arange(0, 3 * n, 3), old, new, *lead)


def _leader_none():
    return _columns([0, 5, 9], [[1, 2, 3], [4, 5, 6], [7, 8, 9]],
                    [[2, 1, 3], [6, 5, 4], [9, 8, 7]], [-1, 4, -1],
                    [2, -1, -1])


def _escaped_topics():
    """A quote, a backslash, a control character, Latin-1, a character
    beyond the BMP: `ensure_ascii` writes each as `json.dumps` does."""
    topics = ['quo"te', "back\\slash", "tab\tnl\n", "café",
              "日本", "emoji\U0001F600", "plain.topic-1_x"]
    return _columns(np.arange(30), [[1, 2, 3]] * 30, [[3, 2, 1]] * 30,
                    [1] * 30, [3] * 30, topics=topics)


def _empty():
    return _columns([], [], [], [], [])


def _objects():
    return list(_rf3(40, seed=3))


CASES = {
    "rf3_no_pads": (lambda: _rf3(2000), True),
    "mixed_rf_pads": (_mixed_rf_pads, True),
    "leader_none": (_leader_none, True),
    "escaped_topics": (_escaped_topics, True),
    "empty_plan": (_empty, True),
    "non_verbose_truncated": (lambda: _rf3(1500), False),
    "list_of_objects": (_objects, True),
}


@pytest.fixture(scope="module")
def solved():
    cc, _backend = _cruise_control(_partitions())
    return cc.rebalance(dryrun=True).optimizer_result


def _operation(solved, plan):
    return OperationResult(
        "rebalance", True, dataclasses.replace(solved, proposals=plan), plan,
        extra={"after": ["the", "plan"]})


def _dict_body(body, plan, verbose):
    """The body as it was written before the text: the same keys in the
    same order, `"proposals"` a dict a move."""
    if not verbose:
        plan = plan[:responses._NON_VERBOSE_PROPOSAL_CAP]
    return {**body, "proposals": responses.proposal_dicts(plan)}


@pytest.mark.parametrize("case", CASES)
def test_served_bytes_are_the_dict_bodys(case, solved):
    draw, verbose = CASES[case]
    plan = draw()
    body = responses.optimization_result(_operation(solved, plan),
                                         verbose=verbose)
    want = json.dumps(_dict_body(body, plan, verbose),
                      separators=(",", ":")).encode()

    assert responses.body_bytes(body) == want
    assert isinstance(body["proposals"], responses.ProposalsJson) \
        == isinstance(plan, ProposalColumns)
    assert list(body)[-2:] == ["proposals", "after"]
    if case == "non_verbose_truncated":
        assert body["proposalsTruncated"] is True
        assert body["numProposals"] == 1500
        assert len(body["proposals"]) == responses._NON_VERBOSE_PROPOSAL_CAP
    if case == "empty_plan":
        assert b'"proposals":[]' in want
    if case == "escaped_topics":
        assert b'\\u00e9' in want and b'\\ud83d\\ude00' in want


def _encoded(form: str) -> float:
    return sum(v for (name, labels), v in SENSORS._counters.items()
               if name == "proposal_rows_encoded"
               and dict(labels).get("form") == form)


def test_readers_in_the_process_see_the_dicts(solved):
    """`len` answers without a dict; any other read parses the text once
    into the list the dict path wrote; the text is still what is served."""
    plan = _rf3(500, seed=11)
    body = responses.optimization_result(_operation(solved, plan),
                                         verbose=True)
    dicts = _dict_body(body, plan, True)
    lazy = body["proposals"]
    objects = _encoded("objects")

    assert len(lazy) == 500 and lazy
    assert _encoded("objects") == objects
    assert isinstance(lazy, list)
    assert lazy == dicts["proposals"] and dicts["proposals"] == lazy
    assert not lazy != dicts["proposals"]
    assert _encoded("objects") == objects + 500
    assert lazy[0] == dicts["proposals"][0]
    assert lazy[-3:] == dicts["proposals"][-3:]
    assert list(lazy) == list(reversed(list(reversed(lazy))))
    assert dicts["proposals"][7] in lazy and lazy.index(lazy[7]) == 7
    assert lazy.copy() == dicts["proposals"] and repr(lazy) == repr(lazy[:])
    assert len(lazy) == 500
    assert _encoded("objects") == objects + 500      # parsed once
    assert body == dicts
    assert _as_text(body) == _as_text(dicts)
    for kwargs in ({}, {"indent": 2}, {"sort_keys": True},
                   {"separators": (",", ":")}):
        assert json.dumps(body, **kwargs) == json.dumps(dicts, **kwargs)
    assert responses.body_bytes(body) == json.dumps(
        dicts, separators=(",", ":")).encode()
    for copied in (copy.deepcopy(body), pickle.loads(pickle.dumps(body))):
        assert copied == dicts
        assert responses.body_bytes(copied) == responses.body_bytes(body)
    fresh = responses.optimization_result(_operation(solved, plan),
                                          verbose=True)
    assert copy.deepcopy(fresh)["proposals"] == dicts["proposals"]


def test_counter_splits_text_from_objects(solved):
    """`proposal_rows_encoded_total{form=}` and the `proposal_diff`
    segment's `encoded`: columns are encoded as text (a non-verbose body's
    rows only), a plain list of objects through dicts."""
    log = JourneyLog(enabled=True)

    def render(plan, verbose=True):
        jny = log.open("PROPOSALS")
        with journey_scope(jny):
            body = responses.optimization_result(_operation(solved, plan),
                                                 verbose=verbose)
        (attrs,) = [a for name, _s, a in jny.segments
                    if name == "proposal_diff"]
        return body, attrs

    text, objects = _encoded("text"), _encoded("objects")
    body, attrs = render(_rf3(1200))
    assert attrs == {"numProposals": 1200, "encoded": "text"}
    responses.body_bytes(body)
    assert (_encoded("text"), _encoded("objects")) == (text + 1200, objects)

    body, attrs = render(_rf3(1200), verbose=False)
    assert attrs["encoded"] == "text"
    assert _encoded("text") == text + 1200 + 1000

    plain = _objects()
    body, attrs = render(plain)
    assert attrs == {"numProposals": len(plain), "encoded": "objects"}
    assert (_encoded("text"), _encoded("objects")) \
        == (text + 2200, objects + len(plain))
    assert type(body["proposals"]) is list


def test_a_served_proposal_is_written_from_its_text():
    """Over HTTP: the body parses to the plan's dicts, its bytes are the
    compact dump of what they parse to, and the served path built no
    dict (`form="objects"` unmoved, `form="text"` the plan's length)."""
    from .test_tracing import _served
    server, api, _scheduler = _served(fleet=False)
    api._async_wait_s = 180
    try:
        text, objects = _encoded("text"), _encoded("objects")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.server_address[1]}"
                "/kafkacruisecontrol/proposals?verbose=true"
                "&ignore_proposal_cache=true", timeout=300) as resp:
            raw = resp.read()
    finally:
        server.shutdown()
        server.server_close()
        api.shutdown()
    body = json.loads(raw)
    assert body["numProposals"] == len(body["proposals"]) > 0
    assert raw == json.dumps(body, separators=(",", ":")).encode()
    assert all(-1 not in p["oldReplicas"] + p["newReplicas"]
               for p in body["proposals"])
    assert _encoded("text") == text + body["numProposals"]
    assert _encoded("objects") == objects


def test_objects_render_as_the_columns_do():
    """The fallback writes a plain list of `ExecutionProposal`s as the
    columns' text reads."""
    plan = _mixed_rf_pads()
    objects = list(plan)
    assert all(isinstance(p, ExecutionProposal) for p in objects)
    assert json.loads(plan.to_json()) == responses.proposal_dicts(objects)


def test_the_render_microbench_compares_equal_bytes():
    from cruise_control_tpu.utils.microbench import run_render_bench
    out = run_render_bench(seed=2**31 + 5, rows=(50, 700), repeats=1)
    assert out["seed"] == 2**31 + 5 and set(out["results"]) == {"50", "700"}
    assert all(r["equal"] and r["dicts"] >= 0 and r["text"] >= 0
               for r in out["results"].values())


def test_concurrent_readers_parse_a_text_once(solved):
    """Readers of one stored body on many threads (pollers of a user task)
    see the whole list, and the text is parsed once."""
    import sys
    import threading

    plan = _rf3(2000, seed=5)
    want = responses.proposal_dicts(plan)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(10):
            lazy = responses.optimization_result(
                _operation(solved, plan), verbose=True)["proposals"]
            objects = _encoded("objects")
            seen = []
            threads = [threading.Thread(target=lambda: seen.append(
                list(lazy) == want and lazy[-1] == want[-1]))
                for _ in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert seen == [True] * 24
            assert _encoded("objects") == objects + 2000
    finally:
        sys.setswitchinterval(interval)
