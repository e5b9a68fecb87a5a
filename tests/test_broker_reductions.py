"""Per-broker reductions of the flat replica axis (model/tensors.py
``broker_best`` / ``broker_count`` / ``broker_flag_at``, docs/DESIGN.md
"Per-broker reductions of the flat replica axis"): the dense
compare-and-reduce form and the ``segment_*`` form give the same answers,
bit for bit, so the source selection picks the same cards, the chain walks
the same trajectory, and ``solver.dispatch`` says which form was traced.
The segment form is the oracle: it is what the CPU runs unforced. Likewise
the top-k's of the whole flat replica axis (``flat_top_k``, docs/DESIGN.md
"Top-k of the flat replica axis"): the two-level form returns
``lax.top_k``'s values, indices and order, and ``lax.top_k`` is the
oracle.
"""

import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.analyzer import candidates as cand_mod
from cruise_control_tpu.analyzer.candidates import select_sources
from cruise_control_tpu.analyzer.chain import (
    chain_optimize_full, optimize_chain,
)
from cruise_control_tpu.analyzer.constraint import BalancingConstraint
from cruise_control_tpu.analyzer.optimizer import goals_by_priority
from cruise_control_tpu.analyzer.search import ExclusionMasks, SearchConfig
from cruise_control_tpu.config.cruise_control_config import (
    CruiseControlConfig,
)
from cruise_control_tpu.model import tensors
from cruise_control_tpu.model.fixtures import random_cluster
from cruise_control_tpu.model.tensors import (
    BrokerState, ClusterTensors, broker_best, broker_count, broker_flag_at,
    broker_reduce_form, broker_segments, flat_top_k, flat_topk_form,
    flat_topk_row_len, flatten_slots, offline_per_broker, offline_replicas,
    set_broker_state, two_level_top_k,
)
from cruise_control_tpu.utils.tracing import TRACER

FORMS = ("segment", "dense")
B = 6
INF = np.inf

# name -> (weights, brokers) of a flat axis; broker B is the dead bucket
FLAT_AXES = {
    "ties break by the lowest flat index": (
        [5.0, 5.0, 1.0, 5.0, 7.0, 7.0, 7.0, 2.0],
        [0, 0, 0, 1, 2, 2, 2, 1]),
    "brokers holding 0, 1 and 2 finite weights": (
        [3.0, -INF, 4.0, 9.0, -INF, -INF, 1.0],
        [1, 1, 2, 2, 3, 3, 5]),
    "nothing finite anywhere": ([-INF] * 5, [0, 1, 2, 3, 4]),
    "the dead bucket holds the heaviest": (
        [1e30, 2.0, 1e30, 3.0, 3.0], [B, 0, B, 4, 4]),
    "a drain's 1e30 beside ordinary weights": (
        [1e30, 1e30, 0.5, 1e30, 0.25, 0.125], [2, 2, 2, 3, 3, 0]),
    "an infinite weight is no best": ([INF, 1.0, 2.0], [0, 0, 1]),
    "every replica on one broker": ([1.0, 4.0, 4.0, 2.0], [3, 3, 3, 3]),
}


def _flat(name):
    w, seg = FLAT_AXES[name]
    return jnp.asarray(w, jnp.float32), jnp.asarray(seg, jnp.int32)


@pytest.mark.parametrize("name", sorted(FLAT_AXES))
def test_broker_best_and_second_best_equal_under_both_forms(name):
    fw, seg = _flat(name)
    n = fw.shape[0]
    got = {}
    for form in FORMS:
        w1, i1 = broker_best(fw, seg, B, form)
        w2, i2 = broker_best(fw, seg, B, form, skip=i1)
        got[form] = [np.asarray(x) for x in (w1, i1, w2, i2)]
    for a, b in zip(got["segment"], got["dense"]):
        np.testing.assert_array_equal(a, b)
    # and both are the plain definition: max, lowest index attaining it
    w1, i1, w2, i2 = got["dense"]
    w_np, seg_np = np.asarray(fw), np.asarray(seg)
    for broker in range(B):
        mine = [i for i in range(n) if seg_np[i] == broker]
        finite = [i for i in mine if np.isfinite(w_np[i])]
        if not finite or max(w_np[i] for i in mine) == INF:
            assert i1[broker] == n
            continue
        first = min(finite, key=lambda i: (-w_np[i], i))
        assert (w1[broker], i1[broker]) == (w_np[first], first)
        rest = [i for i in finite if i != first]
        if rest:
            second = min(rest, key=lambda i: (-w_np[i], i))
            assert (w2[broker], i2[broker]) == (w_np[second], second)
        else:
            assert i2[broker] == n and w2[broker] == -INF


@pytest.mark.parametrize("name", sorted(FLAT_AXES))
def test_broker_count_and_flag_lookup_equal_under_both_forms(name):
    fw, seg = _flat(name)
    flags = jnp.isfinite(fw)
    table = jnp.arange(B) % 2 == 0
    counts = [np.asarray(broker_count(flags, seg, B, f)) for f in FORMS]
    looked = [np.asarray(broker_flag_at(table, seg, f)) for f in FORMS]
    np.testing.assert_array_equal(*counts)
    np.testing.assert_array_equal(*looked)
    seg_np = np.asarray(seg)
    np.testing.assert_array_equal(
        counts[0], [(np.asarray(flags) & (seg_np == i)).sum()
                    for i in range(B)])
    np.testing.assert_array_equal(
        looked[0], [s < B and s % 2 == 0 for s in seg_np])


def test_form_follows_shape_and_backend(monkeypatch):
    """One place chooses, from the static shapes and the backend: the CPU
    keeps the segment form at every size; elsewhere the dense form up to
    DENSE_BROKER_CELLS cells, which holds the benchmark's cells and the
    largest size the microbench measured (1,024 brokers / 100,000
    partitions), and nothing beyond."""
    cells = tensors.DENSE_BROKER_CELLS
    assert broker_reduce_form(16, 1536) == "segment"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert broker_reduce_form(128, 30_000) == "dense"
    assert broker_reduce_form(256, 75_000) == "dense"
    assert broker_reduce_form(1, cells) == "dense"
    assert broker_reduce_form(1, cells + 1) == "segment"
    assert broker_reduce_form(1024, 300_000) == "dense"
    assert broker_reduce_form(2048, 600_000) == "segment"
    assert broker_reduce_form(7168, 3_000_000) == "segment"


def _force(monkeypatch, form):
    """Steer the one chooser, for every module that imported it. A jitted
    program traced under another form must be dropped by the caller."""
    for mod in (tensors, cand_mod):
        monkeypatch.setattr(mod, "broker_reduce_form", lambda b, n: form)


def _cluster(name):
    """(state, source_score, weight) of one selection scenario."""
    rng = np.random.default_rng(11)
    kw = dict(num_brokers=12, num_topics=4, num_partitions=96, rf=3,
              num_racks=4, seed=3, skew_to_first=2.0)
    if name == "padded partitions and -1 slots":
        kw.update(rf=2)
    state, _meta = random_cluster(**kw)
    shape = state.assignment.shape
    score = jnp.asarray(rng.uniform(0.1, 1.0, state.num_brokers),
                        jnp.float32)
    weight = jnp.asarray(rng.uniform(1.0, 2.0, shape), jnp.float32)
    if name == "weight ties":
        weight = jnp.asarray(rng.integers(1, 4, shape), jnp.float32)
    elif name == "brokers with 0, 1 and 2 eligible replicas":
        # -inf all but one replica of broker 1 and two of broker 2, and
        # every replica of broker 3
        a = np.asarray(state.assignment)
        w = np.asarray(weight).copy()
        for broker, keep in ((1, 1), (2, 2), (3, 0)):
            at = np.argwhere(a == broker)
            for p, s in at[keep:]:
                w[p, s] = -INF
        weight = jnp.asarray(w)
    elif name == "no source at all":
        score = jnp.full(state.num_brokers, -1.0)
    elif name == "one source broker":
        score = jnp.where(jnp.arange(state.num_brokers) == 4, 1.0, 0.0)
    elif name == "replicas on DEAD brokers":
        state = set_broker_state(state, jnp.asarray([0, 7]),
                                 BrokerState.DEAD)
        off = offline_replicas(state)
        assert int(off.sum()) > 0
        score = score + offline_per_broker(state, off)
        weight = jnp.where(off, 1e30, weight)
    elif name == "padded partitions and -1 slots":
        a = np.asarray(state.assignment).copy()
        a[::5, 1] = -1
        mask = np.asarray(state.partition_mask).copy()
        mask[-7:] = False
        state = dataclasses.replace(state, assignment=jnp.asarray(a),
                                    partition_mask=jnp.asarray(mask))
    return state, score, weight


SCENARIOS = ("plain", "weight ties",
             "brokers with 0, 1 and 2 eligible replicas", "no source at all",
             "one source broker", "replicas on DEAD brokers",
             "padded partitions and -1 slots")


@pytest.mark.parametrize("slot_major", (False, True),
                         ids=("partition-major", "slot-major"))
@pytest.mark.parametrize("k_src", (16, 64), ids=("k<4b", "k>4b"))
@pytest.mark.parametrize("name", SCENARIOS)
def test_select_sources_same_cards_under_both_forms(monkeypatch, name,
                                                    k_src, slot_major):
    """Cards, validity and the on-source mask, in both flat layouts, with
    the per-broker blocks narrower than the brokers (k_src < 4 b: the
    broker top-k cuts) and as wide (k_src > 4 b: every broker offers its
    two)."""
    state, score, weight = _cluster(name)
    assert (k_src < 4 * state.num_brokers) == (k_src == 16)
    monkeypatch.setattr(tensors, "slot_major_flat", lambda: slot_major)
    got = {}
    try:
        for form in FORMS:
            _force(monkeypatch, form)
            got[form] = [np.asarray(x) for x in
                         select_sources(state, score, weight, k_src)]
            assert cand_mod.source_select() == form
    finally:
        monkeypatch.undo()
    for a, b in zip(got["segment"], got["dense"]):
        np.testing.assert_array_equal(a, b)
    _p, _s, valid, on_source, fallback = got["dense"]
    assert not fallback
    if name == "no source at all":
        assert not valid.any() and not on_source.any()
    else:
        assert valid.any() and on_source.any()
    if name == "replicas on DEAD brokers":
        # the global block (half the cards or more) is offline replicas
        # first: the drain's priority rides the 1e30 weight through here
        p, s, ok, _on, _fb = got["dense"]
        off = np.asarray(offline_replicas(state))
        assert off[p[ok], s[ok]].sum() >= min(int(off.sum()), k_src // 2)


@pytest.mark.parametrize("name", ("plain", "replicas on DEAD brokers",
                                  "padded partitions and -1 slots"))
def test_offline_per_broker_equal_under_both_forms(monkeypatch, name):
    state, _score, _weight = _cluster(name)
    off = offline_replicas(state)
    got = {}
    for form in FORMS:
        monkeypatch.setattr(tensors, "broker_reduce_form",
                            lambda b, n, form=form: form)
        got[form] = np.asarray(offline_per_broker(state, off))
    np.testing.assert_array_equal(got["segment"], got["dense"])
    seg = np.asarray(broker_segments(state))
    flat_off = np.asarray(flatten_slots(off))
    np.testing.assert_array_equal(
        got["dense"], [(flat_off & (seg == i)).sum()
                       for i in range(state.num_brokers)])
    assert (got["dense"].sum() > 0) == (name == "replicas on DEAD brokers")


def test_fused_chain_at_16_512_same_trajectory_with_the_dense_form_forced(
        monkeypatch):
    """One fused ``optimize_chain`` pass over the default chain at 16
    brokers / 512 partitions, a broker dead: final placement, rounds and
    proposals by goal are the segment form's, and the dispatch span
    carries the form that was traced."""
    state, meta = random_cluster(num_brokers=16, num_topics=4,
                                 num_partitions=512, rf=3, num_racks=4,
                                 seed=5, skew_to_first=2.0)
    state = set_broker_state(state, jnp.asarray([3]), BrokerState.DEAD)
    goals = tuple(goals_by_priority(CruiseControlConfig()))
    cfg = SearchConfig(num_sources=32, num_dests=6, moves_per_round=32,
                       max_rounds=60)
    args = (state, goals, BalancingConstraint(), cfg, meta.num_topics,
            ExclusionMasks())

    def one_pass():
        chain_optimize_full.clear_cache()
        with TRACER.span("test.pass") as root:
            out = optimize_chain(*args)
        (dispatch,) = [c for c in root.children
                       if c.name == "solver.dispatch"]
        return out, dispatch.attributes

    (st_seg, infos_seg), attrs = one_pass()
    assert attrs["source_select"] == "segment"
    assert attrs["accept_lookup"] == "grid"
    try:
        _force(monkeypatch, "dense")
        jax.clear_caches()
        (st_dense, infos_dense), attrs = one_pass()
        assert attrs["source_select"] == "dense"
    finally:
        monkeypatch.undo()
        jax.clear_caches()

    np.testing.assert_array_equal(np.asarray(st_seg.assignment),
                                  np.asarray(st_dense.assignment))
    np.testing.assert_array_equal(np.asarray(st_seg.leader_slot),
                                  np.asarray(st_dense.leader_slot))
    assert int(offline_replicas(st_dense).sum()) == 0
    assert sum(i["rounds"] for i in infos_seg) > len(goals)
    assert infos_seg == infos_dense


def test_fused_chain_at_64_1024_same_trajectory_over_the_candidate_rows(
        monkeypatch):
    """One fused ``optimize_chain`` pass over the default chain at 64
    brokers / 1,024 partitions with 16 sources (a quarter of 4 kept, M =
    20 rows of 64), a broker dead, the dense form forced: the rows form
    traces, and placement, rounds and proposals by goal are the segment
    form's. The dispatch span and ``solver_source_fallback_rounds_total``
    carry the pass's fallback rounds, the tally's sum."""
    from cruise_control_tpu.utils.sensors import SENSORS
    state, meta = random_cluster(num_brokers=64, num_topics=8,
                                 num_partitions=1024, rf=3, num_racks=4,
                                 seed=5, skew_to_first=2.0)
    state = set_broker_state(state, jnp.asarray([3]), BrokerState.DEAD)
    goals = tuple(goals_by_priority(CruiseControlConfig()))
    cfg = SearchConfig(num_sources=16, num_dests=6, moves_per_round=16,
                       max_rounds=60)
    args = (state, goals, BalancingConstraint(), cfg, meta.num_topics,
            ExclusionMasks())

    def one_pass():
        chain_optimize_full.clear_cache()
        before = SENSORS.counter_total("solver_source_fallback_rounds")
        with TRACER.span("test.pass") as root:
            out = optimize_chain(*args)
        (dispatch,) = [c for c in root.children
                       if c.name == "solver.dispatch"]
        counted = SENSORS.counter_total("solver_source_fallback_rounds") \
            - before
        return out, dispatch.attributes, counted

    (st_seg, infos_seg), attrs, counted = one_pass()
    assert attrs["source_select"] == "segment"
    assert attrs["source_fallback_rounds"] == counted == 0
    try:
        _force(monkeypatch, "dense")
        jax.clear_caches()
        (st_rows, infos_rows), attrs, counted = one_pass()
        assert attrs["source_select"] == "rows"
    finally:
        monkeypatch.undo()
        jax.clear_caches()

    np.testing.assert_array_equal(np.asarray(st_seg.assignment),
                                  np.asarray(st_rows.assignment))
    np.testing.assert_array_equal(np.asarray(st_seg.leader_slot),
                                  np.asarray(st_rows.leader_slot))
    fallbacks = sum(i.pop("source_fallback_rounds") for i in infos_rows)
    assert attrs["source_fallback_rounds"] == fallbacks == counted
    assert fallbacks <= sum(i["rounds"] for i in infos_rows)
    for info in infos_seg:
        info.pop("source_fallback_rounds")
    assert sum(i["rounds"] for i in infos_seg) > len(goals)
    assert infos_seg == infos_rows


@pytest.mark.parametrize("case", ("bbest_dense", "bbest_sort",
                                  "bbest_rows", "bcount_dense", "flat_two128",
                                  "flat_two256", "flat_two512",
                                  "flat_two1024"))
def test_microbench_broker_forms_compute_the_same(case):
    """The microbench's ``bbest_*`` / ``bcount_*`` classes price the SAME
    work: every form leaves the carry its segment form leaves (at 64
    brokers keeping 4, so ``bbest_rows`` reduces 20 rows of 64); each
    ``flat_two<k>`` the carry ``flat_sort<k>`` leaves (over 6,144 flat
    replicas, so every k keeps fewer rows than there are)."""
    from cruise_control_tpu.utils.microbench import _build_cases
    assert cand_mod.source_rows(4, 64, "dense") == 20
    if case.startswith("flat_"):
        run, inputs = _build_cases(64, 2048, quarter=4)
        oracle = case.replace("two", "sort")
    else:
        run, inputs = _build_cases(64, 64, quarter=4)
        oracle = case.split("_")[0] + "_segment"
    want = np.asarray(run(inputs[oracle], 2, oracle))
    assert (want != np.asarray(inputs[oracle])).any()
    np.testing.assert_array_equal(
        np.asarray(run(inputs[case], 2, case)), want)


# name -> whether the rows form falls back to every broker's row
ROWS_SCENARIOS = {
    "many sources, all finite": False,
    "the top-M brokers' weights all -inf": True,
    "fewer sources than the quarter, as in a drain": False,
    "ties in the score across the M-th place": False,
}
ROWS_B, ROWS_K = 64, 16          # quarter 4, M = 20: 2 M <= B


def _rows_cluster(name):
    """(state, source_score, weight) of one rows-form scenario at 64
    brokers."""
    rng = np.random.default_rng(17)
    state, _meta = random_cluster(num_brokers=ROWS_B, num_topics=8,
                                  num_partitions=256, rf=3, num_racks=4,
                                  seed=7, skew_to_first=2.0)
    shape = state.assignment.shape
    score = rng.uniform(0.1, 1.0, ROWS_B)
    weight = rng.uniform(1.0, 2.0, shape)
    a = np.asarray(state.assignment)
    m = cand_mod.source_rows(ROWS_K // 4, ROWS_B, "dense")
    if name == "the top-M brokers' weights all -inf":
        top = np.argsort(-score, kind="stable")[:m]
        weight[np.isin(a, top)] = -INF
    elif name == "fewer sources than the quarter, as in a drain":
        score = np.full(ROWS_B, -1.0)
        score[[5, 40]] = [900.0, 2.0]
    elif name == "ties in the score across the M-th place":
        # four levels of 16 brokers: M = 20 cuts the second level; the 14
        # lowest ids of the first level hold nothing finite, so the kept
        # quarter reaches into the tie
        score = np.repeat([1.0, 0.75, 0.5, 0.25], 16)[rng.permutation(ROWS_B)]
        first = np.flatnonzero(score == 1.0)[:14]
        weight[np.isin(a, first)] = -INF
    return (state, jnp.asarray(score, jnp.float32),
            jnp.asarray(weight, jnp.float32))


@pytest.mark.parametrize("name", sorted(ROWS_SCENARIOS))
def test_rows_form_picks_the_full_paths_cards(monkeypatch, name):
    """The dense pair over the rows of the M brokers of highest score
    (``candidates.broker_blocks``) returns the cards, validity and
    on-source mask of the full path, bit for bit: the segment form and the
    dense form over every broker's row. Its fallback flag is set exactly
    where fewer than a quarter of the M rows hold a finite best while the
    M-th score is above 0."""
    state, score, weight = _rows_cluster(name)
    got = {}
    try:
        for form in ("rows", "dense", "segment"):
            _force(monkeypatch, "segment" if form == "segment" else "dense")
            if form == "dense":
                monkeypatch.setattr(cand_mod, "source_rows",
                                    lambda q, b, f: None)
            got[form] = [np.asarray(x) for x in
                         select_sources(state, score, weight, ROWS_K)]
            assert cand_mod.source_select() == form
            monkeypatch.undo()
    finally:
        monkeypatch.undo()
    for form in ("dense", "segment"):
        for a, b in zip(got[form][:4], got["rows"][:4]):
            np.testing.assert_array_equal(a, b)
        assert not got[form][4]
    assert bool(got["rows"][4]) == ROWS_SCENARIOS[name]
    assert got["rows"][2].any()


def _argmax_rows(jaxpr, in_cond=False):
    """(rows, inside a cond?) of every argmax over a [rows, n] operand,
    through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "argmax":
            shape = eqn.invars[0].aval.shape
            if len(shape) == 2:
                yield shape[0], in_cond
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(x, "jaxpr", x)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _argmax_rows(
                        sub, in_cond or eqn.primitive.name == "cond")


@pytest.mark.parametrize("brokers, outside, inside", [
    (1024, [80, 80], [1024, 1024]),
    (104, [104, 104], []),
])
def test_the_traced_selection_reduces_the_candidate_rows(
        monkeypatch, brokers, outside, inside):
    """CPU, jaxpr walk, no compile, the dense form forced: at 1,024
    brokers with 256 sources (a quarter of 64 kept) the selection's two
    argmax reductions run over M = 80 rows, and over all 1,024 only in the
    fallback's ``cond`` branch; at 104 brokers (2 M > B) it traces no rows
    form: both over every broker, outside any ``cond``."""
    state, _meta = random_cluster(num_brokers=brokers, num_topics=8,
                                  num_partitions=brokers * 2, rf=3,
                                  num_racks=4, seed=1)
    score = jnp.ones(brokers, jnp.float32)
    weight = jnp.ones(state.assignment.shape, jnp.float32)
    _force(monkeypatch, "dense")
    jaxpr = jax.make_jaxpr(
        lambda st, sc, w: select_sources(st, sc, w, 256))(
            state, score, weight).jaxpr
    found = list(_argmax_rows(jaxpr))
    assert sorted(r for r, c in found if not c) == outside
    assert sorted(r for r, c in found if c) == inside


# ---- top-k of the flat replica axis ----------------------------------------

def _draw(name):
    """(x, k, row_len) of one two-level scenario: seeded draws."""
    rng = np.random.default_rng(23)
    if name == "plain":
        return rng.normal(size=1000), 37, 16
    if name == "heavy ties":
        return rng.integers(0, 4, 1000).astype(float), 100, 8
    if name == "whole rows of one value":
        return np.repeat(rng.integers(0, 3, 125), 8).astype(float), 50, 8
    if name == "rows of -inf":
        x = rng.normal(size=1024)
        x.reshape(64, 16)[rng.random(64) < 0.7] = -INF
        return x, 200, 16
    if name == "all -inf":
        return np.full(777, -INF), 64, 8
    if name == "+inf and -inf among ties":
        return rng.choice([-INF, INF, 1.0, 2.0], 999), 300, 4
    if name == "signed zeros":
        return rng.choice([-0.0, 0.0, -1.0], 512), 100, 8
    if name == "k = 1":
        return rng.integers(0, 2, 500).astype(float), 1, 8
    if name == "k = n_flat":
        return rng.integers(0, 5, 96).astype(float), 96, 8
    if name == "n_flat not a multiple of L":
        return rng.integers(0, 9, 1001).astype(float), 33, 64
    if name == "a drain's 1e30 beside -inf":
        x = np.where(rng.random(3000) < 0.8, -INF, rng.uniform(1, 2, 3000))
        x[rng.integers(0, 3000, 40)] = 1e30
        return x, 128, 16
    raise KeyError(name)


TWO_LEVEL_DRAWS = ("plain", "heavy ties", "whole rows of one value",
                   "rows of -inf", "all -inf", "+inf and -inf among ties",
                   "signed zeros", "k = 1", "k = n_flat",
                   "n_flat not a multiple of L",
                   "a drain's 1e30 beside -inf")


def _same_top_k(got, want):
    """Values (bit for bit: -0.0 is not +0.0), indices and order."""
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]).view(np.int32),
                                  np.asarray(want[0]).view(np.int32))


@pytest.mark.parametrize("name", TWO_LEVEL_DRAWS)
def test_two_level_top_k_is_lax_top_k(name):
    x, k, row_len = _draw(name)
    x = jnp.asarray(x, jnp.float32)
    _same_top_k(jax.jit(two_level_top_k, static_argnums=(1, 2))(
        x, k, row_len), jax.lax.top_k(x, k))


def test_two_level_top_k_under_vmap():
    """The megabatch body runs the round under ``vmap``: batched, the form
    is still ``lax.top_k`` cluster by cluster."""
    rng = np.random.default_rng(29)
    x = jnp.asarray(np.where(rng.random((3, 1000)) < 0.3, -INF,
                             rng.integers(0, 5, (3, 1000))), jnp.float32)
    _same_top_k(jax.vmap(lambda v: two_level_top_k(v, 50, 16))(x),
                jax.vmap(lambda v: jax.lax.top_k(v, 50))(x))


def _force_topk(monkeypatch, form):
    """Steer the top-k chooser, for every module that imported it."""
    for mod in (tensors, cand_mod):
        monkeypatch.setattr(mod, "flat_topk_form", lambda n, k: form)


@pytest.mark.parametrize("k", (128, 256, 512, 1024))
@pytest.mark.parametrize("n_flat", (30_720, 76_800, 307_200))
def test_flat_top_k_forced_two_level_at_the_microbench_sizes(
        monkeypatch, n_flat, k):
    """``flat_top_k`` with the two-level form forced, at every (n_flat, k)
    the microbench prices, on a draw with ties and -inf as the source
    selection's weights have them."""
    rng = np.random.default_rng(n_flat + k)
    x = np.where(rng.random(n_flat) < 0.5, -INF,
                 rng.integers(0, 50, n_flat).astype(float))
    x = jnp.asarray(x, jnp.float32)
    _force_topk(monkeypatch, "two_level")
    _same_top_k(jax.jit(flat_top_k, static_argnums=1)(x, k),
                jax.lax.top_k(x, k))


def test_flat_topk_form_follows_shape(monkeypatch):
    """One place chooses, from the static shapes alone: the two-level form
    at every k the served grids use at 104, 256 and 1,024 padded brokers
    (30,720 / 76,800 / 307,200 flat replicas), ``lax.top_k`` where the
    rows kept would be more than a quarter of the axis; the same on every
    backend."""
    def forms():
        return {(n, k): flat_topk_form(n, k)
                for n in (1_536, 30_720, 76_800, 307_200)
                for k in (128, 256, 512, 1024, n)}

    on_cpu = forms()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert forms() == on_cpu
    for n in (30_720, 76_800, 307_200):
        for k in (128, 256, 512):
            assert on_cpu[n, k] == "two_level"
            assert 4 * k * flat_topk_row_len(n, k) <= n
        assert on_cpu[n, n] == "sort"
    assert on_cpu[76_800, 1024] == on_cpu[307_200, 1024] == "two_level"
    assert on_cpu[30_720, 1024] == "sort"
    assert {on_cpu[1_536, k] for k in (128, 256, 512, 1024)} == {"sort"}


def test_fused_chain_at_64_1024_same_trajectory_with_the_two_level_top_k(
        monkeypatch):
    """One fused ``optimize_chain`` pass over the default chain at 64
    brokers / 1,024 partitions, a broker dead, once with each top-k form
    forced: placement, leader slots, rounds and proposals by goal are the
    same, and the dispatch span says which form was traced."""
    state, meta = random_cluster(num_brokers=64, num_topics=8,
                                 num_partitions=1024, rf=3, num_racks=4,
                                 seed=5, skew_to_first=2.0)
    state = set_broker_state(state, jnp.asarray([3]), BrokerState.DEAD)
    goals = tuple(goals_by_priority(CruiseControlConfig()))
    cfg = SearchConfig(num_sources=64, num_dests=6, moves_per_round=32,
                       max_rounds=60)
    args = (state, goals, BalancingConstraint(), cfg, meta.num_topics,
            ExclusionMasks())

    def one_pass():
        chain_optimize_full.clear_cache()
        with TRACER.span("test.pass") as root:
            out = optimize_chain(*args)
        (dispatch,) = [c for c in root.children
                       if c.name == "solver.dispatch"]
        return out, dispatch.attributes

    try:
        _force_topk(monkeypatch, "sort")
        jax.clear_caches()
        (st_sort, infos_sort), attrs = one_pass()
        assert attrs["flat_topk"] == "sort"
        _force_topk(monkeypatch, "two_level")
        jax.clear_caches()
        (st_two, infos_two), attrs = one_pass()
        assert attrs["flat_topk"] == "two_level"
    finally:
        monkeypatch.undo()
        jax.clear_caches()

    np.testing.assert_array_equal(np.asarray(st_sort.assignment),
                                  np.asarray(st_two.assignment))
    np.testing.assert_array_equal(np.asarray(st_sort.leader_slot),
                                  np.asarray(st_two.leader_slot))
    assert sum(i["rounds"] for i in infos_sort) > len(goals)
    assert infos_sort == infos_two


def _top_k_operands(jaxpr):
    """The operand length of every top_k, through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            yield eqn.invars[0].aval.shape[-1]
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(x, "jaxpr", x)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _top_k_operands(sub)


def _zero_cluster(brokers, partitions):
    """A cluster of the served padded shapes; only shapes matter."""
    return ClusterTensors(
        assignment=jnp.zeros((partitions, 3), jnp.int32),
        leader_slot=jnp.zeros(partitions, jnp.int32),
        leader_load=jnp.zeros((partitions, 4)),
        follower_load=jnp.zeros((partitions, 4)),
        capacity=jnp.ones((brokers, 4)),
        rack=jnp.zeros(brokers, jnp.int32),
        broker_state=jnp.zeros(brokers, jnp.int8),
        topic=jnp.zeros(partitions, jnp.int32),
        partition_mask=jnp.ones(partitions, bool),
        broker_mask=jnp.ones(brokers, bool))


@pytest.mark.parametrize("brokers, partitions, form, flat_sorts", [
    (1024, 102_400, "two_level", 0),
    (104, 10_240, "two_level", 0),
    (16, 512, "sort", 2),
])
def test_the_traced_round_takes_the_chosen_flat_top_k(
        monkeypatch, brokers, partitions, form, flat_sorts):
    """CPU, jaxpr walk, no compile, the chip's choices: at 1,024 and 104
    brokers (307,200 / 30,720 flat replicas) the global block (k 128) and
    the leadership block (k 256) of a grid of 256 sources never rank the
    whole axis, only the rows' best elements and the rows kept; at 16
    brokers (1,536), where the rows kept would be most of the axis, both
    are ``lax.top_k`` of the whole axis. ``flat_topk`` says so."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    state = _zero_cluster(brokers, partitions)
    n_flat = partitions * 3
    score = jnp.ones(brokers, jnp.float32)
    weight = jnp.ones(state.assignment.shape, jnp.float32)

    def round_top_ks(st, sc, w):
        cand, _layout = cand_mod.generate_candidates(
            st, None, sc, sc, w, 256, 16, include_leadership=True,
            leadership_only=True)
        return cand

    jaxpr = jax.make_jaxpr(round_top_ks)(state, score, weight).jaxpr
    sizes = list(_top_k_operands(jaxpr))
    assert sizes.count(n_flat) == flat_sorts
    assert cand_mod.flat_topk() == form
    if form == "two_level":
        row_lens = [flat_topk_row_len(n_flat, k) for k in (128, 256)]
        rows = [-(-n_flat // row) for row in row_lens]
        kept = [k * row for k, row in zip((128, 256), row_lens)]
        assert sorted(rows + kept) == sorted(
            s for s in sizes if s > brokers)
