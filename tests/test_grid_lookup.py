"""Grid lookups (analyzer/candidates.py CandidateGrid, docs/DESIGN.md "Grid
lookups"): a goal reads per-broker, per-topic and per-partition tables
through ``deltas.at_*``; with the candidate grid's margins attached the
lookup runs there and is broadcast, without them it is the per-candidate
gather, and ``compute_deltas`` builds the [N] fields themselves on the
margins. The two forms must agree exactly (the fields on every candidate,
the goals on every valid one), walk the same trajectory, and the round body
must not gather per candidate.
"""

import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.analyzer import chain as chain_mod
from cruise_control_tpu.analyzer.candidates import (
    compute_deltas, generate_candidates, select_sources,
)
from cruise_control_tpu.analyzer.chain import (
    _chain_round_body, chain_optimize_full, optimize_chain,
)
from cruise_control_tpu.analyzer.constraint import BalancingConstraint
from cruise_control_tpu.analyzer.derived import compute_derived
from cruise_control_tpu.analyzer.goals import (
    ALL_GOALS, BrokerSetAwareGoal, MinTopicLeadersPerBrokerGoal,
)
from cruise_control_tpu.analyzer.optimizer import goals_by_priority
from cruise_control_tpu.analyzer.search import (
    ExclusionMasks, SearchConfig, cumulative_select, goal_aux,
)
from cruise_control_tpu.config.cruise_control_config import (
    CruiseControlConfig,
)
from cruise_control_tpu.model.fixtures import random_cluster
from cruise_control_tpu.model.tensors import (
    BrokerState, offline_replicas, set_broker_state,
)

B, K_SRC, K_DST = 16, 24, 5


def _cluster(new=(14, 15)):
    """Offline replicas (a dead broker), new brokers, and below an
    excluded broker: every mask the goals read per broker is mixed."""
    state, meta = random_cluster(num_brokers=B, num_topics=5,
                                 num_partitions=120, rf=3, num_racks=4,
                                 seed=7, skew_to_first=2.0)
    state = set_broker_state(state, jnp.asarray([2]), BrokerState.DEAD)
    state = set_broker_state(state, jnp.asarray(new), BrokerState.NEW)
    assert int(offline_replicas(state).sum()) > 0
    return state, meta


def _masks():
    return ExclusionMasks(
        excluded_replica_move_brokers=jnp.arange(B) == 5)


# every registered goal, and the two whose tables are empty by default
GOALS = {name: cls() for name, cls in ALL_GOALS.items()}
GOALS["MinTopicLeadersPerBrokerGoal[min=1]"] = \
    MinTopicLeadersPerBrokerGoal(min_leaders=1)
GOALS["BrokerSetAwareGoal[2 sets]"] = BrokerSetAwareGoal(
    broker_sets=tuple(i % 2 for i in range(B)))


def _candidates(state, derived, src_score, rng, k_src, first_dst=None):
    """The chain round body's uniform grid (move block with a targeted
    column drawn at random, leadership block) for ``k_src`` source rows:
    destinations by replica count, ``first_dst`` ahead of them all."""
    dst_score = jnp.where(derived.allowed_replica_move,
                          -derived.broker_replicas.astype(jnp.float32),
                          -jnp.inf)
    if first_dst is not None:
        dst_score = dst_score.at[first_dst].set(1.0)
    weight = jnp.asarray(rng.uniform(1.0, 2.0, state.assignment.shape),
                         jnp.float32)
    _p, _s, src_valid, _on, _fb = select_sources(state, src_score, weight,
                                                 k_src)
    targeted = (jnp.asarray(rng.integers(0, B, k_src), jnp.int32),
                src_valid)
    cand, layout = generate_candidates(
        state, derived, src_score, dst_score, weight, k_src, K_DST,
        include_leadership=True, extra_dst=targeted)
    assert layout == ((k_src, K_DST + 1), (k_src, 3))
    return cand, layout, src_valid


def _grid_and_flat():
    """The grid on the fixture, as grid-attached and as plain deltas.
    Sources and destinations are spread so every region holds valid
    candidates."""
    state, _meta = _cluster()
    masks = _masks()
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)
    cand, layout, _src_valid = _candidates(
        state, derived, jnp.ones(B), np.random.default_rng(0), K_SRC)
    grid = compute_deltas(state, derived, cand, layout)
    flat = compute_deltas(state, derived, cand)
    assert grid.grid is not None and flat.grid is None
    return state, derived, layout, grid, flat


K_SRC_EDGES = 128
LEAD_EXCLUDED = 3


def _edges_grid_and_flat():
    """The row view's edges on one grid: partitions with fewer than S
    replicas (a ``-1`` slot, which the leadership block offers as a
    destination and one crafted move row names as its source), leaders on
    every slot, far more source rows asked (128) than the two source
    brokers hold replicas (rows that are invalid, with clipped indices), a
    leadership-excluded broker among the destinations of leader replicas,
    and an offline source (DEAD broker 2). Returns (state, derived, cand,
    layout, grid deltas, flat deltas)."""
    state, _meta = _cluster()
    n_p = state.num_partitions
    rng = np.random.default_rng(1)
    leader_slot = rng.integers(0, 3, n_p)
    assignment = np.asarray(state.assignment).copy()
    short = rng.choice(n_p, 30, replace=False)
    assignment[short, 2] = -1
    leader_slot[short] = rng.integers(0, 2, len(short))
    state = dataclasses.replace(
        state, assignment=jnp.asarray(assignment),
        leader_slot=jnp.asarray(leader_slot, state.leader_slot.dtype))
    lead_excluded = jnp.arange(B) == LEAD_EXCLUDED
    derived = compute_derived(state, None, jnp.arange(B) == 5, lead_excluded)
    # two sources: the DEAD broker and the lightest live one
    light = int(np.argmin(np.where(np.arange(B) == 2, 10**6, np.bincount(
        assignment[assignment >= 0], minlength=B))))
    src_score = jnp.zeros(B).at[jnp.asarray([2, light])].set(1.0)
    # the leadership-excluded broker first among the shared columns
    cand, layout, src_valid = _candidates(
        state, derived, src_score, rng, K_SRC_EDGES, first_dst=LEAD_EXCLUDED)
    assert 0 < int(src_valid.sum()) < K_SRC_EDGES // 2
    k_cols = layout[0][1]
    # one valid move row names the EMPTY slot of a short partition as its
    # source (the whole row: a row fixes the moving slot)
    row = int(np.flatnonzero(np.asarray(src_valid))[0])
    at = slice(row * k_cols, (row + 1) * k_cols)
    cand = dataclasses.replace(
        cand, partition=cand.partition.at[at].set(int(short[0])),
        src_slot=cand.src_slot.at[at].set(2))
    return (state, derived, cand, layout,
            compute_deltas(state, derived, cand, layout),
            compute_deltas(state, derived, cand))


def _fields_equal(grid, flat):
    for f in dataclasses.fields(flat):
        if f.name != "grid" and getattr(flat, f.name) is not None:
            np.testing.assert_array_equal(
                np.asarray(getattr(grid, f.name)),
                np.asarray(getattr(flat, f.name)), err_msg=f.name)


def _regions(layout):
    """name -> [N] bool: the move block's shared columns, its targeted
    column, the leadership block."""
    (k_src, k_cols), (k_l, s) = layout
    n_move = k_src * k_cols
    idx = np.arange(n_move + k_l * s)
    in_move = idx < n_move
    last = in_move & (idx % k_cols == k_cols - 1)
    return {"shared columns": in_move & ~last, "targeted column": last,
            "leadership block": ~in_move}


def test_grid_fields_match_flat_on_valid_candidates():
    """The [N] fields stay (selection, apply and the flight stats read
    them), and the margins reproduce them on EVERY candidate, valid or
    not: the fields are built on the margins themselves."""
    _state, _derived, layout, grid, flat = _grid_and_flat()
    _fields_equal(grid, flat)
    valid = np.asarray(flat.valid)
    for name, region in _regions(layout).items():
        assert (valid & region).any(), f"no valid candidate in {name}"
    b_ids = jnp.arange(B)
    np.testing.assert_array_equal(np.asarray(grid.at_src(b_ids))[valid],
                                  np.asarray(flat.src_broker)[valid])
    np.testing.assert_array_equal(np.asarray(grid.at_dst(b_ids))[valid],
                                  np.asarray(flat.dst_broker)[valid])
    # the one allowed difference: an invalid candidate reads its row's and
    # column's brokers where the flat fields read broker 0
    assert (np.asarray(flat.src_broker)[~valid] == 0).all()


def test_grid_fields_match_flat_on_the_row_views_edges():
    """Every field on every candidate again, where the row view could go
    wrong, and each edge is shown to be on the grid."""
    state, derived, cand, layout, grid, flat = _edges_grid_and_flat()
    _fields_equal(grid, flat)
    (k_src, k_cols), (k_l, s) = layout
    n_move = k_src * k_cols
    valid = np.asarray(flat.valid)
    kind_move = np.arange(cand.n) < n_move
    p = np.asarray(cand.partition)
    assignment = np.asarray(state.assignment)
    leader_slot = np.asarray(state.leader_slot)
    for name, region in _regions(layout).items():
        assert (valid & region).any(), f"no valid candidate in {name}"
        assert (~valid & region).any(), f"no invalid candidate in {name}"
    # rows that are no source at all (clipped indices), in both blocks
    offered = np.asarray(cand.valid)
    assert (~offered[:n_move]).reshape(k_src, k_cols).all(axis=1).any()
    assert (~offered[n_move:]).reshape(k_l, s).all(axis=1).any()
    # a -1 slot as a leadership destination, and as a move's source
    lead_dst = assignment[p[n_move:], np.asarray(cand.dst_slot)[n_move:]]
    assert ((lead_dst < 0) & offered[n_move:]).any()
    src_empty = kind_move & offered \
        & (assignment[p, np.asarray(cand.src_slot)] < 0)
    assert src_empty.any() and not valid[src_empty].any()
    # leaders on slots other than 0, moving and handing leadership over
    src_slot = np.asarray(flat.src_slot)
    assert (valid & kind_move & (np.asarray(flat.leader_delta) > 0)
            & (src_slot > 0)).any()
    assert (valid & ~kind_move & (src_slot > 0)).any()
    # an offline source, valid
    src = np.asarray(flat.src_broker)
    assert (valid & kind_move & (src == 2)).any()
    # the leadership-excluded broker: offered to leader replicas of a live
    # broker and refused, taken for followers and for offline leaders
    dst_raw = np.where(kind_move, np.asarray(cand.dst_broker),
                       np.concatenate([np.zeros(n_move, int), lead_dst]))
    leader_moves = kind_move & offered & (dst_raw == LEAD_EXCLUDED) \
        & (np.asarray(cand.src_slot) == leader_slot[p])
    src_raw = assignment[p, np.maximum(np.asarray(cand.src_slot), 0)]
    assert (leader_moves & (src_raw != 2)).any()
    assert not valid[leader_moves & (src_raw != 2)].any()
    assert (valid & kind_move
            & (np.asarray(flat.dst_broker) == LEAD_EXCLUDED)).any()
    assert not (valid & ~kind_move
                & (np.asarray(flat.dst_broker) == LEAD_EXCLUDED)).any()
    # and the goals' lookups agree there too, on every valid candidate
    b_ids = jnp.arange(B)
    np.testing.assert_array_equal(np.asarray(grid.at_src(b_ids))[valid],
                                  src[valid])
    np.testing.assert_array_equal(np.asarray(grid.at_dst(b_ids))[valid],
                                  np.asarray(flat.dst_broker)[valid])


def _plain_deltas(state, derived, cand):
    """``compute_deltas``' rules one candidate at a time, in plain Python
    over numpy copies: what both forms have to return."""
    assignment = np.asarray(state.assignment)
    leader_slot = np.asarray(state.leader_slot)
    lead, foll = (np.asarray(state.leader_load),
                  np.asarray(state.follower_load))
    alive, may_lead, online_ok, offline_ok, movable = (
        np.asarray(x) for x in (
            derived.alive, derived.allowed_leadership,
            derived.replica_dest_ok, derived.allowed_replica_move,
            derived.movable_partition))
    b, r = len(alive), lead.shape[1]
    out = {k: np.zeros(cand.n, np.int32) for k in (
        "src_broker", "dst_broker", "replica_delta", "leader_delta",
        "src_slot", "dst_slot")}
    out["load_delta"] = np.zeros((cand.n, r), np.float32)
    out["valid"] = np.zeros(cand.n, bool)
    out["partition"] = np.asarray(cand.partition)
    out["topic"] = np.asarray(state.topic)[out["partition"]]
    fields = [np.asarray(x) for x in (cand.kind, cand.partition,
                                      cand.src_slot, cand.dst_broker,
                                      cand.dst_slot, cand.valid)]
    for i, (kind, p, c_slot, c_dst, d_slot, offered) in enumerate(
            zip(*fields)):
        move = kind == 0
        slot = c_slot if move else leader_slot[p]
        src = assignment[p, max(slot, 0)]
        dst = c_dst if move else assignment[p, max(d_slot, 0)]
        if not (offered and movable[p] and slot >= 0 and src >= 0
                and 0 <= dst < b and alive[dst]):
            continue
        is_leader = slot == leader_slot[p]
        offline = not alive[src]
        if move:
            ok = dst not in assignment[p] and src != dst \
                and (offline_ok[dst] if offline else online_ok[dst]) \
                and (not is_leader or offline or may_lead[dst])
            delta = lead[p] if is_leader else foll[p]
        else:
            ok = d_slot >= 0 and d_slot != leader_slot[p] \
                and may_lead[dst] and leader_slot[p] >= 0
            delta = lead[p] - foll[p]
        if ok:
            out["valid"][i] = True
            out["src_broker"][i], out["dst_broker"][i] = src, dst
            out["load_delta"][i] = delta
            out["replica_delta"][i] = int(move)
            out["leader_delta"][i] = int(is_leader or not move)
            out["src_slot"][i] = slot
            out["dst_slot"][i] = 0 if move else d_slot
    return out


def test_both_forms_return_what_the_plain_rules_return():
    """The legitimacy rules are stated once in ``compute_deltas``; this
    states them a second time, plainly, so that the one statement cannot
    drift unseen (the flat form is every other test's oracle)."""
    state, derived, cand, _layout, grid, flat = _edges_grid_and_flat()
    want = _plain_deltas(state, derived, cand)
    assert 0 < want["valid"].sum() < cand.n
    for form in (grid, flat):
        for name, value in want.items():
            np.testing.assert_array_equal(
                np.asarray(getattr(form, name)), value, err_msg=name)


@pytest.mark.parametrize("name", sorted(GOALS))
def test_goal_acceptance_and_improvement_equal_flat_form(name):
    goal = GOALS[name]
    state, derived, layout, grid, flat = _grid_and_flat()
    _state, meta = _cluster()
    constraint = BalancingConstraint()
    aux = goal_aux(goal, state, derived, constraint, meta.num_topics)
    valid = np.asarray(flat.valid)
    for method in ("acceptance", "improvement"):
        got = np.asarray(getattr(goal, method)(
            state, derived, constraint, aux, grid))
        want = np.asarray(getattr(goal, method)(
            state, derived, constraint, aux, flat))
        assert got.shape == want.shape == valid.shape
        for region_name, region in _regions(layout).items():
            m = valid & region
            np.testing.assert_array_equal(
                got[m], want[m], err_msg=f"{name}.{method}, {region_name}")
        # improvement marks invalid candidates itself; both forms agree
        if method == "improvement":
            assert (got[~valid] == -np.inf).all() \
                and (want[~valid] == -np.inf).all()


def test_selected_sub_batch_takes_the_flat_form():
    """``recheck``'s sub (the m selected candidates, with ``pre_*`` terms)
    re-indexes the [N] fields, so it must carry no grid."""
    state, _derived, layout, grid, _flat = _grid_and_flat()
    seen = []

    def recheck(sub, has_earlier):
        seen.append(sub)
        return jnp.ones(sub.valid.shape[0], dtype=bool)

    score = jnp.where(grid.valid, 1.0, -jnp.inf)
    cumulative_select(state, grid, score, layout, 16, 16, False, recheck,
                      extra_last_col=True)
    (sub,) = seen
    assert sub.grid is None
    assert sub.pre_dst_load is not None and sub.valid.shape == (16,)


def test_layout_other_than_move_plus_leadership_is_refused():
    state, derived, _layout, _grid, _flat = _grid_and_flat()
    cand, layout = generate_candidates(
        state, derived, jnp.ones(B), jnp.zeros(B),
        jnp.ones(state.assignment.shape), K_SRC, K_DST,
        include_leadership=False)
    assert len(layout) == 1
    with pytest.raises(ValueError, match="move \\+ leadership"):
        compute_deltas(state, derived, cand, layout)


CHAIN_CFG = SearchConfig(num_sources=32, num_dests=6, moves_per_round=32,
                         max_rounds=40)


def _default_chain():
    return tuple(goals_by_priority(CruiseControlConfig()))


def test_whole_chain_pass_same_with_layout_passed_and_withheld(monkeypatch):
    """One ``optimize_chain`` pass over the default chain: assignment,
    leader slots, rounds and per-goal stats do not depend on the form.
    A new broker on each of the four racks: while a broker is NEW replicas
    move only onto NEW brokers (``derived.replica_dest_ok``), and the
    cluster is drawn without regard to racks, so two would leave
    RackAwareGoal a partition it cannot repair."""
    state, meta = _cluster(new=(12, 13, 14, 15))
    goals = _default_chain()
    assert len(goals) == 15
    args = (state, goals, BalancingConstraint(), CHAIN_CFG, meta.num_topics,
            _masks())

    chain_optimize_full.clear_cache()
    st_grid, infos_grid = optimize_chain(*args)
    assert chain_mod.accept_lookup() == "grid"

    # undo() below also puts the traced form back for later tests
    monkeypatch.setattr(chain_mod, "_accept_lookup_traced", "grid")
    monkeypatch.setattr(
        chain_mod, "compute_deltas",
        lambda state, derived, cand, layout=None:
        compute_deltas(state, derived, cand))
    chain_optimize_full.clear_cache()
    try:
        st_flat, infos_flat = optimize_chain(*args)
        assert chain_mod.accept_lookup() == "flat"
    finally:
        monkeypatch.undo()
        chain_optimize_full.clear_cache()

    np.testing.assert_array_equal(np.asarray(st_grid.assignment),
                                  np.asarray(st_flat.assignment))
    np.testing.assert_array_equal(np.asarray(st_grid.leader_slot),
                                  np.asarray(st_flat.leader_slot))
    assert sum(i["rounds"] for i in infos_grid) > len(goals)
    assert infos_grid == infos_flat


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _per_candidate_gathers(jaxpr, n, scope, inside=False):
    """Gather equations under ``scope`` whose output has one row per
    candidate (leading dimension ``n``), through every nested jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here and eqn.primitive.name == "gather":
            shape = eqn.outvars[0].aval.shape
            if shape and shape[0] == n:
                found.append(eqn)
        for sub in _sub_jaxprs(eqn):
            found += _per_candidate_gathers(sub, n, scope, here)
    return found


@pytest.mark.parametrize("scope, most", [("round.accept", 8),
                                         ("round.deltas", 0)])
def test_round_accept_does_not_gather_per_candidate(scope, most):
    """CPU, jaxpr walk, no compile: in the round body with the default
    chain at most 8 gathers under ``round.accept`` have one output row per
    candidate (212 before the grid: 76 in the acceptance stack, 136 in the
    improvement branches), and none under ``round.deltas`` (15 while the
    deltas were gathered per candidate, PR 35). A goal, or a field of the
    deltas, that indexes by candidate again fails here, not in a
    benchmark."""
    state, meta = _cluster()
    goals = _default_chain()
    cfg = SearchConfig(num_sources=32, num_dests=6, moves_per_round=32,
                       max_rounds=1)
    # no other axis of the program has this length
    n = 32 * (6 + 1) + 32 * 3
    assert n not in (B, meta.num_topics, state.num_partitions,
                     state.num_partitions * 3, 32, 6, 7, 32 + 32,
                     6 + 32 + 32 * 3)

    def body(state, active_idx, prior_mask):
        return _chain_round_body(
            state, None, active_idx, prior_mask, goals,
            BalancingConstraint(), cfg, meta.num_topics, _masks())

    jaxpr = jax.make_jaxpr(body)(
        state, jnp.int32(0), jnp.zeros(len(goals), bool))
    per_candidate = _per_candidate_gathers(jaxpr.jaxpr, n, scope)
    assert len(per_candidate) <= most, [str(e) for e in per_candidate]


def test_the_walker_sees_the_flat_forms_gathers():
    """What the walk above counts is there to be counted: without a
    layout ``compute_deltas`` gathers once per candidate, with one it
    gathers on the margins only."""
    state, derived, cand, layout, _grid, _flat = _edges_grid_and_flat()

    def walk(*layout_or_none):
        jaxpr = jax.make_jaxpr(
            lambda st, dv, c: compute_deltas(st, dv, c, *layout_or_none))(
                state, derived, cand)
        return _per_candidate_gathers(jaxpr.jaxpr, cand.n, "round.deltas")

    assert len(walk()) >= 10
    assert walk(layout) == []


@pytest.mark.parametrize("case", ("accept_margin", "accept_packed",
                                  "deltas_margin"))
def test_microbench_accept_forms_compute_the_same(case):
    """The microbench's lookup forms (utils/microbench.py ``accept_*``,
    ``deltas_*``) price the SAME work: every form leaves the carry its
    flat form leaves."""
    from cruise_control_tpu.utils.microbench import _build_cases
    run, inputs = _build_cases(64, 64)
    flat = case.split("_")[0] + "_flat"
    want = np.asarray(run(inputs[flat], 2, flat))
    assert (want != np.asarray(inputs[flat])).any()
    np.testing.assert_array_equal(
        np.asarray(run(inputs[case], 2, case)), want)


INNER_SCORE_SCOPES = ("round.score_derived", "round.score_goals",
                      "round.score_offline")


def test_the_names_inside_round_score_are_metadata_only(monkeypatch):
    """``round.score`` carries three names inside it (PR 36) so that a
    profile says what inside it costs. The lowered text of the scoring
    half is the same with and without them once locations are left out:
    the compile cache's key ignores them, so no deployment pays a cold
    compile for the names."""
    import contextlib

    state, meta = _cluster()
    goals = _default_chain()
    cfg = SearchConfig(num_sources=32, num_dests=6, moves_per_round=32,
                       max_rounds=1)

    def lowered():
        # a function of its own each time: jit keeps the traced jaxpr,
        # names and all, by the function it was given
        def half(state, active_idx, prior_mask):
            sc = chain_mod._scored_candidates(
                state, None, active_idx, prior_mask, goals,
                BalancingConstraint(), cfg, meta.num_topics, _masks(),
                global_partitions=state.num_partitions)
            return sc.score, sc.accept

        return jax.jit(half).lower(state, jnp.int32(0),
                                   jnp.zeros(len(goals), bool))

    named = lowered()
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: contextlib.nullcontext() if name in INNER_SCORE_SCOPES
        else real(name))
    plain = lowered()
    monkeypatch.undo()
    with_names = named.as_text(debug_info=True)
    for scope in INNER_SCORE_SCOPES:
        assert f"round.score/{scope}/" in with_names
        assert scope not in plain.as_text(debug_info=True)
    assert "round.score/" in plain.as_text(debug_info=True)
    assert named.as_text() == plain.as_text()
