"""Grid lookups (analyzer/candidates.py CandidateGrid, docs/DESIGN.md "Grid
lookups"): a goal reads per-broker, per-topic and per-partition tables
through ``deltas.at_*``; with the candidate grid's margins attached the
lookup runs there and is broadcast, without them it is the per-candidate
gather. The two forms must agree exactly on every valid candidate, walk
the same trajectory, and the round body must not gather per candidate.
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


def _grid_and_flat():
    """The chain round body's uniform grid on the fixture (move block with
    a targeted column, leadership block), as grid-attached and as plain
    deltas. Sources and destinations are spread so every region holds
    valid candidates."""
    state, _meta = _cluster()
    masks = _masks()
    derived = compute_derived(state, masks.excluded_topics,
                              masks.excluded_replica_move_brokers,
                              masks.excluded_leadership_brokers)
    src_score = jnp.ones(B)
    dst_score = jnp.where(derived.allowed_replica_move,
                          -derived.broker_replicas.astype(jnp.float32),
                          -jnp.inf)
    rng = np.random.default_rng(0)
    weight = jnp.asarray(rng.uniform(1.0, 2.0, state.assignment.shape),
                         jnp.float32)
    _p, _s, src_valid, _on = select_sources(state, src_score, weight, K_SRC)
    targeted = (jnp.asarray(rng.integers(0, B, K_SRC), jnp.int32),
                src_valid)
    cand, layout = generate_candidates(
        state, derived, src_score, dst_score, weight, K_SRC, K_DST,
        include_leadership=True, extra_dst=targeted)
    assert layout == ((K_SRC, K_DST + 1), (K_SRC, 3))
    grid = compute_deltas(state, derived, cand, layout)
    flat = compute_deltas(state, derived, cand)
    assert grid.grid is not None and flat.grid is None
    return state, derived, layout, grid, flat


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
    them), and the margins reproduce them on every valid candidate."""
    _state, _derived, layout, grid, flat = _grid_and_flat()
    for f in dataclasses.fields(flat):
        if f.name != "grid" and getattr(flat, f.name) is not None:
            np.testing.assert_array_equal(
                np.asarray(getattr(grid, f.name)),
                np.asarray(getattr(flat, f.name)), err_msg=f.name)
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


def test_round_accept_does_not_gather_per_candidate():
    """CPU, jaxpr walk, no compile: in the round body with the default
    chain at most 8 gathers under ``round.accept`` have one output row per
    candidate (212 before the grid: 76 in the acceptance stack, 136 in the
    improvement branches). A goal that indexes by candidate again fails
    here, not in a benchmark."""
    state, meta = _cluster()
    goals = _default_chain()
    cfg = SearchConfig(num_sources=32, num_dests=6, moves_per_round=32,
                       max_rounds=1)
    # no other axis of the program has this length
    n = 32 * (6 + 1) + 32 * 3
    assert n not in (B, meta.num_topics, state.num_partitions,
                     state.num_partitions * 3, 32, 6, 7)

    def body(state, active_idx, prior_mask):
        return _chain_round_body(
            state, None, active_idx, prior_mask, goals,
            BalancingConstraint(), cfg, meta.num_topics, _masks())

    jaxpr = jax.make_jaxpr(body)(
        state, jnp.int32(0), jnp.zeros(len(goals), bool))
    per_candidate = _per_candidate_gathers(jaxpr.jaxpr, n, "round.accept")
    assert len(per_candidate) <= 8, [str(e) for e in per_candidate]
    # the walker does see per-candidate gathers where they remain
    assert _per_candidate_gathers(jaxpr.jaxpr, n, "round.deltas")


@pytest.mark.parametrize("case", ("accept_margin", "accept_packed"))
def test_microbench_accept_forms_compute_the_same(case):
    """The microbench's lookup forms (utils/microbench.py ``accept_*``)
    price the SAME work: every form leaves the carry the flat form
    leaves."""
    from cruise_control_tpu.utils.microbench import _build_cases
    run, inputs = _build_cases(64, 64)
    want = np.asarray(run(inputs["accept_flat"], 2, "accept_flat"))
    assert (want != np.asarray(inputs["accept_flat"])).any()
    np.testing.assert_array_equal(
        np.asarray(run(inputs[case], 2, case)), want)
