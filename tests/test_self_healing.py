"""The self-healing priority read from the carry (analyzer/chain.py
``_self_healing``, docs/DESIGN.md "The move round"): the per-broker term is
the DEAD brokers' replica counts, which the aggregate carry holds, and the
per-slot mask is built only while a replica is offline, inside a ``cond``.
The formula it replaced stays here as the oracle: the same numbers on a
drain, a healthy cluster and a scale-out, under both per-broker reduction
forms, the same plan from the fused chain and from the mesh's move rounds,
and a body in which a healthy round walks no replica for it.
"""

import contextlib
from functools import partial

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from cruise_control_tpu.analyzer import candidates as cand_mod
from cruise_control_tpu.analyzer import chain as chain_mod
from cruise_control_tpu.analyzer.agg import compute_agg
from cruise_control_tpu.analyzer.chain import (
    _chain_round_body, _scored_candidates, chain_optimize_full,
    optimize_chain,
)
from cruise_control_tpu.analyzer.constraint import (
    BalancingConstraint, OptimizationOptions,
)
from cruise_control_tpu.analyzer.derived import compute_derived, healing
from cruise_control_tpu.analyzer.goals import ReplicaDistributionGoal
from cruise_control_tpu.analyzer.optimizer import (
    ensure_evacuated, goals_by_priority,
)
from cruise_control_tpu.analyzer.search import ExclusionMasks, SearchConfig
from cruise_control_tpu.config.cruise_control_config import (
    CruiseControlConfig,
)
from cruise_control_tpu.model import tensors
from cruise_control_tpu.model.fixtures import random_cluster
from cruise_control_tpu.model.tensors import (
    BrokerState, offline_per_broker, offline_replicas, set_broker_state,
)
from cruise_control_tpu.parallel import make_mesh, shard_cluster
from cruise_control_tpu.parallel import chain_sharded
from cruise_control_tpu.parallel.mesh import _mask_specs, _psum, _state_specs
from cruise_control_tpu.utils.sensors import SENSORS
from cruise_control_tpu.utils.tracing import TRACER

B, TOPICS, PARTITIONS = 16, 5, 128
FIXTURES = ("drain", "healthy", "scale-out")
FORMS = ("segment", "dense")
CFG = SearchConfig(num_sources=32, num_dests=6, moves_per_round=32,
                   max_rounds=40)
CHAIN = tuple(goals_by_priority(CruiseControlConfig()))


def _cluster(name):
    """Two DEAD brokers (a drain), none, or a NEW broker on each of the
    four racks (a scale-out: replicas move only onto NEW brokers, and two
    would leave RackAwareGoal a partition it cannot repair)."""
    state, meta = random_cluster(num_brokers=B, num_topics=TOPICS,
                                 num_partitions=PARTITIONS, rf=3,
                                 num_racks=4, seed=7, skew_to_first=2.0)
    if name == "drain":
        state = set_broker_state(state, jnp.asarray([2, 9]),
                                 BrokerState.DEAD)
    elif name == "scale-out":
        state = set_broker_state(state, jnp.asarray([12, 13, 14, 15]),
                                 BrokerState.NEW)
    assert (int(offline_replicas(state).sum()) > 0) == (name == "drain")
    return state, meta


def _old_self_healing(state, derived, src_score, weight, is_lead_only,
                      psum=None):
    """The formula ``_self_healing`` replaced (PR 36's
    ``round.score_offline``): the [P, S] mask every round, its per-broker
    reduction over the flat replica axis (``psum``'d on a mesh), and the
    targets' pause from the mask."""
    off = offline_replicas(state)
    offline_pb = offline_per_broker(state, off)
    if psum is not None:
        offline_pb = psum(offline_pb)
    src_score = src_score + jnp.where(is_lead_only, 0.0, offline_pb)
    weight = jnp.where(off & ~is_lead_only, 1e30, weight)
    offline = off.any() if psum is None else psum(off.sum()) > 0
    return src_score, weight, offline, offline & ~is_lead_only


@contextlib.contextmanager
def _old_formula(psum=None):
    """Every route's scoring half on the old formula while inside; the
    caller drops what was traced meanwhile."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(chain_mod, "_self_healing",
                   partial(_old_self_healing, psum=psum))
        yield


@contextlib.contextmanager
def _form(form):
    """Steer the one chooser of the per-broker reduction form, for every
    module that imported it (test_broker_reductions.py's ``_force``)."""
    with pytest.MonkeyPatch.context() as mp:
        for mod in (tensors, cand_mod):
            mp.setattr(mod, "broker_reduce_form", lambda b, n: form)
        yield


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("name", FIXTURES)
def test_the_carry_term_is_the_old_per_broker_reduction(name, form):
    """The DEAD brokers' replica counts, from the carry or recomputed,
    equal ``offline_per_broker`` over ``offline_replicas`` in both forms,
    and ``_self_healing`` returns the old formula's scores, weights and
    predicate for a move goal and a leadership-only one."""
    state, meta = _cluster(name)
    rng = np.random.default_rng(3)
    src_score = jnp.asarray(rng.uniform(-1.0, 1.0, B), jnp.float32)
    weight = jnp.asarray(rng.uniform(1.0, 2.0, state.assignment.shape),
                         jnp.float32)
    with _form(form):
        old_pb = np.asarray(offline_per_broker(state,
                                               offline_replicas(state)))
        for agg in (compute_agg(state, meta.num_topics), None):
            derived = compute_derived(state, agg=agg)
            new_pb = np.where(np.asarray(derived.alive), 0.0,
                              np.asarray(derived.broker_replicas))
            np.testing.assert_array_equal(new_pb, old_pb)
            assert bool(healing(derived)) == (name == "drain")
            for lead_only in (False, True):
                args = (state, derived, src_score, weight,
                        jnp.bool_(lead_only))
                got = chain_mod._self_healing(*args)
                want = _old_self_healing(*args)
                for a, b in zip(got, want, strict=True):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
    assert (old_pb.sum() > 0) == (name == "drain")


def _scoring_half(goal_idx, num_topics):
    """The one-chip scoring half for goal ``goal_idx`` of the default
    chain under its prior goals: (score, accept, the deltas' leaves, the
    frame's moving-offline flag, the old one)."""
    prior = jnp.arange(len(CHAIN)) < goal_idx

    def half(state):
        sc = _scored_candidates(
            state, compute_agg(state, num_topics), jnp.int32(goal_idx),
            prior, CHAIN, BalancingConstraint(), CFG, num_topics,
            ExclusionMasks(), global_partitions=state.num_partitions)
        d = sc.deltas
        moving = d.replica_delta > 0
        return (sc.score, sc.accept, jax.tree.leaves(d.without_grid()),
                d.src_offline & moving,
                d.at_src_slot(offline_replicas(state)) & moving)

    return jax.jit(half)


@pytest.mark.parametrize("name", FIXTURES)
def test_the_frames_moving_offline_flag_and_the_scoring_half(name):
    """``deltas.src_offline`` (the frame's per-row lookup of the source's
    dead bit) gives the old ``at_src_slot(offline_replicas)`` on every
    candidate, and the scoring half returns the old formula's scores,
    acceptance and deltas, byte for byte."""
    state, meta = _cluster(name)
    goal_idx = next(i for i, g in enumerate(CHAIN)
                    if isinstance(g, ReplicaDistributionGoal))
    score, accept, leaves, moving_new, moving_old = \
        _scoring_half(goal_idx, meta.num_topics)(state)
    with _old_formula():
        old = _scoring_half(goal_idx, meta.num_topics)(state)
    np.testing.assert_array_equal(np.asarray(moving_new),
                                  np.asarray(moving_old))
    assert np.asarray(moving_new).any() == (name == "drain")
    assert np.isfinite(np.asarray(score)).any()
    for a, b in zip(jax.tree.leaves((score, accept, leaves)),
                    jax.tree.leaves(old[:3]), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def plans():
    """name -> ((state, infos, dispatch attributes, counter delta) of the
    fused chain, (state, infos) of the old formula's): one compile a side,
    the three fixtures share the shapes."""
    def one_pass(name, with_counter):
        state, meta = _cluster(name)
        before = SENSORS.counter_total("solver_healing_rounds")
        with TRACER.span("test.pass") as root:
            out, infos = optimize_chain(state, CHAIN, BalancingConstraint(),
                                        CFG, meta.num_topics)
            if with_counter:
                ensure_evacuated(CHAIN, infos, lambda: out, meta,
                                 OptimizationOptions(), root)
        (dispatch,) = [c for c in root.children
                       if c.name == "solver.dispatch"]
        delta = SENSORS.counter_total("solver_healing_rounds") - before
        return out, infos, dict(dispatch.attributes), delta

    chain_optimize_full.clear_cache()
    new = {n: one_pass(n, True) for n in FIXTURES}
    chain_optimize_full.clear_cache()
    try:
        with _old_formula():
            old = {n: one_pass(n, False)[:2] for n in FIXTURES}
    finally:
        chain_optimize_full.clear_cache()
    return {n: (new[n], old[n]) for n in FIXTURES}


@pytest.mark.parametrize("name", FIXTURES)
def test_fused_chain_plan_equals_the_old_formulas(plans, name):
    (st, infos, _attrs, _delta), (st_old, infos_old) = plans[name]
    np.testing.assert_array_equal(np.asarray(st.assignment),
                                  np.asarray(st_old.assignment))
    np.testing.assert_array_equal(np.asarray(st.leader_slot),
                                  np.asarray(st_old.leader_slot))
    assert sum(i["rounds"] for i in infos) > len(CHAIN)
    assert infos == infos_old
    assert int(offline_replicas(st).sum()) == 0


@pytest.mark.parametrize("name", FIXTURES)
def test_healing_rounds_counted_where_a_replica_was_offline(plans, name):
    """``solver_healing_rounds_total`` and the dispatch's
    ``healing_rounds``: 0 where no broker is DEAD; in a drain more than 0
    and no more than the pass's evacuation rounds (every round of a goal
    entered with a replica offline, swap rounds too)."""
    (_st, infos, attrs, delta), _old = plans[name]
    healing_rounds = sum(i["healing_rounds"] for i in infos)
    evacuation = sum(i["rounds"] for i in infos if i["offline_before"] > 0)
    assert attrs["healing_rounds"] == healing_rounds == delta
    if name == "drain":
        assert 0 < healing_rounds <= evacuation
    else:
        assert healing_rounds == 0 == evacuation


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _under(jaxpr, scope, inside=False, in_cond=False):
    """(eqn, inside a cond?) for every equation under ``scope``, through
    every nested jaxpr."""
    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn, in_cond
        for sub in _sub_jaxprs(eqn):
            yield from _under(sub, scope, here,
                              in_cond or eqn.primitive.name == "cond")


def _walks_replicas(eqn, p, s, b):
    """The equation builds the per-slot dead bits (a [P, S] gather) or
    reduces them per broker ([B, n_flat] compare-and-sum, or the
    segment-sum into B + 1 buckets)."""
    n_flat = p * s
    shapes = [v.aval.shape for v in eqn.invars if hasattr(v, "aval")] \
        + [v.aval.shape for v in eqn.outvars]
    if eqn.primitive.name == "gather":
        return eqn.outvars[0].aval.shape in ((p, s), (n_flat,))
    if eqn.primitive.name.startswith("scatter"):
        return (b + 1,) in shapes
    return (b, n_flat) in shapes


def _round_jaxpr(state, meta):
    prior = jnp.zeros(len(CHAIN), bool)
    cfg = SearchConfig(num_sources=32, num_dests=6, moves_per_round=32,
                       max_rounds=1)

    def body(state, active_idx):
        return _chain_round_body(
            state, compute_agg(state, meta.num_topics), active_idx, prior,
            CHAIN, BalancingConstraint(), cfg, meta.num_topics,
            ExclusionMasks(), stats="tally")

    return jax.make_jaxpr(body)(state, jnp.int32(0)).jaxpr


@pytest.mark.parametrize("form", FORMS)
def test_a_healthy_round_walks_no_replica_outside_the_cond(form):
    """CPU, jaxpr walk, no compile: under ``round.score_offline`` the
    round body holds no [B, n_flat] reduction and no segment-sum, and its
    [P, S] gather of the dead bits sits in a ``cond`` branch only. The old
    formula, walked the same way, shows both outside any ``cond``: the
    walk sees what it counts."""
    state, meta = _cluster("healthy")
    p, s = state.assignment.shape

    def walk():
        with _form(form):
            jaxpr = _round_jaxpr(state, meta)
        found = [(e, c) for e, c in _under(jaxpr, "round.score_offline")
                 if _walks_replicas(e, p, s, B)]
        return ([e for e, c in found if not c],
                [e for e, c in found if c],
                [e for e, _c in _under(jaxpr, "round.score_offline")
                 if e.primitive.name == "cond"])

    outside, in_branch, conds = walk()
    assert outside == [], [str(e) for e in outside]
    assert len(conds) == 1
    assert any(e.primitive.name == "gather" for e in in_branch)
    with _old_formula():
        outside_old, _in_branch, conds_old = walk()
    assert conds_old == []
    kinds = {e.primitive.name for e in outside_old}
    assert "gather" in kinds
    assert kinds - {"gather"}, kinds     # the per-broker reduction


COLLECTIVES = {"psum", "psum2", "pmax", "pmin", "ppermute", "all_gather",
               "all_to_all", "psum_scatter", "reduce_scatter",
               "psum_invariant", "pbroadcast"}


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in _sub_jaxprs(eqn):
            yield from _primitives(sub)


def _mesh_moves(mesh, state, meta, rounds):
    """``rounds`` of the mesh's move kernel for ReplicaDistributionGoal
    alone: the final assignment."""
    goals = (ReplicaDistributionGoal(),)
    move = chain_sharded._make_chain_phase_kernels.__wrapped__(
        mesh, goals, BalancingConstraint(), CFG, meta.num_topics,
        (False, False, False), 8, 64)[0]
    out, applied, ran = move(shard_cluster(state, mesh), ExclusionMasks(),
                             jnp.int32(0), jnp.asarray([False]),
                             jnp.int32(rounds))
    assert int(applied) > 0 and int(ran) == rounds
    return np.asarray(jax.device_get(out.assignment))


def test_the_mesh_body_has_no_collective_in_either_branch_and_the_old_plan():
    """The mesh round body (``_chain_round_local`` under ``shard_map`` on
    the 8 virtual devices): the ``cond`` under ``round.score_offline``
    holds no collective in either branch (the body holds them elsewhere),
    and a drain's move rounds place every replica where the old formula,
    with its ``psum``s, placed it."""
    assert len(jax.devices()) >= 8
    mesh = make_mesh(8)
    state, meta = _cluster("drain")
    goals = (ReplicaDistributionGoal(),)

    def local(st, masks):
        agg = compute_agg(st, meta.num_topics, psum=_psum)
        ns, _agg, applied = chain_sharded._chain_round_local(
            st, agg, masks, jnp.int32(0), jnp.asarray([False]), goals=goals,
            constraint=BalancingConstraint(), cfg=CFG,
            num_topics=meta.num_topics, num_shards=8)
        return ns.assignment, applied

    mapped = shard_map(local, mesh=mesh,
                       in_specs=(_state_specs(),
                                 _mask_specs((False, False, False))),
                       out_specs=(P("p"), P()), check_vma=False)
    jaxpr = jax.make_jaxpr(mapped)(shard_cluster(state, mesh),
                                   ExclusionMasks()).jaxpr
    assert set(_primitives(jaxpr)) & COLLECTIVES
    conds = [e for e, _c in _under(jaxpr, "round.score_offline")
             if e.primitive.name == "cond"]
    assert len(conds) == 1
    for branch in _sub_jaxprs(conds[0]):
        assert not set(_primitives(branch)) & COLLECTIVES

    new = _mesh_moves(mesh, state, meta, 6)
    with _old_formula(psum=_psum):
        old = _mesh_moves(mesh, state, meta, 6)
    np.testing.assert_array_equal(new, old)
    assert (new != np.asarray(state.assignment)).any()
