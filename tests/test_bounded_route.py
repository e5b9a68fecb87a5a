"""The bounded per-goal route with the wide grids, at a CPU size: a 32-broker
cluster takes it once both 512-broker switches
(``solver.fused.chain.max.brokers``, ``solver.wide.batch.min.brokers``) are
lowered through the optimizer's config, as a 1,000-broker cluster takes it
with the defaults.

Every pass over the same model gives the same plan, whatever budgets the
dispatch controller chose, for a rebalance and for a drain (three brokers
removed: DEAD, excluded from replica moves and leadership, as
``facade.remove_brokers`` marks them): a dispatch resumes its pass from
the previous dispatch's carry (``chain.PassCarry``: the aggregates, the
pass's round count, the last round's applied count, its tallies), so a
split pass walks the unsplit loop's rounds, and a dispatch enqueued after
the pass's fixed point runs none. The dispatch series carry a ``grid``
label, the bounded route counts its healing rounds, and the bounded
``solver.dispatch`` spans say what the pump did."""

import re

import numpy as np
import pytest

from cruise_control_tpu.analyzer.constraint import OptimizationOptions
from cruise_control_tpu.analyzer.optimizer import (
    GoalOptimizer, goals_by_priority,
)
from cruise_control_tpu.common.broker_state import BrokerState
from cruise_control_tpu.config.cruise_control_config import (
    CruiseControlConfig,
)
from cruise_control_tpu.model.fixtures import random_cluster
from cruise_control_tpu.model.tensors import set_broker_state
from cruise_control_tpu.utils.sensors import SENSORS
from cruise_control_tpu.utils.tracing import TRACER

LOWERED = {"solver.fused.chain.max.brokers": "16",
           "solver.wide.batch.min.brokers": "16",
           # a cap the parent's budget-1 passes reach (they never saw
           # their fixed point), so the comparison ends in seconds
           "max.solver.rounds": "200"}
_SERIES = re.compile(r"^kafka_cruisecontrol_(\w+?)\{([^}]*)\} (\S+)$")


# A heavy, a middle and a light broker of the placement skew, on three racks.
REMOVED = (2, 13, 27)


@pytest.fixture(scope="module")
def cluster():
    return random_cluster(num_brokers=32, num_topics=4, num_partitions=960,
                          rf=3, num_racks=8, seed=5, skew_to_first=2.0)


@pytest.fixture(scope="module", params=["rebalance", "drain"])
def load(request, cluster):
    """(state, meta, options) of a pass: the cluster as it is, or with
    ``REMOVED`` marked as ``facade.remove_brokers`` marks them."""
    state, meta = cluster
    if request.param == "rebalance":
        return state, meta, None
    for b in REMOVED:
        state = set_broker_state(state, np.int32(b), int(BrokerState.DEAD))
    ids = tuple(meta.broker_ids[b] for b in REMOVED)
    return state, meta, OptimizationOptions(
        excluded_brokers_for_replica_move=ids,
        excluded_brokers_for_leadership=ids)


def solve(load, settings, passes=1):
    """Plans of ``passes`` passes on ONE optimizer (its dispatch
    controllers persist from pass to pass, as the served path's do)."""
    state, meta, options = load
    cfg = CruiseControlConfig(settings)
    opt = GoalOptimizer(cfg)
    out = []
    for _ in range(passes):
        _final, res = opt.optimizations(state, meta,
                                        goals=goals_by_priority(cfg),
                                        options=options)
        out.append(res)
    return out


def plan(res):
    """What a served body holds of a pass, less its times."""
    moves = sorted((p.topic, p.partition, tuple(p.old_replicas),
                    tuple(p.new_replicas), p.old_leader, p.new_leader)
                   for p in res.proposals)
    rounds = [(g.name, g.rounds, g.moves_applied, g.succeeded)
              for g in res.goal_results]
    return moves, rounds, res.balancedness_after


def series(name):
    """{labels string: value} of one series of the exposition."""
    out = {}
    for line in SENSORS.render().splitlines():
        m = _SERIES.match(line)
        if m and m.group(1) == name:
            out[m.group(2)] = float(m.group(3))
    return out


def moved(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def counted(settings, load, passes):
    """Passes of ``solve`` with the healing and evacuation rounds they
    added to the counters: (results, {grid: healing rounds}, evacuation
    rounds)."""
    healing = series("solver_healing_rounds_total")
    evacuation = SENSORS.counter_total("solver_evacuation_rounds")
    results = solve(load, settings, passes)
    by_grid = moved(healing, series("solver_healing_rounds_total"))
    return (results, by_grid,
            SENSORS.counter_total("solver_evacuation_rounds") - evacuation)


def check_healing(load, by_grid, evacuation):
    """A drain's bounded passes heal in some of their evacuation rounds and
    in no other; a rebalance heals in none."""
    assert set(by_grid) <= {'grid="narrow"', 'grid="wide"'}, by_grid
    healed = sum(by_grid.values())
    if load[2] is None:
        assert healed == 0 == evacuation
    else:
        assert 0 < healed <= evacuation


@pytest.fixture(scope="module")
def unsplit(load):
    """The plan of passes that one dispatch runs whole (budget 1,024, the
    controller's ceiling, held fixed)."""
    res, = solve(load, {**LOWERED, "solver.dispatch.max.rounds": "1024",
                        "solver.dispatch.target.seconds": "0"})
    return plan(res)


@pytest.mark.parametrize("budget", [1, 4, 16])
def test_the_plan_repeats_at_any_dispatch_budget(load, unsplit, budget):
    """Two passes at a fixed budget of 1, 4 or 16 rounds a dispatch give
    the plan of the unsplit passes, move for move and round for round."""
    (first, second), by_grid, evacuation = counted({
        **LOWERED, "solver.dispatch.max.rounds": str(budget),
        "solver.dispatch.target.seconds": "0"}, load, passes=2)
    assert plan(first) == plan(second) == unsplit
    assert first.proposals and sum(r for _g, r, _m, _s in unsplit[1]) > 16
    check_healing(load, by_grid, evacuation)


def test_the_plan_repeats_under_the_adaptive_controller(load, unsplit):
    """A controller that doubles its budget after every full dispatch
    splits each pass, and the second pass, elsewhere: the same plan."""
    (first, second), by_grid, evacuation = counted({
        **LOWERED, "solver.dispatch.max.rounds": "1",
        "solver.dispatch.target.seconds": "1000000"}, load, passes=2)
    assert plan(first) == plan(second) == unsplit
    check_healing(load, by_grid, evacuation)


@pytest.mark.parametrize("wide_from", ["16", "0"])
def test_wide_rounds_are_counted_where_the_wide_grid_ran(cluster, wide_from):
    """``solver_dispatch_rounds{grid="wide"}`` sums the rounds of exactly
    the goals the optimizer widened (``_wide_config``: the goals that
    prefer wide batches, from ``solver.wide.batch.min.brokers`` on; 0
    widens none), ``grid="narrow"`` the others'; speculative dispatches
    add none."""
    cfg = CruiseControlConfig({**LOWERED,
                               "solver.wide.batch.min.brokers": wide_from})
    prefers = {g.name: g.prefers_wide_batches for g in goals_by_priority(cfg)}
    before = series("solver_dispatch_rounds_sum")
    res, = solve((*cluster, None),
                 {**LOWERED, "solver.wide.batch.min.brokers": wide_from})
    rounds = moved(before, series("solver_dispatch_rounds_sum"))
    by_grid = {grid: sum(v for k, v in rounds.items()
                         if f'grid="{grid}"' in k) for grid in
               ("narrow", "wide", "fused")}
    wide = sum(g.rounds for g in res.goal_results if prefers[g.name])
    narrow = sum(g.rounds for g in res.goal_results if not prefers[g.name])
    if wide_from == "0":
        narrow, wide = narrow + wide, 0
    assert wide_from == "0" or wide > 0
    assert by_grid == {"narrow": narrow, "wide": wide, "fused": 0}
    assert all('grid="' in k for k in rounds)


def test_the_fused_route_labels_its_dispatch_fused(cluster):
    before = series("solver_dispatches_total")
    rounds_before = series("solver_dispatch_rounds_sum")
    res, = solve((*cluster, None), {})
    assert moved(before, series("solver_dispatches_total")) \
        == {'grid="fused",kind="chain"': 1.0}
    assert moved(rounds_before, series("solver_dispatch_rounds_sum")) \
        == {'grid="fused",kind="chain"':
            float(sum(g.rounds for g in res.goal_results))}
    # the whole chain's healing rounds are counted under the same label
    assert 'grid="fused"' in series("solver_healing_rounds_total")


def spans(node):
    yield node
    for child in node.get("children", ()):
        yield from spans(child)


def attributes(span):
    """A span's attributes as the values it was given (OTLP JSON wraps
    each, an int as a string)."""
    out = {}
    for a in span["attributes"]:
        (kind, v), = a["value"].items()
        out[a["key"]] = int(v) if kind == "intValue" else v
    return out


def test_the_bounded_spans_say_what_the_pump_did(cluster):
    """Each bounded ``solver.dispatch`` span (one a pass) carries its
    ``grid`` (the goal's), ``pass_rounds`` (the rounds the pass searched),
    ``speculative`` (the pump's dispatches after the fixed point: at most
    one), ``budget_max`` (the largest budget a dispatch had) and the
    whole pass's ``healing_rounds``."""
    was = TRACER.enabled
    TRACER.configure(enabled=True)
    try:
        TRACER.clear()
        cfg = CruiseControlConfig({**LOWERED,
                                   "solver.dispatch.max.rounds": "4",
                                   "solver.dispatch.target.seconds": "0"})
        prefers = {g.name: g.prefers_wide_batches
                   for g in goals_by_priority(cfg)}
        res, = solve((*cluster, None),
                     {**LOWERED, "solver.dispatch.max.rounds": "4",
                      "solver.dispatch.target.seconds": "0"})
        trace, = TRACER.traces(limit=1)
    finally:
        TRACER.configure(enabled=was)
    rounds = {}
    for goal in spans(trace["root"]):
        if goal["name"] != "goal.solve":
            continue
        attrs = attributes(goal)
        for s in spans(goal):
            if s["name"] != "solver.dispatch":
                continue
            a = attributes(s)
            assert a["route"] == "bounded"
            assert a["grid"] == ("wide" if prefers[attrs["goal"]]
                                 else "narrow")
            assert a["pass_rounds"] == a["rounds"]
            assert a["speculative"] in (0, 1)
            assert a["budget_max"] == 4
            assert a["healing_rounds"] == 0     # no broker is DEAD
            rounds[attrs["goal"]] = rounds.get(attrs["goal"], 0) \
                + a["pass_rounds"]
    assert rounds == {g.name: g.rounds for g in res.goal_results
                      if g.rounds}
    assert np.any([r > 4 for r in rounds.values()])
