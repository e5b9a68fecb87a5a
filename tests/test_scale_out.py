"""The scale-out held to its rule: ``add_brokers`` through the facade on
seeded deployments of ``benchmarks/benchlib/deployment.py`` (16 brokers /
512 partitions placed by Kafka's own rack-aware assignor, the last two or
four brokers new and empty), on the routes ``tests/test_drain.py`` runs,
against the plain sequential fill of ``benchlib/scaleout_reference.py``
(numpy, imports nothing of the program). Upstream documents of ``POST
/add_broker`` that replicas move only from the existing brokers onto the
new ones: every goal, swap and transport keeps to it
(``analyzer/derived.py:replica_dest_ok``), and a plan that does not raises
``OptimizationFailureError`` and is not returned."""

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import drain_reference, reference, scaleout_reference  # noqa: E402
from benchlib.deployment import build  # noqa: E402

from cruise_control_tpu.analyzer import OptimizationFailureError  # noqa: E402
from cruise_control_tpu.analyzer import chain as chain_module  # noqa: E402
from cruise_control_tpu.analyzer import optimizer as optimizer_module  # noqa: E402
from cruise_control_tpu.api import responses  # noqa: E402
from cruise_control_tpu.utils.tracing import TRACER  # noqa: E402
from tests.test_drain import (  # noqa: E402
    GUARANTEES, ROUTES, ROUTES_BEYOND_ONE_CLUSTER, counter, facade, spans,
)

DEPLOYMENT = {"brokers": 16, "partitions": 512, "topics": 4,
              "replication_factor": 3, "racks": 8, "placement_skew": 1.0,
              "load_skew": 3.0, "target_utilization": 0.4, "instance_seed": 0,
              "placement": "kafka_rack_aware", "operation": "add_broker"}
# 14 + 2: the new brokers sit on racks 6 and 7, which keep one old broker
# each; 12 + 4: racks 4 to 7, whose only brokers the new ones are then.
NEW = ([14, 15], [12, 13, 14, 15])
# The program against the reference, in replicas placed on the new brokers.
# The reference stops at the lower edge of ReplicaDistributionGoal's band
# (floor(96 / 1.1) = 87 a broker); the program fills to that band and then
# balances LOAD onto the same brokers, up to the band's upper edge
# (ceil(96 * 1.1) = 106): 106 / 87 = 1.22 is the most the band allows. It
# reads 1.22 (212 against 174) at 14 + 2 and 1.13-1.22 at 12 + 4.
FACTOR = 1.25
# A chain whose last goal trades replicas (supports_swap): its swap rounds
# run after ReplicaDistributionGoal has filled the new brokers.
ENDS_IN_SWAPS = ["RackAwareGoal", "ReplicaDistributionGoal",
                 "DiskUsageDistributionGoal"]
CASES = {
    # the served default: all 15 goals, no hard goal has work to do
    "full-chain": {},
    "ends-in-swaps": {"goals": ENDS_IN_SWAPS},
    # the old brokers' replicas drawn without regard to racks: RackAwareGoal
    # (hard) has to move replicas while the new brokers exist, and only the
    # four of 12 + 4 offer every partition a rack it does not use
    "rack-broken-start": {"deployment": {"placement": "skewed_random"},
                          "new": [NEW[1]]},
}


def scale_out(new, **patch):
    return build({**DEPLOYMENT, "operation_brokers": new, **patch})


def placed_onto(dep, plan):
    """[(partition row, broker it came from, broker it went to)] of the
    replicas a plan places on a broker that did not hold the partition.
    The served lists put the leader first, so a slot's place may change:
    each placed broker is paired with a broker that left, in list order."""
    out = []
    for p in plan:
        row = dep.index_of(p["topicPartition"]["topic"],
                           p["topicPartition"]["partition"])
        came = [b for b in p["newReplicas"] if b not in p["oldReplicas"]]
        left = [b for b in p["oldReplicas"] if b not in p["newReplicas"]]
        out += [(row, src, dst) for src, dst in zip(left, came)]
    return out


def dispatch_attributes(trace_root):
    return [{a["key"]: a["value"] for a in s["attributes"]}
            for s in spans(trace_root) if s["name"] == "solver.dispatch"]


def check_against_the_reference(dep, new, plan, rack_broken=False):
    """The program's plan held to ``reference.NUMBERS`` + ``onto_old_broker``
    and to the plain fill; returns the replicas it placed on new brokers."""
    theirs = reference.evaluate(dep, GUARANTEES, plan)
    assert not any(theirs["numbers"].values()), theirs["numbers"]
    edge = scaleout_reference.lower_edge(dep)
    after = dep.assignment.copy()
    for p in plan:
        after[dep.index_of(p["topicPartition"]["topic"],
                           p["topicPartition"]["partition"])] \
            = p["newReplicas"]
    counts = np.bincount(after.ravel(), minlength=dep.brokers)
    assert (counts[new] >= edge).all(), (counts[new], edge)
    # no old broker gained a replica of a partition it did not hold
    moves = placed_onto(dep, plan)
    assert moves and all(dst in new for _row, _src, dst in moves)
    if rack_broken:     # the greedy keeps racks, it does not repair them
        return len(moves)
    filled = scaleout_reference.scale_out(dep, GUARANTEES)
    assert filled is not None
    ours = reference.evaluate(dep, GUARANTEES,
                              drain_reference.as_proposals(dep, filled))
    assert not any(ours["numbers"].values()), ours["numbers"]
    assert (np.bincount(filled.ravel(), minlength=dep.brokers)[new]
            >= edge).all()
    assert ours["info"]["replicas_placed"] == edge * len(new)
    assert len(moves) <= FACTOR * ours["info"]["replicas_placed"]
    return len(moves)


@pytest.mark.parametrize("route,case,new", [
    *((r, c, n) for c, spec in CASES.items() for n in spec.get("new", NEW)
      for r in ROUTES),
    *((r, "full-chain", NEW[0]) for r in ROUTES_BEYOND_ONE_CLUSTER)],
    ids=lambda v: "+".join(map(str, v)) if isinstance(v, list) else v)
def test_replicas_move_only_onto_the_new_brokers(route, case, new,
                                                 monkeypatch):
    spec = CASES[case]
    dep = scale_out(new, **spec.get("deployment", {}))
    rack_broken = case == "rack-broken-start"
    assert (reference.rack_violations(dep, dep.assignment) > 0) == rack_broken

    swap_rounds = []
    if case == "ends-in-swaps" and route == "pergoal":
        # the unbounded per-goal route drives the swap rounds from the
        # host: see that they ran, and on what
        real = chain_module.chain_swap_rounds

        def seen(state, *args, **kwargs):
            out = real(state, *args, **kwargs)
            swap_rounds.append((np.bincount(
                np.asarray(state.assignment).ravel(),
                minlength=dep.brokers)[new], int(out[2])))
            return out
        monkeypatch.setattr(chain_module, "chain_swap_rounds", seen)

    before = (counter("solver_scale_out_replicas", onto="new"),
              counter("solver_scale_out_replicas", onto="old"),
              counter("solver_scale_out_rounds"))
    cc = facade(dep, route)
    try:
        result = cc.add_brokers(new, dryrun=True, goals=spec.get("goals"))
        traces = TRACER.traces(operation="add_broker", limit=1)
    finally:
        cc.shutdown()
    plan = responses.optimization_result(result, verbose=True)["proposals"]
    placed = check_against_the_reference(dep, new, plan, rack_broken)

    goals = {g.name: g for g in result.optimizer_result.goal_results}
    if case == "full-chain":
        assert len(goals) == 15
    if rack_broken:     # a hard goal moved replicas, onto new brokers alone
        assert goals["RackAwareGoal"].moves_applied > 0
    if case == "ends-in-swaps":
        assert list(goals) == ENDS_IN_SWAPS
        assert goals["DiskUsageDistributionGoal"].rounds > 0
    if swap_rounds:
        edge = scaleout_reference.lower_edge(dep)
        assert all((filled >= edge).all() and rounds >= 1
                   for filled, rounds in swap_rounds)

    # the pass's scale-out accounting, and the span that shows it as one
    rounds = sum(g.rounds for g in goals.values())
    assert counter("solver_scale_out_replicas", onto="new") - before[0] \
        == placed
    assert counter("solver_scale_out_replicas", onto="old") == before[1]
    assert counter("solver_scale_out_rounds") - before[2] == rounds > 0
    if route == "megabatch":    # its dispatches are several clusters'
        return
    dispatches = dispatch_attributes(traces[0]["root"])
    assert dispatches or route == "pergoal"
    for attrs in dispatches:
        assert attrs["new_brokers"] == {"intValue": str(len(new))}
        assert attrs["placed_on_new"] == {"intValue": str(placed)}
        assert attrs["placed_on_old"] == {"intValue": "0"}


@pytest.mark.parametrize("route", ROUTES)
def test_an_offline_replica_goes_where_self_healing_sends_it(route):
    """One DEAD broker beside two NEW: its replicas may go to any broker
    that is allowed replica moves, old or new (self-healing is not held up
    by a scale-out), and the guarantee does not count them; every other
    replica still moves onto the new brokers alone."""
    new, dead = NEW[0], 5
    dep = scale_out(new)
    stranded = {(int(p), dead) for p in
                np.nonzero((dep.assignment == dead).any(axis=1))[0]}
    failures = counter("analyzer_optimization_failures")
    before = counter("solver_scale_out_replicas", onto="old")
    cc = facade(dep, route)
    try:
        cc._admin.kill_broker(dead)
        rounds = cc._config.get_int("num.partition.metrics.windows") + 1
        cc._load_monitor.task_runner.run_sampling_once(
            end_ms=(rounds + 1) * 1000)     # the monitor sees it dead
        result = cc.add_brokers(new, dryrun=True)
    finally:
        cc.shutdown()
    plan = responses.optimization_result(result, verbose=True)["proposals"]
    gone = dataclasses.replace(dep, alive=np.arange(dep.brokers) != dead)
    theirs = reference.evaluate(gone, GUARANTEES, plan)["numbers"]
    onto_old = theirs.pop("onto_old_broker")    # it knows no exemption
    assert not any(theirs.values()), theirs
    moves = placed_onto(dep, plan)
    assert {(row, src) for row, src, _dst in moves if src == dead} \
        == stranded
    healed_onto_old = [m for m in moves if m[2] not in new]
    assert len(healed_onto_old) == onto_old > 0
    # A partition that lost its dead replica and another one may list the
    # broker the dead one went to first, so ``placed_onto`` cannot pair
    # them: each partition places on an old broker at most the one replica
    # it had on the dead broker.
    rows = [row for row, _src, _dst in healed_onto_old]
    assert len(set(rows)) == len(rows)
    assert set(rows) <= {row for row, _src in stranded}
    assert counter("solver_scale_out_replicas", onto="old") - before \
        == onto_old
    assert counter("analyzer_optimization_failures") == failures


def one_replica_onto_an_old_broker(monkeypatch):
    """The diff's fetched arrays altered as a broken rule would leave them:
    the first replica the plan places on a new broker lands on an old one
    instead."""
    real = optimizer_module.fetch_diff

    def altered(initial, final):
        fetched = real(initial, final)
        a1 = fetched.a1.copy()
        is_new = fetched.broker_state == 2      # BrokerState.NEW
        p, s = np.argwhere(is_new[np.maximum(a1, 0)] & (a1 != fetched.a0))[0]
        a1[p, s] = next(b for b in range(len(is_new)) if not is_new[b]
                        and b not in fetched.a0[p] and b not in a1[p])
        return dataclasses.replace(fetched, a1=a1)
    monkeypatch.setattr(optimizer_module, "fetch_diff", altered)


@pytest.mark.parametrize("route", [*ROUTES, "megabatch"])
def test_a_plan_that_places_on_an_old_broker_fails(route, monkeypatch):
    one_replica_onto_an_old_broker(monkeypatch)
    dep = scale_out(NEW[0])
    failures = counter("analyzer_optimization_failures")
    cc = facade(dep, route)
    try:
        with pytest.raises(OptimizationFailureError,
                           match="1 replicas placed on brokers"):
            cc.add_brokers(NEW[0], dryrun=True)
    finally:
        cc.shutdown()
    assert counter("analyzer_optimization_failures") == failures + 1


def post_add_broker(api, new):
    query = "dryrun=true&verbose=true&brokerid=" + ",".join(map(str, new))
    status, answer, headers = api.handle(
        "POST", "/kafkacruisecontrol/add_broker", query)
    for _ in range(600):        # a 202 carries the task's id: poll it
        if status != 202:
            break
        time.sleep(0.05)
        status, answer, headers = api.handle(
            "POST", "/kafkacruisecontrol/add_broker", query,
            {"User-Task-ID": headers["User-Task-ID"]})
    return status, answer


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "onto-an-old-broker"])
def test_the_api_answers_a_scale_out(broken, monkeypatch):
    """POST /add_broker: a sound plan is served, GET /trace shows the pass
    as a scale-out and the exposition counts it; a plan that breaks the
    rule is answered as a drain that cannot finish is
    (test_drain.test_the_api_answers_both_failures_alike)."""
    from cruise_control_tpu.api.server import CruiseControlApi
    from cruise_control_tpu.utils.sensors import SENSORS
    if broken:
        one_replica_onto_an_old_broker(monkeypatch)
    new = NEW[0]
    dep = scale_out(new)
    cc = facade(dep, "fused")
    api = CruiseControlApi(cc)
    try:
        status, answer = post_add_broker(api, new)
        _s, traced, _h = api.handle("GET", "/kafkacruisecontrol/trace",
                                    "operation=add_broker&entries=1")
    finally:
        api.shutdown()
        cc.shutdown()
    if broken:
        assert status == 500
        assert "OptimizationFailureError" in answer["errorMessage"]
        assert "proposals" not in answer
        return
    assert status == 200
    placed = check_against_the_reference(dep, new, answer["proposals"])
    attrs, = dispatch_attributes(traced["traces"][0]["root"])
    assert attrs["new_brokers"] == {"intValue": "2"}
    assert attrs["placed_on_new"] == {"intValue": str(placed)}
    assert attrs["placed_on_old"] == {"intValue": "0"}
    exposition = SENSORS.render()
    for series in ('solver_scale_out_replicas_total{onto="new"}',
                   'solver_scale_out_replicas_total{onto="old"}',
                   "solver_scale_out_rounds_total"):
        assert "kafka_cruisecontrol_" + series in exposition


# -- the seams the deployment comes through (PR 31's, which a benchmark PR
#    could not put here: benchmarks/tests/test_seams.py has the rest) ------

@pytest.mark.parametrize("new", [[], *NEW], ids=lambda n: f"{len(n)}-new")
def test_kafkas_assignor_draws_the_start(new):
    dep = scale_out(new, operation="add_broker" if new else "proposals")
    a = dep.assignment
    assert a.shape == (512, 3)
    assert reference.rack_violations(dep, a) == 0
    srt = np.sort(a, axis=1)
    assert (srt[:, 1:] != srt[:, :-1]).all()        # no broker twice
    hosts = np.setdiff1d(np.arange(dep.brokers), new)
    replicas = np.bincount(a.ravel(), minlength=dep.brokers)
    leaders = np.bincount(a[:, 0], minlength=dep.brokers)
    assert replicas[new].sum() == 0 == leaders[new].sum()
    # racks of unequal size: KIP-36's smaller racks' brokers take more
    assert np.abs(replicas[hosts] / replicas[hosts].mean() - 1).max() <= 0.10
    assert np.abs(leaders[hosts] / leaders[hosts].mean() - 1).max() <= 0.25
    assert reference.evaluate(dep, GUARANTEES, [])["numbers"][
        "over_capacity"] == 0


def move(dep, i, new_replicas, leader=None):
    topic, part = dep.topic_partition(i)
    old = dep.assignment[i].tolist()
    return {"topicPartition": {"topic": topic, "partition": part},
            "oldLeader": old[0], "oldReplicas": old,
            "newLeader": new_replicas[0] if leader is None else leader,
            "newReplicas": new_replicas}


@pytest.mark.parametrize("plan,expected", [
    ("all_onto_new", 0), ("one_onto_old", 1), ("leadership_only", 0),
    ("back_onto_an_old_replica", 0), ("nothing", 0)])
def test_onto_old_broker_on_hand_made_plans(plan, expected):
    dep = scale_out(NEW[0])
    a = dep.assignment.tolist()
    used = set(dep.broker_rack[dep.assignment[1]])
    an_old_broker = next(b for b in range(14) if b not in a[1]
                         and dep.broker_rack[b] not in used)
    plans = {
        "all_onto_new": [move(dep, 0, a[0][:2] + [14]),
                         move(dep, 1, [15] + a[1][1:])],
        "one_onto_old": [move(dep, 0, a[0][:2] + [14]),
                         move(dep, 1, a[1][:2] + [an_old_broker])],
        "leadership_only": [move(dep, 0, a[0], leader=a[0][1]),
                            move(dep, 1, a[1][::-1])],
        "back_onto_an_old_replica": [move(dep, 0, [14, a[0][1], a[0][0]])],
        # what the guarantee cannot tell from a scale-out that was done
        # (PERF.md section 7: new_broker_underfilled)
        "nothing": [],
    }
    numbers = reference.evaluate(dep, GUARANTEES, plans[plan])["numbers"]
    assert numbers["onto_old_broker"] == expected
    assert sum(numbers.values()) == expected    # and no other number moves
