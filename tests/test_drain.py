"""The decommission held to its guarantee: ``remove_brokers`` through the
facade on seeded deployments of ``benchmarks/benchlib/deployment.py`` (16
brokers / 512 partitions), on the three routes that serve one cluster,
against the plain sequential drain of ``benchlib/drain_reference.py``
(numpy, imports nothing of the program). A drain that can finish leaves
nothing on the removed brokers; one that cannot raises
``OptimizationFailureError`` and returns no partial plan."""

import json
import os
import sys
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import drain_reference, reference          # noqa: E402
from benchlib.deployment import CPU, DISK, NW_IN, NW_OUT, build  # noqa: E402
from benchlib.sut import (  # noqa: E402
    BASE_CONFIG, DeploymentSampler, parse_exposition, series_total,
)

from cruise_control_tpu.analyzer import (  # noqa: E402
    GoalOptimizer, OptimizationFailureError,
)
from cruise_control_tpu.api import responses  # noqa: E402
from cruise_control_tpu.common.resources import Resource  # noqa: E402
from cruise_control_tpu.config.cruise_control_config import (  # noqa: E402
    CruiseControlConfig,
)
from cruise_control_tpu.executor.admin import (  # noqa: E402
    InMemoryAdminBackend, PartitionState,
)
from cruise_control_tpu.facade import CruiseControl  # noqa: E402
from cruise_control_tpu.monitor import (  # noqa: E402
    LoadMonitor, StaticCapacityResolver,
)
from cruise_control_tpu.utils.sensors import SENSORS  # noqa: E402
from cruise_control_tpu.utils.tracing import TRACER  # noqa: E402

# How GoalOptimizer picks each single-cluster route (optimizer.py): the
# whole chain in one dispatch; per goal in bounded dispatches (the chain is
# fused but the cluster is over the fused route's broker limit), on the
# narrow grids and with the wide ones (the cluster is also at the wide
# grids' broker threshold), as every cluster above 512 brokers takes it;
# per goal, unbounded.
ROUTES = {
    "fused": {},
    "bounded": {"solver.fused.chain.max.brokers": 8},
    "bounded-wide": {"solver.fused.chain.max.brokers": 8,
                     "solver.wide.batch.min.brokers": 8},
    "pergoal": {"solver.chain.fused": False},
}
# The routes that tally the rounds that built the offline mask.
HEALING_COUNTED = ("fused", "bounded", "bounded-wide")
# The other two places the per-goal infos converge: a cluster of a
# megabatch, and the whole chain SPMD over the (virtual) devices.
ROUTES_BEYOND_ONE_CLUSTER = {"megabatch": {}, "mesh": {}}
GUARANTEES = {"capacity_threshold": {"cpu": 0.7, "nw_in": 0.8, "nw_out": 0.8,
                                     "disk": 0.8},
              "max_replicas_per_broker": 10000}
# Drawn so that the drain is all a plan needs: every broker starts under
# its capacity thresholds (worst 0.84 of a threshold), which the greedy
# reference does not repair where the served chain would.
DEPLOYMENT = {"brokers": 16, "partitions": 512, "topics": 4,
              "replication_factor": 3, "racks": 8, "placement_skew": 1.0,
              "load_skew": 3.0, "target_utilization": 0.4, "instance_seed": 0,
              "operation": "remove_broker"}
# Heavy and light ends of the placement skew; two brokers of one rack pair.
REMOVED = ([3, 7], [0, 9])
# RF 3 on four brokers with two removed: every partition keeps a replica on
# a removed broker, and the two that stay hold it already.
TOO_FEW_BROKERS = {**DEPLOYMENT, "brokers": 4, "partitions": 64, "topics": 1,
                   "racks": 4, "operation_brokers": [2, 3]}
# 16 brokers at 75 % of capacity hold 12 brokers' worth of NW_IN and DISK;
# the 14 that stay may hold 14 x 0.8 = 11.2.
TOO_MUCH_LOAD = {**DEPLOYMENT, "placement_skew": 0.0,
                 "target_utilization": 0.75, "operation_brokers": [3, 7]}


def facade(dep, route):
    """monitor -> optimizer -> facade as ``benchlib/sut.py`` wires them,
    without the HTTP server and on the library's single-device optimizer."""
    config = CruiseControlConfig({
        **BASE_CONFIG, "min.valid.partition.ratio": 0.0,
        "cpu.capacity.threshold": 0.7, "disk.capacity.threshold": 0.8,
        "network.inbound.capacity.threshold": 0.8,
        "network.outbound.capacity.threshold": 0.8,
        **{**ROUTES, **ROUTES_BEYOND_ONE_CLUSTER}[route]})
    states = []
    for i, reps in enumerate(dep.assignment.tolist()):
        topic, part = dep.topic_partition(i)
        states.append(PartitionState(topic, part, tuple(reps), reps[0],
                                     isr=tuple(reps)))
    backend = InMemoryAdminBackend(states)
    for b in range(dep.brokers):    # a new broker hosts nothing yet
        backend.revive_broker(b)
    monitor = LoadMonitor(
        config, backend, samplers=[DeploymentSampler(dep)],
        capacity_resolver=StaticCapacityResolver({}, {
            Resource.CPU: dep.capacity[CPU], Resource.DISK: dep.capacity[DISK],
            Resource.NW_IN: dep.capacity[NW_IN],
            Resource.NW_OUT: dep.capacity[NW_OUT]}),
        broker_racks={b: f"rack{r}"
                      for b, r in enumerate(dep.broker_rack.tolist())})
    optimizer = GoalOptimizer(config, mesh="auto" if route == "mesh" else None)
    cc = CruiseControl(config, backend, load_monitor=monitor,
                       optimizer=optimizer)
    if route == "megabatch":    # facade._optimize: the batched program
        cc.megabatch_solve_width = 2
    for k in range(config.get_int("num.partition.metrics.windows") + 1):
        monitor.task_runner.run_sampling_once(end_ms=(k + 1) * 1000)
    return cc


def counter(name, **labels):
    """A counter as the benchmark's metrics read it, off the exposition."""
    return series_total(parse_exposition(SENSORS.render()), name + "_total",
                        **labels)


def wide_rounds():
    """Rounds the bounded route searched on the wide grids so far."""
    return series_total(parse_exposition(SENSORS.render()),
                        "solver_dispatch_rounds_sum", grid="wide")


@pytest.mark.parametrize("removed", REMOVED, ids=lambda r: "-".join(map(str, r)))
@pytest.mark.parametrize("route", ROUTES)
def test_drain_agrees_with_the_plain_reference(route, removed):
    dep = build({**DEPLOYMENT, "operation_brokers": removed})
    forced = drain_reference.must_move(dep, removed)
    greedy = drain_reference.drain(dep, removed, GUARANTEES)
    assert greedy is not None
    ours = reference.evaluate(dep, GUARANTEES,
                              drain_reference.as_proposals(dep, greedy))
    assert not any(ours["numbers"].values()), ours["numbers"]

    before = (counter("solver_offline_replicas", when="before"),
              counter("solver_offline_replicas", when="remaining"),
              counter("solver_evacuation_rounds"),
              counter("solver_healing_rounds"), wide_rounds())
    cc = facade(dep, route)
    try:
        result = cc.remove_brokers(removed, dryrun=True)
        traces = TRACER.traces(operation="remove_broker", limit=1)
    finally:
        cc.shutdown()

    # the counts of reference.py, limit 0: nothing (so no leader) on the
    # removed brokers, RF kept, live brokers only, racks and capacity held
    plan = responses.optimization_result(result, verbose=True)["proposals"]
    theirs = reference.evaluate(dep, GUARANTEES, plan)
    assert not any(theirs["numbers"].values()), theirs["numbers"]
    assert all(p["newLeader"] not in removed for p in plan)
    # what HAD to move is the same set in both, and the program moved it
    assert {(dep.index_of(p["topicPartition"]["topic"],
                          p["topicPartition"]["partition"]), s)
            for p in plan for s, b in enumerate(p["oldReplicas"])
            if b in removed} == forced
    # Tolerance 0.05 of a threshold. The served chain rebalances the whole
    # cluster where the greedy only places what it must, so it should be
    # the better of the two (it reads 0.62 against 0.84 here). A broker
    # holds 96 replicas at half a threshold, so the heaviest replica (four
    # times the mean under load_skew 3) is 0.02 of one: the room is two or
    # three such replicas, which the last distribution goals may trade away
    # while they balance another resource. More would be load piled up.
    assert theirs["info"]["capacity_worst"] \
        <= ours["info"]["capacity_worst"] + 0.05

    # the pass's drain accounting, and the span that shows it as one
    assert counter("solver_offline_replicas", when="before") - before[0] \
        == len(forced)
    assert counter("solver_offline_replicas", when="remaining") == before[1]
    assert counter("solver_evacuation_rounds") > before[2]
    # the whole-chain dispatch and the bounded passes tally the rounds that
    # built the offline mask: some, and no more than the evacuation rounds
    healed = counter("solver_healing_rounds") - before[3]
    if route in HEALING_COUNTED:
        assert 0 < healed <= counter("solver_evacuation_rounds") - before[2]
    else:
        assert healed == 0
    # the wide grids ran on the route that forces them, and only there
    assert (wide_rounds() > before[4]) == (route == "bounded-wide")
    dispatches = [s for s in spans(traces[0]["root"])
                  if s["name"] == "solver.dispatch"]
    # (the unbounded per-goal route opens goal.solve spans and no dispatch)
    assert dispatches or route == "pergoal"
    for d in dispatches:
        attrs = {a["key"]: a["value"] for a in d["attributes"]}
        assert attrs["offline_before"] == {"intValue": str(len(forced))}
        assert attrs["offline_remaining"] == {"intValue": "0"}
        assert attrs["excluded_brokers"] == {"intValue": str(len(removed))}
        if route in HEALING_COUNTED:
            assert attrs["healing_rounds"] == {"intValue": str(int(healed))}


def spans(node):
    yield node
    for child in node["children"]:
        yield from spans(child)


@pytest.mark.parametrize("route,case", [
    *((r, c) for c in ("too-few-brokers", "too-much-load") for r in ROUTES),
    *((r, "too-few-brokers") for r in ROUTES_BEYOND_ONE_CLUSTER)])
def test_a_drain_that_cannot_finish_fails(route, case):
    case = {"too-few-brokers": TOO_FEW_BROKERS,
            "too-much-load": TOO_MUCH_LOAD}[case]
    dep = build(case)
    removed = list(dep.operation_brokers)
    assert drain_reference.drain(dep, removed, GUARANTEES) is None
    stays = dep.brokers - len(removed)
    if case is TOO_FEW_BROKERS:     # provably: fewer brokers left than RF
        assert stays < dep.rf
    else:                           # provably: more disk than may be held
        assert dep.rf * dep.leader_load[:, DISK].sum() \
            > stays * dep.capacity[DISK] * 0.8
    failures = counter("analyzer_optimization_failures")
    cc = facade(dep, route)
    try:
        with pytest.raises(OptimizationFailureError):
            cc.remove_brokers(removed, dryrun=True)
    finally:
        cc.shutdown()
    assert counter("analyzer_optimization_failures") == failures + 1


@pytest.mark.parametrize("case", [TOO_FEW_BROKERS, TOO_MUCH_LOAD],
                         ids=["drain-left-unfinished", "hard-goal-unsatisfied"])
def test_the_api_answers_both_failures_alike(case):
    """POST /remove_broker for a drain that cannot finish is answered as an
    unsatisfied hard goal is: the status the front door gives every
    optimisation failure, the error's type and its count, and no plan."""
    from cruise_control_tpu.api.server import CruiseControlApi
    dep = build(case)
    cc = facade(dep, "fused")
    api = CruiseControlApi(cc)
    try:
        status, answer, headers = api.handle(
            "POST", "/kafkacruisecontrol/remove_broker",
            "dryrun=true&verbose=true&brokerid="
            + ",".join(map(str, dep.operation_brokers)))
        for _ in range(600):    # a 202 carries the task's id: poll it
            if status != 202:
                break
            time.sleep(0.05)
            status, answer, headers = api.handle(
                "POST", "/kafkacruisecontrol/remove_broker",
                "dryrun=true&verbose=true&brokerid="
                + ",".join(map(str, dep.operation_brokers)),
                {"User-Task-ID": headers["User-Task-ID"]})
    finally:
        api.shutdown()
        cc.shutdown()
    assert status == 500
    assert "OptimizationFailureError" in answer["errorMessage"]
    assert "proposals" not in answer


def test_the_decommission_at_its_published_size_keeps_to_its_recipe():
    """``configs/kafka-1000b-100kp-drain.json``: BASELINE config 4 uncut,
    50 distinct brokers of 1,000 on all 8 racks, the small drain's five
    first, nothing reduced; its cluster, scaled to 40 brokers with the
    named brokers that lie below 40, is one the plain drain empties."""
    with open(os.path.join(BENCH, "configs",
                           "kafka-1000b-100kp-drain.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           "kafka-100b-10kp-drain.json")) as f:
        small = json.load(f)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[cfg["name"]]
    removed = cfg["operation_brokers"]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    assert (cfg["brokers"], cfg["partitions"], cfg["drained_brokers"]) \
        == (1000, 100000, 50) == tuple(cfg["source_scale"][k] for k in
                                       ("brokers", "partitions",
                                        "drained_brokers"))
    assert len(set(removed)) == 50 == len(removed)
    assert removed == [20 * i + (i + 2) % 8 for i in range(50)]
    assert removed[:5] == small["operation_brokers"]
    dep = build(cfg)
    assert set(dep.broker_rack[removed].tolist()) == set(range(8))
    assert cfg["guarantees"] == small["guarantees"]

    scaled = build({**cfg, "brokers": 40, "partitions": 4000, "topics": 4,
                    "operation_brokers": [b for b in removed if b < 40]})
    assert scaled.operation_brokers == (2, 23)
    greedy = drain_reference.drain(scaled, scaled.operation_brokers,
                                   cfg["guarantees"])
    assert greedy is not None
    numbers = reference.evaluate(
        scaled, cfg["guarantees"],
        drain_reference.as_proposals(scaled, greedy))["numbers"]
    assert numbers["on_removed_broker"] == 0 == numbers["rack_violations"]
