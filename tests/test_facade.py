"""Facade orchestration: model-backed operations, proposal cache, state
dashboard, and end-to-end self-healing through the detector manager
(reference parity: KafkaCruiseControl.java + runnable/ + the
AnomalyDetectorManager fix path)."""

import numpy as np
import pytest

from cruise_control_tpu.common.resources import Resource
from cruise_control_tpu.config.cruise_control_config import CruiseControlConfig
from cruise_control_tpu.detector import AnomalyStatus, BrokerFailures
from cruise_control_tpu.executor.admin import InMemoryAdminBackend, PartitionState
from cruise_control_tpu.executor.executor import Executor
from cruise_control_tpu.facade import CruiseControl
from cruise_control_tpu.monitor import LoadMonitor, StaticCapacityResolver
from cruise_control_tpu.monitor.sampling import SyntheticSampler


def _partitions(brokers=(0, 1, 2, 3), topics=2, parts=6, rf=2):
    out = {}
    for t in range(topics):
        for p in range(parts):
            # Skewed: broker 0 leads everything (real rebalance work).
            reps = (brokers[0], brokers[1 + (t + p) % (len(brokers) - 1)])[:rf]
            out[(f"t{t}", p)] = PartitionState(f"t{t}", p, reps, reps[0],
                                               isr=reps)
    return out


def _cruise_control(partitions, extra_cfg=None, synchronous_executor=True):
    backend = InMemoryAdminBackend(partitions.values())
    cfg = CruiseControlConfig({
        "partition.metrics.window.ms": 1000,
        "num.partition.metrics.windows": 3,
        "min.valid.partition.ratio": 0.0,
        "anomaly.detection.interval.ms": 60_000,
        "max.solver.rounds": 40,
        "failed.brokers.file.path": "",   # no cross-run persistence in tests

        **(extra_cfg or {})})
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6, Resource.NW_OUT: 1e6})
    monitor = LoadMonitor(cfg, backend, samplers=[SyntheticSampler()],
                          capacity_resolver=caps,
                          broker_racks={b: f"r{b % 2}" for b in range(8)})
    executor = Executor(backend, synchronous=synchronous_executor)
    cc = CruiseControl(cfg, backend, load_monitor=monitor, executor=executor)
    for k in range(1, 4):
        monitor.task_runner.run_sampling_once(end_ms=k * 1000)
    return cc, backend


def test_rebalance_dryrun_produces_proposals_and_does_not_execute():
    cc, backend = _cruise_control(_partitions())
    before = backend.describe_partitions()
    res = cc.rebalance(dryrun=True)
    assert res.proposals, "skewed cluster must yield proposals"
    assert not res.executed
    assert backend.describe_partitions() == before
    assert res.optimizer_result.balancedness_after >= \
        res.optimizer_result.balancedness_before


def test_rebalance_executes_against_backend():
    cc, backend = _cruise_control(_partitions())
    res = cc.rebalance(dryrun=False)
    assert res.executed
    cc.executor.await_completion()
    after = backend.describe_partitions()
    applied = {(t, p): st.replicas for (t, p), st in after.items()}
    for pr in res.proposals:
        assert set(applied[(pr.topic, pr.partition)]) == set(pr.new_replicas)


def test_proposals_cache_hits_until_generation_changes():
    cc, _ = _cruise_control(_partitions())
    r1 = cc.proposals()
    assert r1.reason != "cached"
    r2 = cc.proposals()
    assert r2.reason == "cached"
    # New samples → new model generation → fresh computation.
    cc.load_monitor.task_runner.run_sampling_once(end_ms=10_000)
    assert cc.proposals().reason != "cached"


def test_remove_brokers_moves_all_replicas_off():
    cc, _ = _cruise_control(_partitions(brokers=(0, 1, 2, 3)))
    res = cc.remove_brokers([3], dryrun=True)
    for pr in res.proposals:
        assert 3 not in pr.new_replicas
    held = [pr for pr in res.proposals if 3 in pr.old_replicas]
    # Every partition broker 3 hosted must be moved away.
    parts_on_3 = [(t, p) for (t, p), st in
                  cc._admin.describe_partitions().items() if 3 in st.replicas]
    assert {(pr.topic, pr.partition) for pr in held} == set(parts_on_3)


def test_add_brokers_routes_load_to_new_broker():
    partitions = _partitions(brokers=(0, 1, 2))
    backend = InMemoryAdminBackend(partitions.values())
    backend.revive_broker(4)          # empty new broker joins the cluster
    cfg = CruiseControlConfig({"partition.metrics.window.ms": 1000,
                               "num.partition.metrics.windows": 3,
                               "min.valid.partition.ratio": 0.0,
                               "max.solver.rounds": 40,
                               "failed.brokers.file.path": ""})
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6, Resource.NW_OUT: 1e6})
    monitor = LoadMonitor(cfg, backend, samplers=[SyntheticSampler()],
                          capacity_resolver=caps)
    cc = CruiseControl(cfg, backend, load_monitor=monitor,
                       executor=Executor(backend, synchronous=True))
    for k in range(1, 4):
        monitor.task_runner.run_sampling_once(end_ms=k * 1000)
    res = cc.add_brokers([4], dryrun=True)
    gained = [pr for pr in res.proposals if 4 in pr.new_replicas]
    assert gained, "new broker must receive replicas"
    assert all(set(pr.replicas_to_add) <= {4} for pr in res.proposals), \
        "no old broker may gain a replica"


def test_demote_brokers_sheds_leadership_only():
    cc, _ = _cruise_control(_partitions())
    res = cc.demote_brokers([0], dryrun=True)
    for pr in res.proposals:
        assert set(pr.old_replicas) == set(pr.new_replicas), \
            "demotion must not move replicas"
        assert pr.new_leader != 0


def test_update_topic_replication_factor_grows_rack_aware():
    cc, _ = _cruise_control(_partitions(rf=2))
    # The fixture has 2 racks (r0/r1): growing to RF 3 must refuse without
    # the explicit opt-in (RunnableUtils.java:91-99) ...
    with pytest.raises(ValueError, match="skip_rack_awareness_check"):
        cc.update_topic_replication_factor(["t0"], 3, dryrun=True)
    # ... and RF above the alive-broker count is always impossible (:87-90).
    with pytest.raises(ValueError, match="alive broker"):
        cc.update_topic_replication_factor(["t0"], 5, dryrun=True,
                                           skip_rack_awareness_check=True)
    res = cc.update_topic_replication_factor(["t0"], 3, dryrun=True,
                                             skip_rack_awareness_check=True)
    assert res.proposals
    for pr in res.proposals:
        assert len(pr.new_replicas) == 3
        assert set(pr.old_replicas) <= set(pr.new_replicas)


def test_state_dashboard_sections():
    cc, _ = _cruise_control(_partitions())
    st = cc.state()
    assert {"MonitorState", "ExecutorState", "AnalyzerState",
            "AnomalyDetectorState"} <= set(st)
    assert st["MonitorState"]["numValidWindows"] >= 1
    only = cc.state(substates=["executor"])
    assert set(only) == {"ExecutorState"}


def test_self_healing_broker_failure_end_to_end():
    """Kill a broker → failure detector reports → manager consults notifier
    → fix = remove_brokers → executor applies → no replica remains on the
    dead broker (the reference's BrokerFailureDetectorTest + self-healing
    loop, collapsed into one synchronous pass)."""
    cc, backend = _cruise_control(
        _partitions(brokers=(0, 1, 2, 3)),
        extra_cfg={"self.healing.enabled": True,
                   "broker.failure.self.healing.threshold.ms": 0})
    cc._notifier._alert_threshold_ms = 0
    backend.kill_broker(3)
    # Re-sample so the model sees the dead broker.
    cc.load_monitor.task_runner.run_sampling_once(end_ms=5000)

    detector = [d for d, _i in cc.anomaly_detector._detectors
                if type(d).__name__ == "BrokerFailureDetector"][0]
    anomaly = detector.run_once()
    assert isinstance(anomaly, BrokerFailures) and 3 in anomaly.failed_brokers
    taken = cc.anomaly_detector._take(timeout_s=0.5)
    status = cc.anomaly_detector.handle_anomaly(taken)
    assert status == AnomalyStatus.FIX_STARTED
    cc.executor.await_completion()
    for st in backend.describe_partitions().values():
        assert 3 not in st.replicas


def test_config_excluded_topics_regex_holds_on_rebalance_path():
    """topics.excluded.from.partition.movement must bind the EXECUTING
    operations, not just dryrun previews: no proposal may touch a matching
    topic (KafkaCruiseControlUtils.excludedTopics contract)."""
    cc, backend = _cruise_control(
        _partitions(), extra_cfg={
            "topics.excluded.from.partition.movement": "t0"})
    res = cc.rebalance(dryrun=True)
    assert res.proposals, "t1 still needs rebalancing"
    assert not any(p.topic == "t0" for p in res.proposals), \
        [p.topic for p in res.proposals]
    # the cached-proposal path (PROPOSALS endpoint) honors it too
    res2 = cc.proposals()
    assert not any(p.topic == "t0" for p in res2.proposals)


def test_invalid_excluded_topics_regex_fails_fast():
    from cruise_control_tpu.config.configdef import ConfigException

    with pytest.raises(ConfigException, match="regex"):
        _cruise_control(_partitions(), extra_cfg={
            "topics.excluded.from.partition.movement": "[__"})


def test_background_proposal_precompute_warms_cache():
    """GoalOptimizer.java:152-203 parity: the precompute loop keeps cached
    proposals fresh so a PROPOSALS request hits a warm cache without ever
    computing inline."""
    import time as _time

    cc, _backend = _cruise_control(
        _partitions(), extra_cfg={"proposal.expiration.ms": 2000},
        synchronous_executor=True)
    cc.start_up(block_on_load=False)
    try:
        deadline = _time.time() + 20
        while _time.time() < deadline:
            with cc._proposal_lock:
                if cc._proposal_cache is not None:
                    break
            _time.sleep(0.2)
        with cc._proposal_lock:
            assert cc._proposal_cache is not None, \
                "precompute never populated the cache"
        res = cc.proposals()
        assert res.reason == "cached"
    finally:
        cc.shutdown()
