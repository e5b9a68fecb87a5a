"""Resilience layer + chaos harness (round 9).

Unit coverage: retry/backoff/jitter determinism and circuit-breaker
state transitions under an INJECTED clock (no sleeps, no wall-clock
assertions). Integration coverage: full rebalance/execution cycles
driven through the fault-injecting backend at several seeds — the
acceptance bar is convergence with correct final assignments, zero
flakes, plus partial-window acceptance, executor dead-lettering, the
fleet skip-on-open-breaker path, and the facade's stale-cache
fallback / 503-on-open-breaker behavior.
"""

import pytest

from cruise_control_tpu.common.resources import Resource
from cruise_control_tpu.config.cruise_control_config import CruiseControlConfig
from cruise_control_tpu.executor.admin import (
    InMemoryAdminBackend, PartitionState,
)
from cruise_control_tpu.executor.executor import Executor
from cruise_control_tpu.facade import CruiseControl
from cruise_control_tpu.monitor import LoadMonitor, StaticCapacityResolver
from cruise_control_tpu.monitor.sampling import SyntheticSampler
from cruise_control_tpu.testing.chaos import (
    ChaosAdminBackend, ChaosSampler, ChaosTransientError, FaultSchedule,
    run_faulted_executor_cycle,
)
from cruise_control_tpu.utils.resilience import (
    BreakerOpenError, BreakerState, CircuitBreaker, RetryPolicy,
    call_with_resilience,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# RetryPolicy: backoff + jitter determinism
# ---------------------------------------------------------------------------

def test_backoff_schedule_is_deterministic_and_seeded():
    p1 = RetryPolicy(base_backoff_s=0.1, max_backoff_s=10.0, multiplier=2.0,
                     jitter_ratio=0.2, seed=7)
    p2 = RetryPolicy(base_backoff_s=0.1, max_backoff_s=10.0, multiplier=2.0,
                     jitter_ratio=0.2, seed=7)
    sched1 = [p1.backoff_s("op", a) for a in range(2, 10)]
    sched2 = [p2.backoff_s("op", a) for a in range(2, 10)]
    assert sched1 == sched2, "same seed must replay the same schedule"
    # Jitter only ever SUBTRACTS from the exponential envelope.
    for attempt, b in enumerate(sched1, start=2):
        envelope = min(10.0, 0.1 * 2.0 ** (attempt - 2))
        assert envelope * (1 - 0.2) <= b <= envelope
    # A different seed (or op) jitters differently.
    p3 = RetryPolicy(base_backoff_s=0.1, jitter_ratio=0.2, seed=8)
    assert [p3.backoff_s("op", a) for a in range(2, 10)] != sched1
    assert [p1.backoff_s("other", a) for a in range(2, 10)] != sched1


def test_retry_succeeds_after_transient_failures_with_exact_backoffs():
    policy = RetryPolicy(max_attempts=5, base_backoff_s=0.5, jitter_ratio=0.2,
                         seed=3, overall_deadline_s=1e9)
    clock, sleeps, calls = FakeClock(), [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ChaosTransientError("boom")
        return "ok"

    out = call_with_resilience("flaky.op", flaky, policy=policy,
                               clock=clock, sleep=sleeps.append)
    assert out == "ok"
    assert len(calls) == 3
    assert sleeps == [policy.backoff_s("flaky.op", 2),
                      policy.backoff_s("flaky.op", 3)]


def test_retry_exhaustion_and_nonretryable_classification():
    policy = RetryPolicy(max_attempts=3, base_backoff_s=0.0,
                         jitter_ratio=0.0, overall_deadline_s=1e9)
    calls = []

    def always():
        calls.append(1)
        raise ChaosTransientError("nope")

    with pytest.raises(ChaosTransientError):
        call_with_resilience("x", always, policy=policy,
                             sleep=lambda s: None)
    assert len(calls) == 3, "transient errors retry to the attempt budget"

    calls.clear()

    def broken():
        calls.append(1)
        raise ValueError("a bug, not weather")

    with pytest.raises(ValueError):
        call_with_resilience("x", broken, policy=policy,
                             sleep=lambda s: None)
    assert len(calls) == 1, "programming errors must never retry"


def test_overall_deadline_stops_retrying():
    policy = RetryPolicy(max_attempts=100, base_backoff_s=10.0,
                         jitter_ratio=0.0, overall_deadline_s=15.0)
    clock, calls = FakeClock(), []

    def always():
        calls.append(1)
        raise ChaosTransientError()

    def sleep(s):
        clock.advance(s)

    with pytest.raises(ChaosTransientError):
        call_with_resilience("x", always, policy=policy, clock=clock,
                             sleep=sleep)
    # 10s backoff fits the 15s budget once; the second would overrun.
    assert len(calls) == 2


def test_kafka_protocol_errors_classify_as_transient_by_code():
    """The wire client's retriable broker responses (leadership /
    controller movement) must retry under a RetryPolicy; permanent
    protocol errors must not."""
    from cruise_control_tpu.kafka.wire import messages as m
    from cruise_control_tpu.utils.resilience import default_retryable

    assert default_retryable(m.KafkaProtocolError(m.NOT_CONTROLLER))
    assert default_retryable(m.KafkaProtocolError(m.NOT_LEADER_OR_FOLLOWER))
    assert not default_retryable(m.KafkaProtocolError(m.INVALID_REQUEST))
    assert not default_retryable(m.KafkaProtocolError(m.LOG_DIR_NOT_FOUND))


def test_retries_are_visible_as_spans_and_sensors():
    """Acceptance: every retry shows up in /trace (a resilience.retry
    child span nested in the ambient operation) and /metrics
    (retry_attempts_total{op=})."""
    from cruise_control_tpu.utils.sensors import SENSORS
    from cruise_control_tpu.utils.tracing import TRACER, span_names

    TRACER.configure(enabled=True)
    TRACER.clear()
    policy = RetryPolicy(max_attempts=3, base_backoff_s=0.0,
                         jitter_ratio=0.0)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise ChaosTransientError()
        return "ok"

    with TRACER.span("rebalance", operation="rebalance"):
        call_with_resilience("admin.alter_partition_reassignments", flaky,
                             policy=policy, sleep=lambda s: None)
    (trace,) = TRACER.traces(operation="rebalance", limit=1)
    assert "resilience.retry" in span_names(trace)
    snap = SENSORS.render()
    assert 'retry_attempts_total{op="admin.alter_partition_reassignments"}' \
        in snap.replace("kafka_cruisecontrol_", "")


# ---------------------------------------------------------------------------
# CircuitBreaker: state transitions on an injected clock
# ---------------------------------------------------------------------------

def test_breaker_full_lifecycle_under_injected_clock():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=3, recovery_s=30.0, clock=clock)
    t = "cluster-a"
    assert b.state(t) is BreakerState.CLOSED and b.allow(t)
    b.record_failure(t)
    b.record_failure(t)
    assert b.state(t) is BreakerState.CLOSED, "below threshold stays closed"
    b.record_failure(t)
    assert b.state(t) is BreakerState.OPEN
    assert not b.allow(t)
    assert b.retry_after_s(t) == pytest.approx(30.0)
    clock.advance(29.0)
    assert not b.allow(t)
    assert b.retry_after_s(t) == pytest.approx(1.0)
    clock.advance(1.0)
    assert b.allow(t), "recovery elapsed: half-open probe admitted"
    assert b.state(t) is BreakerState.HALF_OPEN
    # Failed probe re-opens with a FRESH window.
    b.record_failure(t)
    assert b.state(t) is BreakerState.OPEN
    assert b.retry_after_s(t) == pytest.approx(30.0)
    clock.advance(31.0)
    assert b.allow(t)
    b.record_success(t)
    assert b.state(t) is BreakerState.CLOSED
    # A success resets the consecutive-failure count.
    b.record_failure(t)
    b.record_failure(t)
    b.record_success(t)
    b.record_failure(t)
    b.record_failure(t)
    assert b.state(t) is BreakerState.CLOSED


def test_breaker_targets_are_independent_and_guard_raises():
    clock = FakeClock()
    b = CircuitBreaker(failure_threshold=1, recovery_s=10.0, clock=clock)
    b.record_failure("bad")
    assert b.state("bad") is BreakerState.OPEN
    assert b.allow("good"), "one target's breaker must not affect another"
    with pytest.raises(BreakerOpenError) as ei:
        b.guard("bad")
    assert ei.value.retry_after_s == pytest.approx(10.0)
    b.guard("good")  # no raise


def test_disabled_breaker_and_noop_wrapper_passthrough():
    b = CircuitBreaker(failure_threshold=0)
    for _ in range(10):
        b.record_failure("t")
    assert b.allow("t")
    assert call_with_resilience("x", lambda: 42) == 42


# ---------------------------------------------------------------------------
# Chaos schedule + faulted executor cycles
# ---------------------------------------------------------------------------

def test_fault_schedule_is_deterministic_and_stoppable():
    s1 = FaultSchedule(seed=5, fault_rate=0.3)
    s2 = FaultSchedule(seed=5, fault_rate=0.3)
    rolls1 = [s1.next_fault("op") for _ in range(300)]
    rolls2 = [s2.next_fault("op") for _ in range(300)]
    assert rolls1 == rolls2
    injected = [k for k in rolls1 if k is not None]
    assert 0.15 < len(injected) / 300 < 0.45, "rate must be roughly honored"
    assert {"timeout", "transient", "partial", "slow"} >= set(injected)
    s1.stop()
    assert all(s1.next_fault("op") is None for _ in range(50))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_faulted_executor_cycle_converges(seed):
    """Acceptance: a full execution against the fault-injecting backend
    (25% transient rate, deterministic seed) completes with correct
    final assignments — across three seeds, no flakes."""
    r = run_faulted_executor_cycle(seed=seed, fault_rate=0.25,
                                   max_attempts=8, dead_letter_attempts=6)
    assert r["converged"], r
    assert r["abandoned"] == 0
    assert r["faults_injected"] > 0, "the schedule must actually fire"


def test_executor_dead_letters_unsubmittable_tasks():
    """A submission that NEVER reaches the backend is dead-lettered to
    EXECUTION_ABANDONED after the attempt budget (with a notifier
    event) instead of hanging until the global task timeout."""
    from cruise_control_tpu.analyzer.proposals import ExecutionProposal

    parts = {("t", 0): PartitionState("t", 0, (0, 1), 0, isr=(0, 1))}
    backend = InMemoryAdminBackend(parts.values())

    class DeadControlPlane:
        def __getattr__(self, name):
            return getattr(backend, name)

        def alter_partition_reassignments(self, targets):
            raise ChaosTransientError("control plane unreachable")

    events = []

    class Recorder:
        def on_execution_finished(self, summary):
            events.append(("finished", summary))

        def on_execution_stopped(self, summary):
            events.append(("stopped", summary))

        def on_tasks_abandoned(self, summary):
            events.append(("abandoned", summary))

    policy = RetryPolicy(max_attempts=2, base_backoff_s=0.0,
                         jitter_ratio=0.0)
    ex = Executor(DeadControlPlane(), synchronous=True,
                  progress_check_interval_s=0.0, adjuster_enabled=False,
                  retry_policy=policy, dead_letter_attempts=2,
                  notifier=Recorder())
    ex.execute_proposals([ExecutionProposal(
        topic="t", partition=0, old_leader=0, old_replicas=(0, 1),
        new_replicas=(1, 2), new_leader=1)], uuid="dead-letter")
    counts = ex.execution_state()["taskCounts"]
    assert counts["inter_broker_replica_action"] == {"abandoned": 1}
    kinds = [k for k, _ in events]
    assert "abandoned" in kinds and "finished" in kinds
    abandoned = dict(events)["abandoned"]
    assert abandoned["numTasks"] == 1 and abandoned["uuid"] == "dead-letter"


def test_leadership_verify_failures_kill_but_never_dead_letter():
    """elect_leaders lands but the completion read-back keeps failing:
    the tasks must NOT be reported as EXECUTION_ABANDONED ('control
    plane never got through' — a lie here); after the verify budget
    they are DEAD-marked, with no on_tasks_abandoned event."""
    from cruise_control_tpu.analyzer.proposals import ExecutionProposal

    parts = {("t", 0): PartitionState("t", 0, (0, 1), 1, isr=(0, 1))}
    backend = InMemoryAdminBackend(parts.values())

    class BlindReadback:
        def __init__(self):
            self.elections = 0

        def __getattr__(self, name):
            return getattr(backend, name)

        def elect_leaders(self, partitions):
            self.elections += 1
            return backend.elect_leaders(partitions)

        def describe_partitions(self):
            raise ChaosTransientError("metadata unreachable")

    events = []

    class Recorder:
        def on_execution_finished(self, summary):
            pass

        def on_execution_stopped(self, summary):
            pass

        def on_tasks_abandoned(self, summary):
            events.append(summary)

    admin = BlindReadback()
    ex = Executor(admin, synchronous=True, progress_check_interval_s=0.0,
                  adjuster_enabled=False,
                  retry_policy=RetryPolicy(max_attempts=2, base_backoff_s=0.0,
                                           jitter_ratio=0.0),
                  dead_letter_attempts=3, notifier=Recorder())
    ex.execute_proposals([ExecutionProposal(
        topic="t", partition=0, old_leader=1, old_replicas=(0, 1),
        new_replicas=(0, 1), new_leader=0)], uuid="blind")
    counts = ex.execution_state()["taskCounts"]["leader_action"]
    assert counts == {"dead": 1}, counts
    assert not events, "verify failures must not fire on_tasks_abandoned"
    assert admin.elections == 3, "requeued re-elections up to the budget"


def test_executor_task_timeout_sensor_and_notifier_event():
    """The deduped timeout helper fires on both poll paths: a stalled
    reassignment past task_timeout_s is DEAD-marked with a
    task_timeouts_total sensor and an on_task_timeout notifier event."""
    from cruise_control_tpu.analyzer.proposals import ExecutionProposal
    from cruise_control_tpu.utils.sensors import SENSORS

    parts = {("t", 0): PartitionState("t", 0, (0, 1), 0, isr=(0, 1)),
             # Broker 2 hosts something, so it is ALIVE — the stalled
             # task must hit the TIMEOUT branch, not dead-destination.
             ("t", 1): PartitionState("t", 1, (2,), 2, isr=(2,))}
    # steps_per_tick=0: the simulated cluster never completes the move.
    backend = InMemoryAdminBackend(parts.values(), steps_per_tick=0)
    timeouts = []

    class Recorder:
        def on_execution_finished(self, summary):
            pass

        def on_execution_stopped(self, summary):
            pass

        def on_task_timeout(self, task):
            timeouts.append(task)

    ex = Executor(backend, synchronous=True, progress_check_interval_s=0.0,
                  adjuster_enabled=False, task_timeout_s=0.0,
                  notifier=Recorder())
    ex.execute_proposals([ExecutionProposal(
        topic="t", partition=0, old_leader=0, old_replicas=(0, 1),
        new_replicas=(0, 2), new_leader=0)], uuid="timeout")
    counts = ex.execution_state()["taskCounts"]
    assert counts["inter_broker_replica_action"] == {"dead": 1}
    assert len(timeouts) == 1 and timeouts[0]["state"] == "in_progress"
    snap = SENSORS.render()
    assert "task_timeouts_total" in snap


# ---------------------------------------------------------------------------
# Fetcher: partial-window acceptance + stable assignment
# ---------------------------------------------------------------------------

class _RecordingAgg:
    def __init__(self):
        self.batches = []

    def add_samples_batch(self, ents, time_ms, vals):
        self.batches.append((ents, time_ms, vals))


class _NullStore:
    def store_samples(self, result):
        pass


def _split_assignor(partitions, num_fetchers):
    buckets = [{} for _ in range(num_fetchers)]
    for i, (key, st) in enumerate(sorted(partitions.items())):
        buckets[i % num_fetchers][key] = st
    return buckets


class _FailingSampler:
    def get_samples(self, partitions, start_ms, end_ms):
        raise ChaosTransientError("sampler down")

    def close(self):
        pass


def _fetch_partitions(n=8):
    return {(f"t{i}", 0): PartitionState(f"t{i}", 0, (0,), 0, isr=(0,))
            for i in range(n)}


def test_fetcher_accepts_partial_window_above_floor():
    from cruise_control_tpu.monitor.sampling.fetcher import (
        MetricFetcherManager,
    )
    pagg, bagg = _RecordingAgg(), _RecordingAgg()
    mgr = MetricFetcherManager(
        [SyntheticSampler(), _FailingSampler()], pagg, bagg, _NullStore(),
        assignor=_split_assignor, min_completeness=0.25)
    merged = mgr.fetch_metric_samples(_fetch_partitions, 0, 1000)
    assert merged.skipped_partitions == 4, "the failed fetcher's bucket"
    assert len(merged.partition_samples) == 4, "the healthy bucket landed"
    assert pagg.batches, "partial window must still be ingested"
    mgr.shutdown()


def test_fetcher_rejects_window_below_completeness_floor():
    from cruise_control_tpu.monitor.sampling.fetcher import (
        MetricFetcherManager, PartialWindowError,
    )
    pagg, bagg = _RecordingAgg(), _RecordingAgg()
    mgr = MetricFetcherManager(
        [SyntheticSampler(), _FailingSampler()], pagg, bagg, _NullStore(),
        assignor=_split_assignor, min_completeness=0.75)
    with pytest.raises(PartialWindowError):
        mgr.fetch_metric_samples(_fetch_partitions, 0, 1000)
    assert not pagg.batches, "a rejected window must not be ingested"
    mgr.shutdown()


def test_fetcher_retries_flaky_sampler_to_success():
    from cruise_control_tpu.monitor.sampling.fetcher import (
        MetricFetcherManager,
    )

    class FlakyOnce:
        def __init__(self):
            self.calls = 0
            self.inner = SyntheticSampler()

        def get_samples(self, partitions, start_ms, end_ms):
            self.calls += 1
            if self.calls == 1:
                raise ChaosTransientError("first call drops")
            return self.inner.get_samples(partitions, start_ms, end_ms)

        def close(self):
            pass

    pagg, bagg = _RecordingAgg(), _RecordingAgg()
    flaky = FlakyOnce()
    mgr = MetricFetcherManager(
        [flaky], pagg, bagg, _NullStore(),
        retry_policy=RetryPolicy(max_attempts=3, base_backoff_s=0.0,
                                 jitter_ratio=0.0))
    merged = mgr.fetch_metric_samples(_fetch_partitions, 0, 1000)
    assert flaky.calls == 2
    assert merged.skipped_partitions == 0
    assert len(merged.partition_samples) == 8
    mgr.shutdown()


# ---------------------------------------------------------------------------
# Fleet scheduler: skip-on-open-breaker
# ---------------------------------------------------------------------------

def test_fleet_scheduler_skips_open_breaker_cluster_and_recovers():
    from cruise_control_tpu.fleet.scheduler import FleetScheduler, JobKind

    clock = FakeClock()
    breaker = CircuitBreaker(failure_threshold=2, recovery_s=30.0,
                             clock=clock, name="fleet")
    sched = FleetScheduler(starvation_bound_s=1e9, clock=clock,
                           breaker=breaker)

    def boom():
        raise ChaosTransientError("cluster broken")

    for _ in range(2):
        f = sched.submit("bad", JobKind.ON_DEMAND, boom)
        sched.run_pending()
        with pytest.raises(ChaosTransientError):
            f.result(timeout=1)
    assert breaker.state("bad") is BreakerState.OPEN

    ran = []
    f_bad = sched.submit("bad", JobKind.ON_DEMAND, lambda: ran.append("bad"))
    f_good = sched.submit("good", JobKind.ON_DEMAND,
                          lambda: ran.append("good") or "ok")
    sched.run_pending()
    with pytest.raises(BreakerOpenError):
        f_bad.result(timeout=1)
    assert f_good.result(timeout=1) == "ok"
    assert ran == ["good"], "open-breaker cluster skipped, healthy one ran"

    # Recovery window elapses: the next job is the half-open probe; its
    # success closes the breaker.
    clock.advance(31.0)
    f2 = sched.submit("bad", JobKind.ON_DEMAND, lambda: "recovered")
    sched.run_pending()
    assert f2.result(timeout=1) == "recovered"
    assert breaker.state("bad") is BreakerState.CLOSED


# ---------------------------------------------------------------------------
# Detector isolation
# ---------------------------------------------------------------------------

def test_detector_breaker_isolates_crashing_detector():
    from cruise_control_tpu.detector.manager import AnomalyDetectorManager

    cfg = CruiseControlConfig({
        "resilience.breaker.failure.threshold": 2,
        "resilience.breaker.recovery.ms": 30_000,
        "failed.brokers.file.path": ""})
    mgr = AnomalyDetectorManager(cfg)
    clock = FakeClock()
    mgr._detector_breaker = CircuitBreaker(failure_threshold=2,
                                           recovery_s=30.0, clock=clock,
                                           name="detector")

    class Crashing:
        def __init__(self):
            self.runs = 0

        def run_once(self):
            self.runs += 1
            raise RuntimeError("detector bug")

    det = Crashing()
    assert not mgr.run_detector_once(det)
    assert not mgr.run_detector_once(det)
    assert det.runs == 2
    # Breaker open: further ticks are skipped without invoking it.
    assert not mgr.run_detector_once(det)
    assert not mgr.run_detector_once(det)
    assert det.runs == 2
    clock.advance(31.0)
    assert not mgr.run_detector_once(det)
    assert det.runs == 3, "recovery window elapsed: probe tick runs again"


# ---------------------------------------------------------------------------
# Facade: stale-cache fallback + breaker-gated 503, end-to-end chaos
# ---------------------------------------------------------------------------

def _partitions(brokers=(0, 1, 2, 3), topics=2, parts=6, rf=2):
    out = {}
    for t in range(topics):
        for p in range(parts):
            reps = (brokers[0], brokers[1 + (t + p) % (len(brokers) - 1)])[:rf]
            out[(f"t{t}", p)] = PartitionState(f"t{t}", p, reps, reps[0],
                                               isr=reps)
    return out


def _chaos_cruise_control(fault_rate=0.15, seed=11, extra_cfg=None):
    backend = InMemoryAdminBackend(_partitions().values())
    chaos = ChaosAdminBackend(backend, seed=seed, fault_rate=fault_rate)
    cfg = CruiseControlConfig({
        "partition.metrics.window.ms": 1000,
        "num.partition.metrics.windows": 3,
        "min.valid.partition.ratio": 0.0,
        "anomaly.detection.interval.ms": 60_000,
        "max.solver.rounds": 40,
        "failed.brokers.file.path": "",
        "resilience.retry.base.backoff.ms": 0,
        "resilience.retry.max.backoff.ms": 0,
        "resilience.retry.max.attempts": 8,
        **(extra_cfg or {})})
    caps = StaticCapacityResolver({}, {Resource.CPU: 100.0, Resource.DISK: 1e7,
                                       Resource.NW_IN: 1e6,
                                       Resource.NW_OUT: 1e6})
    sampler = ChaosSampler(SyntheticSampler(),
                           schedule=chaos.schedule)
    monitor = LoadMonitor(cfg, chaos, samplers=[sampler],
                          capacity_resolver=caps,
                          broker_racks={b: f"r{b % 2}" for b in range(8)})
    executor = Executor(chaos, synchronous=True, adjuster_enabled=False,
                        progress_check_interval_s=0.0,
                        retry_policy=RetryPolicy(
                            max_attempts=8, base_backoff_s=0.0,
                            jitter_ratio=0.0, seed=seed),
                        dead_letter_attempts=6)
    cc = CruiseControl(cfg, chaos, load_monitor=monitor, executor=executor)
    for k in range(1, 5):
        monitor.task_runner.run_sampling_once(end_ms=k * 1000)
    return cc, backend, chaos


@pytest.mark.parametrize("seed", [11, 23, 42])
def test_full_rebalance_cycle_through_chaos_backend(seed):
    """The headline chaos test: sample → model → optimize → execute with
    ≥10% injected transient failure rate end to end; the cycle must
    complete with the proposals actually applied on the (unwrapped)
    backend, deterministically per seed."""
    cc, backend, chaos = _chaos_cruise_control(fault_rate=0.15, seed=seed)
    res = cc.rebalance(dryrun=False)
    assert res.proposals, "skewed cluster must yield proposals"
    assert res.executed
    cc.executor.await_completion()
    counts = cc.executor.execution_state()["taskCounts"]
    assert counts["inter_broker_replica_action"].get("abandoned", 0) == 0
    after = backend.describe_partitions()
    for pr in res.proposals:
        assert set(after[(pr.topic, pr.partition)].replicas) \
            == set(pr.new_replicas)
    assert chaos.schedule.faults_injected > 0
    # Faults stop → the next full cycle is clean and still converges.
    chaos.schedule.stop()
    cc.load_monitor.task_runner.run_sampling_once(end_ms=10_000)
    res2 = cc.rebalance(dryrun=False)
    cc.executor.await_completion()
    after2 = backend.describe_partitions()
    for pr in res2.proposals:
        assert set(after2[(pr.topic, pr.partition)].replicas) \
            == set(pr.new_replicas)


def test_facade_serves_stale_cache_then_503_when_breaker_opens():
    cc, _backend, chaos = _chaos_cruise_control(
        fault_rate=0.0, extra_cfg={"resilience.breaker.failure.threshold": 2,
                                   "resilience.breaker.recovery.ms": 60_000})
    chaos.schedule.stop()
    good = cc.proposals()
    assert good.reason != "cached" and not good.extra.get("stale")

    def explode(*a, **k):
        raise RuntimeError("model build failed")

    cc._optimizer.optimizations = explode
    # Failure 1 + 2 (fresh model generations force real computes that
    # fail): stale fallback, marked clearly.
    for k in range(2):
        cc.load_monitor.task_runner.run_sampling_once(end_ms=(10 + k) * 1000)
        res = cc.proposals()
        assert res.extra.get("stale") is True
        assert tuple(res.proposals) == tuple(good.proposals)
        assert "stale cache fallback" in res.reason
    # Threshold reached: breaker open → fail fast with Retry-After.
    cc.load_monitor.task_runner.run_sampling_once(end_ms=12_000)
    with pytest.raises(BreakerOpenError) as ei:
        cc.proposals()
    assert ei.value.retry_after_s > 0


def test_facade_ignore_proposal_cache_refuses_stale_fallback():
    """An explicit ignore_proposal_cache=true is a contract: the caller
    refused cached answers, so a failed compute must raise, not serve
    the stale set with a 200."""
    cc, _backend, chaos = _chaos_cruise_control(fault_rate=0.0)
    chaos.schedule.stop()
    cc.proposals()  # prime the cache

    def explode(*a, **k):
        raise RuntimeError("model build failed")

    cc._optimizer.optimizations = explode
    with pytest.raises(RuntimeError, match="model build failed"):
        cc.proposals(ignore_proposal_cache=True)


def test_facade_chaos_enabled_config_wraps_admin():
    backend = InMemoryAdminBackend(_partitions().values())
    cfg = CruiseControlConfig({
        "chaos.enabled": True, "chaos.seed": 4, "chaos.fault.rate": 0.5,
        "failed.brokers.file.path": ""})
    cc = CruiseControl(cfg, backend)
    assert isinstance(cc._admin, ChaosAdminBackend)
    assert cc._admin.schedule.seed == 4
