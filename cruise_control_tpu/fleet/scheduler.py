"""Fair solver-work scheduler: one device (or mesh), many clusters.

All solver work in a fleet funnels through this scheduler, which
decides whose work runs next. Three priority classes — self-healing >
expiring proposal cache > on-demand requests — with round-robin
fairness ACROSS clusters inside each class, and a starvation bound:
any job that has waited longer than the bound runs next regardless of
class, oldest first, so a cluster flooding a higher class can delay
but never indefinitely starve another cluster's work.

Multi-replica control plane (round 23, ``fleet.shard.workers``): N
solver worker threads drain the SAME queue, sharing the process's
persistent AOT cache and shape registry (both are process-global — a
program any worker compiles is warm for all). Placement is
bucket-affine: the first worker to solve a batch key becomes its home,
so a bucket's compiled megabatch program stays hot on the replica that
owns it instead of ping-ponging. Two forms of work-stealing keep the
fairness contract fleet-wide: an OVERDUE job (past the starvation
bound) is taken by whichever worker sees it first regardless of
affinity — the bound is a promise to the cluster, not to a worker —
and an otherwise-idle worker steals affined work rather than sit while
another replica's queue is deep. ``workers=1`` is byte-identical to
the single-worker scheduler of rounds 6-22.

The reference has no analogue (one JVM per cluster = the OS scheduler);
the closest relative is GoalOptimizer's proposal-precompute executor
(GoalOptimizer.java:112-119), which this subsumes fleet-wide: the
pacer enqueues one EXPIRING_CACHE job per cluster at that cluster's
configured cadence (fleet.precompute.cadence.ms) whenever its proposal
cache is no longer fresh.
"""

from __future__ import annotations

import dataclasses
import enum
import logging
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable

from ..utils.resilience import BreakerOpenError, CircuitBreaker

LOG = logging.getLogger(__name__)


class JobKind(enum.IntEnum):
    """Priority classes, lower = more urgent."""

    SELF_HEALING = 0
    EXPIRING_CACHE = 1
    ON_DEMAND = 2


@dataclasses.dataclass
class SolverJob:
    kind: JobKind
    cluster_id: str
    fn: Callable[[], Any]
    future: Future
    enqueued_at: float
    seq: int
    # Coalescing hint (megabatch mode): queued jobs sharing a non-None
    # batch_key are drained together when one of them is picked and
    # solved as ONE batched device program. ``payload`` carries what the
    # batch runner needs (fleet.megabatch.PrecomputePayload); ``fn``
    # stays the solo fallback for inline/shutdown execution.
    batch_key: tuple | None = None
    payload: Any = None
    # Heal-ledger correlation (round 16): the ambient heal handle at
    # submit time (None when no heal in flight). A self-healing fix
    # routed through the scheduler re-enters its heal scope on the
    # worker thread and attributes its queue wait to the chain.
    heal: Any = None
    # The submitter's ambient trace span (None outside any): the worker
    # re-enters it, so a served request's fleet.job stays in its trace.
    parent_span: Any = None


class FleetScheduler:
    """Single-consumer priority queue over the fleet's solver work.

    ``submit`` returns a Future; one worker thread (or a test calling
    ``run_pending`` synchronously) drains the queue. ``clock`` is
    injectable so starvation/fairness behavior is testable without
    real waiting.
    """

    @classmethod
    def from_config(cls, config) -> "FleetScheduler":
        """Build with the configured starvation bound
        (fleet.scheduler.starvation.bound.ms), worker replica count
        (fleet.shard.workers) and the per-cluster circuit breaker
        (resilience.breaker.*)."""
        return cls(
            starvation_bound_s=config.get_long(
                "fleet.scheduler.starvation.bound.ms") / 1000.0,
            breaker=CircuitBreaker.from_config(config, name="fleet"),
            workers=config.get_int("fleet.shard.workers"))

    def __init__(self, starvation_bound_s: float = 30.0,
                 clock: Callable[[], float] = time.monotonic,
                 breaker: CircuitBreaker | None = None,
                 workers: int = 1):
        self._starvation_bound_s = starvation_bound_s
        self._clock = clock
        self._workers_n = max(1, int(workers))
        # Per-cluster breaker (round 9): a cluster whose jobs keep
        # failing trips open and its queued work is SKIPPED (futures
        # fail fast with BreakerOpenError) instead of burning solver
        # rounds and starving the round-robin for healthy clusters.
        self._breaker = breaker
        self._cond = threading.Condition()
        self._queue: list[SolverJob] = []
        self._seq = 0
        self._picks = 0
        # cluster -> pick-counter value of its last pick, for round-robin
        # fairness inside a priority class (least recently served wins).
        self._last_served: dict[str, int] = {}
        self._stop = threading.Event()
        self._shut = False
        self._worker: threading.Thread | None = None
        self._solvers: list[threading.Thread] = []
        # batch_key -> home worker id (round 23 bucket affinity): set by
        # the first pick of a job carrying that key; later picks prefer
        # the home worker so the bucket's compiled megabatch program
        # stays hot there. Overdue jobs and idle workers steal across it.
        self._affinity: dict[tuple, int] = {}
        self._pacer: threading.Thread | None = None
        self._registry = None
        self._jobs_run = 0
        # Megabatch coalescing (round 14): when a batch runner is
        # attached, a picked job with a batch_key drains every queued
        # job sharing that key and the whole set solves as ONE batched
        # device program. Fairness and the starvation bound apply to
        # BATCHES: the pick that seeds a batch is chosen by the normal
        # priority/fairness/starvation rules, and every coalesced
        # cluster counts as served by that pick.
        self._batch_runner: Callable[[list[SolverJob]], None] | None = None
        # (cluster_id, kind) keys currently executing — a SET because a
        # coalesced megabatch executes many clusters' jobs at once and
        # the pacer must see every one of them as in-flight.
        self._active: set[tuple[str, JobKind]] = set()

    def set_batch_runner(self, runner: "Callable | None") -> None:
        """Attach the megabatch coalescing runner (fleet.megabatch).
        ``runner(jobs)`` receives the drained batch and must resolve
        every job's future; None disables coalescing."""
        with self._cond:
            self._batch_runner = runner

    @property
    def coalescing(self) -> bool:
        return self._batch_runner is not None

    # -- submission --------------------------------------------------------
    def submit(self, cluster_id: str, kind: JobKind,
               fn: Callable[[], Any], batch_key: tuple | None = None,
               payload: Any = None) -> Future:
        from ..utils.heal_ledger import current_heal
        from ..utils.tracing import TRACER
        heal = current_heal()
        job = SolverJob(kind=kind, cluster_id=cluster_id, fn=fn,
                        future=Future(), enqueued_at=self._clock(),
                        seq=self._next_seq(), batch_key=batch_key,
                        payload=payload,
                        heal=heal if heal.recording else None,
                        parent_span=TRACER.current_span())
        with self._cond:
            if self._shut:
                # After shutdown nothing drains the queue; a queued job's
                # .result() would block its caller forever. Run inline —
                # correctness over fairness (mirrors the not-running
                # guards at the call sites).
                inline = True
            else:
                inline = False
                self._queue.append(job)
                self._cond.notify()
        from ..utils.sensors import SENSORS
        SENSORS.count("fleet_scheduler_jobs_submitted",
                      labels={"cluster": cluster_id, "kind": kind.name})
        if inline:
            self._run(job)
        return job.future

    def _next_seq(self) -> int:
        with self._cond:
            self._seq += 1
            return self._seq

    def pending(self, cluster_id: str | None = None,
                kind: JobKind | None = None) -> int:
        with self._cond:
            return sum(1 for j in self._queue
                       if (cluster_id is None or j.cluster_id == cluster_id)
                       and (kind is None or j.kind == kind))

    # -- selection ---------------------------------------------------------
    def _pick_locked(self, worker_id: int = 0) -> SolverJob | None:
        """Next job for ``worker_id`` under priority + fairness + the
        starvation bound + bucket affinity (round 23).
        Caller holds the condition lock."""
        if self._queue and self._breaker is not None:
            # Skip (fail fast) queued jobs for open-breaker clusters —
            # an API caller blocked on the future gets 503 + Retry-After,
            # the pacer's precompute re-enqueues next sweep, and healthy
            # clusters' work proceeds. ``allow`` flips a recovered
            # cluster to half-open, so its next job runs as the probe.
            skipped = [j for j in self._queue
                       if not self._breaker.allow(j.cluster_id)]
            if skipped:
                from ..utils.sensors import SENSORS
                for job in skipped:
                    self._queue.remove(job)
                    SENSORS.count("fleet_jobs_skipped",
                                  labels={"cluster": job.cluster_id,
                                          "kind": job.kind.name})
                    if job.heal is not None:
                        # A fix skipped by an open breaker is a
                        # documented heal terminal — the manager also
                        # resolves breaker_skipped on the raised error,
                        # but the resolve is idempotent (first wins) and
                        # a non-fix correlated job records it here.
                        job.heal.resolve("breaker_skipped",
                                         cluster=job.cluster_id)
                    job.future.set_exception(BreakerOpenError(
                        job.cluster_id,
                        self._breaker.retry_after_s(job.cluster_id)))
        if not self._queue:
            return None
        now = self._clock()
        stolen = False
        overdue = [j for j in self._queue
                   if now - j.enqueued_at >= self._starvation_bound_s]
        if overdue:
            # The bound dominates everything — including affinity: the
            # oldest overdue job runs on WHICHEVER worker sees it first
            # (the bound is a promise to the cluster, not to a worker),
            # so the starvation guarantee holds fleet-wide.
            job = min(overdue, key=lambda j: (j.enqueued_at, j.seq))
            stolen = self._affined_elsewhere(job, worker_id)
        else:
            best_kind = min(j.kind for j in self._queue)
            in_class = [j for j in self._queue if j.kind == best_kind]
            # Bucket affinity (round 23): prefer jobs homed on this
            # worker or not yet homed; an idle worker STEALS an
            # affined-elsewhere job rather than sit while another
            # replica's share is deep (throughput over placement — the
            # shared AOT cache makes a steal a cache miss, not a
            # recompile).
            mine = [j for j in in_class
                    if not self._affined_elsewhere(j, worker_id)]
            pool = mine or in_class
            stolen = not mine
            # Round-robin by cluster: the cluster served longest ago goes
            # first; within a cluster, FIFO.
            job = min(pool, key=lambda j: (
                self._last_served.get(j.cluster_id, 0), j.seq))
        self._queue.remove(job)
        self._picks += 1
        self._last_served[job.cluster_id] = self._picks
        if job.batch_key is not None:
            from ..utils.sensors import SENSORS
            home = self._affinity.get(job.batch_key)
            if home is None:
                # First pick homes the bucket on this worker.
                self._affinity[job.batch_key] = worker_id
            elif home == worker_id:
                SENSORS.count("fleet_shard_affinity_hits")
            if stolen:
                # A steal re-homes the bucket: the stealing worker's
                # dispatch caches are now the warm ones.
                self._affinity[job.batch_key] = worker_id
                SENSORS.count("fleet_shard_steals")
        # Marked active HERE, under the same lock as the dequeue: a
        # pacer sweep must never observe the job as neither queued nor
        # active (the window between dequeue and execution).
        self._active.add((job.cluster_id, job.kind))
        return job

    def _affined_elsewhere(self, job: SolverJob, worker_id: int) -> bool:
        """Whether the job's bucket is homed on a DIFFERENT worker (jobs
        without a batch key are never affined — any worker serves
        them)."""
        if job.batch_key is None:
            return False
        home = self._affinity.get(job.batch_key)
        return home is not None and home != worker_id

    def _take_locked(self, worker_id: int = 0) -> list[SolverJob] | None:
        """Pick the next job, then — in coalescing mode — drain every
        queued job sharing its batch_key into one megabatch. The PICK is
        fairness's unit (priority, round-robin, starvation bound all
        choose the seed job); the drained peers ride along and every
        coalesced cluster counts as served by this pick, so the
        round-robin cannot re-serve a freshly batched cluster ahead of
        one still waiting. Caller holds the condition lock."""
        job = self._pick_locked(worker_id)
        if job is None:
            return None
        batch = [job]
        if self._batch_runner is not None and job.batch_key is not None:
            peers = [j for j in self._queue
                     if j.batch_key == job.batch_key]
            for p in peers:
                self._queue.remove(p)
                self._last_served[p.cluster_id] = self._picks
                self._active.add((p.cluster_id, p.kind))
            batch += peers
        return batch

    def _run(self, job: SolverJob) -> None:
        from ..utils.heal_ledger import heal_scope
        from ..utils.sensors import SENSORS, cluster_label
        from ..utils.tracing import TRACER
        wait_s = max(self._clock() - job.enqueued_at, 0.0)
        SENSORS.record_timer("fleet_scheduler_queue_wait", wait_s,
                             labels={"cluster": job.cluster_id,
                                     "kind": job.kind.name})
        # Queue-wait DISTRIBUTION per priority class: the timer above
        # collapses to count/sum/last/max; fairness regressions live in
        # the tail, which only a histogram preserves.
        SENSORS.observe("fleet_queue_wait_seconds", wait_s,
                        labels={"cluster": job.cluster_id,
                                "kind": job.kind.name})
        if job.heal is not None:
            # Where the heal's time went, scheduler edition: the chain
            # sees how long the fix sat behind other clusters' work.
            job.heal.phase("solver_queued", kind=job.kind.name,
                           waitS=round(wait_s, 6))
        t0 = time.monotonic()
        try:
            # The wrapping span carries the queue wait. A job submitted
            # under a span (a served request's engine worker) stays in
            # that trace: the submitter's span is re-entered here like the
            # heal scope, because ContextVars do not cross into the worker
            # thread. A job nobody's request caused (the pacer's
            # precomputes, a heal) has no parent, so fleet.job IS the root
            # and the op span nests under it.
            with cluster_label(job.cluster_id), \
                    TRACER.attach(job.parent_span), \
                    TRACER.span("fleet.job", operation=f"fleet.{job.kind.name.lower()}",
                                cluster=job.cluster_id, kind=job.kind.name,
                                queue_wait_s=round(wait_s, 6)), \
                    heal_scope(job.heal):
                result = job.fn()
        except BaseException as e:  # noqa: BLE001 — carried by the future
            if self._breaker is not None:
                self._breaker.record_failure(job.cluster_id)
            job.future.set_exception(e)
        else:
            if self._breaker is not None:
                self._breaker.record_success(job.cluster_id)
            job.future.set_result(result)
        finally:
            with self._cond:
                self._active.discard((job.cluster_id, job.kind))
        self._jobs_run += 1
        SENSORS.record_timer("fleet_scheduler_job",
                             time.monotonic() - t0,
                             labels={"cluster": job.cluster_id,
                                     "kind": job.kind.name})

    def _run_batch(self, jobs: list[SolverJob]) -> None:
        """Execute a coalesced megabatch through the batch runner. The
        runner must resolve every job's future (result or exception);
        anything it leaves unresolved — or a batch-level crash — fails
        the affected futures here so no caller ever blocks forever.
        Per-cluster breaker accounting mirrors ``_run``'s."""
        from ..utils.sensors import SENSORS
        from ..utils.tracing import TRACER
        t0 = time.monotonic()
        for job in jobs:
            wait_s = max(self._clock() - job.enqueued_at, 0.0)
            SENSORS.record_timer("fleet_scheduler_queue_wait", wait_s,
                                 labels={"cluster": job.cluster_id,
                                         "kind": job.kind.name})
            SENSORS.observe("fleet_queue_wait_seconds", wait_s,
                            labels={"cluster": job.cluster_id,
                                    "kind": job.kind.name})
            if job.heal is not None:
                job.heal.phase("solver_queued", kind=job.kind.name,
                               waitS=round(wait_s, 6))
        try:
            # No ambient cluster label: the batch belongs to the FLEET
            # (per-cluster attribution happens inside the runner with
            # explicit labels; an ambient lead-cluster label would
            # mislabel the batch-level occupancy sensors).
            with TRACER.span("fleet.megabatch",
                             operation="fleet.megabatch",
                             clusters=",".join(j.cluster_id
                                               for j in jobs),
                             occupancy=len(jobs)):
                self._batch_runner(jobs)
        except BaseException as e:  # noqa: BLE001 — carried by futures
            for job in jobs:
                if not job.future.done():
                    job.future.set_exception(e)
        finally:
            for job in jobs:
                if not job.future.done():
                    job.future.set_exception(RuntimeError(
                        "megabatch runner left the job unresolved"))
            with self._cond:
                for job in jobs:
                    self._active.discard((job.cluster_id, job.kind))
            self._jobs_run += len(jobs)
        if self._breaker is not None:
            for job in jobs:
                if job.future.cancelled() or \
                        job.future.exception() is not None:
                    self._breaker.record_failure(job.cluster_id)
                else:
                    self._breaker.record_success(job.cluster_id)
        SENSORS.count("fleet_jobs_coalesced", len(jobs))
        SENSORS.record_timer("fleet_scheduler_job",
                             time.monotonic() - t0,
                             labels={"cluster": jobs[0].cluster_id,
                                     "kind": jobs[0].kind.name})

    def run_pending(self, max_jobs: int | None = None,
                    worker_id: int = 0) -> int:
        """Synchronously drain queued jobs on the calling thread (the
        deterministic test driver; also usable by an embedder that wants
        its own loop). ``worker_id`` is the replica identity used for
        bucket affinity — tests drive multi-worker placement by calling
        with different ids. Returns the number of jobs run."""
        ran = 0
        while max_jobs is None or ran < max_jobs:
            with self._cond:
                batch = self._take_locked(worker_id)
            if batch is None:
                break
            if self._batch_runner is not None \
                    and batch[0].batch_key is not None:
                self._run_batch(batch)
            else:
                self._run(batch[0])
            ran += len(batch)
        return ran

    # -- worker + precompute pacer ----------------------------------------
    def bind(self, registry) -> None:
        """Attach the registry whose clusters the pacer sweeps (called by
        FleetRegistry at construction; no threads started)."""
        self._registry = registry

    def start(self, registry=None, pacer_interval_s: float = 1.0,
              pacer: bool = True) -> None:
        """Start the solver worker thread(s) — ``fleet.shard.workers``
        replicas draining the shared queue; with a registry (or one
        already bound), also the precompute pacer that keeps every
        unpaused cluster's proposal cache warm at its configured cadence
        (``pacer=False`` starts the workers alone)."""
        registry = registry or self._registry
        self._registry = registry
        with self._cond:
            self._shut = False
        if not any(t.is_alive() for t in self._solvers):
            self._stop.clear()
            self._solvers = [
                threading.Thread(target=self._worker_loop, args=(i,),
                                 daemon=True, name=f"fleet-solver-{i}")
                for i in range(self._workers_n)]
            for t in self._solvers:
                t.start()
            # ``_worker`` stays an alias of replica 0 for embedders that
            # poke at the single-worker field directly.
            self._worker = self._solvers[0]
            from ..utils.sensors import SENSORS
            SENSORS.gauge("fleet_shard_workers", self._workers_n)
        if pacer and registry is not None and (self._pacer is None
                                               or not self._pacer.is_alive()):
            self._pacer = threading.Thread(
                target=self._pacer_loop, args=(pacer_interval_s,),
                daemon=True, name="fleet-precompute-pacer")
            self._pacer.start()

    def _worker_loop(self, worker_id: int = 0) -> None:
        while not self._stop.is_set():
            with self._cond:
                batch = self._take_locked(worker_id)
                if batch is None:
                    self._cond.wait(timeout=0.2)
                    continue
            if self._batch_runner is not None \
                    and batch[0].batch_key is not None:
                self._run_batch(batch)
            else:
                self._run(batch[0])

    def _pacer_loop(self, interval_s: float) -> None:
        while not self._stop.wait(interval_s):
            try:
                self.pace_once()
            except Exception:  # noqa: BLE001 — pacing must not die
                LOG.exception("fleet precompute pacing failed")

    def pace_once(self) -> int:
        """One pacing sweep: enqueue an EXPIRING_CACHE precompute for
        every unpaused cluster whose cadence has elapsed and that has no
        precompute already queued. Returns the number enqueued."""
        if self._registry is None:
            return 0
        # Prewarm deferral (round 18): while the shared solver's
        # background shape sweep is still compiling, hold paced
        # precomputes back one sweep — racing them would compile the
        # same per-shape programs twice on the startup critical path.
        # Due clusters enqueue on the first sweep after prewarm settles
        # (last_precompute is untouched here).
        from ..warmstart import prewarm_manager
        optimizer = getattr(self._registry, "optimizer", None)
        mgr = prewarm_manager(optimizer) if optimizer is not None else None
        if mgr is not None and mgr.running:
            from ..utils.sensors import SENSORS
            SENSORS.count("fleet_pacer_prewarm_deferrals")
            return 0
        n = 0
        for entry in self._registry.entries():
            if entry.paused:
                continue
            cadence_s = entry.config.get_long(
                "fleet.precompute.cadence.ms") / 1000.0
            now = self._clock()
            # Predicted-violation promotion (round 19): a cluster whose
            # predictive detector just precomputed a projected target is
            # due NOW — its real proposal cache must be hot (and
            # warm-seeded from the predicted target) before the real
            # violation lands, not a cadence later.
            predicted = bool(getattr(entry.cc,
                                     "predicted_precompute_pending", False))
            if not predicted and now - entry.last_precompute < cadence_s:
                continue
            with self._cond:
                # One lock acquisition for BOTH states: a precompute that
                # is queued or still executing must suppress re-enqueue —
                # chaining redundant back-to-back solves would hog the
                # device for any cluster whose precompute outlasts its
                # cadence.
                key = (entry.cluster_id, JobKind.EXPIRING_CACHE)
                busy = key in self._active or any(
                    (j.cluster_id, j.kind) == key for j in self._queue)
            if busy:
                continue
            entry.last_precompute = now
            if predicted:
                entry.cc.predicted_precompute_pending = False
                from ..utils.sensors import SENSORS
                SENSORS.count("fleet_pacer_predicted_promotions",
                              labels={"cluster": entry.cluster_id})
            cc, cid = entry.cc, entry.cluster_id
            # Overlap host-side model assembly with whatever solve is
            # currently holding the device: kick the monitor's background
            # prefetch BEFORE enqueueing, so by the time this cluster's
            # precompute reaches the head of the queue its cluster model
            # is already built (the solve then starts immediately instead
            # of paying the assembly on the device's critical path).
            prefetch = getattr(getattr(cc, "load_monitor", None),
                               "prefetch_model", None)
            if prefetch is not None:
                try:
                    prefetch()
                except Exception:  # noqa: BLE001 — overlap is best-effort
                    LOG.debug("fleet: model prefetch kickoff for %s failed",
                              cid, exc_info=True)
            def precompute(cc=cc, cid=cid):
                opt = getattr(cc, "optimizer", None)
                if opt is None or not hasattr(opt, "thread_dispatch_stats"):
                    return cc.proposals()
                seq0 = opt.thread_pass_seq()
                result = cc.proposals()
                # Megastep dispatch accounting per cluster: the pacer's
                # precompute is the steady-state solve, so its dispatch
                # count / rounds-per-dispatch ARE the fleet's device-link
                # cost profile (and the visible payoff of the optimizer's
                # pass-persistent AdaptiveDispatch budget). Attribution
                # uses the optimizer's THREAD-LOCAL pass record: the
                # solve (if any) ran synchronously on this worker thread
                # inside proposals(), so an advanced thread_pass_seq
                # proves the stats are exactly this precompute's — a
                # cache-served request advances nothing, and passes that
                # other clusters' facade threads start concurrently are
                # invisible here (the shared last_dispatch_stats slot
                # could report either).
                if opt.thread_pass_seq() == seq0:
                    return result
                from ..utils.sensors import SENSORS
                ds = opt.thread_dispatch_stats()
                if ds.get("dispatch_count"):
                    SENSORS.gauge("fleet_precompute_dispatches",
                                  ds["dispatch_count"],
                                  labels={"cluster": cid})
                    SENSORS.gauge("fleet_precompute_rounds_per_dispatch_p50",
                                  ds["rounds_per_dispatch_p50"],
                                  labels={"cluster": cid})
                return result

            # Whole-bucket batch fills (ROADMAP item 3): in coalescing
            # mode every due cluster's precompute carries its bucket's
            # batch key, so a sweep that finds the whole bucket due
            # emits ONE megabatch fill instead of per-cluster solves
            # (the runner reports fleet_precompute_dispatches{cluster=}
            # from the split readback). A cluster with no recorded
            # bucket yet (first build pending) submits solo.
            batch_key = payload = None
            if self._batch_runner is not None:
                from .megabatch import PrecomputePayload, precompute_batch_key
                batch_key = precompute_batch_key(entry)
                if batch_key is not None:
                    payload = PrecomputePayload(cluster_id=cid, cc=cc)
            fut = self.submit(cid, JobKind.EXPIRING_CACHE, precompute,
                              batch_key=batch_key, payload=payload)

            def report(f, cid=cid):
                # The pacer owns this future — surface failures, else a
                # cluster whose precompute consistently fails would serve
                # a cold cache with no trace anywhere.
                exc = None if f.cancelled() else f.exception()
                if exc is not None:
                    LOG.warning("fleet: precompute for %s failed: %s",
                                cid, exc)
                    from ..utils.sensors import SENSORS
                    SENSORS.count("fleet_precompute_failures",
                                  labels={"cluster": cid})

            fut.add_done_callback(report)
            n += 1
        return n

    def shutdown(self) -> None:
        self._stop.set()
        with self._cond:
            self._shut = True
            self._cond.notify_all()
        for t in (*self._solvers, self._pacer):
            if t is not None and t.is_alive():
                t.join(timeout=10.0)
        self._solvers = []
        self._worker = self._pacer = None
        with self._cond:
            leftovers, self._queue = self._queue, []
        for job in leftovers:
            job.future.cancel()

    @property
    def jobs_run(self) -> int:
        return self._jobs_run

    @property
    def breaker(self) -> CircuitBreaker | None:
        """The per-cluster circuit breaker (None = breaking disabled)."""
        return self._breaker

    def ensure_breaker(self, config) -> None:
        """Attach the configured per-cluster breaker when none was
        injected (the FleetRegistry's wiring hook for bare schedulers);
        an existing breaker — including an injected-clock test one — is
        left untouched. Runs on the scheduler's own clock."""
        if self._breaker is None:
            self._breaker = CircuitBreaker.from_config(
                config, name="fleet", clock=self._clock)

    @property
    def running(self) -> bool:
        """True while any worker thread is draining the queue (callers
        that would block on a Future must run inline when nothing
        drains)."""
        return any(t.is_alive() for t in self._solvers)
