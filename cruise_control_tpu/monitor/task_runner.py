"""Sampling task scheduler: the monitor's background loop.

Reference parity: monitor/task/LoadMonitorTaskRunner.java:33,245 (state
machine NOT_STARTED → RUNNING/SAMPLING ↔ PAUSED, with BOOTSTRAPPING,
TRAINING and LOADING excursions), SamplingTask / BootstrapTask /
SampleLoadingTask. The executor pauses sampling around proposal execution
(Executor.java:1408-1424) via set_mode(ONGOING_EXECUTION).
"""

from __future__ import annotations

import enum
import logging
import threading


from ..executor.admin import AdminBackend
from .sampling.fetcher import MetricFetcherManager
from .sampling.sampler import now_ms
from .sampling.sample_store import SampleStore

LOG = logging.getLogger(__name__)


class RunnerState(enum.Enum):
    NOT_STARTED = "NOT_STARTED"
    LOADING = "LOADING"
    RUNNING = "RUNNING"
    SAMPLING = "SAMPLING"
    BOOTSTRAPPING = "BOOTSTRAPPING"
    TRAINING = "TRAINING"
    PAUSED = "PAUSED"


class SamplingMode(enum.Enum):
    RUNNING = "RUNNING"
    PAUSED = "PAUSED"
    ONGOING_EXECUTION = "ONGOING_EXECUTION"  # reduced-scope sampling during moves


class LoadMonitorTaskRunner:
    def __init__(self, fetcher: MetricFetcherManager, metadata: AdminBackend,
                 sample_store: SampleStore, sampling_interval_ms: int):
        self._fetcher = fetcher
        self._metadata = metadata
        self._store = sample_store
        self._interval_ms = int(sampling_interval_ms)
        self._state = RunnerState.NOT_STARTED
        self._mode = SamplingMode.RUNNING
        self._mode_reason = ""
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._last_sample_ms = 0
        self._samples_loaded = 0

    # -- lifecycle --------------------------------------------------------
    def start(self, block_on_load: bool = True) -> None:
        with self._lock:
            if self._state is not RunnerState.NOT_STARTED:
                return
            self._state = RunnerState.LOADING
        if block_on_load:
            self._load_samples()
            self._start_sampling_thread()
        else:
            def boot():
                self._load_samples()
                self._start_sampling_thread()
            threading.Thread(target=boot, name="sample-loading", daemon=True).start()

    def _load_samples(self) -> None:
        try:
            loaded = self._store.load_samples()
            self._samples_loaded = self._fetcher.replay(loaded)
            if self._samples_loaded:
                LOG.info("replayed %d samples from sample store", self._samples_loaded)
        except Exception:
            LOG.exception("sample store replay failed; starting cold")
        with self._lock:
            self._state = RunnerState.RUNNING

    def _start_sampling_thread(self) -> None:
        self._thread = threading.Thread(target=self._run, name="sampling-task",
                                        daemon=True)
        self._thread.start()

    def shutdown(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    # -- mode / state ------------------------------------------------------
    def set_mode(self, mode: SamplingMode, reason: str = "") -> None:
        with self._lock:
            self._mode = mode
            self._mode_reason = reason
            if self._state in (RunnerState.RUNNING, RunnerState.PAUSED):
                self._state = (RunnerState.PAUSED if mode is SamplingMode.PAUSED
                               else RunnerState.RUNNING)

    @property
    def sampling_mode(self) -> SamplingMode:
        return self._mode

    @property
    def state_name(self) -> str:
        return self._state.value

    @property
    def samples_loaded(self) -> int:
        return self._samples_loaded

    # -- the loop ----------------------------------------------------------
    def _run(self) -> None:
        while not self._stop.wait(self._interval_ms / 1000.0):
            if self._mode is SamplingMode.PAUSED:
                continue
            self.run_sampling_once()

    def run_sampling_once(self, end_ms: int | None = None) -> None:
        """One sampling interval (SamplingTask.run); callable directly for
        deterministic tests and simulations."""
        end = end_ms if end_ms is not None else now_ms()
        start = self._last_sample_ms or (end - self._interval_ms)
        with self._lock:
            if self._state is RunnerState.RUNNING:
                self._state = RunnerState.SAMPLING
        from .sampling.fetcher import PartialWindowError
        try:
            # The describe runs inside the fetch's span (as its first
            # child), so one span times the whole sampling round.
            self._fetcher.fetch_metric_samples(
                self._metadata.describe_partitions, start, end)
            self._last_sample_ms = end
        except PartialWindowError as e:
            # The window is below the completeness floor and LOST either
            # way — advance the clock so the next interval fetches only
            # ITS span. Leaving start pinned would re-fetch the whole
            # outage range every interval (O(outage²) sampler work).
            LOG.warning("sampling interval [%s, %s) rejected: %s",
                        start, end, e)
            self._last_sample_ms = end
        except Exception:
            LOG.exception("sampling interval [%s, %s) failed", start, end)
        finally:
            with self._lock:
                if self._state is RunnerState.SAMPLING:
                    self._state = RunnerState.RUNNING

    def bootstrap(self, start_ms: int, end_ms: int, clear_metrics: bool = True,
                  ) -> None:
        """BootstrapTask.run: replay a historic range through the samplers
        window by window to warm the aggregators."""
        with self._lock:
            prev = self._state
            self._state = RunnerState.BOOTSTRAPPING
        try:
            if clear_metrics:
                self._fetcher.clear()
            # one metadata read serves every window of the replay
            partitions = self._metadata.describe_partitions()
            t = start_ms
            while t < end_ms and not self._stop.is_set():
                nxt = min(t + self._interval_ms, end_ms)
                try:
                    self._fetcher.fetch_metric_samples(
                        lambda: partitions, t, nxt, store=False)
                except Exception:  # noqa: BLE001 — one bad window (e.g.
                    # below the partial-completeness floor, or a range
                    # predating available metrics) must not abort the
                    # whole historic replay; later windows still warm.
                    LOG.warning("bootstrap window [%s, %s) failed; "
                                "continuing", t, nxt, exc_info=True)
                t = nxt
            self._last_sample_ms = end_ms
        finally:
            with self._lock:
                self._state = prev
