"""One general traffic generator, driven by a mix's data file.

A mix (``benchmarks/traffic/<name>.json``) has two optional parts:

- ``solvers``: a closed loop of ``clients`` threads. Each runs one sampling
  round, then sends the deployment's operation and reads the completed
  body, back to back until the window's time has passed; the request in
  flight finishes and counts.
- ``reads``: an open loop at ``rate_per_s``. The schedule is made from the
  seed before the window: every seed gets the SAME set of gaps (the
  quantiles of the arrival distribution) and the same multiset of
  endpoints, in another order. Each read is timed from when it was due.

Clients are threads of this process that never touch JAX: they speak HTTP
over loopback (the program's ``client.Responder`` protocol: a 202 or a
``progress`` body is resumed by ``User-Task-ID`` until the operation
completes).
"""

from __future__ import annotations

import contextlib
import dataclasses
import http.client
import json
import math
import queue
import threading
import time
import urllib.parse

import numpy as np

URL_PREFIX = "/kafkacruisecontrol"
USER_TASK_HEADER = "User-Task-ID"


@dataclasses.dataclass
class Solve:
    started: float
    ended: float
    status: int
    body: dict | None
    before: dict
    after: dict
    round_s: float          # the sampling round before the request


@dataclasses.dataclass
class Read:
    endpoint: str
    due: float
    started: float = math.nan
    ended: float = math.nan
    status: int = 0
    keep: bool = False      # one of the sample the reference checks
    body: dict | None = None


def http_call(port: int, method: str, endpoint: str, params: dict,
              timeout_s: float, parse: bool = True,
              ) -> tuple[int, dict | None]:
    """One operation to its completed body. Returns (status, body); a
    status of 0 is a transport failure or a timeout. ``parse=False``
    reads a completed body to its last byte and drops it."""
    query = urllib.parse.urlencode(params)
    path = f"{URL_PREFIX}/{endpoint}" + (f"?{query}" if query else "")
    deadline = time.monotonic() + timeout_s
    headers: dict[str, str] = {}
    while True:
        left = deadline - time.monotonic()
        if left <= 0:
            return 0, None
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=left)
        try:
            conn.request(method, path, headers=headers)
            resp = conn.getresponse()
            raw = resp.read()
            status = resp.status
            task_id = resp.getheader(USER_TASK_HEADER)
        except (OSError, http.client.HTTPException):
            return 0, None
        finally:
            conn.close()
        if status == 200 and not parse and b'"progress"' not in raw[:256]:
            return status, None
        try:
            body = json.loads(raw) if raw else None
        except ValueError:
            return status, None
        if status == 202 or (isinstance(body, dict) and "progress" in body):
            if task_id:
                headers[USER_TASK_HEADER] = task_id
            continue
        return status, body


def read_schedule(reads: dict, seed: int, seconds: float) -> list[Read]:
    """The open loop's arrivals over ``seconds``: N = rate * seconds reads,
    the first due at 0, whose N - 1 gaps are the quantiles of the arrival
    distribution, shuffled by the seed, and whose endpoints are the mix's
    shares of N, shuffled by the seed. ``arrivals``: ``poisson``
    (exponential gaps), ``uniform`` (equal gaps) or ``burst``
    (``burst_size`` reads at once, then the gap that keeps the rate)."""
    n = max(2, round(float(reads["rate_per_s"]) * seconds))
    rng = np.random.default_rng([seed, 0x5EAD])
    arrivals = reads.get("arrivals", "poisson")
    mean_gap = seconds / n
    if arrivals == "poisson":
        gaps = -np.log(1.0 - (np.arange(n - 1) + 0.5) / (n - 1))
        gaps *= mean_gap * (n - 1) / gaps.sum()
        rng.shuffle(gaps)
    elif arrivals == "uniform":
        gaps = np.full(n - 1, mean_gap)
    elif arrivals == "burst":
        size = int(reads["burst_size"])
        gaps = np.where(np.arange(1, n) % size == 0, mean_gap * size, 0.0)
    else:
        raise ValueError(f"arrivals {arrivals!r}")
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    mix = reads["mix"]
    total = sum(float(m["weight"]) for m in mix)
    counts = [int(n * float(m["weight"]) / total) for m in mix]
    counts[0] += n - sum(counts)
    endpoints = np.repeat(np.arange(len(mix)), counts)
    rng.shuffle(endpoints)
    out = [Read(mix[e]["endpoint"], float(t)) for e, t in zip(endpoints, due)]
    sample = int(reads.get("checked_per_endpoint", 8))
    for e in range(len(mix)):
        rows = np.flatnonzero(endpoints == e)
        for i in rng.choice(rows, size=min(sample, len(rows)), replace=False):
            out[i].keep = True
    return out


def read_stats(reads: list[Read]) -> dict:
    """How the open loop went, for the run's record: how late the
    generator ran, and the latencies (from due time, in ms) of the reads
    that were answered."""
    if not reads:
        return {"due": 0}
    late = sorted(r.started - r.due for r in reads
                  if not math.isnan(r.started))
    ms = sorted(1000 * (r.ended - r.due) for r in reads if r.status == 200)

    def pick(q):
        return ms[max(0, math.ceil(len(ms) * q / 100) - 1)] if ms else None

    return {"due": len(reads), "answered": len(ms),
            "generator_late_ms_mean": 1000 * sum(late) / max(1, len(late)),
            "generator_late_ms_max": 1000 * late[-1] if late else 0.0,
            "ms_mean": sum(ms) / len(ms) if ms else None,
            "ms_p50": pick(50), "ms_p90": pick(90), "ms_p95": pick(95),
            "ms_max": pick(100)}


class Window:
    """Runs one mix against a served deployment for ``seconds``."""

    def __init__(self, mix: dict, port: int, request: tuple, seed: int,
                 seconds: float, sampling_round, counters,
                 annotate=contextlib.nullcontext):
        self._mix, self._port, self._request = mix, port, request
        self._seconds = seconds
        self._sampling_round, self._counters = sampling_round, counters
        self._annotate = annotate
        self._round_lock = threading.Lock()
        self.solves: list[Solve] = []
        self.reads = read_schedule(mix["reads"], seed, seconds) \
            if mix.get("reads") else []
        self.errors: list[BaseException] = []
        self.t0 = math.nan
        # Set by a traced run: called by the solving client between two
        # requests (so a trace holds whole requests only).
        self.between_requests = None

    def solve_once(self, timeout_s: float) -> Solve:
        method, endpoint, params = self._request
        with self._round_lock:  # the monitor takes one round at a time
            t_round = time.monotonic()
            with self._annotate("bench.sampling_round"):
                self._sampling_round()
            before = self._counters()
        started = time.monotonic()
        with self._annotate("bench.request"):
            status, body = http_call(self._port, method, endpoint, params,
                                     timeout_s)
        ended = time.monotonic()
        return Solve(started, ended, status, body, before, self._counters(),
                     started - t_round)

    def _solver(self) -> None:
        try:
            timeout_s = float(self._mix["solvers"].get("timeout_s", 120))
            while time.monotonic() - self.t0 < self._seconds:
                if self.between_requests is not None:
                    self.between_requests()
                solve = self.solve_once(timeout_s)
                self.solves.append(solve)
        except BaseException as e:  # noqa: BLE001 — reported by run()
            self.errors.append(e)

    def _reader(self, jobs: queue.Queue, timeout_s: float) -> None:
        try:
            while (read := jobs.get()) is not None:
                read.started = time.monotonic() - self.t0
                read.status, read.body = http_call(
                    self._port, "GET", read.endpoint, {}, timeout_s,
                    parse=read.keep)
                read.ended = time.monotonic() - self.t0
        except BaseException as e:  # noqa: BLE001 — reported by run()
            self.errors.append(e)

    def _dispatcher(self, jobs: queue.Queue, workers: int) -> None:
        for read in self.reads:
            delay = self.t0 + read.due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            jobs.put(read)
        for _ in range(workers):
            jobs.put(None)

    def run(self) -> None:
        """Blocks until the window has closed and every request in flight
        has ended (or timed out)."""
        threads = []
        solvers = self._mix.get("solvers") or {}
        for i in range(int(solvers.get("clients", 0))):
            threads.append(threading.Thread(target=self._solver,
                                            name=f"bench-solver-{i}"))
        if self.reads:
            reads = self._mix["reads"]
            workers = int(reads.get("workers", 32))
            jobs: queue.Queue = queue.Queue()
            threads.append(threading.Thread(
                target=self._dispatcher, args=(jobs, workers),
                name="bench-read-dispatch"))
            threads += [threading.Thread(
                target=self._reader,
                args=(jobs, float(reads.get("timeout_s", 30))),
                name=f"bench-reader-{i}") for i in range(workers)]
        self.t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if self.errors:
            raise self.errors[0]
