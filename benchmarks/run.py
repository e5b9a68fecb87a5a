#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and metrics are files found by the names given
there (see README.md). One process holds the chip: the served program, the
clients (threads that never touch JAX) and the trace reduction all run
here. Chip or fail: without a TPU (or with fewer chips than the cell asks
for) the command exits non-zero before it prints any number, unless
``--rehearse`` is given, which stamps every line ``cpu`` and prints no
device metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import contextlib   # noqa: E402
import gc           # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402
import tempfile     # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchlib import reference, trace                   # noqa: E402
from benchlib.deployment import build, load_json        # noqa: E402
from benchlib.metrics import Context, read_metric       # noqa: E402
from benchlib.sut import series_total                   # noqa: E402
from benchlib.traffic import Window, http_call, read_stats  # noqa: E402

# A traced run traces whole requests, from the second of the window on,
# until this many seconds have passed: a trace of the whole window is too
# large to bring back.
TRACE_SECONDS = 5.0
_COMPILE_COUNTERS = ("xla_compile_events_total",
                     "xla_compile_cache_misses_total",
                     "xla_compile_cache_hits_total")


class BenchFailure(Exception):
    """The run cannot give a result."""


def say(msg: str) -> None:
    print(msg, flush=True)


def find_cell(benchmark: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json: "
                           f"{sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in benchmark["configs"]}[cell["config"]]
    return cell, config


def metrics_of(benchmark: dict, kind: str, workload: str) -> list[dict]:
    return [m for m in benchmark[kind]
            if "workloads" not in m or workload in m["workloads"]]


def device_report(chips: int, rehearse: bool) -> dict:
    import jax
    devices = jax.devices()
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        if report["platform"] != "cpu":
            raise BenchFailure("--rehearse is for JAX_PLATFORMS=cpu")
        return report
    if report["platform"] != "tpu" or report["count"] < chips:
        raise BenchFailure(
            f"found {report['count']} x {report['kind']} "
            f"({report['platform']}), the cell needs {chips} TPU chip(s): "
            "not measuring (a CPU rehearsal needs --rehearse)")
    return report


def compile_events(series: dict) -> float:
    return sum(series_total(series, c) for c in _COMPILE_COUNTERS)


class Tracer:
    """Starts the profiler between two requests of the solving client and
    stops it between two later ones, so that the trace holds whole
    requests."""

    def __init__(self, window: Window):
        self._window = window
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.first = self.last = None
        self._t_started = None

    def between_requests(self) -> None:
        import jax
        done = len(self._window.solves)
        if self._t_started is None and done >= 1:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._t_started, self.first = time.monotonic(), done
        elif self.last is None and self._t_started is not None \
                and done > self.first \
                and time.monotonic() - self._t_started >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        import jax
        if self._t_started is not None and self.last is None:
            self.last = len(self._window.solves)
            jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        try:
            if self.last is None or self.last <= self.first:
                return None
            return trace.reduce(trace.load_events(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def full_collections():
    """Yields a list that gets the seconds of every full collection of
    Python's collector while the block runs: a stall in one run's window
    is told by it from the machine's noise."""
    pauses, started = [], [0.0]

    def on_collection(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                started[0] = time.monotonic()
            else:
                pauses.append(round(time.monotonic() - started[0], 4))

    gc.callbacks.append(on_collection)
    try:
        yield pauses
    finally:
        gc.callbacks.remove(on_collection)


def warm_up(served, window: Window, mix: dict) -> None:
    """Every shape the cell's traffic uses, and no other: the mix's reads,
    then solves until one compiles nothing."""
    for entry in (mix.get("reads") or {}).get("mix", ()):
        for _ in range(2):
            t = time.monotonic()
            status, _body = http_call(served.port, "GET", entry["endpoint"],
                                      {}, 300)
            say(f"set-up: warm GET /{entry['endpoint']} -> {status}, "
                f"{time.monotonic() - t:.3f} s")
            if status != 200:
                raise BenchFailure(f"warm read /{entry['endpoint']} "
                                   f"answered {status}")
    if not mix.get("solvers"):
        return
    for attempt in range(4):
        solve = window.solve_once(1100)
        compiled = compile_events(solve.after) - compile_events(solve.before)
        seconds = series_total(solve.after, "xla_compile_seconds_sum") \
            - series_total(solve.before, "xla_compile_seconds_sum")
        say(f"set-up: warm solve {attempt} -> {solve.status}, "
            f"{solve.ended - solve.started:.3f} s (sampling round "
            f"{solve.round_s:.3f} s), compile events +{compiled:g} "
            f"({seconds:.1f} s in backend compiles)")
        if solve.status != 200 or not solve.body:
            raise BenchFailure(f"warm solve answered {solve.status}")
        if not compiled:
            return
    raise BenchFailure("the solve still compiles after 4 warm requests")


def compare(dep, cfg: dict, mix: dict, window: Window, good: list,
            compiles: float, passes: float, faults: bool,
            ) -> tuple[dict, dict]:
    """The comparison, once the window has closed and the program is gone:
    every body the window completed and a seed-drawn sample of its reads
    against the plain reference. Returns {number: [reading, limit]} and,
    with ``faults``, what every planted fault reads on the same answers."""
    t_ref = time.monotonic()
    bodies = [s.body["proposals"] for s in good]
    evaluations = [reference.evaluate(dep, cfg["guarantees"], b)
                   for b in bodies]
    compared = {k: [v, 0] for k, v in reference.worst(
        evaluations, reference.numbers_of(dep.operation)).items()}
    checked = [r for r in window.reads if r.keep and r.status == 200]
    if window.reads:
        wrong = [r for r in checked
                 if reference.read_mismatch(dep, r.endpoint, r.body)]
        for r in wrong:
            say(f"comparison: read /{r.endpoint} due at {r.due:.3f} s "
                f"disagrees with the deployment: {str(r.body)[:400]}")
        compared["read_mismatch"] = [len(wrong), 0]
        compared["no_read_checked"] = [0 if checked else 1, 0]
    compared["compiles_in_window"] = [compiles, 0]
    # A request that was answered without a solve of its own (a replayed
    # or coalesced proposal) is not an answer to it.
    compared["unsolved"] = [max(0.0, len(window.solves) - passes), 0]
    compared["no_proposal"] = [0 if good or not mix.get("solvers") else 1, 0]
    faulted = {}
    if faults:
        from benchlib.faults import READ_FAULTS, planted
        for fault, number in planted(dep.operation).items():
            faulted[fault.__name__] = {number: reference.worst([
                reference.evaluate(dep, cfg["guarantees"], fault(b, dep))
                for b in bodies])[number]}
        for fault, number in READ_FAULTS.items() if checked else ():
            faulted[fault.__name__] = {number: sum(
                reference.read_mismatch(dep, r.endpoint, fault(r.body, dep))
                for r in checked)}
    info = evaluations[-1]["info"] if evaluations else {}
    by_goal = {name: int(g["rounds"]) for name, g in
               good[-1].body["summary"]["goals"].items()
               if int(g["rounds"])} if good else {}
    say(f"comparison: {len(evaluations)} bodies and {len(checked)} reads "
        f"against the reference in {time.monotonic() - t_ref:.3f} s; "
        f"last body: {info}, rounds by goal {by_goal}")
    return compared, faulted


def run_cell(benchmark: dict, workload: str, seed: int, seconds: float,
             traced: bool, device: dict, t_start: float,
             cfg_patch: dict | None = None, faults: bool = False,
             mix_patch: dict | None = None) -> dict:
    """One run of one cell; returns the result line's object.
    ``cfg_patch`` (a control, or a deployment that is no cell yet) and
    ``faults`` (every planted fault of ``benchlib/faults.py`` and of the
    operation's own rule applied in turn to the window's answers, under
    the key ``faulted``) and ``mix_patch`` (the sweep for the rate the
    reads sustain) are for ``readings.py`` and the tests: the command never
    sets them."""
    import jax
    from benchlib.sut import Served

    cell, config = find_cell(benchmark, workload)
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    cfg.update(cfg_patch or {})
    mix = load_json("traffic", cell["traffic"])
    if mix_patch:
        mix["reads"] = {**mix["reads"], **mix_patch}
    rehearsal = device["platform"] != "tpu"
    dep = build(cfg)
    t_built = time.monotonic()
    served = Served(cfg, dep)
    try:
        say(f"set-up: {workload} seed {seed}: {dep.brokers} brokers, "
            f"{dep.partitions} partitions, rf {dep.rf}, {dep.racks} racks, "
            f"{dep.topics} topics, {len(cfg['goals'])} goals, operation "
            f"{dep.operation}; platform={device['platform']} "
            f"kind={device['kind']} devices={device['count']} "
            f"compile_cache={jax.config.jax_compilation_cache_dir}")
        t_served = time.monotonic()
        rounds = served.fill_windows()
        t_filled = time.monotonic()
        window = Window(mix, served.port, served.request(dep), seed, seconds,
                        served.sampling_round, served.counters,
                        annotate=jax.profiler.TraceAnnotation if traced
                        else contextlib.nullcontext)
        warm_up(served, window, mix)
        at_setup = served.counters()
        say(f"set-up: phases: start-up {t_built - t_start:.3f} s (imports, "
            f"reaching the chip, deployment), wiring {t_served - t_built:.3f} s, {rounds} "
            f"sampling rounds {t_filled - t_served:.3f} s, warm requests "
            f"{time.monotonic() - t_filled:.3f} s; backend compiles "
            f"{series_total(at_setup, 'xla_compile_events_total'):g} "
            f"({series_total(at_setup, 'xla_compile_seconds_sum'):.1f} s), "
            f"persistent-cache hits "
            f"{series_total(at_setup, 'xla_compile_cache_hits_total'):g}, "
            f"misses "
            f"{series_total(at_setup, 'xla_compile_cache_misses_total'):g}")
        tracer = None
        if traced and mix.get("solvers"):
            tracer = Tracer(window)
            window.between_requests = tracer.between_requests
        setup_s = time.monotonic() - t_start
        with full_collections() as collections:
            window.run()
        if tracer is not None:
            tracer.stop()
        at_close = served.counters()
        peaks = [d.memory_stats() for d in jax.local_devices()]
        memory_peak = max((s["peak_bytes_in_use"] for s in peaks if s),
                          default=0)
    finally:
        served.close()
    del served
    gc.collect()

    reduced = tracer.reduce() if tracer is not None else None
    if reduced is not None:
        say(f"trace: {reduced['requests']} requests in a window of "
            f"{reduced['window_s']:.6f} s, device busy "
            f"{reduced['busy_s']:.6f} s; seconds by program: "
            f"{json.dumps(reduced['modules'])}")
    good = [s for s in window.solves if s.status == 200 and s.body
            and not s.body.get("stale") and "summary" in s.body]
    failed = len(window.solves) - len(good)
    failed += sum(1 for r in window.reads if r.status != 200)
    passes = series_total(at_close, "pass_seq") \
        - series_total(at_setup, "pass_seq")
    ctx = Context(
        cfg=cfg, mix=mix, seconds=seconds, setup_s=setup_s, t0=window.t0,
        at_setup=at_setup, at_close=at_close, solves=good,
        reads=window.reads, device=device, trace=reduced,
        traced_solves=[s for s in window.solves[tracer.first:tracer.last]
                       if s in good] if reduced else [])

    compared, faulted = compare(dep, cfg, mix, window, good,
                                compile_events(at_close)
                                - compile_events(at_setup), passes, faults)
    correct = all(v <= limit for v, limit in compared.values())

    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in metrics_of(benchmark, kind, workload):
        if rehearsal and m["source"] == "device_trace":
            continue
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": len(window.solves) + len(window.reads),
        "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": memory_peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["workload"] = {
        "name": workload, "seed": seed, "seconds": seconds,
        "proposals": len(good),
        "request_s": [round(s.ended - s.started, 4) for s in window.solves],
        "sampling_round_s": [round(s.round_s, 4) for s in window.solves],
        "full_collections_s": collections,
        "reads": read_stats(window.reads)}
    if faults:
        result["faulted"] = faulted
    result["compared"] = compared
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on JAX_PLATFORMS=cpu: every line is stamped "
                    "cpu and no device metric is printed")
    ap.add_argument("--benchmark",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    try:
        with open(args.benchmark) as f:
            benchmark = json.load(f)
        cell, _config = find_cell(benchmark, args.workload)
        device = device_report(int(cell["chips"]), args.rehearse)
        result = run_cell(benchmark, args.workload, args.seed, args.seconds,
                          bool(args.trace), device, T_START)
    except BaseException as e:  # noqa: BLE001 — any failure: no result line
        import traceback
        traceback.print_exc()
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        sys.stdout.flush()
        # A failed run may leave a solve in flight on a server thread;
        # interpreter teardown under a running XLA execution aborts.
        os._exit(3)
    line = json.dumps(result)
    sys.stdout.flush()
    for name, (value, limit) in result["compared"].items():
        print(f"compared: {name} = {value:g} (limit {limit:g})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
