#!/usr/bin/env python3
"""Readings for the limits of ``correct``: one cell on many seeds in one
process (set-up compiles once), as the program stands or as its control.

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 --seconds 8 [--control <name>] [--patch <file>] [--faults]

A control is the program itself with a path of its own switched on: a
patch of the configuration's ``controls`` that takes goals out of the
chain, which breaks the guarantee that they hold after the moves. It has
to come out as not correct. ``--patch`` lays the keys of a JSON file over
the cell's configuration: a deployment that is no cell yet (another
``placement``, ``operation``, ``operation_brokers``) read on the chip
before the PR that adds it. ``--faults`` also applies every planted fault
of ``benchlib/faults.py`` and of the operation's own rule
(``guarantees/<operation>.py``) to the window's answers and prints what
each reads. The benchmark's own runs never run any of these.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", help="name of one of the "
                    "configuration's controls")
    ap.add_argument("--patch", help="JSON file of configuration keys "
                    "laid over the cell's (after the control's)")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--read-rates", help="comma-separated reads/s: sweep "
                    "the open loop's rate on the first seed (to find what "
                    "the system sustains; a cell's rate is fixed in its mix)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--benchmark",
                    default=os.path.join(run.ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    with open(args.benchmark) as f:
        benchmark = json.load(f)
    cell, config = run.find_cell(benchmark, args.workload)
    device = run.device_report(int(cell["chips"]), args.rehearse)
    patch = None
    if args.control:
        with open(os.path.join(run.ROOT, config["file"])) as f:
            patch = json.load(f)["controls"][args.control]["patch"]
    if args.patch:
        with open(args.patch) as f:
            patch = {**(patch or {}), **json.load(f)}
    verdicts = []
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [None] * len(seeds)
    if args.read_rates:
        rates = [float(r) for r in args.read_rates.split(",")]
        seeds = seeds[:1] * len(rates)
    for seed, rate in zip(seeds, rates):
        result = run.run_cell(benchmark, args.workload, seed, args.seconds,
                              bool(args.trace), device, time.monotonic(),
                              cfg_patch=patch, faults=args.faults,
                              mix_patch=rate and {"rate_per_s": rate})
        verdicts.append(result["correct"])
        print("reading: " + json.dumps({
            "workload": args.workload, "control": args.control, "seed": seed,
            "read_rate": rate, "reads": result["workload"]["reads"],
            "correct": result["correct"], "failed": result["failed"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "compared": {k: v[0] for k, v in result["compared"].items()},
            "proposals": result["workload"]["proposals"],
            "request_s": result["workload"]["request_s"],
            "device": result["device"],
            "idle_gaps": result.get("breakdown", {}).get("idle_gaps"),
            "faulted": result.get("faulted"),
        }), flush=True)
    print(f"readings: {sum(verdicts)} of {len(verdicts)} correct "
          f"({'control ' + args.control if args.control else 'program as configured'})",
          flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)    # server threads of closed deployments may linger
