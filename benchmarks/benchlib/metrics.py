"""What a metric's reader is given, and how the harness finds readers.

A metric is two files under ``benchmarks/metrics/``: ``<name>.json`` (its
unit, source, layer, the end-to-end metric it moves, and any parameters
of its own) and ``<name>.py`` with ``read(ctx) -> float | None``. A reader
that finds nothing to read returns None and the harness leaves the metric
out of the line; it never returns 0 for a share of a roofline or a peak.
"""

from __future__ import annotations

import dataclasses

from .deployment import load_json, load_module
from .sut import series_total


@dataclasses.dataclass
class Context:
    cfg: dict                   # the configuration's file
    mix: dict                   # the traffic mix's file
    seconds: float
    setup_s: float
    t0: float                   # the window's first instant (monotonic)
    at_setup: dict              # the program's counters when set-up ended
    at_close: dict              # ... and when the window had closed
    solves: list                # completed solves of the window, in order
    reads: list                 # every read that was due in the window
    device: dict
    trace: dict | None = None   # trace.reduce() of a traced run
    traced_solves: list = dataclasses.field(default_factory=list)
    param: dict = dataclasses.field(default_factory=dict)

    def delta(self, name: str, **labels) -> float:
        """How far a counter moved inside the window."""
        return (series_total(self.at_close, name, **labels)
                - series_total(self.at_setup, name, **labels))


def rounds(solve) -> int:
    """Search rounds of one served proposal, from its body's counts."""
    return sum(int(g["rounds"])
               for g in solve.body["summary"]["goals"].values())


def program_seconds(ctx: Context) -> float | None:
    """Device seconds, in the traced window, of the XLA programs whose
    names hold one of the metric's ``programs`` parts. None, and a line
    that says so, where they are under the metric's
    ``least_share_of_busy`` of the device's busy seconds: the trace has
    then lost module events, and a time per round taken from it would read
    fast (the metric's file has the readings)."""
    seconds = sum(sec for name, sec in ctx.trace["modules"].items()
                  if any(part in name for part in ctx.param["programs"]))
    busy_s = ctx.trace["busy_s"]
    if seconds < ctx.param["least_share_of_busy"] * busy_s:
        print(f"trace: {ctx.param['name']} not reported: its programs "
              f"took {seconds:.6f} s of the device's {busy_s:.6f} busy "
              f"seconds, under {ctx.param['least_share_of_busy']:g} of them",
              flush=True)
        return None
    return seconds


def read_metric(name: str, ctx: Context) -> float | None:
    value = load_module("metrics", name).read(dataclasses.replace(
        ctx, param=load_json("metrics", name)))
    return None if value is None else float(value)
