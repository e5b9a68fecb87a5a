"""From a profiler trace to numbers: device busy / idle, time per
operation and per program, and what the host was doing in the idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded
fixture without a chip: ``load_events`` turns an ``.xplane.pb`` into plain
lists (it needs jax's ``ProfileData``), ``reduce`` turns those lists into
the numbers (plain Python). Times are nanoseconds on the trace's clock; the
harness's own threads write ``bench.*`` host spans into the same trace
with ``jax.profiler.TraceAnnotation``, so gaps and spans share a clock.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


def load_events(trace_dir: str) -> dict:
    """{"devices": {plane: {"ops": [[name, start, dur]], "modules": [...]}},
    "host": [[name, start, dur]]} from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] += [[op_name(e.name), float(e.start_ns),
                              float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX)]
    return out


def op_name(text: str) -> str:
    """``%fusion.7 = f32[8]{0} fusion(...)`` -> ``fusion.7``: the trace
    names a device operation by its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")[:96]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(events: list) -> dict:
    """Seconds by name, each event's duration less what the events nested
    inside it cover (a ``while`` spans its body's operations on the same
    line)."""
    out: dict[str, float] = {}
    stack: list[list] = []     # [name, end, self_ns]

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + max(item[2], 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    while stack:
        close(stack.pop())
    return out


def _label_gaps(gs: float, ge: float, spans: list, busy: list):
    """Cuts one idle gap at the harness's host spans and yields
    (label, start, end) pieces: what the harness saw the host doing."""
    cursor = gs
    for name, s, d in spans:
        lo, hi = max(cursor, s), min(ge, s + d)
        if hi <= lo:
            continue
        if lo > cursor:
            yield "between requests", cursor, lo
        cursor = hi
        if name != "bench.request":
            yield name[len(HOST_SPAN_PREFIX):].replace("_", " "), lo, hi
            continue
        inside = [b for b in busy if b[1] > s and b[0] < s + d]
        if not inside:
            yield "request: no device op", lo, hi
        elif hi <= inside[0][0]:
            yield ("request: before first device op (refresh, dispatch)",
                   lo, hi)
        elif lo >= inside[-1][1]:
            yield ("request: after last device op (diff, render, HTTP)",
                   lo, hi)
        else:
            yield "request: between device ops", lo, hi
    if ge > cursor:
        yield "between requests", cursor, ge


def reduce(events: dict) -> dict | None:
    """The trace's numbers over the window that the harness's host spans
    cover, or None where no operation ran on a device. ``modules`` gives
    the device seconds of each XLA program (module) by name."""
    spans = sorted(events["host"], key=lambda e: e[1])
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices or not spans:
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(s + d for _, s, d in spans)
    busy_s, per_device = [], {}
    for plane, dev in sorted(devices.items()):
        busy = clip(union([(s, s + d) for _, s, d in dev["ops"]]), lo, hi)
        per_device[plane] = busy
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
    if not any(busy_s):
        return None
    first = per_device[sorted(per_device)[0]]
    gaps, cursor = [], lo
    for s, e in first:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    by_label: dict[str, float] = {}
    for gs, ge in gaps:
        for label, lo_, hi_ in _label_gaps(gs, ge, spans, first):
            by_label[label] = by_label.get(label, 0.0) + (hi_ - lo_) / 1e9
    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    for dev in devices.values():
        in_window = [e for e in dev["ops"] if e[1] + e[2] > lo and e[1] < hi]
        for name, sec in self_times(in_window).items():
            ops[name] = ops.get(name, 0.0) + sec / len(devices)
        for name, s, d in dev["modules"]:
            inside = (min(s + d, hi) - max(s, lo)) / 1e9
            if inside > 0:
                name = name.split("(")[0]
                modules[name] = modules.get(name, 0.0) + inside / len(devices)

    def top(seconds: dict) -> list:
        return [[k, v] for k, v in sorted(seconds.items(),
                                          key=lambda kv: -kv[1])[:10]]

    requests = [(s, s + d) for name, s, d in spans if name == "bench.request"]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "modules": modules,
        "requests": len(requests),
        "device_ops": top(ops),
        "idle_gaps": top(by_label),
    }
