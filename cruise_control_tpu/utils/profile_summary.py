"""From a profiler capture to a summary an operator can read in the
response of ``GET /profile``: device busy and idle time, device seconds by
XLA program, by named scope of the round body and of the largest single
operations, and what the program was doing in the device's idle gaps.

Two steps, so that the arithmetic is tested on a small recorded fixture
without a chip: ``load_events`` turns an ``.xplane.pb`` into plain lists,
``reduce`` turns those lists into the numbers (plain Python). The loader
reads the file's protobuf wire format itself: the name scope of a device
operation (the ``jax.named_scope`` path the lowering wrote) is a stat of
the operation's METADATA (``tf_op``), which ``jax.profiler.ProfileData``
does not expose, and the wire format is five field types.

What the capture holds of the program: every live span of
``utils.tracing`` and every journey segment is a ``cc.<name>`` event on
its thread's line of the host plane (``tracing.annotation``), on the same
clock as the device plane's operations. Times are nanoseconds.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from .tracing import ANNOTATION_PREFIX

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SCOPE_STAT = "tf_op"
# The scopes the solver's bodies carry (analyzer/chain.py, candidates.py,
# search.py, agg.py): phases of a round, the swap round, per-goal work.
SCOPE = re.compile(r"(?:^|/)((?:round|swap|goal)\.[a-z_]+)(?=/|:|$)")
UNSCOPED = "(unscoped)"
NO_SPAN = "(no span)"
NO_PROGRAM = "(no program)"
LARGEST_OPERATIONS = 10


# -- step 1: the file -> plain lists ------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields and fixed-width ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, memoryview | None]:
    key, value = 0, None
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf) -> dict | None:
    """One XPlane: its name, the (name, scope text) of every event
    metadata, and its lines still unparsed."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            if value is not None:
                stat_names[key] = next(
                    (_text(x) for n, x in _fields(value) if n == 2), "")
    if not (name.startswith(DEVICE_PLANE_PREFIX)
            or name.startswith(HOST_PLANE_PREFIX)):
        return None
    scope_ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
    names: dict[int, tuple[str, str]] = {}
    for key, value in event_meta.items():
        event_name = scope = ""
        for number, v in _fields(value if value is not None else b""):
            if number == 2:
                event_name = _text(v)
            elif number == 5 and scope_ids:
                stat = dict(_fields(v))
                if stat.get(1) in scope_ids and 5 in stat:
                    scope = _text(stat[5])
        names[key] = (event_name, scope)
    return {"name": name, "lines": lines, "names": names}


def _line(buf) -> tuple[str, float, list]:
    """(name, timestamp_ns, [event messages]) of one XLine."""
    name, t0_ns, raw = "", 0, []
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            t0_ns = v
        elif number == 4:
            raw.append(v)
    return name, t0_ns, raw


def _events(raw: list, t0_ns: float, names: dict, keep=None) -> list:
    """[[name, scope text, start_ns, duration_ns]] of a line's events,
    only those whose name ``keep`` accepts where it is given."""
    events = []
    for ev in raw:
        meta = offset_ps = duration_ps = 0
        for number, v in _fields(ev):
            if number == 1:
                meta = v
            elif number == 2:
                offset_ps = v
            elif number == 3:
                duration_ps = v
        name, scope = names.get(meta, ("", ""))
        if keep is None or keep(name):
            events.append([name, scope, t0_ns + offset_ps / 1000.0,
                           duration_ps / 1000.0])
    return events


def load_events(trace_dir: str) -> dict:
    """{"devices": {plane: {"ops": [[name, scope, start, dur]],
    "modules": [[name, start, dur]]}}, "host": [[name, start, dur]]} from
    the newest capture under ``trace_dir``: device operations named as
    ``op_name`` names them and labelled with their innermost scope, XLA
    programs, and the program's own ``cc.*`` host spans."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        space = memoryview(f.read())
    out: dict = {"devices": {}, "host": []}
    for number, v in _fields(space):
        if number != 1:
            continue
        plane = _plane(v)
        if plane is None:
            continue
        names = plane["names"]
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            dev = out["devices"].setdefault(plane["name"],
                                            {"ops": [], "modules": []})
            for line in plane["lines"]:
                line_name, t0_ns, raw = _line(line)
                if line_name == OPS_LINE:
                    dev["ops"] += [[op_name(n), scope_of(s), t, d]
                                   for n, s, t, d
                                   in _events(raw, t0_ns, names)]
                elif line_name == MODULES_LINE:
                    dev["modules"] += [[n.split("(")[0], t, d]
                                       for n, _s, t, d
                                       in _events(raw, t0_ns, names)]
        else:
            for line in plane["lines"]:
                _name, t0_ns, raw = _line(line)
                out["host"] += [
                    [n, t, d] for n, _s, t, d in _events(
                        raw, t0_ns, names,
                        lambda name: name.startswith(ANNOTATION_PREFIX))]
    return out


def op_name(text: str) -> str:
    """``%fusion.7 = f32[8]{0} fusion(...)`` -> ``fusion.7``: the trace
    names a device operation by its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")[:96]


def scope_of(text: str) -> str:
    """The innermost ``round.*`` / ``swap.*`` / ``goal.*`` scope of an
    operation's name-scope path, ``(unscoped)`` where it has none."""
    found = SCOPE.findall(text)
    return found[-1] if found else UNSCOPED


# -- step 2: plain lists -> numbers -------------------------------------------

def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_ns(events: list) -> list[float]:
    """Each event's duration less what the events nested inside it cover
    (a ``while`` spans its body's operations on the same line), in the
    order of ``events`` sorted by (start, -duration)."""
    out = [0.0] * len(events)
    stack: list[list] = []     # [index, end, self_ns]
    for i, ev in enumerate(events):
        start, dur = ev[-2], ev[-1]
        while stack and stack[-1][1] <= start:
            idx, _end, own = stack.pop()
            out[idx] = max(own, 0.0)
        if stack:
            stack[-1][2] -= dur
        stack.append([i, start + dur, dur])
    for idx, _end, own in stack:
        out[idx] = max(own, 0.0)
    return out


def _span_timeline(spans: list) -> tuple[list[float], list[str]]:
    """The host spans of every thread flattened into one timeline:
    ``labels[i]`` is the innermost span (the one that started last) that
    covers ``[bounds[i], bounds[i + 1])``, ``(no span)`` where none does."""
    bounds = sorted({t for _n, s, d in spans for t in (s, s + d)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    labels, active, nxt = [], [], 0
    for lo in bounds[:-1]:
        while nxt < len(by_start) and by_start[nxt][1] <= lo:
            active.append(by_start[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] + sp[2] > lo]
        # the innermost: the latest start, the shorter on a tie
        inner = max(active, key=lambda sp: (sp[1], -sp[2]), default=None)
        labels.append(inner[0][len(ANNOTATION_PREFIX):] if inner
                      else NO_SPAN)
    return bounds, labels


def _idle_by_span(gaps: list[tuple[float, float]], spans: list) -> dict:
    bounds, labels = _span_timeline(spans)
    out: dict[str, float] = {}

    def add(label: str, ns: float) -> None:
        if ns > 0:
            out[label] = out.get(label, 0.0) + ns / 1e9

    for gs, ge in gaps:
        if not labels or ge <= bounds[0] or gs >= bounds[-1]:
            add(NO_SPAN, ge - gs)
            continue
        add(NO_SPAN, bounds[0] - gs)
        add(NO_SPAN, ge - bounds[-1])
        i = max(bisect.bisect_right(bounds, gs) - 1, 0)
        while i < len(labels) and bounds[i] < ge:
            add(labels[i], min(ge, bounds[i + 1]) - max(gs, bounds[i]))
            i += 1
    return out


def _rounded(seconds: dict) -> dict:
    return {k: round(v, 9) for k, v in
            sorted(seconds.items(), key=lambda kv: -kv[1])}


def reduce(events: dict) -> dict | None:
    """The capture's numbers, or None where no operation ran on a device.
    The window runs from the first to the last event the capture holds
    (device operations and ``cc.*`` spans); seconds by program, by scope
    and by operation (its own, as by scope; the ten largest) are means
    over the devices; idle gaps are the first device's."""
    devices = {k: v for k, v in sorted(events["devices"].items())
               if v["ops"]}
    if not devices:
        return None
    spans = [sp for sp in events["host"] if sp[2] > 0]
    starts = [e[-2] for d in devices.values() for e in d["ops"]]
    ends = [e[-2] + e[-1] for d in devices.values() for e in d["ops"]]
    lo = min(starts + [s for _n, s, _d in spans])
    hi = max(ends + [s + d for _n, s, d in spans])
    busy_s, first_busy = [], None
    scopes: dict[str, float] = {}
    programs: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    operations: dict[tuple[str, str], list] = {}   # -> [s, events, scope]
    share = 1.0 / len(devices)
    for dev in devices.values():
        busy = _union([(e[-2], e[-2] + e[-1]) for e in dev["ops"]])
        if first_busy is None:
            first_busy = busy
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        modules = sorted(dev["modules"], key=lambda m: m[1])
        module_starts = [m[1] for m in modules]
        for name, _s, d in modules:
            programs[name] = programs.get(name, 0.0) + share * d / 1e9
        ops = sorted(dev["ops"], key=lambda e: (e[-2], -e[-1]))
        for (name, scope, start, _dur), own in zip(ops, _self_ns(ops)):
            scopes[scope] = scopes.get(scope, 0.0) + share * own / 1e9
            at = bisect.bisect_right(module_starts, start) - 1
            inside = at >= 0 and start < modules[at][1] + modules[at][2]
            program = modules[at][0] if inside else NO_PROGRAM
            if scope == UNSCOPED:
                unscoped[program] = unscoped.get(program, 0.0) \
                    + share * own / 1e9
            # an instruction's name is its program's own: fusion.7 of one
            # program is not fusion.7 of another
            entry = operations.setdefault((program, name), [0.0, 0.0, scope])
            entry[0] += share * own / 1e9
            entry[1] += share
    gaps, cursor = [], lo
    for s, e in first_busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    window_s = (hi - lo) / 1e9
    busy = sum(busy_s) / len(busy_s)
    return {
        "windowS": round(window_s, 9),
        "busyS": round(busy, 9),
        "idlePct": round(100.0 * (1.0 - busy / window_s), 3)
        if window_s > 0 else 0.0,
        "numDevices": len(devices),
        "deviceSecondsByProgram": _rounded(programs),
        "deviceSecondsByScope": _rounded(scopes),
        "unscopedSecondsByProgram": _rounded(unscoped),
        "deviceSecondsByOperation": [
            {"operation": name, "program": program, "scope": scope,
             "seconds": round(seconds, 9), "events": round(events, 3)}
            for (program, name), (seconds, events, scope) in sorted(
                operations.items(), key=lambda kv: -kv[1][0]
            )[:LARGEST_OPERATIONS]],
        "idleSecondsBySpan": _rounded(_idle_by_span(gaps, spans)),
    }


def summarize(trace_dir: str) -> dict | None:
    """``reduce(load_events(trace_dir))``: what ``GET /profile`` adds to
    its response under ``summary``."""
    return reduce(load_events(trace_dir))
