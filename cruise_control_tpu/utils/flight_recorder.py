"""Solver flight recorder: per-goal, per-dispatch search telemetry.

The two open perf fronts in ROADMAP (acceptance-density-limited count
goals; the never-run real-TPU op-class campaign) are blocked on
VISIBILITY: the search internals were only reachable through offline
tools (``tools/diag_tr_density.py``), and the megastep's donated
on-device loops (round 10) make host-side introspection scarce by
design. This module is the deliberate readback channel:

- **Per-round ring** (single-device megastep path): the chain move
  drivers optionally carry a small ``[ring, stats]`` f32 buffer through
  the ``lax.while_loop`` and write one stats row per search round —
  applied moves, valid/accepted/positive candidate counts, per-source
  winner rows, and the active goal's violation total (the
  ``diag_tr_density`` attribution made first-class, on device). The
  ring rides the megastep's EXISTING async stats readback: the host
  reads it exactly when it reads the dispatch's scalars, so pipelining
  is untouched.
- **Per-dispatch records** (all paths, sharded included): budget,
  rounds, applied, donation/speculative flags, elapsed wall-clock, and
  the AdaptiveDispatch controller's current budget ``k`` — the
  controller state the staleness contract otherwise hides.
- **Per-goal records**: entry/exit violation + objective, offline
  counts, deficit-sizing decisions (``chain.deficit_sized_config``) and
  the search-grid geometry in force.
- **Bounded pass ring**: completed optimization passes live in a
  bounded deque, served by ``GET /kafkacruisecontrol/solver``
  (``?cluster=``, ``?goal=``, ``?entries=``) and exported as
  ``solver_flight_*`` sensors.

Contract (pinned in tests/test_flight_recorder.py):

- **Trajectory parity**: recording adds REDUCTIONS over tensors the
  round body already computes — never a new selection input — so the
  solver trajectory is byte-identical with recording on or off (the
  same discipline as the megastep's budget invariance).
- **Near-zero disabled overhead**: when disabled, every hook resolves
  to a shared no-op object whose methods are empty (the tracing
  ``_NullSpan`` discipline); bench emits the measured ns/call as
  ``flight_recorder_noop_overhead``.
"""

from __future__ import annotations

import collections
import json
import threading
import time

from .sensors import SENSORS, current_cluster_label

# Columns of the on-device per-round stats row (chain._chain_round_body
# stats="row"). ``violation`` is the active goal's broker-violation total
# at round ENTRY (the tensors the row reduces over are the pre-apply
# state): trajectory[N] equals exit-of-round N-1, and the goal's recorded
# exit stats carry the final post-pass value — recomputing violations
# post-apply would double the per-round aux work and break the
# reductions-only parity contract.
STAT_COLUMNS = ("applied", "valid", "accepted", "positive", "winners",
                "violation")
STAT_WIDTH = len(STAT_COLUMNS)

# Acceptance-density histogram bounds: density = accepted moves per round
# / selection width, spanning "one move squeezed out of a 2048-wide grid"
# (~5e-4) to a fully saturated round (1.0).
DENSITY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0)


def decode_ring(ring, rounds: int) -> list[list[float]]:
    """Unscramble a per-round ring buffer: rows were written at
    ``round % len(ring)``, so with more rounds than slots the OLDEST
    surviving row starts at ``rounds % len(ring)``. Returns the rows in
    round order (oldest first), at most ``len(ring)`` of them."""
    import numpy as np
    a = np.asarray(ring)
    n = a.shape[0]
    if n == 0 or rounds <= 0:
        return []
    if rounds <= n:
        rows = a[:rounds]
    else:
        start = rounds % n
        rows = np.concatenate([a[start:], a[:start]])
    return [[float(x) for x in row] for row in rows]


class _NullGoalFlight:
    """Shared no-op goal hook: the disabled path costs one attribute
    load + one empty-method call per record site (all of which sit at
    dispatch/pass granularity, never per-candidate)."""

    __slots__ = ()
    recording = False
    ring_rounds = 0

    def entry(self, *a, **kw) -> None:
        pass

    def exit(self, *a, **kw) -> None:
        pass

    def sizing(self, *a, **kw) -> None:
        pass

    def grid(self, *a, **kw) -> None:
        pass

    def dispatch(self, *a, **kw) -> None:
        pass


class _NullPassFlight:
    __slots__ = ()
    recording = False

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def goal(self, name: str) -> _NullGoalFlight:
        return _NULL_GOAL

    def record_goal_infos(self, infos) -> None:
        pass

    def set(self, **kw) -> None:
        pass


_NULL_GOAL = _NullGoalFlight()
_NULL_PASS = _NullPassFlight()

# Public no-op goal hook: chain drivers default their ``flight=`` seam to
# this so every record site is an unconditional call on a do-nothing
# object (no branches on the solver driver paths).
NO_FLIGHT = _NULL_GOAL


class GoalFlight:
    """Recorder handle for one goal's optimization inside a pass."""

    __slots__ = ("name", "viol_before", "viol_after", "obj_before",
                 "obj_after", "offline_before", "offline_after",
                 "grid_sources", "grid_dests", "grid_moves",
                 "selection_width", "sizing_info", "dispatches",
                 "_recorder")

    recording = True

    def __init__(self, name: str, recorder: "FlightRecorder"):
        self.name = name
        self._recorder = recorder
        self.viol_before = self.viol_after = None
        self.obj_before = self.obj_after = None
        self.offline_before = self.offline_after = None
        self.grid_sources = self.grid_dests = self.grid_moves = 0
        self.selection_width = 0
        self.sizing_info: dict | None = None
        self.dispatches: list[dict] = []

    @property
    def ring_rounds(self) -> int:
        return self._recorder.ring_rounds

    def entry(self, violation: float, objective: float = 0.0,
              offline: int = 0) -> None:
        self.viol_before = round(float(violation), 4)
        self.obj_before = float(objective)
        self.offline_before = int(offline)

    def exit(self, violation: float, objective: float = 0.0,
             offline: int = 0) -> None:
        self.viol_after = round(float(violation), 4)
        self.obj_after = float(objective)
        self.offline_after = int(offline)

    def grid(self, num_sources: int, num_dests: int,
             moves_per_round: int) -> None:
        self.grid_sources = int(num_sources)
        self.grid_dests = int(num_dests)
        self.grid_moves = int(moves_per_round)
        # Selection admits at most max(moves, sources) candidates per
        # round — the denominator of acceptance density.
        self.selection_width = max(self.grid_moves, self.grid_sources)

    def sizing(self, entry_violation: float, base_moves: int,
               base_sources: int, sized_moves: int, sized_sources: int,
               cap: int) -> None:
        """One deficit-sizing decision (chain.deficit_sized_config)."""
        self.sizing_info = {
            "entryViolation": round(float(entry_violation), 2),
            "baseMoves": int(base_moves), "baseSources": int(base_sources),
            "sizedMoves": int(sized_moves),
            "sizedSources": int(sized_sources), "cap": int(cap),
            "applied": (sized_moves != base_moves
                        or sized_sources != base_sources)}

    def dispatch(self, kind: str, budget: int, rounds: int, applied: int,
                 donated: bool = False, speculative: bool = False,
                 elapsed_s: float = 0.0, controller_k: int | None = None,
                 ring=None) -> None:
        """One device dispatch's readback. ``ring`` is the on-device
        per-round stats buffer (or None on paths without it: swap phases,
        the sharded kernels, speculative re-runs). Acceptance density is
        only defined for MOVE dispatches on a known grid (the recorded
        ``grid()`` geometry is the move config's — swap kernels run their
        own fixed grid, and the single-dispatch whole-chain paths never
        record one): everything else reports 0.0 and stays out of the
        density histogram. Direct-assignment dispatches
        (``kind="direct"``, analyzer.direct — ``budget`` is the sweep cap
        and ``rounds`` the sweeps run) are deliberately in that
        "everything else": a transport solve has no per-round selection
        grid, so folding its moves-per-sweep into the density histogram
        would masquerade as an off-scale greedy density and corrupt the
        exact distribution the kill-attribution investigation reads."""
        density = (float(applied) / max(1, int(rounds))) \
            / self.selection_width \
            if (kind == "move" and not speculative
                and self.selection_width > 0) else 0.0
        rec = {
            "kind": kind, "budget": int(budget), "rounds": int(rounds),
            "applied": int(applied), "donated": bool(donated),
            "speculative": bool(speculative),
            "elapsedS": round(float(elapsed_s), 4),
            "acceptanceDensity": round(density, 6),
        }
        if controller_k is not None:
            rec["controllerK"] = int(controller_k)
        if ring is not None:
            rows = decode_ring(ring, int(rounds))
            rec["rounds_log"] = [
                {c: (int(v) if c != "violation" else round(v, 2))
                 for c, v in zip(STAT_COLUMNS, row)} for row in rows]
        self.dispatches.append(rec)
        self._recorder._on_dispatch(self, rec)

    # -- export ------------------------------------------------------------
    def kill_attribution(self) -> dict | None:
        """Aggregate candidate-kill attribution over every recorded round
        (the diag_tr_density stages): where the grid's cards went. None
        when no per-round rows were captured.

        Stage semantics (matching the counts the round body can reduce
        on-device): ``killedByPriorVeto`` = valid cards a prior goal's
        acceptance vetoed; ``killedByNonPositive`` = accepted cards with
        no positive improvement; ``killedByPerSourceReduce`` = positive
        cards that lost their source row's winner slot
        (search.reduce_per_source — one winner per source); and
        ``killedByDedupRecheck`` = winner rows dropped by the selection
        stage, which bundles per-partition/broker dedup, the
        moves-per-round cap, and the joint acceptance recheck
        (diag_tr_density's own final 'selected after dedup+recheck'
        stage — the three are one fused kernel and not separable without
        re-running selection)."""
        rows = [r for d in self.dispatches for r in d.get("rounds_log", ())]
        if not rows:
            return None
        valid = sum(r["valid"] for r in rows)
        accepted = sum(r["accepted"] for r in rows)
        positive = sum(r["positive"] for r in rows)
        winners = sum(r["winners"] for r in rows)
        applied = sum(r["applied"] for r in rows)
        return {
            "rounds": len(rows), "validCards": valid,
            "killedByPriorVeto": max(0, valid - accepted),
            "killedByNonPositive": max(0, accepted - positive),
            "killedByPerSourceReduce": max(0, positive - winners),
            "killedByDedupRecheck": max(0, winners - applied),
            "applied": applied,
        }

    def violation_trajectory(self) -> list[float]:
        """Per-round active-goal violation totals at round ENTRY (see
        STAT_COLUMNS — entry[N] = exit[N-1]; the final post-pass value is
        ``violationAfter``), in round order, across every move dispatch
        that carried the ring."""
        return [round(r["violation"], 2) for d in self.dispatches
                for r in d.get("rounds_log", ())]

    def to_dict(self) -> dict:
        moves = sum(d["applied"] for d in self.dispatches
                    if not d["speculative"])
        rounds = sum(d["rounds"] for d in self.dispatches
                     if not d["speculative"])
        # Density over MOVE dispatches only, and only when a grid was
        # recorded: the fused/sharded-unbounded goal summaries have no
        # selection width (a raw moves-per-round would masquerade as a
        # density > 1), and swap kernels run their own fixed grid.
        m_moves = sum(d["applied"] for d in self.dispatches
                      if not d["speculative"] and d["kind"] == "move")
        m_rounds = sum(d["rounds"] for d in self.dispatches
                       if not d["speculative"] and d["kind"] == "move")
        density = (m_moves / m_rounds / self.selection_width) \
            if m_rounds and self.selection_width > 0 else 0.0
        # Solve-mode label: without it, a goal bulk-solved by the direct
        # kernel shows near-zero greedy rounds and a ~0 density, which
        # reads as "the search died instantly" — kill attribution must
        # not be misread as zero-density when the transport simply took
        # the work.
        kinds = {d["kind"] for d in self.dispatches}
        if "direct" in kinds:
            mode = "direct+greedy" if kinds & {"move", "swap", "chain"} \
                else "direct"
        else:
            mode = "greedy"
        out = {
            "goal": self.name,
            "solveMode": mode,
            "violationBefore": self.viol_before,
            "violationAfter": self.viol_after,
            "offlineBefore": self.offline_before,
            "offlineAfter": self.offline_after,
            "rounds": rounds, "movesApplied": moves,
            "dispatchCount": len(self.dispatches),
            "acceptanceDensity": round(density, 6),
            "grid": {"sources": self.grid_sources, "dests": self.grid_dests,
                     "movesPerRound": self.grid_moves,
                     "selectionWidth": self.selection_width},
            "dispatches": self.dispatches,
        }
        if self.sizing_info is not None:
            out["deficitSizing"] = self.sizing_info
        kills = self.kill_attribution()
        if kills is not None:
            out["killAttribution"] = kills
            out["violationTrajectory"] = self.violation_trajectory()
        return out


class PassFlight:
    """Context manager recording one optimization pass. Closing appends
    the pass to the recorder's bounded ring and emits its sensors."""

    recording = True

    def __init__(self, recorder: "FlightRecorder", seq: int,
                 shape: tuple[int, int] | None, cluster: str | None):
        self._recorder = recorder
        self.seq = seq
        self.shape = shape
        self.cluster = cluster
        self.started_ms = int(recorder._clock() * 1000)
        self.attributes: dict = {}
        self.goals: list[GoalFlight] = []
        self._t0 = recorder._monotonic()

    def __enter__(self) -> "PassFlight":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.attributes.setdefault("error", exc_type.__name__)
        self._recorder._close_pass(
            self, self._recorder._monotonic() - self._t0)
        return False

    def goal(self, name: str) -> GoalFlight:
        g = GoalFlight(name, self._recorder)
        self.goals.append(g)
        return g

    def set(self, **attributes) -> None:
        self.attributes.update(attributes)

    def record_goal_infos(self, infos) -> None:
        """Goal-level summaries for the single-dispatch whole-chain paths
        (fused + sharded-unbounded): no per-dispatch detail exists — the
        whole chain ran in ONE XLA execution — but entry/exit violations
        and round/move counts still land in the flight record."""
        for info in infos:
            g = self.goal(info["goal"])
            if "violation_before" in info:
                g.entry(violation=info["violation_before"],
                        offline=info.get("offline_before", 0))
            g.exit(violation=info["residual_violation"],
                   objective=info.get("objective", 0.0),
                   offline=info.get("offline_remaining", 0))
            g.dispatches.append({
                "kind": "chain", "budget": 0, "rounds": info["rounds"],
                "applied": info["moves_applied"], "donated": False,
                "speculative": False, "elapsedS": 0.0,
                "acceptanceDensity": 0.0})

    def to_dict(self) -> dict:
        return {
            "passSeq": self.seq,
            "cluster": self.cluster,
            "path": self.attributes.get("path"),
            "shape": {"partitions": self.shape[0], "brokers": self.shape[1]}
            if self.shape else None,
            "startedAtMs": self.started_ms,
            "durationS": self.attributes.get("durationS"),
            "attributes": {k: v for k, v in self.attributes.items()
                           if k not in ("durationS", "path")},
            "goals": [g.to_dict() for g in self.goals],
        }


class FlightRecorder:
    """Process-wide recorder: pass factory + bounded pass ring + export
    (the ``utils.tracing.Tracer`` pattern)."""

    def __init__(self, max_passes: int = 64, ring_rounds: int = 128,
                 clock=time.time, monotonic=time.monotonic):
        # Injectable clocks (CCSA004 seam, the SimClock discipline): the
        # recorder's pass timestamps/durations are observability-only —
        # already excluded from scenario score JSON (round 12) — but an
        # injected pair keeps a twin's flight dumps replay-stable too.
        self._clock = clock
        self._monotonic = monotonic
        self._lock = threading.Lock()
        self._enabled = True
        self._ring_rounds = int(ring_rounds)
        self._passes: collections.deque[PassFlight] = \
            collections.deque(maxlen=max_passes)
        self.passes_closed = 0
        self.dispatches_recorded = 0

    # -- configuration -----------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def ring_rounds(self) -> int:
        """Length of the on-device per-round stats ring. A TRACE-TIME
        constant: changing it recompiles the recording chain kernels, so
        it is process-config, not per-request."""
        return self._ring_rounds

    def configure(self, enabled: bool | None = None,
                  max_passes: int | None = None,
                  ring_rounds: int | None = None) -> None:
        with self._lock:
            if enabled is not None:
                self._enabled = bool(enabled)
            if max_passes is not None \
                    and max_passes != self._passes.maxlen:
                self._passes = collections.deque(
                    self._passes, maxlen=max(1, max_passes))
            if ring_rounds is not None:
                self._ring_rounds = max(0, int(ring_rounds))

    # -- recording ---------------------------------------------------------
    def pass_scope(self, seq: int = 0,
                   shape: tuple[int, int] | None = None,
                   cluster: str | None = None):
        """Open a pass record (context manager). Disabled → shared no-op
        whose ``goal()`` returns the shared no-op goal hook. ``cluster``
        overrides the ambient cluster label — the megabatch solver opens
        one pass PER CLUSTER in the batch from a single worker thread, so
        ``GET /solver`` keeps answering per cluster."""
        if not self._enabled:
            return _NULL_PASS
        return PassFlight(self, seq, shape,
                          cluster if cluster is not None
                          else current_cluster_label())

    def _on_dispatch(self, goal: GoalFlight, rec: dict) -> None:
        with self._lock:
            self.dispatches_recorded += 1
        # Only move dispatches on a known grid carry a defined density —
        # a swap or gridless sample would skew the exact histogram the
        # density investigation reads.
        if rec["speculative"] or rec["kind"] != "move" \
                or goal.selection_width <= 0:
            return
        SENSORS.observe("solver_acceptance_density",
                        rec["acceptanceDensity"],
                        labels={"goal": goal.name},
                        buckets=DENSITY_BUCKETS)

    def _close_pass(self, p: PassFlight, duration_s: float) -> None:
        p.attributes["durationS"] = round(duration_s, 4)
        with self._lock:
            self.passes_closed += 1
            self._passes.append(p)
        SENSORS.count("solver_flight_passes")
        for g in p.goals:
            kills = g.kill_attribution()
            if kills is None:
                continue
            labels = {"goal": g.name}
            SENSORS.count("solver_flight_rounds", kills["rounds"],
                          labels=labels)
            SENSORS.count("solver_flight_killed_prior_veto",
                          kills["killedByPriorVeto"], labels=labels)
            SENSORS.count("solver_flight_killed_nonpositive",
                          kills["killedByNonPositive"], labels=labels)
            SENSORS.count("solver_flight_killed_source_reduce",
                          kills["killedByPerSourceReduce"], labels=labels)
            SENSORS.count("solver_flight_killed_dedup_recheck",
                          kills["killedByDedupRecheck"], labels=labels)
            if g.viol_after is not None:
                SENSORS.gauge("solver_flight_residual_violation",
                              g.viol_after, labels=labels)

    # -- export ------------------------------------------------------------
    def passes(self, cluster: str | None = None, goal: str | None = None,
               limit: int | None = None) -> list[dict]:
        """Recent completed passes, newest first. ``cluster`` filters by
        the ambient cluster label the pass ran under; ``goal`` keeps only
        passes touching that goal AND trims each pass's goal list to it."""
        with self._lock:
            snapshot = list(self._passes)
        out: list[dict] = []
        if limit is not None and limit <= 0:
            return out
        for p in reversed(snapshot):
            if cluster is not None and p.cluster != cluster:
                continue
            d = p.to_dict()
            if goal is not None:
                d["goals"] = [g for g in d["goals"] if g["goal"] == goal]
                if not d["goals"]:
                    continue
            out.append(d)
            if limit is not None and len(out) >= limit:
                break
        return out

    def marker(self) -> int:
        """Opaque position marker for ``passes_since`` (the simulator's
        per-scenario summary hook)."""
        with self._lock:
            return self.passes_closed

    def passes_since(self, marker: int) -> list[dict]:
        """Passes closed after ``marker`` (oldest first), best-effort: the
        bounded ring may already have evicted the oldest ones."""
        with self._lock:
            new = self.passes_closed - marker
            snapshot = list(self._passes)[-new:] if new > 0 else []
        return [p.to_dict() for p in snapshot]

    def dump_json(self, path: str) -> int:
        """Write every retained pass as one JSON document (bench/CI
        artifact). Returns the number of passes written."""
        with self._lock:
            snapshot = list(self._passes)
        doc = {"numPasses": len(snapshot),
               "passes": [p.to_dict() for p in snapshot]}
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return len(snapshot)

    def clear(self) -> None:
        with self._lock:
            self._passes.clear()


FLIGHT = FlightRecorder()


def summarize_passes(passes: list[dict]) -> dict:
    """Aggregate a pass list into the compact summary the digital-twin
    scenario score embeds (wall-clock-free: only counts and densities, so
    the summary is deterministic for a deterministic trajectory)."""
    dispatches = rounds = moves = 0
    direct_dispatches = direct_moves = 0
    kills = {"killedByPriorVeto": 0, "killedByNonPositive": 0,
             "killedByPerSourceReduce": 0, "killedByDedupRecheck": 0}
    by_goal: dict[str, dict] = {}
    for p in passes:
        for g in p.get("goals", ()):
            real = [d for d in g.get("dispatches", ())
                    if not d.get("speculative")]
            dispatches += len(real)
            direct_dispatches += sum(1 for d in real
                                     if d.get("kind") == "direct")
            direct_moves += sum(d["applied"] for d in real
                                if d.get("kind") == "direct")
            g_rounds = sum(d["rounds"] for d in real)
            g_moves = sum(d["applied"] for d in real)
            rounds += g_rounds
            moves += g_moves
            ka = g.get("killAttribution")
            if ka:
                for k in kills:
                    kills[k] += ka[k]
            slot = by_goal.setdefault(
                g["goal"], {"passes": 0, "rounds": 0, "moves": 0,
                            "lastViolationAfter": None,
                            "violationTrajectory": []})
            slot["passes"] += 1
            slot["rounds"] += g_rounds
            slot["moves"] += g_moves
            if g.get("violationAfter") is not None:
                slot["lastViolationAfter"] = g["violationAfter"]
                # Pass-over-pass exit violations: the scenario-level WHY
                # (a quality drop shows up as a trajectory that stopped
                # descending, not just a worse final number).
                slot["violationTrajectory"].append(g["violationAfter"])
    # Mean density over MOVE dispatches with a recorded grid only (same
    # definition as GoalFlight.to_dict: gridless goal summaries and swap
    # kernels have no defined density).
    width_weighted = [
        (d["applied"], d["rounds"],
         (g.get("grid") or {}).get("selectionWidth", 0))
        for p in passes for g in p.get("goals", ())
        for d in g.get("dispatches", ())
        if not d.get("speculative") and d.get("kind") == "move"]
    width_weighted = [(a, r, w) for a, r, w in width_weighted if w > 0]
    total_rounds = sum(r for _a, r, _w in width_weighted)
    density = (sum(a / w for a, _r, w in width_weighted)
               / total_rounds) if total_rounds else 0.0
    out = {
        "passes": len(passes), "dispatches": dispatches,
        "rounds": rounds, "movesApplied": moves,
        "meanAcceptanceDensity": round(density, 6),
        "killAttribution": kills,
        "byGoal": {k: by_goal[k] for k in sorted(by_goal)},
    }
    if direct_dispatches:
        # Present only when the direct-assignment kernel ran, so the
        # scenario score JSON (byte-identical pinned digests) is
        # untouched on the greedy-only paths.
        out["directDispatches"] = direct_dispatches
        out["directMoves"] = direct_moves
    return out
