"""Always-hot solver machinery (round 18): warm-start seeds, violation
fingerprints, and per-shape AOT prewarm.

ROADMAP item 3's three composing pieces live here and in their call
sites:

- **Warm starts** — ``WarmSeedStore`` keeps the last ACCEPTED
  ``(assignment, leader_slot)`` per facade (one facade = one cluster;
  fleet clusters each own a store). Under sustained drift most goals are
  already satisfied at the previous target, so seeding the next chain
  solve from it collapses rounds-to-convergence. Safety: the facade
  diffs proposals against the TRUE current model (never the seed), and a
  warm-seeded result that falls below the cold path's sentry band —
  ``solver.warm.start.quality.band`` balancedness drop, or a violated
  goal the seed's own solve did not have — triggers a COUNTED cold
  re-solve (``solver_warm_fallbacks``), so warm starts can never
  silently degrade proposals.

- **Violation fingerprints** — ``violation_fingerprint`` hashes the
  per-goal entry-violation vector the ONE batched
  ``chain_all_goal_stats`` program snapshots before the bounded chain
  loop (analyzer.chain / analyzer.optimizer). A goal whose snapshot
  shows zero entry violation applies nothing, so its dispatches are
  skipped byte-identically (``DispatchStats.goals_skipped``).

- **AOT prewarm** — ``ShapeRegistry`` persists every solved padded
  bucket-shape signature in ``solver_shapes.json`` inside the XLA
  persistent-cache directory in force, and ``PrewarmManager`` compiles
  the whole per-shape kernel set in a background thread at ``start_up``
  (``GoalOptimizer.prewarm_shape`` executes the production kernels on an
  inert synthetic model: full compile, zero search work). Watched by the
  existing ``xla_compile_cache_{hits,misses}`` counters; progress is
  surfaced on ``GET /state`` (AnalyzerState.prewarm) and ``GET /fleet``.

Determinism: this module is in CCSA004's deterministic set — the warm
path influences solver inputs and must be wall-clock/random-free; the
prewarm manager times itself through the injectable ``monotonic`` seam
(observability only).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
import time
import weakref
import zlib
from typing import Any

import numpy as np

LOG = logging.getLogger(__name__)


# -- compile-cache config seam (satellite: solver.compile.cache.*) ---------

def configure_compile_cache(config) -> str | None:
    """Turn on XLA's persistent compilation cache from the
    ``solver.compile.cache.{enabled,dir,min.compile.secs}`` keys. Called
    from facade ``start_up`` so SERVING processes (not just bench/CLI
    wrappers) persist their solver compiles. ``JAX_COMPILATION_CACHE_DIR``
    outranks ``solver.compile.cache.dir`` (the one placement rule lives in
    ``enable_persistent_compile_cache``). Returns the directory in force,
    or None when disabled."""
    if not config.get_boolean("solver.compile.cache.enabled"):
        return None
    from . import enable_persistent_compile_cache
    return enable_persistent_compile_cache(
        config.get("solver.compile.cache.dir") or None,
        min_compile_secs=config.get_double(
            "solver.compile.cache.min.compile.secs"))


# -- violation fingerprints ------------------------------------------------

def violation_fingerprint(violations) -> int:
    """crc32 of the per-goal entry-violation vector (rounded to 1e-6 so
    f32 noise cannot flap the fingerprint). Zero entries are exactly the
    goals the bounded chain loop may skip dispatch-free."""
    v = np.asarray(violations, dtype=np.float64).reshape(-1)
    return zlib.crc32(np.round(v, 6).astype(np.float32).tobytes())


# -- warm-start seeds ------------------------------------------------------

@dataclasses.dataclass
class WarmSeed:
    """The last accepted solver target plus the quality it was accepted
    at (the fallback band's reference point). ``partition_index`` /
    ``broker_ids`` pin the index space the tensors are meaningful in."""

    assignment: Any           # [P, S] device array
    leader_slot: Any          # [P] device array
    partition_index: Any      # ClusterMeta.partition_index (ref)
    broker_ids: Any           # ClusterMeta.broker_ids (ref)
    balancedness_after: float
    violated_after: frozenset


def _same_index(a, b) -> bool:
    # The refresh pipeline's topology cache returns the SAME ClusterMeta
    # object on a topology hit, so the identity check makes steady-state
    # validation O(1); equality is the fallback across rebuilds.
    return a is b or a == b


class WarmSeedStore:
    """Lock-guarded single-slot store of the facade's last accepted
    solve target. A seed is valid for a new model exactly when the
    padded tensor shapes AND the index spaces (partition rows, broker
    axis) match — liveness/load changes do NOT invalidate it: the goal
    chain re-checks everything, and the quality fallback guards the
    rest. No wall-clock: staleness is bounded by topology identity plus
    the fallback band, not by age."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seed: WarmSeed | None = None

    def store(self, final_state, meta, result,
              reference: "tuple[float, frozenset] | None" = None) -> None:
        """Record a solve's final state as the next warm seed (called on
        ACCEPTED results only — quality-flunked warm solves never seed).

        ``reference`` overrides the quality the NEXT warm solve is gated
        against. COLD solves pass None (their own quality re-anchors the
        gate); a gate-passing WARM solve passes the sticky reference —
        max(previous reference, own balancedness) with its own (never
        larger, gate-guaranteed) violated set — so repeated warm solves
        cannot ratchet served quality down by one band per tick: the
        reference only rises until a cold solve re-anchors it."""
        if reference is None:
            reference = (float(result.balancedness_after),
                         frozenset(result.violated_goals_after))
        seed = WarmSeed(
            assignment=final_state.assignment,
            leader_slot=final_state.leader_slot,
            partition_index=meta.partition_index,
            broker_ids=meta.broker_ids,
            balancedness_after=float(reference[0]),
            violated_after=frozenset(reference[1]))
        with self._lock:
            self._seed = seed
        from .utils.sensors import SENSORS
        SENSORS.count("solver_warm_seed_stored")

    def match(self, state, meta) -> WarmSeed | None:
        """The stored seed when it is valid for ``(state, meta)``, else
        None (an invalid seed is dropped and counted — topology moved)."""
        with self._lock:
            seed = self._seed
        if seed is None:
            return None
        if (tuple(seed.assignment.shape) != tuple(state.assignment.shape)
                or tuple(seed.leader_slot.shape)
                != tuple(state.leader_slot.shape)
                or not _same_index(seed.partition_index,
                                   meta.partition_index)
                or not _same_index(seed.broker_ids, meta.broker_ids)):
            # Compare-and-clear: validation ran outside the lock, and a
            # concurrent store() may have replaced the slot with a seed
            # valid for the NEW topology — only drop the exact seed
            # that failed.
            with self._lock:
                if self._seed is seed:
                    self._seed = None
            from .utils.sensors import SENSORS
            SENSORS.count("solver_warm_seed_invalid")
            return None
        return seed

    def clear(self) -> None:
        with self._lock:
            self._seed = None


def warm_quality_ok(result, reference_balancedness: float,
                    reference_violated, band: float) -> bool:
    """THE warm-start sentry-band predicate (shared by the facade's
    serving gate and the bench's served-semantics measurement, so the
    two can never drift): a warm result is acceptable iff it violates
    no goal the reference did not and its balancedness sits within
    ``band`` of the reference."""
    if set(result.violated_goals_after) - set(reference_violated):
        return False
    return result.balancedness_after >= reference_balancedness - band


def seed_band_ok(entry_balancedness: float, entry_violated,
                 seed: WarmSeed, band: float) -> bool:
    """The warm-band PRE-CHECK predicate (round 19, ROADMAP 3a tail):
    the seed scored against the CURRENT loads — one batched
    ``chain_all_goal_stats`` entry snapshot — must sit inside the same
    sentry band ``warm_quality_ok`` enforces after the solve: no
    violated goal the seed's accepted solve did not have, balancedness
    within ``band`` of the accepted reference. Honest trade: the chain
    COULD sometimes repair an out-of-band seed and keep the warm win,
    but the measured drift case (±5 % wave, bench --warmstart) converges
    band-worse and pays attempt+fallback — the pre-check skips that
    doomed double solve. Served results stay byte-equal either way: the
    skip path runs exactly the fallback's cold solve (pinned in
    tests/test_warmstart.py)."""
    if set(entry_violated) - set(seed.violated_after):
        return False
    return entry_balancedness >= seed.balancedness_after - band


def apply_seed(state, seed: WarmSeed):
    """``state`` with the seed's mutable pair swapped in — the warm
    search start. The seed arrays enter the chain exactly like the cold
    pair: the first dispatch donates a device COPY (donate_input=False),
    so the stored seed survives the solve (CCSA002's donation contract
    is unchanged)."""
    return dataclasses.replace(state, assignment=seed.assignment,
                               leader_slot=seed.leader_slot)


# -- shape signatures (prewarm registry entries) ---------------------------

_MASK_FIELDS = ("excluded_topics", "excluded_replica_move_brokers",
                "excluded_leadership_brokers")


def goal_spec(g) -> str | dict | None:
    """Reproducible signature spec of ONE goal instance: the bare
    registry name for a default-constructible goal; a ``{"name",
    "state"}`` dict when the goal carries bound JSON-round-trippable
    dataclass state (round 20: bound-broker-set chains prewarm too —
    the round-18 documented gap); None when the instance cannot be
    rebuilt equal in a fresh process (then the chain records nothing,
    as before)."""
    name = type(g).__name__
    try:
        if type(g)() == g:
            return name
    except Exception:  # noqa: BLE001 — bound state; try the dict spec
        pass
    if not dataclasses.is_dataclass(g):
        return None
    try:
        state = json.loads(json.dumps(dataclasses.asdict(g)))
    except (TypeError, ValueError):
        return None
    spec = {"name": name, "state": state}
    try:
        from .analyzer.goals import ALL_GOALS
        # The spec is only a spec if it round-trips to an EQUAL instance
        # — anything lossy (non-tuple containers, derived fields) must
        # fall back to recording nothing rather than prewarming a
        # different program.
        if goal_from_spec(spec, ALL_GOALS) != g:
            return None
    except Exception:  # noqa: BLE001 — unregistered/unbuildable goal
        return None
    return spec


def goal_from_spec(spec: str | dict, registry: dict):
    """Rebuild a goal instance from its signature spec (KeyError for
    names missing from ``registry``). JSON turned the frozen dataclass's
    tuples into lists; top-level sequence fields convert back."""
    if isinstance(spec, str):
        return registry[spec]()
    cls = registry[spec["name"]]
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name in spec.get("state", {}):
            v = spec["state"][f.name]
            kwargs[f.name] = tuple(v) if isinstance(v, list) else v
    return cls(**kwargs)


def shape_signature(state, num_topics: int, goal_chain, masks,
                    batch: int = 0) -> dict | None:
    """JSON-serializable identity of one solved shape: every tensor
    field's (shape, dtype), the mask layout, the goal chain (by
    registry name, or ``goal_spec`` dicts for goals with bound
    JSON-round-trippable state; chains with irreproducible state record
    nothing), and the megabatch width. Enough to rebuild an inert
    synthetic model and re-compile the exact kernel set."""
    names = []
    for g in goal_chain:
        spec = goal_spec(g)
        if spec is None:
            return None
        names.append(spec)
    tensors = {}
    for f in dataclasses.fields(state):
        arr = getattr(state, f.name)
        tensors[f.name] = [list(arr.shape), str(arr.dtype)]
    mask_shapes = {}
    for name in _MASK_FIELDS:
        m = getattr(masks, name)
        mask_shapes[name] = None if m is None \
            else [list(m.shape), str(m.dtype)]
    return {"tensors": tensors, "num_topics": int(num_topics),
            "goals": names, "mask_shapes": mask_shapes,
            "batch": int(batch)}


def synthetic_state(entry: dict):
    """An inert model at the entry's recorded shape (the
    ``inert_state_like`` encoding built from a signature instead of a
    template): all-dead masked brokers, empty masked partitions — every
    kernel compiles fully against it but runs zero search work."""
    import jax.numpy as jnp

    from .common.broker_state import BrokerState
    from .model.tensors import ClusterTensors
    fills = {"assignment": -1, "leader_slot": -1,
             "broker_state": int(BrokerState.DEAD)}
    kwargs = {}
    for name, (shape, dtype) in entry["tensors"].items():
        kwargs[name] = jnp.full(tuple(shape), fills.get(name, 0),
                                dtype=dtype)
    return ClusterTensors(**kwargs)


def synthetic_masks(entry: dict):
    """Inert all-False exclusion masks matching the entry's recorded
    presence layout (mask presence is a compile-time property of the
    kernels)."""
    import jax.numpy as jnp

    from .analyzer.search import ExclusionMasks
    shapes = entry.get("mask_shapes") or {}

    def build(name):
        spec = shapes.get(name)
        if spec is None:
            return None
        return jnp.zeros(tuple(spec[0]), dtype=spec[1])

    return ExclusionMasks(*(build(n) for n in _MASK_FIELDS))


class ShapeRegistry:
    """The persisted set of solved shape signatures, one JSON file kept
    in the XLA persistent-cache directory (it follows wherever the cache
    is placed). Atomic rewrite on every NEW shape; the set is tiny (one
    entry per padded bucket shape x chain x mask layout)."""

    def __init__(self, path: str):
        self._path = path
        self._lock = threading.Lock()
        self._known: dict[str, dict] | None = None

    @property
    def path(self) -> str:
        return self._path

    def _load_locked(self) -> None:
        if self._known is not None:
            return
        try:
            with open(self._path) as f:
                data = json.load(f)
            self._known = dict(data) if isinstance(data, dict) else {}
        except (OSError, ValueError):
            self._known = {}

    def record(self, entry: dict) -> bool:
        """Add one signature; returns True when it was new (and
        persisted)."""
        key = format(zlib.crc32(
            json.dumps(entry, sort_keys=True).encode()), "08x")
        with self._lock:
            self._load_locked()
            if key in self._known:
                return False
            self._known[key] = entry
            try:
                os.makedirs(os.path.dirname(self._path), exist_ok=True)
                tmp = f"{self._path}.tmp"
                with open(tmp, "w") as f:
                    json.dump(self._known, f, sort_keys=True)
                os.replace(tmp, self._path)
            except OSError:
                LOG.debug("prewarm shape registry write failed",
                          exc_info=True)
        from .utils.sensors import SENSORS
        SENSORS.count("prewarm_shapes_recorded")
        return True

    def entries(self) -> list[dict]:
        with self._lock:
            self._load_locked()
            return [dict(v) for v in self._known.values()]


class PrewarmManager:
    """Background compiler of the known shape set. ``start()`` is
    idempotent and double-start safe (one thread per manager, ever);
    re-prewarming is pointless in-process — the jit caches already hold
    everything the first run compiled. Status is served on GET /state
    and /fleet; the xla_compile_cache_{hits,misses} counters say whether
    the compiles were disk retrievals or cold builds."""

    def __init__(self, optimizer, registry: ShapeRegistry,
                 monotonic=time.monotonic):
        # Weak ref: the module registry is weak-keyed by the optimizer,
        # and a manager (held as that entry's VALUE) strongly
        # referencing its key would keep the key alive forever — the
        # exact leak the weak keying exists to prevent. A sweep whose
        # optimizer died mid-run just stops.
        self._optimizer_ref = weakref.ref(optimizer)
        self._registry = registry
        self._monotonic = monotonic
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._state = "idle"
        self.shapes_total = 0
        self.shapes_done = 0
        self.shapes_failed = 0
        self.shapes_skipped = 0
        self.duration_s = 0.0

    @property
    def registry(self) -> ShapeRegistry:
        return self._registry

    @property
    def running(self) -> bool:
        with self._lock:
            return self._state == "running"

    def start(self) -> bool:
        """Spawn the prewarm thread; False when already started (running
        OR finished — a second start_up never re-compiles)."""
        with self._lock:
            if self._thread is not None:
                return False
            self._state = "running"
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="solver-prewarm")
            thread = self._thread
        thread.start()
        return True

    def join(self, timeout: float | None = None) -> None:
        with self._lock:
            thread = self._thread
        if thread is not None:
            thread.join(timeout)

    def _run(self) -> None:
        from .utils.sensors import SENSORS
        t0 = self._monotonic()
        entries = self._registry.entries()
        with self._lock:
            self.shapes_total = len(entries)
        for entry in entries:
            optimizer = self._optimizer_ref()
            if optimizer is None:
                break
            try:
                ok = optimizer.prewarm_shape(entry)
            except Exception:  # noqa: BLE001 — warm the rest regardless
                LOG.warning("prewarm of shape entry failed", exc_info=True)
                with self._lock:
                    self.shapes_failed += 1
                SENSORS.count("prewarm_shapes_failed")
                continue
            with self._lock:
                if ok:
                    self.shapes_done += 1
                else:
                    self.shapes_skipped += 1
                self.duration_s = self._monotonic() - t0
            # Two explicit call sites: gen_docs/CCSA006 discover sensor
            # names by scanning for a literal after the call paren, so a
            # conditional name would vanish from SENSORS.md.
            if ok:
                SENSORS.count("prewarm_shapes_compiled")
            else:
                SENSORS.count("prewarm_shapes_skipped")
        with self._lock:
            self._state = "done"
            self.duration_s = self._monotonic() - t0
        SENSORS.gauge("prewarm_duration_seconds", self.duration_s)

    def status_dict(self) -> dict:
        with self._lock:
            return {"state": self._state,
                    "shapesTotal": self.shapes_total,
                    "shapesDone": self.shapes_done,
                    "shapesFailed": self.shapes_failed,
                    "shapesSkipped": self.shapes_skipped,
                    "durationS": round(self.duration_s, 3)}


# Module-level prewarm registry: ONE manager per (prewarm-enabled)
# optimizer, so a fleet's clusters sharing a GoalOptimizer prewarm once
# and a facade restarting its lifecycle never spawns a second compile
# sweep. Weak-keyed by the optimizer: a process that builds and drops
# many prewarm-enabled facades (test suites, embedders) must not pin
# every optimizer — and its jit/controller caches — for process
# lifetime; when the optimizer dies its manager entry (the only strong
# ref to the manager once the sweep thread finishes) dies with it.
_REGISTRY_LOCK = threading.Lock()
_MANAGERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def ensure_prewarm(optimizer, config, start: bool = True,
                   ) -> PrewarmManager | None:
    """Create (once) and start (idempotently) the prewarm manager for
    ``optimizer`` per ``config``. Returns None when prewarm is disabled
    or the persistent compile cache is off — the shape registry lives in
    the cache directory, and prewarming without persistence
    would re-pay every compile on the next restart anyway."""
    if not config.get_boolean("solver.prewarm.enabled"):
        return None
    cache_dir = configure_compile_cache(config)
    if cache_dir is None:
        return None
    with _REGISTRY_LOCK:
        mgr = _MANAGERS.get(optimizer)
        if mgr is None:
            registry = ShapeRegistry(
                os.path.join(cache_dir, "solver_shapes.json"))
            optimizer.attach_shape_registry(registry)
            mgr = PrewarmManager(optimizer, registry)
            _MANAGERS[optimizer] = mgr
    if start:
        mgr.start()
    return mgr


def prewarm_manager(optimizer) -> PrewarmManager | None:
    """The optimizer's prewarm manager, or None when none exists
    (prewarm disabled)."""
    with _REGISTRY_LOCK:
        return _MANAGERS.get(optimizer)


def prewarm_status(optimizer) -> dict | None:
    """The optimizer's prewarm progress (GET /state, GET /fleet), or
    None when no manager exists (prewarm disabled)."""
    mgr = prewarm_manager(optimizer)
    return mgr.status_dict() if mgr is not None else None
