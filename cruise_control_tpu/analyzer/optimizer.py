"""GoalOptimizer: run the goal chain by priority, collect stats, diff
proposals.

Reference parity: analyzer/GoalOptimizer.java:435-524 (optimizations():
iterate goals in priority order, each mutating the shared model under the
acceptance of all previously optimized goals; per-goal stats + durations;
diff initial vs final into proposals) and OptimizerResult.java.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Callable, Sequence

import jax.numpy as jnp
import numpy as np

from ..common.broker_state import BrokerState
from ..config.abstract_config import resolve_class
from ..config.cruise_control_config import CruiseControlConfig
from ..model.stats import ClusterModelStats, cluster_stats
from ..model.tensors import ClusterMeta, ClusterTensors
from .chain import optimize_chain, optimize_goal_in_chain
from .constraint import BalancingConstraint, OptimizationOptions
from .goals import ALL_GOALS
from .goals.base import Goal
from .proposals import (
    ExecutionProposal, FetchedDiff, compare_diff, count_leadership_only,
    fetch_diff,
)
from .search import ExclusionMasks, OptimizationFailureError, SearchConfig

LOG = logging.getLogger(__name__)

# Balancedness score weights (KafkaCruiseControlUtils.java:831-856): each
# priority level weighs priorityWeight× the next, hard goals weigh
# strictnessWeight×, normalized to MAX_BALANCEDNESS_SCORE.
MAX_BALANCEDNESS_SCORE = 100.0


@dataclasses.dataclass
class GoalResult:
    name: str
    is_hard: bool
    succeeded: bool
    rounds: int
    moves_applied: int
    residual_violation: float
    duration_s: float
    violated_before: bool
    swaps_applied: int = 0


@dataclasses.dataclass
class OptimizerResult:
    proposals: Sequence[ExecutionProposal]
    goal_results: list[GoalResult]
    stats_before: ClusterModelStats
    stats_after: ClusterModelStats
    violated_goals_before: list[str]
    violated_goals_after: list[str]
    balancedness_before: float
    balancedness_after: float
    duration_s: float

    def summary(self) -> dict:
        return {
            "num_proposals": len(self.proposals),
            "num_leadership_only": count_leadership_only(self.proposals),
            "violated_goals_before": self.violated_goals_before,
            "violated_goals_after": self.violated_goals_after,
            "balancedness_before": round(self.balancedness_before, 3),
            "balancedness_after": round(self.balancedness_after, 3),
            "duration_s": round(self.duration_s, 3),
            "goals": {g.name: {"rounds": g.rounds, "moves": g.moves_applied,
                               "violation": round(g.residual_violation, 4)}
                      for g in self.goal_results},
        }


def goals_by_priority(cfg: CruiseControlConfig,
                      goal_names: Sequence[str] | None = None) -> list[Goal]:
    """Instantiate the goal chain (KafkaCruiseControlUtils.goalsByPriority:
    config reflection over dotted paths; short names resolve through the
    registry)."""
    specs = list(goal_names) if goal_names else cfg.get_list("goals")
    goals = []
    for spec in specs:
        short = spec.rsplit(".", 1)[-1]
        cls = ALL_GOALS.get(short)
        if cls is None:
            cls = resolve_class(spec)
        goals.append(cls())
    return goals


def balancedness_score(goals: Sequence[Goal], violated: set[str],
                       priority_weight: float = 1.1,
                       strictness_weight: float = 1.5) -> float:
    """100 minus the normalized weighted cost of violated goals
    (GoalViolationDetector.refreshBalancednessScore:282-287)."""
    weights = []
    for i, g in enumerate(goals):
        w = priority_weight ** (len(goals) - 1 - i)
        if g.is_hard:
            w *= strictness_weight
        weights.append(w)
    total = sum(weights) or 1.0
    cost = sum(w for g, w in zip(goals, weights) if g.name in violated)
    return MAX_BALANCEDNESS_SCORE * (1.0 - cost / total)


def _apportioned_goal_results(goal_chain: Sequence[Goal], infos: list[dict],
                              chain_s: float) -> list[GoalResult]:
    """GoalResults from whole-chain kernel stats. Per-goal wall-clock cannot
    be measured inside one dispatch; the chain time is apportioned by each
    goal's share of search rounds (equal split when no goal ran).
    violated_before follows the reference (GoalOptimizer.java:450-482): a
    goal was violated BEFORE optimization iff it had work to do when its
    turn came, or it failed."""
    total_rounds = sum(info["rounds"] for info in infos) or None
    return [GoalResult(
        name=g.name, is_hard=g.is_hard, succeeded=info["succeeded"],
        rounds=info["rounds"], moves_applied=info["moves_applied"],
        residual_violation=info["residual_violation"],
        duration_s=chain_s * (info["rounds"] / total_rounds
                              if total_rounds else 1 / len(infos)),
        violated_before=info["violated_on_entry"] or not info["succeeded"],
        swaps_applied=info.get("swaps_applied", 0))
        for g, info in zip(goal_chain, infos)]


def _count_optimization_failure() -> None:
    """One more pass that ended in ``OptimizationFailureError``: a hard
    goal left unsatisfied or a drain left unfinished, counted alike."""
    from ..utils.sensors import SENSORS
    SENSORS.count("analyzer_optimization_failures")


def _dispatch_spans(span):
    """The closed ``solver.dispatch`` spans under ``span`` (directly, or
    under a ``goal.solve`` on the per-goal routes); none with tracing off."""
    pending = list(getattr(span, "children", ()))
    while pending:
        child = pending.pop()
        if child.name == "solver.dispatch":
            yield child
        else:
            pending.extend(child.children)


def ensure_evacuated(goal_chain: Sequence[Goal], infos: Sequence[dict],
                     final_state: Callable[[], ClusterTensors],
                     meta: ClusterMeta, options: OptimizationOptions,
                     span=None) -> None:
    """The drain's guarantee, checked once a pass where every route's
    per-goal infos converge: a pass that could move replicas and ends with
    one still offline (on a DEAD broker: a removal marks its brokers DEAD,
    so those are the replicas of the brokers being removed) has failed, as
    an unsatisfied hard goal has (GoalUtils.ensureNoOfflineReplicas, which
    the reference's replica-moving goals call). It raises, and no partial
    plan is returned. Reads the host scalars the chain's stats already
    fetched; only the failure path calls ``final_state`` and reads the
    device, to name the brokers.

    Also the pass's drain accounting: ``solver_offline_replicas_total
    {when="before"|"remaining"}`` (the first goal's entry count, the last
    goal's exit count), ``solver_evacuation_rounds_total`` (rounds of the
    goals entered with replicas offline), and ``offline_before``,
    ``offline_remaining``, ``excluded_brokers`` on the pass's
    ``solver.dispatch`` spans under ``span``. Where the route tallies them
    (the whole-chain dispatch and the bounded per-goal route, each of which
    counts them in ``solver_healing_rounds_total{grid=}``), the pass's
    move rounds that built the per-slot offline mask
    (``chain._self_healing``): ``healing_rounds`` on the same spans."""
    if not infos:
        return
    from ..utils.sensors import SENSORS
    before = infos[0]["offline_before"]
    remaining = infos[-1]["offline_remaining"]
    SENSORS.count("solver_offline_replicas", before,
                  labels={"when": "before"})
    SENSORS.count("solver_offline_replicas", remaining,
                  labels={"when": "remaining"})
    SENSORS.count("solver_evacuation_rounds", sum(
        info["rounds"] for info in infos if info["offline_before"] > 0))
    healing = None
    if "healing_rounds" in infos[0]:
        healing = sum(info["healing_rounds"] for info in infos)
    for dispatch in _dispatch_spans(span):
        dispatch.set(
            offline_before=before, offline_remaining=remaining,
            excluded_brokers=len(options.excluded_brokers_for_replica_move))
        if healing is not None:
            dispatch.set(healing_rounds=healing)
    if remaining == 0 or all(g.leadership_only for g in goal_chain):
        return
    from ..model.tensors import offline_replicas
    state = final_state()
    held = np.asarray(state.assignment)[np.asarray(offline_replicas(state))]
    stuck = sorted(meta.broker_ids[b] for b in np.unique(held))
    raise OptimizationFailureError(
        f"{remaining} of {before} offline replicas could not be moved off "
        f"dead or removed brokers {stuck}: no eligible destination under "
        "the hard goals")


def record_goal_outcomes(goal_chain: Sequence[Goal], infos: Sequence[dict],
                         meta: ClusterMeta, span=None) -> None:
    """What the search left behind and what its acceptance stack cost,
    counted once a pass from the host scalars the chain's stats already
    fetched: ``solver_goals_violated_after_total{goal=}`` (1 for each goal
    still violated after its turn, what ``goalSummary`` renders as
    ``VIOLATED``; 0 for the others, so that the series exists), and, where
    the route tallies them (the whole-chain dispatch, which keeps no
    flight ring; the bounded route's ring feeds ``solver_flight_killed_*``
    instead), ``solver_round_candidates_total{goal=,stage="valid"|
    "accepted"}``: the move rounds' valid candidates, and those of them
    that every EARLIER goal's acceptance let through. On the pass's
    ``solver.dispatch`` spans under ``span``: ``racks`` (the racks the
    model holds) and ``prior_veto_share`` (vetoed over valid, the pass's
    total)."""
    from ..utils.sensors import SENSORS
    valid = accepted = 0.0
    for goal, info in zip(goal_chain, infos):
        SENSORS.count("solver_goals_violated_after",
                      0.0 if info["succeeded"] else 1.0,
                      labels={"goal": goal.name})
        if "candidates_valid" not in info:
            continue
        for stage in ("valid", "accepted"):
            SENSORS.count("solver_round_candidates",
                          info["candidates_" + stage],
                          labels={"goal": goal.name, "stage": stage})
        valid += info["candidates_valid"]
        accepted += info["candidates_accepted"]
    for dispatch in _dispatch_spans(span):
        dispatch.set(racks=len(meta.rack_names))
        if valid:
            dispatch.set(prior_veto_share=round(1.0 - accepted / valid, 4))


def ensure_only_new_brokers_receive(fetched: FetchedDiff,
                                    infos: Sequence[dict], meta: ClusterMeta,
                                    span=None) -> None:
    """The scale-out's guarantee, checked once a pass beside the drain's
    (``ensure_evacuated``), on the arrays the proposal diff has already
    fetched: one numpy pass, no device read of its own. A pass that ran
    with a NEW broker and whose plan places a replica on a broker that is
    neither NEW nor a replica of that partition before the plan has broken
    what ``POST /add_broker`` documents (``derived.replica_dest_ok`` names
    the source); it raises, and no partial plan is returned. A replica that
    was OFFLINE (its slot sat on a DEAD broker) is exempt, as it is in the
    search (``derived.broker_masks_at``). 0 by construction: this is the
    program's own witness, not a filter.

    Also the pass's scale-out accounting, only when a NEW broker exists:
    ``solver_scale_out_replicas_total{onto="new"|"old"}`` (replicas the
    plan places on NEW brokers / on others, the exempt ones among them),
    ``solver_scale_out_rounds_total`` (the rounds of the goals run with a
    NEW broker present), and ``new_brokers``, ``placed_on_new``,
    ``placed_on_old`` on the pass's ``solver.dispatch`` spans under
    ``span``."""
    new = fetched.broker_state == int(BrokerState.NEW)
    if not new.any():
        return
    from ..utils.sensors import SENSORS
    a0, a1 = fetched.a0, fetched.a1
    placed = (a1 >= 0) & fetched.mask[:, None] \
        & ~(a1[:, :, None] == a0[:, None, :]).any(axis=2)
    on_new = placed & new[np.maximum(a1, 0)]
    on_old = placed & ~on_new
    # a move keeps its slot, so the slot's broker before the plan is the
    # replica's: offline if that broker was DEAD
    was_offline = (a0 >= 0) & (fetched.broker_state[np.maximum(a0, 0)]
                               == int(BrokerState.DEAD))
    breach = on_old & ~was_offline
    n_new, n_old = int(on_new.sum()), int(on_old.sum())
    SENSORS.count("solver_scale_out_replicas", n_new, labels={"onto": "new"})
    SENSORS.count("solver_scale_out_replicas", n_old, labels={"onto": "old"})
    SENSORS.count("solver_scale_out_rounds",
                  sum(info["rounds"] for info in infos))
    for dispatch in _dispatch_spans(span):
        dispatch.set(new_brokers=int(new.sum()), placed_on_new=n_new,
                     placed_on_old=n_old)
    if breach.any():
        onto = sorted(meta.broker_ids[b] for b in np.unique(a1[breach]))
        raise OptimizationFailureError(
            f"{int(breach.sum())} replicas placed on brokers {onto} that "
            "are not new while new brokers exist: add_broker moves "
            "replicas only from the existing brokers onto the new ones")


# Goals whose direct-transport arm stays ahead of greedy even at sparse
# geometry (bench --transport, ROADMAP 2d): TR's [T, B] cell plane keeps
# enough surplus per cell for the fractional plan to pay for itself,
# while Replica/LeaderReplica at the same density solve faster under
# deficit-sized greedy (the documented honest negative — 2 reverts).
_SPARSE_DIRECT_GOALS = ("TopicReplicaDistributionGoal",)


def replica_density(state, num_topics: int) -> float:
    """Replicas per (topic, broker) transport cell — the geometry that
    decides the per-goal direct-vs-greedy choice. The transport plans
    shed/fill whole cells; below ~2 replicas/cell most cells cannot
    donate without emptying, so count goals spend their sweeps on
    stranded movers that greedy would simply route around."""
    cells = max(1, int(num_topics) * int(state.num_brokers))
    slots = int(state.assignment.shape[-1])
    return float(int(state.num_partitions) * slots) / float(cells)


def direct_goal_choice(density: float,
                       threshold: float) -> "tuple[str, ...] | None":
    """Per-goal density-aware path choice (ROADMAP 2d): None = every
    direct-eligible goal keeps the direct arm (dense regime / choice
    disabled); at sparse geometry only ``_SPARSE_DIRECT_GOALS`` keep it
    and the rest take deficit-sized greedy."""
    if threshold <= 0 or density >= threshold:
        return None
    return _SPARSE_DIRECT_GOALS


class GoalOptimizer:
    """Facade over the batched chain search (GoalOptimizer.java:65).

    ``mesh``: a 1-D ``jax.sharding.Mesh`` to run the solver SPMD over
    multiple chips (partition axis sharded, collectives over ICI). Pass
    ``mesh="auto"`` to use all local devices when more than one is present.
    The reference's scale mechanism here is a precompute thread pool
    (GoalOptimizer.java:112-119); the TPU-native one is the mesh."""

    def __init__(self, config: CruiseControlConfig | None = None,
                 mesh=None):
        self._config = config or CruiseControlConfig()
        self._constraint = BalancingConstraint.from_config(self._config)
        self._cand_budget = self._config.get_int("solver.candidates.per.round")
        # An EXPLICITLY configured candidate budget is a hard bound (the
        # operator's memory knob); the default value means "auto-scale with
        # cluster size".
        self._cand_budget_explicit = \
            "solver.candidates.per.round" in self._config.originals()
        self._moves_base = self._config.get_int("solver.moves.per.round")
        self._max_rounds = self._config.get_int("max.solver.rounds")
        self._priority_weight = self._config.get_double("goal.balancedness.priority.weight")
        self._strictness_weight = self._config.get_double("goal.balancedness.strictness.weight")
        self._fused_chain = self._config.get_boolean("solver.chain.fused")
        self._fused_max_brokers = self._config.get_int(
            "solver.fused.chain.max.brokers")
        self._dispatch_rounds = self._config.get_int(
            "solver.dispatch.max.rounds")
        self._dispatch_target_s = self._config.get_double(
            "solver.dispatch.target.seconds")
        self._megastep_donate = self._config.get_boolean(
            "solver.megastep.donate")
        self._async_readback = self._config.get_boolean(
            "solver.dispatch.async.readback")
        self._deficit_moves_cap = self._config.get_int(
            "solver.deficit.moves.cap")
        self._direct_enabled = self._config.get_boolean(
            "solver.direct.assignment.enabled")
        self._direct_max_sweeps = self._config.get_int(
            "solver.direct.max.sweeps")
        self._direct_sparse_margin = self._config.get_double(
            "solver.direct.sparse.margin.frac")
        self._direct_sparse_salt = self._config.get_string(
            "solver.direct.sparse.rounding.salt")
        self._direct_sparse_threshold = self._config.get_double(
            "solver.direct.density.sparse.threshold")
        # Device-sharded megabatch (round 23): shard the CLUSTER axis of
        # fleet solves across the mesh when one is attached.
        self._shard_enabled = self._config.get_boolean(
            "fleet.shard.enabled")
        # Fingerprint goal skipping (round 18): ONE batched stats program
        # snapshots every goal's entry violation before the bounded
        # per-goal loop; goals with nothing to do consume zero dispatches
        # (byte-identical — a violation-free goal applies nothing).
        self._fingerprint_skip = self._config.get_boolean(
            "solver.fingerprint.skip.enabled")
        # Prewarm shape registry (round 18, warmstart.ensure_prewarm):
        # when attached, every solve records its padded tensor signature
        # so a FRESH process can compile the whole per-shape kernel set
        # in a background thread before its first request.
        self._shape_registry = None
        # Adaptive dispatch controllers PERSIST across optimization passes,
        # keyed by MODEL SHAPE: per-round cost is a property of the
        # cluster shape, so the budget learned on one pass carries to the
        # next pass of the SAME shape — the fleet pacer's repeated
        # precomputes skip the relearning ramp — while a fleet-shared
        # optimizer can never apply a big budget learned on a cheap small
        # cluster to a 10x-larger one's first dispatch (watchdog risk).
        # The shape key is the padded bucket shape, so the set stays tiny.
        import threading
        self._controllers: dict = {}
        self._controllers_lock = threading.Lock()
        self._dispatch_stats = None
        self._pass_seq = 0
        # Exact per-caller attribution on a shared optimizer: each pass
        # also records (seq, stats) thread-locally, so a caller whose
        # solve runs synchronously on its own thread (the fleet pacer)
        # can read back THE pass it ran, immune to passes other threads
        # start concurrently or to its request being cache-served.
        self._tls = threading.local()
        if mesh == "auto":
            import jax

            from ..parallel.mesh import make_mesh
            mesh = make_mesh() if len(jax.devices()) > 1 else None
        self._mesh = mesh if (mesh is not None
                              and mesh.devices.size > 1) else None
        self._devices_used = int(self._mesh.devices.size) if self._mesh else 1

    @property
    def mesh(self):
        return self._mesh

    def solver_devices(self) -> int:
        """Device count the LAST optimization pass actually ran on (bench
        reporting — the mesh falls back to single-device when the partition
        axis does not divide it, and reporting the mesh size then would
        corrupt the vs-baseline comparison)."""
        return self._devices_used

    def last_dispatch_stats(self) -> dict:
        """Dispatch accounting of the LAST optimization pass (bench/CI
        surface): dispatch_count, rounds_per_dispatch_p50, donated and
        speculative tallies. Empty dict before any pass. On a fleet-shared
        optimizer this reflects the most recently STARTED pass — callers
        that need per-job attribution (the pacer's precompute job) must
        read it on the solving thread immediately after their own solve
        returns, before another thread can start a pass — and compare
        ``pass_seq()`` across the call to detect that no new pass ran at
        all (a cache-served request must not claim another pass's
        stats)."""
        return self._dispatch_stats.as_dict() if self._dispatch_stats \
            else {}

    def pass_seq(self) -> int:
        """Monotonic count of optimization passes STARTED on this
        optimizer. Pairs with last_dispatch_stats(): a caller whose
        request may be served from a proposal cache snapshots the seq
        before and after — unchanged seq means no solve ran, so the
        current stats belong to some other caller's pass."""
        return self._pass_seq

    def thread_pass_seq(self) -> int:
        """Seq of the last pass run ON THE CALLING THREAD (0 if none).
        Unlike pass_seq() this cannot be advanced by another thread's
        pass, so snapshot-before / compare-after brackets exactly the
        caller's own solves."""
        last = getattr(self._tls, "last_pass", None)
        return last[0] if last else 0

    def thread_dispatch_stats(self) -> dict:
        """Dispatch accounting of the last pass run ON THE CALLING
        THREAD — exact attribution for embedders (the fleet pacer) whose
        solve happens synchronously inside their call, regardless of
        what passes other threads start meanwhile. {} if this thread
        never ran one."""
        last = getattr(self._tls, "last_pass", None)
        return last[1].as_dict() if last else {}

    def _controller_pair(self, state: ClusterTensors, batch: int = 0,
                         devices: int = 1):
        """(narrow, wide) persistent AdaptiveDispatch pair for this model
        shape (created on first use; lock-guarded — facade request
        threads and the fleet worker may solve concurrently).

        ``batch`` > 0 keys a MEGABATCH width into the shape: a batched
        round costs ~occupancy× a single-cluster round on a busy device,
        so the budget learned on solo solves of this shape must not carry
        onto the first 8-wide fleet dispatch (and vice versa) — same
        cost-class discipline as the narrow/wide split.

        ``devices`` keys the mesh size of the SHARDED megabatch (round
        23): a width-64 batch over 4 devices costs a width-16 round per
        step, not a width-64 one, so its budget must not mix with the
        single-device batch=64 controller's (the controller-keying
        contract in DESIGN.md).

        Only the dict lookup is locked: the controllers themselves are
        deliberately unsynchronized. Two same-shape solves running
        concurrently contend for the device, inflate each other's
        observed per-dispatch wall-clock, and can transiently halve the
        shared budget — accepted, because the error is bounded (k never
        leaves [1, max]), self-correcting (k doubles again on the next
        on-target dispatch of a solo pass), and affects only dispatch
        boundaries, never the trajectory. A lock around observe/budget
        would serialize readbacks across solves on the hot path to
        protect a heuristic."""
        from .chain import AdaptiveDispatch
        key = (state.num_partitions, state.num_brokers, batch, devices)
        # ccsa: ok[CCSA007] PR 5 tolerance, machine-readable: registry
        # lookups locked below; the AdaptiveDispatch values are
        # deliberately unsynchronized — bounded (k stays in [1, max]),
        # self-correcting, dispatch-boundary-only (see docstring)
        with self._controllers_lock:
            pair = self._controllers.get(key)
            if pair is None:
                pair = (AdaptiveDispatch(max(1, self._dispatch_rounds),
                                         self._dispatch_target_s),
                        AdaptiveDispatch(max(1, self._dispatch_rounds),
                                         self._dispatch_target_s))
                self._controllers[key] = pair
        return pair

    def _megastep_config(self, num_brokers: int,
                         density: "float | None" = None):
        """Resolve the megastep knobs for one pass. Deficit-aware count-
        goal sizing shares the wide-batch regime gate: below it the fused
        whole-chain kernel is the production path and the bounded drivers
        must walk its exact trajectory (the cross-path parity contract).

        ``density`` (ROADMAP 2d, round 23): the model's replica density —
        below ``solver.direct.density.sparse.threshold`` the per-goal
        path choice keeps the direct arm only for the goals measured
        faster there (see ``direct_goal_choice``). None skips the choice
        (all direct-eligible goals take the direct arm)."""
        from .chain import MegastepConfig
        threshold = self._config.get_int("solver.wide.batch.min.brokers")
        in_regime = threshold > 0 and num_brokers >= threshold
        chosen = None
        if density is not None:
            chosen = direct_goal_choice(density,
                                        self._direct_sparse_threshold)
        return MegastepConfig(
            donate=self._megastep_donate,
            async_readback=self._async_readback,
            deficit_moves_cap=self._deficit_moves_cap if in_regime else 0,
            # Direct-assignment transport shares the wide-regime gate: it
            # REPLACES deficit-sized greedy there; below the gate the
            # greedy path is kept byte-identical (the parity pins).
            direct_assignment=self._direct_enabled and in_regime,
            direct_max_sweeps=self._direct_max_sweeps,
            direct_sparse_margin=self._direct_sparse_margin,
            direct_sparse_salt=self._direct_sparse_salt,
            direct_goals=chosen)

    def deficit_sizing_active(self, num_brokers: int) -> bool:
        """Whether a SERIAL solve of this broker count would run
        deficit-aware count-goal sizing. The megabatch path structurally
        disables it (the grid cannot specialize to one batch member), so
        callers with a choice of path — the facade's fleet-wired
        ``_optimize`` seam — must keep the serial path in this regime or
        silently change solution quality vs a standalone deployment."""
        return self._megastep_config(num_brokers).deficit_moves_cap > 0

    @property
    def constraint(self) -> BalancingConstraint:
        return self._constraint

    def search_config(self, state: ClusterTensors) -> SearchConfig:
        """Scale-aware candidate pruning (replaces round-2's fixed
        num_dests=16, which capped broker-deduped goals at ~16 accepted
        moves per round regardless of cluster size — VERDICT r2 weak #3).

        The grid budget grows with broker count so per-round parallelism
        tracks the cluster: conflict-free selection admits at most
        ~min(num_sources, num_dests, B/2) moves per round for goals whose
        acceptance reads per-broker totals, so num_dests must scale with B
        or round counts scale as O(moves_needed / 16). Wide grids are
        near-free on TPU (one fused kernel); round count is the scarce
        resource."""
        b = state.num_brokers
        budget = self._cand_budget if self._cand_budget_explicit \
            else max(self._cand_budget, min(131_072, b * 64))
        num_dests = max(16, min(512, b // 4))
        if self._cand_budget_explicit:
            # Honor the operator's budget as a bound on the move grid:
            # sources × dests ≤ budget (floors drop to the minimum viable).
            num_dests = min(num_dests, max(4, budget // 16))
            num_sources = max(16, min(1024, budget // num_dests))
        else:
            # Batch width is a QUALITY knob, not just a speed knob
            # (measured at 1k/100k, seed 42): 256 sources → 1,142 rounds,
            # balancedness 86.0; 500 → 644 rounds but 82.7; 1,000 → 341
            # rounds but 74.5. Wider joint batches mean fewer re-scoring
            # points per move, and the coarser layout the early count
            # goals lock in is then defended by their acceptance against
            # the later resource-distribution goals' fixes. Keep the
            # measured-best grid; round count is bought with dispatch
            # amortization (AdaptiveDispatch) instead.
            num_sources = max(64, min(1024, budget // num_dests))
        moves = max(self._moves_base, min(1024, b // 2))
        return SearchConfig(num_sources=num_sources, num_dests=num_dests,
                            moves_per_round=moves,
                            max_rounds=self._max_rounds)

    # -- entry snapshots (round 19: forecast scoring + warm pre-check) -----
    def goal_entry_stats(self, state: ClusterTensors, meta: ClusterMeta,
                         goals: Sequence[Goal] | None = None,
                         options: OptimizationOptions | None = None,
                         ) -> tuple[list[Goal], np.ndarray, np.ndarray, int]:
        """Every goal's entry (violation, objective) plus the offline
        count on ``state`` in ONE batched device program — the round-18
        ``chain_all_goal_stats`` snapshot as a public seam. Two callers:
        the predictive detector scores the forecaster's PROJECTED model
        through it, and the facade's warm-band pre-check scores the warm
        seed against the drifted loads before committing to the full
        chain. Returns (resolved chain, [G] violations, [G] objectives,
        offline replicas)."""
        options = options or OptimizationOptions()
        chain = list(goals) if goals is not None \
            else goals_by_priority(self._config)
        chain = self._resolve_broker_sets(chain, meta)
        masks = self._masks(state, meta, options)
        from .chain import chain_all_goal_stats
        av, ao, aoff = chain_all_goal_stats(
            state, tuple(chain), self._constraint, meta.num_topics, masks)
        return chain, np.asarray(av), np.asarray(ao), int(aoff)

    def balancedness_of(self, chain: Sequence[Goal],
                        violated: "set[str] | Sequence[str]") -> float:
        """The 0..100 balancedness score of a violated-goal set under
        this optimizer's configured weights (the same formula the
        detector and OptimizerResult use)."""
        return balancedness_score(list(chain), set(violated),
                                  self._priority_weight,
                                  self._strictness_weight)

    def _masks(self, state: ClusterTensors, meta: ClusterMeta,
               options: OptimizationOptions) -> ExclusionMasks:
        topic_mask = None
        if options.excluded_topics:
            excluded = set(options.excluded_topics)
            topic_mask = jnp.asarray(np.array(
                [t in excluded for t in meta.topic_names]
                + [False] * (state.num_partitions - len(meta.topic_names)), dtype=bool))
        rm_mask = None
        if options.excluded_brokers_for_replica_move:
            idx = {bid: i for i, bid in enumerate(meta.broker_ids)}
            m = np.zeros(state.num_brokers, dtype=bool)
            for bid in options.excluded_brokers_for_replica_move:
                if bid in idx:
                    m[idx[bid]] = True
            rm_mask = jnp.asarray(m)
        ld_mask = None
        if options.excluded_brokers_for_leadership:
            idx = {bid: i for i, bid in enumerate(meta.broker_ids)}
            m = np.zeros(state.num_brokers, dtype=bool)
            for bid in options.excluded_brokers_for_leadership:
                if bid in idx:
                    m[idx[bid]] = True
            ld_mask = jnp.asarray(m)
        return ExclusionMasks(excluded_topics=topic_mask,
                              excluded_replica_move_brokers=rm_mask,
                              excluded_leadership_brokers=ld_mask)

    def _widen(self, search_cfg: SearchConfig,
               num_brokers: int) -> SearchConfig:
        """The wide-batch grid: sources x solver.wide.batch.source.multiplier
        (default 8), 2x moves — floored at the base config so an
        operator-raised solver.moves.per.round can never make the "wide"
        config narrower than the narrow one. Wide sources are additionally
        capped at the BROKER count: conflict-free selection admits at most
        ~B/2 same-round moves, so width beyond ~B only inflates per-round
        cost (measured: at 1k brokers 2048-wide rounds cost more wall-clock
        than the extra rounds they save; at 7k they cut total rounds 28%
        at identical quality)."""
        mult = self._config.get_int("solver.wide.batch.source.multiplier")
        # The width cap bounds SELECTION size m = max(moves, sources) too;
        # with the O(m log m) segment cumulative (candidates.py) the old
        # m² matmul ceiling no longer binds it — the cap stays a measured
        # quality/throughput constant.
        cap = 2048
        return dataclasses.replace(
            search_cfg,
            num_sources=max(search_cfg.num_sources,
                            min(cap, search_cfg.num_sources * mult,
                                num_brokers)),
            moves_per_round=max(search_cfg.moves_per_round,
                                min(cap, search_cfg.moves_per_round * 2)))

    def _wide_config(self, search_cfg: SearchConfig,
                     goal_chain: Sequence[Goal],
                     num_brokers: int) -> SearchConfig | None:
        """The widened grid for Goal.prefers_wide_batches goals on the
        bounded path, or None when out of regime. Source-limited late-chain
        goals cut their round count ~4x at measured-identical quality
        (TopicReplicaDistribution at 1k/100k: 482 -> 106 rounds, same
        balancedness and violated set; one extra compile of the chain
        kernels at the wide shape)."""
        threshold = self._config.get_int("solver.wide.batch.min.brokers")
        if threshold <= 0 or num_brokers < threshold \
                or not any(g.prefers_wide_batches for g in goal_chain):
            return None
        return self._widen(search_cfg, num_brokers)

    def _resolve_broker_sets(self, goal_chain: list[Goal],
                             meta: ClusterMeta) -> list[Goal]:
        """Bind broker→broker-set ids into any BrokerSetAwareGoal that has
        none: the configured mapping policy
        (replica.to.broker.set.mapping.policy.class, called with
        (config, broker_ids) — BrokerSetResolutionHelper), else the
        brokerSets.json file resolver (broker.set.config.file)."""
        from .goals.broker_set import BrokerSetAwareGoal, broker_sets_from_file
        if not any(isinstance(g, BrokerSetAwareGoal) and not g.broker_sets
                   for g in goal_chain):
            return goal_chain
        sets: tuple[int, ...] | None = None
        policy = self._config.get("replica.to.broker.set.mapping.policy.class")
        if policy:
            cls = resolve_class(policy) if isinstance(policy, str) else policy
            mapper = cls() if isinstance(cls, type) else cls
            sets = tuple(mapper(self._config, list(meta.broker_ids)))
        else:
            import os
            path = self._config.get("broker.set.config.file")
            if path and os.path.exists(path):
                sets = broker_sets_from_file(path, list(meta.broker_ids))
        if sets is None:
            # The operator put BrokerSetAwareGoal in the chain but no
            # mapping resolves — failing loud beats a vacuous constraint
            # (empty sets = one implicit cluster-wide set, which would let
            # replicas cross broker-set boundaries silently).
            raise ValueError(
                "BrokerSetAwareGoal is configured but no broker-set mapping "
                "is available: set replica.to.broker.set.mapping.policy.class "
                f"or point broker.set.config.file at an existing file "
                f"(currently {self._config.get('broker.set.config.file')!r})")
        return [dataclasses.replace(g, broker_sets=sets)
                if isinstance(g, BrokerSetAwareGoal) and not g.broker_sets
                else g for g in goal_chain]

    def optimizations(self, state: ClusterTensors, meta: ClusterMeta,
                      goals: Sequence[Goal] | None = None,
                      options: OptimizationOptions | None = None,
                      initial_state: ClusterTensors | None = None,
                      ) -> tuple[ClusterTensors, OptimizerResult]:
        """Run the goal chain; returns (final_state, OptimizerResult).

        ``initial_state`` (round 18 warm starts): the TRUE current model
        when ``state`` is a warm-seeded search start — the proposal
        diff, stats_before, and the before picture
        (violated_goals_before / balancedness_before, from one batched
        violation snapshot of the true initial — per-goal violations at
        chain start rather than the serial path's at-its-turn reading)
        are computed against it, so results always describe reality,
        never the previous target."""
        from ..utils.flight_recorder import FLIGHT
        from ..utils.progress import step
        from ..utils.tracing import TRACER
        from ..utils.xla_telemetry import shape_scope
        step("OptimizationForGoalChain")
        # seq anticipates the increment inside _optimizations_traced (the
        # one place _pass_seq advances), so the flight record and
        # pass_seq()/thread_pass_seq() agree on the pass's identity.
        with TRACER.span("analyzer.optimize",
                         num_partitions=state.num_partitions,
                         num_brokers=state.num_brokers) as _opt_span, \
                shape_scope(state.num_partitions, state.num_brokers), \
                FLIGHT.pass_scope(
                    seq=self._pass_seq + 1,
                    shape=(state.num_partitions,
                           state.num_brokers)) as flight_pass:
            try:
                return self._optimizations_traced(
                    state, meta, goals, options, _opt_span, flight_pass,
                    t_start=time.time(), initial_state=initial_state)
            except OptimizationFailureError:
                _count_optimization_failure()
                raise

    def _optimizations_traced(self, state: ClusterTensors, meta: ClusterMeta,
                              goals: Sequence[Goal] | None,
                              options: OptimizationOptions | None,
                              _opt_span, flight_pass, t_start: float,
                              initial_state: ClusterTensors | None = None,
                              ) -> tuple[ClusterTensors, OptimizerResult]:
        from ..utils.tracing import TRACER
        options = options or OptimizationOptions()
        goal_chain = list(goals) if goals is not None \
            else goals_by_priority(self._config)
        goal_chain = self._resolve_broker_sets(goal_chain, meta)
        masks = self._masks(state, meta, options)
        search_cfg = self.search_config(state)
        # fast_mode (ParameterUtils FAST_MODE_PARAM): the reference bounds
        # per-broker greedy time (fast.mode.per.broker.move.timeout.ms,
        # ResourceDistributionGoal.java:470-475). The batch-search analogue:
        # every goal runs the WIDE grid (fewer, coarser rounds) and each
        # goal's search wall-clock is capped at timeout_ms x num_brokers on
        # the bounded-dispatch path.
        fast = bool(options.fast_mode)
        if fast:
            search_cfg = self._widen(search_cfg, state.num_brokers)
        fast_budget_s = (self._config.get_long(
            "fast.mode.per.broker.move.timeout.ms") * state.num_brokers
            / 1000.0) if fast else 0.0
        # Warm-seeded solves diff against the TRUE current model: the
        # chain runs from the seeded ``state`` but proposals/stats_before
        # describe moves from reality (facade warm-start contract).
        initial = initial_state if initial_state is not None else state
        stats_before = cluster_stats(initial)
        self._maybe_record_shape(state, meta, goal_chain, masks)

        from .chain import DispatchStats
        stats = DispatchStats()
        self._dispatch_stats = stats
        self._pass_seq += 1
        self._tls.last_pass = (self._pass_seq, stats)
        megastep = self._megastep_config(
            state.num_brokers,
            density=replica_density(state, meta.num_topics))

        mesh = self._mesh
        if mesh is not None and state.num_partitions % mesh.devices.size != 0:
            # Partition axis must divide the mesh (pad via the builder's
            # partition_bucket to avoid this fallback).
            LOG.warning(
                "num_partitions %d not divisible by mesh size %d: falling "
                "back to the single-device solver for this pass",
                state.num_partitions, mesh.devices.size)
            mesh = None
        self._devices_used = int(mesh.devices.size) if mesh is not None else 1
        if mesh is not None:
            # Multi-chip production path: whole chain, one dispatch, SPMD
            # over the mesh (parallel.chain_sharded).
            from ..parallel import optimize_chain_sharded, shard_cluster
            t0 = time.time()
            state = shard_cluster(state, mesh)
            # Same large-cluster dispatch bound as the single-device path:
            # one multi-minute XLA execution trips device-runtime watchdogs.
            bounded = (self._fused_max_brokers > 0
                       and state.num_brokers > self._fused_max_brokers)
            # donate_input stays False: shard_cluster's device_put is a
            # NO-OP (alias, not copy) when the input is already sharded
            # exactly right — e.g. a caller feeding back the sharded
            # state a previous pass returned — and donating an aliased
            # buffer would delete it under ``initial`` and the caller.
            # The first bounded dispatch instead donates a cheap device
            # copy of the two mutable tensors (chain_sharded's
            # can_donate gate), same discipline as the single-device
            # chain_owns_state gate.
            # The persistent per-shape controllers ride along so mesh
            # precomputes skip the budget-relearning ramp too; the wide
            # one bills the deficit-sized count goals' dispatches.
            ctl_pair = self._controller_pair(state) if bounded \
                else (None, None)
            flight_pass.set(path="mesh", bounded=bounded)
            state, infos = optimize_chain_sharded(
                state, goal_chain, self._constraint, search_cfg,
                meta.num_topics, mesh, masks,
                dispatch_rounds=self._dispatch_rounds if bounded else 0,
                dispatch_target_s=self._dispatch_target_s,
                dispatch=ctl_pair[1 if fast else 0],
                dispatch_wide=ctl_pair[1],
                megastep=megastep, stats=stats, donate_input=False,
                flight=flight_pass)
            if not bounded:
                stats.record("chain", sum(i["rounds"] for i in infos),
                             grid="fused")
                flight_pass.record_goal_infos(infos)
            goal_results = _apportioned_goal_results(
                goal_chain, infos, time.time() - t0)
        elif self._fused_chain and not fast and (
                self._fused_max_brokers == 0
                or state.num_brokers <= self._fused_max_brokers):
            # Production path at small/medium scale: the whole chain in ONE
            # device dispatch (chain.chain_optimize_full).
            t0 = time.time()
            flight_pass.set(path="fused")
            state, infos = optimize_chain(
                state, goal_chain, self._constraint, search_cfg,
                meta.num_topics, masks)
            stats.record("chain", sum(i["rounds"] for i in infos),
                         grid="fused")
            flight_pass.record_goal_infos(infos)
            goal_results = _apportioned_goal_results(
                goal_chain, infos, time.time() - t0)
        else:
            # Per-goal bounded-dispatch path: same kernels and trajectory,
            # ≤ solver.dispatch.max.rounds search rounds per XLA execution
            # so no single dispatch runs long enough to trip a device
            # runtime's execution watchdog at 1k+ brokers (also kept for
            # equivalence tests and per-goal wall-clock attribution). Same
            # on-entry violated_before semantics as the fused path.
            dispatch_rounds = self._dispatch_rounds \
                if (self._fused_chain or fast) else 0
            # One adaptive controller across the chain AND across
            # same-shape passes (see __init__): per-round cost is a
            # property of the cluster shape, not the goal, so the budget
            # learned on goal 1 carries to goal 15 — and to the next
            # precompute of this shape.
            ctl_pair = self._controller_pair(state) if dispatch_rounds > 0 \
                else (None, None)
            # Fast mode runs every goal on the WIDENED grid, so its
            # dispatches belong to the wide controller's cost class — the
            # narrow controller's persisted budget would overshoot ~4x on
            # the first wide dispatch (the exact cross-contamination the
            # narrow/wide split exists to prevent).
            controller = ctl_pair[1] if fast else ctl_pair[0]
            # In fast mode search_cfg is already wide for every goal — a
            # second per-goal widening would compile a third grid shape.
            wide_cfg = None if fast else self._wide_config(
                search_cfg, goal_chain, state.num_brokers)
            # Wide rounds cost ~4x a narrow round, so the wide goals get
            # their OWN dispatch controller: a round budget learned on
            # cheap narrow dispatches would overshoot the wall-clock
            # target ~4x on the first wide dispatch (watchdog territory),
            # then depress the narrow goals' budget after the halving.
            # Deficit-sized count goals belong to the same wide cost
            # class: chain.deficit_sized_config can widen their
            # sources/moves past the wide grid even though they run the
            # narrow cfg, so billing them to the narrow controller would
            # recreate exactly that overshoot-then-depress cycle — and
            # persist it across same-shape passes.
            deficit_sizing = megastep.deficit_moves_cap > 0
            flight_pass.set(path="bounded" if dispatch_rounds > 0
                            else "pergoal")
            # Fingerprint goal skipping (round 18): ONE batched stats
            # program snapshots every goal's entry (violation, objective)
            # plus the goal-independent offline count and drain flag.
            # While no goal has mutated the state (chain_owns_state
            # False), each goal's entry stats come from the snapshot —
            # and a goal it shows inactive consumes zero dispatches.
            # After the first mutation the hints are stale and goals
            # dispatch their own entry stats exactly as before.
            hint_viol = hint_obj = None
            hint_off = 0
            hint_drain = None
            if self._fingerprint_skip and not fast:
                from ..warmstart import violation_fingerprint
                from .chain import chain_all_goal_stats
                av, ao, aoff = chain_all_goal_stats(
                    state, tuple(goal_chain), self._constraint,
                    meta.num_topics, masks)
                hint_viol = np.asarray(av)
                hint_obj = np.asarray(ao)
                hint_off = int(aoff)
                hint_drain = False
                if masks.excluded_replica_move_brokers is not None:
                    from .chain import excluded_hosting_replicas
                    hint_drain = bool(excluded_hosting_replicas(
                        state,
                        masks.excluded_replica_move_brokers).any())
                stats.fingerprint = violation_fingerprint(hint_viol)
            goal_results = []
            infos = []
            # Donation gate for the chain's FIRST mutating dispatch: until
            # some goal has actually run a dispatch, the threaded state is
            # still the caller's buffers (``initial`` feeds the proposal
            # diff) and must not be donated; afterwards every input is a
            # chain-owned intermediate.
            chain_owns_state = False
            for i, g in enumerate(goal_chain):
                t0 = time.time()
                use_wide = wide_cfg is not None and g.prefers_wide_batches
                cfg_used = wide_cfg if use_wide else search_cfg
                wide_class = use_wide or (deficit_sizing and g.count_based)
                entry = None
                if hint_viol is not None and not chain_owns_state:
                    entry = (float(hint_viol[i]), float(hint_obj[i]),
                             hint_off)
                with TRACER.span("goal.solve", goal=g.name,
                                 candidates=cfg_used.num_sources
                                 * cfg_used.num_dests) as gsp:
                    state, info = optimize_goal_in_chain(
                        state, goal_chain, i, self._constraint,
                        cfg_used, meta.num_topics, masks,
                        dispatch_rounds=dispatch_rounds,
                        dispatch=ctl_pair[1] if wide_class else controller,
                        wall_budget_s=fast_budget_s,
                        megastep=megastep, stats=stats,
                        donate_input=chain_owns_state,
                        flight=flight_pass.goal(g.name),
                        entry_stats=entry,
                        drain_hint=hint_drain if entry is not None
                        else None,
                        grid="wide" if use_wide or fast else "narrow")
                    chain_owns_state |= info["rounds"] > 0 \
                        or info.get("direct_sweeps", 0) > 0
                    gsp.set(rounds=info["rounds"],
                            moves_applied=info["moves_applied"],
                            succeeded=info["succeeded"])
                infos.append(info)
                goal_results.append(GoalResult(
                    name=g.name, is_hard=g.is_hard,
                    succeeded=info["succeeded"],
                    rounds=info["rounds"], moves_applied=info["moves_applied"],
                    residual_violation=info["residual_violation"],
                    duration_s=time.time() - t0,
                    violated_before=info["violated_on_entry"]
                    or not info["succeeded"],
                    swaps_applied=info.get("swaps_applied", 0)))

        ensure_evacuated(goal_chain, infos, lambda: state, meta, options,
                         _opt_span)
        record_goal_outcomes(goal_chain, infos, meta, _opt_span)
        if stats.goals_skipped:
            from ..utils.sensors import SENSORS as _S
            _S.count("solver_goals_skipped", stats.goals_skipped)
        if initial_state is not None:
            # Warm-seeded solve: the per-goal entry stats describe the
            # SEEDED search start, but the user-facing "before" picture
            # (violated_goals_before, balancedness_before) must describe
            # reality — one batched snapshot on the true initial.
            from .chain import chain_all_violations
            av0 = np.asarray(chain_all_violations(
                initial, tuple(goal_chain), self._constraint,
                meta.num_topics, masks))
            violated_before = [g.name for g, v in zip(goal_chain, av0)
                               if float(v) > 1e-6]
        else:
            violated_before = [r.name for r in goal_results
                               if r.violated_before]
        violated_after = [r.name for r in goal_results if not r.succeeded]
        with TRACER.span("analyzer.proposal_diff") as dsp:
            with TRACER.span("diff.stats"):
                stats_after = cluster_stats(state)
            fetched = fetch_diff(initial, state)
            ensure_only_new_brokers_receive(fetched, infos, meta, _opt_span)
            proposals = compare_diff(fetched, meta)
            dsp.set(num_proposals=len(proposals))
        _opt_span.set(num_proposals=len(proposals),
                      violated_goals_after=",".join(violated_after),
                      devices=self.solver_devices())
        # proposal-computation-timer + per-pass gauges
        # (GoalOptimizer.java:128, Sensors.md).
        from ..utils.sensors import SENSORS
        SENSORS.record_timer("analyzer_proposal_computation",
                             time.time() - t_start)
        SENSORS.gauge("analyzer_num_proposals", len(proposals))
        SENSORS.gauge("analyzer_violated_goals_after", len(violated_after))
        SENSORS.gauge("analyzer_solver_devices", self.solver_devices())
        result = OptimizerResult(
            proposals=proposals, goal_results=goal_results,
            stats_before=stats_before, stats_after=stats_after,
            violated_goals_before=violated_before,
            violated_goals_after=violated_after,
            balancedness_before=balancedness_score(
                goal_chain, set(violated_before), self._priority_weight,
                self._strictness_weight),
            balancedness_after=balancedness_score(
                goal_chain, set(violated_after), self._priority_weight,
                self._strictness_weight),
            duration_s=time.time() - t_start,
        )
        return state, result

    # -- megabatch: whole buckets of clusters in one device program --------
    def megabatch_chain(self, meta: ClusterMeta,
                        goals: Sequence[Goal] | None = None) -> tuple:
        """The resolved goal chain a megabatch slot would run — the
        grouping key component the fleet assembler compares: clusters may
        share one compiled batched program only when their resolved
        chains are identical (broker-set bindings included)."""
        goal_chain = list(goals) if goals is not None \
            else goals_by_priority(self._config)
        return tuple(self._resolve_broker_sets(goal_chain, meta))

    def optimizations_megabatch(self, items: Sequence[tuple],
                                goals: Sequence[Goal] | None = None,
                                options: OptimizationOptions | None = None,
                                width: int = 0,
                                ) -> list:
        """Solve MANY same-bucket clusters in one batched device program
        (ROADMAP item 3): every model in ``items`` — a sequence of
        ``(state, meta, cluster_id)`` or ``(state, meta, cluster_id,
        options)`` — is stacked along a leading cluster axis and the
        whole goal chain runs through the batched megastep drivers
        (chain.optimize_goal_in_chain_megabatch), so the fleet pays
        max-over-clusters rounds instead of the serial sum and ONE
        compiled program per bucket shape serves any occupancy.

        PER-ITEM options (the 4-tuple form, round 15) carry each
        cluster's own exclusion set — the fix path's recently-removed
        brokers, a future's drained brokers — into per-cluster exclusion
        MASKS stacked along the cluster axis. Mask presence is
        normalized across the batch: when any item excludes along a
        field, items without exclusions get an all-False mask (inert:
        it filters nothing), so mixed batches share one compiled mask
        layout instead of splitting into per-presence programs.

        Preconditions (the fleet assembler's grouping contract — violated
        ones raise ValueError before any device work): identical padded
        bucket shape including the replica-slot axis, identical
        ``num_topics``, an identical resolved goal chain, and no fast
        mode. ``width`` > len(items) pads the batch with inert
        zero-weight cluster slots (all-dead brokers, fully masked
        partitions) so one compiled program per bucket shape serves any
        occupancy.

        Deficit-aware count-goal sizing is forced OFF: it specializes the
        search grid to one cluster's entry violation, which cannot be
        shared across a batch. Controllers are the persistent per-shape
        pair keyed WITH the batch width (see _controller_pair).

        Returns a list aligned with ``items``: ``(final_state,
        OptimizerResult)`` per cluster, or the per-cluster Exception a
        serial solve would have raised (hard-goal failure / stats
        regression) — one cluster's failure never aborts its batchmates.
        """
        import contextlib

        import jax

        from .chain import (
            DispatchStats, inert_state_like, optimize_goal_in_chain_megabatch,
            stack_states, unstack_state,
        )
        from ..utils.flight_recorder import FLIGHT, NO_FLIGHT
        from ..utils.sensors import SENSORS, cluster_label
        from ..utils.tracing import TRACER
        from ..utils.xla_telemetry import shape_scope

        if not items:
            return []
        options = options or OptimizationOptions()
        n = len(items)
        states = [it[0] for it in items]
        metas = [it[1] for it in items]
        cluster_ids = [it[2] if len(it) > 2 else None for it in items]
        opts_list = [it[3] if len(it) > 3 and it[3] is not None else options
                     for it in items]
        # Optional per-item TRUE initial state (5th element, round 18
        # warm starts): the chain solves from the seeded ``state`` but
        # each cluster's proposal diff / stats_before / before-picture
        # use reality.
        warm_seeded = [len(it) > 4 and it[4] is not None for it in items]
        true_initials = [it[4] if w else it[0]
                         for w, it in zip(warm_seeded, items)]
        if any(o.fast_mode for o in opts_list):
            raise ValueError("megabatch does not support fast_mode")
        shape0 = jax.tree.map(lambda x: x.shape, states[0])
        for st in states[1:]:
            if jax.tree.map(lambda x: x.shape, st) != shape0:
                raise ValueError("megabatch models must share one padded "
                                 "bucket shape")
        num_topics = metas[0].num_topics
        if any(m.num_topics != num_topics for m in metas):
            raise ValueError("megabatch models must share num_topics")
        chain0 = self.megabatch_chain(metas[0], goals)
        for m in metas[1:]:
            if self.megabatch_chain(m, goals) != chain0:
                raise ValueError("megabatch models must share one resolved "
                                 "goal chain")
        goal_chain = list(chain0)

        masks_list = self._uniform_mask_presence(
            [self._masks(st, m, o)
             for st, m, o in zip(states, metas, opts_list)])

        # Device-sharded megabatch (round 23): with a mesh attached (and
        # fleet.shard.enabled) the CLUSTER axis shards across it —
        # c/ndev slots per device, batch width padded to a device
        # multiple with the same inert slots that pad occupancy (the
        # fleet/bucketing.py append-only geometry: pow2 steps of the
        # device count, so the compiled-shape set stays bounded).
        mesh = self._mesh if self._shard_enabled else None
        ndev = int(mesh.devices.size) if mesh is not None else 1
        c = max(n, int(width) or n)
        if mesh is not None:
            from ..fleet.bucketing import geometric_round_up
            c = geometric_round_up(c, ndev, 2.0)
        pad = c - n
        if pad:
            inert = inert_state_like(states[0])
            states = states + [inert] * pad
            # Pad slots need mask rows too (the stacked mask axis must
            # match the cluster axis): all-False masks matching the real
            # clusters' presence pattern — an inert slot excludes
            # nothing, and it generates no candidates anyway.
            import jax.numpy as jnp
            pad_masks = ExclusionMasks(*(
                None if f is None else jnp.zeros_like(f)
                for f in (masks_list[0].excluded_topics,
                          masks_list[0].excluded_replica_move_brokers,
                          masks_list[0].excluded_leadership_brokers)))
            masks_list = masks_list + [pad_masks] * pad
        batched_masks = self._stack_masks(masks_list)
        cluster_mask = np.concatenate([np.ones(n, dtype=bool),
                                       np.zeros(pad, dtype=bool)])

        state0 = items[0][0]
        search_cfg = self.search_config(state0)
        megastep = dataclasses.replace(
            self._megastep_config(
                state0.num_brokers,
                density=replica_density(state0, num_topics)),
            deficit_moves_cap=0)
        dispatch_rounds = max(1, self._dispatch_rounds)
        ctl_pair = self._controller_pair(state0, batch=c, devices=ndev)
        wide_cfg = self._wide_config(search_cfg, goal_chain,
                                     state0.num_brokers)

        physical = DispatchStats()
        per_cluster_stats = [DispatchStats() for _ in range(c)]
        self._dispatch_stats = physical
        t_start = time.time()

        batched = stack_states(states)
        if mesh is not None:
            from ..parallel.megabatch_sharded import (
                shard_megabatch, shard_megabatch_masks,
            )
            batched = shard_megabatch(batched, mesh)
            batched_masks = shard_megabatch_masks(batched_masks, mesh)
        self._devices_used = ndev
        initial_states = true_initials
        stats_before = [cluster_stats(st) for st in initial_states]
        self._maybe_record_shape(states[0], metas[0], goal_chain,
                                 masks_list[0], batch=c)

        # Fingerprint goal skipping, batched (round 18): one [C, G]
        # snapshot for the whole chain; a goal it shows inactive for
        # EVERY cluster consumes zero batched dispatches. Hints go stale
        # at the first mutation (chain_owns_state), like the serial path.
        hint = None
        hint_drain = None
        if self._fingerprint_skip:
            from ..warmstart import violation_fingerprint
            from .chain import (
                excluded_hosting_replicas, megabatch_all_goal_stats,
            )
            if mesh is not None:
                from ..parallel.megabatch_sharded import (
                    megabatch_all_goal_stats_sharded,
                )
                av, ao, aoff = megabatch_all_goal_stats_sharded(
                    mesh, batched, tuple(goal_chain), self._constraint,
                    num_topics, batched_masks)
            else:
                av, ao, aoff = megabatch_all_goal_stats(
                    batched, tuple(goal_chain), self._constraint,
                    num_topics, batched_masks)
            hint = (np.asarray(av), np.asarray(ao), np.asarray(aoff))
            if batched_masks.excluded_replica_move_brokers is not None:
                hint_drain = np.asarray(jax.vmap(excluded_hosting_replicas)(
                    batched,
                    batched_masks.excluded_replica_move_brokers,
                ).any(axis=(1, 2)))
            else:
                hint_drain = np.zeros(c, dtype=bool)
            physical.fingerprint = violation_fingerprint(hint[0])

        results_per_goal: list[list[dict]] = []
        durations: list[float] = []
        dead = np.zeros(c, dtype=bool)
        errors: list[Exception | None] = [None] * c
        with contextlib.ExitStack() as scopes:
            flight_passes = []
            for b in range(c):
                if not cluster_mask[b]:
                    flight_passes.append(None)
                    continue
                self._pass_seq += 1
                fp = FLIGHT.pass_scope(
                    seq=self._pass_seq,
                    shape=(state0.num_partitions, state0.num_brokers),
                    cluster=cluster_ids[b])
                scopes.enter_context(fp)
                fp.set(path="megabatch", occupancy=n, batch_width=c)
                flight_passes.append(fp)
            self._tls.last_pass = (self._pass_seq, physical)
            with TRACER.span("analyzer.megabatch", occupancy=n,
                             batch_width=c,
                             num_partitions=state0.num_partitions,
                             num_brokers=state0.num_brokers) as sp, \
                    shape_scope(state0.num_partitions, state0.num_brokers):
                chain_owns_state = False
                for i, g in enumerate(goal_chain):
                    t0 = time.time()
                    use_wide = wide_cfg is not None and g.prefers_wide_batches
                    cfg_used = wide_cfg if use_wide else search_cfg
                    flights = [
                        flight_passes[b].goal(g.name)
                        if flight_passes[b] is not None else NO_FLIGHT
                        for b in range(c)]
                    entry = None
                    if hint is not None and not chain_owns_state:
                        entry = (hint[0][:, i], hint[1][:, i], hint[2])
                    batched, infos = optimize_goal_in_chain_megabatch(
                        batched, goal_chain, i, self._constraint, cfg_used,
                        num_topics, batched_masks, cluster_mask & ~dead,
                        dispatch_rounds=dispatch_rounds,
                        dispatch=ctl_pair[1 if use_wide else 0],
                        megastep=megastep, stats=per_cluster_stats,
                        physical_stats=physical, flights=flights,
                        donate_input=chain_owns_state,
                        entry_stats=entry,
                        drain_hint=hint_drain if entry is not None
                        else None, mesh=mesh)
                    chain_owns_state |= any(
                        info["rounds"] > 0 or info.get("direct_sweeps", 0) > 0
                        for info in infos)
                    durations.append(time.time() - t0)
                    results_per_goal.append(infos)
                    for b, info in enumerate(infos):
                        if cluster_mask[b] and not dead[b] \
                                and "error" in info:
                            # The serial solve would raise HERE and leave
                            # the cluster at exactly this state; freezing
                            # it for the rest of the chain preserves that.
                            errors[b] = self._megabatch_error(info)
                            dead[b] = True
                for b in range(n):
                    if dead[b]:
                        continue
                    try:
                        ensure_evacuated(
                            goal_chain, [infos[b] for infos
                                         in results_per_goal],
                            lambda: unstack_state(batched, b), metas[b],
                            opts_list[b])
                    except OptimizationFailureError as e:
                        errors[b] = e
                        dead[b] = True
                for e in errors:
                    if isinstance(e, OptimizationFailureError):
                        _count_optimization_failure()
                sp.set(dispatches=physical.dispatch_count,
                       errors=int(dead[cluster_mask].sum()))
            if physical.goals_skipped:
                SENSORS.count("solver_goals_skipped",
                              physical.goals_skipped)

        # Warm-path before picture, ONE batched snapshot for every
        # warm-seeded member (a per-cluster host loop of
        # chain_all_violations would pay one dispatch + readback per
        # cluster, eroding exactly the dispatch savings warm starts
        # buy).
        warm_violated_before: dict[int, list] = {}
        warm_rows = [b for b in range(n) if warm_seeded[b]
                     and errors[b] is None]
        if warm_rows:
            from .chain import megabatch_all_goal_stats, stack_states
            init_batch = stack_states([initial_states[b]
                                       for b in warm_rows])
            init_masks = self._stack_masks([masks_list[b]
                                            for b in warm_rows])
            av, _ao, _aoff = megabatch_all_goal_stats(
                init_batch, tuple(goal_chain), self._constraint,
                num_topics, init_masks)
            av = np.asarray(av)
            for i, b in enumerate(warm_rows):
                warm_violated_before[b] = [
                    g.name for g, v in zip(goal_chain, av[i])
                    if float(v) > 1e-6]

        out: list = []
        for b in range(n):
            cid = cluster_ids[b]
            if errors[b] is not None:
                out.append(errors[b])
                continue
            final = unstack_state(batched, b)
            goal_results = [GoalResult(
                name=g.name, is_hard=g.is_hard,
                succeeded=results_per_goal[i][b]["succeeded"],
                rounds=results_per_goal[i][b]["rounds"],
                moves_applied=results_per_goal[i][b]["moves_applied"],
                residual_violation=results_per_goal[i][b][
                    "residual_violation"],
                duration_s=durations[i],
                violated_before=results_per_goal[i][b]["violated_on_entry"]
                or not results_per_goal[i][b]["succeeded"],
                swaps_applied=results_per_goal[i][b]["swaps_applied"])
                for i, g in enumerate(goal_chain)
                if i < len(results_per_goal)]
            if b in warm_violated_before:
                # Reality-first "before" picture, from the one batched
                # snapshot above (same semantics as the serial warm
                # path).
                violated_before = warm_violated_before[b]
            else:
                violated_before = [r.name for r in goal_results
                                   if r.violated_before]
            violated_after = [r.name for r in goal_results
                              if not r.succeeded]
            with cluster_label(cid) if cid is not None \
                    else contextlib.nullcontext():
                fetched = fetch_diff(initial_states[b], final)
                try:
                    ensure_only_new_brokers_receive(
                        fetched, [infos[b] for infos in results_per_goal],
                        metas[b])
                except OptimizationFailureError as e:
                    _count_optimization_failure()
                    out.append(e)
                    continue
                proposals = compare_diff(fetched, metas[b])
                result = OptimizerResult(
                    proposals=proposals, goal_results=goal_results,
                    stats_before=stats_before[b],
                    stats_after=cluster_stats(final),
                    violated_goals_before=violated_before,
                    violated_goals_after=violated_after,
                    balancedness_before=balancedness_score(
                        goal_chain, set(violated_before),
                        self._priority_weight, self._strictness_weight),
                    balancedness_after=balancedness_score(
                        goal_chain, set(violated_after),
                        self._priority_weight, self._strictness_weight),
                    duration_s=time.time() - t_start)
                SENSORS.record_timer("analyzer_proposal_computation",
                                     time.time() - t_start)
                SENSORS.gauge("analyzer_num_proposals", len(proposals))
                SENSORS.gauge("analyzer_violated_goals_after",
                              len(violated_after))
            out.append((final, result))
        self._megabatch_cluster_stats = {
            cluster_ids[b] or b: per_cluster_stats[b].as_dict()
            for b in range(n)}
        SENSORS.observe("solver_megabatch_occupancy", float(n),
                        buckets=(1, 2, 4, 8, 16, 32, 64))
        SENSORS.gauge("solver_megabatch_width", float(c))
        return out

    def last_megabatch_cluster_stats(self) -> dict:
        """Per-cluster dispatch accounting of the LAST megabatch pass,
        split out of the batched readback (cluster id -> DispatchStats
        dict). The fleet runner reads it to report
        fleet_precompute_dispatches{cluster=} exactly."""
        return dict(getattr(self, "_megabatch_cluster_stats", {}))

    # -- prewarm (round 18, warmstart.py) ----------------------------------
    def attach_shape_registry(self, registry) -> None:
        """warmstart.ensure_prewarm's recording seam: every solve after
        this records its padded tensor signature, so a FRESH process can
        compile the whole per-shape kernel set before its first request."""
        self._shape_registry = registry

    def _maybe_record_shape(self, state, meta, goal_chain, masks,
                            batch: int = 0) -> None:
        reg = self._shape_registry
        if reg is None:
            return
        try:
            from ..warmstart import shape_signature
            sig = shape_signature(state, meta.num_topics, goal_chain,
                                  masks, batch=batch)
            if sig is not None:
                reg.record(sig)
        except Exception:  # noqa: BLE001 — recording must never break a solve
            LOG.debug("prewarm shape recording failed", exc_info=True)

    def prewarm_shape(self, entry: dict) -> bool:
        """Warm the solver-program set for ONE recorded shape signature by
        EXECUTING the production chain kernels on an inert synthetic model
        of that shape (zero-round budgets, all-dead brokers: every kernel
        compiles fully but does no search work). In-process this fills the
        jit dispatch caches the first real solve will hit; with the
        persistent compile cache enabled the XLA backend artifacts also
        land on disk, so the NEXT restart retrieves instead of compiling.
        Returns False when the entry is not reproducible here (unknown
        goal spec, or a mesh shape mismatch) — never raises for
        a merely mismatched entry; kernel failures propagate to the
        prewarm manager, which records and continues. Bound-state goal
        chains (e.g. broker-set mappings) rebuild from their signature
        specs, and mesh-sharded optimizers warm the sharded chain
        programs (_prewarm_shape_sharded) — both round-18 gaps closed in
        round 20; round 23 extends the mesh path to megabatch entries
        (the sharded megabatch chain)."""
        import jax
        from ..utils.flight_recorder import FLIGHT
        from ..warmstart import synthetic_masks, synthetic_state
        from .chain import (
            chain_all_goal_stats, chain_goal_stats, chain_optimize_full,
            chain_optimize_rounds, chain_optimize_rounds_donated,
            chain_swap_rounds, chain_swap_rounds_donated, donation_enabled,
            megabatch_all_goal_stats, megabatch_goal_stats,
            megabatch_optimize_rounds, megabatch_optimize_rounds_donated,
            megabatch_swap_rounds, megabatch_swap_rounds_donated,
            stack_states, start_pass, strip_mutable,
        )
        from .goals import ALL_GOALS
        names = entry.get("goals") or []
        if not names:
            return False
        try:
            from ..warmstart import goal_from_spec
            goals = tuple(goal_from_spec(s, ALL_GOALS) for s in names)
        except Exception:  # noqa: BLE001 — unknown/irreproducible spec
            return False
        if self._mesh is not None:
            return self._prewarm_shape_sharded(entry, goals)
        state = synthetic_state(entry)
        masks = synthetic_masks(entry)
        num_topics = int(entry["num_topics"])
        batch = int(entry.get("batch") or 0)
        constraint = self._constraint
        cfg = self.search_config(state)
        megastep = self._megastep_config(state.num_brokers)
        donate = donation_enabled(megastep)
        ring_n = FLIGHT.ring_rounds if FLIGHT.enabled else 0
        wide_cfg = self._wide_config(cfg, goals, state.num_brokers)
        idx = jnp.int32(0)
        prior = jnp.asarray([False] * len(goals))
        zero = jnp.int32(0)

        def wait(out):
            jax.tree.map(lambda x: x.block_until_ready()
                         if hasattr(x, "block_until_ready") else x, out)

        if batch > 0:
            batched = stack_states([state] * batch)
            bmasks = ExclusionMasks(*(
                None if f is None else jnp.stack([f] * batch)
                for f in (masks.excluded_topics,
                          masks.excluded_replica_move_brokers,
                          masks.excluded_leadership_brokers)))
            active = jnp.zeros((batch,), bool)
            if self._fingerprint_skip:
                wait(megabatch_all_goal_stats(batched, goals, constraint,
                                              num_topics, bmasks))
            wait(megabatch_goal_stats(batched, idx, goals, constraint,
                                      num_topics, bmasks))
            for c in [cfg] + ([wide_cfg] if wide_cfg else []):
                if donate:
                    rest = dataclasses.replace(
                        batched,
                        assignment=jnp.zeros(
                            (batch, 0, batched.assignment.shape[2]),
                            batched.assignment.dtype),
                        leader_slot=jnp.zeros((batch, 0),
                                              batched.leader_slot.dtype))
                    wait(megabatch_optimize_rounds_donated(
                        jnp.copy(batched.assignment),
                        jnp.copy(batched.leader_slot), rest, active, idx,
                        prior, goals, constraint, c, num_topics, bmasks,
                        zero, ring_rounds=ring_n))
                else:
                    wait(megabatch_optimize_rounds(
                        batched, active, idx, prior, goals, constraint, c,
                        num_topics, bmasks, zero, ring_rounds=ring_n))
            if donate:
                rest = dataclasses.replace(
                    batched,
                    assignment=jnp.zeros(
                        (batch, 0, batched.assignment.shape[2]),
                        batched.assignment.dtype),
                    leader_slot=jnp.zeros((batch, 0),
                                          batched.leader_slot.dtype))
                wait(megabatch_swap_rounds_donated(
                    jnp.copy(batched.assignment),
                    jnp.copy(batched.leader_slot), rest, active, idx,
                    prior, goals, constraint, num_topics, bmasks, 8, 64,
                    zero))
            else:
                wait(megabatch_swap_rounds(batched, active, idx, prior,
                                           goals, constraint, num_topics,
                                           bmasks, 8, 64, zero))
            return True

        fused = self._fused_chain and (
            self._fused_max_brokers == 0
            or state.num_brokers <= self._fused_max_brokers)
        if fused:
            # The production path at this scale is the ONE whole-chain
            # program — the 46-63 s warmup compile of BENCH r02/r03.
            wait(chain_optimize_full(state, goals, constraint, cfg,
                                     num_topics, masks))
            return True
        # Mirror _optimizations_traced's per-goal dispatch selection
        # exactly: with the fused chain configured, oversized clusters
        # run BOUNDED dispatches (traced budget arg); with it off, the
        # per-goal drivers run unbounded (no budget arg — a different
        # trace, so a prewarm of the wrong variant would warm nothing).
        bounded = self._fused_chain and self._dispatch_rounds > 0
        if self._fingerprint_skip:
            wait(chain_all_goal_stats(state, goals, constraint, num_topics,
                                      masks))
        wait(chain_goal_stats(state, idx, goals, constraint, num_topics,
                              masks))
        for c in [cfg] + ([wide_cfg] if wide_cfg else []):
            if donate and bounded:
                wait(chain_optimize_rounds_donated(
                    jnp.copy(state.assignment), jnp.copy(state.leader_slot),
                    start_pass(state, num_topics), strip_mutable(state), idx,
                    prior, goals, constraint, c, num_topics, masks, zero,
                    ring_rounds=ring_n))
            elif bounded:
                wait(chain_optimize_rounds(state, idx, prior, goals,
                                           constraint, c, num_topics, masks,
                                           budget=zero, ring_rounds=ring_n,
                                           resume=start_pass(state,
                                                             num_topics)))
            else:
                wait(chain_optimize_rounds(state, idx, prior, goals,
                                           constraint, c, num_topics, masks,
                                           ring_rounds=ring_n))
        if donate and bounded:
            wait(chain_swap_rounds_donated(
                jnp.copy(state.assignment), jnp.copy(state.leader_slot),
                start_pass(state, num_topics), strip_mutable(state), idx,
                prior, goals, constraint, num_topics, masks, 8, 64, zero))
        elif bounded:
            wait(chain_swap_rounds(state, idx, prior, goals, constraint,
                                   num_topics, masks, budget=zero,
                                   resume=start_pass(state, num_topics)))
        else:
            wait(chain_swap_rounds(state, idx, prior, goals, constraint,
                                   num_topics, masks))
        return True

    def _prewarm_shape_sharded(self, entry: dict, goals: tuple) -> bool:
        """Mesh analogue of ``prewarm_shape`` (the round-18 documented
        gap): compile the sharded chain programs THIS process would run
        for the entry's shape by executing them on an inert sharded
        synthetic model — the whole-chain ``_make_chain_full`` program at
        fused scale, the per-goal phase kernels (donated or plain,
        matching the megastep donation mode) past fused.max.brokers,
        mirroring ``_optimize``'s mesh-branch selection exactly.
        Megabatch entries (batch > 0) warm the SHARDED megabatch chain
        (round 23, closing the round-20 "single-device machinery" gap):
        the stacked synthetic batch is placed cluster-axis-sharded and
        the shard_map stats/move/swap twins run at zero budget — the
        batch must be a device multiple (optimizations_megabatch pads
        real batches to one, so recorded signatures already are).
        Non-batch shapes whose partition axis does not divide the mesh
        stay unreproducible (the _optimize fallback would run them
        single-device anyway). Deficit-sized wide kernels still compile
        lazily at their pow2-quantized widths — sizing depends on live
        violation counts no signature can know."""
        import jax

        from ..parallel import shard_cluster
        from ..parallel.chain_sharded import (
            _make_chain_full, _make_chain_phase_kernels,
        )
        from ..warmstart import synthetic_masks, synthetic_state
        from .chain import donation_enabled, strip_mutable
        mesh = self._mesh
        state = synthetic_state(entry)
        masks = synthetic_masks(entry)
        num_topics = int(entry["num_topics"])
        cfg = self.search_config(state)
        batch = int(entry.get("batch") or 0)
        if batch > 0:
            if not self._shard_enabled or batch % mesh.devices.size != 0:
                return False
            return self._prewarm_megabatch_sharded(
                entry, goals, state, masks, num_topics, batch, cfg)
        if state.num_partitions % mesh.devices.size != 0:
            return False
        presence = (masks.excluded_topics is not None,
                    masks.excluded_replica_move_brokers is not None,
                    masks.excluded_leadership_brokers is not None)

        def wait(out):
            jax.tree.map(lambda x: x.block_until_ready()
                         if hasattr(x, "block_until_ready") else x, out)

        sharded = shard_cluster(state, mesh)
        bounded = (self._fused_max_brokers > 0
                   and state.num_brokers > self._fused_max_brokers)
        if not bounded:
            fn = _make_chain_full(mesh, goals, self._constraint, cfg,
                                  num_topics, presence, 8, 64)
            wait(fn(sharded, masks))
            return True
        megastep = self._megastep_config(state.num_brokers)
        donate = donation_enabled(megastep)
        move, swap, stats, move_d, swap_d = _make_chain_phase_kernels(
            mesh, goals, self._constraint, cfg, num_topics, presence,
            8, 64)
        idx = jnp.int32(0)
        prior = jnp.asarray([False] * len(goals))
        zero = jnp.int32(0)
        wait(stats(sharded, masks, idx))
        if donate:
            a, ls, *_ = move_d(jnp.copy(sharded.assignment),
                               jnp.copy(sharded.leader_slot),
                               strip_mutable(sharded), masks, idx, prior,
                               zero)
            wait((a, ls))
            a, ls, *_ = swap_d(jnp.copy(sharded.assignment),
                               jnp.copy(sharded.leader_slot),
                               strip_mutable(sharded), masks, idx, prior,
                               zero)
            wait((a, ls))
        else:
            wait(move(sharded, masks, idx, prior, zero))
            wait(swap(sharded, masks, idx, prior, zero))
        return True

    def _prewarm_megabatch_sharded(self, entry: dict, goals: tuple,
                                   state, masks, num_topics: int,
                                   batch: int, cfg) -> bool:
        """Warm the device-sharded megabatch kernel set for one recorded
        (shape, batch) signature: the mirror of ``prewarm_shape``'s
        batch > 0 block with every kernel routed through its shard_map
        twin, so a fresh fleet replica's first batched solve hits warm
        dispatch caches (and, with the persistent compile cache, warm
        XLA artifacts) at the mesh size it will actually run."""
        import jax

        from ..parallel.megabatch_sharded import (
            megabatch_all_goal_stats_sharded, megabatch_goal_stats_sharded,
            megabatch_optimize_rounds_donated_sharded,
            megabatch_optimize_rounds_sharded,
            megabatch_swap_rounds_donated_sharded,
            megabatch_swap_rounds_sharded, shard_megabatch,
            shard_megabatch_masks,
        )
        from ..utils.flight_recorder import FLIGHT
        from .chain import donation_enabled, stack_states
        mesh = self._mesh
        constraint = self._constraint
        megastep = self._megastep_config(state.num_brokers)
        donate = donation_enabled(megastep)
        ring_n = FLIGHT.ring_rounds if FLIGHT.enabled else 0
        wide_cfg = self._wide_config(cfg, goals, state.num_brokers)
        idx = jnp.int32(0)
        prior = jnp.asarray([False] * len(goals))
        zero = jnp.int32(0)

        def wait(out):
            jax.tree.map(lambda x: x.block_until_ready()
                         if hasattr(x, "block_until_ready") else x, out)

        batched = shard_megabatch(stack_states([state] * batch), mesh)
        bmasks = shard_megabatch_masks(ExclusionMasks(*(
            None if f is None else jnp.stack([f] * batch)
            for f in (masks.excluded_topics,
                      masks.excluded_replica_move_brokers,
                      masks.excluded_leadership_brokers))), mesh)
        active = jnp.zeros((batch,), bool)
        if self._fingerprint_skip:
            wait(megabatch_all_goal_stats_sharded(
                mesh, batched, goals, constraint, num_topics, bmasks))
        wait(megabatch_goal_stats_sharded(mesh, batched, idx, goals,
                                          constraint, num_topics, bmasks))
        for c in [cfg] + ([wide_cfg] if wide_cfg else []):
            if donate:
                rest = dataclasses.replace(
                    batched,
                    assignment=jnp.zeros(
                        (batch, 0, batched.assignment.shape[2]),
                        batched.assignment.dtype),
                    leader_slot=jnp.zeros((batch, 0),
                                          batched.leader_slot.dtype))
                wait(megabatch_optimize_rounds_donated_sharded(
                    mesh, jnp.copy(batched.assignment),
                    jnp.copy(batched.leader_slot), rest, active, idx,
                    prior, goals, constraint, c, num_topics, bmasks,
                    zero, ring_rounds=ring_n))
            else:
                wait(megabatch_optimize_rounds_sharded(
                    mesh, batched, active, idx, prior, goals, constraint,
                    c, num_topics, bmasks, zero, ring_rounds=ring_n))
        if donate:
            rest = dataclasses.replace(
                batched,
                assignment=jnp.zeros(
                    (batch, 0, batched.assignment.shape[2]),
                    batched.assignment.dtype),
                leader_slot=jnp.zeros((batch, 0),
                                      batched.leader_slot.dtype))
            wait(megabatch_swap_rounds_donated_sharded(
                mesh, jnp.copy(batched.assignment),
                jnp.copy(batched.leader_slot), rest, active, idx, prior,
                goals, constraint, num_topics, bmasks, 8, 64, zero))
        else:
            wait(megabatch_swap_rounds_sharded(
                mesh, batched, active, idx, prior, goals, constraint,
                num_topics, bmasks, 8, 64, zero))
        return True

    @staticmethod
    def _uniform_mask_presence(masks_list: list[ExclusionMasks],
                               ) -> list[ExclusionMasks]:
        """Normalize per-cluster mask presence for stacking: a field set
        by ANY cluster is filled with an inert all-False mask for the
        rest (excluding nothing is exactly what an absent mask means),
        so per-item options never split a batch by mask layout."""
        import jax.numpy as jnp
        fields = ("excluded_topics", "excluded_replica_move_brokers",
                  "excluded_leadership_brokers")
        fills = {}
        for name in fields:
            first = next((getattr(m, name) for m in masks_list
                          if getattr(m, name) is not None), None)
            if first is not None:
                fills[name] = jnp.zeros_like(first)
        if not fills:
            return masks_list
        return [ExclusionMasks(**{
            name: getattr(m, name) if getattr(m, name) is not None
            else fills.get(name) for name in fields})
            for m in masks_list]

    @staticmethod
    def _stack_masks(masks_list: list[ExclusionMasks]) -> ExclusionMasks:
        """Stack per-cluster exclusion masks along the cluster axis.
        Presence must be uniform: a field is None for every cluster or an
        array for every cluster (the batched kernels compile one mask
        layout per program)."""
        import jax.numpy as jnp

        def stack_field(name: str):
            vals = [getattr(m, name) for m in masks_list]
            present = [v is not None for v in vals]
            if not any(present):
                return None
            if not all(present):
                raise ValueError(
                    f"megabatch exclusion-mask presence for {name} must "
                    "be uniform across the batch")
            return jnp.stack(vals)

        return ExclusionMasks(
            excluded_topics=stack_field("excluded_topics"),
            excluded_replica_move_brokers=stack_field(
                "excluded_replica_move_brokers"),
            excluded_leadership_brokers=stack_field(
                "excluded_leadership_brokers"))

    @staticmethod
    def _megabatch_error(info: dict) -> Exception:
        from .chain import StatsRegressionError
        cls = {"StatsRegressionError": StatsRegressionError,
               "OptimizationFailureError": OptimizationFailureError}.get(
            info.get("error_type"), RuntimeError)
        return cls(info.get("error", "megabatch cluster solve failed"))
