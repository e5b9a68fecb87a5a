"""Goal SPI for the TPU solver.

Reference parity: analyzer/goals/Goal.java:39-163 (optimize /
actionAcceptance / completeness) and AbstractGoal.java. Redesigned for
batch evaluation: a goal is a STATIC (hashable, frozen) object whose methods
are pure traced functions over (state, derived, constraint, deltas). The
sequential callback protocol "every previously optimized goal must accept
the action" (AbstractGoal.maybeApplyBalancingAction:230) becomes an AND over
each goal's vectorized ``acceptance`` mask, evaluated for thousands of
candidates at once.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ...model.tensors import ClusterTensors, replica_load_total
from ..candidates import CandidateDeltas
from ..constraint import BalancingConstraint
from ..derived import DerivedState


@dataclasses.dataclass(frozen=True)
class Goal:
    """Base goal. Subclasses override the kernel methods; instances carry
    only static config (so they can be jit-static arguments)."""

    name: str = "goal"
    is_hard: bool = False
    include_leadership: bool = False
    leadership_only: bool = False
    # Swap phase eligibility (ResourceDistributionGoal.java:421-430: only
    # when plain moves fail to reach the band are swaps tried).
    supports_swap: bool = False
    # True when acceptance/improvement depend ONLY on the candidate's own
    # partition (rack layout, broker-set membership, preferred leader) and
    # not on per-broker totals: the conflict-free accept step may then take
    # MANY moves per broker per round (only one per partition), which is
    # what makes structural goals converge in O(P / num_sources) rounds
    # instead of O(P / num_dests).
    independent_per_broker: bool = False
    # True when broker_violations/source_score are additive reductions over
    # the partition axis (rack duplicates, non-preferred leaders): under a
    # partition-sharded mesh the sharded search psums them across devices.
    partition_additive_scores: bool = False
    # True for goals whose per-round accepted-move count is source-limited
    # and whose band structure tolerates wide joint batches WITHOUT the
    # final-quality loss wider batches cause for the early count/resource
    # goals (measured at 1k/100k, docs/DESIGN.md): the bounded per-goal
    # driver runs these with a 4x source grid. Only goals late enough in
    # the chain that their coarser placements cannot be locked in against
    # later goals' fixes should set this (validated for
    # TopicReplicaDistributionGoal: rounds 482 -> 106, balancedness and
    # violated set unchanged).
    prefers_wide_batches: bool = False
    # True for the count-distribution family (replica / leader-replica /
    # topic-replica counts): total band violation ≈ 2 × the moves still
    # needed, so the bounded megastep driver may size the per-round move
    # budget and source width from the MEASURED surplus
    # (chain.deficit_sized_config) instead of the configured constant —
    # an O(10k)-move imbalance then stops burning hundreds of fixed-width
    # rounds. Resource goals must NOT set this: their violation is in
    # load units, not move counts.
    count_based: bool = False
    # True for goals whose decisions read measured resource loads (the
    # capacity / resource-distribution / potential-NW-out / leader-bytes-in
    # family): they need a substantially complete metric model, mirroring
    # ResourceDistributionGoal.clusterModelCompletenessRequirements:164-167
    # (numWindows/2 valid windows + min.valid.partition.ratio). Structural
    # goals (rack, counts, preferred leader) run on topology alone — one
    # window, any coverage (ReplicaDistributionAbstractGoal's weak
    # requirements).
    uses_resource_metrics: bool = False
    # True when the goal's fixed point has a closed-form transport
    # formulation the direct-assignment kernel (analyzer.direct) can
    # solve: ``direct_spec`` must then return the count plane + band +
    # grouping the kernel plans over. Only the count-distribution family
    # qualifies; whether the kernel actually RUNS additionally requires
    # every prior goal in the chain to be guard-representable
    # (analyzer.direct.direct_eligible).
    supports_direct: bool = False

    def completeness_requirements(self, num_windows: int,
                                  min_valid_partition_ratio: float,
                                  ) -> tuple[int, float]:
        """(min_valid_windows, min_monitored_partitions_ratio) this goal
        needs before its output is trustworthy
        (Goal.clusterModelCompletenessRequirements)."""
        if self.uses_resource_metrics:
            return max(1, num_windows // 2), min_valid_partition_ratio
        return 1, 0.0

    # -- evaluation kernels (traced) --------------------------------------
    def prepare_partial(self, state: ClusterTensors, num_topics: int) -> Any:
        """Per-round aux tensors that are ADDITIVE over the partition axis
        (e.g. [T, B] topic counts). Under a partition-sharded mesh each
        device computes its partial and the search psums the pytree."""
        return None

    def partial_from_agg(self, agg) -> Any:
        """This goal's prepare_partial result read from the incrementally-
        maintained AggCarry (analyzer.agg) instead of an O(P·S) recompute,
        or None when the goal is not agg-backed. The returned partial is
        already GLOBAL (no psum needed on a mesh)."""
        return None

    def finalize_aux(self, partial: Any, state: ClusterTensors,
                     derived: DerivedState,
                     constraint: BalancingConstraint) -> Any:
        """Non-additive post-processing of the (already psum'd) partial
        (e.g. balance bands from counts). Default: aux = partial."""
        return partial

    def prepare(self, state: ClusterTensors, derived: DerivedState,
                constraint: BalancingConstraint, num_topics: int) -> Any:
        """Single-device aux composition. Do NOT override this — the search
        paths call prepare_partial/finalize_aux directly (the sharded path
        psums the partial between them); override THOSE to customize aux, or
        an override would be silently bypassed during optimization."""
        return self.finalize_aux(self.prepare_partial(state, num_topics),
                                 state, derived, constraint)

    def broker_violations(self, state, derived, constraint, aux) -> jax.Array:
        """[B] violation magnitude per broker (0 = satisfied)."""
        raise NotImplementedError

    def objective(self, state, derived, constraint, aux) -> jax.Array:
        """Scalar, lower is better. Default: total violation."""
        return self.broker_violations(state, derived, constraint, aux).sum()

    def acceptance(self, state, derived, constraint, aux,
                   deltas: CandidateDeltas) -> jax.Array:
        """[N] bool — does this (already-optimized) goal tolerate each
        candidate action? (Goal.actionAcceptance, vectorized.)"""
        return jnp.ones(deltas.valid.shape[0], dtype=bool)

    def improvement(self, state, derived, constraint, aux,
                    deltas: CandidateDeltas) -> jax.Array:
        """[N] — decrease of this goal's objective if the candidate is
        applied (positive = improves). Default: pairwise violation delta."""
        raise NotImplementedError

    def swap_leg_acceptance(self, state, derived, constraint, aux,
                            leg: CandidateDeltas) -> jax.Array:
        """[N] bool — tolerate one directional leg of a swap, judged as an
        ordinary move. Default: ``acceptance``. Per-partition structural
        goals (rack, broker-set, topic counts) keep this; goals judged on
        per-broker TOTALS override it to all-true and judge the net
        transfer in ``swap_net_acceptance`` instead. The sharded solver
        evaluates leg acceptance on the device OWNING the leg's partition —
        implementations may index per-partition state freely."""
        return self.acceptance(state, derived, constraint, aux, leg)

    def swap_net_acceptance(self, state, derived, constraint, aux,
                            net: CandidateDeltas) -> jax.Array:
        """[N] bool — tolerate the NET transfer of a swap (replica counts
        unchanged, load(a) − load(b) moves src→dst). Default: all-true.
        CONTRACT: implementations must use only broker-indexed state
        (``derived`` aggregates, capacities) and the deltas' own fields —
        ``net.partition`` holds GLOBAL partition ids under the sharded
        solver, so per-partition gathers are out of bounds there."""
        return jnp.ones(net.valid.shape[0], dtype=bool)

    def swap_improvement(self, state, derived, constraint, aux,
                         fwd: CandidateDeltas, rev: CandidateDeltas,
                         net: CandidateDeltas) -> jax.Array:
        """[N] — decrease of this goal's objective if the SWAP is applied.
        Default: ``improvement`` on the net transfer (sufficient for
        totals-judged goals, where a swap is the signed net move).
        Structural goals whose objective lives on BOTH legs — e.g. the
        kafka-assigner even-rack goal, where each leg can fix or create a
        rack duplicate while the net transfer moves no replica — override
        this to score the legs (the reference's swap inner loop evaluates
        the exchange as a pair, KafkaAssignerEvenRackAwareGoal.java)."""
        return self.improvement(state, derived, constraint, aux, net)

    def swap_dest_score(self, state, derived, constraint, aux) -> jax.Array:
        """[B] — counterparty attractiveness for the SWAP grid. Default:
        ``dest_score``. Goals whose move destinations exclude exactly the
        brokers swaps exist to reach (the even-rack goal's dest_score
        drops over-ceiling brokers, but a count-preserving exchange WANTS
        the over-ceiling broker holding the replica to take back)
        override this."""
        return self.dest_score(state, derived, constraint, aux)

    def swap_light_weight(self, state, derived, constraint, aux,
                          ) -> jax.Array:
        """[P, S] — what a replica WEIGHS in a swap: the counterparty gives
        its lightest by this, and a swap is offered only where the
        overloaded broker's replica outweighs it (maxSourceReplicaLoad).
        Default: ``replica_weight``. A goal whose ``replica_weight`` ranks
        replicas for the MOVE grid by something other than their size (the
        resource goals' fit priority) says here what the size is."""
        return self.replica_weight(state, derived, constraint, aux)

    def swap_acceptance(self, state, derived, constraint, aux,
                        fwd: CandidateDeltas, rev: CandidateDeltas,
                        net: CandidateDeltas) -> jax.Array:
        """[N] bool — tolerate each candidate SWAP: both directional legs
        pass ``swap_leg_acceptance`` and the net transfer passes
        ``swap_net_acceptance`` (ActionType.INTER_BROKER_REPLICA_SWAP
        handling in the reference's actionAcceptance). Override the two
        components, not this composition — the sharded solver calls them
        separately (legs on the owning device, net on the replicated
        pairing grid)."""
        return self.swap_leg_acceptance(state, derived, constraint, aux, fwd) \
            & self.swap_leg_acceptance(state, derived, constraint, aux, rev) \
            & self.swap_net_acceptance(state, derived, constraint, aux, net)

    # -- candidate generation hints ---------------------------------------
    def source_score(self, state, derived, constraint, aux) -> jax.Array:
        """[B] — >0 means the broker should shed (rebalanceForBroker's
        requireLessLoad set)."""
        return self.broker_violations(state, derived, constraint, aux)

    def dest_score(self, state, derived, constraint, aux) -> jax.Array:
        """[B] — destination attractiveness; -inf = ineligible."""
        raise NotImplementedError

    def replica_weight(self, state, derived, constraint, aux) -> jax.Array:
        """[P, S] — which replicas to move first (SortedReplicas analogue)."""
        return replica_load_total(state)

    def card_dest_ok(self, state, cand_p: jax.Array, cand_s: jax.Array,
                     ) -> "jax.Array | None":
        """Optional [k, B] bool: the brokers this goal's ``acceptance``
        lets the source card ``(cand_p, cand_s)[i]`` move to, as far as
        that depends on the card alone and not on loads or counts (a rack
        rule: the racks of the partition's other replicas). None when the
        goal has no such rule. Once the goal is a PRIOR goal, what pairs a
        card with destinations it could not otherwise reach reads the
        conjunction of these (``search.prior_card_dest_ok``; today the swap
        grid's counterparties): a hint that saves vetoes, never a bypass,
        ``acceptance`` still judges every candidate."""
        return None

    def target_dests(self, state, derived, constraint, aux,
                     cand_p: jax.Array, cand_s: jax.Array,
                     src_valid: jax.Array, rank_stride: int = 1,
                     rank_offset=0,
                     ) -> "tuple[jax.Array, jax.Array] | None":
        """Optional constructive per-card destination (analyzer.fill): for
        the selected source replicas ``(cand_p, cand_s)[k]``, return
        (dst_broker [k] int32, ok [k] bool) — one destination built for
        each card — or None when the goal has no per-card destination
        rule. The search appends the result as an extra column of the
        move grid; all acceptance/selection machinery applies unchanged,
        so a targeted destination is a HINT, never a bypass.

        ``rank_stride``/``rank_offset`` map local fill ranks onto a
        GLOBAL fill-position space (position = rank·stride + offset):
        the partition-sharded mesh passes (num_shards, shard) so each
        device claims an interleaved, collision-free slice of the shared
        deficit/headroom profile — without it every device fills the
        same positions and the targeted column collapses mesh quality
        (measured r5). Single-device callers keep the identity (1, 0)."""
        return None

    def direct_spec(self, state, derived, constraint, aux, num_topics: int):
        """The direct-assignment transport formulation
        (analyzer.direct; only meaningful when ``supports_direct``):
        ``(counts [G, B], lower [G, 1], upper [G, 1], group [P, S] int32,
        movable [P, S] bool)`` — the count plane the goal balances, its
        band, which group each replica slot belongs to, and which
        replicas the goal may relocate. ``G`` = 1 for cluster-wide count
        goals, ``num_topics`` for per-topic planes."""
        return None


def pair_improvement(values: jax.Array, deltas: CandidateDeltas,
                     delta: jax.Array, viol_fn) -> jax.Array:
    """Improvement of Σ viol(broker) restricted to the touched (src, dst)
    pair. ``values[B]`` is the per-broker quantity, ``delta[N]`` how much
    each candidate transfers, ``viol_fn(value, at)`` the violation
    magnitude, where ``at`` is ``deltas.at_src`` or ``deltas.at_dst`` so
    per-broker limits are looked up at the same end."""
    v_src, v_dst = deltas.at_src(values), deltas.at_dst(values)
    before = viol_fn(v_src, deltas.at_src) + viol_fn(v_dst, deltas.at_dst)
    after = viol_fn(v_src - delta, deltas.at_src) \
        + viol_fn(v_dst + delta, deltas.at_dst)
    return jnp.where(deltas.valid, before - after, -jnp.inf)


def donor_widened_shed(values: jax.Array, lower, upper,
                       derived: DerivedState) -> jax.Array:
    """Per-broker shed pressure with donor widening
    (ResourceDistributionGoal.java:388 requireMoreLoad): anything above the
    upper band sheds; when some eligible broker sits below the lower band,
    every broker above the LOWER band becomes a donor for move-in.
    ``values`` is [B] (or [T, B] for per-topic bands with broadcastable
    lower/upper); masked to alive brokers."""
    eligible = derived.alive & derived.allowed_replica_move
    under_any = ((values < lower) & eligible).any(axis=-1, keepdims=True)
    over = jnp.maximum(values - upper, 0.0)
    donor = jnp.where(under_any, jnp.maximum(values - lower, 0.0), 0.0)
    return jnp.where(derived.alive, over + donor, 0.0)
