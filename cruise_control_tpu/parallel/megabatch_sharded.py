"""Device-sharded megabatch: the fleet's CLUSTER axis on the mesh.

Round 14 solves a whole bucket of clusters in ONE donated program on one
device; this module grows that cluster dimension onto the 1-D device
mesh (ROADMAP item 3, the Podracer/Anakin + Brax idiom already cited
in-tree: keep loops on-device, batch everything through one program
across the mesh). Each megabatch driver — move, swap, direct transport,
goal stats — gets a ``shard_map`` twin that places
``batch_width / n_devices`` cluster slots per device:

- EVERY stacked field shards along the leading cluster axis (unlike the
  partition-axis solver in ``parallel/chain_sharded.py``, there are no
  replicated topology planes here — ``stack_states`` stacks the whole
  pytree, so capacity/rack/broker planes carry the cluster axis too);
- clusters are INDEPENDENT, so the per-device body is literally the
  single-device batched driver at local width and there are NO
  collectives — each device's ``lax.while_loop`` early-exits on its OWN
  clusters' ``active.any()``, which is the scaling win: a device whose
  shard converged goes idle instead of spinning frozen-select rounds
  until the slowest cluster fleet-wide finishes;
- the one-behind pump (``chain.run_megabatch_pass``) is unchanged: the
  per-cluster early-exit mask chains dispatch-to-dispatch as a sharded
  device value, exactly like the state.

Byte parity per cluster against the single-device megabatch is the
correctness contract (tests/test_megabatch_sharded.py pins it at two
bucket shapes x two occupancies): the freeze-select discipline makes a
cluster's trajectory depend only on its own rows and the shared global
round index, so splitting the batch across devices — each running the
same rounds until ITS shard converges — changes nothing per cluster.
Inert pad slots (``chain.inert_state_like``) shard along the same axis
and stay byte-frozen; pad-to-device-multiple is the optimizer's job
(``optimizations_megabatch`` rounds the batch width up, the same
append-only padding soundness as ``fleet/bucketing.py``).

Donation contract (CCSA002): identical to the single-device donated
twins — the batched mutable pair ``{assignment[C,P,S],
leader_slot[C,P]}`` rides as two separately-donated sharded arguments
and the stacked remainder travels read-only with zero-row placeholders.
``jnp.copy`` preserves sharding, so the chain layer's copy-on-first-
dispatch donation guard works unchanged on sharded inputs.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analyzer.search import ExclusionMasks
from ..model.tensors import ClusterTensors
from .mesh import PARTITION_AXIS

# The fleet mesh is the solver mesh: one 1-D axis. For the megabatch
# twins that axis carries CLUSTERS (each device holds whole clusters),
# not partition rows — same mesh object, different sharded dimension.
CLUSTER_AXIS = PARTITION_AXIS


def cluster_state_specs() -> ClusterTensors:
    """PartitionSpec pytree for a STACKED ClusterTensors: every field
    leads with the cluster axis (``stack_states`` stacks the whole
    pytree), so every field shards along the mesh."""
    c = P(CLUSTER_AXIS)
    return ClusterTensors(
        assignment=c, leader_slot=c, leader_load=c, follower_load=c,
        capacity=c, rack=c, broker_state=c, topic=c, partition_mask=c,
        broker_mask=c, host=c)


def megabatch_mask_specs(
        mask_presence: tuple[bool, bool, bool]) -> ExclusionMasks:
    """Specs for the stacked exclusion masks: present fields carry the
    cluster axis (the optimizer stacks one mask row per cluster)."""
    c = P(CLUSTER_AXIS)
    return ExclusionMasks(
        excluded_topics=c if mask_presence[0] else None,
        excluded_replica_move_brokers=c if mask_presence[1] else None,
        excluded_leadership_brokers=c if mask_presence[2] else None)


def masks_presence(masks: ExclusionMasks) -> tuple[bool, bool, bool]:
    return (masks.excluded_topics is not None,
            masks.excluded_replica_move_brokers is not None,
            masks.excluded_leadership_brokers is not None)


def shard_megabatch(batched: ClusterTensors, mesh: Mesh) -> ClusterTensors:
    """Place a stacked megabatch on the mesh, cluster axis sharded. The
    batch width must divide the mesh (the optimizer pads it to a device
    multiple before stacking)."""
    n = mesh.devices.size
    c = batched.assignment.shape[0]
    if c % n != 0:
        raise ValueError(
            f"megabatch width {c} not divisible by mesh size {n}")
    specs = cluster_state_specs()
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), batched,
        specs)


def shard_megabatch_masks(masks: ExclusionMasks,
                          mesh: Mesh) -> ExclusionMasks:
    """Place the stacked mask fields on the mesh (None fields stay
    None)."""
    sh = NamedSharding(mesh, P(CLUSTER_AXIS))
    return ExclusionMasks(*(
        None if f is None else jax.device_put(f, sh)
        for f in (masks.excluded_topics,
                  masks.excluded_replica_move_brokers,
                  masks.excluded_leadership_brokers)))


@lru_cache(maxsize=64)
def _make_move_kernels(mesh: Mesh, goals, constraint, cfg, num_topics: int,
                       mask_presence: tuple[bool, bool, bool],
                       ring_rounds: int):
    """Sharded move-megastep pair (plain, donated): the per-device body
    IS ``chain._megabatch_rounds_driver`` at local width — no
    collectives, per-device early exit."""
    from ..analyzer.chain import _megabatch_rounds_driver
    rep = P()
    cs = P(CLUSTER_AXIS)
    state_specs = cluster_state_specs()
    mask_specs = megabatch_mask_specs(mask_presence)
    ring_spec = cs if ring_rounds > 0 else None

    def body(states, active0, masks, active_idx, prior_mask, budget):
        return _megabatch_rounds_driver(
            states, active0, active_idx, prior_mask, goals, constraint,
            cfg, num_topics, masks, budget, ring_rounds=ring_rounds)

    def move_body_donated(assignment, leader_slot, rest, active0, masks,
                          active_idx, prior_mask, budget):
        states = dataclasses.replace(rest, assignment=assignment,
                                     leader_slot=leader_slot)
        final, total, rounds, active, ring = _megabatch_rounds_driver(
            states, active0, active_idx, prior_mask, goals, constraint,
            cfg, num_topics, masks, budget, ring_rounds=ring_rounds)
        return (final.assignment, final.leader_slot, total, rounds,
                active, ring)

    move = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, cs, mask_specs, rep, rep, rep),
        out_specs=(state_specs, cs, cs, cs, ring_spec), check_vma=False))
    move_d = jax.jit(shard_map(
        move_body_donated, mesh=mesh,
        in_specs=(cs, cs, state_specs, cs, mask_specs, rep, rep, rep),
        out_specs=(cs, cs, cs, cs, cs, ring_spec), check_vma=False),
        donate_argnums=(0, 1))
    return move, move_d


@lru_cache(maxsize=64)
def _make_swap_kernels(mesh: Mesh, goals, constraint, num_topics: int,
                       mask_presence: tuple[bool, bool, bool], moves: int,
                       max_rounds: int):
    """Sharded swap-megastep pair (plain, donated)."""
    from ..analyzer.chain import _megabatch_swap_driver
    rep = P()
    cs = P(CLUSTER_AXIS)
    state_specs = cluster_state_specs()
    mask_specs = megabatch_mask_specs(mask_presence)

    def body(states, active0, masks, active_idx, prior_mask, budget):
        return _megabatch_swap_driver(
            states, active0, active_idx, prior_mask, goals, constraint,
            num_topics, masks, moves, max_rounds, budget)

    def swap_body_donated(assignment, leader_slot, rest, active0, masks,
                          active_idx, prior_mask, budget):
        states = dataclasses.replace(rest, assignment=assignment,
                                     leader_slot=leader_slot)
        final, total, rounds, active = _megabatch_swap_driver(
            states, active0, active_idx, prior_mask, goals, constraint,
            num_topics, masks, moves, max_rounds, budget)
        return final.assignment, final.leader_slot, total, rounds, active

    swap = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, cs, mask_specs, rep, rep, rep),
        out_specs=(state_specs, cs, cs, cs), check_vma=False))
    swap_d = jax.jit(shard_map(
        swap_body_donated, mesh=mesh,
        in_specs=(cs, cs, state_specs, cs, mask_specs, rep, rep, rep),
        out_specs=(cs, cs, cs, cs, cs), check_vma=False),
        donate_argnums=(0, 1))
    return swap, swap_d


@lru_cache(maxsize=64)
def _make_direct_kernels(mesh: Mesh, goals, index: int, constraint,
                         num_topics: int,
                         mask_presence: tuple[bool, bool, bool],
                         max_sweeps: int, margin_frac: float, seed: int):
    """Sharded direct-transport pair for ONE goal index (the megabatch
    freeze-discipline sweep loop of ``analyzer.direct``, per-device at
    local width). Like the single-device twin, the sweep body is
    selected by trace-time dispatch on the goal index, so the kernel is
    built per-(mesh, index) — the lru_cache bounds the set to the
    direct-eligible count goals actually reached."""
    from ..analyzer.direct import _megabatch_direct_driver
    cs = P(CLUSTER_AXIS)
    state_specs = cluster_state_specs()
    mask_specs = megabatch_mask_specs(mask_presence)

    def body(states, active0, masks):
        return _megabatch_direct_driver(
            states, active0, goals, index, constraint, num_topics, masks,
            max_sweeps, margin_frac=margin_frac, seed=seed)

    def direct_body_donated(assignment, leader_slot, rest, active0, masks):
        states = dataclasses.replace(rest, assignment=assignment,
                                     leader_slot=leader_slot)
        final, total, sweeps, active = _megabatch_direct_driver(
            states, active0, goals, index, constraint, num_topics, masks,
            max_sweeps, margin_frac=margin_frac, seed=seed)
        return final.assignment, final.leader_slot, total, sweeps, active

    direct = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(state_specs, cs, mask_specs),
        out_specs=(state_specs, cs, cs, cs), check_vma=False))
    direct_d = jax.jit(shard_map(
        direct_body_donated, mesh=mesh,
        in_specs=(cs, cs, state_specs, cs, mask_specs),
        out_specs=(cs, cs, cs, cs, cs), check_vma=False),
        donate_argnums=(0, 1))
    return direct, direct_d


@lru_cache(maxsize=64)
def _make_stats_kernels(mesh: Mesh, goals, constraint, num_topics: int,
                        mask_presence: tuple[bool, bool, bool]):
    """Sharded (per-goal stats, all-goal stats) pair — the entry/exit
    and fingerprint-snapshot programs on the sharded cluster axis."""
    from ..analyzer.chain import (
        _chain_all_goal_stats_body, _chain_goal_stats_body, _mask_axes,
    )
    rep = P()
    cs = P(CLUSTER_AXIS)
    state_specs = cluster_state_specs()
    mask_specs = megabatch_mask_specs(mask_presence)

    def stats_body(states, masks, active_idx):
        mask_fields, mask_ax = _mask_axes(masks)

        def per_cluster(s, tm, rm, lm):
            return _chain_goal_stats_body(s, active_idx, goals, constraint,
                                          num_topics,
                                          ExclusionMasks(tm, rm, lm))

        return jax.vmap(per_cluster, in_axes=(0,) + mask_ax)(states,
                                                             *mask_fields)

    def all_stats_body(states, masks):
        mask_fields, mask_ax = _mask_axes(masks)

        def per_cluster(s, tm, rm, lm):
            return _chain_all_goal_stats_body(s, goals, constraint,
                                              num_topics,
                                              ExclusionMasks(tm, rm, lm))

        return jax.vmap(per_cluster, in_axes=(0,) + mask_ax)(states,
                                                             *mask_fields)

    stats = jax.jit(shard_map(
        stats_body, mesh=mesh, in_specs=(state_specs, mask_specs, rep),
        out_specs=(cs, cs, cs), check_vma=False))
    all_stats = jax.jit(shard_map(
        all_stats_body, mesh=mesh, in_specs=(state_specs, mask_specs),
        out_specs=(cs, cs, cs), check_vma=False))
    return stats, all_stats


# ---------------------------------------------------------------------------
# Call-compatible wrappers: the chain layer swaps these in for the
# single-device jitted kernels (same argument order, leading mesh) so
# make_enqueue / the direct pre-pass / the stats readbacks stay
# single-path.
# ---------------------------------------------------------------------------

def megabatch_optimize_rounds_sharded(mesh: Mesh, states, active0,
                                      active_idx, prior_mask, goals,
                                      constraint, cfg, num_topics: int,
                                      masks, budget, ring_rounds: int = 0):
    """Sharded twin of ``chain.megabatch_optimize_rounds``."""
    move, _ = _make_move_kernels(mesh, goals, constraint, cfg, num_topics,
                                 masks_presence(masks), ring_rounds)
    final, total, rounds, active, ring = move(
        states, active0, masks, jnp.int32(active_idx), prior_mask,
        jnp.int32(budget))
    if ring_rounds > 0:
        return final, total, rounds, active, ring
    return final, total, rounds, active


def megabatch_optimize_rounds_donated_sharded(mesh: Mesh, assignment,
                                              leader_slot, rest, active0,
                                              active_idx, prior_mask, goals,
                                              constraint, cfg,
                                              num_topics: int, masks,
                                              budget, ring_rounds: int = 0):
    """Sharded twin of ``chain.megabatch_optimize_rounds_donated``."""
    _, move_d = _make_move_kernels(mesh, goals, constraint, cfg,
                                   num_topics, masks_presence(masks),
                                   ring_rounds)
    a, l, total, rounds, active, ring = move_d(
        assignment, leader_slot, rest, active0, masks,
        jnp.int32(active_idx), prior_mask, jnp.int32(budget))
    if ring_rounds > 0:
        return a, l, total, rounds, active, ring
    return a, l, total, rounds, active


def megabatch_swap_rounds_sharded(mesh: Mesh, states, active0, active_idx,
                                  prior_mask, goals, constraint,
                                  num_topics: int, masks, moves: int,
                                  max_rounds: int, budget):
    """Sharded twin of ``chain.megabatch_swap_rounds``."""
    swap, _ = _make_swap_kernels(mesh, goals, constraint, num_topics,
                                 masks_presence(masks), moves, max_rounds)
    return swap(states, active0, masks, jnp.int32(active_idx), prior_mask,
                jnp.int32(budget))


def megabatch_swap_rounds_donated_sharded(mesh: Mesh, assignment,
                                          leader_slot, rest, active0,
                                          active_idx, prior_mask, goals,
                                          constraint, num_topics: int,
                                          masks, moves: int,
                                          max_rounds: int, budget):
    """Sharded twin of ``chain.megabatch_swap_rounds_donated``."""
    _, swap_d = _make_swap_kernels(mesh, goals, constraint, num_topics,
                                   masks_presence(masks), moves,
                                   max_rounds)
    return swap_d(assignment, leader_slot, rest, active0, masks,
                  jnp.int32(active_idx), prior_mask, jnp.int32(budget))


def megabatch_direct_rounds_sharded(mesh: Mesh, states, active0, goals,
                                    index: int, constraint,
                                    num_topics: int, masks,
                                    max_sweeps: int = 8,
                                    margin_frac: float = 0.25,
                                    seed: int | None = None):
    """Sharded twin of ``direct.megabatch_direct_rounds``."""
    from ..analyzer.direct import SPARSE_ROUNDING_SEED
    direct, _ = _make_direct_kernels(
        mesh, goals, index, constraint, num_topics, masks_presence(masks),
        max_sweeps, margin_frac,
        SPARSE_ROUNDING_SEED if seed is None else seed)
    return direct(states, active0, masks)


def megabatch_direct_rounds_donated_sharded(mesh: Mesh, assignment,
                                            leader_slot, rest, active0,
                                            goals, index: int, constraint,
                                            num_topics: int, masks,
                                            max_sweeps: int = 8,
                                            margin_frac: float = 0.25,
                                            seed: int | None = None):
    """Sharded twin of ``direct.megabatch_direct_rounds_donated``."""
    from ..analyzer.direct import SPARSE_ROUNDING_SEED
    _, direct_d = _make_direct_kernels(
        mesh, goals, index, constraint, num_topics, masks_presence(masks),
        max_sweeps, margin_frac,
        SPARSE_ROUNDING_SEED if seed is None else seed)
    return direct_d(assignment, leader_slot, rest, active0, masks)


def megabatch_goal_stats_sharded(mesh: Mesh, states, active_idx, goals,
                                 constraint, num_topics: int, masks):
    """Sharded twin of ``chain.megabatch_goal_stats``."""
    stats, _ = _make_stats_kernels(mesh, goals, constraint, num_topics,
                                   masks_presence(masks))
    return stats(states, masks, jnp.int32(active_idx))


def megabatch_all_goal_stats_sharded(mesh: Mesh, states, goals, constraint,
                                     num_topics: int, masks):
    """Sharded twin of ``chain.megabatch_all_goal_stats`` (the
    fingerprint-skip snapshot)."""
    _, all_stats = _make_stats_kernels(mesh, goals, constraint, num_topics,
                                       masks_presence(masks))
    return all_stats(states, masks)
