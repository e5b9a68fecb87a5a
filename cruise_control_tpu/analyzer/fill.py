"""Constructive destination assignment for the candidate grid.

The top-k × top-k grid gives every source replica the same ``num_dests``
globally-best destinations. For goals whose destination demand is
PER-CARD — count goals need a broker with headroom in *this card's
topic*, resource goals need a broker whose band gap fits *this card's
size* — the shared destination list is the round-count bottleneck at
scale: the reference's greedy never pays it because each
``rebalanceForBroker`` walks candidate brokers per replica
(AbstractGoal.java:82-135), while the batched grid funnels thousands of
sources through ≤ 32 destinations (measured r4: TopicReplica ≈ 65% of
the 7k/1M wall-clock; DiskUsage tail ≈ 50 accepted moves/round).

This module computes one TARGETED destination per source card, appended
to the move block as an extra grid column (candidates.generate_candidates
``extra_dst``), so each card competes with a destination constructed for
it:

- ``deficit_fill_dests``: proportional fill over per-(topic, broker)
  deficits then remaining headroom — card ranks within their topic are
  mapped through the cumulative deficit/headroom profile, so a round's
  joint assignment respects every cell's integer headroom by
  construction (TopicReplicaDistributionGoal.java /
  ReplicaDistributionAbstractGoal.java band semantics).
- ``best_fit_dests``: first-fit-decreasing style matching for resource
  goals — each card's replica size is matched round-robin across the
  destinations whose band gap fits it
  (ResourceDistributionGoal.java:380-435 requireLessLoad, without the
  shared-destination funnel).

All kernels are O(k·log B) gathers + O(T·B) cumsums — no [k, B]
materialization. Card ranks are device-local, so the move round appends
the column on ONE partition shard only (``chain._scored_candidates``;
docs/DESIGN.md "Known limits" has what the interleaved fill measured).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Scale gate (measured at 7k/1M, r5): the per-round cost of the targeted
# branch (per-card fill ranks + cumulative profiles) buys nothing at
# north-star scale — TopicReplica reaches the same deep fixed point
# without it (~242 s vs ~288 s per full pass) because the 2048-wide grid
# already saturates the deficit profile over enough rounds; at tool/mid
# scale the column clears residuals the shared grid cannot reach. Static
# per-shape decision (num_partitions is a trace-time constant).
TARGET_DESTS_MAX_P = 500_000


def targets_enabled(num_partitions: int) -> bool:
    return num_partitions < TARGET_DESTS_MAX_P


def pow2_width(n: int) -> int:
    """Round a measured work size up to the next power of two — the
    compile-count quantization of every deficit-sized grid width (each
    distinct static width is a new XLA program, so sized widths must come
    from a tiny set)."""
    return 1 << max(0, int(n) - 1).bit_length()


def row_searchsorted(cum: jax.Array, rows: jax.Array, q: jax.Array,
                     ) -> jax.Array:
    """Per-card first index j with ``cum[rows[i], j] > q[i]`` (rows of
    ``cum`` non-decreasing); returns ``cum.shape[1]`` when no such j.
    Manual binary search: ceil(log2(n)) unrolled steps of [k] gathers —
    never materializes the [k, n] row gather."""
    n = cum.shape[1]
    lo = jnp.zeros(q.shape, jnp.int32)
    hi = jnp.full(q.shape, n, jnp.int32)
    # Interval width n halves per step; width-1 intervals need one final
    # step to resolve, so ceil(log2(n)) + 1 <= n.bit_length() + 1 overall.
    for _ in range(max(1, int(n).bit_length())):
        mid = (lo + hi) // 2
        v = cum[rows, jnp.minimum(mid, n - 1)]
        gt = v > q
        hi = jnp.where(gt & (mid < hi), mid, hi)
        lo = jnp.where(gt, lo, jnp.minimum(mid + 1, hi))
    return hi


def rank_within_group(group: jax.Array, valid: jax.Array) -> jax.Array:
    """[k] — number of EARLIER valid cards with the same group id (the
    card's fill position within its group). O(k²) boolean mask over the
    card batch (k ≤ a few thousand)."""
    k = group.shape[0]
    idx = jnp.arange(k)
    earlier = idx[:, None] > idx[None, :]
    same = group[:, None] == group[None, :]
    return (earlier & same & valid[None, :]).sum(axis=1).astype(jnp.int32)


def exclusive_rank(valid: jax.Array) -> jax.Array:
    """[k] — number of earlier valid cards (single-group fast path)."""
    c = jnp.cumsum(valid.astype(jnp.int32))
    return (c - valid.astype(jnp.int32)).astype(jnp.int32)


def deficit_fill_dests(topic_idx: jax.Array, rank: jax.Array,
                       deficit: jax.Array, headroom: jax.Array,
                       eligible: jax.Array,
                       ) -> tuple[jax.Array, jax.Array]:
    """Targeted destination per card by proportional fill.

    ``deficit``/``headroom`` are [G, B] NON-NEGATIVE integer-valued floats
    (deficit ⊆ headroom is NOT assumed — headroom here is the capacity
    REMAINING after the deficit portion). Card i (group g = topic_idx[i],
    fill position q = rank[i]) lands in the broker owning position q of
    the concatenated [deficit | headroom] profile of its group — deficits
    fill first, every broker receives at most deficit+headroom cards per
    round. Returns (dst [k] int32, ok [k] bool)."""
    f32 = jnp.float32
    d = jnp.where(eligible[None, :], deficit, 0.0).astype(f32)
    h = jnp.where(eligible[None, :], headroom, 0.0).astype(f32)
    cum_d = jnp.cumsum(d, axis=1)
    cum_h = jnp.cumsum(h, axis=1)
    tot_d = cum_d[:, -1][topic_idx]
    tot_h = cum_h[:, -1][topic_idx]
    q = rank.astype(f32) + 0.5  # strictly inside the owning cell
    in_def = q < tot_d
    j_d = row_searchsorted(cum_d, topic_idx, q)
    j_h = row_searchsorted(cum_h, topic_idx, q - tot_d)
    b = deficit.shape[1]
    dst = jnp.where(in_def, j_d, j_h)
    ok = (q < tot_d + tot_h) & (dst < b)
    return jnp.clip(dst, 0, b - 1).astype(jnp.int32), ok


def best_fit_dests(size: jax.Array, rank: jax.Array, headroom: jax.Array,
                   eligible: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Targeted destination per card by size fit: destinations sorted by
    band gap descending; card i (size s, fill position q = rank[i]) is
    assigned round-robin across the destinations whose gap fits s.
    Returns (dst [k] int32, ok [k] bool)."""
    b = headroom.shape[0]
    key = jnp.where(eligible, headroom, -jnp.inf)
    vals, idx = jax.lax.top_k(key, b)  # descending
    # m = count of destinations with gap >= size: first j with
    # -vals[j] > -size on the ascending -vals row.
    m = row_searchsorted(-vals[None, :], jnp.zeros_like(rank), -size)
    ok = (m > 0) & jnp.isfinite(size) & (size > 0)
    q = rank % jnp.maximum(m, 1)
    dst = idx[jnp.clip(q, 0, b - 1)]
    return dst.astype(jnp.int32), ok
