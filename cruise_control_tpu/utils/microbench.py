"""In-process device microbench: per-op-class cost inside a fused
while_loop, at solver-realistic shapes.

The op-class campaign ROADMAP item 2 waits on (scatter/top-k/small-op
marginals on a real chip) lived only in ``tools/microbench_device.py`` —
runnable exclusively from a shell on the host that holds the chip. This
module is the same measurement as a library call, served by
``GET /kafkacruisecontrol/profile?microbench=true`` so the marginals are
one HTTP call away from the serving process that holds the chip (the
CLI tool now wraps this module, so the two can never drift).

Marginal method per class (tools/profile_round.py discipline): run k and
2k iterations of a tight ``lax.while_loop`` of the class's body and
report ``(t2k - tk) / k`` — the fixed per-dispatch cost cancels.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

# Op classes, in the order they appear in the solver round body's cost
# profile (see tools/profile_parts.py): top-k selections over the
# flattened replica axis, segment reductions for per-broker aggregates,
# grid gathers, scatter applies, elementwise sweeps, and the pairwise
# cumulative-select mask. The last three are the direct-assignment
# transport kernel's op classes (analyzer.direct, round 17): the
# multi-key segmented sort of the replica axis, the cumsum
# rank-assignment (cumulative profile + per-card binary search), and
# the one-shot scatter apply of a full mover batch — so the ROADMAP
# item-2 chip campaign can attribute the new kernel in the same
# ``GET /profile?microbench=true`` call as the greedy round's classes.
# The round-21 sparse plan adds three more: the cell-aggregate segment
# sum onto the [G, B] count plane, the fractional-target systematic
# rounding (hash uniforms + per-group cumsum diff), and the
# stride-interleaved composite-key sort the mesh rank layout pays
# instead of the plain segsort. Round 23 adds the fused variant of that
# sort: quantize the weight into the low bits of ONE composite integer
# key so the interleave costs a single single-key sort frame instead of
# two two-key frames — the candidate replacement the chip campaign
# prices against ``stride_sort``. The ``accept_*`` classes (PR 26) price
# the round body's per-broker lookups: ``_ACCEPT_TERMS`` [B] tables read
# at every candidate of the fused route's grid (256 sources x (B/4 shared
# destinations + the targeted column) + 256 x 3 leadership) — gathered
# once per candidate (``accept_flat``), gathered on the grid's margins
# and broadcast (``accept_margin``, CandidateGrid's form), and the
# margins with the terms packed into one [B, F] table gathered as rows
# (``accept_packed``). The ``bbest_*`` / ``bcount_*`` classes (PR 28) price
# the source selection's per-broker reductions of the flat replica axis
# (model.tensors.broker_best, best and second best with the source lookup,
# as ``select_sources`` runs them, and the pick of the ``quarter`` brokers
# the grid keeps; broker_count): the solver's own ``segment`` and ``dense``
# forms, and for the best a sort-based alternative (one three-key
# ``lax.sort``, the first two of each run). ``bbest_rows`` is the solver's
# selection itself (``candidates.broker_blocks``): the dense pair over the
# rows of the quarter's candidate brokers alone (``candidates.source_rows``).
# ``topk128`` beside them is the selection's global block alone. The
# ``deltas_*`` classes (PR 35) price ``compute_deltas`` itself on the same
# grid over a random cluster of (brokers, partitions): every field gathered
# once per candidate (``deltas_flat``, no layout passed) and built on the
# grid's margins (``deltas_margin``, the layout passed: the round's form).
# The ``flat_sort<k>`` / ``flat_two<k>`` classes price the move
# round's two top-k's of the whole flat replica axis
# (``model.tensors.flat_top_k``) at the k's the grids use: ``lax.top_k``
# itself, one sort of the axis, and the two-level form at the row length
# ``flat_topk_row_len`` gives.
FLAT_TOPK_KS = (128, 256, 512, 1024)
CASE_NAMES = ("topk128", "topk1024", "approx1024", "segsum", "segmax",
              "gather_grid", "scatter_m", "elemwise", "pairwise_m",
              "segsort", "rankfill", "scatter_apply",
              "cell_segsum", "frac_round", "stride_sort",
              "stride_sort_fused", "accept_flat", "accept_margin",
              "accept_packed", "bbest_segment", "bbest_dense", "bbest_sort",
              "bbest_rows", "bcount_segment", "bcount_dense", "deltas_flat",
              "deltas_margin") \
    + tuple(f"flat_{form}{k}" for k in FLAT_TOPK_KS
            for form in ("sort", "two"))

_ACCEPT_TERMS = 100


def _build_cases(brokers: int, partitions: int, quarter: int = 64):
    import jax
    import jax.numpy as jnp

    s = 3
    n_flat = partitions * s
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (n_flat,))
    seg = jax.random.randint(key, (n_flat,), 0, brokers)
    grid = 256 * max(16, min(512, brokers // 4))
    gscore = jax.random.normal(key, (grid,))
    gidx = jax.random.randint(key, (grid,), 0, brokers)
    m = 512
    midx = jax.random.randint(key, (m,), 0, brokers)
    mvals = jax.random.normal(key, (m, 4))
    loads = jax.random.normal(key, (brokers, 4))

    # accept_* classes: the fused route's candidate grid at ``brokers``
    # (pass the PADDED count: 128 / 256 for the benchmark's cells), with
    # random brokers on its margins. ``grid`` is the solver's own
    # CandidateGrid, so the margin forms time the code the round runs.
    from ..analyzer.candidates import CandidateGrid, _span
    k_src = k_l = 256
    k_dst = max(16, min(512, brokers // 4))
    n_rows, n_dst = k_src + k_l, k_dst + k_src + k_l * s
    akeys = jax.random.split(key, 3)
    no_rows = jnp.zeros(n_rows, jnp.int32)
    grid = CandidateGrid(
        layout=((k_src, k_dst + 1), (k_l, s)),
        row_src=jax.random.randint(akeys[0], (n_rows,), 0, brokers),
        dst_margin=jax.random.randint(akeys[1], (n_dst,), 0, brokers),
        row_partition=no_rows, row_topic=no_rows, row_src_slot=no_rows)
    tables = jax.random.normal(akeys[2], (_ACCEPT_TERMS, brokers))
    src_n = grid.from_rows(grid.row_src)       # [N] broker per candidate
    dst_n = grid.from_dst(grid.dst_margin)
    half = _ACCEPT_TERMS // 2

    def accept_terms(at_src, at_dst):
        """The consumer every form shares: 50 source terms compared with
        50 destination terms per candidate, as the acceptance stack
        compares them; counted, not and-ed, so every term shows in the
        result."""
        ok = jnp.zeros(src_n.shape, jnp.int32)
        for f in range(half):
            ok += at_src(f) <= at_dst(half + f) + 0.5
        return ok.sum().astype(jnp.float32)

    # bbest_* / bcount_*: every flat replica on one of ``brokers`` brokers
    # or in the dead bucket, as ``broker_segments`` lays them out; the
    # carry's first ``brokers`` entries are the brokers' source scores.
    from ..analyzer.candidates import _keep_brokers, broker_blocks
    from ..model.tensors import (
        broker_best, broker_count, broker_flag_at, flat_topk_row_len,
        two_level_top_k,
    )
    bseg = jax.random.randint(akeys[0], (n_flat,), 0, brokers + 1)
    b_ids = jnp.arange(brokers, dtype=jnp.int32)
    quarter = min(quarter, brokers)

    def best_two(v, form):
        """What ``select_sources`` asks per broker, on the carry as the
        weights: which replicas sit on a source broker, each broker's best
        and second best of them, and the ``quarter`` brokers kept."""
        score = _span(v, 0, brokers)
        on = broker_flag_at(score > 0.0, bseg,
                            "dense" if form == "rows" else form)
        fw = jnp.where(on, v, -jnp.inf)
        if form == "rows":
            return broker_blocks(fw, bseg, score, quarter, "dense")[:4]
        w1, i1 = broker_best(fw, bseg, brokers, form)
        w2, i2 = broker_best(fw, bseg, brokers, form, skip=i1)
        return _keep_brokers(score, w1, i1, w2, i2, quarter, n_flat)

    def best_two_sorted(v):
        """The same pair from ONE sort of the axis by (broker, weight
        descending, flat index): a run's first two entries. The source
        lookup is the dense one: the sort replaces the pair only."""
        score = _span(v, 0, brokers)
        on = broker_flag_at(score > 0.0, bseg, "dense")
        fw = jnp.where(on, v, -jnp.inf)
        ss, sw, si = jax.lax.sort(
            (bseg, -fw, jnp.arange(n_flat, dtype=jnp.int32)), num_keys=3)
        start = jnp.searchsorted(ss, b_ids, side="left")
        out = []
        for pos in (start, start + 1):
            at = jnp.minimum(pos, n_flat - 1)
            w = jnp.where((pos < n_flat) & (ss[at] == b_ids), -sw[at],
                          -jnp.inf)
            out += [w, jnp.where(jnp.isfinite(w), si[at], n_flat)]
        return _keep_brokers(score, *out, quarter, n_flat)

    # deltas_*: a cluster of (brokers, partitions) at RF 3 with three
    # distinct brokers a partition and leaders on every slot, and the grid
    # above with random partitions on its rows. The carry shifts every
    # row's partition, so no lookup is loop-invariant.
    from ..analyzer.candidates import Candidates, compute_deltas
    from ..analyzer.derived import compute_derived
    from ..model.tensors import ClusterTensors
    dkeys = jax.random.split(akeys[2], 5)
    hop = jax.random.randint(dkeys[0], (partitions, s), 1,
                             max(2, brokers // 3))
    first = jax.random.randint(dkeys[1], (partitions, 1), 0, brokers)
    lload = jax.random.uniform(dkeys[2], (partitions, 4))
    dstate = ClusterTensors(
        assignment=(first + jnp.cumsum(hop, axis=1) - hop) % brokers,
        leader_slot=jax.random.randint(dkeys[3], (partitions,), 0, s),
        leader_load=lload, follower_load=0.5 * lload,
        capacity=jnp.ones((brokers, 4)),
        rack=jnp.arange(brokers, dtype=jnp.int32) % 8,
        broker_state=jnp.zeros(brokers, jnp.int8),
        topic=jax.random.randint(dkeys[3], (partitions,), 0,
                                 max(1, brokers // 10)),
        partition_mask=jnp.ones(partitions, bool),
        broker_mask=jnp.ones(brokers, bool))
    dderived = compute_derived(dstate)
    n_move = k_src * (k_dst + 1)
    row_p = jax.random.randint(dkeys[4], (n_rows,), 0, partitions)
    row_slot = jax.random.randint(dkeys[0], (k_src,), 0, s)
    zeros_n = jnp.zeros(src_n.shape, jnp.int32)
    dcand = Candidates(
        kind=(jnp.arange(len(src_n)) >= n_move).astype(jnp.int8),
        partition=grid.from_rows(row_p),
        src_slot=zeros_n.at[:n_move].set(jnp.repeat(row_slot, k_dst + 1)),
        dst_broker=zeros_n.at[:n_move].set(dst_n[:n_move]),
        dst_slot=zeros_n.at[n_move:].set(
            jnp.tile(jnp.arange(s, dtype=jnp.int32), k_l)),
        valid=jnp.ones(src_n.shape, bool))

    def deltas_step(v, layout):
        """``compute_deltas`` with every row's partition shifted by the
        carry; every field's bits summed into the next carry (exact in
        any order), so none is dead and the forms can be compared."""
        cand = dataclasses.replace(
            dcand, partition=(dcand.partition + v[0]) % partitions)
        d = compute_deltas(dstate, dderived, cand, layout)
        total = jnp.int32(0)
        for f in dataclasses.fields(d):
            a = getattr(d, f.name)
            if f.name == "grid" or a is None:
                continue
            if jnp.issubdtype(a.dtype, jnp.floating):
                a = jax.lax.bitcast_convert_type(a, jnp.int32)
            total += a.astype(jnp.int32).sum()
        return v + 1 + total % 2

    shift0 = jnp.zeros((1,), jnp.int32)

    def fold(arrays):
        """Every array into the carry's next value, so none is dead."""
        total = jnp.float32(0.0)
        for a in arrays:
            a = jnp.where(jnp.isfinite(a), a, 1.0) \
                if jnp.issubdtype(a.dtype, jnp.floating) else a % 7
            total += a.sum().astype(jnp.float32)
        return total * 1e-9

    def loop(body, carry, iters):
        def c(st):
            return st[0] < iters

        def bd(st):
            i, x = st
            return (i + 1, body(x))
        return jax.lax.while_loop(c, bd, (jnp.int32(0), carry))[1]

    @partial(jax.jit, static_argnames=("iters", "which"))
    def run(x, iters, which):
        if which == "topk128":
            return loop(lambda v: jax.lax.top_k(v + 1.0, 128)[0].sum() + v,
                        x, iters)
        if which == "topk1024":
            return loop(lambda v: jax.lax.top_k(v + 1.0, 1024)[0].sum() + v,
                        x, iters)
        if which == "approx1024":
            return loop(
                lambda v: jax.lax.approx_max_k(v + 1.0, 1024)[0].sum() + v,
                x, iters)
        if which == "segsum":
            return loop(
                lambda v: v + jax.ops.segment_sum(
                    v, seg, num_segments=brokers + 1)[seg] * 1e-9, x, iters)
        if which == "segmax":
            return loop(
                lambda v: v + jax.ops.segment_max(
                    v, seg, num_segments=brokers + 1)[seg] * 1e-9, x, iters)
        if which == "gather_grid":
            return loop(
                lambda v: v + (v[gidx % grid] * 1e-9).sum(), x, iters)
        if which == "scatter_m":
            return loop(
                lambda v: v.at[midx].add(mvals * 1e-9), x, iters)
        if which == "elemwise":
            return loop(lambda v: jnp.where(v > 0, v * 0.999999, v), x, iters)
        if which == "pairwise_m":
            # attach_cumulative-like [m, m] mask + matmul
            def bd(v):
                mask = (v[:, :1] > v[None, :, 0]).astype(jnp.float32)
                return v + (mask @ v) * 1e-9
            return loop(bd, x, iters)
        if which == "segsort":
            # direct.py's mover selection: multi-key (cell, weight) sort
            # of the flattened replica axis + within-run ranks.
            idx = jnp.arange(n_flat, dtype=jnp.int32)

            def bd(v):
                sc, sk, _si = jax.lax.sort((seg.astype(jnp.int32), v, idx),
                                           num_keys=2)
                return v + sk * 1e-9 + (sc[:1] - sc[:1]).astype(v.dtype)
            return loop(bd, x, iters)
        if which == "rankfill":
            # cumsum rank-assignment (fill.deficit_fill_dests shape): a
            # [G, B] cumulative profile + per-card binary search.
            from ..analyzer.fill import deficit_fill_dests
            g_rows = 64
            prof = jnp.abs(jax.random.normal(key, (g_rows, brokers)))
            elig = jnp.ones((brokers,), bool)
            grp = (seg % g_rows).astype(jnp.int32)
            rank = jnp.arange(n_flat, dtype=jnp.int32) % brokers

            def bd(v):
                dst, ok = deficit_fill_dests(grp, rank, prof + v[0] * 1e-9,
                                             prof, elig)
                return v + ok.sum() * 1e-12 + dst.sum() * 1e-12
            return loop(bd, x, iters)
        if which == "cell_segsum":
            # direct.py's count-plane aggregation: segment_sum of the
            # flattened replica axis onto [G, B] cells via the composite
            # cell id grp·(B+1)+broker (the +1 row absorbs unassigned).
            g_rows = 64
            cell = (seg % g_rows) * (brokers + 1) + seg

            def bd(v):
                plane = jax.ops.segment_sum(
                    jnp.ones_like(v), cell,
                    num_segments=g_rows * (brokers + 1))
                return v + plane[cell] * 1e-9
            return loop(bd, x, iters)
        if which == "frac_round":
            # The sparse plan's fractional-target rounding: splitmix
            # hash uniforms per group, then the systematic cumsum-diff
            # rounding over the [G, B] plane (analyzer.direct round 21).
            from ..analyzer.direct import (
                SPARSE_ROUNDING_SEED, _hash_uniform, _round_systematic,
            )
            g_rows = 64
            frac = jnp.abs(jax.random.normal(key, (g_rows, brokers))) * 0.7
            gids = jnp.arange(g_rows, dtype=jnp.int32)

            def bd(v):
                u = _hash_uniform(gids, v[0, 0].astype(jnp.int32),
                                  SPARSE_ROUNDING_SEED)
                t = _round_systematic(frac + v * 1e-9, u)
                return v + t * 1e-9
            return loop(bd, frac, iters)
        if which == "stride_sort":
            # The mesh rank layout's extra cost over plain segsort: the
            # composite (key·stride + block) two-key sort PLUS the
            # second group-ordinal sort frame (analyzer.direct round
            # 21, rank_stride treatment).
            stride = 8
            idx = jnp.arange(n_flat, dtype=jnp.int32)
            blk = idx % stride
            ck = seg.astype(jnp.int32) * stride + blk

            def bd(v):
                cs, cv, ci = jax.lax.sort((ck, v, idx), num_keys=2)
                gb = (cs // stride) * stride + blk[ci]
                gs, _gv, _gi = jax.lax.sort((gb, cv, ci), num_keys=2)
                return v + cv * 1e-9 + (gs[:1] - gs[:1]).astype(v.dtype)
            return loop(bd, x, iters)
        if which == "stride_sort_fused":
            # Fused composite-key variant of stride_sort: the weight is
            # quantized to 11 bits and packed under the (key·stride +
            # block) composite, so ONE single-key sort frame yields the
            # interleaved order — ties inside a quantization bucket
            # break by index, which the solver tolerates (ordering
            # within an epsilon band is already arbitrary).
            stride = 8
            idx = jnp.arange(n_flat, dtype=jnp.int32)
            blk = idx % stride
            ck = seg.astype(jnp.int32) * stride + blk

            def bd(v):
                q = (v * 1024.0).astype(jnp.int32)
                fk = ck * 2048 + (q & 2047)
                fs, fv, _fi = jax.lax.sort((fk, v, idx), num_keys=1)
                return v + fv * 1e-9 + (fs[:1] - fs[:1]).astype(v.dtype)
            return loop(bd, x, iters)
        if which == "accept_flat":
            return loop(lambda v: v + 1e-9 * accept_terms(
                lambda f: v[f][src_n], lambda f: v[f][dst_n]), x, iters)
        if which == "accept_margin":
            return loop(lambda v: v + 1e-9 * accept_terms(
                lambda f: grid.from_rows(v[f][grid.row_src]),
                lambda f: grid.from_dst(v[f][grid.dst_margin])), x, iters)
        if which == "accept_packed":
            def bd(v):
                t = v.T                                  # [B, F]
                rs, ds = t[grid.row_src], t[grid.dst_margin]  # rows of F
                return v + 1e-9 * accept_terms(
                    lambda f: grid.from_rows(rs[:, f]),
                    lambda f: grid.from_dst(ds[:, f]))
            return loop(bd, x, iters)
        if which in ("bbest_segment", "bbest_dense", "bbest_rows"):
            form = which.split("_")[1]
            return loop(lambda v: v + fold(best_two(v, form)), x, iters)
        if which == "bbest_sort":
            return loop(lambda v: v + fold(best_two_sorted(v)), x, iters)
        if which in ("bcount_segment", "bcount_dense"):
            form = which.split("_")[1]
            return loop(lambda v: v + fold(
                [broker_count(v > 0.0, bseg, brokers, form)]), x, iters)
        if which == "deltas_flat":
            return loop(lambda v: deltas_step(v, None), x, iters)
        if which == "deltas_margin":
            return loop(lambda v: deltas_step(v, grid.layout), x, iters)
        if which.startswith(("flat_sort", "flat_two")):
            k = int(which.removeprefix("flat_sort").removeprefix("flat_two"))
            row_len = flat_topk_row_len(n_flat, k)
            top = (lambda v: jax.lax.top_k(v, k)) if "sort" in which else \
                (lambda v: two_level_top_k(v, k, row_len))
            return loop(lambda v: v + fold(top(v)), x, iters)
        if which == "scatter_apply":
            # one-shot scatter apply of a full mover batch onto [P, S].
            plane = jnp.zeros((partitions, s), jnp.int32)
            rows = jnp.arange(n_flat, dtype=jnp.int32) // s
            cols = jnp.arange(n_flat, dtype=jnp.int32) % s

            def bd(v):
                sel = v > 0
                r = jnp.where(sel, rows, partitions)
                upd = plane.at[r, cols].set(seg.astype(jnp.int32),
                                            mode="drop")
                return v + upd[0, 0].astype(v.dtype) * 1e-9
            return loop(bd, x, iters)
        raise ValueError(which)

    inputs = {"topk128": w, "topk1024": w, "approx1024": w, "segsum": w,
              "segmax": w, "gather_grid": gscore, "scatter_m": loads,
              "elemwise": w, "pairwise_m": mvals, "segsort": w,
              "rankfill": w, "scatter_apply": w, "cell_segsum": w,
              "frac_round": w, "stride_sort": w, "stride_sort_fused": w,
              "accept_flat": tables, "accept_margin": tables,
              "accept_packed": tables, "bbest_segment": w, "bbest_dense": w,
              "bbest_sort": w, "bbest_rows": w, "bcount_segment": w,
              "bcount_dense": w,
              "deltas_flat": shift0, "deltas_margin": shift0}
    inputs.update({name: w for name in CASE_NAMES
                   if name.startswith("flat_")})
    return run, inputs


def run_microbench(brokers: int = 1000, partitions: int = 100_000,
                   iters: int = 16,
                   cases: tuple[str, ...] | None = None,
                   quarter: int = 64) -> dict:
    """Measure each op class's marginal ms/iteration inside a fused
    while_loop at (brokers, partitions) scale (``quarter``: the brokers
    the ``bbest_*`` classes keep, 64 on the narrow grid). Returns
    ``{platform, brokers, partitions, iters, results: {case: ms_per_iter
    | {"error": ...}}}`` — a failed class records its error and the rest
    keep running (the same per-case isolation as the CLI tool)."""
    import jax

    run, inputs = _build_cases(brokers, partitions, quarter)
    results: dict[str, float | dict] = {}
    for name in (cases or CASE_NAMES):
        if name not in inputs:
            results[name] = {"error": f"unknown case {name!r}"}
            continue
        x = inputs[name]
        try:
            # Warm EACH timed variant (iters is static: k and 2k are
            # separate compilations a smaller warmup would not cover).
            jax.block_until_ready(run(x, iters, name))
            jax.block_until_ready(run(x, 2 * iters, name))
            t0 = time.monotonic()
            jax.block_until_ready(run(x, iters, name))
            t1 = time.monotonic()
            jax.block_until_ready(run(x, 2 * iters, name))
            t2 = time.monotonic()
            results[name] = round(
                ((t2 - t1) - (t1 - t0)) / iters * 1e3, 4)
        except Exception as e:  # noqa: BLE001 — per-case isolation
            results[name] = {"error": f"{type(e).__name__}: {e}"}
    return {"platform": jax.devices()[0].platform,
            "brokers": int(brokers), "partitions": int(partitions),
            "iters": int(iters), "quarter": int(quarter),
            "unit": "ms_per_iter", "results": results}


# The plan sizes the benchmark's cells serve in a body (100-broker
# rebalance, 250-broker rebalance, 1,000-broker rebalance).
RENDER_ROWS = (6369, 16275, 72470)


def run_render_bench(seed: int = 0, rows: tuple[int, ...] = RENDER_ROWS,
                     repeats: int = 5) -> dict:
    """Host-side, no device: a served body's ``"proposals"`` written from
    a plan in columns both ways, a dict a move then ``json.dumps``
    (``responses.proposal_dicts``, the render before the text) and the
    columns' own text (``ProposalColumns.to_json``), on synthetic columns
    at RF 3 over 1,000 brokers drawn from ``seed``. Median ms of
    ``repeats`` a form and size, the collector off (what its pauses cost
    depends on the heap around the call); ``equal`` says the bytes are."""
    import gc
    import json
    import statistics

    import numpy as np

    from ..analyzer.proposals import ProposalColumns
    from ..api.responses import proposal_dicts

    def dicts(plan):
        return json.dumps(proposal_dicts(plan), separators=(",", ":")).encode()

    rng = np.random.default_rng(seed)
    results = {}
    for n in rows:
        b0 = rng.integers(0, 1000, n)
        d1 = rng.integers(1, 1000, n)
        d2 = rng.integers(1, 999, n)
        d2 += d2 >= d1
        old = np.stack([b0, (b0 + d1) % 1000, (b0 + d2) % 1000], axis=1)
        new = np.roll(old, 1, axis=1)
        index = [(f"topic-{p // 1000}", p % 1000) for p in range(2 * n)]
        plan = ProposalColumns(
            partition_index=index,
            rows=np.sort(rng.choice(2 * n, n, replace=False)),
            old_replicas=old, new_replicas=new, old_leader=old[:, 0],
            new_leader=new[:, 0], data_to_move_mb=np.zeros(n))
        timed, texts = {}, {}
        was_enabled = gc.isenabled()
        gc.disable()
        try:
            for name, write in (("dicts", dicts),
                                ("text", ProposalColumns.to_json)):
                seconds = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    texts[name] = write(plan)
                    seconds.append(time.perf_counter() - t0)
                timed[name] = round(statistics.median(seconds) * 1e3, 3)
        finally:
            if was_enabled:
                gc.enable()
        results[str(n)] = {**timed,
                           "equal": texts["dicts"] == texts["text"]}
    return {"seed": int(seed), "repeats": int(repeats), "unit": "ms",
            "results": results}


if __name__ == "__main__":
    import argparse
    import json

    parser = argparse.ArgumentParser(
        description="host-side cases of the microbench (the device classes "
                    "run through tools/microbench_device.py)")
    parser.add_argument("case", choices=["render"])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--repeats", type=int, default=5)
    args = parser.parse_args()
    print(json.dumps(run_render_bench(args.seed, repeats=args.repeats)))
