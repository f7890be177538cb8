"""Batched top-k search engine: prune → compact → MXU candidate pass.

This module is the single entry point for batched leaf-cascade search.  It
consumes the precomputed per-(query, leaf) pruning inputs — summarization
lower bounds ``d_lb`` and conformal-adjusted filter predictions ``d_F``
(−inf ⇒ never prunes) — plus the flat leaf layout, and produces top-k
ids/dists and the paper's pruning counters.  ``search.py`` (single device),
``distributed.py`` (per-shard body under shard_map) and the serving drivers
all route through here instead of owning their own copies of the masked-scan
pattern.

Two strategies over identical semantics:

* ``strategy="scan"`` — every leaf's distances are computed, in one jitted
  fixed-shape program of two phases: a leaf-major pass reads each leaf's
  slab once for the whole batch (a contiguous ``dynamic_slice`` at the
  leaf's start; the series is leaf-sorted) and keeps its k smallest
  distances per query, then :func:`replay_cascade` replays the exact
  cascade over those summaries in each query's lower-bound order: the
  compact strategy's phase 3 with every leaf a survivor and no host
  round-trip.  Wall-clock is O(all leaves) regardless of how well the
  cascade prunes; it is the shard_map-safe form (compaction needs
  data-dependent shapes, which cannot live under jit).  The ``trace`` and
  ``audit`` variants keep the sequential per-query cascade, which the
  tests pin bitwise against the two-phase program.

* ``strategy="compact"`` — three phases, so compute shrinks with the pruning
  ratio:

    1. *mask*: scan the single best-lb leaf per query (the leaf the
       sequential cascade always scans first) to seed a best-so-far ``bsf0``,
       then keep only leaves with ``d_lb ≤ bsf0`` and ``d_F ≤ bsf0``.  Since
       the sequential cascade's bsf only decreases after the first leaf,
       these survivors are a superset of the leaves the scan strategy scans.
    2. *compact*: gather the survivors' rows into dense per-query candidate
       slabs.  Queries are bucketed by survivor count (rounded up to powers
       of two) so padding waste is bounded and the jit cache is keyed on a
       bounded set of bucket shapes; each bucket walks its slab in
       fixed-size leaf chunks to bound the gathered working set.
    3. *candidates*: one batched distance pass over the slabs through
       ``kernels.l2_scan`` (``matmul`` impl = the pairwise-L2 kernel's
       ‖q‖²+‖s‖²−2qs decomposition, a batched GEMM on the MXU) and one
       ``lax.top_k`` per (query, leaf), followed by an exact *replay* of the
       bsf cascade over the per-leaf top-k summaries.  The replay makes the
       same prune/scan decisions — and, with the ``direct`` distance impl
       (the off-TPU default), returns bitwise-identical top-k ids/dists and
       counters — as ``strategy="scan"``, because merging a leaf's k
       smallest distances is equivalent to merging all of them, and every
       leaf the sequential cascade scans is available (the phase-1 superset
       guarantee; the probe's leaf-0 values are reused verbatim so the two
       bsf trajectories coincide exactly).  The TPU-default ``matmul`` impl
       trades bitwise parity for MXU throughput: decisions and results then
       match scan to float tolerance only (z-normalized series sit exactly
       where ‖q‖²+‖s‖²−2qs cancels), the same trade the ``l2_scan`` kernel
       itself makes.

Cost model: scan is Q·L·R·m multiply-adds (R = max leaf size) and reads
L·R·m series values per batch (each leaf once; a per-query walk reads
Q·L·R·m), plus Q·L·k merge work in the replay; compact is Q·R·m (probe) +
Σ_q C_q·R·m (candidates) + Q·L·k merge work, with C_q the survivor count —
i.e. the heavy term scales with (1 − pruning ratio).
Measured with the earlier per-query scan (benchmarks/engine_bench.py, CPU,
50k×128 randwalk, L=512, Q=32, k=5, experiments/engine_bench.json): scan
stays flat at 206–225 ms across the sweep while compact tracks the pruning
ratio — 158 ms at ratio 0.65 (lower bounds only, 1.31×), 133 ms at 0.67
(1.68×), 44 ms at 0.88 (5.1×), 29 ms at 0.97 (7.9×), 26 ms at 0.98 (8.5×).
In the adversarial all-leaves-survive case (tests/test_engine.py) compact
degrades to scan-plus-probe-overhead instead of winning.

The reported ``searched``/``pruned_*`` counters follow the paper's
searched-leaf accounting of the sequential cascade (both strategies agree
exactly); ``computed`` additionally reports how many leaves the compact
engine actually paid distance compute for (the phase-1 superset).

The same leaf-slab layer serves the *build* side (paper Alg. 1 steps 2–5):
``nn_distance_all_leaves`` / ``nn_distance_own_leaf`` are the batched
training-target passes filter_training routes through (no per-leaf Python
loops), and ``replay_cascade`` is the one copy of the bsf cascade that
conformal calibration replays on precollected matrices.  The compact search
path additionally accepts ``dist_impl="pairwise"``: each bucket's survivor
leaves union into one shared slab scored by the ``l2_scan`` Pallas kernel
all-pairs (ROADMAP follow-up; float-tolerance parity like ``matmul``).

The distributed per-shard body gets the same prune→compact economics from
``compact_bsf_cascade``: a fixed-width variant of the compaction that is
legal *inside* ``shard_map``, where the bucketing above (data-dependent
shapes, host-side counts) is not.  Survivor leaf ids compact into one
static ``max_survivors``-capacity buffer per query (stable argsort
selection), the buffer is scored through the same batched candidate
primitives, and :func:`replay_cascade` replays the exact cascade from the
collective bsf seed — bitwise-identical to ``masked_bsf_scan`` under the
``direct`` impl.  The static-shape trade: capacity is paid whether or not
survivors fill it, and queries whose survivors overflow the capacity fall
back to the masked scan (one ``lax.cond``), keeping semantics exact.
``distributed._make_shard_body`` routes through it by default.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import sanitize
from ..kernels.l2_scan import ops as l2_ops
from ..obs import audit as obs_audit
from ..obs.audit import AuditParts, FilterAudit
from ..obs.trace import CascadeTrace, select as _trace_select, zero_trace

_INF = jnp.float32(jnp.inf)

# gathered candidate working-set target per bucket chunk (bytes of f32 rows);
# chunks are derived from it and rounded to powers of two → bounded jit cache.
# Small enough that the gathered chunk stays cache-resident: the chunk is
# consumed (distance + per-leaf top-k) immediately inside the fori_loop, so
# a larger target only adds memory traffic (measured 3× slower at 128 MiB).
_CHUNK_BYTES = 4 << 20

# leaves per loop step of the scan strategy's leaf-major pass (an unrolled
# run; the work per leaf is unchanged).  Measured on a TPU v5e at both
# benchmark cells' shapes, the pass plus replay took 430 → 333 ms
# (Q=64, R=256, m=96, L=16,389, k=10) and 49 → 44 ms (Q=16, m=256,
# L=4,097, k=1) from 1 to 4: the loop's per-step cost is amortised.
_LEAVES_PER_STEP = 4


@dataclasses.dataclass
class EngineResult:
    topk_d: jnp.ndarray          # (Q, k)
    topk_i: jnp.ndarray          # (Q, k) row ids into the flat series (−1 pad)
    n_searched: jnp.ndarray      # (Q,) cascade accounting (paper metric)
    n_pruned_lb: jnp.ndarray     # (Q,)
    n_pruned_filter: jnp.ndarray  # (Q,)
    n_computed: jnp.ndarray      # (Q,) leaves distance-computed (≥ n_searched)
    trace: Optional[CascadeTrace] = None  # run_cascade(trace=True) flight data
    audit: Optional[FilterAudit] = None   # run_cascade(audit=True) leaf health


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# strategy="scan" — every leaf scored, no host round-trip (the jit-safe form,
# since compaction needs data-dependent shapes)
# ---------------------------------------------------------------------------


def _leafwise_topk(series, leaf_start, leaf_size, queries, kk, max_leaf):
    """Every leaf's kk smallest distances to every query, leaf-major.

    ``_LEAVES_PER_STEP`` leaves per loop step; each leaf's slab is one
    contiguous ``dynamic_slice`` at a scalar start (the series is
    leaf-sorted), read once for the whole batch and scored in the direct
    form of the sequential cascade.  A slab gathered per query instead
    lowers on the TPU to one serial copy per query.  Returns (vals (Q, L,
    kk), ids (Q, L, kk)): +inf / −1 past a leaf's size, ties toward the
    lower row (``l2_ops.leaf_topk``'s order).
    """
    row_ids = jnp.arange(max_leaf)

    def step(_, leaf):
        start = leaf_start[leaf]
        slab = jax.lax.dynamic_slice_in_dim(series, start, max_leaf, 0)
        diff = slab[None] - queries[:, None, :]
        d = jnp.sqrt((diff * diff).sum(-1))                   # (Q, R)
        d = jnp.where(row_ids < leaf_size[leaf], d, _INF)
        neg, arg = jax.lax.top_k(-d, kk)
        ids = jnp.where(jnp.isfinite(neg), start + arg, -1)
        return None, (-neg, ids.astype(jnp.int32))

    _, (vals, ids) = jax.lax.scan(step, None,
                                  jnp.arange(leaf_start.shape[0]),
                                  unroll=_LEAVES_PER_STEP)
    return vals.transpose(1, 0, 2), ids.transpose(1, 0, 2)


@functools.partial(jax.jit,
                   static_argnames=("k", "max_leaf", "trace", "audit"))
def _scan_cascade(series, leaf_start, leaf_size, queries, d_lb, d_F,
                  bsf_ub, k, max_leaf, trace=False, audit=False):
    order = jnp.argsort(d_lb, axis=1)
    if not (trace or audit):
        # the compact strategy's two phases with every leaf a survivor:
        # each leaf's k smallest for the whole batch, then the exact
        # cascade replayed over them in each query's lb order.
        leaf_d, leaf_i = _leafwise_topk(series, leaf_start, leaf_size,
                                        queries, min(k, max_leaf), max_leaf)
        return _replay_cascade(leaf_d, leaf_i, d_lb, d_F, order, k=k,
                               bsf_ub=bsf_ub)

    # the instrumented variants keep the sequential per-query cascade; the
    # parity tests pin it bitwise against the two-phase program above.
    row_ids = jnp.arange(max_leaf)
    L = d_lb.shape[1]

    def per_query(q, lb_row, dF_row, order_row, ub):
        def step(carry, leaf):
            # the replay's decisions (see _replay_cascade for why ub enters
            # the lb test only) over the leaf's full distance block, plus
            # three masked-sum counters (box/seed split of the lb prune and
            # the exact distance rows consulted) and the per-leaf decision
            # planes (visit order) for the FilterAudit reduction: d.min()
            # over the masked slab is the leaf's exact NN distance when
            # scanned — a free byproduct of the distance pass — and +inf
            # when pruned.
            topk_d, topk_i, n_s, n_plb, n_pf, n_box, n_seed, n_rows = carry
            bsf = topk_d[-1]
            p_lb = lb_row[leaf] > jnp.minimum(bsf, ub)
            p_box = lb_row[leaf] > bsf
            p_seed = jnp.logical_and(p_lb, ~p_box)
            p_f = jnp.logical_and(~p_lb, dF_row[leaf] > bsf)
            pruned = p_lb | p_f
            start = leaf_start[leaf]
            slab = jax.lax.dynamic_slice_in_dim(series, start, max_leaf, 0)
            diff = slab - q[None, :]
            d = jnp.sqrt((diff * diff).sum(-1))
            d = jnp.where((row_ids < leaf_size[leaf]) & ~pruned, d, _INF)
            ids = (start + row_ids).astype(jnp.int32)
            alld = jnp.concatenate([topk_d, d])
            alli = jnp.concatenate([topk_i, ids])
            neg_top, arg = jax.lax.top_k(-alld, k)
            rows = jnp.where(pruned, 0, leaf_size[leaf]).astype(jnp.int32)
            ys = (p_box, p_seed, p_f, ~pruned, d.min())
            return (-neg_top, alli[arg],
                    n_s + (~pruned).astype(jnp.int32),
                    n_plb + p_lb.astype(jnp.int32),
                    n_pf + p_f.astype(jnp.int32),
                    n_box + p_box.astype(jnp.int32),
                    n_seed + p_seed.astype(jnp.int32),
                    n_rows + rows), ys

        init = (jnp.full((k,), _INF), jnp.full((k,), -1, jnp.int32),
                jnp.int32(0), jnp.int32(0), jnp.int32(0),
                jnp.int32(0), jnp.int32(0), jnp.int32(0))
        out, ys = jax.lax.scan(step, init, order_row)
        if not audit:
            return out
        vb, vs, vf, vk, vnn = ys                  # (L,) in visit order

        def scat(v, fill):
            base = jnp.full((L,), fill, v.dtype)
            return base.at[order_row].set(v)      # order is a permutation

        parts = AuditParts(scat(vb, False), scat(vs, False),
                           scat(vf, False), scat(vk, False),
                           scat(vk, False), scat(vnn, _INF))
        return out + (parts,)

    return jax.vmap(per_query)(queries, d_lb, d_F, order, bsf_ub)


# ---------------------------------------------------------------------------
# strategy="compact" — phase 2/3 pieces
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("kk", "max_leaf", "chunk", "dist_impl"))
def _bucket_leaf_topk(series, leaf_start, leaf_size, queries_b, leaf_b,
                      kk, max_leaf, chunk, dist_impl):
    """Per-leaf k-smallest distances for a compacted survivor bucket.

    queries_b: (Qb, m); leaf_b: (Qb, C) survivor leaf ids, C a multiple of
    ``chunk``; invalid slots carry leaf id == L (one past the end) so their
    gathers clamp harmlessly and their scatters drop.  Returns
    (vals (Qb, C, kk), ids (Qb, C, kk)) with +inf/−1 in invalid slots.
    """
    Qb, C = leaf_b.shape
    L = leaf_start.shape[0]
    row_ids = jnp.arange(max_leaf)

    def step(i, acc):
        vals_acc, ids_acc = acc
        lf = jax.lax.dynamic_slice_in_dim(leaf_b, i * chunk, chunk, 1)
        valid = lf < L                                   # (Qb, c)
        starts = leaf_start[jnp.minimum(lf, L - 1)]
        sizes = jnp.where(valid, leaf_size[jnp.minimum(lf, L - 1)], 0)
        rows = starts[..., None] + row_ids               # (Qb, c, R)
        slabs = series[rows]                             # (Qb, c, R, m)
        d = l2_ops.gathered_leaf_l2(queries_b, slabs, dist_impl)
        d = jnp.where(row_ids < sizes[..., None], d, _INF)
        vals, ids = l2_ops.leaf_topk(d, rows, kk)
        ids = jnp.where(jnp.isfinite(vals), ids, -1)
        vals_acc = jax.lax.dynamic_update_slice_in_dim(vals_acc, vals,
                                                       i * chunk, 1)
        ids_acc = jax.lax.dynamic_update_slice_in_dim(ids_acc, ids,
                                                      i * chunk, 1)
        return vals_acc, ids_acc

    init = (jnp.full((Qb, C, kk), _INF), jnp.full((Qb, C, kk), -1, jnp.int32))
    return jax.lax.fori_loop(0, C // chunk, step, init)


@functools.partial(jax.jit, static_argnames=("k", "trace"))
def _replay_cascade(leaf_d, leaf_i, d_lb, d_F, order, k, bsf0=None,
                    leaf_valid=None, bsf_ub=None, trace=False):
    """Jitted body of :func:`replay_cascade` — see the wrapper's docstring.

    Identical decision logic and merge arithmetic to the sequential cascade
    (``_scan_cascade``'s instrumented variants) — the k smallest of (running
    top-k ∪ a leaf's k smallest) equal the k smallest of (running top-k ∪
    all the leaf's distances), and ties resolve the same way because the
    running top-k precedes the leaf block in both concats — but each step
    merges k values instead of computing max_leaf·m distances.

    This is the single copy of the bsf cascade's decision logic: the compact
    search strategy runs it over gathered candidate summaries, the scan
    strategy over every leaf's summaries, conformal calibration
    (``conformal.simulate_search``) runs it with k=1 over the
    precollected d_L matrices, and the distributed fixed-width compaction
    (``compact_bsf_cascade``) runs it with k=1 from a collective bsf seed —
    no series data touched.

    bsf0: optional (Q,) best-so-far seed — enters the running top-k as one
    phantom candidate (id −1), matching ``masked_bsf_scan``'s scalar-bsf
    init for k=1.  leaf_valid: optional (L,) mask; invalid (shard-padding)
    leaves are lb-pruned unconditionally, exactly as the masked scan treats
    ``leaf_size == 0``.  bsf_ub: optional (Q,) prune-only upper bound on the
    true k-th NN distance (see ``run_cascade``) — tightens the *lower-bound*
    prune via ``min(bsf, ub)`` without ever entering the learned-filter test
    or the top-k merge.
    """
    invalid = (jnp.zeros(leaf_d.shape[1], bool) if leaf_valid is None
               else ~jnp.asarray(leaf_valid))
    if bsf0 is None:
        bsf0 = jnp.full(leaf_d.shape[0], _INF)
    if bsf_ub is None:
        bsf_ub = jnp.full(leaf_d.shape[0], _INF)

    def per_query(ld, li, lb_row, dF_row, order_row, b0, ub):
        def step(carry, leaf):
            topk_d, topk_i, n_s, n_plb, n_pf = carry
            # lb-prune against min(bsf, ub): ub is a proven upper bound on
            # the true k-th NN distance (see run_cascade's bsf_ub contract),
            # so a leaf with lb > min(bsf, ub) holds no top-k member —
            # pruning it cannot change the answer, only the searched count.
            # The learned-filter test stays against the witnessed bsf only:
            # conformal offsets are calibrated against the unseeded cascade
            # (where the best-lb leaf is visited at bsf = INF), so tightening
            # d_F's threshold with ub would break the recall contract.
            bsf = topk_d[-1]
            p_lb = jnp.logical_or(lb_row[leaf] > jnp.minimum(bsf, ub),
                                  invalid[leaf])
            p_f = jnp.logical_and(~p_lb, dF_row[leaf] > bsf)
            pruned = p_lb | p_f
            vals = jnp.where(pruned, _INF, ld[leaf])
            alld = jnp.concatenate([topk_d, vals])
            alli = jnp.concatenate([topk_i, li[leaf]])
            neg_top, arg = jax.lax.top_k(-alld, k)
            return (-neg_top, alli[arg],
                    n_s + (~pruned).astype(jnp.int32),
                    n_plb + p_lb.astype(jnp.int32),
                    n_pf + p_f.astype(jnp.int32)), None

        def step_traced(carry, leaf):
            # mirrors `step` plus the box/seed split of the lb prune
            # (invalid shard-padding leaves count as box-pruned).
            topk_d, topk_i, n_s, n_plb, n_pf, n_box, n_seed = carry
            bsf = topk_d[-1]
            p_lb = jnp.logical_or(lb_row[leaf] > jnp.minimum(bsf, ub),
                                  invalid[leaf])
            p_box = jnp.logical_or(lb_row[leaf] > bsf, invalid[leaf])
            p_seed = jnp.logical_and(p_lb, ~p_box)
            p_f = jnp.logical_and(~p_lb, dF_row[leaf] > bsf)
            pruned = p_lb | p_f
            vals = jnp.where(pruned, _INF, ld[leaf])
            alld = jnp.concatenate([topk_d, vals])
            alli = jnp.concatenate([topk_i, li[leaf]])
            neg_top, arg = jax.lax.top_k(-alld, k)
            return (-neg_top, alli[arg],
                    n_s + (~pruned).astype(jnp.int32),
                    n_plb + p_lb.astype(jnp.int32),
                    n_pf + p_f.astype(jnp.int32),
                    n_box + p_box.astype(jnp.int32),
                    n_seed + p_seed.astype(jnp.int32)), None

        if trace:
            init = (jnp.full((k,), _INF).at[0].set(b0),
                    jnp.full((k,), -1, jnp.int32),
                    jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.int32(0), jnp.int32(0))
            out, _ = jax.lax.scan(step_traced, init, order_row)
            return out
        init = (jnp.full((k,), _INF).at[0].set(b0),
                jnp.full((k,), -1, jnp.int32),
                jnp.int32(0), jnp.int32(0), jnp.int32(0))
        (td, ti, n_s, n_plb, n_pf), _ = jax.lax.scan(step, init, order_row)
        return td, ti, n_s, n_plb, n_pf

    return jax.vmap(per_query, in_axes=(0, 0, 0, 0, 0, 0, 0))(
        leaf_d, leaf_i, d_lb, d_F, order, bsf0, bsf_ub)


def replay_cascade(leaf_d, leaf_i, d_lb, d_F, order, k, bsf0=None,
                   leaf_valid=None, bsf_ub=None, trace=False):
    """Exact sequential-cascade replay over per-leaf top-k summaries.

    The single copy of the bsf cascade's decision logic (see
    :func:`_replay_cascade` for the merge-equivalence argument): the compact
    search strategy runs it over gathered candidate summaries, the scan
    strategy over every leaf's summaries, conformal calibration
    (``conformal.simulate_search``) runs it with k=1 over the precollected
    d_L matrices, and the distributed fixed-width compaction
    (``compact_bsf_cascade``) runs it with k=1 from a collective bsf seed.
    Under ``REPRO_CHECKIFY=1`` eager calls run checkify-instrumented
    (``repro.sanitize``); traced calls pass straight through.

    ``trace=True`` (static) appends two ``(Q,)`` counters — the box/seed
    split of ``n_pruned_lb`` at the *replay* stage (``repro.obs.trace``
    module docstring explains how this differs from the mask-stage
    attribution ``run_cascade(trace=True)`` reports) — and is jit-legal;
    ``trace=False`` lowers to the byte-identical program.
    """
    return sanitize.call(_replay_cascade, leaf_d, leaf_i, d_lb, d_F, order,
                         k=k, bsf0=bsf0, leaf_valid=leaf_valid,
                         bsf_ub=bsf_ub, trace=trace)


def _pow2_chunk(per_leaf_bytes: int, cap: int) -> int:
    """Power-of-two leaf-chunk width keeping a per-step working set of
    ``chunk · per_leaf_bytes`` around ~_CHUNK_BYTES (capped at ``cap``; the
    caller pads its leaf axis up to a multiple of the result)."""
    chunk = max(_CHUNK_BYTES // max(per_leaf_bytes, 1), 1)
    chunk = 1 << (int(chunk).bit_length() - 1)           # pow2 floor
    return min(chunk, cap)


def _chunk_for(Qb: int, C: int, max_leaf: int, m: int) -> int:
    """Chunk width for per-query gathered slabs ((Qb, chunk, R, m) f32)."""
    return _pow2_chunk(Qb * max_leaf * m * 4, _next_pow2(C))


def _union_chunk_for(Qb: int, U: int, max_leaf: int, m: int) -> int:
    """Chunk width for the shared union slab: one (chunk·R, m) slab plus a
    (Qb, chunk·R) distance block per step."""
    return _pow2_chunk((max_leaf * m + Qb * max_leaf) * 4, _next_pow2(U))


@functools.partial(jax.jit, static_argnames=("kk", "max_leaf", "chunk"))
def _union_leaf_topk(series, leaf_start, leaf_size, queries_b, leaf_u,
                     kk, max_leaf, chunk):
    """Per-leaf k-smallest distances over a *shared* survivor-leaf union.

    queries_b: (Qb, m); leaf_u: (U,) the union of the bucket's survivor leaf
    ids (padded with L), U a multiple of ``chunk``.  Every query is scored
    against every union leaf through one all-pairs ``l2_scan`` call per chunk
    — the Pallas kernel path on TPU — trading the per-query gather of
    ``_bucket_leaf_topk`` for kernel-tiled MXU sweeps over one shared slab.
    Returns (vals (Qb, U, kk), ids (Qb, U, kk)) with +inf/−1 padding.
    """
    Qb = queries_b.shape[0]
    U = leaf_u.shape[0]

    def step(i, acc):
        vals_acc, ids_acc = acc
        lu = jax.lax.dynamic_slice_in_dim(leaf_u, i * chunk, chunk, 0)
        slabs, rows, valid = l2_ops.gather_leaf_slabs(
            series, leaf_start, leaf_size, lu, max_leaf)
        d = l2_ops.shared_slab_l2(queries_b, slabs, "pairwise")  # (Qb, c, R)
        d = jnp.where(valid[None, :, :], d, _INF)
        vals, ids = l2_ops.leaf_topk(
            d, jnp.broadcast_to(rows[None], d.shape), kk)
        ids = jnp.where(jnp.isfinite(vals), ids, -1)
        vals_acc = jax.lax.dynamic_update_slice_in_dim(vals_acc, vals,
                                                       i * chunk, 1)
        ids_acc = jax.lax.dynamic_update_slice_in_dim(ids_acc, ids,
                                                      i * chunk, 1)
        return vals_acc, ids_acc

    init = (jnp.full((Qb, U, kk), _INF), jnp.full((Qb, U, kk), -1, jnp.int32))
    return jax.lax.fori_loop(0, U // chunk, step, init)


@jax.jit
def _compact_trace_stats(mask, d_lb, bsf0, bsf0m, leaf_size, leaf0):
    """The compact path's whole mask-stage CascadeTrace, as ONE program.

    The compact cascade is host-orchestrated, so writing these ~20 tiny
    ops eagerly dispatches each one separately — a constant ~ms tax that
    blows the obs bench's <5% traced-overhead budget.  Fused here they
    cost one dispatch next to the (Q, L) mask math they mirror.
    """
    not_m = ~mask
    p_box = not_m & (d_lb > bsf0[:, None])
    p_seed = not_m & ~p_box & (d_lb > bsf0m[:, None])
    p_filt = not_m & ~p_box & ~p_seed
    sizes = leaf_size.astype(jnp.int32)
    # distance rows actually paid: the phase-1 probe pass plus every
    # gathered candidate row (the probe leaf is gathered again in its
    # bucket, then overwritten — both passes are real compute).
    dist_rows = (sizes[leaf0[:, 0]]
                 + jnp.where(mask, sizes[None, :], 0).sum(axis=1))
    Q = mask.shape[0]
    return CascadeTrace(
        pruned_box=p_box.sum(axis=1).astype(jnp.int32),
        pruned_seed=p_seed.sum(axis=1).astype(jnp.int32),
        pruned_filter=p_filt.sum(axis=1).astype(jnp.int32),
        probed=jnp.ones((Q,), jnp.int32),
        survivors=(mask.sum(axis=1) - 1).astype(jnp.int32),
        overflow=jnp.zeros((Q,), jnp.int32),
        distances=dist_rows)


@jax.jit
def _compact_audit_parts(mask, d_lb, bsf0, bsf0m, leaf_nn):
    """The compact path's per-(query, leaf) audit planes, as ONE program.

    Same mask-stage partition as ``_compact_trace_stats`` (and the same
    one-dispatch reasoning); ``kept`` is the survivor mask itself (the
    probe leaf included — its rows were paid twice, probe + gather), and
    ``scored`` is every leaf with a finite gathered summary — equal to
    ``kept`` for the per-query gather impls, a superset under the
    pairwise union (co-resident leaves are scored for free).
    """
    not_m = ~mask
    p_box = not_m & (d_lb > bsf0[:, None])
    p_seed = not_m & ~p_box & (d_lb > bsf0m[:, None])
    p_filt = not_m & ~p_box & ~p_seed
    return AuditParts(p_box, p_seed, p_filt, mask,
                      jnp.isfinite(leaf_nn), leaf_nn)


def _compact_cascade(series, leaf_start, leaf_size, queries, d_lb, d_F,
                     bsf_ub, k, max_leaf, dist_impl, trace=False,
                     audit=False):
    Q, m = queries.shape
    L = leaf_start.shape[0]
    kk = min(k, max_leaf)
    order = jnp.argsort(d_lb, axis=1)                    # (Q, L)

    # -- phase 1: probe the best-lb leaf, mask survivors --------------------
    # (the probe is per-query-gathered either way; under dist_impl="pairwise"
    # it uses the same ‖q‖²+‖s‖²−2qs algebra as the shared-slab kernel, and
    # its values are written verbatim below so the replay stays consistent)
    probe_impl = "matmul" if dist_impl == "pairwise" else dist_impl
    leaf0 = order[:, :1]                                 # (Q, 1)
    p_vals, p_ids = sanitize.call(
        _bucket_leaf_topk, series, leaf_start, leaf_size, queries, leaf0,
        kk=kk, max_leaf=max_leaf, chunk=1, dist_impl=probe_impl)
    bsf0 = p_vals[:, 0, k - 1] if k <= kk else jnp.full((Q,), _INF)
    # the replay's effective lb threshold never exceeds min(bsf0, ub) after
    # the first merge, so masking lb against it keeps the phase-1 superset
    # guarantee while letting a tight warm-start bound shrink the survivor
    # set (and with it the gathered candidate compute) before any distance
    # work is paid.  d_F masks against bsf0 alone — the replay's filter test
    # uses the witnessed bsf (≤ bsf0 after the first merge), never ub.
    bsf0m = jnp.minimum(bsf0, bsf_ub)
    mask = (d_lb <= bsf0m[:, None]) & (d_F <= bsf0[:, None])
    mask = mask.at[jnp.arange(Q), leaf0[:, 0]].set(True)

    if trace:
        # mask-stage attribution: partition the non-survivors by the first
        # bound that excluded them (the probe leaf is in `mask`, so it is
        # excluded from the partition and lands in `probed` instead).
        # Partition is exact by construction: ~mask ⇒ d_lb > bsf0m or
        # d_F > bsf0; box takes d_lb > bsf0, seed takes bsf0 ≥ d_lb > bsf0m
        # (excluded only by the warm-start bound), filter takes the rest.
        aux = _compact_trace_stats(mask, d_lb, bsf0, bsf0m, leaf_size, leaf0)
        dist_rows = aux.distances

    # -- phase 2: bucket queries by survivor count, compact leaf lists ------
    counts = np.asarray(mask.sum(axis=1))
    computed = counts.astype(np.int32).copy()            # per-query paid leaves
    # leaf row L is a scratch row: invalid/padded slots aim their scatters at
    # it (in-bounds by construction, so index sanitizers stay quiet) and it
    # is sliced off before the replay.
    leaf_d = jnp.full((Q, L + 1, kk), _INF)
    leaf_i = jnp.full((Q, L + 1, kk), -1, jnp.int32)
    # survivors first, in ascending-lb order (argsort of bool is stable)
    mask_ord = jnp.take_along_axis(mask, order, axis=1)
    sel_all = jnp.argsort(~mask_ord, axis=1)

    buckets: dict[int, list[int]] = {}
    for qi, c in enumerate(counts):
        buckets.setdefault(min(_next_pow2(max(int(c), 1)), L), []).append(qi)

    for C, qis in sorted(buckets.items()):
        Qb = _next_pow2(len(qis))
        qidx = jnp.asarray((qis + [qis[0]] * (Qb - len(qis)))[:Qb])
        pad_q = jnp.arange(Qb) >= len(qis)
        sel = sel_all[qidx][:, :C]                       # (Qb, C)
        valid = jnp.take_along_axis(mask_ord[qidx], sel, axis=1)
        valid = valid & ~pad_q[:, None]
        leaf = jnp.where(valid,
                         jnp.take_along_axis(order[qidx], sel, axis=1), L)
        if dist_impl == "pairwise":
            # union the bucket's survivor leaves into one shared slab and
            # run the all-pairs l2_scan kernel over it; leaves that are not
            # a given query's survivors come along for free but their
            # summaries are never consulted (the replay prunes them — their
            # d_lb/d_F exceeded that query's bsf0, and bsf only decreases).
            leaf_np = np.asarray(leaf)
            uni = np.unique(leaf_np[leaf_np < L])
            if uni.size == 0:
                continue                                 # all-padding bucket
            # every bucket query pays distance compute for the whole union
            computed[qis] = uni.size
            if trace:
                qis_j = jnp.asarray(qis)
                sizes = leaf_size.astype(jnp.int32)
                uni_rows = sizes[jnp.asarray(uni)].sum()
                dist_rows = dist_rows.at[qis_j].set(
                    sizes[leaf0[qis_j, 0]] + uni_rows)
            chunk = _union_chunk_for(Qb, uni.size, max_leaf, m)
            Up = max(_next_pow2(uni.size), chunk)
            leaf_u = jnp.asarray(np.pad(uni, (0, Up - uni.size),
                                        constant_values=L))
            vals, ids = sanitize.call(
                _union_leaf_topk, series, leaf_start, leaf_size,
                queries[qidx], leaf_u, kk=kk, max_leaf=max_leaf, chunk=chunk)
            # padded queries must not scatter: aim their writes at leaf L
            leaf_sc = jnp.where(pad_q[:, None], L, leaf_u[None, :])
        else:
            chunk = _chunk_for(Qb, C, max_leaf, m)
            Cp = -(-C // chunk) * chunk                  # pad C to chunks
            if Cp > C:                                   # invalid-slot pad
                leaf = jnp.pad(leaf, ((0, 0), (0, Cp - C)), constant_values=L)
            vals, ids = sanitize.call(
                _bucket_leaf_topk, series, leaf_start, leaf_size,
                queries[qidx], leaf,
                kk=kk, max_leaf=max_leaf, chunk=chunk, dist_impl=dist_impl)
            leaf_sc = leaf
        # scatter into the (Q, L+1, kk) summaries; leaf==L slots land in the
        # scratch row
        leaf_d = leaf_d.at[qidx[:, None, None], leaf_sc[:, :, None],
                           jnp.arange(kk)[None, None, :]].set(vals)
        leaf_i = leaf_i.at[qidx[:, None, None], leaf_sc[:, :, None],
                           jnp.arange(kk)[None, None, :]].set(ids)

    leaf_d, leaf_i = leaf_d[:, :L], leaf_i[:, :L]        # drop the scratch row

    # reuse the probe's leaf-0 values verbatim: the replay's bsf after the
    # first merge then equals bsf0 bitwise, which is what makes the phase-1
    # survivor mask a true superset of the replayed cascade's scans.
    leaf_d = leaf_d.at[jnp.arange(Q)[:, None, None], leaf0[:, :, None],
                       jnp.arange(kk)[None, None, :]].set(p_vals)
    leaf_i = leaf_i.at[jnp.arange(Q)[:, None, None], leaf0[:, :, None],
                       jnp.arange(kk)[None, None, :]].set(p_ids)

    # -- phase 3: exact cascade replay over the per-leaf summaries ----------
    td, ti, n_s, n_plb, n_pf = replay_cascade(
        leaf_d, leaf_i, d_lb, d_F, order, k=k, bsf_ub=bsf_ub)
    out = (td, ti, n_s, n_plb, n_pf, jnp.asarray(computed))
    if trace:
        if dist_rows is not aux.distances:       # pairwise union accounting
            aux = aux._replace(distances=dist_rows)
        out = out + (aux,)
    if audit:
        # leaf_d already has the scratch row dropped and the probe leaf's
        # values written verbatim, so column 0 is each scored leaf's exact
        # NN distance (+inf where the leaf was never gathered).
        out = out + (_compact_audit_parts(mask, d_lb, bsf0, bsf0m,
                                          leaf_d[:, :, 0]),)
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def run_cascade(
    series: jnp.ndarray,           # (n + max_leaf, m) leaf-sorted, padded
    leaf_start: jnp.ndarray,       # (L,)
    leaf_size: jnp.ndarray,        # (L,)
    queries: jnp.ndarray,          # (Q, m)
    d_lb: jnp.ndarray,             # (Q, L) summarization lower bounds
    d_F: jnp.ndarray,              # (Q, L) adjusted predictions; −inf = keep
    *,
    k: int,
    max_leaf: int,
    strategy: str = "auto",
    dist_impl: Optional[str] = None,
    bsf_ub: Optional[jnp.ndarray] = None,
    trace: bool = False,
    audit: bool = False,
) -> EngineResult:
    """Batched top-k leaf-cascade search over precomputed pruning inputs.

    strategy: "compact" (default via "auto") computes distances only for
    cascade survivors; "scan" scores every leaf in one fixed-shape program
    (the jit-safe fallback).  With
    ``dist_impl="direct"`` (the off-TPU default) both strategies return
    bitwise-identical results; on TPU the default is "matmul" (the
    pairwise-L2 kernel's decomposition, MXU-tiled), which matches scan only
    to float tolerance — pass dist_impl="direct" there if exact replay
    parity matters more than throughput.  See the module docstring for the
    cost model.
    dist_impl: "direct" | "matmul" | "pairwise" | None (backend default) —
    forwarded to the compact candidate pass.  "pairwise" unions each
    bucket's survivor leaves into one shared slab and runs the ``l2_scan``
    Pallas kernel all-pairs over it (kernel-tiled MXU use, float-tolerance
    parity like "matmul"; off-TPU it lowers to the same matmul algebra);
    "direct"/"matmul" gather per-query candidate slabs instead.
    bsf_ub: optional (Q,) per-query *prune-only* upper bound on the true
    k-th NN distance (e.g. the serving runtime's triangle-inequality
    warm-start bound, ``serving.warmstart``).  It tightens the *lower-bound*
    prune via ``min(bsf, ub)`` but never enters the learned-filter test
    (whose conformal offsets are calibrated against the unseeded bsf
    trajectory — a warm threshold there collapses recall) or the top-k
    merge as a candidate.  In exact mode the returned ids/dists are
    therefore bitwise those of an unseeded run — only ``searched``/
    ``computed`` (and wall-clock on the compact strategy) shrink; in
    filtered mode the conformal recall contract is preserved because a leaf
    with lb > ub holds no true top-k member.  +inf entries are the no-op
    seed.
    trace: static flag; True additionally returns a per-query
    :class:`~repro.obs.trace.CascadeTrace` on ``EngineResult.trace``
    (which bound pruned which leaf, survivors, exact distance rows paid —
    see ``repro.obs.trace`` for the attribution semantics and the
    accounting identity).  Results are bitwise-identical either way.  On
    "compact" the flag is a Python-level branch on extra masked-sum
    counters only, so ``trace=False`` lowers to the byte-identical program;
    on "scan" the instrumented run is the sequential per-query cascade and
    the untraced one a different program (leaf-major pass + replay) with
    bitwise-equal results.
    audit: static flag; True additionally returns a per-leaf
    :class:`~repro.obs.audit.FilterAudit` on ``EngineResult.audit`` —
    prune counts by bound, work saved, and prediction-residual statistics
    (``true_leaf_nn − d_F``) for the leaves the engine scored exactly, at
    zero extra distance computations (see ``repro.obs.audit`` for the
    residual semantics and the per-leaf accounting identity).  Same
    discipline as ``trace``: results are bitwise-identical either way, and
    on "scan" ``audit=True`` runs the same sequential cascade as
    ``trace=True``.
    """
    if strategy == "auto":
        strategy = "compact"
    ub = (jnp.full(queries.shape[0], _INF) if bsf_ub is None
          else jnp.asarray(bsf_ub, jnp.float32))
    aux = None
    parts = None
    if strategy == "scan":
        if audit:
            (td, ti, n_s, n_plb, n_pf, n_box, n_seed, n_rows,
             parts) = sanitize.call(
                _scan_cascade, series, leaf_start, leaf_size, queries,
                d_lb, d_F, ub, k=k, max_leaf=max_leaf, trace=trace,
                audit=True)
            if trace:
                zeros = jnp.zeros(queries.shape[0], jnp.int32)
                aux = CascadeTrace(n_box, n_seed, n_pf, zeros, n_s, zeros,
                                   n_rows)
        elif trace:
            (td, ti, n_s, n_plb, n_pf, n_box, n_seed,
             n_rows) = sanitize.call(
                _scan_cascade, series, leaf_start, leaf_size, queries,
                d_lb, d_F, ub, k=k, max_leaf=max_leaf, trace=True)
            zeros = jnp.zeros(queries.shape[0], jnp.int32)
            aux = CascadeTrace(n_box, n_seed, n_pf, zeros, n_s, zeros,
                               n_rows)
        else:
            td, ti, n_s, n_plb, n_pf = sanitize.call(
                _scan_cascade, series, leaf_start, leaf_size, queries,
                d_lb, d_F, ub, k=k, max_leaf=max_leaf)
        n_c = jnp.full(queries.shape[0], leaf_start.shape[0], jnp.int32)
    elif strategy == "compact":
        out = _compact_cascade(
            series, leaf_start, leaf_size, queries, d_lb, d_F, ub,
            k=k, max_leaf=max_leaf, dist_impl=dist_impl, trace=trace,
            audit=audit)
        td, ti, n_s, n_plb, n_pf, n_c = out[:6]
        rest = list(out[6:])
        if trace:
            aux = rest.pop(0)
        if audit:
            parts = rest.pop(0)
    else:
        raise ValueError(f"unknown engine strategy {strategy!r}")
    fa = None
    if audit:
        fa = obs_audit.reduce_parts(parts, jnp.asarray(d_F, jnp.float32),
                                    leaf_size)
    return EngineResult(td, ti, n_s, n_plb, n_pf, n_c, aux, fa)


# ---------------------------------------------------------------------------
# leaf-slab build passes (filter_training's training-data collection)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("max_leaf", "chunk", "dist_impl"))
def _all_leaves_min(series, leaf_start, leaf_size, queries,
                    max_leaf, chunk, dist_impl):
    Q = queries.shape[0]
    L = leaf_start.shape[0]
    Lp = -(-L // chunk) * chunk
    leaf_ids = jnp.arange(Lp)                            # ids ≥ L are padding

    def step(i, out):
        lu = jax.lax.dynamic_slice_in_dim(leaf_ids, i * chunk, chunk, 0)
        slabs, _, valid = l2_ops.gather_leaf_slabs(
            series, leaf_start, leaf_size, lu, max_leaf)
        d = l2_ops.shared_slab_l2(queries, slabs, dist_impl)  # (Q, c, R)
        dmin = jnp.where(valid[None, :, :], d, _INF).min(-1)  # (Q, c)
        return jax.lax.dynamic_update_slice_in_dim(out, dmin, i * chunk, 1)

    out = jax.lax.fori_loop(0, Lp // chunk, step, jnp.full((Q, Lp), _INF))
    return out[:, :L]


def nn_distance_all_leaves(
    series: jnp.ndarray,
    leaf_start: jnp.ndarray,
    leaf_size: jnp.ndarray,
    queries: jnp.ndarray,          # (Q, m)
    *,
    max_leaf: int,
    dist_impl: Optional[str] = None,
    chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Min distance from every query to every leaf → (Q, L).

    The build side's "first pass" (paper Alg. 1 target collection), as one
    jitted sweep over the padded leaf-slab layer: leaves stream through in
    cache-resident chunks (same budget as the compact engine's candidate
    buckets), each scored by ``shared_slab_l2`` — the ``pairwise`` Pallas
    kernel on TPU, its matmul decomposition elsewhere — and masked-min
    reduced.  No per-leaf Python iteration, no per-leaf retracing.
    """
    Q, m = queries.shape
    L = leaf_start.shape[0]
    dist_impl = dist_impl or l2_ops.default_slab_impl()
    if chunk is None:
        chunk = _pow2_chunk((Q * max_leaf + max_leaf * m) * 4,
                            _next_pow2(L))
    return sanitize.call(_all_leaves_min, series, leaf_start, leaf_size,
                         queries, max_leaf=max_leaf, chunk=chunk,
                         dist_impl=dist_impl)


@functools.partial(jax.jit,
                   static_argnames=("max_leaf", "chunk", "dist_impl"))
def _own_leaf_min(series, leaf_start, leaf_size, local_queries, leaf_ids,
                  max_leaf, chunk, dist_impl):
    F, nq, m = local_queries.shape
    L = leaf_start.shape[0]
    Fp = -(-F // chunk) * chunk
    ids_p = jnp.pad(jnp.asarray(leaf_ids), (0, Fp - F), constant_values=L)
    q_p = jnp.pad(local_queries, ((0, Fp - F), (0, 0), (0, 0)))

    def step(i, out):
        ids = jax.lax.dynamic_slice_in_dim(ids_p, i * chunk, chunk, 0)
        qs = jax.lax.dynamic_slice_in_dim(q_p, i * chunk, chunk, 0)
        slabs, _, valid = l2_ops.gather_leaf_slabs(
            series, leaf_start, leaf_size, ids, max_leaf)
        d = l2_ops.slab_l2(qs, slabs, dist_impl)              # (c, nq, R)
        dmin, _ = l2_ops.slab_masked_min(d, valid)            # (c, nq)
        return jax.lax.dynamic_update_slice_in_dim(out, dmin, i * chunk, 0)

    out = jax.lax.fori_loop(0, Fp // chunk, step, jnp.full((Fp, nq), _INF))
    return out[:F]


def nn_distance_own_leaf(
    series: jnp.ndarray,
    leaf_start: jnp.ndarray,
    leaf_size: jnp.ndarray,
    local_queries: jnp.ndarray,    # (F, nq, m) per-leaf query batches
    leaf_ids: jnp.ndarray,         # (F,)
    *,
    max_leaf: int,
    dist_impl: Optional[str] = None,
    chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Min distance of each leaf's own query batch to that leaf → (F, nq).

    The build side's local-query target pass: one jitted sweep where every
    selected leaf's slab is gathered once and scored against its own noisy
    queries via the vmapped slab primitives (``slab_l2`` — the batched
    ``slab_l2_kernel`` Pallas path on TPU).  Replaces the seed's per-leaf
    ``dynamic_slice`` loop, which retraced and dispatched once per filter.
    """
    F, nq, m = local_queries.shape
    dist_impl = dist_impl or l2_ops.default_slab_impl()
    if chunk is None:
        chunk = _pow2_chunk((nq * max_leaf + max_leaf * m + nq * m) * 4,
                            _next_pow2(max(F, 1)))
    return sanitize.call(_own_leaf_min, series, leaf_start, leaf_size,
                         local_queries, jnp.asarray(leaf_ids),
                         max_leaf=max_leaf, chunk=chunk, dist_impl=dist_impl)


# ---------------------------------------------------------------------------
# shard_map-safe pieces shared with distributed.py
# ---------------------------------------------------------------------------


def probe_best_leaf(series, leaf_start, leaf_size, lb, queries, max_leaf):
    """Min distance to each query's best-lb leaf → (Q,) bsf seed.

    jit/shard_map-safe (static shapes); the collective analogue of the
    engine's phase-1 probe, used by the distributed two-phase exchange.
    Zero-size (shard-padding) leaves are skipped defensively: their lb is
    forced to +inf before the argmin, so the probe never lands on an empty
    leaf and wastes the seed on +inf — regardless of whether the caller
    already masked ``lb``.
    """
    lb = jnp.where(leaf_size[None, :] > 0, lb, _INF)
    best_leaf = lb.argmin(axis=1)
    row_ids = jnp.arange(max_leaf)

    def probe(q, leaf):
        slab = jax.lax.dynamic_slice_in_dim(
            series, leaf_start[leaf], max_leaf, 0)
        dd = jnp.sqrt(((slab - q[None]) ** 2).sum(-1))
        return jnp.where(row_ids < leaf_size[leaf], dd, _INF).min()

    return jax.vmap(probe)(queries, best_leaf)


def masked_bsf_scan(series, leaf_start, leaf_size, lb, d_F, queries,
                    max_leaf, bsf0, bsf_ub=None, trace=False, audit=False):
    """Best-so-far cascade over all leaves from a seed bsf → (bsf, n_s).

    The 1-NN, distance-only form of ``strategy="scan"``; leaves with size 0
    are treated as lb-pruned (shard padding).  jit/shard_map-safe — this is
    the per-shard body ``distributed._local_search`` routes through.

    ``bsf_ub``: optional (Q,) prune-only bound (``run_cascade``'s warm-start
    contract) — it tightens the lb test only, never the filter test.  Unlike
    ``bsf0`` it never enters the bsf carry — the returned bsf is always a
    real (witnessed) distance or the seed, never the bound.

    ``trace=True`` (a Python-level flag — still jit/shard_map-safe)
    appends a ``(n_box, n_seed, n_filter, n_rows)`` tuple of ``(Q,)``
    step-level counters (box/seed split of the lb prune, filter prunes,
    distance rows consulted); padding leaves count as box-pruned.

    ``audit=True`` (also Python-level, shard_map-safe) returns
    ``(bsf, n_s, trace_tuple, parts)`` regardless of ``trace`` — the same
    step-level counters plus the per-(query, leaf)
    :class:`~repro.obs.audit.AuditParts` decision planes in leaf order,
    for the :func:`repro.obs.audit.reduce_parts` leafwise reduction.
    """
    row_ids = jnp.arange(max_leaf)
    order = jnp.argsort(lb, axis=1)
    L = lb.shape[1]
    if bsf_ub is None:
        bsf_ub = jnp.full(queries.shape[0], _INF)

    def per_query(q, lb_row, dF_row, order_row, bsf_init, ub):
        def step(carry, leaf):
            bsf, n_s = carry
            valid = leaf_size[leaf] > 0
            p_lb = jnp.logical_or(lb_row[leaf] > jnp.minimum(bsf, ub),
                                  ~valid)
            p_f = jnp.logical_and(~p_lb, dF_row[leaf] > bsf)
            pruned = p_lb | p_f
            slab = jax.lax.dynamic_slice_in_dim(
                series, leaf_start[leaf], max_leaf, 0)
            diff = slab - q[None, :]
            d = jnp.sqrt((diff * diff).sum(-1))
            d = jnp.where((row_ids < leaf_size[leaf]) & ~pruned, d, _INF)
            bsf = jnp.minimum(bsf, d.min())
            return (bsf, n_s + (~pruned).astype(jnp.int32)), None

        def step_traced(carry, leaf):
            # mirrors `step` plus masked-sum trace counters.
            bsf, n_s, n_box, n_seed, n_pf, n_rows = carry
            valid = leaf_size[leaf] > 0
            p_lb = jnp.logical_or(lb_row[leaf] > jnp.minimum(bsf, ub),
                                  ~valid)
            p_box = jnp.logical_or(lb_row[leaf] > bsf, ~valid)
            p_seed = jnp.logical_and(p_lb, ~p_box)
            p_f = jnp.logical_and(~p_lb, dF_row[leaf] > bsf)
            pruned = p_lb | p_f
            slab = jax.lax.dynamic_slice_in_dim(
                series, leaf_start[leaf], max_leaf, 0)
            diff = slab - q[None, :]
            d = jnp.sqrt((diff * diff).sum(-1))
            d = jnp.where((row_ids < leaf_size[leaf]) & ~pruned, d, _INF)
            bsf = jnp.minimum(bsf, d.min())
            rows = jnp.where(pruned, 0, leaf_size[leaf]).astype(jnp.int32)
            return (bsf, n_s + (~pruned).astype(jnp.int32),
                    n_box + p_box.astype(jnp.int32),
                    n_seed + p_seed.astype(jnp.int32),
                    n_pf + p_f.astype(jnp.int32),
                    n_rows + rows), None

        def step_audit(carry, leaf):
            # mirrors `step_traced` and emits the per-leaf decision planes
            # (visit order); d.min() is the leaf's exact NN distance when
            # scanned, +inf when pruned or padding.
            bsf, n_s, n_box, n_seed, n_pf, n_rows = carry
            valid = leaf_size[leaf] > 0
            p_lb = jnp.logical_or(lb_row[leaf] > jnp.minimum(bsf, ub),
                                  ~valid)
            p_box = jnp.logical_or(lb_row[leaf] > bsf, ~valid)
            p_seed = jnp.logical_and(p_lb, ~p_box)
            p_f = jnp.logical_and(~p_lb, dF_row[leaf] > bsf)
            pruned = p_lb | p_f
            slab = jax.lax.dynamic_slice_in_dim(
                series, leaf_start[leaf], max_leaf, 0)
            diff = slab - q[None, :]
            d = jnp.sqrt((diff * diff).sum(-1))
            d = jnp.where((row_ids < leaf_size[leaf]) & ~pruned, d, _INF)
            bsf = jnp.minimum(bsf, d.min())
            rows = jnp.where(pruned, 0, leaf_size[leaf]).astype(jnp.int32)
            ys = (p_box, p_seed, p_f, ~pruned, d.min())
            return (bsf, n_s + (~pruned).astype(jnp.int32),
                    n_box + p_box.astype(jnp.int32),
                    n_seed + p_seed.astype(jnp.int32),
                    n_pf + p_f.astype(jnp.int32),
                    n_rows + rows), ys

        if audit:
            init = (bsf_init, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.int32(0), jnp.int32(0))
            (bsf, n_s, n_box, n_seed, n_pf, n_rows), ys = jax.lax.scan(
                step_audit, init, order_row)
            vb, vs, vf, vk, vnn = ys              # (L,) in visit order

            def scat(v):
                return jnp.zeros((L,), v.dtype).at[order_row].set(v)

            return (bsf, n_s, n_box, n_seed, n_pf, n_rows,
                    scat(vb), scat(vs), scat(vf), scat(vk), scat(vnn))
        if trace:
            init = (bsf_init, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.int32(0), jnp.int32(0))
            (bsf, n_s, n_box, n_seed, n_pf, n_rows), _ = jax.lax.scan(
                step_traced, init, order_row)
            return bsf, n_s, n_box, n_seed, n_pf, n_rows
        (bsf, n_s), _ = jax.lax.scan(step, (bsf_init, jnp.int32(0)),
                                     order_row)
        return bsf, n_s

    out = jax.vmap(per_query)(queries, lb, d_F, order, bsf0, bsf_ub)
    if audit:
        bsf, n_s, n_box, n_seed, n_pf, n_rows, pb, ps, pf_, kept, nn = out
        parts = AuditParts(pb, ps, pf_, kept, kept, nn)
        return bsf, n_s, (n_box, n_seed, n_pf, n_rows), parts
    if trace:
        bsf, n_s, n_box, n_seed, n_pf, n_rows = out
        return bsf, n_s, (n_box, n_seed, n_pf, n_rows)
    return out


def default_max_survivors(n_leaves: int) -> int:
    """Default fixed survivor capacity for ``compact_bsf_cascade``.

    An eighth of the shard's leaf slots, rounded up to a power of two: small
    enough that the candidate pass beats the masked scan by ~8× at high
    pruning ratios, large enough that well-calibrated cascades rarely
    overflow into the scan fallback.  :func:`tuned_max_survivors` replaces
    this static guess with a percentile of observed survivor counts.
    """
    return min(_next_pow2(max(n_leaves // 8, 1)), _next_pow2(n_leaves))


def tuned_max_survivors(survivor_counts, n_leaves: int,
                        pct: float = 99.0, min_samples: int = 0) -> int:
    """Survivor capacity from observed per-query survivor-count statistics.

    The ``pct``-th percentile of the observed counts, rounded up to a power
    of two (the rounding is the drift headroom), clamped to
    [1, next_pow2(n_leaves)] like the static default.  At matched traffic
    the overflow-fallback frequency is then bounded by ~(100 − pct)% by
    construction instead of hoping the P/8 default fits the workload
    (tests/test_serving.py pins the bound on a drifting distribution).  The
    serving runtime feeds this from its rolling survivor-count window
    (``serving.telemetry.Telemetry.suggest_max_survivors``); with no
    observations yet it degrades to :func:`default_max_survivors`.

    ``min_samples``: below this many observations the ``pct``-th percentile
    of the window is statistically meaningless (e.g. the p99 of 5 samples is
    just their max-ish), and a handful of easy early queries would lock in
    an unstable *low* capacity that overflow-falls-back on the first hard
    one.  Cold-start calls therefore floor the estimate at the configured
    :func:`default_max_survivors` until the window has filled — the
    estimate can tighten traffic upward early, never downward.
    """
    counts = np.asarray(survivor_counts)
    if counts.size == 0:
        return default_max_survivors(n_leaves)
    cap = int(np.ceil(np.percentile(counts, pct)))
    cap = min(_next_pow2(max(cap, 1)), _next_pow2(n_leaves))
    if counts.size < max(int(min_samples), 0):
        cap = max(cap, default_max_survivors(n_leaves))
    return cap


def compact_bsf_cascade(series, leaf_start, leaf_size, lb, d_F, queries,
                        max_leaf, bsf0, *, max_survivors=None,
                        dist_impl=None, bsf_ub=None, trace=False,
                        audit=False):
    """Fixed-width survivor compaction form of ``masked_bsf_scan``.

    Same contract — 1-NN bsf cascade from a seed ``bsf0`` over all leaves,
    returning (bsf (Q,), n_searched (Q,)) — but distance compute is paid
    only for a fixed-capacity buffer of cascade survivors, so the shapes
    stay fully static and the whole thing is legal *inside* ``shard_map``
    (where the single-device engine's data-dependent bucketing is not):

      1. mask survivors (``lb ≤ bsf0``, ``d_F ≤ bsf0``, ``leaf_size > 0``;
         since the cascade's bsf only decreases from ``bsf0``, survivors are
         a superset of the leaves the masked scan actually scans);
      2. compact survivor leaf ids, ascending-lb first, into a static
         ``max_survivors``-wide buffer via stable argsorts (jit-safe), with
         id ``P`` as the harmless-gather sentinel;
      3. score the buffer through the batched ``l2_scan`` candidate
         primitives and replay the exact cascade over the per-leaf minima
         via :func:`replay_cascade` (k=1, seeded with ``bsf0``, padding
         leaves lb-pruned) — bitwise-identical decisions, counters and bsf
         to the masked scan under ``dist_impl="direct"`` given identical
         inputs (tests/test_engine.py pins this; across *differently fused
         programs* the usual XLA caveat applies — a prune threshold within
         an ulp of the bsf may resolve differently, see
         tests/test_distributed.py).

    Queries whose survivor count exceeds the capacity fall back to the
    masked scan (one ``lax.cond`` over the batch), so semantics stay exact
    at any ``max_survivors``; the default capacity is
    :func:`default_max_survivors` of the leaf-slot count.

    ``trace=True`` (a Python-level flag, shard_map-legal) appends a
    per-query :class:`~repro.obs.trace.CascadeTrace`: mask-stage
    box/seed/filter attribution (shard-padding leaves count as
    box-pruned), ``survivors`` entering the candidate pass, the
    ``overflow`` fallback flag, and distance rows paid; overflow queries
    carry the scan fallback's step-level counters instead.  Results are
    bitwise-identical either way; ``trace=False`` lowers to the
    byte-identical program.

    ``audit=True`` (same discipline) additionally appends the
    per-(query, leaf) :class:`~repro.obs.audit.AuditParts` decision planes
    — mask-stage attribution with ``kept`` = the survivor mask and
    ``leaf_nn`` from the candidate pass's per-leaf minima; overflow
    queries carry the masked-scan fallback's step-level planes instead
    (selected per query before any leafwise reduction).  The return is
    ``(bsf, n_s[, trace][, parts])`` in flag order.
    """
    Q, m = queries.shape
    P = leaf_start.shape[0]
    if max_survivors is None:
        max_survivors = default_max_survivors(P)
    # leafi: ignore[LF001]: max_survivors is a host int (caller arg or leaf-count default) — capacity must be static
    C = max(min(int(max_survivors), P), 1)
    dist_impl = dist_impl or l2_ops.default_gathered_impl()
    if bsf_ub is None:
        bsf_ub = jnp.full(Q, _INF)

    valid = leaf_size > 0
    lb = jnp.where(valid[None, :], lb, _INF)
    # prune-only bound: the lb mask uses min(bsf0, ub) — matching the
    # replay's effective lb threshold after the seed merge — while d_F masks
    # against bsf0 alone, because the replay's filter test compares against
    # the witnessed bsf (≤ bsf0), never the warm bound (superset preserved).
    bsf0m = jnp.minimum(bsf0, bsf_ub)
    survive = (lb <= bsf0m[:, None]) & (d_F <= bsf0[:, None]) \
        & valid[None, :]
    n_surv = survive.sum(axis=1).astype(jnp.int32)

    # survivors first, in ascending-lb order (stable argsort of the inverted
    # mask over lb-ordered slots — the same compaction the single-device
    # engine does per bucket, at one static width)
    order = jnp.argsort(lb, axis=1)                      # (Q, P)
    mask_ord = jnp.take_along_axis(survive, order, axis=1)
    sel = jnp.argsort(~mask_ord, axis=1)[:, :C]
    slot_ok = jnp.take_along_axis(mask_ord, sel, axis=1)
    leaf_b = jnp.where(slot_ok, jnp.take_along_axis(order, sel, axis=1), P)

    chunk = _chunk_for(Q, C, max_leaf, m)
    Cp = -(-C // chunk) * chunk                          # pad C to chunks
    if Cp > C:
        leaf_b = jnp.pad(leaf_b, ((0, 0), (0, Cp - C)), constant_values=P)
    vals, _ = sanitize.call(_bucket_leaf_topk, series, leaf_start,
                            leaf_size, queries, leaf_b, kk=1,
                            max_leaf=max_leaf, chunk=chunk,
                            dist_impl=dist_impl)
    # per-leaf min-distance summaries; sentinel (== P) writes land in a
    # scratch row that is sliced off — in-bounds by construction, so index
    # sanitizers stay quiet.
    leaf_min = jnp.full((Q, P + 1), _INF)
    leaf_min = leaf_min.at[jnp.arange(Q)[:, None], leaf_b].set(
        vals[:, :, 0])[:, :P]

    td, _, n_s, _, _ = replay_cascade(
        leaf_min[..., None], jnp.full((Q, P, 1), -1, jnp.int32),
        lb, d_F, order, k=1, bsf0=bsf0, leaf_valid=valid, bsf_ub=bsf_ub)
    bsf_c, ns_c = td[:, 0], n_s

    # overflow queries (survivors > capacity) would replay against missing
    # summaries — route the whole batch through the masked scan and select
    # per query; the cond keeps the scan off the hot path when nobody
    # overflows.
    overflow = n_surv > C
    if not (trace or audit):
        bsf_s, ns_s = jax.lax.cond(
            overflow.any(),
            lambda: masked_bsf_scan(series, leaf_start, leaf_size, lb, d_F,
                                    queries, max_leaf, bsf0, bsf_ub),
            lambda: (jnp.full((Q,), _INF), jnp.zeros((Q,), jnp.int32)))
        return (jnp.where(overflow, bsf_s, bsf_c),
                jnp.where(overflow, ns_s, ns_c))

    zq = jnp.zeros((Q,), jnp.int32)
    if audit:
        bsf_s, ns_s, scan_tr, scan_parts = jax.lax.cond(
            overflow.any(),
            lambda: masked_bsf_scan(series, leaf_start, leaf_size, lb, d_F,
                                    queries, max_leaf, bsf0, bsf_ub,
                                    audit=True),
            lambda: (jnp.full((Q,), _INF), jnp.zeros((Q,), jnp.int32),
                     (zq, zq, zq, zq), obs_audit.zero_parts(Q, P)))
    else:
        bsf_s, ns_s, scan_tr = jax.lax.cond(
            overflow.any(),
            lambda: masked_bsf_scan(series, leaf_start, leaf_size, lb, d_F,
                                    queries, max_leaf, bsf0, bsf_ub,
                                    trace=True),
            lambda: (jnp.full((Q,), _INF), jnp.zeros((Q,), jnp.int32),
                     (zq, zq, zq, zq)))

    # mask-stage attribution of the non-survivors (exact partition —
    # ~survive ⇒ invalid, lb > bsf0m, or d_F > bsf0; invalid/padding leaves
    # land in box because their lb was forced to +inf above).
    not_s = ~survive
    p_box = not_s & ((lb > bsf0[:, None]) | ~valid[None, :])
    p_seed = not_s & ~p_box & (lb > bsf0m[:, None])
    p_filt = not_s & ~p_box & ~p_seed
    rets = (jnp.where(overflow, bsf_s, bsf_c),
            jnp.where(overflow, ns_s, ns_c))
    if trace:
        sizes = leaf_size.astype(jnp.int32)
        compact_rows = jnp.where(survive, sizes[None, :], 0).sum(axis=1)
        s_box, s_seed, s_pf, s_rows = scan_tr
        compact_tr = CascadeTrace(
            pruned_box=p_box.sum(axis=1).astype(jnp.int32),
            pruned_seed=p_seed.sum(axis=1).astype(jnp.int32),
            pruned_filter=p_filt.sum(axis=1).astype(jnp.int32),
            probed=zq, survivors=n_surv, overflow=zq,
            distances=compact_rows)
        scan_as_tr = CascadeTrace(
            pruned_box=s_box, pruned_seed=s_seed, pruned_filter=s_pf,
            probed=zq, survivors=ns_s, overflow=jnp.ones((Q,), jnp.int32),
            distances=s_rows)
        rets = rets + (_trace_select(overflow, scan_as_tr, compact_tr),)
    if audit:
        # leaf_min holds each survivor's exact NN distance (+inf for
        # never-gathered leaves), so it doubles as the audit's leaf_nn.
        compact_parts = AuditParts(p_box, p_seed, p_filt, survive,
                                   jnp.isfinite(leaf_min), leaf_min)
        rets = rets + (obs_audit.select_parts(overflow, scan_parts,
                                              compact_parts),)
    return rets
