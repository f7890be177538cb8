"""LeaFi-enhanced search (paper Alg. 2), TPU-native forms.

Two execution styles over the same semantics:

* ``search_batched`` — throughput form.  Lower bounds and filter predictions
  for *all* leaves are computed up front (hoisting them out of the visit loop
  is exact — neither depends on d_bsf), then the bsf-ordered pruning cascade
  runs through :mod:`repro.core.engine`.  The default ``strategy="compact"``
  computes distances only for cascade survivors (prune → compact → batched
  MXU candidate pass), so wall-clock shrinks with the pruning ratio;
  ``strategy="scan"`` is the fixed-shape fallback that computes every
  leaf (each read once per batch, then the same replay).  Both report
  the paper's hardware-agnostic cost metric (searched-leaf count) exactly
  and return identical results — bitwise with the ``direct`` distance impl
  (the off-TPU default), to float tolerance with the TPU-default ``matmul``
  impl (see the engine module).

* ``search_early`` — latency form for a single query: a while_loop that
  terminates at the first lower bound exceeding d_bsf (visiting in LB order
  makes every later leaf prunable too), with filter-pruned leaf scans
  genuinely skipped via lax.cond.  This is the direct analogue of the
  paper's CPU search loop and gives real wall-clock pruning savings
  on-device.

Setting ``quality_target=None`` (or use_filters=False) disables the filter
cascade: the search is then exact, reproducing the paper's guarantee that a
LeaFi-enhanced index can always answer exactly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import span
from . import bounds as bounds_mod
from . import conformal, engine, filters
from .flat_index import FlatIndex

_INF = jnp.float32(jnp.inf)


@dataclasses.dataclass
class SearchResult:
    dists: np.ndarray            # (Q, k)
    ids: np.ndarray              # (Q, k) original series ids
    searched: np.ndarray         # (Q,) leaves actually scanned
    pruned_lb: np.ndarray        # (Q,) leaves pruned by summarization LB
    pruned_filter: np.ndarray    # (Q,) leaves pruned by learned filters
    n_leaves: int
    # leaves the engine paid distance compute for (== n_leaves on the scan
    # strategy; the phase-1 survivor superset on the compact strategy, the
    # bucket's survivor union under dist_impl="pairwise")
    computed: Optional[np.ndarray] = None
    # search_batched(trace=True): host-side dict of the engine's per-query
    # CascadeTrace fields (repro.obs.trace.to_numpy), else None
    trace: Optional[dict] = None
    # search_batched(audit=True): host-side dict of the engine's per-leaf
    # FilterAudit fields (repro.obs.audit.to_numpy), else None
    audit: Optional[dict] = None

    @property
    def pruning_ratio(self) -> np.ndarray:
        return 1.0 - self.searched / self.n_leaves


@dataclasses.dataclass
class PendingSearch:
    """A dispatched batched search whose device work may still be running.

    JAX arrays are futures: :func:`search_batched_async` returns as soon as
    the engine's programs are enqueued, holding device arrays here, and the
    host blocks only when :meth:`result` materializes them to numpy.  The
    serving runtime's pipelined loop dispatches batch N+1 while batch N's
    arrays are still cooking on device; :meth:`result` then harvests in
    dispatch order.  (The compact strategy's survivor bucketing syncs the
    host once per dispatch — the probe/mask prefix — so its overlap window
    is the candidate pass + replay; the scan strategy dispatches fully
    async.)
    """
    raw: engine.EngineResult
    order: np.ndarray
    n_series: int
    n_leaves: int

    def block_until_ready(self) -> "PendingSearch":
        jax.block_until_ready(self.raw.topk_d)
        return self

    def result(self) -> SearchResult:
        """Materialize to a :class:`SearchResult` (blocks on the device).

        Two spans: ``search.wait``, the host's wait until every output it
        reads is ready, then ``search.fetch``, the device-to-host copies
        (``n_arrays`` of them, ``bytes`` in all) and the id mapping
        through ``order``."""
        from ..obs import audit as obs_audit
        from ..obs import trace as obs_trace
        r = self.raw
        out = [r.topk_i, r.topk_d, r.n_searched, r.n_pruned_lb,
               r.n_pruned_filter, r.n_computed, r.trace, r.audit]
        leaves = jax.tree.leaves(out)
        with span("search.wait", cat="search", q=int(r.topk_d.shape[0]),
                  k=int(r.topk_d.shape[1])):
            jax.block_until_ready(leaves)
        with span("search.fetch", cat="search", n_arrays=len(leaves),
                  bytes=int(sum(x.nbytes for x in leaves))):
            ids_sorted = np.asarray(r.topk_i)
            valid = ids_sorted >= 0
            orig = np.where(valid, self.order[
                np.clip(ids_sorted, 0, self.n_series - 1)], -1)
            return SearchResult(
                dists=np.asarray(r.topk_d), ids=orig,
                searched=np.asarray(r.n_searched),
                pruned_lb=np.asarray(r.n_pruned_lb),
                pruned_filter=np.asarray(r.n_pruned_filter),
                n_leaves=self.n_leaves, computed=np.asarray(r.n_computed),
                trace=(None if r.trace is None
                       else obs_trace.to_numpy(r.trace)),
                audit=(None if r.audit is None
                       else obs_audit.to_numpy(r.audit)))


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def predictions_for_all_leaves(index: FlatIndex, filter_params,
                               leaf_ids: np.ndarray,
                               queries: jnp.ndarray,
                               offsets: np.ndarray | None,
                               use_kernel: bool = True,
                               filter_type: str = "mlp") -> jnp.ndarray:
    """(Q, L) conformal-adjusted filter lower bounds; −inf ⇒ never prunes.

    The cascade prunes a leaf when ``d_F > bsf``, so −inf is the neutral
    element for leaves without a filter: the check can never fire.  Filtered
    leaves get their (offset-adjusted) predictions scattered onto their leaf
    slots.

    ``filter_type`` selects the backbone via :data:`filters.APPLY` (the
    CNN/RNN ablation variants of Table 1 are reachable from search, not just
    from the ablation benchmark).  The MLP path routes shared (F,) offsets
    into the fused megakernel's epilogue — one launch produces the
    offset-adjusted d_F block on TPU.

    ``offsets`` is either one (F,) per-filter vector shared by every query
    (the paper's form: one quality target per batch) or (Q, F) per-query
    rows — the serving runtime's heterogeneous micro-batch form, where each
    query carries its own quality target and hence its own conformal
    adjustment of the same filter predictions.  The per-query rows broadcast
    over the (F, Q) output, so they are applied outside the kernel.
    """
    L = index.n_leaves
    Q = queries.shape[0]
    if filter_params is None or len(leaf_ids) == 0:
        return jnp.full((Q, L), -_INF)
    off = None if offsets is None else jnp.asarray(offsets)
    if filter_type == "mlp" and (off is None or off.ndim == 1):
        preds = filters.apply_mlp_offset(
            filter_params, queries, off, use_kernel)                # (F, Q)
    else:
        preds = filters.APPLY[filter_type](
            filter_params, queries, use_kernel)                    # (F, Q)
        if off is not None:
            preds = preds - (off.T if off.ndim == 2 else off[:, None])
    full = jnp.full((L, Q), -_INF)
    full = full.at[jnp.asarray(leaf_ids)].set(preds)
    return full.T                                                   # (Q, L)


# ---------------------------------------------------------------------------
# batched form
# ---------------------------------------------------------------------------


def search_batched_async(
    index: FlatIndex,
    queries: np.ndarray,
    *,
    k: int = 1,
    filter_params=None,
    leaf_ids: np.ndarray | None = None,
    tuner: Optional[conformal.AutoTuner] = None,
    quality_target: float | np.ndarray | None = None,
    use_filters: bool = True,
    use_kernel: bool = True,
    filter_type: str = "mlp",
    strategy: str = "auto",
    dist_impl: Optional[str] = None,
    bsf_ub: np.ndarray | None = None,
    trace: bool = False,
    audit: bool = False,
) -> PendingSearch:
    """Dispatch a batched LeaFi search without blocking on the device.

    Same arguments and semantics as :func:`search_batched` (which is just
    ``search_batched_async(...).result()``), plus ``bsf_ub``: an optional
    (Q,) per-query prune-only upper bound on the true k-th NN distance
    (``engine.run_cascade``'s warm-start seed — tightens pruning, never
    changes the answer).  Returns a :class:`PendingSearch` holding device
    arrays; call ``.result()`` to materialize.

    ``trace=True`` threads the engine's :class:`repro.obs.CascadeTrace`
    through the cascade (per-query pruning attribution); the materialized
    ``SearchResult.trace`` is its numpy dict.  Results stay bitwise
    identical to ``trace=False``.

    ``audit=True`` threads the engine's per-leaf
    :class:`repro.obs.FilterAudit` (prune/kept counts by bound, work
    saved, prediction-residual health stats — see ``repro.obs.audit``);
    the materialized ``SearchResult.audit`` is its numpy dict.  Same
    zero-cost-when-off discipline as ``trace``.

    Each layer of the dispatch is a span (:mod:`repro.obs.spans`):
    ``search.bounds`` (the queries' upload and their lower bounds),
    ``search.offsets`` (the conformal offsets and their upload),
    ``search.filters`` (the filter sweep and its scatter onto leaves) and
    ``search.cascade`` (the engine's enqueue; under the untraced ``scan``
    its ``rows_read`` and ``rows_held`` args give the series rows the leaf
    pass reads per batch and the rows the index holds).  An exact search
    has no offsets or filters span.
    """
    shape = np.shape(queries)
    Q = int(shape[0]) if len(shape) > 1 else 1
    with span("search.bounds", cat="search", q=Q, n_leaves=index.n_leaves):
        queries = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
        d_lb = bounds_mod.lower_bounds(index, queries)              # (Q, L)
    if quality_target is not None:
        nd = np.ndim(quality_target)
        if nd > 1:
            raise ValueError(
                "quality_target must be a scalar or a (Q,) per-query "
                f"array, got shape {np.shape(quality_target)}")
        if nd == 1 and np.shape(quality_target)[0] != queries.shape[0]:
            raise ValueError(
                f"per-query quality_target has {np.shape(quality_target)[0]} "
                f"entries for {queries.shape[0]} queries")
    offsets = None
    if use_filters and filter_params is not None and tuner is not None \
            and quality_target is not None:
        with span("search.offsets", cat="search",
                  per_query=bool(np.ndim(quality_target))):
            # (F,) or (Q, F)
            offsets = jnp.asarray(tuner.offsets(quality_target))
    if use_filters and filter_params is not None:
        with span("search.filters", cat="search", q=Q,
                  n_filters=len(leaf_ids) if leaf_ids is not None else 0):
            d_F = predictions_for_all_leaves(
                index, filter_params, leaf_ids, queries, offsets, use_kernel,
                filter_type)
    else:
        d_F = jnp.full(d_lb.shape, -_INF)

    rows = ({"rows_read": index.slab_rows, "rows_held": index.n_series}
            if strategy == "scan" and not (trace or audit) else {})
    with span("search.cascade", cat="search", q=Q, k=k, strategy=strategy,
              **rows):
        res = engine.run_cascade(
            index.series, index.leaf_start, index.leaf_size, queries, d_lb,
            d_F, k=k, max_leaf=index.max_leaf_size, strategy=strategy,
            dist_impl=dist_impl, bsf_ub=bsf_ub,
            leaf_classes=index.leaf_classes, trace=trace, audit=audit)
    return PendingSearch(raw=res, order=np.asarray(index.order),
                         n_series=index.n_series, n_leaves=index.n_leaves)


def search_batched(
    index: FlatIndex,
    queries: np.ndarray,
    *,
    k: int = 1,
    filter_params=None,
    leaf_ids: np.ndarray | None = None,
    tuner: Optional[conformal.AutoTuner] = None,
    quality_target: float | np.ndarray | None = None,
    use_filters: bool = True,
    use_kernel: bool = True,
    filter_type: str = "mlp",
    strategy: str = "auto",
    dist_impl: Optional[str] = None,
    bsf_ub: np.ndarray | None = None,
    trace: bool = False,
    audit: bool = False,
) -> SearchResult:
    """Batched LeaFi search.  Exact when filters are disabled.

    ``strategy``/``dist_impl`` select the engine execution plan (see
    :mod:`repro.core.engine`): "compact" (the "auto" default) only computes
    distances for cascade survivors; "scan" is the masked fallback.

    ``quality_target`` is one target shared by the batch (the paper's form)
    or an array of Q per-query targets — the serving runtime's heterogeneous
    micro-batch form, lowered to (Q, F) per-query conformal offset rows (the
    paper's §4.4 "quality target of each query", batched).  The grouped
    fallback :func:`search_batched_grouped` answers the same mixed batch as
    homogeneous sub-batches; tests pin the two equal to float tolerance.
    """
    return search_batched_async(
        index, queries, k=k, filter_params=filter_params, leaf_ids=leaf_ids,
        tuner=tuner, quality_target=quality_target, use_filters=use_filters,
        use_kernel=use_kernel, filter_type=filter_type, strategy=strategy,
        dist_impl=dist_impl, bsf_ub=bsf_ub, trace=trace,
        audit=audit).result()


def search_batched_grouped(
    index: FlatIndex,
    queries: np.ndarray,
    quality_targets: np.ndarray,
    *,
    k: int = 1,
    **kw,
) -> SearchResult:
    """Grouped-sub-batch fallback for per-query quality targets.

    Partitions the batch by unique target, answers each homogeneous group
    through :func:`search_batched` with a scalar target, and stitches the
    results back in request order.  Semantically identical to passing the
    target array straight to ``search_batched`` (the (Q, F)-offset path);
    the sub-batches compile as separate XLA programs, so prune decisions
    tied within an ulp of the bsf may fuse differently — the parity tests
    pin the two paths equal to float tolerance, not bitwise
    (tests/test_serving.py).
    """
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    targets = np.asarray(quality_targets, np.float64).reshape(-1)
    Q = queries.shape[0]
    if targets.shape[0] != Q:
        raise ValueError(f"{targets.shape[0]} targets for {Q} queries")
    out: Optional[SearchResult] = None
    for val in np.unique(targets):
        sel = np.where(targets == val)[0]
        r = search_batched(index, queries[sel], k=k,
                           quality_target=float(val), **kw)
        if out is None:
            out = SearchResult(
                dists=np.empty((Q, r.dists.shape[1]), r.dists.dtype),
                ids=np.empty((Q, r.ids.shape[1]), r.ids.dtype),
                searched=np.empty(Q, r.searched.dtype),
                pruned_lb=np.empty(Q, r.pruned_lb.dtype),
                pruned_filter=np.empty(Q, r.pruned_filter.dtype),
                n_leaves=r.n_leaves,
                computed=np.empty(Q, r.computed.dtype))
        out.dists[sel], out.ids[sel] = r.dists, r.ids
        out.searched[sel], out.computed[sel] = r.searched, r.computed
        out.pruned_lb[sel], out.pruned_filter[sel] = (r.pruned_lb,
                                                      r.pruned_filter)
    assert out is not None
    return out


# ---------------------------------------------------------------------------
# early-termination form (single-query latency path)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "max_leaf"))
def _search_early_core(series, leaf_start, leaf_size, q, lb_row, dF_row,
                       order_row, k, max_leaf):
    L = order_row.shape[0]
    row_ids = jnp.arange(max_leaf)

    def cond(state):
        p, topk_d, *_ = state
        # visiting in LB order: the first lb > bsf prunes all the rest.
        return jnp.logical_and(p < L, lb_row[order_row[jnp.minimum(p, L - 1)]]
                               <= topk_d[-1])

    def body(state):
        p, topk_d, topk_i, n_s, n_pf = state
        leaf = order_row[p]
        bsf = topk_d[-1]
        p_f = dF_row[leaf] > bsf

        def scan_leaf(args):
            topk_d, topk_i = args
            start = leaf_start[leaf]
            slab = jax.lax.dynamic_slice_in_dim(series, start, max_leaf, 0)
            diff = slab - q[None, :]
            d = jnp.sqrt((diff * diff).sum(-1))
            d = jnp.where(row_ids < leaf_size[leaf], d, _INF)
            ids = (start + row_ids).astype(jnp.int32)
            neg_top, arg = jax.lax.top_k(
                -jnp.concatenate([topk_d, d]), k)
            return -neg_top, jnp.concatenate([topk_i, ids])[arg]

        topk_d, topk_i = jax.lax.cond(
            p_f, lambda a: a, scan_leaf, (topk_d, topk_i))
        return (p + 1, topk_d, topk_i, n_s + (~p_f).astype(jnp.int32),
                n_pf + p_f.astype(jnp.int32))

    init = (jnp.int32(0), jnp.full((k,), _INF), jnp.full((k,), -1, jnp.int32),
            jnp.int32(0), jnp.int32(0))
    p, topk_d, topk_i, n_s, n_pf = jax.lax.while_loop(cond, body, init)
    n_plb = L - p
    return topk_d, topk_i, n_s, n_plb, n_pf


def search_early(
    index: FlatIndex,
    query: np.ndarray,
    *,
    k: int = 1,
    filter_params=None,
    leaf_ids: np.ndarray | None = None,
    tuner: Optional[conformal.AutoTuner] = None,
    quality_target: Optional[float] = None,
    use_filters: bool = True,
    filter_type: str = "mlp",
) -> SearchResult:
    """Single-query early-termination search (real pruning skips)."""
    q = jnp.asarray(query, jnp.float32).reshape(1, -1)
    d_lb = bounds_mod.lower_bounds(index, q)[0]
    offsets = None
    if use_filters and filter_params is not None and tuner is not None \
            and quality_target is not None:
        offsets = tuner.offsets(quality_target)
    if use_filters and filter_params is not None:
        d_F = predictions_for_all_leaves(
            index, filter_params, leaf_ids, q, offsets,
            filter_type=filter_type)[0]
    else:
        d_F = jnp.full(d_lb.shape, -_INF)
    order = jnp.argsort(d_lb)
    td, ti, n_s, n_plb, n_pf = _search_early_core(
        index.series, index.leaf_start, index.leaf_size, q[0], d_lb, d_F,
        order,
        k=k, max_leaf=index.max_leaf_size)
    ids_sorted = np.asarray(ti)
    valid = ids_sorted >= 0
    orig = np.where(valid, np.asarray(index.order)[
        np.clip(ids_sorted, 0, index.n_series - 1)], -1)
    return SearchResult(
        dists=np.asarray(td)[None], ids=orig[None],
        searched=np.asarray(n_s)[None], pruned_lb=np.asarray(n_plb)[None],
        pruned_filter=np.asarray(n_pf)[None], n_leaves=index.n_leaves)
