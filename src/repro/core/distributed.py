"""Distributed LeaFi search: leaf-partitioned, shard_map-based.

The paper's system is single-node (CPU threads + one GPU).  At pod scale the
index must shard: leaves are partitioned across devices along the ``model``
mesh axis (round-robin by size for balance, as in DPiSAX/Odyssey), queries
batch along ``data``.  Search is a two-phase exchange:

  Phase 1 — every shard scans its single most-promising local leaf (smallest
            local lower bound); one psum-min establishes a global best-so-far.
            This is the collective analogue of the paper's "a tight bsf early
            makes the cascade effective".
  Phase 2 — every shard runs the LeaFi pruning cascade (summarization LB,
            then calibrated filter prediction) against the *global* bsf over
            its local leaves, scanning only survivors; a final psum-min picks
            the answer (and an argmin exchange resolves the owner).

Collectives used: two ``psum(min)`` on (Q,)-vectors and one final pair —
bytes exchanged are O(Q), independent of collection size, so the exchange
is *communication*-scalable.  The per-shard body is also *compute*-scalable:
by default it runs ``engine.compact_bsf_cascade``, the fixed-width survivor
compaction (static shapes, legal inside shard_map), so each shard pays
distance compute only for a bounded survivor buffer instead of every local
leaf — the distributed analogue of the single-device engine's
prune→compact→candidates plan, with the masked scan kept as the
bitwise-parity fallback (``strategy="scan"``) and as the exact overflow
path.  This file is also what ``launch/dryrun.py --arch leafi-serve``
lowers on the production mesh.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import conformal, engine
from ..obs import audit as obs_audit
from ..obs.audit import FilterAudit
from ..obs.trace import CascadeTrace
from .build import LeaFiIndex

_INF = jnp.float32(jnp.inf)


@dataclasses.dataclass
class ShardedLeaFi:
    """Device-partitioned LeaFi index (leaf-sharded along ``model``)."""
    # per-shard stacked arrays; leading axis = n_shards
    series: jnp.ndarray           # (S, rows_max, m)
    leaf_start: jnp.ndarray       # (S, P)
    leaf_size: jnp.ndarray        # (S, P)   0 ⇒ padding leaf
    lb_lo: jnp.ndarray            # (S, P, d)  box lower edges (pre-scaled)
    lb_hi: jnp.ndarray            # (S, P, d)
    # stacked filter params (+inf-free; has_filter masks unfiltered leaves)
    w1: jnp.ndarray               # (S, P, m, h)
    b1: jnp.ndarray               # (S, P, h)
    w2: jnp.ndarray               # (S, P, h)
    b2: jnp.ndarray               # (S, P)
    y_mean: jnp.ndarray           # (S, P)
    y_std: jnp.ndarray            # (S, P)
    offsets: jnp.ndarray          # (S, P) conformal offsets at build target
    has_filter: jnp.ndarray       # (S, P) bool
    max_leaf: int
    length: int
    kind: str
    qscale: np.ndarray            # (d,) query coordinate pre-scale (box LB)
    # local slot → global leaf id (padding slots carry n_leaves); lets the
    # per-query-offset shard body gather each query's (Q, L) conformal
    # offset row onto this shard's (Q, P) local slots.
    leaf_global: Optional[jnp.ndarray] = None   # (S, P) int32

    def query_coords(self, queries: jnp.ndarray) -> jnp.ndarray:
        """Map raw queries to pre-scaled box coordinates (see kernels.box_lb)."""
        return _query_coords(self.kind, self.lb_lo, self.qscale, queries)


def _query_coords(kind: str, lb_lo, qscale, queries: jnp.ndarray):
    """Raw queries → the pre-scaled box coordinates of ``lb_lo``'s layout."""
    from . import summaries
    if kind == "dstree":
        st = summaries.segment_stats(queries, lb_lo.shape[-1] // 2)
        q = jnp.concatenate([st[..., 0], st[..., 1]], -1)
    else:
        q = summaries.paa(queries, lb_lo.shape[-1])
    return q * jnp.asarray(qscale)


def make_search_mesh(n_data: int, n_model: int,
                     data_axis: str = "data", model_axis: str = "model"):
    """A (data, model) mesh for the distributed search (one shared
    constructor for tests, benchmarks and serving)."""
    return jax.make_mesh((n_data, n_model), (data_axis, model_axis),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def shard_leafi(lfi: LeaFiIndex, n_shards: Optional[int] = None,
                quality_target: Optional[float] = 0.99, *,
                mesh: Optional[Mesh] = None,
                model_axis: str = "model") -> ShardedLeaFi:
    """Partition a built LeaFiIndex into n_shards leaf groups.

    With a ``mesh``, shard ``s`` of every per-shard array is placed on the
    devices at position ``s`` of its ``model_axis``
    (``NamedSharding(mesh, P(model_axis))``): each device holds only its own
    leaves, and ``n_shards`` defaults to that axis' size.  Without one the
    arrays stay uncommitted on the default device (single-device use).
    """
    from ..kernels.filter_mlp import ref as mlp_ref
    if mesh is not None:
        n_shards = n_shards or int(mesh.shape[model_axis])
    index = lfi.index
    L = index.n_leaves
    sizes = np.asarray(index.leaf_size)
    order = np.argsort(-sizes, kind="stable")
    # round-robin by size → balanced rows per shard
    shard_of = np.empty(L, np.int64)
    shard_of[order] = np.arange(L) % n_shards
    P_max = int(np.bincount(shard_of, minlength=n_shards).max())

    # pre-scaled box edges (shared form for both backbones; cf. kernels.box_lb)
    if index.kind == "dstree":
        box = np.asarray(index.payload["eapca_box"])
        w = np.sqrt(np.asarray(index.payload["seg_len"], np.float32))
        lo = np.concatenate([box[..., 0] * w, box[..., 2] * w], -1)
        hi = np.concatenate([box[..., 1] * w, box[..., 3] * w], -1)
        qscale = np.concatenate([w, w])
    else:
        edges = np.asarray(index.payload["sax_edges"])
        wl = edges.shape[1]
        scale = np.sqrt(index.length / wl)
        lo, hi = edges[..., 0] * scale, edges[..., 1] * scale
        qscale = np.full(wl, scale, np.float32)

    m = index.length
    params = lfi.filter_params
    h = params["w1"].shape[-1] if params else m
    offsets_global = conformal.scatter_offsets(
        lfi.tuner, lfi.leaf_ids, L, quality_target) \
        if lfi.tuner is not None else np.zeros(L, np.float32)
    # filter index of every leaf (−1: no filter); weights dequantized once
    filter_of = np.full(L, -1, np.int64)
    filter_of[np.asarray(lfi.leaf_ids, np.int64)] = np.arange(
        len(lfi.leaf_ids))
    if params is not None:
        w1_np, w2_np = (np.asarray(w) for w in mlp_ref.dequantize_weights(
            params["w1"], params["w2"], params.get("w1_scale"),
            params.get("w2_scale")))
        p_np = {k: np.asarray(params[k])
                for k in ("b1", "b2", "y_mean", "y_std")}

    series_np = np.asarray(index.series)
    starts_np = np.asarray(index.leaf_start)
    shard_leaves = [np.where(shard_of == s)[0] for s in range(n_shards)]
    # slack rows keep every dynamic_slice(start, max_leaf) in bounds
    rows_max = max(int(sizes[lv].sum()) for lv in shard_leaves) \
        + index.max_leaf_size

    S = n_shards
    out = ShardedLeaFi(
        series=np.zeros((S, rows_max, m), np.float32),
        leaf_start=np.zeros((S, P_max), np.int32),
        leaf_size=np.zeros((S, P_max), np.int32),
        lb_lo=np.full((S, P_max, lo.shape[-1]), -np.inf, np.float32),
        lb_hi=np.full((S, P_max, lo.shape[-1]), np.inf, np.float32),
        w1=np.zeros((S, P_max, m, h), np.float32),
        b1=np.zeros((S, P_max, h), np.float32),
        w2=np.zeros((S, P_max, h), np.float32),
        b2=np.zeros((S, P_max), np.float32),
        y_mean=np.zeros((S, P_max), np.float32),
        y_std=np.ones((S, P_max), np.float32),
        offsets=np.zeros((S, P_max), np.float32),
        has_filter=np.zeros((S, P_max), bool),
        max_leaf=index.max_leaf_size, length=m, kind=index.kind,
        qscale=qscale.astype(np.float32),
        leaf_global=np.full((S, P_max), L, np.int32),
    )
    for s, leaves in enumerate(shard_leaves):
        n_l = len(leaves)
        sz = sizes[leaves].astype(np.int64)
        cursor = np.concatenate([[0], np.cumsum(sz)[:-1]])
        rows = np.repeat(starts_np[leaves] - cursor, sz) + np.arange(sz.sum())
        out.series[s, :sz.sum()] = series_np[rows]
        out.leaf_global[s, :n_l] = leaves
        out.leaf_start[s, :n_l] = cursor
        out.leaf_size[s, :n_l] = sz
        out.lb_lo[s, :n_l] = lo[leaves]
        out.lb_hi[s, :n_l] = hi[leaves]
        if params is None:
            continue
        fi = filter_of[leaves]
        slot = np.where(fi >= 0)[0]
        fi = fi[slot]
        out.w1[s, slot] = w1_np[fi]
        out.b1[s, slot] = p_np["b1"][fi]
        out.w2[s, slot] = w2_np[fi]
        out.b2[s, slot] = p_np["b2"][fi]
        out.y_mean[s, slot] = p_np["y_mean"][fi]
        out.y_std[s, slot] = p_np["y_std"][fi]
        out.offsets[s, slot] = offsets_global[leaves[slot]]
        out.has_filter[s, slot] = True
    # one host→device copy per shard, straight onto its own device(s)
    place = (jnp.asarray if mesh is None else functools.partial(
        jax.device_put, device=NamedSharding(mesh, P(model_axis))))
    for f in dataclasses.fields(out):
        v = getattr(out, f.name)
        if isinstance(v, np.ndarray) and f.name != "qscale":
            setattr(out, f.name, place(v))
    return out


# ---------------------------------------------------------------------------
# the shard-local search body (runs under shard_map; axis name = 'model')
# ---------------------------------------------------------------------------


def _shard_pruning_inputs(lo, hi, w1, b1, w2, b2, y_mean, y_std, offsets,
                          has_filter, leaf_size, queries, qcoords):
    """Per-shard (Q, P) pruning inputs: box lower bounds + filter preds.

    Padding leaves (size 0) carry (−inf, +inf) box edges, which the
    ``isfinite`` squash collapses to a lower bound of 0 — low enough to win
    the phase-1 probe's argmin and silently waste the bsf seed on an empty
    leaf.  Their lb is therefore forced to +inf here, so they sort last,
    never survive, and never probe.

    ``offsets`` is either one (P,) per-slot conformal offset vector shared
    by every query (the baked single-quality-target form) or (Q, P)
    per-query rows — the serving runtime's mixed-target micro-batch form,
    gathered from global (Q, L) offset rows via ``ShardedLeaFi.leaf_global``.
    """
    d = jnp.maximum(jnp.maximum(lo[None] - qcoords[:, None],
                                qcoords[:, None] - hi[None]), 0.0)
    d = jnp.where(jnp.isfinite(d), d, 0.0)
    lb = jnp.sqrt((d * d).sum(-1))
    lb = jnp.where(leaf_size[None, :] > 0, lb, _INF)

    # local filter predictions: einsum over stacked per-leaf MLPs
    hdd = jax.nn.relu(jnp.einsum("qm,pmh->pqh", queries, w1)
                      + b1[:, None, :])
    pred = jnp.einsum("pqh,ph->pq", hdd, w2) + b2[:, None]
    pred = pred * y_std[:, None] + y_mean[:, None]
    off = offsets if offsets.ndim == 2 else offsets[None, :]   # (1|Q, P)
    d_F = jnp.where(has_filter[None, :], pred.T - off, -_INF)
    return lb, d_F                                       # both (Q, P)


def _local_search(sh_series, sh_start, sh_size, lb, d_F, queries, max_leaf,
                  bsf0, strategy="compact", max_survivors=None,
                  dist_impl=None, bsf_ub=None, trace=False, audit=False):
    """Cascade over this shard's leaves given a starting global bsf.

    Routes through the common engine's shard_map-safe forms:
    ``"compact"`` (default) is the fixed-width survivor compaction — static
    shapes, distance compute only for the survivor buffer, masked-scan
    fallback for overflow queries; ``"scan"`` is the original masked scan,
    kept as the parity fallback (bitwise-identical under the ``direct``
    distance impl).

    ``bsf_ub`` is the serving runtime's prune-only warm-start bound: it
    tightens prune decisions but never enters ``bsf0`` or the returned bsf
    (both must stay witnessed distances — a pmin over unwitnessed bounds
    would corrupt the global answer).

    ``trace=True`` (Python-level, shard_map-legal) appends a per-query
    shard-local :class:`~repro.obs.trace.CascadeTrace` (``probed`` stays 0
    here — the shard body accounts for its phase-1 probe itself).

    ``audit=True`` additionally appends the shard-local per-(query, leaf)
    :class:`~repro.obs.audit.AuditParts` planes — the return is
    ``(bsf, n_s[, trace][, parts])`` in flag order.
    """
    if strategy == "scan":
        if audit:
            bsf, n_s, (n_box, n_seed, n_pf,
                       n_rows), parts = engine.masked_bsf_scan(
                sh_series, sh_start, sh_size, lb, d_F, queries, max_leaf,
                bsf0, bsf_ub=bsf_ub, audit=True)
            if trace:
                zq = jnp.zeros_like(n_s)
                return (bsf, n_s,
                        CascadeTrace(n_box, n_seed, n_pf, zq, n_s, zq,
                                     n_rows), parts)
            return bsf, n_s, parts
        if trace:
            bsf, n_s, (n_box, n_seed, n_pf, n_rows) = engine.masked_bsf_scan(
                sh_series, sh_start, sh_size, lb, d_F, queries, max_leaf,
                bsf0, bsf_ub=bsf_ub, trace=True)
            zq = jnp.zeros_like(n_s)
            return bsf, n_s, CascadeTrace(n_box, n_seed, n_pf, zq, n_s, zq,
                                          n_rows)
        return engine.masked_bsf_scan(sh_series, sh_start, sh_size, lb, d_F,
                                      queries, max_leaf, bsf0, bsf_ub=bsf_ub)
    if strategy == "compact":
        return engine.compact_bsf_cascade(
            sh_series, sh_start, sh_size, lb, d_F, queries, max_leaf, bsf0,
            max_survivors=max_survivors, dist_impl=dist_impl, bsf_ub=bsf_ub,
            trace=trace, audit=audit)
    raise ValueError(f"unknown distributed shard strategy {strategy!r}")


def search_input_specs(n_shards: int, leaves_per_shard: int,
                       rows_per_shard: int, m: int, h: int, n_queries: int,
                       coord_dim: int):
    """ShapeDtypeStructs for dry-running the distributed search at scale.

    Sized like the paper's production setting by default from the caller
    (25M series × len 256, ~16k leaves, MESSI-style 10k leaf capacity).
    Order matches the jitted search signature (idx arrays…, queries, qcoords).
    """
    import jax as _jax
    sd = _jax.ShapeDtypeStruct
    S, P = n_shards, leaves_per_shard
    f32, i32 = jnp.float32, jnp.int32
    return (
        sd((S, rows_per_shard, m), f32),     # series
        sd((S, P), i32), sd((S, P), i32),    # leaf_start, leaf_size
        sd((S, P, coord_dim), f32), sd((S, P, coord_dim), f32),  # lb lo/hi
        sd((S, P, m, h), f32), sd((S, P, h), f32),               # w1, b1
        sd((S, P, h), f32), sd((S, P), f32),                     # w2, b2
        sd((S, P), f32), sd((S, P), f32),                        # y stats
        sd((S, P), f32), sd((S, P), jnp.bool_),                  # offsets, mask
        sd((n_queries, m), f32),                                 # queries
        sd((n_queries, coord_dim), f32),                         # qcoords
    )


def _make_shard_body(max_leaf: int, model_axis: str,
                     strategy: str = "compact",
                     max_survivors: Optional[int] = None,
                     dist_impl: Optional[str] = None,
                     per_query_offsets: bool = False,
                     trace: bool = False,
                     audit: bool = False,
                     data_axes=("data",)):
    """The per-shard two-phase search body (runs under shard_map).

    Phase 1 probes each query's most promising local leaf (engine probe) and
    establishes a global bsf via pmin; phase 2 runs the engine's bsf cascade
    against it — the fixed-width survivor compaction by default, the masked
    scan with ``strategy="scan"`` — and reduces the answer.  Shared by
    ``build_search_fn`` (dry-run lowering) and ``make_distributed_search``.

    With ``per_query_offsets=True`` the body takes three extra inputs —
    ``leaf_global`` (the (S, P) local-slot → global-leaf map), per-query
    (Q, L) conformal offset rows, and a (Q,) prune-only ``bsf_ub`` warm
    bound — so one compiled program serves micro-batches mixing quality
    targets, with the per-leaf offsets gathered onto each shard's local
    slots.  Padding slots gather row L (every (Q, L+…) gather is clamped to
    the last real leaf) but ``has_filter=False`` already disables them.

    With ``trace=True`` the body returns a third output — the per-query
    :class:`~repro.obs.trace.CascadeTrace` psum'd over the model axis:
    pruned-leaf attribution and survivors aggregate across shards,
    ``probed`` counts one phase-1 probe per shard, and ``distances``
    includes each shard's probe rows.  Global accounting over S shards of P
    leaf slots: ``Σ pruned = S·P − survivors`` (the probe leaves are also
    cascade-accounted per shard) with ``probed == S``.

    With ``audit=True`` the body returns one more output — the per-leaf
    :class:`~repro.obs.audit.FilterAudit` for this shard's ``P`` local
    slots, psum'd over ``data_axes`` (queries shard there, so the data-axis
    collective restores full-batch per-leaf counts; ``resid_min`` pmins).
    The model axis is deliberately *not* reduced: each model shard owns
    distinct leaves, so its ``(1, P)`` rows concatenate into the global
    ``(S, P)`` shard-slot layout the host folds with
    :func:`repro.obs.audit.scatter_global` + ``ShardedLeaFi.leaf_global``.
    The phase-1 probe pass is not audited (see ``repro.obs.audit``).
    """

    def _traced_reduce(bsf, n_s, tr, lb, size):
        # each shard's phase-1 probe pays one leaf pass: argmin over the
        # padding-masked lb (same choice probe_best_leaf makes).
        probe_rows = size[lb.argmin(axis=1)].astype(jnp.int32)
        tr = tr._replace(probed=tr.probed + 1,
                         distances=tr.distances + probe_rows)
        tr = jax.tree.map(lambda x: jax.lax.psum(x, model_axis), tr)
        return jax.tree.map(lambda x: x[None], tr)

    def _audit_reduce(parts, d_F, size):
        fa = obs_audit.reduce_parts(parts, d_F, size)
        if data_axes:
            fa = FilterAudit(*(
                jax.lax.pmin(x, data_axes) if name == "resid_min"
                else jax.lax.psum(x, data_axes)
                for name, x in zip(FilterAudit._fields, fa)))
        return jax.tree.map(lambda x: x[None], fa)

    def _phase2(series, start, size, lb, d_F, queries, bsf0, bsf_ub=None):
        out = _local_search(series, start, size, lb, d_F, queries,
                            max_leaf, bsf0, strategy=strategy,
                            max_survivors=max_survivors,
                            dist_impl=dist_impl, bsf_ub=bsf_ub,
                            trace=trace, audit=audit)
        bsf, n_s = out[0], out[1]
        rest = list(out[2:])
        nn = jax.lax.pmin(bsf, model_axis)                      # collective 2
        total_searched = jax.lax.psum(n_s, model_axis)
        rets = (nn[None], total_searched[None])
        if trace:
            rets = rets + (_traced_reduce(bsf, n_s, rest.pop(0), lb, size),)
        if audit:
            rets = rets + (_audit_reduce(rest.pop(0), d_F, size),)
        return rets

    def search_fn(series, start, size, lo, hi, w1, b1, w2, b2, y_mean,
                  y_std, offsets, has_filter, queries, qcoords):
        # inside shard_map: leading shard axis is size 1 → squeeze
        series, start, size = series[0], start[0], size[0]
        lo, hi = lo[0], hi[0]
        w1, b1, w2, b2 = w1[0], b1[0], w2[0], b2[0]
        y_mean, y_std = y_mean[0], y_std[0]
        offsets, has_filter = offsets[0], has_filter[0]

        # (Q, P) lower bounds (padding leaves forced to +inf) + filter preds
        lb, d_F = _shard_pruning_inputs(lo, hi, w1, b1, w2, b2, y_mean,
                                        y_std, offsets, has_filter, size,
                                        queries, qcoords)

        # phase 1: scan the single most promising local leaf
        bsf_local = engine.probe_best_leaf(series, start, size, lb,
                                           queries, max_leaf)
        bsf0 = jax.lax.pmin(bsf_local, model_axis)              # collective 1

        # phase 2: full cascade against the global bsf
        return _phase2(series, start, size, lb, d_F, queries, bsf0)

    def search_fn_pq(series, start, size, lo, hi, w1, b1, w2, b2, y_mean,
                     y_std, offsets, has_filter, leaf_global, queries,
                     qcoords, qoffsets, bsf_ub):
        # inside shard_map: leading shard axis is size 1 → squeeze
        series, start, size = series[0], start[0], size[0]
        lo, hi = lo[0], hi[0]
        w1, b1, w2, b2 = w1[0], b1[0], w2[0], b2[0]
        y_mean, y_std = y_mean[0], y_std[0]
        has_filter, leaf_global = has_filter[0], leaf_global[0]
        del offsets   # baked single-target offsets unused in per-query mode

        # gather each query's (Q, L) offset row onto local slots → (Q, P);
        # padding slots (leaf_global == L) clamp to the last real row and
        # are masked off by has_filter anyway.
        L = qoffsets.shape[1]
        slot = jnp.minimum(leaf_global, L - 1)
        qoff = qoffsets[:, slot]                                # (Q, P)

        lb, d_F = _shard_pruning_inputs(lo, hi, w1, b1, w2, b2, y_mean,
                                        y_std, qoff, has_filter, size,
                                        queries, qcoords)

        bsf_local = engine.probe_best_leaf(series, start, size, lb,
                                           queries, max_leaf)
        bsf0 = jax.lax.pmin(bsf_local, model_axis)              # collective 1

        # warm bound tightens prune decisions only — never folded into bsf0
        # (the pmin'd bsf must stay a witnessed distance on every shard).
        return _phase2(series, start, size, lb, d_F, queries, bsf0,
                       bsf_ub=bsf_ub)

    return search_fn_pq if per_query_offsets else search_fn


def build_search_fn(mesh: Mesh, max_leaf: int, data_axes=("data",),
                    model_axis: str = "model", strategy: str = "compact",
                    max_survivors: Optional[int] = None,
                    dist_impl: Optional[str] = None):
    """The shard_map'ped search as a jit-able function of explicit args."""
    search_fn = _make_shard_body(max_leaf, model_axis, strategy,
                                 max_survivors, dist_impl)
    spec_idx = P(model_axis)
    spec_q = P(data_axes)
    smapped = jax.shard_map(
        search_fn, mesh=mesh,
        in_specs=(spec_idx,) * 13 + (spec_q, spec_q),
        out_specs=(P(model_axis, *data_axes), P(model_axis, *data_axes)),
        check_vma=False)
    in_sh = tuple(NamedSharding(mesh, spec_idx) for _ in range(13)) \
        + (NamedSharding(mesh, spec_q), NamedSharding(mesh, spec_q))
    return jax.jit(smapped, in_shardings=in_sh), spec_idx, spec_q


def make_distributed_search(mesh: Mesh, sharded: ShardedLeaFi,
                            data_axes=("data",), model_axis: str = "model",
                            strategy: str = "compact",
                            max_survivors: Optional[int] = None,
                            dist_impl: Optional[str] = None,
                            per_query_offsets: bool = False,
                            donate: bool = False,
                            trace: bool = False,
                            audit: bool = False):
    """Build the jitted multi-chip search step over ``mesh``.

    Returns fn(queries (Q, m)) → (nn_dist (Q,), total_searched (Q,)), where
    ``total_searched`` is the ``psum``-reduced **total** searched-leaf count
    across all shards per query (replicated per shard by the collective; the
    caller reads one replica) — i.e. it sums to the same accounting as
    running the per-shard cascades on a single device.  Queries shard over
    ``data_axes``; the index over ``model_axis``.

    strategy: ``"compact"`` (default) = fixed-width survivor compaction per
    shard (``engine.compact_bsf_cascade``; ``max_survivors`` caps the static
    buffer, ``dist_impl`` picks the candidate distance algebra);
    ``"scan"`` = the masked-scan parity fallback.

    per_query_offsets: the serving-runtime signature —
    fn(queries (Q, m), qoffsets (Q, L), bsf_ub (Q,)) — where each query
    carries its own per-leaf conformal offset row (mixed quality targets in
    one compiled program; gathered per shard via ``sharded.leaf_global``)
    and ``bsf_ub`` is the prune-only warm-start bound (+inf rows = no-op).

    donate: donate the per-call query/offset/bound buffers to the compiled
    program (per-query mode only) so steady-state pipelined serving re-uses
    their device allocations instead of growing the arena.  Skipped on CPU,
    where XLA ignores donation and warns.

    trace: the returned fn additionally yields a per-query
    :class:`~repro.obs.trace.CascadeTrace` psum'd across shards (see
    ``_make_shard_body``); the nn/searched outputs are bitwise those of
    the untraced program.

    audit: the returned fn additionally yields a per-leaf
    :class:`~repro.obs.audit.FilterAudit` in the ``(S, P)`` shard-slot
    layout — psum'd over the data axes inside the body, concatenated
    across the model axis (each model shard owns distinct leaves).  Fold
    to global ``(L,)`` leaf order with
    ``obs.audit.scatter_global(fa, sharded.leaf_global, n_leaves)``.
    Output order is ``(nn, searched[, trace][, audit])`` in flag order.
    """
    max_leaf = sharded.max_leaf
    spec_idx = P(model_axis)
    spec_q = P(data_axes)
    search_fn = _make_shard_body(max_leaf, model_axis, strategy,
                                 max_survivors, dist_impl,
                                 per_query_offsets=per_query_offsets,
                                 trace=trace, audit=audit,
                                 data_axes=data_axes)
    spec_out = P(model_axis, *data_axes)
    out_specs = (spec_out, spec_out)
    if trace:
        out_specs = out_specs + (CascadeTrace(*((spec_out,) * 7)),)
    if audit:
        # audit fields shard over the model axis only: the leading (1,)
        # per-shard row concatenates into the (S, P) layout, and the
        # data-axis psum already replicated the values across data shards.
        out_specs = out_specs + (FilterAudit(
            *((P(model_axis),) * len(FilterAudit._fields))),)

    idx_args = (sharded.series, sharded.leaf_start, sharded.leaf_size,
                sharded.lb_lo, sharded.lb_hi, sharded.w1, sharded.b1,
                sharded.w2, sharded.b2, sharded.y_mean, sharded.y_std,
                sharded.offsets, sharded.has_filter)
    qscale = jnp.asarray(sharded.qscale)

    def unwrap(out):
        # collectives replicate nn/searched across the model axis; row 0 is
        # the global nn and the all-shard total searched count per query
        rets = (out[0][0], out[1][0])
        rest = list(out[2:])
        if trace:
            rets = rets + (jax.tree.map(lambda x: x[0], rest.pop(0)),)
        if audit:
            rets = rets + (rest.pop(0),)        # (S, P) layout — no unwrap
        return rets

    # the index arrays are arguments of the jitted programs, never
    # closed-over constants: a constant would be baked into the HLO, which
    # at collection scale is gigabytes of program text.
    if per_query_offsets:
        if sharded.leaf_global is None:
            raise ValueError("per_query_offsets needs ShardedLeaFi.leaf_global"
                             " (re-shard with the current shard_leafi)")
        idx_pq = idx_args + (sharded.leaf_global,)
        # qoffsets shard over queries like the batch; the L axis replicates
        smapped = jax.shard_map(
            search_fn, mesh=mesh,
            in_specs=(spec_idx,) * len(idx_pq)
            + (spec_q, spec_q, P(data_axes, None), spec_q),
            out_specs=out_specs,
            check_vma=False,
        )

        def run_pq(idx, queries, qoffsets, bsf_ub):
            qcoords = _query_coords(sharded.kind, idx[3], qscale, queries)
            return unwrap(smapped(*idx, queries, qcoords, qoffsets, bsf_ub))

        donate_kw = {}
        if donate and jax.default_backend() != "cpu":
            donate_kw["donate_argnums"] = (1, 2, 3)
        jitted_pq = jax.jit(run_pq, **donate_kw)
        return (functools.partial(jitted_pq, idx_pq), idx_pq, spec_idx,
                spec_q)

    smapped = jax.shard_map(
        search_fn, mesh=mesh,
        in_specs=(spec_idx,) * len(idx_args) + (spec_q, spec_q),
        out_specs=out_specs,
        check_vma=False,
    )

    @jax.jit
    def run(idx, queries):
        qcoords = _query_coords(sharded.kind, idx[3], qscale, queries)
        return unwrap(smapped(*idx, queries, qcoords))

    return functools.partial(run, idx_args), idx_args, spec_idx, spec_q
