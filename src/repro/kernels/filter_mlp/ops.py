"""Jitted wrappers for stacked filter-MLP inference.

Two entry points over the same stacked parameters:

* :func:`filter_predict` — the original per-filter-step kernel (grid (F,
  Q/bq)), kept as the baseline the fused path is benchmarked against.
* :func:`filter_predict_fused` — the filter-block megakernel (grid (F/bf,
  Q/bq)) with the de-standardization/offset epilogue fused in and optional
  bf16/int8 compressed weights; this is what the search path runs on TPU.

Zero-padding on m and h is exact: padded input dims meet zero w1 rows;
padded hidden dims have zero b1/w2, so relu(0)·0 contributes nothing.
Padded filters (F → bf multiple) have all-zero weights *and stats*, so their
rows are finite garbage-free zeros and are sliced off.  Off-TPU the jnp
oracle runs (see kernels/common.py for the rationale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel, ref
from ..common import pad_to as _pad_to, use_interpret as _use_interpret


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def filter_predict(
    w1: jnp.ndarray,               # (F, m, h)
    b1: jnp.ndarray,               # (F, h)
    w2: jnp.ndarray,               # (F, h)
    b2: jnp.ndarray,               # (F,)
    queries: jnp.ndarray,          # (Q, m)
    *,
    bq: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """All-filters × all-queries raw predictions → (F, Q) float32."""
    if interpret is None:
        if _use_interpret():
            return ref.filter_predict(w1, b1, w2, b2, queries)
        interpret = False
    F, m, h = w1.shape
    Q = queries.shape[0]
    qp = _pad_to(_pad_to(queries, bq, 0), 128, 1)
    w1p = _pad_to(_pad_to(w1, 128, 1), 128, 2)
    b1p = _pad_to(b1, 128, 1)
    w2p = _pad_to(w2, 128, 1)
    out = kernel.filter_mlp_kernel(
        qp, w1p, b1p[:, None], w2p[:, None], b2[:, None, None], bq=bq,
        interpret=interpret)
    return out[:, 0, :Q]


def pack_fused(w1, b1, w2, b2, y_mean, y_std, offsets=None,
               w1_scale=None, w2_scale=None, *, bf: int = 8) -> dict:
    """Stacked (F, …) params → the megakernel's grouped, padded operands.

    Layer-1 weights become (G, m', bf·h') blocks (filter-major within the
    lane axis: lane j of group g is filter ``g·bf + j//h'``), layer-2 rows
    follow the same lane layout as (G, 1, bf·h') and per-filter scalars
    become (G, bf, 1) columns (the kernel module says why the unit axes).
    int8 scales are expanded to per-lane rows here so the kernel's dequant
    is a plain broadcast multiply.  Grouping is one transpose-copy of the
    weight bytes; callers on a hot loop should pack once and reuse.
    """
    F, m, h = w1.shape
    G = -(-F // bf)
    w1p = _pad_to(_pad_to(_pad_to(w1, 128, 1), 128, 2), bf, 0)
    hp = w1p.shape[2]
    w1g = w1p.reshape(G, bf, w1p.shape[1], hp).transpose(0, 2, 1, 3)

    def col(v):                                  # (F,) → (G, bf, 1) f32
        return _pad_to(v.astype(jnp.float32), bf, 0).reshape(G, bf, 1)

    out = {
        "w1g": w1g.reshape(G, w1p.shape[1], bf * hp),
        "b1g": _pad_to(_pad_to(b1, 128, 1), bf, 0)
        .astype(jnp.float32).reshape(G, 1, bf * hp),
        "w2g": _pad_to(_pad_to(w2, 128, 1), bf, 0).reshape(G, 1, bf * hp),
        "b2g": col(b2), "ymg": col(y_mean), "ysg": col(y_std),
        "offg": (jnp.zeros((G, bf, 1), jnp.float32) if offsets is None
                 else col(offsets)),
    }
    for name, s in (("s1g", w1_scale), ("s2g", w2_scale)):
        if s is not None:
            srow = jnp.broadcast_to(
                _pad_to(s.astype(jnp.float32), bf, 0)[:, None],
                (G * bf, hp))
            out[name] = srow.reshape(G, 1, bf * hp)
    return out


@functools.partial(jax.jit, static_argnames=("bq", "bf", "interpret"))
def filter_predict_fused(
    w1: jnp.ndarray,               # (F, m, h) f32 | bf16 | int8
    b1: jnp.ndarray,               # (F, h) float32
    w2: jnp.ndarray,               # (F, h) f32 | bf16 | int8
    b2: jnp.ndarray,               # (F,) float32
    y_mean: jnp.ndarray,           # (F,) de-standardization stats
    y_std: jnp.ndarray,            # (F,)
    queries: jnp.ndarray,          # (Q, m)
    offsets: jnp.ndarray | None = None,     # (F,) conformal offsets
    w1_scale: jnp.ndarray | None = None,    # (F,) int8 scales
    w2_scale: jnp.ndarray | None = None,
    *,
    bq: int = 128,
    bf: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """De-standardized, offset-adjusted predictions → (F, Q) float32.

    One kernel launch replaces kernel + three broadcast passes (y_std,
    y_mean, offsets) over the (F, Q) output.  ``w1.dtype`` selects the
    variant: float32/bfloat16 load-and-upcast, int8 dequants in-kernel via
    the per-filter scales (both required then).
    """
    if interpret is None:
        if _use_interpret():
            return ref.filter_predict_destd(
                w1, b1, w2, b2, y_mean, y_std, queries, offsets,
                w1_scale, w2_scale)
        interpret = False
    if (w1.dtype == jnp.int8) != (w1_scale is not None):
        raise ValueError("int8 weights require w1_scale/w2_scale "
                         "(and float weights must not carry them)")
    F = w1.shape[0]
    Q = queries.shape[0]
    qp = _pad_to(_pad_to(queries, bq, 0), 128, 1)
    g = pack_fused(w1, b1, w2, b2, y_mean, y_std, offsets,
                   w1_scale, w2_scale, bf=bf)
    out = kernel.fused_filter_mlp_kernel(
        qp, g["w1g"], g["b1g"], g["w2g"], g["b2g"], g["ymg"], g["ysg"],
        g["offg"], s1g=g.get("s1g"), s2g=g.get("s2g"),
        bq=bq, bf=bf, interpret=interpret)
    return out[:F, :Q]


reference = ref.filter_predict
fused_reference = ref.filter_predict_destd
