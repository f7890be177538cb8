"""Stacked per-leaf MLP inference Pallas kernels.

The paper runs one tiny MLP per visited leaf on a GPU, one call at a time.
On TPU we stack all F filters' weights — w1 (F, m, h), b1 (F, h), w2 (F, h),
b2 (F,) — and evaluate every (filter × query) pair in grouped-matmul kernels.
Two grid layouts:

* ``filter_mlp_kernel`` — the original per-filter sweep: grid (F, Q/bq);
  each step loads ONE filter's (m, h) weight block into VMEM and pushes a
  bq-query tile through the two layers.  The query tile is re-streamed from
  HBM once per filter, so the sweep is weight/query-bandwidth-bound and
  F-linear regardless of batch size.

* ``fused_filter_mlp_kernel`` — the filter-block megakernel: grid
  (F/bf, Q/bq).  The stacked weights are pre-grouped outside the kernel into
  (F/bf, m, bf·h) layer-1 blocks and (F/bf, bf·h) layer-2 rows, so each step
  evaluates ``bf`` filters with ONE (bq, m) × (m, bf·h) MXU matmul — the
  VMEM-resident query tile is amortized across bf filters' weights (a bf×
  cut of the query re-stream) and the single wide matmul keeps the MXU fed
  where bf narrow ones would each pay their own latency.  Layer 2 is an
  elementwise multiply with the grouped w2 row followed by a per-group sum,
  expressed as a matmul against a block-diagonal 0/1 group-sum operand so it
  also runs on the MXU.  The epilogue applies b2, the per-filter
  ``y_mean``/``y_std`` de-standardization and the conformal offset
  subtraction in-register, so the megakernel's output is the search-ready
  d_F block — no separate broadcast passes over the (F, Q) output.

The fused kernel also takes compressed weights: bf16 blocks are upcast on
load (half the weight stream), int8 blocks carry per-filter max-abs/127
scales (``optim.compress``'s symmetric scheme at filter granularity, a 4×
cut) and the scales are folded in after the matmul — algebraically exact
w.r.t. dequantize-then-multiply because each scale is constant per output
column.

Block layout: Mosaic takes a block whose last two dimensions are multiples
of the (8, 128) tile or equal the array's own.  Every per-filter vector
therefore carries a unit middle axis — (F, 1, h) rows, (G, 1, bf·h) grouped
rows, (G, bf, 1) per-filter columns — whose (1, ·) / (·, 1) block equals
the full dimension; the outputs are written filter-major, so no in-kernel
transpose is needed.

VMEM per fused step at m = h = 256, bf = 8, bq = 128: w1 block 2 MiB f32
(512 KiB int8), double-buffered; query tile 128 KiB; hidden and its
layer-2 product 1 MiB each; the (bf, bf·h) group-sum operand 64 KiB.
tests/test_tpu_compile.py compiles all three weight dtypes at those widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import tpu_params

_NT = (((1,), (1,)), ((), ()))        # contract both operands' last axis
_NN = (((1,), (0,)), ((), ()))


def _mlp_kernel(q_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)                       # (bq, m)
    w1 = w1_ref[0].astype(jnp.float32)                       # (m, h)
    hidden = jnp.maximum(
        jax.lax.dot_general(q, w1, _NN, preferred_element_type=jnp.float32)
        + b1_ref[0].astype(jnp.float32),                     # (bq, h)
        0.0,
    )
    w2 = w2_ref[0].astype(jnp.float32)                       # (1, h)
    out = jax.lax.dot_general(w2, hidden, _NT,
                              preferred_element_type=jnp.float32)  # (1, bq)
    o_ref[0] = out + b2_ref[0]                               # (1, bq)


def filter_mlp_kernel(
    queries: jnp.ndarray,          # (Q, m), Q multiple of bq
    w1: jnp.ndarray,               # (F, m, h)
    b1: jnp.ndarray,               # (F, 1, h)
    w2: jnp.ndarray,               # (F, 1, h)
    b2: jnp.ndarray,               # (F, 1, 1)
    *,
    bq: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-filter sweep → (F, 1, Q) raw predictions."""
    Q, m = queries.shape
    F, _, h = w1.shape
    grid = (F, Q // bq)
    return pl.pallas_call(
        _mlp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, m), lambda f, q: (q, 0)),
            pl.BlockSpec((1, m, h), lambda f, q: (f, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda f, q: (f, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda f, q: (f, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda f, q: (f, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq), lambda f, q: (f, 0, q)),
        out_shape=jax.ShapeDtypeStruct((F, 1, Q), jnp.float32),
        compiler_params=tpu_params("parallel", "parallel"),
        interpret=interpret,
    )(queries, w1, b1, w2, b2)


# ---------------------------------------------------------------------------
# fused filter-block megakernel
# ---------------------------------------------------------------------------


def _group_sum_operand(bf: int, bfh: int, h: int) -> jnp.ndarray:
    """(bf, bf·h) block-diagonal 0/1 matrix: row f sums its filter's h
    hidden lanes.  Built from iota so it materializes in-register — no HBM
    operand, and the layer-2 reduction stays a plain MXU matmul."""
    row = jax.lax.broadcasted_iota(jnp.int32, (bf, bfh), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bf, bfh), 1)
    return (col // h == row).astype(jnp.float32)


def _fused_epilogue(hw, b2_ref, ym_ref, ys_ref, off_ref, o_ref, h, bf):
    # layer-2 group sum straight into filter-major (bf, bq) — the output
    # block's own layout, so nothing is transposed in-kernel
    z = jax.lax.dot_general(
        _group_sum_operand(bf, hw.shape[1], h), hw, _NT,
        preferred_element_type=jnp.float32) + b2_ref[0]      # (bf, bq)
    # epilogue: de-standardize + conformal offset, same op order as the
    # unfused composition (z·y_std + y_mean, then −offset) so the fused
    # output is bitwise-equal to it.
    o_ref[...] = z * ys_ref[0] + ym_ref[0] - off_ref[0]


def _fused_body(q_ref, w1_ref, b1_ref, w2_ref, b2_ref, ym_ref, ys_ref,
                off_ref, o_ref, *, h: int, bf: int):
    q = q_ref[...].astype(jnp.float32)                       # (bq, m)
    w1 = w1_ref[0].astype(jnp.float32)                       # (m, bf·h)
    hidden = jnp.maximum(
        jax.lax.dot_general(q, w1, _NN, preferred_element_type=jnp.float32)
        + b1_ref[0],                                         # (bq, bf·h)
        0.0,
    )
    hw = hidden * w2_ref[0].astype(jnp.float32)              # (bq, bf·h)
    _fused_epilogue(hw, b2_ref, ym_ref, ys_ref, off_ref, o_ref, h, bf)


def _fused_body_q(q_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref, b2_ref,
                  ym_ref, ys_ref, off_ref, o_ref, *, h: int, bf: int):
    """int8 variant: weights arrive quantized; per-filter scales are folded
    in after the layer-1 matmul (exact per output column) and into the
    grouped w2 row before the elementwise multiply."""
    q = q_ref[...].astype(jnp.float32)                       # (bq, m)
    w1 = w1_ref[0].astype(jnp.float32)                       # (m, bf·h) deq.
    hidden = jnp.maximum(
        jax.lax.dot_general(q, w1, _NN, preferred_element_type=jnp.float32)
        * s1_ref[0]                                          # (1, bf·h)
        + b1_ref[0],
        0.0,
    )
    w2 = w2_ref[0].astype(jnp.float32) * s2_ref[0]           # (1, bf·h)
    _fused_epilogue(hidden * w2, b2_ref, ym_ref, ys_ref, off_ref, o_ref, h,
                    bf)


def fused_filter_mlp_kernel(
    queries: jnp.ndarray,          # (Q, m), Q multiple of bq
    w1g: jnp.ndarray,              # (G, m, bf·h) grouped layer-1 blocks
    b1g: jnp.ndarray,              # (G, 1, bf·h) float32
    w2g: jnp.ndarray,              # (G, 1, bf·h)
    b2g: jnp.ndarray,              # (G, bf, 1) float32
    ymg: jnp.ndarray,              # (G, bf, 1) per-filter y_mean
    ysg: jnp.ndarray,              # (G, bf, 1) per-filter y_std
    offg: jnp.ndarray,             # (G, bf, 1) conformal offsets (zeros = none)
    *,
    s1g: jnp.ndarray | None = None,   # (G, 1, bf·h) int8 scales, expanded
    s2g: jnp.ndarray | None = None,   # (G, 1, bf·h)
    bq: int = 128,
    bf: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Grouped operands → (G·bf, Q) de-standardized, offset-adjusted preds.

    ``w1g``/``w2g`` may be float32, bfloat16 or int8; int8 requires the
    expanded per-filter scale rows.  Grouping/padding is the wrapper's job
    (:func:`repro.kernels.filter_mlp.ops.pack_fused`).
    """
    Q, m = queries.shape
    G, _, bfh = w1g.shape
    h = bfh // bf
    quantized = s1g is not None
    body = functools.partial(
        _fused_body_q if quantized else _fused_body, h=h, bf=bf)
    vec_spec = pl.BlockSpec((1, 1, bfh), lambda g, t: (g, 0, 0))
    flt_spec = pl.BlockSpec((1, bf, 1), lambda g, t: (g, 0, 0))
    in_specs = [
        pl.BlockSpec((bq, m), lambda g, t: (t, 0)),
        pl.BlockSpec((1, m, bfh), lambda g, t: (g, 0, 0)),
    ]
    operands = [queries, w1g]
    if quantized:
        in_specs.append(vec_spec)
        operands.append(s1g)
    in_specs += [vec_spec, vec_spec]
    operands += [b1g, w2g]
    if quantized:
        in_specs.append(vec_spec)
        operands.append(s2g)
    in_specs += [flt_spec, flt_spec, flt_spec, flt_spec]
    operands += [b2g, ymg, ysg, offg]
    return pl.pallas_call(
        body,
        grid=(G, Q // bq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bf, bq), lambda g, t: (g, t)),
        out_shape=jax.ShapeDtypeStruct((G * bf, Q), jnp.float32),
        compiler_params=tpu_params("parallel", "parallel"),
        interpret=interpret,
    )(*operands)
