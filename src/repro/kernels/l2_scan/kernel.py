"""Tiled pairwise-L2 Pallas kernel (the LeaFi leaf-scan hot spot).

MESSI scans leaves with SIMD CPU loops; on TPU the same computation is a
matmul: ‖q−s‖² = ‖q‖² + ‖s‖² − 2·q·sᵀ, so the MXU does the heavy lifting.

Grid = (Q/bq, B/bb, m/bk).  The k axis accumulates −2·q·sᵀ into the output
block (index map independent of k); on the last k step the norms are fused in
and the sqrt epilogue runs.  f32 accumulation throughout; inputs may be bf16.

VMEM working set per step: q (bq·bk), s (bb·bk), out (bq·bb) — at the default
128³ tiling ≈ 3 × 64 KiB, comfortably inside the ~16 MiB VMEM budget, leaving
room for double buffering of the q/s streams from HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import tpu_params

# f32 contractions at full precision: the MXU's default rounds f32 operands
# to bf16, which shifts ‖q‖²+‖s‖²−2·q·sᵀ by far more than f32 rounding.
_HIGHEST = jax.lax.Precision.HIGHEST


def _l2_kernel(q_ref, s_ref, qn_ref, sn_ref, o_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    o_ref[...] += -2.0 * jax.lax.dot_general(
        q, s, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        d2 = o_ref[...] + qn_ref[...].T + sn_ref[...]
        o_ref[...] = jnp.sqrt(jnp.maximum(d2, 0.0))


def pairwise_l2_kernel(
    queries: jnp.ndarray,          # (Q, m) — Q, m multiples of the tile
    series: jnp.ndarray,           # (B, m)
    q_norms: jnp.ndarray,          # (1, Q) squared norms
    s_norms: jnp.ndarray,          # (1, B)
    *,
    bq: int = 128,
    bb: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    Q, m = queries.shape
    B, _ = series.shape
    nk = m // bk
    grid = (Q // bq, B // bb, nk)
    return pl.pallas_call(
        functools.partial(_l2_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bb, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bq), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, bb), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bb), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, B), jnp.float32),
        compiler_params=tpu_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(queries, series, q_norms, s_norms)


def _slab_l2_kernel(q_ref, s_ref, qn_ref, sn_ref, o_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[0].astype(jnp.float32)
    s = s_ref[0].astype(jnp.float32)
    o_ref[0] += -2.0 * jax.lax.dot_general(
        q, s, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        d2 = o_ref[0] + qn_ref[0].T + sn_ref[0]
        o_ref[0] = jnp.sqrt(jnp.maximum(d2, 0.0))


def slab_l2_kernel(
    queries: jnp.ndarray,          # (F, Nq, m) per-slab query batches
    slabs: jnp.ndarray,            # (F, R, m) padded leaf slabs
    q_norms: jnp.ndarray,          # (F, 1, Nq) squared norms
    s_norms: jnp.ndarray,          # (F, 1, R)
    *,
    bq: int = 128,
    bb: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched pairwise-L2 over stacked leaf slabs → (F, Nq, R).

    The slab axis F rides as a leading parallel grid dimension (block width
    1): each grid step runs the same ‖q‖²+‖s‖²−2·q·sᵀ accumulation as
    :func:`pairwise_l2_kernel` on one slab's tile, so the F filters of the
    build pipeline share a single kernel launch instead of F dispatches.
    """
    F, Nq, m = queries.shape
    _, R, _ = slabs.shape
    nk = m // bk
    grid = (F, Nq // bq, R // bb, nk)
    return pl.pallas_call(
        functools.partial(_slab_l2_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, bk), lambda f, i, j, k: (f, i, k)),
            pl.BlockSpec((1, bb, bk), lambda f, i, j, k: (f, j, k)),
            pl.BlockSpec((1, 1, bq), lambda f, i, j, k: (f, 0, i)),
            pl.BlockSpec((1, 1, bb), lambda f, i, j, k: (f, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, bb), lambda f, i, j, k: (f, i, j)),
        out_shape=jax.ShapeDtypeStruct((F, Nq, R), jnp.float32),
        compiler_params=tpu_params("parallel", "parallel", "parallel",
                                   "arbitrary"),
        interpret=interpret,
    )(queries, slabs, q_norms, s_norms)
