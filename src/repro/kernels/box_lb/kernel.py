"""Unified box lower-bound Pallas kernel (iSAX MINDIST ∪ DSTree EAPCA LB).

Lower bounds are computed for *every* leaf on *every* query up front in the
LeaFi search (the pruning cascade then runs on scalars), so this kernel's
shape is (Q queries × L leaves × d box dims).  It is VPU-bound — elementwise
max/mul with a small reduction — so the tiling goal is purely bandwidth: keep
(bq × bl × d) intermediates inside VMEM and stream the (L, d) box edges once.

Grid = (Q/bq, L/bl); per-step working set at bq=bl=128, d=16:
128·128·16·4 B = 1 MiB for the broadcast intermediate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import tpu_params


def _box_kernel(q_ref, lo_ref, hi_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)              # (bq, d)
    lo = lo_ref[...].astype(jnp.float32)            # (bl, d)
    hi = hi_ref[...].astype(jnp.float32)
    d = jnp.maximum(
        jnp.maximum(lo[None, :, :] - q[:, None, :], q[:, None, :] - hi[None, :, :]),
        0.0,
    )
    d = jnp.where(jnp.isfinite(d), d, 0.0)
    o_ref[...] = jnp.sqrt((d * d).sum(-1))          # (bq, bl)


def box_lb_kernel(
    q: jnp.ndarray,                # (Q, d), Q multiple of bq
    lo: jnp.ndarray,               # (L, d), L multiple of bl
    hi: jnp.ndarray,               # (L, d)
    *,
    bq: int = 128,
    bl: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    Q, d = q.shape
    L, _ = lo.shape
    grid = (Q // bq, L // bl)
    return pl.pallas_call(
        _box_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bl, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bl, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bl), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, L), jnp.float32),
        compiler_params=tpu_params("parallel", "parallel"),
        interpret=interpret,
    )(q, lo, hi)
