"""Helpers shared by every kernel's jitted wrapper.

Each ``ops.py`` used to carry its own copy of the backend probe and the
padding helpers; they live here once.  The conventions they encode:

* **interpret-vs-oracle**: off-TPU (``interpret=None``) the wrappers run the
  mathematically-identical jnp oracle instead of the Pallas kernel — Pallas
  interpret mode executes the kernel body per grid step in Python, fine for
  validation (tests pass ``interpret=True`` explicitly), hopeless for real
  workloads.
* **zero padding is exact by construction**: operands are padded up to the
  TPU tile multiples with values whose contribution is the identity of the
  reduction they feed (zeros for matmul/L2 terms, ±inf for box edges), and
  the wrapper slices the padding back off.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def tpu_params(*semantics: str) -> pltpu.CompilerParams:
    """Mosaic compiler parameters: one dimension semantic per grid axis.

    Interpret mode ignores them, so every kernel passes them unconditionally.
    """
    return pltpu.CompilerParams(dimension_semantics=semantics)


def use_interpret() -> bool:
    """True when the Pallas kernels should be bypassed for the jnp oracle."""
    return jax.default_backend() != "tpu"


def pad_to(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    """Zero-pad ``axis`` up to the next multiple of ``mult``."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pad_rows(x: jnp.ndarray, mult: int, fill: float) -> jnp.ndarray:
    """Pad the leading axis up to a multiple of ``mult`` with ``fill``."""
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0
    )
