"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state: mesh creation happens only inside launchers, after any
XLA_FLAGS the entrypoint set have taken effect.
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1):
    """Whatever-fits mesh for local runs/examples (1 device ⇒ (1, 1))."""
    n = jax.device_count()
    data = max(n // model, 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
