"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points call :func:`enable_compile_cache` once, at start-up (never at
import).  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing here overrides it.  Otherwise the cache goes to one fixed directory
of the checkout, ``<repo>/.jax_cache`` (git-ignored): the directory is part
of the cache key, so it never carries a temporary name, a process id or a
time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
