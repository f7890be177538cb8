"""Jitted wrapper around the l2_scan kernel: padding, norms, masking, min."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel, ref
from ... import sanitize
from ..common import pad_to as _pad_to, use_interpret as _use_interpret

_INF = jnp.float32(jnp.inf)
# every f32 contraction of the ‖q‖²+‖s‖²−2·q·sᵀ algebra runs at full
# precision: the TPU default rounds f32 operands to bf16, which moves a
# z-normalized distance by far more than the exact-search contract allows.
_HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("bq", "bb", "bk", "interpret"))
def pairwise_l2(
    queries: jnp.ndarray,
    series: jnp.ndarray,
    *,
    bq: int = 128,
    bb: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(Q, m) × (B, m) → (Q, B) euclidean distances via the Pallas kernel.

    Off-TPU (interpret=None) the mathematically-identical jnp oracle runs
    instead: Pallas interpret mode executes the kernel body per grid step in
    Python — fine for validation (tests pass interpret=True explicitly),
    hopeless for the benchmark workloads.
    """
    if interpret is None:
        if _use_interpret():
            return ref.pairwise_l2_matmul(queries, series)
        interpret = False
    Q, m = queries.shape
    B, _ = series.shape
    bk = min(bk, max(128, 1 << (m - 1).bit_length()))  # never exceed padded m
    qp = _pad_to(_pad_to(queries, bq, 0), bk, 1)
    sp = _pad_to(_pad_to(series, bb, 0), bk, 1)
    qn = (qp.astype(jnp.float32) ** 2).sum(-1)[None, :]
    sn = (sp.astype(jnp.float32) ** 2).sum(-1)[None, :]
    out = kernel.pairwise_l2_kernel(
        qp, sp, qn, sn, bq=bq, bb=bb, bk=bk, interpret=interpret
    )
    return out[:Q, :B]


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_min_l2(
    queries: jnp.ndarray,          # (Q, m)
    slab: jnp.ndarray,             # (B, m) leaf slab (may contain padding)
    valid: jnp.ndarray,            # (B,) bool
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-query min distance over the valid rows of a leaf slab.

    Returns (min_dist (Q,), argmin (Q,) — index into the slab).
    """
    d = pairwise_l2(queries, slab, interpret=interpret)
    d = jnp.where(valid[None, :], d, _INF)
    return d.min(axis=1), d.argmin(axis=1)


def default_gathered_impl() -> str:
    """Distance formulation the search engine should use on this backend.

    ``matmul`` is the kernel's decomposition (‖q‖² + ‖s‖² − 2·q·sᵀ): for the
    per-query gathered slabs of the compact search engine it lowers to one
    batched GEMM, which is the MXU mapping of the candidate pass.  Off-TPU we
    default to ``direct`` (elementwise diff-square), which is bitwise-stable
    against the sequential scan path — the engine's parity suite relies on
    that.
    """
    return "matmul" if jax.default_backend() == "tpu" else "direct"


def gathered_leaf_l2(
    queries: jnp.ndarray,          # (N, m)
    slabs: jnp.ndarray,            # (N, C, R, m) per-query gathered leaf rows
    impl: str | None = None,
) -> jnp.ndarray:
    """Euclidean distances from each query to its own candidate slab.

    Unlike :func:`pairwise_l2` (one shared series block for all queries) each
    query here owns a different (C·R)-row candidate set — the output of the
    engine's survivor compaction — so the all-pairs kernel would recompute
    every other query's candidates too.  The ``matmul`` impl keeps the
    kernel's exact algebra but contracts per query (batched GEMM → MXU); the
    ``direct`` impl matches the scan path bit-for-bit.  Returns (N, C, R).
    """
    impl = impl or default_gathered_impl()
    q = queries.astype(jnp.float32)
    s = slabs.astype(jnp.float32)
    if impl == "direct":
        diff = s - q[:, None, None, :]
        return jnp.sqrt((diff * diff).sum(-1))
    if impl == "matmul":
        qn = (q * q).sum(-1)
        sn = (s * s).sum(-1)
        dot = jnp.einsum("ncrm,nm->ncr", s, q,
                         precision=_HIGHEST,
                         preferred_element_type=jnp.float32)
        return jnp.sqrt(jnp.maximum(qn[:, None, None] + sn - 2.0 * dot, 0.0))
    raise ValueError(f"unknown gathered-l2 impl {impl!r}")


def leaf_topk(
    dists: jnp.ndarray,            # (N, C, R) masked distances (+inf invalid)
    rows: jnp.ndarray,             # (N, C, R) global row ids
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-leaf k smallest distances and their row ids → ((N,C,k), (N,C,k)).

    ``lax.top_k`` breaks ties toward the lower index, i.e. toward the lower
    row within the leaf — the same order the sequential scan path merges
    candidates in, which keeps the engine's replay bitwise-faithful.
    """
    neg, arg = jax.lax.top_k(-dists, k)
    return -neg, jnp.take_along_axis(rows, arg, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Leaf-slab batch layer: padded (F, R, m) gathers + vmapped masked primitives.
# The build pipeline (filter_training via core/engine.py) and the engine's
# pairwise candidate pass are expressed on these instead of per-leaf loops.
# ---------------------------------------------------------------------------


def gather_leaf_slabs(
    series: jnp.ndarray,           # (n + max_leaf, m) leaf-sorted, padded
    leaf_start: jnp.ndarray,       # (L,)
    leaf_size: jnp.ndarray,        # (L,)
    leaf_ids: jnp.ndarray,         # (F,) — ids == L are invalid sentinels
    max_leaf: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Padded leaf slabs for a batch of leaves.

    Returns (slabs (F, R, m), rows (F, R) global row ids, valid (F, R)).
    Invalid leaf ids (== L, the engine's padding convention) clamp their
    gathers harmlessly and come back with an all-False valid mask; the
    clamp is explicit (``jnp.minimum``), so ``REPRO_CHECKIFY=1`` eager
    calls (routed through ``repro.sanitize``) stay clean on healthy
    layouts and trip on genuinely corrupted ones (a ``leaf_start`` aimed
    past the padded series rows).
    """
    return sanitize.call(_gather_leaf_slabs, series, leaf_start, leaf_size,
                         leaf_ids, max_leaf)


def _gather_leaf_slabs(series, leaf_start, leaf_size, leaf_ids, max_leaf):
    L = leaf_start.shape[0]
    ids = jnp.asarray(leaf_ids)
    ok = ids < L
    safe = jnp.minimum(ids, L - 1)
    starts = leaf_start[safe]                            # (F,)
    sizes = jnp.where(ok, leaf_size[safe], 0)            # (F,)
    rows = starts[:, None] + jnp.arange(max_leaf)[None, :]
    slabs = series[rows]                                 # (F, R, m)
    valid = jnp.arange(max_leaf)[None, :] < sizes[:, None]
    return slabs, rows.astype(jnp.int32), valid


def default_slab_impl() -> str:
    """Distance formulation for the slab layer on this backend.

    On TPU the batched ``pairwise`` Pallas kernel tiles the MXU directly; off
    TPU ``matmul`` (the identical ‖q‖²+‖s‖²−2·q·sᵀ algebra as one einsum) is
    the fast XLA form.  Both share the matmul decomposition the seed build
    path already routed through, so build-side results stay within float
    tolerance of the per-leaf reference either way.
    """
    return "pairwise" if jax.default_backend() == "tpu" else "matmul"


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def slab_l2(
    queries: jnp.ndarray,          # (F, Nq, m) per-slab query batches
    slabs: jnp.ndarray,            # (F, R, m)
    impl: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Distances from each slab's own query batch to the slab → (F, Nq, R).

    impl: "direct" (elementwise, bitwise-stable vs the scan path), "matmul"
    (one einsum of the kernel's decomposition), or "pairwise" (the batched
    ``slab_l2_kernel`` Pallas path; off-TPU with interpret=None it falls back
    to the mathematically identical matmul form, as :func:`pairwise_l2`
    does).
    """
    impl = impl or default_slab_impl()
    q = queries.astype(jnp.float32)
    s = slabs.astype(jnp.float32)
    if impl == "direct":
        diff = q[:, :, None, :] - s[:, None, :, :]
        return jnp.sqrt((diff * diff).sum(-1))
    if impl == "matmul":
        qn = (q * q).sum(-1)                             # (F, Nq)
        sn = (s * s).sum(-1)                             # (F, R)
        dot = jnp.einsum("fqm,frm->fqr", q, s,
                         precision=_HIGHEST,
                         preferred_element_type=jnp.float32)
        return jnp.sqrt(jnp.maximum(
            qn[:, :, None] + sn[:, None, :] - 2.0 * dot, 0.0))
    if impl == "pairwise":
        if interpret is None:
            if _use_interpret():
                return slab_l2(queries, slabs, "matmul")
            interpret = False
        F, Nq, m = q.shape
        _, R, _ = s.shape
        bq = bb = bk = 128
        qp = _pad_to(_pad_to(q, bq, 1), bk, 2)
        sp = _pad_to(_pad_to(s, bb, 1), bk, 2)
        qn = (qp ** 2).sum(-1)[:, None, :]               # (F, 1, Nq')
        sn = (sp ** 2).sum(-1)[:, None, :]               # (F, 1, R')
        out = kernel.slab_l2_kernel(qp, sp, qn, sn, bq=bq, bb=bb, bk=bk,
                                    interpret=interpret)
        return out[:, :Nq, :R]
    raise ValueError(f"unknown slab-l2 impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def shared_slab_l2(
    queries: jnp.ndarray,          # (Q, m) one query batch shared by all slabs
    slabs: jnp.ndarray,            # (C, R, m)
    impl: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Distances from a shared query batch to every slab → (Q, C, R).

    The all-pairs form: with impl="pairwise" the slabs flatten into one
    (C·R, m) block and the ``l2_scan`` Pallas kernel runs over it directly —
    this is the engine's union-slab candidate pass and the build side's
    all-leaves sweep.
    """
    impl = impl or default_slab_impl()
    q = queries.astype(jnp.float32)
    s = slabs.astype(jnp.float32)
    C, R, m = s.shape
    if impl == "direct":
        diff = q[:, None, None, :] - s[None, :, :, :]
        return jnp.sqrt((diff * diff).sum(-1))
    if impl == "matmul":
        qn = (q * q).sum(-1)                             # (Q,)
        sn = (s * s).sum(-1)                             # (C, R)
        dot = jnp.einsum("qm,crm->qcr", q, s,
                         precision=_HIGHEST,
                         preferred_element_type=jnp.float32)
        return jnp.sqrt(jnp.maximum(
            qn[:, None, None] + sn[None, :, :] - 2.0 * dot, 0.0))
    if impl == "pairwise":
        flat = s.reshape(C * R, m)
        d = pairwise_l2(q, flat, interpret=interpret)
        return d.reshape(q.shape[0], C, R)
    raise ValueError(f"unknown slab-l2 impl {impl!r}")


def slab_masked_min(
    dists: jnp.ndarray,            # (F, Nq, R)
    valid: jnp.ndarray,            # (F, R) bool
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vmapped masked min over slab rows → (min (F, Nq), argmin (F, Nq)).

    The min-reduction half of the slab layer; its top-k sibling is
    :func:`leaf_topk`, which the engine's candidate passes call with
    broadcast row ids.
    """
    d = jnp.where(valid[:, None, :], dists, _INF)
    return d.min(axis=-1), d.argmin(axis=-1)


# the oracle, re-exported for benchmarks that compare both paths
reference = ref.pairwise_l2
