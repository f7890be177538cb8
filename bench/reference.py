"""The plain reference of the benchmark and the comparison that decides
``correct``.

The reference is brute-force k-NN in float32 by direct ``(q - x)²`` sums
over the z-normalized collection in its original row order (copied from
``chip_smoke._reference_knn``): independent of the engine's distance
algebra, of the index layout and of everything the build made.  It runs on
the device in blocks of ``CHUNK`` rows, after the program's state is freed.

``control_knn`` is the same search computed one precision lower (inputs
rounded to bfloat16, products accumulated in float32: what a default-
precision TPU contraction does).  Put in the program's place it has to come
out as not correct; ``bench/control.py`` runs it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 2048                   # reference rows per step
BATCH = 128                    # reference queries per call
RECALL_RTOL = 1e-5             # recall@1 hit: d(served id) <= d_nn·(1+rtol)


def place(collection_z: np.ndarray) -> jax.Array:
    """The z-normalized collection, padded to whole chunks with rows far
    from any query, on the device."""
    pad = (-collection_z.shape[0]) % CHUNK
    z = np.concatenate([collection_z,
                        np.full((pad, collection_z.shape[1]), 1e6,
                                np.float32)])
    return jax.device_put(z)


@functools.partial(jax.jit, static_argnames=("k", "low"))
def _knn(data: jax.Array, queries: jax.Array, k: int, low: bool):
    Q = queries.shape[0]

    def step(i, carry):
        best_d, best_i = carry
        rows = jax.lax.dynamic_slice_in_dim(data, i * CHUNK, CHUNK)
        if low:
            dot = jnp.einsum("qm,rm->qr", queries.astype(jnp.bfloat16),
                             rows.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
            sq = ((queries * queries).sum(-1)[:, None]
                  + (rows * rows).sum(-1)[None, :] - 2.0 * dot)
            d = jnp.sqrt(jnp.maximum(sq, 0.0))
        else:
            diff = queries[:, None, :] - rows[None, :, :]
            d = jnp.sqrt((diff * diff).sum(-1))               # (Q, CHUNK)
        ids = i * CHUNK + jnp.arange(CHUNK, dtype=jnp.int32)
        alld = jnp.concatenate([best_d, d], axis=1)
        alli = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (Q, CHUNK))], axis=1)
        neg, arg = jax.lax.top_k(-alld, k)
        return -neg, jnp.take_along_axis(alli, arg, axis=1)

    init = (jnp.full((Q, k), jnp.inf), jnp.full((Q, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, data.shape[0] // CHUNK, step, init)


def _batched(fn, queries: np.ndarray):
    outs = []
    for b in range(0, len(queries), BATCH):
        q = queries[b:b + BATCH]
        pad = BATCH - len(q)
        if pad:                        # one program shape for every block
            q = np.concatenate([q, np.repeat(q[:1], pad, axis=0)])
        d, i = fn(jnp.asarray(q))
        outs.append((np.asarray(d)[:BATCH - pad], np.asarray(i)[:BATCH - pad]))
    return (np.concatenate([d for d, _ in outs]),
            np.concatenate([i for _, i in outs]))


def knn(data: jax.Array, queries: np.ndarray, k: int):
    """Float32 brute-force k-NN → host (dists (Q, k), ids (Q, k))."""
    return _batched(lambda q: _knn(data, q, k=k, low=False), queries)


def control_knn(data: jax.Array, queries: np.ndarray, k: int):
    """The same k-NN one precision lower: bfloat16 products."""
    return _batched(lambda q: _knn(data, q, k=k, low=True), queries)


@jax.jit
def _dist_of(data: jax.Array, queries: jax.Array, ids: jax.Array):
    rows = data[jnp.clip(ids, 0, data.shape[0] - 1)]            # (Q, k, m)
    diff = rows - queries[:, None, :]
    return jnp.sqrt((diff * diff).sum(-1))


def dist_of(data: jax.Array, queries: np.ndarray, ids: np.ndarray,
            n: int) -> np.ndarray:
    """Float32 distance from each query to each of its given original row
    ids (+inf where an id is not a row of the collection)."""
    out = []
    for b in range(0, len(queries), BATCH):
        q, i = queries[b:b + BATCH], ids[b:b + BATCH]
        pad = BATCH - len(q)
        if pad:
            q = np.concatenate([q, np.repeat(q[:1], pad, axis=0)])
            i = np.concatenate([i, np.repeat(i[:1], pad, axis=0)])
        d = _dist_of(data, jnp.asarray(q), jnp.asarray(i, jnp.int32))
        out.append(np.asarray(d)[:BATCH - pad])
    d = np.concatenate(out)
    return np.where((ids >= 0) & (ids < n), d, np.inf)


def compare(served_ids: np.ndarray, served_d: np.ndarray,
            ref_ids: np.ndarray, ref_d: np.ndarray, own_d: np.ndarray,
            *, tie_tol: float, exact: bool,
            targets: np.ndarray | None = None) -> dict:
    """The numbers compared for one run's answers.

    ``own_d`` is the reference distance of each served id.  Returns
    ``dist_err`` (largest gap between a served distance and the reference
    distance of the served id), and for exact answers ``id_mismatch`` (ranks
    whose id differs from the reference's beyond a distance tie within
    ``tie_tol``), for approximate ones the recall@1 reached per target.
    """
    out = {"dist_err": float(np.max(np.abs(served_d - own_d)))
           if served_d.size else 0.0}
    if exact:
        same = served_ids == ref_ids
        tie = ~same & np.isfinite(own_d) & (np.abs(own_d - ref_d) <= tie_tol)
        out["id_mismatch"] = int((~same & ~tie).sum())
    else:
        hit = own_d[:, 0] <= ref_d[:, 0] * (1 + RECALL_RTOL)
        for t in np.unique(targets):
            sel = targets == t
            out[f"recall@{float(t):g}"] = float(hit[sel].mean())
    return out
