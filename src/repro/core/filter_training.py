"""Training-data generation (paper §4.3) and vmapped filter training.

Two-fold query generation:
* *global* queries — noisy uniform samples of the whole collection, searched
  against every leaf;
* *local*  queries — noisy samples of each selected leaf, searched only
  against their own leaf.

Both collection passes run on the engine's leaf-slab batch layer
(:mod:`repro.core.engine`): local queries are sampled by one vmapped RNG
sweep and both target passes are single jitted chunked sweeps over padded
(F, R, m) leaf slabs — no per-leaf Python iteration, no per-leaf retracing.
The seed's per-leaf forms are kept as ``_reference_*`` oracles; the parity
suite (tests/test_build_pipeline.py) pins the batched paths to them, and
``benchmarks/build_bench.py`` measures the gap.

Training runs every filter simultaneously: parameters are stacked on a
leading F axis and the SGD step is vmapped over it — the TPU-native
equivalent of the paper's 16 CUDA streams.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import engine, filters, summaries
from .flat_index import FlatIndex
from . import bounds as bounds_mod
from ..kernels.l2_scan import ops as l2_ops
from ..obs import span


# ---------------------------------------------------------------------------
# Query generation (paper §5.1 protocol: uniform samples + gaussian noise)
# ---------------------------------------------------------------------------


def make_noisy_queries(series: jax.Array, n_queries: int, key: jax.Array,
                       noise_low: float = 0.1, noise_high: float = 0.4
                       ) -> np.ndarray:
    """Sample series uniformly, add N(0, noise²) with noise ~ U[low, high]."""
    kidx, klvl, knoise = jax.random.split(key, 3)
    n = series.shape[0]
    idx = jax.random.randint(kidx, (n_queries,), 0, n)
    lvl = jax.random.uniform(klvl, (n_queries, 1), minval=noise_low,
                             maxval=noise_high)
    base = jnp.asarray(series)[idx]
    noisy = base + lvl * jax.random.normal(knoise, base.shape)
    return np.asarray(summaries.znormalize(np.asarray(noisy)))


@functools.partial(jax.jit, static_argnames=("n_per_leaf", "m"))
def _sample_local_rng(sizes, keys, n_per_leaf, m, noise_low, noise_high):
    """One vmapped sweep of the per-leaf RNG recipe → (rows, lvl, noise).

    Per filter: split its key exactly as the reference loop does, draw row
    indices within the leaf, one noise level per query, gaussian noise — the
    per-key PRNG streams are identical to the sequential version, so every
    draw matches it bitwise.
    """

    def one(key, size):
        kidx, knoise, klvl = jax.random.split(key, 3)
        rows = jax.random.randint(kidx, (n_per_leaf,), 0, size)
        lvl = jax.random.uniform(klvl, (n_per_leaf, 1), minval=noise_low,
                                 maxval=noise_high)
        noise = jax.random.normal(knoise, (n_per_leaf, m))
        return rows, lvl, noise

    return jax.vmap(one)(keys, sizes)


def make_local_queries(index: FlatIndex, leaf_ids: np.ndarray, n_per_leaf: int,
                       key: jax.Array, noise_low: float = 0.1,
                       noise_high: float = 0.4) -> np.ndarray:
    """(F, n_per_leaf, m) noisy samples drawn from each selected leaf.

    Batched: one jitted vmapped RNG sweep plus one vectorized gather/add
    replace the seed's per-leaf host loop (kept as
    :func:`_reference_local_queries`).  The RNG key schedule is unchanged
    and the noisy-sum stays in numpy (same elementwise rounding, no XLA FMA
    refusion), so the output is bitwise-identical to the reference.
    """
    leaf_ids = np.asarray(leaf_ids)
    keys = jax.random.split(key, len(leaf_ids))
    sizes = jnp.asarray(index.leaf_size)[leaf_ids]
    rows, lvl, noise = _sample_local_rng(
        sizes, keys, n_per_leaf, index.length,
        jnp.float32(noise_low), jnp.float32(noise_high))
    rows = np.asarray(rows) + np.asarray(index.leaf_start)[leaf_ids][:, None]
    # gather on the device, add on the host (the reference's rounding)
    noisy = np.asarray(index.series[jnp.asarray(rows)]) \
        + np.asarray(lvl) * np.asarray(noise)
    return summaries.znormalize(noisy)


# ---------------------------------------------------------------------------
# Target collection ("two-pass" search, array form)
# ---------------------------------------------------------------------------


def nodewise_nn_distances(index: FlatIndex, queries: jnp.ndarray,
                          dist_impl: Optional[str] = None) -> jnp.ndarray:
    """d_L for every (query, leaf): (Q, L).

    The paper's first collection pass — every leaf searched for every query
    — as one jitted sweep over the engine's leaf-slab layer: leaves stream
    through in cache-resident chunks, scored all-pairs (the ``l2_scan``
    Pallas kernel on TPU, its matmul decomposition elsewhere) and masked-min
    reduced per leaf.
    """
    queries = jnp.atleast_2d(jnp.asarray(queries))
    return engine.nn_distance_all_leaves(
        index.series, index.leaf_start, index.leaf_size, queries,
        max_leaf=index.max_leaf_size, dist_impl=dist_impl)


def local_nn_distances(index: FlatIndex, local_queries: np.ndarray,
                       leaf_ids: np.ndarray,
                       dist_impl: Optional[str] = None) -> np.ndarray:
    """d_L of each local query against its own leaf only: (F, n_loc).

    One jitted chunked sweep over the gathered (F, R, m) leaf slabs
    (:func:`engine.nn_distance_own_leaf`) instead of a per-leaf
    ``dynamic_slice`` loop.
    """
    return np.asarray(engine.nn_distance_own_leaf(
        index.series, index.leaf_start, index.leaf_size,
        jnp.asarray(local_queries),
        np.asarray(leaf_ids), max_leaf=index.max_leaf_size,
        dist_impl=dist_impl))


# ---------------------------------------------------------------------------
# Seed per-leaf reference paths — the oracles the batched collection is
# pinned against (tests/test_build_pipeline.py, benchmarks/build_bench.py).
# ---------------------------------------------------------------------------


def _reference_local_queries(index: FlatIndex, leaf_ids: np.ndarray,
                             n_per_leaf: int, key: jax.Array,
                             noise_low: float = 0.1,
                             noise_high: float = 0.4) -> np.ndarray:
    """Seed per-leaf loop for :func:`make_local_queries` (bitwise oracle)."""
    out = np.empty((len(leaf_ids), n_per_leaf, index.length), np.float32)
    keys = jax.random.split(key, len(leaf_ids))
    series = np.asarray(index.series)
    starts, sizes = np.asarray(index.leaf_start), np.asarray(index.leaf_size)
    for i, lf in enumerate(leaf_ids):
        kidx, knoise, klvl = jax.random.split(keys[i], 3)
        rows = np.asarray(
            jax.random.randint(kidx, (n_per_leaf,), 0, int(sizes[lf]))
        ) + int(starts[lf])
        lvl = np.asarray(jax.random.uniform(
            klvl, (n_per_leaf, 1), minval=noise_low, maxval=noise_high))
        noisy = series[rows] + lvl * np.asarray(
            jax.random.normal(knoise, (n_per_leaf, index.length)))
        out[i] = summaries.znormalize(noisy)
    return out


def _reference_nodewise_nn_distances(index: FlatIndex, queries: jnp.ndarray,
                                     block: int = 4096) -> jnp.ndarray:
    """Seed blocked pairwise pass + segment-min for nodewise targets."""
    queries = jnp.atleast_2d(jnp.asarray(queries))
    n, L = index.n_series, index.n_leaves
    series = jnp.asarray(index.series)[:n]
    sizes = np.asarray(index.leaf_size)
    leaf_of_row = jnp.asarray(np.repeat(np.arange(L), sizes), jnp.int32)

    mins = []
    for s in range(0, n, block):
        e = min(s + block, n)
        d = l2_ops.pairwise_l2(queries, series[s:e])          # (Q, b)
        mins.append(
            jax.ops.segment_min(d.T, leaf_of_row[s:e], num_segments=L)
        )                                                     # (L, Q)
    return jnp.stack(mins).min(axis=0).T                      # (Q, L)


def _reference_local_nn_distances(index: FlatIndex,
                                  local_queries: np.ndarray,
                                  leaf_ids: np.ndarray) -> np.ndarray:
    """Seed per-leaf ``dynamic_slice`` loop for the local targets."""
    series = jnp.asarray(index.series)
    starts = np.asarray(index.leaf_start)
    sizes = np.asarray(index.leaf_size)
    out = np.empty(local_queries.shape[:2], np.float32)
    for i, lf in enumerate(leaf_ids):
        s, z = int(starts[lf]), int(sizes[lf])
        slab = jax.lax.dynamic_slice_in_dim(series, s, index.max_leaf_size, 0)
        valid = jnp.arange(index.max_leaf_size) < z
        dmin, _ = l2_ops.masked_min_l2(jnp.asarray(local_queries[i]), slab, valid)
        out[i] = np.asarray(dmin)
    return out


@dataclasses.dataclass
class TrainingData:
    """Everything Alg. 1 collects before filter training."""
    global_queries: np.ndarray        # (n_g, m)
    global_d_L: np.ndarray            # (n_g, L)  node-wise NN distances
    global_d_lb: np.ndarray           # (n_g, L)  summarization lower bounds
    local_queries: np.ndarray         # (F, n_l, m)
    local_d_L: np.ndarray             # (F, n_l)
    leaf_ids: np.ndarray              # (F,) leaves with filters


def collect_training_data(index: FlatIndex, leaf_ids: np.ndarray,
                          n_global: int, n_local: int, key: jax.Array,
                          noise_low: float = 0.1, noise_high: float = 0.4,
                          dist_impl: Optional[str] = None) -> TrainingData:
    """Alg. 1 steps 2–3 on the engine's leaf-slab layer (batched passes)."""
    kg, kl = jax.random.split(key)
    with span("collect.global", cat="build", n_global=n_global):
        gq = make_noisy_queries(index.series[: index.n_series],
                                n_global, kg, noise_low, noise_high)
        d_L = np.asarray(nodewise_nn_distances(index, jnp.asarray(gq),
                                               dist_impl))
        d_lb = np.asarray(bounds_mod.lower_bounds(index, jnp.asarray(gq)))
    with span("collect.local", cat="build", n_local=n_local,
              n_filters=len(leaf_ids)):
        lq = make_local_queries(index, leaf_ids, n_local, kl,
                                noise_low, noise_high)
        ld = local_nn_distances(index, lq, leaf_ids, dist_impl)
    return TrainingData(gq, d_L, d_lb, lq, ld, np.asarray(leaf_ids))


def _reference_collect_training_data(index: FlatIndex, leaf_ids: np.ndarray,
                                     n_global: int, n_local: int,
                                     key: jax.Array,
                                     noise_low: float = 0.1,
                                     noise_high: float = 0.4) -> TrainingData:
    """Seed per-leaf collection, kept as the parity/benchmark baseline."""
    kg, kl = jax.random.split(key)
    gq = make_noisy_queries(np.asarray(index.series[: index.n_series]),
                            n_global, kg, noise_low, noise_high)
    d_L = np.asarray(_reference_nodewise_nn_distances(index, jnp.asarray(gq)))
    d_lb = np.asarray(bounds_mod.lower_bounds(index, jnp.asarray(gq)))
    lq = _reference_local_queries(index, leaf_ids, n_local, kl,
                                  noise_low, noise_high)
    ld = _reference_local_nn_distances(index, lq, leaf_ids)
    return TrainingData(gq, d_L, d_lb, lq, ld, np.asarray(leaf_ids))


# ---------------------------------------------------------------------------
# vmapped SGD training
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    epochs: int = 300
    batch: int = 128
    lr: float = 1e-2
    momentum: float = 0.9
    val_fraction: float = 0.2          # paper: train/val split 4:1
    hidden: int | None = None
    seed: int = 0


def _sgd_step(params, grads, vel, lr, momentum):
    new_vel = jax.tree.map(lambda v, g: momentum * v + g, vel, grads)
    new_params = jax.tree.map(lambda p, v: p - lr * v, params, new_vel)
    return new_params, new_vel


@functools.partial(jax.jit, static_argnames=("cfg",))
def _train_filters_jit(params, xg, yg, xl, yl, val_mask_g, val_mask_l, cfg):
    """All-filters SGD.  Shapes:
    xg (n_g, m) shared; yg (F, n_g); xl (F, n_l, m); yl (F, n_l).
    Targets are standardized per filter before entry.
    Carries best-validation parameters (the paper's plateau/early-stop
    criterion, expressed scan-compatibly).
    """
    F, n_g = yg.shape
    n_l = yl.shape[1]
    n_steps = cfg.epochs * max((n_g + n_l) // cfg.batch, 1)
    w_g = n_g / (n_g + n_l)

    trainable = ("w1", "b1", "w2", "b2")

    def loss_fn(tp, key):
        kg, kl = jax.random.split(key)
        ig = jax.random.randint(kg, (cfg.batch,), 0, n_g)
        il = jax.random.randint(kl, (max(cfg.batch // 4, 1),), 0, n_l)
        pred_g = filters.apply_mlp_raw(tp, xg[ig])             # (F, bg)
        err_g = (pred_g - yg[:, ig]) ** 2 * (1 - val_mask_g[None, ig])

        def local_pred(tp_f, x_f):
            h = jax.nn.relu(x_f @ tp_f["w1"] + tp_f["b1"])
            return h @ tp_f["w2"] + tp_f["b2"]

        pred_l = jax.vmap(local_pred)(tp, xl[:, il])           # (F, bl)
        err_l = (pred_l - yl[:, il]) ** 2 * (1 - val_mask_l[None, il])
        return w_g * err_g.mean() + (1 - w_g) * err_l.mean()

    def val_loss(tp):
        pred_g = filters.apply_mlp_raw(tp, xg)
        err = ((pred_g - yg) ** 2 * val_mask_g[None, :]).sum(1)
        return err / jnp.maximum(val_mask_g.sum(), 1)          # (F,)

    tparams = {k: params[k] for k in trainable}
    vel = jax.tree.map(jnp.zeros_like, tparams)
    best = tparams
    best_val = jnp.full((F,), jnp.inf)

    eval_every = max(n_steps // 20, 1)

    def step(carry, step_key):
        tp, vel, best, best_val, i = carry
        # step-decayed lr: /10 at 60% and 85% of the budget (paper: divide
        # lr by 10 when validation plateaus; schedule form is deterministic)
        lr = cfg.lr * jnp.where(i < 0.6 * n_steps, 1.0,
                                jnp.where(i < 0.85 * n_steps, 0.1, 0.01))
        grads = jax.grad(loss_fn)(tp, step_key)
        tp, vel = _sgd_step(tp, grads, vel, lr, cfg.momentum)

        def do_eval(args):
            tp, best, best_val = args
            vl = val_loss(tp)                                  # (F,)
            improved = vl < best_val
            new_best = jax.tree.map(
                lambda b, c: jnp.where(
                    improved.reshape((F,) + (1,) * (c.ndim - 1)), c, b),
                best, tp)
            return new_best, jnp.minimum(vl, best_val)

        best, best_val = jax.lax.cond(
            i % eval_every == 0, do_eval, lambda a: (a[1], a[2]),
            (tp, best, best_val))
        return (tp, vel, best, best_val, i + 1), None

    keys = jax.random.split(jax.random.PRNGKey(cfg.seed), n_steps)
    (tp, _, best, best_val, _), _ = jax.lax.scan(
        step, (tparams, vel, best, best_val, 0), keys)
    return best, best_val


def train_filters(index: FlatIndex, data: TrainingData,
                  cfg: TrainConfig = TrainConfig(),
                  key: jax.Array | None = None
                  ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, np.ndarray]]:
    """Train one MLP filter per selected leaf; returns (params, report)."""
    key = key if key is not None else jax.random.PRNGKey(cfg.seed)
    F = len(data.leaf_ids)
    m = index.length
    params = filters.init_mlp(key, F, m, cfg.hidden)

    yg = jnp.asarray(data.global_d_L[:, data.leaf_ids].T)      # (F, n_g)
    yl = jnp.asarray(data.local_d_L)                           # (F, n_l)
    # per-filter target standardization over the filter's own target mix
    y_all = jnp.concatenate([yg, yl], axis=1)
    y_mean = y_all.mean(axis=1)
    y_std = y_all.std(axis=1) + 1e-6
    params["y_mean"], params["y_std"] = y_mean, y_std
    ygz = (yg - y_mean[:, None]) / y_std[:, None]
    ylz = (yl - y_mean[:, None]) / y_std[:, None]

    n_g, n_l = yg.shape[1], yl.shape[1]
    rng = np.random.default_rng(cfg.seed)
    vg = np.zeros(n_g, np.float32)
    vg[rng.choice(n_g, int(n_g * cfg.val_fraction), replace=False)] = 1
    vl = np.zeros(n_l, np.float32)
    vl[rng.choice(n_l, max(int(n_l * cfg.val_fraction), 1), replace=False)] = 1

    with span("train.sgd", cat="build", n_filters=F, epochs=cfg.epochs):
        best, best_val = _train_filters_jit(
            params, jnp.asarray(data.global_queries), ygz,
            jnp.asarray(data.local_queries), ylz,
            jnp.asarray(vg), jnp.asarray(vl), cfg)
    params.update(best)
    report = {"val_rmse_z": np.asarray(jnp.sqrt(best_val))}
    return params, report
