"""Data, query and arrival generators of the benchmark.

Copies of the program's generators (``repro.data.series``: RandWalk and the
Deep-like stand-in, the paper's §5.1 query protocol; ``repro.core.summaries
.znormalize``; ``repro.serving.batcher.poisson_trace``'s arrival arithmetic),
kept here so that no change to the program can change the data the yardstick
measures with.  Everything is drawn from numpy generators seeded by the
run's ``--seed``.
"""
from __future__ import annotations

import numpy as np


def znormalize(series: np.ndarray, eps: float = 1e-8) -> np.ndarray:
    """Per-series z-normalization."""
    series = np.asarray(series, np.float32)
    mu = series.mean(axis=-1, keepdims=True)
    sd = series.std(axis=-1, keepdims=True)
    return (series - mu) / (sd + eps)


def randwalk(n: int, m: int, seed: int) -> np.ndarray:
    """RandWalk: cumulative sums of N(0, 1) steps (Hydra's generator)."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, m), dtype=np.float32).cumsum(axis=1)


def _clustered_vectors(n: int, m: int, seed: int, n_clusters: int,
                       intrinsic_dim: int, noise: float) -> np.ndarray:
    """Near-manifold clustered vectors (image-descriptor-like)."""
    rng = np.random.default_rng(seed)
    out = np.empty((n, m), np.float32)
    sizes = rng.multinomial(n, np.ones(n_clusters) / n_clusters)
    row = 0
    for c in range(n_clusters):
        k = sizes[c]
        center = rng.standard_normal(m).astype(np.float32) * 2.0
        basis = rng.standard_normal((intrinsic_dim, m)).astype(np.float32)
        coef = rng.standard_normal((k, intrinsic_dim)).astype(np.float32)
        out[row:row + k] = center + coef @ basis / np.sqrt(intrinsic_dim) \
            + noise * rng.standard_normal((k, m)).astype(np.float32)
        row += k
    rng.shuffle(out, axis=0)
    return out


def deep_like(n: int, m: int, seed: int) -> np.ndarray:
    """Clustered stand-in with the shape of 96-d Deep1B descriptors."""
    return _clustered_vectors(n, m, seed, n_clusters=max(n // 2000, 8),
                              intrinsic_dim=16, noise=0.3)


GENERATORS = {"randwalk": randwalk, "deep_like": deep_like}


def make_collection(config: dict, seed: int) -> np.ndarray:
    """The configuration's collection, host float32 (n, m): the rows drawn
    from the configuration's ``data_seed``, in an order drawn from ``seed``.

    Every run indexes the same set of rows, so the index has the same
    leaves, and every program the same shapes, whatever the run's seed: a
    cell's programs are all in the compilation cache after its first run.
    The order changes every original row id the answers are mapped back to.
    """
    rows = GENERATORS[config["generator"]](config["n"], config["m"],
                                           config["data_seed"])
    return rows[np.random.default_rng(seed).permutation(len(rows))]


def make_queries(series: np.ndarray, n: int, noise: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Paper §5.1: uniformly drawn rows plus N(0, noise²) noise in
    z-normalized space, z-normalized again.  Each call draws fresh rows and
    fresh noise, so no two queries of a run are equal."""
    base = znormalize(series[rng.integers(0, len(series), n)])
    noisy = base + noise * rng.standard_normal(base.shape).astype(np.float32)
    return znormalize(noisy)


def poisson_arrivals(rate: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson stream at ``rate`` per second
    (exponential gaps, as ``poisson_trace`` draws them)."""
    n = int(rate * seconds * 1.5) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    while due[-1] < seconds:
        due = np.concatenate(
            [due, due[-1] + np.cumsum(rng.exponential(1.0 / rate, n))])
    return due[due < seconds]


def seeds(seed: int, n: int) -> list:
    """``n`` independent 31-bit seeds derived from the run's seed (the
    program's PRNG keys take 32-bit signed seeds; ``--seed`` may be larger)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]
