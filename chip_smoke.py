"""Bring-up smoke of the LeaFi build-and-serve path on a TPU.

    python chip_smoke.py                  # one chip, n = 1,048,576 x 256
    python chip_smoke.py --n 65536        # a smaller collection
    python chip_smoke.py --chips 4        # leaf-sharded search, 1x4 mesh

One chip: a seeded random-walk collection is built into a LeaFi index
(``build.build_leafi`` with ``launch/serve.py``'s config), served through a
``ServingSession`` (``warmup``, exact ``search`` micro-batches, and an
open-loop trace of mixed quality targets and k through ``serve``), and every
answer is checked against a brute-force float32 k-NN computed on the chip:
exact-mode ids must match up to distance ties within ``DIST_TOL``, and the
recall achieved per quality target may not fall more than ``RECALL_MARGIN``
below the target.  ``--chips 4`` builds the same collection on one device,
shards it over a 1x4 mesh (``DistributedExecutor``) and checks the
distributed answers against the single-device search and the reference.

Earlier lines print plain ``name: value`` measurements; the last line is
one JSON object naming the device.  Without a TPU the script exits non-zero
and prints no result; it has no CPU fallback.  All work is one process.
The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import build, distributed  # noqa: E402
from repro.core.summaries import znormalize  # noqa: E402
from repro.data.series import make_query_set, randwalk  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import leafi_config  # noqa: E402
from repro.serving import (DistributedExecutor, MicroBatcher,  # noqa: E402
                           ServingSession, poisson_trace)

TARGETS = (0.9, 0.99)          # approximate quality targets served
KS = (1, 10)                   # neighbours per request
DIST_TOL = 1e-3                # exact mode: |d_engine − d_reference| bound
RECALL_MARGIN = 0.1            # fail when recall < target − margin
REF_CHUNK = 2048               # reference rows per step
# the rest of the run's shape: series length, query pool, served requests,
# micro-batch cap (largest bucket), query noise (paper §5.1), seed
DEFAULTS = dict(m=256, queries=256, requests=512, batch=64, noise=0.1,
                seed=0)


class SmokeError(AssertionError):
    """A phase produced a wrong or empty answer."""


def log(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


# ---------------------------------------------------------------------------
# phases (also driven at tiny n by tests/test_chip_smoke.py)
# ---------------------------------------------------------------------------


def make_collection(n: int, m: int, seed: int) -> np.ndarray:
    """Seeded random walks (the paper's RandWalk protocol), host float32."""
    return randwalk(n, m, seed)


def build_index(collection: np.ndarray, seed: int) -> build.LeaFiIndex:
    lfi = build.build_leafi(collection, leafi_config(seed))
    if len(lfi.leaf_ids) == 0:
        raise SmokeError("the build selected 0 filters")
    return lfi


def reference_data(collection: np.ndarray) -> jax.Array:
    """The z-normalized collection in original order, padded to whole
    reference chunks with rows far from any query, on the device."""
    z = znormalize(collection)
    pad = (-z.shape[0]) % REF_CHUNK
    z = np.concatenate([z, np.full((pad, z.shape[1]), 1e6, np.float32)])
    return jax.device_put(z)


def reference_knn(data: jax.Array, queries: np.ndarray, k: int,
                  batch: int = 64):
    """Brute-force k-NN of ``queries`` in batches → host (dists, ids)."""
    out = [_reference_knn(data, jnp.asarray(queries[b:b + batch]), k)
           for b in range(0, len(queries), batch)]
    return (np.concatenate([np.asarray(d) for d, _ in out]),
            np.concatenate([np.asarray(i) for _, i in out]))


@functools.partial(jax.jit, static_argnames=("k",))
def _reference_knn(data: jax.Array, queries: jax.Array, k: int):
    """Brute-force float32 k-NN: direct (q − x)² sums, chunk by chunk.

    Independent of the engine's distance algebra and of the index layout;
    ids are original collection rows.  Returns (dists (Q, k), ids (Q, k)).
    """
    Q = queries.shape[0]

    def step(i, carry):
        best_d, best_i = carry
        rows = jax.lax.dynamic_slice_in_dim(data, i * REF_CHUNK, REF_CHUNK)
        diff = queries[:, None, :] - rows[None, :, :]
        d = jnp.sqrt((diff * diff).sum(-1))                  # (Q, chunk)
        ids = i * REF_CHUNK + jnp.arange(REF_CHUNK, dtype=jnp.int32)
        alld = jnp.concatenate([best_d, d], axis=1)
        alli = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (Q, REF_CHUNK))], axis=1)
        neg, arg = jax.lax.top_k(-alld, k)
        return -neg, jnp.take_along_axis(alli, arg, axis=1)

    init = (jnp.full((Q, k), jnp.inf), jnp.full((Q, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, data.shape[0] // REF_CHUNK, step, init)


def check_exact(ids: np.ndarray, dists: np.ndarray, ref_ids: np.ndarray,
                ref_dists: np.ndarray, data: jax.Array,
                queries: np.ndarray) -> dict:
    """Exact-mode answers against the reference, up to distance ties.

    A rank may hold a different id than the reference only when that id's
    reference-computed distance equals the reference's at that rank within
    ``DIST_TOL`` (a tie); the returned distances must agree within it too.
    """
    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    rows = jnp.asarray(np.clip(ids, 0, None))
    diff = data[rows] - jnp.asarray(queries)[:, None]
    own = np.asarray(jnp.sqrt((diff * diff).sum(-1)))
    same = ids == ref_ids
    tie = ~same & (ids >= 0) & (np.abs(own - ref_dists) <= DIST_TOL)
    d_err = float(np.abs(np.asarray(dists) - ref_dists).max())
    bad = int((~same & ~tie).sum())
    if bad or not d_err <= DIST_TOL:
        raise SmokeError(f"exact mode: {bad} ids differ beyond ties, max "
                         f"distance error {d_err} (tolerance {DIST_TOL})")
    return {"ids_equal": int(same.sum()), "ties": int(tie.sum()),
            "max_dist_err": d_err}


def check_recall(report: dict) -> dict:
    """Recall@1 achieved per quality target (the serve report's rule)."""
    got = {float(t): rec["recall"]
           for t, rec in report["recall_by_target"].items()}
    for t in TARGETS:
        if t not in got:
            raise SmokeError(f"no request was served at target {t}")
        if not got[t] >= t - RECALL_MARGIN:
            raise SmokeError(f"recall {got[t]} at target {t} is more than "
                             f"{RECALL_MARGIN} below it")
    return got


def warm_latency(fn, repeats: int = 3) -> float:
    """Median wall-clock of ``fn`` (which blocks on its result), warm."""
    fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------


def _setup(args, platform: str):
    t0 = time.perf_counter()
    collection = make_collection(args.n, args.m, args.seed)
    log("n", args.n)
    log("m", args.m)
    log("data_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    lfi = build_index(collection, args.seed)
    log("build_s", time.perf_counter() - t0)
    rep = lfi.build_report
    log("leaves", lfi.index.n_leaves)
    log("filters", len(lfi.leaf_ids))
    for phase in ("t_index_build", "t_collect", "t_train", "t_calibrate"):
        log(f"build_{phase[2:]}_s", rep[phase])
    series_dev = {str(d) for d in lfi.index.series.devices()}
    log("series_device", ",".join(sorted(series_dev)))
    if not all(d.platform == platform for d in lfi.index.series.devices()):
        raise SmokeError(f"the collection is not on the {platform} device")
    data = reference_data(collection)
    queries = make_query_set(collection, args.queries, args.noise,
                             args.seed + 1)
    return collection, lfi, data, queries


def run_one_chip(args, platform: str = "tpu") -> dict:
    """Build, warm, search exactly, serve a mixed trace; returns the
    serve report.  Raises :class:`SmokeError` on any wrong answer."""
    _, lfi, data, queries = _setup(args, platform)
    session = ServingSession(lfi)
    B = args.batch

    # compile time: the warm-up compiles every (bucket, k) program and runs
    # each once
    t0 = time.perf_counter()
    n_warm = session.warmup(max_batch=B, ks=KS, queries=queries,
                            targets=TARGETS)
    log("warmup_programs", n_warm)
    log("compile_s", time.perf_counter() - t0)

    # exact micro-batches through the session, against the reference
    for k in KS:
        ref_d, ref_i = reference_knn(data, queries, k, B)
        for b in range(0, len(queries), B):
            q = queries[b:b + B]
            res = session.search(q, None, k=k)
            stats = check_exact(res.ids, res.dists, ref_i[b:b + B],
                                ref_d[b:b + B], data, q)
            log(f"exact_k{k}_batch{b // B}",
                f"ids_equal={stats['ids_equal']} ties={stats['ties']} "
                f"max_dist_err={stats['max_dist_err']:.3g} "
                f"pruning={float(res.pruning_ratio.mean()):.4f}")

    # warm per-batch latency: a full bucket, all its programs compiled
    q = queries[:B]
    t = np.resize(np.asarray(TARGETS), len(q))
    for k in KS:
        log(f"warm_batch_exact_k{k}_s",
            warm_latency(lambda: session.search(q, None, k=k, record=False)))
        log(f"warm_batch_mixed_k{k}_s",
            warm_latency(lambda: session.search(q, t, k=k, record=False)))

    # open-loop trace: mixed targets and k through the micro-batcher
    trace = poisson_trace(queries, rate=1e4, n_requests=args.requests,
                          targets=TARGETS, ks=KS, seed=args.seed + 2)
    ref_d, _ = reference_knn(data, queries, 1, B)
    oracle = {r.rid: float(ref_d[r.pool_row, 0]) for r in trace}
    report = session.serve(trace, recall_oracle=oracle,
                           batcher=MicroBatcher(max_batch=B, max_wait=2e-3))
    log("served_requests", report["n_requests"])
    log("served_batches", report["n_batches"])
    log("serve_p50_s", report["p50"])
    log("serve_p99_s", report["p99"])
    log("serve_pruning", report["pruning_ratio"])
    for t, rec in sorted(check_recall(report).items()):
        log(f"recall_at_target_{t}", rec)
    return report


def run_four_chips(args, platform: str = "tpu") -> None:
    """Shard the one-device index over every visible device (1×D mesh) and
    check the distributed answers against the single-device search and the
    reference.  Raises :class:`SmokeError` on any wrong answer."""
    _, lfi, data, queries = _setup(args, platform)
    B = args.batch
    n_dev = len(jax.devices())
    mesh = distributed.make_search_mesh(1, n_dev)
    executor = DistributedExecutor(lfi, mesh)
    per_dev: dict = {}
    for v in vars(executor.sharded).values():
        for sh in getattr(v, "addressable_shards", ()):
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    for dev in jax.devices():
        log(f"shard_bytes_device{dev.id}", per_dev.get(dev.id, 0))
        log(f"bytes_in_use_device{dev.id}",
            (dev.memory_stats() or {}).get("bytes_in_use"))
    if len(per_dev) != n_dev:
        raise SmokeError(f"shards landed on {len(per_dev)} of {n_dev} "
                         "devices")

    session = ServingSession(lfi)
    ref_all, ref_ids = reference_knn(data, queries, 1, B)
    for b in range(0, len(queries), B):
        q, ref_d = queries[b:b + B], ref_all[b:b + B]
        dist = executor.dispatch(q, None, 1).result()
        single = session.search(q, None, k=1, record=False)
        check_exact(single.ids, single.dists, ref_ids[b:b + B], ref_d,
                    data, q)
        err = float(np.abs(dist.dists - ref_d).max())
        if not err <= DIST_TOL:
            raise SmokeError(f"distributed exact 1-NN off the reference "
                             f"by {err}")
        log(f"dist_exact_batch{b // B}",
            f"max_dist_err={err:.3g} searched="
            f"{float(np.mean(dist.searched)):.1f}/{lfi.index.n_leaves}")
    t = np.resize(np.asarray(TARGETS), B)
    q, ref_d = queries[:B], ref_all[:B, 0]
    log("dist_warm_batch_exact_s", warm_latency(
        lambda: executor.dispatch(q, None, 1).result()))
    log("dist_warm_batch_mixed_s", warm_latency(
        lambda: executor.dispatch(q, t, 1).result()))
    log("single_warm_batch_exact_s", warm_latency(
        lambda: session.search(q, None, k=1, record=False)))
    got = executor.dispatch(q, t, 1).result().dists[:, 0]
    for tv in TARGETS:
        sel = t == tv
        rec = float((got[sel] <= ref_d[sel] * (1 + 1e-5) + 1e-6).mean())
        log(f"dist_recall_at_target_{tv}", rec)
        if not rec >= tv - RECALL_MARGIN:
            raise SmokeError(f"distributed recall {rec} at target {tv}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="collection size (series of length 256)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the leaf-sharded search over a 1x4 mesh only")
    args = ap.parse_args(argv)
    args = argparse.Namespace(**DEFAULTS, **vars(args))

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) visible", file=sys.stderr)
        return 2
    log("cache_dir", enable_compile_cache())
    log("device_kind", devices[0].device_kind)
    (run_four_chips if args.chips == 4 else run_one_chip)(args)
    for dev in devices[:args.chips]:
        log(f"peak_bytes_device{dev.id}",
            (dev.memory_stats() or {}).get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
