"""Distributed scan-vs-compact shard-body wall-clock across pruning ratios.

The distributed engine's claim mirrors the single-device one: the per-shard
compute term should scale with (1 − pruning ratio) instead of staying
O(local leaves).  This benchmark pins that on a 1×N host-device mesh: one
leaf-sharded index, one query batch, and a sweep of filter aggressiveness
levels; at each level both shard strategies — ``"scan"`` (masked bsf scan
over every local leaf) and ``"compact"`` (fixed-width survivor compaction,
``engine.compact_bsf_cascade``) — answer the same two-phase exchange, and we
record wall-clock, the psum'd searched-leaf total, and their bitwise parity.

Pruning is controlled synthetically (as in ``engine_bench``): filter slots
are zeroed so the stacked-MLP prediction collapses to its bias, and the bias
of every leaf outside the globally best ``keep`` fraction (ranked by mean
box lower bound over the query batch) is set huge — those leaves
filter-prune at any finite bsf.  The compact strategy's static survivor
capacity is sized per level from the kept-per-shard maximum, the same
statistic a deployment would tune it from.

The sweep runs in a subprocess so the forced host-device count never leaks
into (or collides with) the parent's already-initialized jax runtime — the
same isolation trick tests/test_distributed.py uses.  The child is pinned to
``JAX_PLATFORMS=cpu`` (its N devices are forced host devices), so it can
never contend with the parent for an accelerator, and its payload is
labelled ``"platform": "cpu"``: these are CPU timings, not chip numbers.

    PYTHONPATH=src python -m benchmarks.dist_bench \
        --out experiments/dist_bench.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Tuple

KEEP_FRACTIONS = (1.0, 0.5, 0.25, 0.1, 0.05, 0.02)


def _child(args) -> Dict:
    """The measured sweep; runs with the forced host-device count active."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build, distributed, tree
    from repro.data.series import make_query_set

    D = args.devices
    rng = np.random.default_rng(1)
    S = rng.standard_normal((args.n, args.m), dtype=np.float32).cumsum(axis=1)
    index = tree.build_dstree(S, leaf_capacity=args.leaf_capacity)
    lfi = build.LeaFiIndex(index, None, np.empty(0, np.int64), None,
                           build.LeaFiConfig(), {})
    sharded = distributed.shard_leafi(lfi, n_shards=D)
    # shrink the (all-zero) filter slots to a realistic small hidden dim so
    # the stacked-MLP prediction einsum doesn't dominate both strategies
    h = 8
    sharded.w1 = sharded.w1[..., :h]
    sharded.b1 = sharded.b1[..., :h]
    sharded.w2 = sharded.w2[..., :h]

    queries = jnp.asarray(make_query_set(S, args.queries, noise=0.3, seed=7))
    qc = np.asarray(sharded.query_coords(queries))

    # mean box lower bound per (shard, leaf) over the batch — the global
    # promise ranking the keep levels cut on (padding leaves rank last)
    lo, hi = np.asarray(sharded.lb_lo), np.asarray(sharded.lb_hi)
    sizes = np.asarray(sharded.leaf_size)
    d = np.maximum(np.maximum(lo[:, None] - qc[None, :, None],
                              qc[None, :, None] - hi[:, None]), 0.0)
    d = np.where(np.isfinite(d), d, 0.0)
    score = np.sqrt((d * d).sum(-1)).mean(axis=1)        # (S, P)
    valid = sizes > 0
    score = np.where(valid, score, np.inf)
    L_valid = int(valid.sum())
    order = np.argsort(score, axis=None)                 # global flat ranking

    from repro.core.engine import _next_pow2

    mesh = distributed.make_search_mesh(1, D)
    levels = []
    for frac in KEEP_FRACTIONS:
        r = max(int(round(frac * L_valid)), 1)
        keep = np.zeros(score.shape, bool)
        keep.flat[order[:r]] = True
        # pruned leaves: an active zero-filter whose bias (= its prediction)
        # exceeds any finite bsf → filter-pruned in phase 2
        prune = valid & ~keep
        lvl = dataclasses.replace(
            sharded,
            has_filter=jnp.asarray(prune),
            b2=jnp.asarray(np.where(prune, np.float32(1e30), 0.0)))
        # per-query survivors never exceed the kept-per-shard maximum, so
        # this capacity provably avoids the overflow fallback
        cap = _next_pow2(max(int(keep.sum(axis=1).max()), 1))

        rec = {"level": f"keep{r}", "keep_frac": frac, "kept": r,
               "max_survivors": cap}
        outs = {}
        for strategy in ("scan", "compact"):
            # dist_impl="direct" keeps the candidate pass on the scan's
            # distance algebra (it is also the off-TPU default)
            run, *_ = distributed.make_distributed_search(
                mesh, lvl, strategy=strategy, max_survivors=cap,
                dist_impl="direct")
            with mesh:
                nn, tot = run(queries)                   # warmup / compile
                jax.block_until_ready(nn)
                t0 = time.perf_counter()
                for _ in range(args.repeat):
                    nn, tot = run(queries)
                jax.block_until_ready(nn)
                dt = (time.perf_counter() - t0) / args.repeat
            outs[strategy] = (np.asarray(nn), np.asarray(tot))
            rec[f"{strategy}_ms"] = dt * 1e3
            rec[f"{strategy}_searched"] = float(np.asarray(tot).mean())
        # the shard strategies must agree: float tolerance on nn, a small
        # slack on counts (ulp-tied prune decisions can flip between two
        # separately compiled programs — see tests/test_distributed.py)
        np.testing.assert_allclose(outs["compact"][0], outs["scan"][0],
                                   rtol=2e-6, err_msg=str(rec))
        assert np.abs(outs["compact"][1].astype(np.int64)
                      - outs["scan"][1].astype(np.int64)).max() <= 8, rec
        rec["pruning_ratio"] = 1.0 - rec["compact_searched"] / L_valid
        rec["speedup"] = rec["scan_ms"] / max(rec["compact_ms"], 1e-12)
        levels.append(rec)
        print(f"# {rec['level']}: prune={rec['pruning_ratio']:.3f} "
              f"scan={rec['scan_ms']:.1f}ms compact={rec['compact_ms']:.1f}ms "
              f"({rec['speedup']:.2f}x)", file=sys.stderr)

    return {"platform": jax.devices()[0].platform,
            "n": args.n, "m": args.m, "L": L_valid, "n_shards": D,
            "leaf_capacity": args.leaf_capacity, "n_queries": args.queries,
            "levels": levels}


def bench_dist(n: int = 48_000, m: int = 128, leaf_capacity: int = 128,
               n_queries: int = 64, devices: int = 4,
               repeat: int = 3) -> Tuple[List[str], Dict]:
    """Run the sweep in a fresh subprocess with D forced host devices."""
    from . import common

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # forced host devices, never the chip
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    cmd = [sys.executable, "-m", "benchmarks.dist_bench", "--run-child",
           "--n", str(n), "--m", str(m),
           "--leaf-capacity", str(leaf_capacity),
           "--queries", str(n_queries), "--devices", str(devices),
           "--repeat", str(repeat)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=3600)
    if r.returncode != 0:
        raise RuntimeError(
            f"dist_bench child failed:\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}")
    payload = json.loads(r.stdout)
    rows = [common.csv_line(
        f"dist/{payload['platform']}/{rec['level']}", rec["compact_ms"] * 1e3,
        f"prune={rec['pruning_ratio']:.3f};scan={rec['scan_ms']:.1f}ms;"
        f"compact={rec['compact_ms']:.1f}ms;cap={rec['max_survivors']};"
        f"speedup={rec['speedup']:.2f}x")
        for rec in payload["levels"]]
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/dist_bench.json")
    ap.add_argument("--run-child", action="store_true",
                    help="internal: run the measured sweep in-process "
                         "(expects XLA_FLAGS already set)")
    ap.add_argument("--n", type=int, default=48_000)
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--leaf-capacity", type=int, default=128)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--repeat", type=int, default=3)
    args = ap.parse_args()

    if args.run_child:
        json.dump(_child(args), sys.stdout, default=float)
        return

    from . import common
    rows, payload = bench_dist(
        n=args.n, m=args.m, leaf_capacity=args.leaf_capacity,
        n_queries=args.queries, devices=args.devices, repeat=args.repeat)
    common.write_suite_payload(rows, payload, args.out)


if __name__ == "__main__":
    main()
