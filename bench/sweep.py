"""Find an open-loop cell's knee: the highest offered rate at which the
backlog does not grow across the window.

    python bench/sweep.py --workload rw256-approx-open --seed 1 \
        --seconds 10 --rates 60,90,120,150,180

Builds and warms the cell once, then serves the cell's traffic at each
rate in turn, on the wall clock, and prints one JSON line per rate: the
answered count, p50/p99 latency, and how the backlog moved — the mean
latency of the last quarter of the requests over that of the first
quarter, and how long the queue took to drain after the last arrival.  A
rate is sustained when every request is answered, the ratio stays under
``GROWTH`` and the queue drains within ``DRAIN_S``; the last line names the
knee, the highest rate below the first one that is not sustained (a faster
rate that recovers above it does not count), and 0.8 × it, the rate the
cell's traffic file fixes.  Needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")


GROWTH = 1.5          # last-quarter over first-quarter mean latency
DRAIN_S = 1.0         # seconds to empty the queue after the last arrival


def sustained(row: dict) -> bool:
    return (row["answered"] == row["offered"] and row["growth"] < GROWTH
            and row["drain_s"] < DRAIN_S)


def knee(rows) -> Optional[float]:
    """The highest rate below the first rate that is not sustained."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate_qps"]):
        if not sustained(r):
            break
        best = r["rate_qps"]
    return best


def backlog(served) -> dict:
    """Latency trend of one window: first and last quarter by due time."""
    import numpy as np
    lat = served.latency_s()
    q = max(len(lat) // 4, 1)
    return {"answered": int(np.isfinite(lat).sum()), "offered": len(lat),
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "first_quarter_ms": float(np.mean(lat[:q])) * 1e3,
            "last_quarter_ms": float(np.mean(lat[-q:])) * 1e3,
            "growth": float(np.mean(lat[-q:]) / np.mean(lat[:q])),
            "drain_s": float(served.end - served.seconds),
            "batches": len(served.batches),
            "mean_fill": float(np.mean([b["n_valid"] for b in
                                        served.batches]))}


def sweep(root: str, workload: str, seed: int, seconds: float, rates,
          out=sys.stdout) -> list:
    from bench import cell, gen

    spec = cell.load(root, workload)
    config, traffic = spec["config"], dict(spec["traffic"])
    s_data, s_build, s_q, s_wq = gen.seeds(seed, 4)
    collection = gen.make_collection(config, s_data)
    session = cell.serve(config, collection, s_build)
    cell.warm_up(session, collection, traffic, s_wq)
    rows = []
    for i, rate in enumerate(rates):
        traffic["rate_qps"] = float(rate)
        served = cell._drive(session, collection, traffic, seconds,
                             s_q + 7 * i)
        row = {"rate_qps": float(rate), **backlog(served)}
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing was run", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    rows = sweep(ROOT, args.workload, args.seed, args.seconds,
                 [float(r) for r in args.rates.split(",")])
    k = knee(rows)
    print(json.dumps({"knee_qps": k,
                      "rate_qps": None if k is None else 0.8 * k}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
