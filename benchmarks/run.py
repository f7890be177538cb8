"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes the full payloads to
experiments/bench_results.json (EXPERIMENTS.md is generated from those).

  PYTHONPATH=src python -m benchmarks.run                  # full sweep
  PYTHONPATH=src python -m benchmarks.run --quick          # randwalk-only
  PYTHONPATH=src python -m benchmarks.run --suite build    # one suite only

Standalone suites (``--suite``) run a single benchmark module and write its
own experiments/ payload: ``build`` → build_bench (batched vs per-leaf
training-data collection), ``engine`` → engine_bench (scan vs compact vs
pairwise cascade execution), ``dist`` → dist_bench (scan vs fixed-width
compact shard bodies on a 1×N host-device mesh), ``serve`` → serve_bench
(micro-batched mixed-quality-target open-loop serving vs the homogeneous
batch path), ``filters`` → filters_bench (per-filter vs fused filter
inference kernels × weight dtype, with the roofline bound pin), ``obs`` →
obs_bench (traced vs untraced cascade throughput across pruning ratios —
the observability overhead pin).
"""
from __future__ import annotations

import argparse
import json
import os
import time

from . import (build_bench, common, dist_bench, engine_bench, filters_bench,
               kernels_bench, obs_bench, paper_tables, serve_bench, wallclock)

SUITES = {
    "build": (build_bench.bench_build, "experiments/build_bench.json"),
    "engine": (engine_bench.bench_engine, "experiments/engine_bench.json"),
    "dist": (dist_bench.bench_dist, "experiments/dist_bench.json"),
    "serve": (serve_bench.bench_serve, "experiments/serve_bench.json"),
    "filters": (filters_bench.bench_filters,
                "experiments/filters_bench.json"),
    "obs": (obs_bench.bench_obs, "experiments/obs_bench.json"),
}


def _run_suite(name: str, out: str | None) -> None:
    fn, default_out = SUITES[name]
    rows, payload = fn()
    common.write_suite_payload(rows, payload, out or default_out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="randwalk-only, skips sweeps")
    ap.add_argument("--datasets", default=None,
                    help="comma-separated subset")
    ap.add_argument("--suite", default=None, choices=sorted(SUITES),
                    help="run one registered suite and exit")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.suite:
        _run_suite(args.suite, args.out)
        return
    args.out = args.out or "experiments/bench_results.json"

    datasets = (args.datasets.split(",") if args.datasets
                else (("randwalk",) if args.quick else common.DATASETS))
    all_rows, payloads = [], {}
    t_start = time.perf_counter()

    for ds in datasets:
        for backbone in ("dstree", "isax"):
            setup = common.get_setup(ds, backbone)
            tag = f"{ds}/{backbone}"
            for fn in (paper_tables.bench_pruning_ratio,
                       paper_tables.bench_query_time,
                       paper_tables.bench_recall_targets,
                       paper_tables.bench_build_time):
                rows, payload = fn(setup)
                all_rows += [r.replace(f"/{ds}/", f"/{tag}/") for r in rows]
                payloads[f"{fn.__name__}/{tag}"] = payload

    if not args.quick:
        for fn, key in ((paper_tables.bench_scalability, "scalability"),
                        (paper_tables.bench_node_threshold, "node_threshold"),
                        (paper_tables.bench_memory_budget, "memory_budget"),
                        (paper_tables.bench_local_data, "local_data")):
            rows, payload = fn()
            all_rows += rows
            payloads[key] = payload

    rows, payload = paper_tables.bench_model_type()
    all_rows += rows
    payloads["model_type"] = payload

    for ds in ("randwalk", "sift") if not args.quick else ("randwalk",):
        setup = common.get_setup(ds, "dstree")
        rows, payload = wallclock.bench_wallclock(setup)
        all_rows += rows
        payloads[f"wallclock/{ds}"] = payload
    # paper-regime leaves (large |N|): where Eq. 4 predicts wall-clock wins
    setup = wallclock.paper_regime_setup("sift" if not args.quick
                                         else "randwalk")
    rows, payload = wallclock.bench_wallclock(setup)
    all_rows += [r.replace("wallclock/", "wallclock_bigleaf/") for r in rows]
    payloads["wallclock_bigleaf"] = payload

    rows, payload = kernels_bench.bench_kernels()
    all_rows += rows
    payloads["kernels"] = payload

    # scan-vs-compact engine wall-clock across pruning ratios
    rows, payload = engine_bench.bench_engine(
        n=10_000 if args.quick else 50_000)
    all_rows += rows
    payloads["engine"] = payload

    for r in all_rows:
        print(r)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(payloads, f, indent=1, default=float)
    print(f"# total {time.perf_counter() - t_start:.1f}s "
          f"→ {len(all_rows)} rows → {args.out}")


if __name__ == "__main__":
    main()
