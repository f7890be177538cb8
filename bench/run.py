"""Run one cell of the chip benchmark once.

    python bench/run.py --workload rw256-approx-open --seed 7 --seconds 10 \
        --trace 0

Loads the cell named in ``BENCHMARK.json``, makes its collection from the
seed, builds the LeaFi index through the program, warms up the cell's own
programs, measures for ``--seconds`` seconds, checks every answer against
the plain reference and prints one JSON line last: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache is kept in ``.jax_cache/bench`` inside
the checkout, for programs of every size, so only a cell's first run in a
checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import cell
    return cell.run(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START, cache_dir=CACHE_DIR)


if __name__ == "__main__":
    sys.exit(main())
