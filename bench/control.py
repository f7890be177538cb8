"""The control of the check: the plain reference computed one precision
lower, put in the program's place, has to come out as not correct.

    python bench/control.py --workload rw256-approx-open \
        --seeds 11,12,13 --seconds 15

For each seed it makes the run's collection and the window's queries as
``run.py`` does (an open loop's requests due in ``--seconds``; a closed
loop's first ``--queries``, with their targets where it has any), answers
them with ``reference.control_knn`` and compares those answers with the
float32 reference exactly as a run is compared.  It prints one JSON line
per seed with the numbers compared and ``correct``.  The program is not
built: the control replaces it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_run(root: str, workload: str, seed: int, seconds: float,
                n_queries: int) -> dict:
    import numpy as np
    from bench import cell, gen, loops

    spec = cell.load(root, workload)
    config, traffic = spec["config"], spec["traffic"]
    s_data, _, s_q, _ = gen.seeds(seed, 4)
    collection = gen.make_collection(config, s_data)
    if traffic["loop"] == "open":
        due, queries, targets = cell._traffic_inputs(
            collection, traffic, seconds, s_q)
    else:
        queries = gen.make_queries(collection, n_queries, traffic["noise"],
                                   np.random.default_rng(s_q))
        draw_targets = cell.closed_targets(traffic)
        due = np.zeros(n_queries)
        targets = None if draw_targets is None else draw_targets(n_queries)
    n, k = len(queries), traffic["k"]
    served = loops.Served(
        queries=queries, targets=targets, due=due, done=np.zeros(n),
        ids=np.zeros((n, k), np.int64), dists=np.zeros((n, k), np.float32),
        searched=np.zeros(n), n_leaves=1, batches=[], late=np.zeros(0),
        seconds=seconds, end=seconds)
    compared = cell.check(collection, served, config, traffic, control=True)
    return {"workload": workload, "seed": seed,
            "correct": cell.passed(compared), "compared": compared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--queries", type=int, default=1024,
                    help="closed loop: queries compared per seed")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    for s in args.seeds.split(","):
        print(json.dumps(control_run(ROOT, args.workload, int(s),
                                     args.seconds, args.queries)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
