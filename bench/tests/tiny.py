"""A tiny benchmark tree for the CPU tests: one configuration, an open and
a closed traffic mix and their cells, written beside the real metric
readers, so the harness runs end to end in seconds."""
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

CONFIG = {
    "name": "tiny-dstree", "source": "test", "generator": "randwalk",
    "n": 1200, "m": 16, "data_seed": 3, "precision": "float32",
    "engine_strategy": "scan",
    "leafi": {"backbone": "dstree", "leaf_capacity": 128, "n_global": 40,
              "n_local": 10, "t_filter_over_t_series": 20.0,
              "train_epochs": 1},
    "limits": {"dist_err": 0.001, "recall_sigmas": 3, "calib_queries": 12},
    "reduced": ["n"],
}
OPEN = {"loop": "open", "rate_qps": 100.0, "arrival_seed": 7, "k": 1,
        "targets": [0.9, 0.95, 0.99], "noise": 0.1, "max_batch": 4,
        "max_wait_s": 0.002, "in_flight": 1, "warmup_s": 0.5}
CLOSED = {"loop": "closed", "outstanding": 8, "k": 3, "targets": None,
          "noise": 0.1, "max_batch": 4, "warmup_s": 0.5}


def make_root(path) -> str:
    """BENCHMARK.json with the tiny cells (every metric of the real one,
    its cells renamed), the tiny files, and the real metric readers."""
    path = str(path)
    os.makedirs(os.path.join(path, "bench", "configs"))
    os.makedirs(os.path.join(path, "bench", "traffic"))
    os.symlink(os.path.join(BENCH, "metrics"),
               os.path.join(path, "bench", "metrics"))
    files = {"configs/tiny-dstree.json": CONFIG,
             "traffic/tiny-open.json": OPEN,
             "traffic/tiny-closed.json": CLOSED}
    for name, body in files.items():
        with open(os.path.join(path, "bench", name), "w") as f:
            json.dump(body, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rename = {"rw256-approx-open": "tiny-open",
              "deep96-exact-bulk": "tiny-closed"}
    bench["configs"] = [{"name": "tiny-dstree", "source": "test",
                         "file": "bench/configs/tiny-dstree.json",
                         "reduced": ["n"], "why": "test"}]
    bench["workloads"] = [
        {"name": w, "config": "tiny-dstree", "traffic": w, "chips": 1,
         "why": "test"} for w in ("tiny-open", "tiny-closed")]
    for mt in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in mt:
            mt["workloads"] = [rename[w] for w in mt["workloads"]]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return path


def session(seed: int = 5):
    """A served tiny index and its collection."""
    from bench import cell, gen
    from repro.core import build
    from repro.serving import ServingSession
    collection = gen.make_collection(CONFIG, seed)
    lfi = build.build_leafi(collection, cell.leafi_config(CONFIG, seed))
    return ServingSession(lfi), collection
