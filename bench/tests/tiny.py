"""A tiny twin of a benchmark tree for the CPU tests.

``make_root`` mirrors every configuration, traffic mix and cell of a
``BENCHMARK.json`` (the repository's own by default): each is shrunk by
one rule and renamed ``tiny-<name>``, every metric's ``workloads`` list is
renamed by the same rule, and the tree's metric readers are linked beside
them.  So the harness runs every cell end to end on the CPU in seconds,
and a cell added by data alone is tested the moment it is added.
"""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
REAL = os.path.join(ROOT, "BENCHMARK.json")

# What a twin changes.  A configuration keeps its generator, engine
# strategy, backbone and every other key it sets; a traffic mix keeps its
# loop, k, targets, seeds and in-flight depth.
SIZES = {"n": 1200, "m": 16}
LEAFI = {"leaf_capacity": 128, "n_global": 40, "n_local": 10,
         "train_epochs": 1}
LIMITS = {"calib_queries": 12}
TRAFFIC = {"rate_qps": 100.0, "outstanding": 8, "max_batch": 4,
           "warmup_s": 0.5}


def twin(name: str) -> str:
    return f"tiny-{name}"


def twin_config(config: dict) -> dict:
    out = copy.deepcopy(config)
    out.update(SIZES, name=twin(config["name"]))
    out["leafi"].update(LEAFI)
    out["limits"].update(LIMITS)
    return out


def twin_traffic(traffic: dict) -> dict:
    return {key: TRAFFIC.get(key, v) for key, v in traffic.items()}


def load(path: str = REAL) -> dict:
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str, bench_json: str = REAL) -> dict:
    """The traffic file ``name`` of the tree ``bench_json`` lies in."""
    return load(os.path.join(os.path.dirname(os.path.abspath(bench_json)),
                             "bench", "traffic", f"{name}.json"))


def cells(bench_json: str = REAL) -> list:
    """The twins' names of the cells of ``bench_json``."""
    return [twin(w["name"]) for w in load(bench_json)["workloads"]]


def make_root(path, bench_json: str = REAL) -> str:
    """A benchmark tree at ``path`` mirroring ``bench_json`` and the
    configuration, traffic and metric files of the tree it lies in."""
    path, src = str(path), os.path.dirname(os.path.abspath(bench_json))
    bench = load(bench_json)
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(path, "bench", sub))
    os.symlink(os.path.join(src, "bench", "metrics"),
               os.path.join(path, "bench", "metrics"))

    def write(rel, body):
        with open(os.path.join(path, rel), "w") as f:
            json.dump(body, f)

    for c in bench["configs"]:
        with open(os.path.join(src, c["file"])) as f:
            body = twin_config(json.load(f))
        c.update(name=body["name"], file=f"bench/configs/{body['name']}.json")
        write(c["file"], body)
    for name in sorted({w["traffic"] for w in bench["workloads"]}):
        write(f"bench/traffic/{twin(name)}.json",
              twin_traffic(load_traffic(name, bench_json)))
    for w in bench["workloads"]:
        w.update(name=twin(w["name"]), config=twin(w["config"]),
                 traffic=twin(w["traffic"]))
    for mt in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in mt:
            mt["workloads"] = [twin(w) for w in mt["workloads"]]
    write("BENCHMARK.json", bench)
    return path


def first_config(bench_json: str = REAL) -> dict:
    """The twin of the first configuration of ``bench_json``."""
    with open(os.path.join(os.path.dirname(os.path.abspath(bench_json)),
                           load(bench_json)["configs"][0]["file"])) as f:
        return twin_config(json.load(f))


def first_traffic(loop: str, bench_json: str = REAL) -> dict:
    """The twin of the first traffic mix of ``bench_json`` whose loop is
    ``loop``."""
    mixes = (load_traffic(w["traffic"], bench_json)
             for w in load(bench_json)["workloads"])
    return twin_traffic(next(t for t in mixes if t["loop"] == loop))


def session(config: dict = None, seed: int = 5):
    """A served tiny index of ``config`` (the first configuration's twin)
    and its collection."""
    from bench import cell, gen
    from repro.core import build
    from repro.serving import ServingSession
    config = config or first_config()
    collection = gen.make_collection(config, seed)
    lfi = build.build_leafi(collection, cell.leafi_config(config, seed))
    return ServingSession(lfi, strategy=config["engine_strategy"]), \
        collection
