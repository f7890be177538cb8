"""One run of one cell: set-up, the measured window, the check, the line.

Everything a cell is made of is found by name, so a new cell needs new
files and entries only:

* ``BENCHMARK.json`` — the cell (``workloads``), its configuration and
  traffic names, and the metrics it reports;
* ``bench/configs/<config>.json`` — the collection's generator and sizes,
  the index's build settings, the guarantees and the limits of the check;
* ``bench/traffic/<traffic>.json`` — the loop and its parameters;
* ``bench/metrics/<metric>.py`` — the metric's reader, ``read(ctx)``,
  returning a number or None (nothing to read: the metric is left out).
  Where that file is absent, the reader of the name's stem serves it:
  ``compiles.py`` reads ``compiles.open`` and ``compiles.bulk``, the one
  quantity split by the end-to-end metric it moves.  A reader that reads
  nothing where the trace holds no device operation says so with
  ``CHIP_ONLY = True``; the CPU tests expect every other metric of a cell.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from . import gen, loops, reference, tracing

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
WINDOW = "bench.window"


class CellError(RuntimeError):
    """The cell cannot run here (no chip, a missing file)."""


def load(root: str, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metric lists."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    base = os.path.join(root, "bench")
    with open(os.path.join(base, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(base, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)

    def mine(metrics):
        return [mt for mt in metrics
                if workload in mt.get("workloads", [workload])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"]),
            "metrics_dir": os.path.join(base, "metrics")}


def reader(metrics_dir: str, name: str):
    path = os.path.join(metrics_dir, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(metrics_dir, f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def serve(config: dict, collection: np.ndarray, seed: int):
    """Build the configuration's index and open a serving session on it."""
    from repro.core import build
    from repro.serving import ServingSession
    lfi = build.build_leafi(collection, leafi_config(config, seed))
    return ServingSession(lfi, strategy=config["engine_strategy"])


def leafi_config(config: dict, seed: int):
    """The program's build settings named by the configuration file."""
    from repro.core import build, filter_training
    kw = dict(config["leafi"])
    epochs = kw.pop("train_epochs")
    return build.LeaFiConfig(
        **kw, seed=seed,
        train=filter_training.TrainConfig(epochs=epochs, seed=seed))


class CompileCounter:
    """Counts programs made ready (compiled or read from the persistent
    cache) while ``on``, through a ``jax.monitoring`` listener."""

    def __init__(self):
        self.on = False
        self.n = 0

    def __call__(self, event: str, duration: float, **_):
        if self.on and event == BACKEND_COMPILE:
            self.n += 1


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def _traffic_inputs(collection, traffic, seconds, s_queries):
    """Due times, queries and targets of an open loop's window.

    The due times and the targets are drawn from the traffic file's
    ``arrival_seed``, so every run offers the same schedule; the queries
    are drawn from the run's seed, fresh in every run."""
    rng = np.random.default_rng(traffic["arrival_seed"])
    due = gen.poisson_arrivals(traffic["rate_qps"], seconds, rng)
    targets = np.asarray(traffic["targets"], np.float64)[
        rng.integers(0, len(traffic["targets"]), len(due))]
    queries = gen.make_queries(collection, len(due), traffic["noise"],
                               np.random.default_rng(s_queries))
    return due, queries, targets


def _drive(session, collection, traffic, seconds, s_queries):
    """Run the traffic's loop for ``seconds`` → :class:`loops.Served`."""
    t = traffic
    if t["loop"] == "open":
        due, queries, targets = _traffic_inputs(collection, t, seconds,
                                                s_queries)
        return loops.open_loop(
            session, queries, due, targets, k=t["k"],
            max_batch=t["max_batch"], max_wait=t["max_wait_s"],
            in_flight=t["in_flight"], seconds=seconds)
    if t["loop"] == "closed":
        rng = np.random.default_rng(s_queries)
        return loops.closed_loop(
            session, lambda c: gen.make_queries(collection, c, t["noise"],
                                                rng),
            k=t["k"], outstanding=t["outstanding"],
            max_batch=t["max_batch"], seconds=seconds,
            draw_targets=closed_targets(t))
    raise CellError(f"unknown loop {t['loop']!r}")


def closed_targets(traffic):
    """``c`` ↦ the recall targets of a closed loop's next ``c`` requests,
    uniform over the traffic's targets and drawn from its ``target_seed``,
    so every run offers the same sequence (None: the requests are exact)."""
    if traffic["targets"] is None:
        return None
    rng = np.random.default_rng(traffic["target_seed"])
    choices = np.asarray(traffic["targets"], np.float64)
    return lambda c: choices[rng.integers(0, len(choices), c)]


def warm_up(session, collection, traffic, s_queries) -> None:
    """Compile the cell's own programs: every (bucket, k) shape its batcher
    can form, then a slice of its own traffic (fresh queries), which
    reaches every program the window runs."""
    t = traffic
    if t["loop"] == "open":
        q = gen.make_queries(collection, t["max_batch"], t["noise"],
                             np.random.default_rng(s_queries))
        session.warmup(max_batch=t["max_batch"], ks=(t["k"],), queries=q,
                       targets=tuple(t["targets"]))
    _drive(session, collection, traffic, t["warmup_s"], s_queries + 1)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def check(collection: np.ndarray, served: loops.Served, config: dict,
          traffic: dict, control: bool = False) -> dict:
    """Compare every answered request with the plain reference.

    Returns {name: {"value", "limit", "rule"}}.  ``control`` puts the
    reference one precision lower (``reference.control_knn``) in the
    program's place first.
    """
    lim = config["limits"]
    ok = served.answered
    exact = traffic["targets"] is None
    k = traffic["k"]
    data = reference.place(gen.znormalize(collection))
    q = served.queries[ok]
    ref_d, ref_i = reference.knn(data, q, k)
    ids, dists = served.ids[ok], served.dists[ok]
    if control:
        dists, ids = reference.control_knn(data, q, k)
    own = reference.dist_of(data, q, ids, len(collection))
    del data
    got = reference.compare(ids, dists, ref_i, ref_d, own,
                            tie_tol=lim["dist_err"], exact=exact,
                            targets=None if exact else served.targets[ok])
    n_due = int((served.due < served.seconds).sum()) \
        if traffic["loop"] == "open" else int(ok.sum())
    out = {"unanswered": {"value": n_due - int(ok.sum()), "limit": 0,
                          "rule": "<="},
           "dist_err": {"value": got["dist_err"], "limit": lim["dist_err"],
                        "rule": "<="}}
    if exact:
        out["id_mismatch"] = {"value": got["id_mismatch"], "limit": 0,
                              "rule": "<="}
    else:
        for t in traffic["targets"]:
            name = f"recall@{float(t):g}"
            n_t = int((served.targets[ok] == t).sum())
            out[name] = {"value": got.get(name, 0.0),
                         "limit": recall_floor(t, n_t, lim),
                         "rule": ">="}
    return out


def recall_floor(target: float, n: int, limits: dict) -> float:
    """The configuration's recall guarantee for ``n`` requests at
    ``target``: the target less ``recall_sigmas`` binomial standard
    deviations of the calibration and of the run's own sample."""
    var = target * (1 - target) * (1 / limits["calib_queries"]
                                   + 1 / max(n, 1))
    return float(target - limits["recall_sigmas"] * np.sqrt(var))


def passed(compared: dict) -> bool:
    for c in compared.values():
        v, lim = c["value"], c["limit"]
        if not (v <= lim if c["rule"] == "<=" else v >= lim):
            return False
    return True


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, require_chip: bool = True,
        cache_dir: Optional[str] = None, out=None, err=None) -> int:
    """One run of ``workload``; prints the result line; returns the exit
    code (0 after a result line, 2 when the cell cannot run here)."""
    out = out or sys.stdout
    err = err or sys.stderr
    spec = load(root, workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]

    import jax
    from jax._src import monitoring

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=err)
        return 2
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from repro.obs import spans

    s_data, s_build, s_q, s_wq = gen.seeds(seed, 4)
    counter = CompileCounter()
    monitoring.register_event_duration_secs_listener(counter)
    rec = spans.SpanRecorder(maxlen=1 << 20)
    try:
        with spans.recording(rec):
            collection = gen.make_collection(config, s_data)
            log(out, "data", t_start)
            session = serve(config, collection, s_build)
            log(out, "build", t_start)
            t_w = time.perf_counter()
            warm_up(session, collection, traffic, s_wq)
            warmup_s = time.perf_counter() - t_w
            log(out, "warm-up", t_start)
            n_filters = len(session.lfi.leaf_ids)

            trace_dir = tempfile.mkdtemp(prefix="bench_trace_") \
                if trace else None
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0       # annotations stay
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            counter.on = True
            t_win = time.perf_counter()
            setup_s = t_win - t_start
            with jax.profiler.TraceAnnotation(WINDOW):
                served = _drive(session, collection, traffic, seconds,
                                s_q)
            t_end = time.perf_counter()
            counter.on = False
            if trace:
                jax.profiler.stop_trace()
        dev = devices[0]
        peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        del session
        gc.collect()
        compared = check(collection, served, config, traffic)
        log(out, "check", t_start)
    finally:
        monitoring.unregister_event_duration_listener(counter)

    ctx = {
        "workload": workload, "config": config, "traffic": traffic,
        "served": served, "setup_s": setup_s, "warmup_s": warmup_s,
        "peak_bytes": peak, "compiles": counter.n, "n_filters": n_filters,
        "spans": rec.spans(), "window_pc": (t_win, t_end),
        "device_kind": dev.device_kind, "trace": None,
    }
    result = {"correct": passed(compared),
              "attempted": int(compared["unanswered"]["value"]
                               + served.answered.sum()),
              "failed": int(compared["unanswered"]["value"]),
              "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}}
    if trace:
        ev = tracing.load(trace_dir)
        _rm(trace_dir)
        lo, hi = tracing.window(ev["host"], WINDOW)
        planes = sorted(ev["devices"])[:cell["chips"]]
        per_plane = {p: tracing.on_host_clock(ev["devices"][p], lo, hi)
                     for p in planes}
        dev_ev = [e for p in planes for e in per_plane[p]]
        busy = np.mean([tracing.busy_ns(per_plane[p], lo, hi)
                        for p in planes]) if planes else 0.0
        ctx["trace"] = {"device": dev_ev, "host": ev["host"], "lo": lo,
                        "hi": hi, "busy_ns": busy, "planes": planes}
        print(f"bench: trace: {len(dev_ev)} device events on {planes}, "
              f"{len(ev['host'])} host events", file=out)
        # the part of the window the device record covers
        cut = tracing.recorded_until(dev_ev, hi)
        result["device"]["busy_s"] = busy * 1e-9
        result["device"]["window_s"] = (cut - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": tracing.top_ops(dev_ev, lo, cut),
            "idle_gaps": tracing.idle_gaps(dev_ev, ev["host"], lo, cut)}
    for mt in spec["per_layer" if trace else "end_to_end"]:
        v = reader(spec["metrics_dir"], mt["name"])(ctx)
        if v is not None:
            result["metrics"][mt["name"]] = {"value": float(v),
                                             "unit": mt["unit"]}
    late = served.late * 1e3 if served.late.size else np.zeros(1)
    print(f"bench: {workload} seed {seed}: {int(served.answered.sum())} "
          f"answered in {served.end:.3f} s, {len(served.batches)} batches, "
          f"{counter.n} programs made ready in the window; generator "
          f"lateness p99 {np.percentile(late, 99):.3f} ms, max "
          f"{late.max():.3f} ms", file=out)
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} (limit {c['rule']} "
              f"{c['limit']!r})", file=err)
    result["compared"] = compared
    print(json.dumps(result), file=out, flush=True)
    return 0


def log(out, phase: str, t_start: float) -> None:
    print(f"bench: {phase} done at {time.perf_counter() - t_start:.3f} s",
          file=out, flush=True)


def _rm(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)
