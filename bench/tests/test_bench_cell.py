"""Whole runs of every cell of BENCHMARK.json on the CPU, through its tiny
twin: each is found by name, is correct and reports the metrics whose
lists name it; a configuration, traffic file and cell added by data alone
are picked up with no existing file edited; and the entry point refuses to
run without a TPU."""
import io
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import cell
from bench.tests import tiny

CELLS = tiny.cells()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def _run(root, workload, trace):
    out, err = io.StringIO(), io.StringIO()
    rc = cell.run(root, workload, 2 ** 31 + 3, 0.5, trace,
                  t_start=time.perf_counter(), require_chip=False,
                  out=out, err=err)
    return rc, out.getvalue().splitlines(), err.getvalue().splitlines()


def read_on_cpu(root, workload, kind):
    """The metrics of ``kind`` whose list names ``workload`` or that have
    no list, less those a CPU run cannot read: the device trace's, and
    those whose reader needs a device operation (``CHIP_ONLY``)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = os.path.join(root, "bench", "metrics")
    return {mt["name"] for mt in bench[kind]
            if workload in mt.get("workloads", [workload])
            and mt["source"] != "device_trace"
            and not cell.reader(metrics, mt["name"]).__globals__.get(
                "CHIP_ONLY", False)}


def _reports_its_metrics(root, workload, trace):
    rc, out, err = _run(root, workload, trace)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    kind = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == read_on_cpu(root, workload, kind)
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    # the numbers compared are the last lines on standard error
    names = [ln.split(":")[0] for ln in err[-len(line["compared"]):]]
    assert names == [f"check {n}" for n in line["compared"]]
    return line


@pytest.mark.parametrize("workload", CELLS)
def test_new_cell_is_found_by_name(root, workload):
    _reports_its_metrics(root, workload, False)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_closed_cell_reports_its_layers(root, workload):
    """Every cell's traced run (open ones too, despite the name)."""
    line = _reports_its_metrics(root, workload, True)
    m = line["metrics"]
    for name in m:
        if name.startswith("compiles."):
            # read by the stem's reader, compiles.py
            assert m[name]["value"] >= 0
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


# a configuration, a traffic mix and a cell added by data alone, with a
# per-layer metric and a reader of its own
EXTRA_TRAFFIC = {"loop": "open", "rate_qps": 50.0, "arrival_seed": 11,
                 "k": 1, "targets": [0.9, 0.99], "noise": 0.1,
                 "max_batch": 64, "max_wait_s": 0.002, "in_flight": 1,
                 "warmup_s": 3}
EXTRA_READER = '''"""Batches the window dispatched."""


def read(ctx):
    return len(ctx["served"].batches)
'''
BACKBONES = {"dstree": {}, "isax": {"backbone": "isax", "word_len": 16}}


@pytest.mark.parametrize("backbone", sorted(BACKBONES))
def test_cell_added_by_data_alone(tmp_path, backbone):
    src = tmp_path / "src"
    shutil.copytree(tiny.BENCH, src / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = tiny.load()
    with open(os.path.join(tiny.ROOT, bench["configs"][0]["file"])) as f:
        config = json.load(f)
    config["name"] = "extra"
    config["leafi"].update(BACKBONES[backbone])
    for rel, body in [("bench/configs/extra.json", config),
                      ("bench/traffic/extra-open.json", EXTRA_TRAFFIC)]:
        (src / rel).write_text(json.dumps(body))
    (src / "bench/metrics/extra_batches.py").write_text(EXTRA_READER)
    bench["configs"].append({"name": "extra", "source": "test",
                             "file": "bench/configs/extra.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "extra-cell", "config": "extra",
                               "traffic": "extra-open", "chips": 1,
                               "why": "test"})
    p50 = next(mt for mt in bench["end_to_end"] if mt["name"] == "p50_ms")
    p50["workloads"].append("extra-cell")
    bench["per_layer"].append({
        "name": "extra_batches", "unit": "batches", "better": "lower",
        "source": "program_counter", "layer": "serving", "moves": "p50_ms",
        "workloads": ["extra-cell"]})
    (src / "BENCHMARK.json").write_text(json.dumps(bench))

    real = tiny.make_root(tmp_path / "real")
    root = tiny.make_root(tmp_path / "root", str(src / "BENCHMARK.json"))
    # nothing else changes: the real cells' twins and their files
    got, want = tiny.load(os.path.join(root, "BENCHMARK.json")), \
        tiny.load(os.path.join(real, "BENCHMARK.json"))
    assert got["workloads"][:-1] == want["workloads"]
    assert got["configs"][:-1] == want["configs"]
    assert got["per_layer"][:-1] == want["per_layer"]
    for mt, was in zip(got["end_to_end"], want["end_to_end"]):
        extra = ["tiny-extra-cell"] if mt["name"] == "p50_ms" else []
        assert mt == dict(was, **({"workloads": was["workloads"] + extra}
                                  if "workloads" in was else {}))
    for sub in ("configs", "traffic"):
        for name in os.listdir(os.path.join(real, "bench", sub)):
            with open(os.path.join(real, "bench", sub, name)) as a, \
                    open(os.path.join(root, "bench", sub, name)) as b:
                assert a.read() == b.read()
    # the twin keeps every build key it sets (backbone, word length) but
    # the sizes
    twin = tiny.load(os.path.join(root, "bench/configs/tiny-extra.json"))
    assert twin["leafi"] == dict(config["leafi"], **tiny.LEAFI)

    _reports_its_metrics(root, "tiny-extra-cell", False)
    line = _reports_its_metrics(root, "tiny-extra-cell", True)
    assert line["metrics"]["extra_batches"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         tiny.load()["workloads"][0]["name"], "--seed", "1", "--seconds",
         "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for ln in proc.stdout.splitlines():
        assert not ln.startswith("{")


def test_run_refuses_without_a_tpu():
    _no_result(_cli(tiny.ROOT))


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    _no_result(_cli(tmp_path))
