"""Whole runs of a cell on the CPU: a new configuration, traffic file and
BENCHMARK.json entry are picked up with no existing file edited, and the
entry point refuses to run without a TPU."""
import io
import json
import os
import subprocess
import sys
import time

import pytest

from bench import cell
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


def _run(root, workload, trace):
    out, err = io.StringIO(), io.StringIO()
    rc = cell.run(root, workload, 2 ** 31 + 3, 0.5, trace,
                  t_start=time.perf_counter(), require_chip=False,
                  out=out, err=err)
    return rc, out.getvalue().splitlines(), err.getvalue().splitlines()


def test_new_cell_is_found_by_name(root):
    rc, out, err = _run(root, "tiny-open", False)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"p50_ms", "p99_ms", "setup_s"}
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    # the numbers compared are the last lines on standard error
    names = [ln.split(":")[0] for ln in err[-len(line["compared"]):]]
    assert names == [f"check {n}" for n in line["compared"]]


def test_traced_closed_cell_reports_its_layers(root):
    rc, out, _ = _run(root, "tiny-closed", True)
    assert rc == 0
    line = json.loads(out[-1])
    assert line["correct"] is True
    m = line["metrics"]
    # compiles.bulk is read by the stem's reader, compiles.py
    assert set(m) == {"compiles.bulk", "build_s", "warmup_s"}
    assert m["compiles.bulk"]["value"] >= 0
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rw256-approx-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for ln in proc.stdout.splitlines():
        assert not ln.startswith("{")


def test_run_refuses_without_a_tpu():
    _no_result(_cli(tiny.ROOT))


def test_run_refuses_without_the_program(tmp_path):
    import shutil
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    _no_result(_cli(tmp_path))
