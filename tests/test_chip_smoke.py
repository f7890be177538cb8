"""chip_smoke.py's phases at a tiny collection on the CPU.

The script itself refuses to run without a TPU; these tests drive the same
phases (build, warm-up, exact search against the brute-force reference,
the mixed-target serve trace, the sharded path) so the script cannot rot
between chip runs.
"""
import argparse
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _args(**kw):
    base = dict(n=3000, m=64, queries=24, requests=48, batch=8, noise=0.1,
                seed=0, chips=1)
    base.update(kw)
    return argparse.Namespace(**base)


def test_one_chip_phases(smoke, capsys):
    report = smoke.run_one_chip(_args(), platform=jax.devices()[0].platform)
    out = capsys.readouterr().out
    lines = dict(ln.split(": ", 1) for ln in out.splitlines() if ": " in ln)
    assert int(lines["filters"]) > 0
    # every exact micro-batch matched the reference (ties within DIST_TOL)
    exact = [v for k, v in lines.items() if k.startswith("exact_k")]
    assert len(exact) == 2 * 3
    assert report["n_requests"] == 48
    for t in smoke.TARGETS:
        assert float(lines[f"recall_at_target_{t}"]) >= t - \
            smoke.RECALL_MARGIN


def test_exact_search_matches_reference(smoke):
    args = _args()
    collection = smoke.make_collection(args.n, args.m, args.seed)
    lfi = smoke.build_index(collection, args.seed)
    assert len(lfi.leaf_ids) > 0
    data = smoke.reference_data(collection)
    from repro.data.series import make_query_set
    q = make_query_set(collection, 16, 0.1, 1)
    ref_d, ref_i = smoke.reference_knn(data, q, 10)
    res = lfi.search_exact(q, k=10)
    stats = smoke.check_exact(res.ids, res.dists, ref_i, ref_d, data, q)
    assert stats["ids_equal"] + stats["ties"] == q.shape[0] * 10
    # a wrong answer is caught: swap in a far row for one neighbour
    bad = np.array(res.ids)
    bad[0, 0] = int(ref_i[0, -1]) if ref_i[0, -1] != bad[0, 0] else 0
    with pytest.raises(smoke.SmokeError):
        smoke.check_exact(bad, res.dists + 1.0, ref_i, ref_d, data, q)


def test_sharded_phase(smoke, capsys):
    smoke.run_four_chips(_args(queries=16),
                         platform=jax.devices()[0].platform)
    out = capsys.readouterr().out
    assert "dist_exact_batch0: max_dist_err=" in out
    assert f"shard_bytes_device{jax.devices()[0].id}" in out


def test_main_refuses_without_tpu(smoke, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert smoke.main(["--n", "64"]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
