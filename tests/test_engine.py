"""Engine exactness parity: strategy="compact" vs strategy="scan" vs brute
force.

The compact path replays the sequential cascade over per-leaf top-k
summaries, so it must reproduce the scan path's top-k ids/dists AND its
pruning counters bitwise — including under active (lossy) filter pruning,
where the decisions depend on the evolving best-so-far.  These tests pin
that contract across backbones, k, filter regimes, and the adversarial
all-leaves-survive case.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import bounds, build, engine, filter_training, search, tree


@pytest.fixture(scope="module", params=["dstree", "isax"])
def index_small(request, randwalk_small):
    if request.param == "dstree":
        return tree.build_dstree(randwalk_small[:2000], leaf_capacity=64)
    return tree.build_isax(randwalk_small[:2000], leaf_capacity=64)


def _run(index, queries, d_lb, d_F, k, strategy):
    return engine.run_cascade(
        jnp.asarray(index.series), jnp.asarray(index.leaf_start),
        jnp.asarray(index.leaf_size), queries, d_lb, d_F,
        k=k, max_leaf=index.max_leaf_size, strategy=strategy)


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a.topk_d), np.asarray(b.topk_d))
    np.testing.assert_array_equal(np.asarray(a.topk_i), np.asarray(b.topk_i))
    np.testing.assert_array_equal(np.asarray(a.n_searched),
                                  np.asarray(b.n_searched))
    np.testing.assert_array_equal(np.asarray(a.n_pruned_lb),
                                  np.asarray(b.n_pruned_lb))
    np.testing.assert_array_equal(np.asarray(a.n_pruned_filter),
                                  np.asarray(b.n_pruned_filter))


def _synthetic_predictions(d_lb, seed=0):
    """Deterministic noisy per-leaf NN 'predictions' → real filter pruning."""
    lb = np.asarray(d_lb)
    rng = np.random.default_rng(seed)
    noise = rng.standard_normal(lb.shape).astype(np.float32)
    return jnp.asarray(lb * (1.4 + 0.4 * noise) + 2.0)


@pytest.mark.parametrize("k", [1, 10])
def test_compact_matches_scan_bitwise_exact(index_small, queries_small, k):
    q = jnp.asarray(queries_small)
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = jnp.full(d_lb.shape, -jnp.inf)
    a = _run(index_small, q, d_lb, d_F, k, "scan")
    b = _run(index_small, q, d_lb, d_F, k, "compact")
    _assert_bitwise(a, b)
    # compact must not have paid for more leaves than exist, nor fewer than
    # it reports as scanned
    assert (np.asarray(b.n_computed) <= index_small.n_leaves).all()
    assert (np.asarray(b.n_computed) >= np.asarray(b.n_searched)).all()


@pytest.mark.parametrize("k", [1, 10])
def test_compact_matches_scan_bitwise_with_filter_pruning(
        index_small, queries_small, k):
    q = jnp.asarray(queries_small)
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb)
    a = _run(index_small, q, d_lb, d_F, k, "scan")
    b = _run(index_small, q, d_lb, d_F, k, "compact")
    assert np.asarray(a.n_pruned_filter).sum() > 0   # the cascade is active
    _assert_bitwise(a, b)


def test_all_leaves_survive_adversarial(index_small, queries_small):
    """Zero lower bounds + no filters: nothing prunes, the compact path must
    degrade to the full-width bucket (empty-pruning path) and stay exact."""
    q = jnp.asarray(queries_small)
    d_lb = jnp.zeros((q.shape[0], index_small.n_leaves), jnp.float32)
    d_F = jnp.full(d_lb.shape, -jnp.inf)
    a = _run(index_small, q, d_lb, d_F, 3, "scan")
    b = _run(index_small, q, d_lb, d_F, 3, "compact")
    _assert_bitwise(a, b)
    assert (np.asarray(b.n_computed) == index_small.n_leaves).all()
    assert (np.asarray(b.n_searched) == index_small.n_leaves).all()


def test_k_larger_than_leaf_capacity(index_small, queries_small):
    q = jnp.asarray(queries_small[:8])
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb, seed=3)
    k = index_small.max_leaf_size + 17
    a = _run(index_small, q, d_lb, d_F, k, "scan")
    b = _run(index_small, q, d_lb, d_F, k, "compact")
    _assert_bitwise(a, b)


def _scan(index, queries, d_lb, d_F, k, bsf_ub, trace):
    return engine.run_cascade(
        jnp.asarray(index.series), jnp.asarray(index.leaf_start),
        jnp.asarray(index.leaf_size), queries, d_lb, d_F,
        k=k, max_leaf=index.max_leaf_size, strategy="scan", bsf_ub=bsf_ub,
        trace=trace)


@pytest.mark.parametrize("k", [1, 10, "over_leaf"])
def test_leafwise_scan_matches_sequential_scan_bitwise(
        index_small, queries_small, k):
    """The untraced scan (every leaf's k smallest read leaf-major once for
    the batch, then the replay) == the sequential per-query cascade that
    trace=True keeps == compact with the direct distance form, bitwise,
    with filters pruning, a finite warm-start bound and a batch of 11."""
    q = jnp.asarray(queries_small[:11])
    if k == "over_leaf":
        k = index_small.max_leaf_size + 17
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb, seed=5)
    want_d, _ = _brute_force(index_small, np.asarray(q), k)
    ub = jnp.asarray(want_d[:, -1] * np.float32(1.05))
    a = _scan(index_small, q, d_lb, d_F, k, ub, trace=False)
    b = _scan(index_small, q, d_lb, d_F, k, ub, trace=True)
    c = engine.run_cascade(
        jnp.asarray(index_small.series), jnp.asarray(index_small.leaf_start),
        jnp.asarray(index_small.leaf_size), q, d_lb, d_F,
        k=k, max_leaf=index_small.max_leaf_size, strategy="compact",
        dist_impl="direct", bsf_ub=ub)
    assert np.asarray(b.n_pruned_filter).sum() > 0   # the cascade is active
    _assert_bitwise(a, b)
    _assert_bitwise(a, c)


def test_untraced_scan_gathers_no_series_slab(index_small, queries_small):
    """The mechanism: the untraced scan reads the series only through
    dynamic slices at a scalar (per-leaf) start.  A gather whose operand is
    the series is what the chip runs as one serial copy per query."""
    q = jnp.asarray(queries_small[:5])
    series = jnp.asarray(index_small.series)
    d_lb = bounds.lower_bounds(index_small, q)
    txt = engine._scan_cascade.lower(
        series, jnp.asarray(index_small.leaf_start),
        jnp.asarray(index_small.leaf_size), q, d_lb,
        jnp.full(d_lb.shape, -jnp.inf), jnp.full((5,), jnp.inf),
        k=3, max_leaf=index_small.max_leaf_size).as_text()
    series_t = "tensor<{}x{}xf32>".format(*series.shape)
    gathers = [ln for ln in txt.splitlines() if "stablehlo.gather" in ln]
    assert gathers                       # the small per-query gathers stay
    assert not [ln for ln in gathers if f": ({series_t}," in ln]
    assert "stablehlo.dynamic_slice" in txt


def _brute_force(index, queries, k):
    S = np.asarray(index.series[: index.n_series])
    d = np.sqrt(((queries[:, None, :] - S[None]) ** 2).sum(-1))
    rows = np.argsort(d, axis=1)[:, :k]
    return np.take_along_axis(d, rows, 1), np.asarray(index.order)[rows]


@pytest.mark.parametrize("strategy", ["scan", "compact"])
def test_exact_search_equals_brute_force(index_small, queries_small,
                                         strategy):
    res = search.search_batched(index_small, queries_small, k=5,
                                use_filters=False, strategy=strategy)
    want_d, want_i = _brute_force(index_small, queries_small, k=5)
    np.testing.assert_allclose(res.dists, want_d, rtol=1e-4)
    assert (np.sort(res.ids, 1) == np.sort(want_i, 1)).all()
    want_computed = (index_small.n_leaves if strategy == "scan"
                     else res.searched)
    assert (res.computed >= want_computed).all()


def test_leafi_end_to_end_strategies_agree(randwalk_small):
    """Built index with trained filters + conformal offsets: both engine
    strategies return identical results through the public search API."""
    cfg = build.LeaFiConfig(backbone="dstree", leaf_capacity=64,
                            n_global=60, n_local=16,
                            t_filter_over_t_series=10.0,
                            train=filter_training.TrainConfig(epochs=5))
    lfi = build.build_leafi(randwalk_small[:1500], cfg)
    rng = np.random.default_rng(11)
    q = (randwalk_small[rng.integers(0, 1500, 16)]
         + 0.25 * rng.standard_normal((16, randwalk_small.shape[1]))
         .astype(np.float32))
    for k in (1, 10):
        a = lfi.search(q, k=k, quality_target=0.99, strategy="scan")
        b = lfi.search(q, k=k, quality_target=0.99, strategy="compact")
        np.testing.assert_array_equal(a.dists, b.dists)
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.searched, b.searched)
        np.testing.assert_array_equal(a.pruned_lb, b.pruned_lb)
        np.testing.assert_array_equal(a.pruned_filter, b.pruned_filter)


@pytest.mark.parametrize("dist_impl", ["matmul", "pairwise"])
def test_lossy_impls_close_to_direct(index_small, queries_small, dist_impl):
    """The MXU distance impls (matmul decomposition; the union-slab pairwise
    kernel path) are numerically different from the scan path but must agree
    to float tolerance and make identical id choices on well-separated
    data."""
    q = jnp.asarray(queries_small[:8])
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = jnp.full(d_lb.shape, -jnp.inf)
    a = _run(index_small, q, d_lb, d_F, 5, "scan")
    b = engine.run_cascade(
        jnp.asarray(index_small.series), jnp.asarray(index_small.leaf_start),
        jnp.asarray(index_small.leaf_size), q, d_lb, d_F,
        k=5, max_leaf=index_small.max_leaf_size, strategy="compact",
        dist_impl=dist_impl)
    np.testing.assert_allclose(np.asarray(a.topk_d), np.asarray(b.topk_d),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(a.topk_i), np.asarray(b.topk_i))


@pytest.mark.parametrize("k", [1, 10])
def test_pairwise_impl_with_filter_pruning(index_small, queries_small, k):
    """Union-slab pairwise candidates under an active filter cascade: the
    non-survivor leaves that ride along in the shared slab must never leak
    into results or counters (float-tolerance engine parity)."""
    q = jnp.asarray(queries_small)
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb)
    a = _run(index_small, q, d_lb, d_F, k, "scan")
    b = engine.run_cascade(
        jnp.asarray(index_small.series), jnp.asarray(index_small.leaf_start),
        jnp.asarray(index_small.leaf_size), q, d_lb, d_F,
        k=k, max_leaf=index_small.max_leaf_size, strategy="compact",
        dist_impl="pairwise")
    assert np.asarray(a.n_pruned_filter).sum() > 0
    np.testing.assert_allclose(np.asarray(a.topk_d), np.asarray(b.topk_d),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(a.topk_i), np.asarray(b.topk_i))
    np.testing.assert_array_equal(np.asarray(a.n_searched),
                                  np.asarray(b.n_searched))
    np.testing.assert_array_equal(np.asarray(a.n_pruned_lb),
                                  np.asarray(b.n_pruned_lb))
    np.testing.assert_array_equal(np.asarray(a.n_pruned_filter),
                                  np.asarray(b.n_pruned_filter))


# ---------------------------------------------------------------------------
# shard_map-safe pieces: probe + fixed-width compact cascade (1-NN forms)
# ---------------------------------------------------------------------------


def _bsf_args(index):
    return (jnp.asarray(index.series), jnp.asarray(index.leaf_start),
            jnp.asarray(index.leaf_size))


def test_probe_best_leaf_skips_empty_leaves(index_small, queries_small):
    q = jnp.asarray(queries_small[:8])
    series, starts, sizes = _bsf_args(index_small)
    ml = index_small.max_leaf_size
    lb = bounds.lower_bounds(index_small, q)
    want = engine.probe_best_leaf(series, starts, sizes, lb, q, ml)
    # append an empty (shard-padding) leaf advertising an unbeatable lb of 0,
    # exactly what the pre-fix distributed body produced: the probe must
    # tie-break away from it instead of returning +inf
    starts2 = jnp.concatenate([starts, jnp.zeros((1,), starts.dtype)])
    sizes2 = jnp.concatenate([sizes, jnp.zeros((1,), sizes.dtype)])
    lb2 = jnp.concatenate([lb, jnp.zeros((q.shape[0], 1))], axis=1)
    got = engine.probe_best_leaf(series, starts2, sizes2, lb2, q, ml)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cap", [None, 1, 4])
def test_compact_bsf_cascade_matches_masked_scan(index_small, queries_small,
                                                 cap):
    """Fixed-width compaction == masked scan, bitwise, at any capacity
    (cap=1 forces the overflow→scan fallback for nearly every query)."""
    q = jnp.asarray(queries_small)
    series, starts, sizes = _bsf_args(index_small)
    ml = index_small.max_leaf_size
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb)
    bsf0 = engine.probe_best_leaf(series, starts, sizes, d_lb, q, ml)
    a = engine.masked_bsf_scan(series, starts, sizes, d_lb, d_F, q, ml, bsf0)
    b = engine.compact_bsf_cascade(series, starts, sizes, d_lb, d_F, q, ml,
                                   bsf0, max_survivors=cap)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_compact_bsf_cascade_all_survive(index_small, queries_small):
    """Zero lower bounds + no filters: every leaf survives; small caps must
    overflow into the exact scan fallback, a full cap must not overflow."""
    q = jnp.asarray(queries_small[:8])
    series, starts, sizes = _bsf_args(index_small)
    ml = index_small.max_leaf_size
    L = index_small.n_leaves
    d_lb = jnp.zeros((q.shape[0], L), jnp.float32)
    d_F = jnp.full(d_lb.shape, -jnp.inf)
    bsf0 = engine.probe_best_leaf(series, starts, sizes, d_lb, q, ml)
    a = engine.masked_bsf_scan(series, starts, sizes, d_lb, d_F, q, ml, bsf0)
    for cap in (engine.default_max_survivors(L), L):
        b = engine.compact_bsf_cascade(series, starts, sizes, d_lb, d_F, q,
                                       ml, bsf0, max_survivors=cap)
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
        assert (np.asarray(b[1]) == L).all()


def test_compact_bsf_cascade_padding_leaves(index_small, queries_small):
    """Shard-padding leaf slots (size 0) with adversarial raw lb 0: both
    1-NN forms must prune them; an all-padding shard returns the seed."""
    q = jnp.asarray(queries_small[:8])
    series, starts, sizes = _bsf_args(index_small)
    ml = index_small.max_leaf_size
    extra = 5
    starts2 = jnp.concatenate([starts, jnp.zeros((extra,), starts.dtype)])
    sizes2 = jnp.concatenate([sizes, jnp.zeros((extra,), sizes.dtype)])
    d_lb = bounds.lower_bounds(index_small, q)
    d_lb2 = jnp.concatenate(
        [d_lb, jnp.zeros((q.shape[0], extra))], axis=1)
    d_F2 = _synthetic_predictions(d_lb2)
    bsf0 = engine.probe_best_leaf(series, starts2, sizes2, d_lb2, q, ml)
    assert np.isfinite(np.asarray(bsf0)).all()
    a = engine.masked_bsf_scan(series, starts2, sizes2, d_lb2, d_F2, q, ml,
                               bsf0)
    b = engine.compact_bsf_cascade(series, starts2, sizes2, d_lb2, d_F2, q,
                                   ml, bsf0)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))

    # all-padding: nothing to scan, bsf stays at the (+inf) seed, n_s == 0
    allpad = jnp.zeros_like(sizes2)
    bsf0p = engine.probe_best_leaf(series, starts2, allpad, d_lb2, q, ml)
    c = engine.compact_bsf_cascade(series, starts2, allpad, d_lb2, d_F2, q,
                                   ml, bsf0p)
    d = engine.masked_bsf_scan(series, starts2, allpad, d_lb2, d_F2, q, ml,
                               bsf0p)
    np.testing.assert_array_equal(np.asarray(c[0]), np.asarray(d[0]))
    assert (np.asarray(c[1]) == 0).all() and (np.asarray(d[1]) == 0).all()


def test_pairwise_impl_all_leaves_survive(index_small, queries_small):
    """Adversarial empty-pruning case on the union path: the shared slab is
    the whole index; results must still match scan."""
    q = jnp.asarray(queries_small[:8])
    d_lb = jnp.zeros((q.shape[0], index_small.n_leaves), jnp.float32)
    d_F = jnp.full(d_lb.shape, -jnp.inf)
    a = _run(index_small, q, d_lb, d_F, 3, "scan")
    b = engine.run_cascade(
        jnp.asarray(index_small.series), jnp.asarray(index_small.leaf_start),
        jnp.asarray(index_small.leaf_size), q, d_lb, d_F,
        k=3, max_leaf=index_small.max_leaf_size, strategy="compact",
        dist_impl="pairwise")
    np.testing.assert_allclose(np.asarray(a.topk_d), np.asarray(b.topk_d),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(a.topk_i), np.asarray(b.topk_i))
    assert (np.asarray(b.n_searched) == index_small.n_leaves).all()


# ---------------------------------------------------------------------------
# cascade trace (repro.obs): trace=True must be result-invisible and must
# account for every leaf slot, per query, on every strategy
# ---------------------------------------------------------------------------


def _assert_trace_accounts(trace, n_leaves):
    assert trace is not None
    res = obs.accounting_residual(trace, n_leaves)
    np.testing.assert_array_equal(np.asarray(res),
                                  np.zeros(res.shape, np.int64))
    for field in trace:
        assert (np.asarray(field) >= 0).all()


@pytest.mark.parametrize("strategy", ["scan", "compact"])
def test_trace_is_bitwise_invisible(index_small, queries_small, strategy):
    """Both backbones (fixture) x both strategies: the traced program must
    return bitwise-identical results and counters to the untraced one, and
    its per-query attribution must partition the leaf set exactly:
    pruned_box + pruned_seed + pruned_filter == L - survivors - probed."""
    q = jnp.asarray(queries_small)
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb)
    for k in (1, 5):
        a = _run(index_small, q, d_lb, d_F, k, strategy)
        b = engine.run_cascade(
            jnp.asarray(index_small.series),
            jnp.asarray(index_small.leaf_start),
            jnp.asarray(index_small.leaf_size), q, d_lb, d_F,
            k=k, max_leaf=index_small.max_leaf_size, strategy=strategy,
            trace=True)
        _assert_bitwise(a, b)
        assert a.trace is None
        _assert_trace_accounts(b.trace, index_small.n_leaves)
        # an active filter cascade must be visible in the attribution
        assert np.asarray(b.trace.pruned_filter).sum() > 0


def test_trace_through_search_batched(index_small, queries_small):
    """Public API: search_batched(trace=True) materializes the numpy dict
    and stays bitwise-identical to the untraced call."""
    a = search.search_batched(index_small, queries_small, k=3,
                              use_filters=False, strategy="compact")
    b = search.search_batched(index_small, queries_small, k=3,
                              use_filters=False, strategy="compact",
                              trace=True)
    np.testing.assert_array_equal(a.dists, b.dists)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.searched, b.searched)
    assert a.trace is None and isinstance(b.trace, dict)
    total = (b.trace["pruned_box"] + b.trace["pruned_seed"]
             + b.trace["pruned_filter"] + b.trace["survivors"]
             + b.trace["probed"])
    np.testing.assert_array_equal(
        total, np.full(len(queries_small), index_small.n_leaves))


@pytest.mark.parametrize("cap", [None, 1])
def test_compact_bsf_cascade_trace_parity(index_small, queries_small, cap):
    """The 1-NN fixed-width form: traced == untraced bitwise at any
    capacity; cap=1 forces the overflow->scan fallback, which the trace
    must flag while keeping the leaf accounting exact."""
    q = jnp.asarray(queries_small)
    series, starts, sizes = _bsf_args(index_small)
    ml = index_small.max_leaf_size
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb)
    bsf0 = engine.probe_best_leaf(series, starts, sizes, d_lb, q, ml)
    a = engine.compact_bsf_cascade(series, starts, sizes, d_lb, d_F, q, ml,
                                   bsf0, max_survivors=cap)
    b = engine.compact_bsf_cascade(series, starts, sizes, d_lb, d_F, q, ml,
                                   bsf0, max_survivors=cap, trace=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    _assert_trace_accounts(b[2], index_small.n_leaves)
    if cap == 1:
        assert np.asarray(b[2].overflow).sum() > 0


def test_masked_bsf_scan_trace_parity(index_small, queries_small):
    q = jnp.asarray(queries_small)
    series, starts, sizes = _bsf_args(index_small)
    ml = index_small.max_leaf_size
    d_lb = bounds.lower_bounds(index_small, q)
    d_F = _synthetic_predictions(d_lb)
    bsf0 = engine.probe_best_leaf(series, starts, sizes, d_lb, q, ml)
    a = engine.masked_bsf_scan(series, starts, sizes, d_lb, d_F, q, ml, bsf0)
    b = engine.masked_bsf_scan(series, starts, sizes, d_lb, d_F, q, ml, bsf0,
                               trace=True)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    n_box, n_seed, n_pf, n_rows = b[2]
    total = (np.asarray(n_box) + np.asarray(n_seed) + np.asarray(n_pf)
             + np.asarray(a[1]))
    np.testing.assert_array_equal(
        total, np.full(q.shape[0], index_small.n_leaves))
