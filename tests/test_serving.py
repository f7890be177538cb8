"""Serving runtime: batcher policy determinism, per-query-target parity,
per-group recall on calibration queries, checkpoint cold start, and
survivor-capacity auto-tuning.

Parity caveat: the vectorized (Q, F)-offset path and the grouped-sub-batch
fallback compile as *different XLA programs* over the same per-query
arithmetic, so prune decisions tied within an ulp of the bsf may fuse
differently — the pins below use float tolerance plus a small searched-count
slack, not bitwise equality (cf. tests/test_distributed.py).
"""
import jax
import numpy as np
import pytest

from repro.core import build, conformal, engine, filter_training, search
from repro.core.summaries import znormalize
from repro.serving import (MicroBatcher, ServingSession, Telemetry,
                           latency_percentiles, load_index, poisson_trace,
                           run_trace, save_index)


@pytest.fixture(scope="module", params=["dstree", "isax"])
def lfi(request, randwalk_small):
    cfg = build.LeaFiConfig(backbone=request.param, leaf_capacity=64,
                            n_global=200, n_local=50,
                            t_filter_over_t_series=10.0,
                            train=filter_training.TrainConfig(epochs=30))
    return build.build_leafi(randwalk_small[:2500], cfg)


@pytest.fixture(scope="module")
def mixed_queries(randwalk_small):
    rng = np.random.default_rng(11)
    q = znormalize(randwalk_small[rng.integers(0, 2500, 48)]
                   + 0.2 * rng.standard_normal((48, 96)).astype(np.float32))
    targets = np.asarray([0.7, 0.85, 0.95])[rng.integers(0, 3, 48)]
    return q, targets


# ---------------------------------------------------------------------------
# per-query quality targets: vectorized (Q, F) offsets vs grouped fallback
# ---------------------------------------------------------------------------


def _search_kw(lfi):
    return dict(filter_params=lfi.filter_params, leaf_ids=lfi.leaf_ids,
                tuner=lfi.tuner)


@pytest.mark.parametrize("strategy", ["scan", "compact"])
def test_per_query_offsets_match_grouped(lfi, mixed_queries, strategy):
    q, targets = mixed_queries
    vec = search.search_batched(lfi.index, q, k=5, quality_target=targets,
                                strategy=strategy, **_search_kw(lfi))
    grp = search.search_batched_grouped(lfi.index, q, targets, k=5,
                                        strategy=strategy, **_search_kw(lfi))
    np.testing.assert_allclose(vec.dists, grp.dists, rtol=1e-5, atol=1e-6)
    # ulp-tied prune decisions may differ across programs: tiny slack only
    assert np.abs(vec.searched - grp.searched).max() <= 2
    neq = vec.ids != grp.ids
    assert neq.mean() <= 0.02, f"{neq.sum()} id mismatches beyond ties"


def test_uniform_target_array_matches_scalar(lfi, mixed_queries):
    """A constant target array is the scalar path, batched."""
    q, _ = mixed_queries
    arr = search.search_batched(lfi.index, q, quality_target=np.full(48, 0.9),
                                **_search_kw(lfi))
    sca = search.search_batched(lfi.index, q, quality_target=0.9,
                                **_search_kw(lfi))
    np.testing.assert_allclose(arr.dists, sca.dists, rtol=1e-5, atol=1e-6)
    assert np.abs(arr.searched - sca.searched).max() <= 2


def test_target_array_length_mismatch_raises(lfi, mixed_queries):
    q, _ = mixed_queries
    with pytest.raises(ValueError, match="per-query quality_target"):
        search.search_batched(lfi.index, q, quality_target=np.full(7, 0.9),
                              **_search_kw(lfi))
    with pytest.raises(ValueError, match="scalar or a \\(Q,\\)"):
        search.search_batched(lfi.index, q,
                              quality_target=np.full((len(q), 1), 0.9),
                              **_search_kw(lfi))


def _calibration_split(lfi):
    """The global queries build_leafi calibrated on, and mixed targets."""
    cfg = lfi.config
    key = jax.random.PRNGKey(cfg.seed)
    kdata, _ = jax.random.split(key)
    kg, _ = jax.random.split(kdata)
    gq = filter_training.make_noisy_queries(
        np.asarray(lfi.index.series[:lfi.index.n_series]),
        cfg.n_global, kg, 0.1, 0.4)
    n_cal = max(int(cfg.n_global * cfg.calib_fraction), 8)
    rng = np.random.default_rng(3)
    targets = np.asarray([0.7, 0.85, 0.95])[rng.integers(0, 3, n_cal)]
    return gq[-n_cal:], targets


def _assert_recall_meets_targets(res, exact, targets):
    """Each target group's recall@1 meets its target, up to the one-query
    quantization of a small group (1/n)."""
    hit = np.asarray(conformal.recall_at_1(res.dists[:, 0],
                                           exact.dists[:, 0])) > 0
    for t in np.unique(targets):
        sel = targets == t
        recall = hit[sel].mean()
        assert recall >= t - 1.0 / sel.sum() - 1e-9, \
            f"target {t}: recall {recall:.3f} over {sel.sum()} queries"


def test_per_group_recall_meets_targets_on_calibration_queries(lfi):
    """Mixed targets on the build's own calibration split: each group's
    achieved recall must meet its requested target."""
    calib, targets = _calibration_split(lfi)
    _assert_recall_meets_targets(lfi.search(calib, quality_target=targets),
                                 lfi.search_exact(calib), targets)


@pytest.fixture(scope="module")
def lfi_isax16(randwalk_small):
    """An iSAX trie of word length 16 (iSAX2+ / MESSI's), whose leaves run
    from one row to the capacity."""
    cfg = build.LeaFiConfig(backbone="isax", word_len=16, leaf_capacity=64,
                            n_global=200, n_local=50,
                            t_filter_over_t_series=10.0,
                            train=filter_training.TrainConfig(epochs=30))
    return build.build_leafi(randwalk_small[:2500], cfg)


def test_isax16_scan_session_exact_and_recall(lfi_isax16, mixed_queries):
    """The scan engine reads the trie's leaves by size class: its exact
    answers are brute force's, ids and distances, its approximate ones
    meet each target on the calibration split, and its cascade span
    counts the rows the leaf pass reads."""
    from repro.obs import spans
    idx = lfi_isax16.index
    assert np.asarray(idx.leaf_size).min() == 1 and len(idx.leaf_classes) > 2
    session = ServingSession(lfi_isax16, strategy="scan")
    q, _ = mixed_queries
    with spans.recording() as rec:
        got = session.search_exact(q, k=5)
    S = np.asarray(idx.series[:idx.n_series])
    d = np.sqrt(((q[:, None, :] - S[None]) ** 2).sum(-1))
    rows = np.argsort(d, axis=1, kind="stable")[:, :5]
    np.testing.assert_allclose(got.dists, np.take_along_axis(d, rows, 1),
                               rtol=1e-5)
    np.testing.assert_array_equal(got.ids, np.asarray(idx.order)[rows])
    cascade = [sp for sp in rec.spans() if sp.name == "search.cascade"]
    assert [(sp.args["rows_read"], sp.args["rows_held"])
            for sp in cascade] == [(idx.slab_rows, idx.n_series)]
    assert idx.slab_rows < idx.n_leaves * idx.max_leaf_size / 2

    calib, targets = _calibration_split(lfi_isax16)
    _assert_recall_meets_targets(
        session.search(calib, quality_targets=targets),
        session.search_exact(calib), targets)


# ---------------------------------------------------------------------------
# micro-batcher: bucket/flush policy + determinism under a seeded trace
# ---------------------------------------------------------------------------


def _toy_trace(rate, n=64, seed=5, ks=(1, 5)):
    pool = np.random.default_rng(0).standard_normal((8, 4)).astype(np.float32)
    return poisson_trace(pool, rate=rate, n_requests=n,
                         targets=(0.8, 0.9, 0.99), ks=ks, seed=seed)


def _drive(trace, max_batch, max_wait, service=1e-3):
    batcher = MicroBatcher(max_batch=max_batch, max_wait=max_wait)
    return run_trace(trace, batcher, lambda b: None,
                     service_time=lambda b: service)


def test_batcher_policy_and_completeness():
    trace = _toy_trace(rate=2000.0)       # saturating arrivals
    completions, batch_log = _drive(trace, max_batch=8, max_wait=0.01)
    assert sorted(completions) == [r.rid for r in trace]   # all served once
    arrivals = {r.rid: r.arrival for r in trace}
    ks = {r.rid: r.k for r in trace}
    for b in batch_log:
        assert b["bucket"] in (1, 2, 4, 8) and b["n_valid"] <= b["bucket"]
    # FIFO within each k-group; batches are k-homogeneous by construction
    for k in (1, 5):
        order = [rid for rid in sorted(completions,
                                       key=lambda r: completions[r]["finish"])
                 if ks[rid] == k]
        assert all(arrivals[a] <= arrivals[b] + 1e-12
                   for a, b in zip(order, order[1:]))


def test_batcher_deadline_flush_under_light_load():
    """At low rate every request flushes at its deadline, not max_batch."""
    trace = _toy_trace(rate=10.0, n=16, ks=(1,))
    service = 1e-3
    max_wait = 0.01
    completions, batch_log = _drive(trace, max_batch=8, max_wait=max_wait,
                                    service=service)
    for b in batch_log:
        assert b["n_valid"] < 8           # never a size flush at this rate
    for rid, c in completions.items():
        # a request can join an older request's batch (the deadline is the
        # *oldest* member's), so only the upper bound is per-request
        assert c["latency"] <= max_wait + 2 * service + 1e-9
    # …but each batch's oldest member did wait out the full deadline
    for finish in {c["finish"] for c in completions.values()}:
        members = [c for c in completions.values() if c["finish"] == finish]
        assert max(m["latency"] for m in members) >= max_wait - 1e-9


def test_batcher_trace_replay_is_deterministic():
    trace = _toy_trace(rate=500.0)
    a_c, a_log = _drive(trace, max_batch=8, max_wait=0.005)
    b_c, b_log = _drive(trace, max_batch=8, max_wait=0.005)
    # everything but the measured wall-clock around execute is replayable
    def strip(log):
        return [{k: v for k, v in b.items() if k != "wall"} for b in log]
    assert strip(a_log) == strip(b_log)
    assert {r: c["latency"] for r, c in a_c.items()} == \
        {r: c["latency"] for r, c in b_c.items()}
    # and the trace itself replays identically from its seed
    t2 = _toy_trace(rate=500.0)
    assert [(r.rid, r.arrival, r.k, r.quality_target) for r in t2] == \
        [(r.rid, r.arrival, r.k, r.quality_target) for r in trace]


# ---------------------------------------------------------------------------
# session: cold start round-trip + end-to-end serve loop
# ---------------------------------------------------------------------------


def test_index_checkpoint_roundtrip_search_parity(lfi, mixed_queries,
                                                  tmp_path):
    q, targets = mixed_queries
    path = str(tmp_path / "leafi_idx")
    save_index(path, lfi)
    lfi2 = load_index(path)
    assert lfi2.index.kind == lfi.index.kind
    assert lfi2.config.backbone == lfi.config.backbone
    a = lfi.search(q, k=3, quality_target=targets)
    b = lfi2.search(q, k=3, quality_target=targets)
    # identical arrays through identical programs: exact equality
    np.testing.assert_array_equal(a.dists, b.dists)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.searched, b.searched)


def test_index_checkpoint_roundtrip_keeps_leaf_classes(lfi_isax16,
                                                       mixed_queries,
                                                       tmp_path):
    """The loaded index reads the same size classes, so the scan engine
    answers as it did before the save, bitwise."""
    q, targets = mixed_queries
    path = str(tmp_path / "isax16_idx")
    save_index(path, lfi_isax16)
    lfi2 = load_index(path)
    assert lfi2.index.leaf_classes == lfi_isax16.index.leaf_classes
    assert lfi2.index.slab_rows == lfi_isax16.index.slab_rows
    a = ServingSession(lfi_isax16, strategy="scan").search(
        q, targets, k=3, record=False)
    b = ServingSession(lfi2, strategy="scan").search(
        q, targets, k=3, record=False)
    np.testing.assert_array_equal(a.dists, b.dists)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.searched, b.searched)


def test_serving_session_end_to_end(lfi, mixed_queries):
    q, _ = mixed_queries
    session = ServingSession(lfi, strategy="compact")
    n = session.warmup(max_batch=4, ks=(1,), queries=q)
    assert n == 3 and session.warmup(max_batch=4, ks=(1,)) == 0  # cached
    trace = poisson_trace(q, rate=400.0, n_requests=24,
                          targets=(0.8, 0.95), ks=(1,), seed=2)
    exact = session.search_exact(np.stack([r.query for r in trace]))
    oracle = {r.rid: float(exact.dists[i, 0]) for i, r in enumerate(trace)}
    report = session.serve(
        trace, batcher=MicroBatcher(max_batch=4, max_wait=0.002),
        recall_oracle=oracle)
    assert report["n_requests"] == 24
    assert report["throughput_qps"] > 0
    assert np.isfinite(report["p99"]) and report["p50"] <= report["p99"]
    groups = report["recall_by_target"]
    assert set(groups) <= {0.8, 0.95}
    assert sum(g["n"] for g in groups.values()) == 24
    for g in groups.values():
        assert 0.0 <= g["recall"] <= 1.0


# ---------------------------------------------------------------------------
# telemetry + survivor-capacity auto-tuning
# ---------------------------------------------------------------------------


def test_latency_percentiles_helper():
    p = latency_percentiles(np.arange(1, 101))
    assert p["p50"] == pytest.approx(50.5)
    assert p["p99"] == pytest.approx(99.01)
    assert np.isnan(latency_percentiles([])["p95"])


def test_tuned_max_survivors_bounds_overflow():
    """Percentile-chosen capacity keeps the overflow-fallback frequency
    bounded on fresh traffic from the same workload (the regression the
    static P/8 default cannot promise)."""
    L = 1024
    rng = np.random.default_rng(0)
    calib = rng.lognormal(mean=3.0, sigma=0.8, size=4000).astype(int) + 1
    cap = engine.tuned_max_survivors(calib, L, pct=99.0)
    assert 1 <= cap <= 2 * L and cap & (cap - 1) == 0      # pow2, clamped
    fresh = rng.lognormal(mean=3.0, sigma=0.8, size=4000).astype(int) + 1
    assert (fresh > cap).mean() <= 0.02                    # ~1% by design
    # degenerate inputs fall back to the static default
    assert engine.tuned_max_survivors([], L) == \
        engine.default_max_survivors(L)
    # huge observed counts clamp at the leaf-slot ceiling
    assert engine.tuned_max_survivors([10 * L], L) <= \
        engine.tuned_max_survivors([L], L)


def test_telemetry_feeds_capacity_and_counters(lfi, mixed_queries):
    q, targets = mixed_queries
    session = ServingSession(lfi, strategy="compact")
    res = session.search(q, quality_targets=targets, k=1)
    tel = session.telemetry
    assert tel.n_requests == len(q)
    assert 0.0 <= tel.pruning_ratio() <= 1.0
    cap = tel.suggest_max_survivors()
    assert cap >= 1 and cap & (cap - 1) == 0
    # capacity covers ≥99% of the observed survivor counts
    surv = np.asarray(res.computed)
    assert (surv > cap).mean() <= 0.01 + 1.0 / len(surv)


def test_suggest_max_survivors_cold_start_floors_at_default():
    """A handful of easy early queries must not lock in an unstable low
    capacity: below ~100/(100−pct) observations the suggestion is floored
    at the engine's static default (regression for the cold-start bug
    where 3 lucky queries suggested capacity 4 on a 1024-leaf index)."""
    L = 1024
    tel = Telemetry()
    tel.n_leaves = L
    tel.survivors.extend([1, 2, 3])                  # cold window
    assert tel.suggest_max_survivors() >= engine.default_max_survivors(L)
    # with a full window the percentile speaks for itself again, even when
    # it sits *below* the static default
    tel2 = Telemetry()
    tel2.n_leaves = L
    tel2.survivors.extend([4] * 400)
    assert tel2.suggest_max_survivors() == \
        engine.tuned_max_survivors(np.full(400, 4), L)
    assert tel2.suggest_max_survivors() < engine.default_max_survivors(L)


# ---------------------------------------------------------------------------
# bsf warm-starting: prune-only bound semantics + the rolling cache
# ---------------------------------------------------------------------------


def test_bsf_ub_exact_mode_is_bitwise_and_prunes_no_worse(lfi, mixed_queries):
    """Exact mode (no filters): a valid prune-only upper bound never changes
    the answer — bitwise — and never scans more leaves in aggregate."""
    q, _ = mixed_queries
    for strategy in ("scan", "compact"):
        base = search.search_batched(lfi.index, q, k=3, strategy=strategy)
        ub = base.dists[:, -1] * (1 + 1e-6) + 1e-6       # ≥ true 3rd-NN dist
        seeded = search.search_batched(lfi.index, q, k=3, strategy=strategy,
                                       bsf_ub=ub)
        np.testing.assert_array_equal(seeded.dists, base.dists, strategy)
        np.testing.assert_array_equal(seeded.ids, base.ids, strategy)
        assert seeded.searched.sum() <= base.searched.sum(), strategy
        assert (seeded.computed <= base.computed).all() if strategy == \
            "compact" else True


def test_bsf_ub_filtered_mode_keeps_recall(lfi, mixed_queries):
    """With filters the seeded cascade is not bitwise (the tighter lb prune
    changes the bsf trajectory and with it the filter decisions), but the
    bound only ever enters the *lb* test — a leaf with lb > ub ≥ d_true
    holds no true NN — while the learned-filter test keeps its witnessed-bsf
    threshold, so conformal recall semantics are preserved."""
    q, targets = mixed_queries
    exact = search.search_batched(lfi.index, q, k=1)
    ub = exact.dists[:, 0] * (1 + 1e-6) + 1e-6
    base = search.search_batched(lfi.index, q, k=1, quality_target=targets,
                                 **_search_kw(lfi))
    seeded = search.search_batched(lfi.index, q, k=1, quality_target=targets,
                                   bsf_ub=ub, **_search_kw(lfi))
    hit_base = conformal.recall_at_1(base.dists[:, 0], exact.dists[:, 0])
    hit_seed = conformal.recall_at_1(seeded.dists[:, 0], exact.dists[:, 0])
    assert np.mean(hit_seed) >= np.mean(hit_base) - 0.05
    assert seeded.searched.sum() <= base.searched.sum()
    # seeded distances are still witnessed: never below the exact answer
    assert (seeded.dists[:, 0] >= exact.dists[:, 0] - 1e-4).all()


def test_bsf_cache_bounds_are_valid_and_staged_commits_lag():
    from repro.serving import BsfCache

    rng = np.random.default_rng(3)
    base = rng.standard_normal((32, 16)).astype(np.float32)
    dists = rng.uniform(1.0, 2.0, 32).astype(np.float32)
    cache = BsfCache(capacity=16)
    assert cache.seed(base, 1) is None                   # cold
    cache.update(base, dists, k=1)
    assert len(cache) == 16                              # ring capacity
    near = base[-16:] + 0.01 * rng.standard_normal((16, 16)).astype(
        np.float32)
    ub = cache.seed(near, 1)
    # triangle inequality: ub ≥ cached dist − drift, and finite
    assert np.isfinite(ub).all()
    assert (ub >= dists[-16:] - 0.2).all()
    assert cache.seed(near, 5) is None                   # per-k rings
    # staging: nothing lands until commit_through reaches the seq
    cache2 = BsfCache()
    cache2.stage(0, base[:4], dists[:4], k=1)
    cache2.stage(1, base[4:8], dists[4:8], k=1)
    assert cache2.seed(base, 1) is None
    cache2.commit_through(0)
    assert len(cache2) == 4
    cache2.commit_through(5)
    assert len(cache2) == 8
    # nonfinite kth distances (padded/failed rows) are skipped
    cache3 = BsfCache()
    cache3.update(base[:4], np.array([1.0, np.inf, np.nan, 2.0]), k=1)
    assert len(cache3) == 2
    cache3.reset()
    assert len(cache3) == 0 and cache3.seed(base, 1) is None


# ---------------------------------------------------------------------------
# pipelined serving: overlapped dispatch vs the serial loop (bitwise)
# ---------------------------------------------------------------------------


def _serve_mode(lfi, trace, *, pipeline, warm):
    session = ServingSession(lfi, strategy="compact", warm_start=warm)
    session.warmup(max_batch=8, ks=(1,),
                   queries=np.stack([r.query for r in trace[:8]]))
    report = session.serve(
        trace, batcher=MicroBatcher(max_batch=8, max_wait=0.004),
        service_time=lambda b: 1e-3 * max(b.bucket / 8, 0.25),
        pipeline=pipeline)
    return session, report


@pytest.mark.parametrize("warm", [False, True])
def test_pipelined_serve_matches_serial_bitwise(lfi, mixed_queries, warm):
    """The tentpole determinism pin: pipelined serving (overlapped dispatch,
    1 batch in flight) produces the identical batch sequence, completion
    times, and bitwise-identical per-request results as the serial loop —
    including with cross-batch bsf warm-starting (the staged-commit rule
    makes both modes observe identical cache states)."""
    q, _ = mixed_queries
    trace = poisson_trace(q, rate=900.0, n_requests=64,
                          targets=(0.8, 0.95), ks=(1,), seed=9)
    s0, r0 = _serve_mode(lfi, trace, pipeline=0, warm=warm)
    s1, r1 = _serve_mode(lfi, trace, pipeline=1, warm=warm)
    host_keys = ("wall", "dispatch_s", "harvest_s", "t_disp", "t_done")

    def strip(log):
        return [{k: v for k, v in b.items() if k not in host_keys}
                for b in log]
    assert strip(r0["batches"]) == strip(r1["batches"])
    for rid in r0["completions"]:
        c0, c1 = r0["completions"][rid], r1["completions"][rid]
        assert c0["latency"] == c1["latency"], rid
        assert c0["result"] == c1["result"], rid      # bitwise (==, no tol)
    # pipelined logs carry the overlap accounting
    assert all(b["harvest_s"] is not None for b in r1["batches"])
    assert all(b["t_done"] >= b["t_disp"] for b in r1["batches"])


def test_warm_start_serving_preserves_recall(lfi, mixed_queries):
    q, _ = mixed_queries
    trace = poisson_trace(q, rate=900.0, n_requests=48, targets=(0.95,),
                          ks=(1,), seed=4)
    cold = ServingSession(lfi, strategy="compact", warm_start=False)
    warm = ServingSession(lfi, strategy="compact", warm_start=True)
    exact = cold.search_exact(np.stack([r.query for r in trace]))
    oracle = {r.rid: float(exact.dists[i, 0]) for i, r in enumerate(trace)}
    reps = {}
    for name, s in (("cold", cold), ("warm", warm)):
        s.warmup(max_batch=8, ks=(1,), queries=q)
        reps[name] = s.serve(
            trace, batcher=MicroBatcher(max_batch=8, max_wait=0.004),
            recall_oracle=oracle,
            service_time=lambda b: 1e-3)
    rc = reps["cold"]["recall_by_target"][0.95]["recall"]
    rw = reps["warm"]["recall_by_target"][0.95]["recall"]
    assert rw >= rc - 0.05
    # warm bounds are prune-only: distances never undercut the oracle
    for rid, c in reps["warm"]["completions"].items():
        assert c["result"]["dist"] >= oracle[rid] - 1e-4


def test_phase_telemetry_lands_in_summary(lfi, mixed_queries):
    q, _ = mixed_queries
    assert "phases" not in Telemetry().summary()         # empty: no key
    trace = poisson_trace(q, rate=900.0, n_requests=24, targets=(0.9,),
                          ks=(1,), seed=6)
    session, _ = _serve_mode(lfi, trace, pipeline=1, warm=True)
    summ = session.telemetry.summary()
    phases = summ["phases"]
    assert set(phases) == {"queue_wait", "form", "execute"}
    for ph in phases.values():
        assert np.isfinite(ph["p50"]) and ph["p50"] <= ph["p99"]
    # queue waits are per-request (virtual clock), phases per batch
    assert len(session.telemetry.queue_wait) == 24
    assert len(session.telemetry.form_s) == len(session.telemetry.exec_s)


def test_run_trace_pipelined_requires_service_model():
    from repro.serving import run_trace_pipelined
    trace = _toy_trace(rate=500.0, n=8, ks=(1,))
    with pytest.raises(ValueError, match="service_time"):
        run_trace_pipelined(trace, MicroBatcher(), lambda b: b,
                            lambda h: None, service_time=None)
    with pytest.raises(ValueError, match="max_in_flight"):
        run_trace_pipelined(trace, MicroBatcher(), lambda b: b,
                            lambda h: None, service_time=lambda b: 1e-3,
                            max_in_flight=0)
