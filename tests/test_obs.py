"""Observability subsystem (`repro.obs`): registry instruments, span
recording, Chrome trace export, the recall-drift hook, and the serve-level
trace-determinism pin.

The determinism contract under test: with an injected ``service_time``,
every registry instrument not declared ``wall=True`` and every non-``ts``/
``dur`` field of the exported Chrome trace is bitwise-reproducible across
two seeded serving runs — wall-clock may appear *only* in the snapshot's
``"wall"`` subtree and in the trace's ``ts``/``dur`` fields.
"""
from __future__ import annotations

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro import obs
from repro.core import bounds, build, engine, filter_training, tree
from repro.data.series import make_query_set
from repro.launch.serve import _print_serve_report
from repro.obs import audit as obs_audit
from repro.obs import explain as obs_explain
from repro.obs import export
from repro.obs.health import LeafHealthBoard
from repro.obs.metrics import MetricsRegistry, RecallDriftMonitor
from repro.obs.spans import SpanRecorder
from repro.serving import (BsfCache, MicroBatcher, Request,
                           ServingSession, Telemetry, poisson_trace)
from repro.serving.shadow import explain_query, leaf_of_ids, sample_mask


# ---------------------------------------------------------------------------
# metrics registry: counters / gauges / histograms
# ---------------------------------------------------------------------------

def test_counter_labels_and_monotonicity():
    r = MetricsRegistry()
    c = r.counter("reqs", help="requests")
    c.inc()
    c.inc(2.0)
    c.inc(5, target="0.9")
    assert c.value() == 3.0
    assert c.value(target="0.9") == 5.0
    assert c.value(target="0.99") == 0.0
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)


def test_gauge_last_write_wins():
    r = MetricsRegistry()
    g = r.gauge("depth")
    assert g.value(default=-1.0) == -1.0
    g.set(3)
    g.set(7, lane="a")
    g.set(4)
    assert g.value() == 4.0
    assert g.value(lane="a") == 7.0


def test_histogram_lifetime_vs_window():
    r = MetricsRegistry()
    h = r.histogram("lat", window=4)
    h.extend([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    assert h.count() == 6                       # lifetime survives overflow
    assert h.window_values() == [3.0, 4.0, 5.0, 6.0]
    p = h.percentiles((50,))
    assert p["p50"] == pytest.approx(4.5)
    h.reset_window()
    assert h.window_values() == []
    assert h.count() == 6                       # lifetime survives the flush
    assert np.isnan(h.percentiles((50,))["p50"])   # empty window: NaN, no raise


def test_registry_idempotent_creation_and_kind_mismatch():
    r = MetricsRegistry()
    a = r.counter("x")
    assert r.counter("x") is a                  # second creation: same object
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x")


def test_snapshot_segregates_wall_instruments():
    r = MetricsRegistry()
    r.counter("n").inc(3)
    r.histogram("virt", window=8).observe(1.0)
    r.histogram("wallclock_s", window=8, wall=True).observe(0.125)
    snap = r.snapshot()
    assert snap["counters"]["n"] == 3.0
    assert snap["histograms"]["virt"]["count"] == 1
    assert "wallclock_s" not in snap["histograms"]
    assert snap["wall"]["histograms"]["wallclock_s"]["count"] == 1
    json.dumps(snap)                            # snapshot is JSON-clean


def test_delta_reports_counter_movement():
    r = MetricsRegistry()
    c = r.counter("n")
    c.inc(2)
    prev = r.snapshot()
    c.inc(3, target="0.9")
    d = r.delta(prev)
    assert d == {'n{target=0.9}': 3.0}


def test_jsonl_and_prometheus_export(tmp_path):
    r = MetricsRegistry()
    r.counter("serve_requests_total").inc(5)
    r.histogram("serve_latency_s", window=8).extend([0.1, 0.2, 0.3])
    r.histogram("empty_h", window=8)            # registered, never observed
    jl = tmp_path / "m.jsonl"
    export.write_metrics(jl, r)
    rows = [json.loads(line) for line in jl.read_text().splitlines()]
    by_name = {row["name"]: row for row in rows}
    assert by_name["serve_requests_total"]["value"] == 5.0
    assert by_name["serve_latency_s"]["count"] == 3
    assert "empty_h" not in by_name             # no series yet → no row
    prom = tmp_path / "m.prom"
    export.write_metrics(prom, r)
    text = prom.read_text()
    assert "# TYPE serve_requests_total counter" in text
    assert "serve_requests_total 5.0" in text
    assert 'serve_latency_s{quantile="0.5"}' in text
    assert "serve_latency_s_count 3" in text


def test_prometheus_escapes_pathological_label_values(tmp_path):
    """Prometheus 0.0.4 label-value escaping: backslash, quote and newline
    must come out as \\\\, \\" and \\n — a raw newline would split the
    exposition line and corrupt the whole scrape."""
    r = MetricsRegistry()
    evil = 'a\\b"c\nd'
    r.counter("evil_total").inc(1, path=evil)
    prom = tmp_path / "m.prom"
    export.write_metrics(prom, r)
    text = prom.read_text()
    assert 'evil_total{path="a\\\\b\\"c\\nd"} 1.0' in text
    # the value never splits its exposition line
    metric_lines = [ln for ln in text.splitlines()
                    if ln.startswith("evil_total{")]
    assert len(metric_lines) == 1
    assert metric_lines[0].endswith(" 1.0")


# ---------------------------------------------------------------------------
# recall-drift monitor (ROADMAP item 1's recalibration hook)
# ---------------------------------------------------------------------------

def test_recall_drift_flag_needs_min_samples_then_fires_and_clears():
    r = MetricsRegistry()
    mon = RecallDriftMonitor(r, window=16, min_samples=8)
    for _ in range(7):
        mon.observe(0.95, False)
    assert mon.drifting() == {0.95: False}      # below min_samples: no flag
    mon.observe(0.95, False)
    assert mon.drifting() == {0.95: True}
    assert mon.any_drifting()
    assert r.gauge("serve_recall_drift").value(target="0.95") == 1.0
    assert r.gauge("serve_recall_windowed").value(target="0.95") == 0.0
    for _ in range(16):                         # window fills with hits
        mon.observe(0.95, True)
    assert mon.drifting() == {0.95: False}
    assert r.gauge("serve_recall_drift").value(target="0.95") == 0.0
    assert mon.windowed_recall()[0.95] == 1.0


def test_telemetry_surfaces_drift_in_summary():
    tel = Telemetry(drift_window=16, drift_min_samples=4)
    for _ in range(6):
        tel.observe_recall(0.9, False)
    assert tel.recall_drifting() == {0.9: True}
    s = tel.summary()
    assert s["recall_drifting"] == {0.9: True}
    assert s["recall_windowed"][0.9] == 0.0
    assert s["recall_by_target"][0.9]["n"] == 6


# ---------------------------------------------------------------------------
# telemetry facade: registry-backed, NaN-safe when empty
# ---------------------------------------------------------------------------

def test_fresh_telemetry_is_nan_safe_everywhere():
    tel = Telemetry()
    assert np.isnan(tel.latency_percentiles()["p50"])
    assert np.isnan(tel.pruning_ratio())
    s = tel.summary()
    assert s["n_requests"] == 0 and s["n_batches"] == 0
    assert np.isnan(s["p99"])
    assert "phases" not in s                    # no wall-clock seen yet
    assert "recall_drifting" not in s
    assert not tel.latencies and len(tel.queue_wait) == 0


def test_telemetry_windows_are_registry_instruments():
    tel = Telemetry(window=8)
    tel.record_latency(0.25)
    tel.survivors.extend([2, 3, 4])             # pre-registry deque surface
    tel.record_phases(queue_wait=[0.001, 0.002], form_s=0.01, exec_s=0.02)
    snap = tel.snapshot()
    assert snap["histograms"]["serve_latency_s"]["count"] == 1
    assert snap["histograms"]["serve_survivor_leaves"]["sum"] == 9.0
    assert snap["histograms"]["serve_queue_wait_s"]["count"] == 2
    # host wall-clock phases live under the maskable "wall" subtree only
    assert "serve_form_s" not in snap["histograms"]
    assert snap["wall"]["histograms"]["serve_form_s"]["count"] == 1
    assert snap["wall"]["histograms"]["serve_exec_s"]["count"] == 1
    assert list(tel.survivors) == [2.0, 3.0, 4.0]
    tel.flush_windows()
    assert len(tel.latencies) == 0
    assert tel.snapshot()["histograms"]["serve_latency_s"]["count"] == 1


# ---------------------------------------------------------------------------
# span recording
# ---------------------------------------------------------------------------

def test_recording_captures_nesting_and_restores_previous_recorder():
    before = obs.get_recorder()
    with obs.recording() as rec:
        assert obs.get_recorder() is rec
        with obs.span("outer", cat="t", a=1):
            with obs.span("inner", cat="t"):
                pass
    assert obs.get_recorder() is before
    inner, outer = rec.spans()                  # append order: close order
    assert (inner.name, inner.depth) == ("inner", 1)
    assert (outer.name, outer.depth) == ("outer", 0)
    assert outer.args == {"a": 1}
    assert inner.lane == outer.lane == 0        # dense lanes, not thread ids
    assert outer.dur >= inner.dur >= 0.0
    # sids count entries; the parent is the enclosing span's sid
    assert (outer.sid, outer.parent) == (0, -1)
    assert (inner.sid, inner.parent) == (1, 0)


def test_span_handle_gives_sid_on_entry_and_dur_after_exit():
    rec = SpanRecorder()
    with rec.span("outer") as a:
        assert a.dur is None and (a.sid, a.parent) == (0, -1)
        with rec.span("inner") as b:
            assert b.parent == a.sid
    inner, outer = rec.spans()
    assert (inner.dur, outer.dur) == (b.dur, a.dur)
    assert a.dur >= b.dur >= 0.0


def test_recorder_is_bounded_and_drains():
    rec = SpanRecorder(maxlen=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    got = rec.drain()
    assert [s.name for s in got] == ["s6", "s7", "s8", "s9"]
    assert rec.spans() == []


def test_disabled_recorder_records_nothing():
    rec = SpanRecorder(enabled=False)
    with rec.span("x") as h:
        pass
    assert rec.spans() == []
    assert h.dur is None and h.sid == -1        # and times nothing


def test_span_maps_onto_the_profiler_clock(tmp_path):
    """A span's ``t0``, mapped through the recorder's one clock anchor,
    lands on the start of its own annotation in the profiler's host plane
    (which counts from the session's ``profile_start_time``)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    with rec.span("clock.probe"):
        pass
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    t_start = dict(planes["Task Environment"].stats)["profile_start_time"]
    (ann,) = [e.start_ns for ln in planes["/host:CPU"].lines
              for e in ln.events if e.name == "clock.probe"]
    (s,) = rec.spans()
    assert abs(rec.to_trace_ns(s.t0) - t_start - ann) < 100_000   # 100 us


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------

def _demo_batch_log():
    return [
        # serial run_trace entry: no t_disp → one combined execute slice
        {"bucket": 4, "n_valid": 3, "k": 1, "service": 0.01,
         "rids": [0, 1, 2], "wall": 0.02},
        # pipelined entry: dispatch / in-flight / harvest lanes
        {"bucket": 8, "n_valid": 8, "k": 1, "service": 0.01,
         "rids": list(range(3, 11)), "t_disp": 10.0, "dispatch_s": 0.001,
         "t_done": 10.5, "harvest_s": 0.002},
    ]


def test_chrome_trace_lane_layout():
    with obs.recording() as rec:
        with obs.span("build.train", cat="build", n_filters=3):
            pass
    trace = export.chrome_trace(spans=rec.drain(),
                                batch_log=_demo_batch_log())
    evs = trace["traceEvents"]
    lanes = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert lanes == {"serve/dispatch", "serve/in-flight", "serve/harvest",
                     "spans/lane0"}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    serial = xs["batch[4x k=1]"]
    assert serial["tid"] == 1 and serial["ts"] == 0.0
    assert serial["dur"] == pytest.approx(0.02 * 1e6)
    assert serial["args"]["n_requests"] == 3
    assert xs["dispatch batch[8x k=1]"]["tid"] == 1
    assert xs["in-flight batch[8x k=1]"]["tid"] == 2
    assert xs["harvest batch[8x k=1]"]["tid"] == 3
    span_ev = xs["build.train"]
    assert span_ev["tid"] == 10 and span_ev["args"] == {"n_filters": 3,
                                                        "depth": 0}


def test_mask_wallclock_zeroes_only_ts_dur():
    trace = export.chrome_trace(batch_log=_demo_batch_log())
    masked = export.mask_wallclock(trace)
    for e in masked["traceEvents"]:
        if e["ph"] == "X":
            assert e["ts"] == 0.0 and e["dur"] == 0.0
    # non-wall-clock fields survive untouched; the input is not mutated
    assert ([(e["name"], e.get("args")) for e in masked["traceEvents"]]
            == [(e["name"], e.get("args")) for e in trace["traceEvents"]])
    assert any(e.get("dur", 0.0) > 0.0 for e in trace["traceEvents"])


def test_write_chrome_trace_roundtrips(tmp_path):
    path = tmp_path / "trace.json"
    trace = export.write_chrome_trace(path, batch_log=_demo_batch_log())
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(trace))
    assert loaded["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# cascade-trace host helpers (device-side semantics: tests/test_engine.py)
# ---------------------------------------------------------------------------

def test_cascade_trace_host_helpers():
    z = obs.zero_trace(3)
    assert all(np.asarray(f).shape == (3,) for f in z)
    t = obs.CascadeTrace(*(np.full((3,), i, np.int32)
                           for i in range(len(z._fields))))
    both = obs.combine(t, t)
    assert np.array_equal(np.asarray(both.pruned_filter),
                          np.asarray(t.pruned_filter) * 2)
    sel = obs.select(np.asarray([True, False, True]), t, z)
    assert np.asarray(sel.survivors).tolist() == [4, 0, 4]
    d = obs.to_numpy(t)
    assert set(d) == set(t._fields)
    assert d["distances"].dtype == np.int64
    # residual: n_leaves = Σpruned + survivors + probed ⇒ zero
    n_leaves = int(0 + 1 + 2 + 3 + 4)
    assert np.asarray(obs.accounting_residual(t, n_leaves)).tolist() \
        == [0, 0, 0]


# ---------------------------------------------------------------------------
# serve-level determinism + zero-request regression (needs a built index)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lfi_obs(randwalk_small):
    cfg = build.LeaFiConfig(backbone="dstree", leaf_capacity=64,
                            n_global=120, n_local=24,
                            t_filter_over_t_series=10.0,
                            train=filter_training.TrainConfig(epochs=20))
    return build.build_leafi(randwalk_small[:2000], cfg)


def _serve_once(lfi, trace, oracle):
    tel = Telemetry(drift_window=32, drift_min_samples=8)
    session = ServingSession(lfi, telemetry=tel)
    with obs.recording() as rec:
        report = session.serve(
            trace, batcher=MicroBatcher(max_batch=8, max_wait=0.004),
            recall_oracle=oracle, service_time=lambda b: 0.002)
    chrome = export.mask_wallclock(export.chrome_trace(
        spans=rec.drain(), batch_log=report["batches"]))
    return report, tel.snapshot(), chrome


def test_serve_observability_is_deterministic_modulo_wallclock(
        lfi_obs, queries_small):
    trace = poisson_trace(queries_small, rate=500.0, n_requests=48,
                          targets=(0.9, 0.99), seed=5)
    session = ServingSession(lfi_obs)
    exact = session.search_exact(queries_small)
    oracle = {r.rid: float(np.asarray(exact.dists)[r.pool_row, 0])
              for r in trace}
    rep1, snap1, chrome1 = _serve_once(lfi_obs, trace, oracle)
    rep2, snap2, chrome2 = _serve_once(lfi_obs, trace, oracle)
    assert rep1["n_requests"] == 48

    # wall-clock leaked somewhere it shouldn't ⇒ these dumps differ
    def masked(snap):
        s = dict(snap)
        wall = s.pop("wall")
        return s, wall
    s1, wall1 = masked(snap1)
    s2, _ = masked(snap2)
    assert json.dumps(s1, sort_keys=True) == json.dumps(s2, sort_keys=True)
    assert json.dumps(chrome1, sort_keys=True) \
        == json.dumps(chrome2, sort_keys=True)

    # ... and the run did populate every layer being compared
    assert s1["counters"]["serve_requests_total"] == 48.0
    assert s1["histograms"]["serve_latency_s"]["count"] == 48
    assert wall1["histograms"]["serve_form_s"]["count"] == rep1["n_batches"]
    assert any(k.startswith("serve_recall_windowed") for k in s1["gauges"])
    spans_seen = {e["name"] for e in chrome1["traceEvents"]
                  if e["ph"] == "X"}
    assert "serve.dispatch" in spans_seen and "serve.harvest" in spans_seen


DISPATCH_CHILDREN = ("search.bounds", "search.offsets", "search.filters",
                     "search.cascade")
HARVEST_CHILDREN = ("search.wait", "search.fetch")


def test_served_batch_spans_nest_by_cause(lfi_obs, queries_small):
    session = ServingSession(lfi_obs)
    batcher = MicroBatcher(max_batch=4, max_wait=0.0)
    for i in range(4):
        batcher.submit(Request(rid=i, query=queries_small[i], k=1,
                               quality_target=(0.9, 0.99)[i % 2]))
    (batch,) = batcher.poll(0.0)
    session.execute(batch)                      # compile outside the capture
    with obs.recording() as rec:
        pb = session.dispatch(batch)
        session.harvest(pb)
    got = rec.spans()
    sp = {s.name: s for s in got}
    assert len(sp) == len(got) == 8
    for name, kids in (("serve.dispatch", DISPATCH_CHILDREN),
                       ("serve.harvest", HARVEST_CHILDREN)):
        top = sp[name]
        assert (top.parent, top.depth, top.args["seq"]) == (-1, 0, pb.seq)
        assert [s.name for s in got if s.parent == top.sid] == list(kids)
        for kid in kids:
            assert sp[kid].depth == 1
            assert top.t0 <= sp[kid].t0
            assert sp[kid].t0 + sp[kid].dur <= top.t0 + top.dur
    # the fetch names every device-to-host copy the result makes
    r = pb.pending.raw
    copied = (r.topk_i, r.topk_d, r.n_searched, r.n_pruned_lb,
              r.n_pruned_filter, r.n_computed)
    assert sp["search.fetch"].args == {
        "n_arrays": 6, "bytes": sum(x.nbytes for x in copied)}
    # one timer per phase: the telemetry's phases are the spans' durations
    tel = session.telemetry
    assert list(tel.form_s)[-1] == sp["serve.dispatch"].dur
    assert list(tel.exec_s)[-1] == sp["serve.harvest"].dur


def test_exact_search_records_no_offsets_or_filters(lfi_obs, queries_small):
    session = ServingSession(lfi_obs)
    with obs.recording() as rec:
        session.search_exact(queries_small[:4], k=3)
    got = rec.spans()
    assert [s.name for s in got] == ["search.bounds", "search.cascade",
                                     "search.wait", "search.fetch"]
    assert all(s.parent == -1 and s.depth == 0 for s in got)
    assert got[1].args == {"q": 4, "k": 3, "strategy": "compact"}


@pytest.mark.parametrize("targets", [None, (0.9, 0.99)])
def test_answers_bitwise_equal_with_spans_on_and_off(lfi_obs, queries_small,
                                                     targets):
    session = ServingSession(lfi_obs)
    q = queries_small[:8]
    t = None if targets is None else np.resize(targets, len(q))
    with obs.recording(SpanRecorder(enabled=False)) as off:
        a = session.search(q, t, k=2, record=False)
    with obs.recording() as on:
        b = session.search(q, t, k=2, record=False)
    assert off.spans() == [] and on.spans()
    for f in ("dists", "ids", "searched", "pruned_lb", "pruned_filter",
              "computed"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_zero_request_serve_report_is_nan_safe(lfi_obs, capsys):
    session = ServingSession(lfi_obs)
    report = session.serve([], service_time=lambda b: 0.001)
    assert report["n_requests"] == 0
    assert "throughput_qps" not in report
    assert np.isnan(report["p50"])
    _print_serve_report(report)                 # must not raise (regression)
    out = capsys.readouterr().out
    assert "0 requests" in out and "no completions" in out
    assert session.telemetry.summary()["n_requests"] == 0


# ---------------------------------------------------------------------------
# per-leaf audit: engine-level pins (both backbones x both strategies)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=["dstree", "isax"])
def obs_index(request, randwalk_small):
    builder = (tree.build_dstree if request.param == "dstree"
               else tree.build_isax)
    return builder(randwalk_small, 64)


def _cascade(index, q, d_lb, d_F, k, strategy, **kw):
    return engine.run_cascade(
        jnp.asarray(index.series), jnp.asarray(index.leaf_start),
        jnp.asarray(index.leaf_size), q, d_lb, d_F,
        k=k, max_leaf=index.max_leaf_size, strategy=strategy, **kw)


def _synthetic_predictions(d_lb, seed=0):
    """Deterministic noisy per-leaf NN 'predictions' → real filter pruning
    (same construction tests/test_engine.py prunes with)."""
    lb = np.asarray(d_lb)
    rng = np.random.default_rng(seed)
    noise = rng.standard_normal(lb.shape).astype(np.float32)
    return jnp.asarray(lb * (1.4 + 0.4 * noise) + 2.0)


@pytest.mark.parametrize("strategy", ["scan", "compact"])
def test_audit_results_bitwise_and_per_leaf_identity(
        obs_index, queries_small, strategy):
    """audit=True returns bitwise-identical answers and counters, and the
    per-leaf accounting identity partitions the query batch exactly."""
    q = jnp.asarray(queries_small)
    n_queries = q.shape[0]
    d_lb = bounds.lower_bounds(obs_index, q)
    d_F = _synthetic_predictions(d_lb)
    for k in (1, 5):
        a = _cascade(obs_index, q, d_lb, d_F, k, strategy)
        b = _cascade(obs_index, q, d_lb, d_F, k, strategy, audit=True)
        np.testing.assert_array_equal(np.asarray(a.topk_d),
                                      np.asarray(b.topk_d))
        np.testing.assert_array_equal(np.asarray(a.topk_i),
                                      np.asarray(b.topk_i))
        np.testing.assert_array_equal(np.asarray(a.n_searched),
                                      np.asarray(b.n_searched))
        fa = b.audit
        assert not np.asarray(obs_audit.accounting_residual_leaf(
            fa, n_queries)).any()
        fa_np = obs_audit.to_numpy(fa)
        # the synthetic cascade is active and audited as such
        assert fa_np["pruned_filter"].sum() > 0
        assert fa_np["kept"].sum() > 0
        # residual bookkeeping: histogram mass == observations, violations
        # are a subset, scored >= kept (union co-residents score for free)
        np.testing.assert_array_equal(fa_np["resid_buckets"].sum(-1),
                                      fa_np["resid_count"])
        assert (fa_np["violations"] <= fa_np["resid_count"]).all()
        assert (fa_np["scored"] >= fa_np["kept"]).all()
        assert (fa_np["resid_count"] <= fa_np["scored"]).all()
        # resid_min is +inf exactly where nothing was observed
        unobserved = fa_np["resid_count"] == 0
        assert np.isinf(fa_np["resid_min"][unobserved]).all()
        assert np.isfinite(fa_np["resid_min"][~unobserved]).all()


@pytest.mark.parametrize("strategy", ["scan", "compact"])
def test_trace_attributes_warm_start_seed_prunes(
        obs_index, queries_small, strategy):
    """BsfCache-seeded bsf_ub: answers stay bitwise (exact mode) and the
    accounting identity still partitions the leaf set exactly — on both
    strategies.  The attribution itself is strategy-shaped: the scan visits
    leaves in ascending-lb order, so by the time any leaf has lb > ub every
    leaf holding a true top-k member (lb ≤ d_k ≤ ub) is already scanned and
    the converged bsf dominates any *valid* bound — seed-only prunes are
    impossible there (pinned at exactly zero).  The compact strategy
    attributes at the mask stage against the probe seed bsf0, which a warm
    bound undercuts whenever the probe leaf is not the k-NN leaf — so its
    pruned_seed is live (pinned > 0)."""
    q = jnp.asarray(queries_small)
    L = obs_index.n_leaves
    d_lb = bounds.lower_bounds(obs_index, q)
    d_F = jnp.full(d_lb.shape, -jnp.inf)
    cold = _cascade(obs_index, q, d_lb, d_F, 1, strategy, trace=True)
    # no warm bound → nothing can be seed-attributed
    assert np.asarray(cold.trace.pruned_seed).sum() == 0
    assert not np.asarray(obs.accounting_residual(cold.trace, L)).any()

    cache = BsfCache()
    cache.update(queries_small, np.asarray(cold.topk_d)[:, 0], k=1)
    ub = cache.seed(queries_small, k=1)
    assert ub is not None and np.isfinite(ub).all()
    warm = _cascade(obs_index, q, d_lb, d_F, 1, strategy, trace=True,
                    bsf_ub=jnp.asarray(ub))
    # prune-only contract: bitwise answers, never more leaves searched
    np.testing.assert_array_equal(np.asarray(cold.topk_d),
                                  np.asarray(warm.topk_d))
    np.testing.assert_array_equal(np.asarray(cold.topk_i),
                                  np.asarray(warm.topk_i))
    assert (np.asarray(warm.n_searched)
            <= np.asarray(cold.n_searched)).all()
    seed_prunes = np.asarray(warm.trace.pruned_seed).sum()
    if strategy == "scan":
        assert seed_prunes == 0         # ascending-lb order: see docstring
    else:
        assert seed_prunes > 0          # probe bsf0 undercut by the bound
    assert not np.asarray(obs.accounting_residual(warm.trace, L)).any()
    # per-leaf audit agrees with the per-query trace on the attribution
    audited = _cascade(obs_index, q, d_lb, d_F, 1, strategy, audit=True,
                       bsf_ub=jnp.asarray(ub))
    fa_np = obs_audit.to_numpy(audited.audit)
    assert fa_np["pruned_seed"].sum() == seed_prunes
    assert not np.asarray(obs_audit.accounting_residual_leaf(
        audited.audit, q.shape[0])).any()


# no deadline: every example's leaf layout is a new program to compile,
# and a compile is not what the property is about
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
       backbone=st.sampled_from(["dstree", "isax"]),
       strategy=st.sampled_from(["scan", "compact"]))
def test_accounting_residual_zero_property(seed, backbone, strategy):
    """Property: the trace accounting residual is zero per query and the
    audit identity is zero per leaf, across random leaf layouts, random
    filter planes and random (valid) warm-start bounds, both backbones."""
    rng = np.random.default_rng(seed)
    S = rng.standard_normal((512, 32), dtype=np.float32).cumsum(axis=1)
    cap = int(8 + (seed % 5) * 12)              # leaf layout varies w/ seed
    builder = tree.build_dstree if backbone == "dstree" else tree.build_isax
    index = builder(S, cap)
    queries = make_query_set(S, 4, noise=0.3, seed=seed % 997)
    q = jnp.asarray(queries)
    d_lb = bounds.lower_bounds(index, q)
    no_f = jnp.full(d_lb.shape, -jnp.inf)
    keep = jnp.asarray(rng.random(d_lb.shape) < 0.5)
    d_F = jnp.where(keep, no_f, _synthetic_predictions(d_lb, seed=seed))
    # a valid prune-only bound: the exact nn, inflated
    exact = _cascade(index, q, d_lb, no_f, 1, strategy)
    ub = np.asarray(exact.topk_d)[:, 0] * (1 + 1e-6) + 1e-6
    res = _cascade(index, q, d_lb, d_F, 1, strategy, trace=True,
                   audit=True, bsf_ub=jnp.asarray(ub))
    assert not np.asarray(
        obs.accounting_residual(res.trace, index.n_leaves)).any()
    assert not np.asarray(
        obs_audit.accounting_residual_leaf(res.audit, 4)).any()


# ---------------------------------------------------------------------------
# shadow sampler: pure helpers
# ---------------------------------------------------------------------------

def test_sample_mask_is_deterministic_and_batching_invariant():
    rids = np.arange(1000)
    whole = sample_mask(rids, 0.25, seed=3)
    split = np.concatenate([sample_mask(rids[:137], 0.25, seed=3),
                            sample_mask(rids[137:], 0.25, seed=3)])
    np.testing.assert_array_equal(whole, split)   # batching-invariant
    np.testing.assert_array_equal(whole, sample_mask(rids, 0.25, seed=3))
    assert 0.15 < whole.mean() < 0.35             # roughly the asked rate
    assert not sample_mask(rids, 0.0, seed=3).any()
    assert sample_mask(rids, 1.0, seed=3).all()
    # the seed offsets the hash, so a distant seed shadows a different set
    assert (whole != sample_mask(rids, 0.25, seed=1 << 31)).any()


def test_leaf_of_ids_names_the_holding_leaf(obs_index):
    rng = np.random.default_rng(0)
    order = np.asarray(obs_index.order)
    ids = rng.integers(0, order.shape[0], 64)
    leaves = leaf_of_ids(obs_index, ids)
    starts = np.asarray(obs_index.leaf_start)
    sizes = np.asarray(obs_index.leaf_size)
    assert ((0 <= leaves) & (leaves < obs_index.n_leaves)).all()
    for i, leaf in zip(ids, leaves):
        members = order[starts[leaf]: starts[leaf] + sizes[leaf]]
        assert i in members, (i, leaf)


# ---------------------------------------------------------------------------
# leaf-health scoreboard (unit level; serve-level wiring below)
# ---------------------------------------------------------------------------

def _audit_dict(L, **cols):
    base = {k: np.zeros(L, np.int64)
            for k in ("violations", "resid_count", "scored", "kept",
                      "pruned_box", "pruned_seed", "pruned_filter",
                      "rows_saved")}
    base["resid_sum"] = np.zeros(L, np.float64)
    base["resid_min"] = np.full(L, np.inf)
    for k, v in cols.items():
        base[k] = np.asarray(v)
    return base


def test_health_board_flags_reasons_and_severity_order():
    r = MetricsRegistry()
    board = LeafHealthBoard(window=4, registry=r, min_resid_count=8,
                            violation_rate_threshold=0.05,
                            resid_min_threshold=-0.5)
    # leaf 1: high violation rate; leaf 2: one deep violation (too few
    # observations for the rate flag); leaves 0/3 healthy
    board.record_audit(_audit_dict(
        4, violations=[0, 3, 1, 0], resid_count=[9, 10, 2, 9],
        resid_min=[0.2, -0.05, -1.0, 0.3]), n_queries=16)
    # shadow truth: two filter-attributed misses at leaf 3, one box-
    # attributed miss at leaf 0 (float-tie noise → must NOT flag)
    board.record_shadow([{"leaf": 3, "bound": "filter"},
                         {"leaf": 3, "bound": "filter"},
                         {"leaf": 0, "bound": "box"}], n_queries=8)
    reps = board.filters_needing_attention()
    # ground truth outranks rates; higher rate outranks lower
    assert [rep.leaf for rep in reps] == [3, 2, 1]
    by_leaf = {rep.leaf: rep for rep in reps}
    assert by_leaf[3].reasons == ["shadow-miss"]
    assert by_leaf[3].shadow_misses == 2
    assert by_leaf[2].reasons == ["deep-violation"]
    assert by_leaf[1].reasons == ["violation-rate"]
    assert by_leaf[1].violation_rate == pytest.approx(0.3)
    assert board.filters_needing_attention(limit=1)[0].leaf == 3
    # registry surface: lifetime counters + windowed flag gauge
    assert r.counter("health_violations_total").value() == 4.0
    assert r.counter("health_shadow_misses_total").value(bound="filter") \
        == 2.0
    assert r.gauge("health_flagged_leaves").value() == 3.0
    json.dumps(board.snapshot())                # JSON-clean
    board.reset()                               # post-recalibration flush
    assert board.filters_needing_attention() == []
    assert r.gauge("health_flagged_leaves").value() == 0.0


def test_health_board_rejects_mismatched_leaf_count():
    board = LeafHealthBoard()
    board.record_audit(_audit_dict(4), n_queries=2)
    with pytest.raises(ValueError, match="leaves"):
        board.record_audit(_audit_dict(5), n_queries=2)


# ---------------------------------------------------------------------------
# serve-level: shadow recall vs calibration, injected staleness, explain
# ---------------------------------------------------------------------------

def _serve_shadowed(lfi, queries, n_requests=64, target=0.95, rate=1.0):
    trace = poisson_trace(queries, rate=500.0, n_requests=n_requests,
                          targets=(target,), ks=(1,), seed=11)
    session = ServingSession(lfi, audit=True, shadow_rate=rate,
                             shadow_seed=7)
    report = session.serve(
        trace, batcher=MicroBatcher(max_batch=8, max_wait=0.004),
        service_time=lambda b: 0.002)
    return session, report


def test_shadow_recall_agrees_with_calibration_estimate(
        lfi_obs, queries_small):
    """Acceptance pin: shadow-sampled *true* recall agrees with the
    calibration-split estimate within the binomial CI (+ slack for the
    finite calibration split itself)."""
    target = 0.95
    session, report = _serve_shadowed(lfi_obs, queries_small,
                                      target=target)
    sh = report["shadow"]
    assert sh["n_shadowed"] == 64               # rate=1.0 shadows everything
    calib = min(target,
                float(lfi_obs.build_report.get("calib_best_quality", 1.0)))
    ci = 1.96 * np.sqrt(calib * (1.0 - calib) / sh["n_shadowed"])
    assert abs(sh["recall_mean"] - calib) <= ci + 0.05, (sh["recall_mean"],
                                                         calib, ci)
    for m in sh["misses"]:                      # every miss fully attributed
        assert m["bound"] in ("box", "seed", "filter", "timing")
        assert 0 <= m["leaf"] < lfi_obs.index.n_leaves
        assert "rid" in m and "d_F" in m
    # the audit stream reached the health board alongside the shadow stream
    assert session.telemetry.health.n_leaves == lfi_obs.index.n_leaves
    assert session.shadow.summary()["n_shadowed"] == 64


def test_injected_stale_filter_is_flagged_with_correct_leaf(
        lfi_obs, queries_small):
    """Acceptance pin: perturbing one leaf's conformal offset (smaller
    offset → larger adjusted prediction → over-pruning) must surface that
    exact leaf at the top of filters_needing_attention()."""
    exact = lfi_obs.search_exact(queries_small, k=1)
    nn_leaves = leaf_of_ids(lfi_obs.index, np.asarray(exact.ids)[:, 0])
    filtered = set(int(leaf) for leaf in lfi_obs.leaf_ids)
    cand = np.asarray([leaf for leaf in nn_leaves if int(leaf) in filtered])
    assert cand.size, "no filtered leaf holds a pool query's true NN"
    target_leaf = int(np.bincount(cand).argmax())
    f_idx = int(np.nonzero(
        np.asarray(lfi_obs.leaf_ids) == target_leaf)[0][0])

    tuner = lfi_obs.tuner
    knots_o = np.asarray(tuner.knots_o).copy()
    max_off = np.asarray(tuner.max_offset).copy()
    knots_o[f_idx] -= 1e3                       # d_F = pred − offset → huge
    max_off[f_idx] -= 1e3
    stale = dataclasses.replace(
        lfi_obs, tuner=dataclasses.replace(
            tuner, knots_o=knots_o.astype(np.float32),
            max_offset=max_off.astype(np.float32)))

    session, report = _serve_shadowed(stale, queries_small)
    flagged = session.telemetry.filters_needing_attention()
    assert flagged, "stale filter went unflagged"
    top = flagged[0]
    assert top.leaf == target_leaf              # the *correct* leaf id
    assert "shadow-miss" in top.reasons
    assert top.shadow_misses >= 1
    # every one of those misses is shadow-confirmed against exact truth and
    # attributed to the filter bound at the injected leaf
    guilty = [m for m in report["shadow"]["misses"]
              if m["leaf"] == target_leaf]
    assert guilty and all(m["bound"] == "filter" for m in guilty)
    # the summary surfaces the same list (the recalibration trigger)
    summary = session.telemetry.summary()
    assert summary["filters_needing_attention"][0]["leaf"] == target_leaf

    # control: the unperturbed index never accumulates that many confirmed
    # filter misses at the injected leaf
    clean_session, clean_report = _serve_shadowed(lfi_obs, queries_small)
    clean_guilty = [m for m in clean_report["shadow"]["misses"]
                    if m["leaf"] == target_leaf and m["bound"] == "filter"]
    assert len(clean_guilty) < len(guilty)


def test_explain_query_gathers_and_renders(lfi_obs, queries_small):
    session = ServingSession(lfi_obs, audit=True)
    ctx = explain_query(session, queries_small[0], target=0.95, k=3, rid=7)
    assert ctx["rid"] == 7 and ctx["k"] == 3
    assert len(ctx["served"]["dists"]) == 3
    cas = ctx["cascade"]
    assert cas["n_leaves"] == lfi_obs.index.n_leaves
    assert 0 < cas["searched"] <= cas["n_leaves"]
    # single-query audit planes render as per-leaf verdicts, closest first
    assert ctx["leaves"]
    assert {row["verdict"] for row in ctx["leaves"]} \
        <= {"kept", "box", "seed", "filter"}
    assert any(row["verdict"] == "kept" for row in ctx["leaves"])
    lbs = [row["d_lb"] for row in ctx["leaves"]]
    assert lbs == sorted(lbs)
    assert 0.0 <= ctx["shadow"]["recall"] <= 1.0
    text = obs_explain.render_text(ctx)
    assert "explain rid=7 k=3" in text
    assert "served kNN" in text and "cascade:" in text
    assert "shadow truth" in text
    json.loads(obs_explain.render_json(ctx))    # valid JSON round-trip


# ---------------------------------------------------------------------------
# bench smoke (slow): the audit-overhead pin's code path cannot rot
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_obs_bench_trace_audit_smoke():
    from benchmarks.obs_bench import bench_trace_audit
    rows, payload = bench_trace_audit(n=3000, m=64, leaf_capacity=64,
                                      n_queries=8, k=3, repeat=2)
    assert "max_compact_audit_overhead_pct" in payload
    assert len(payload["levels"]) == 4
    assert any("obs/max_compact_audit_overhead" in row for row in rows)
