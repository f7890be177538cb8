"""The reduction from a profiler trace to busy time, idle share and the
breakdown, on synthetic events and on a recorded trace."""
import glob

import pytest

from bench import tracing

# two device ops overlapping, one alone, one outside the window (ns)
DEV = [("fusion.1", 100, 300), ("filter_predict_fused.1", 250, 400),
       ("fusion.2", 600, 700), ("fusion.9", 2000, 2100)]
HOST = [("bench.window", 0, 1000), ("serve.dispatch", 400, 600),
        ("PjitFunction(step)", 420, 590), ("bench.wait", 700, 1000)]


def test_merge_clips_and_unions():
    assert tracing.merge([(5, 20), (0, 10), (30, 40), (35, 90)], 0, 50) == \
        [(0, 20), (30, 50)]
    assert tracing.merge([(60, 70)], 0, 50) == []


def test_busy_and_idle_share():
    busy = tracing.busy_ns(DEV, 0, 1000)
    assert busy == (400 - 100) + (700 - 600)
    assert 1 - busy / 1000 == pytest.approx(0.6)


def test_record_ends_at_the_last_device_event():
    assert tracing.recorded_until(DEV, 1000) == 1000     # fusion.9 is later
    assert tracing.recorded_until(DEV[:3], 1000) == 700
    assert tracing.recorded_until([], 1000) == 1000


def test_top_ops_orders_by_device_time():
    top = tracing.top_ops(DEV, 0, 1000)
    assert [n for n, _ in top] == ["fusion.1",
                                   "filter_predict_fused.1",
                                   "fusion.2"]
    assert top[0][1] == pytest.approx(200e-9)


def test_idle_gaps_named_by_innermost_host_event():
    gaps = dict(tracing.idle_gaps(DEV, HOST, 0, 1000, min_gap_ns=0))
    # [0,100) under bench.window only; [400,600) under the jitted call
    # (innermost); [700,1000) under bench.wait
    assert gaps == pytest.approx({"bench.window": 100e-9,
                                  "PjitFunction(step)": 200e-9,
                                  "bench.wait": 300e-9})
    total = sum(gaps.values()) * 1e9
    assert total == pytest.approx(1000 - tracing.busy_ns(DEV, 0, 1000))
    # gaps shorter than the threshold are summed, not attributed
    short = dict(tracing.idle_gaps(DEV, HOST, 0, 1000, min_gap_ns=250))
    assert short == pytest.approx({tracing.SHORT_GAP: 300e-9,
                                   "bench.wait": 300e-9})


def test_device_events_on_another_clock_are_shifted_to_the_window():
    far = [(n, s + 10 ** 18, e + 10 ** 18) for n, s, e in DEV[:3]]
    moved = tracing.on_host_clock(far, 50, 1000)
    assert moved[0][1] == 50 and moved[1] == ("filter_predict_fused.1",
                                              200, 350)
    assert tracing.on_host_clock(DEV, 0, 1000) == DEV


def test_recorded_trace_has_the_window(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = tracing.load(str(tmp_path))
    lo, hi = tracing.window(ev["host"], "bench.window")
    assert hi > lo
    # the CPU backend has no device plane: nothing runs "on a device"
    assert all(not evs for evs in ev["devices"].values()) or \
        ev["devices"] == {}
    with pytest.raises(KeyError):
        tracing.window(ev["host"], "no.such.annotation")
