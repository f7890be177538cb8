"""The readers of the search path's spans (``wait_ms``, ``fetch_ms``,
``offsets_ms``) and of the host's wait against the device (``wait_idle``),
fed synthetic spans and trace events: the window, the mean per batch, the
idle share of a hand-built timeline and where its device events stop, and
nothing to read where nothing is there."""
import os

import pytest

from bench import cell
from bench.tests import tiny
from repro.obs.spans import Span

METRICS = os.path.join(tiny.BENCH, "metrics")
SPAN_METRICS = {"wait_ms.open": "search.wait", "wait_ms.bulk": "search.wait",
                "wait_ms.approx": "search.wait",
                "fetch_ms.open": "search.fetch",
                "fetch_ms.bulk": "search.fetch",
                "fetch_ms.approx": "search.fetch",
                "offsets_ms.open": "search.offsets",
                "offsets_ms.approx": "search.offsets"}
DEVICE = [("op", 100.0, 200.0), ("op", 150.0, 300.0), ("op", 600.0, 700.0)]


def _span(name, t0, dur):
    return Span(name, "search", t0, dur, 0, 1, {})


def _ctx(spans=(), host=(), device=DEVICE, lo=0.0, hi=1000.0):
    return {"spans": list(spans), "window_pc": (10.0, 20.0),
            "trace": {"device": list(device), "host": list(host), "lo": lo,
                      "hi": hi}}


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_reader_means_the_batches_inside_the_window(metric):
    name = SPAN_METRICS[metric]
    spans = [_span(name, 9.0, 5.0),                   # before the window
             _span(name, 10.0, 0.002), _span(name, 15.0, 0.004),
             _span(name, 20.0, 0.006),                # the window's ends count
             _span(name, 20.5, 9.0),                  # after it
             _span("serve.dispatch", 12.0, 1.0)]      # another span
    read = cell.reader(METRICS, metric)
    assert read(_ctx(spans)) == pytest.approx(4.0)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_reader_has_nothing_to_read(metric):
    read = cell.reader(METRICS, metric)
    name = SPAN_METRICS[metric]
    assert read(_ctx([_span("serve.dispatch", 12.0, 1.0)])) is None
    assert read(_ctx([_span(name, 30.0, 1.0)])) is None      # outside
    # a run with no device operation in its trace (the CPU) reads nothing
    assert read(_ctx([_span(name, 12.0, 1.0)], device=())) is None


@pytest.mark.parametrize("metric", ["wait_idle.open", "wait_idle.bulk"])
def test_wait_idle_on_a_hand_built_timeline(metric):
    read = cell.reader(METRICS, metric)
    # device busy [100, 300) and [600, 700); waits [50, 250) and [500,
    # 650): 200 + 150 = 350 ns of waiting, of which 150 + 50 ns see the
    # device busy
    host = [("search.wait", 50.0, 250.0), ("search.wait", 500.0, 650.0),
            ("serve.dispatch", 0.0, 1000.0)]
    assert read(_ctx(host=host)) == pytest.approx(100.0 * 150.0 / 350.0)
    # overlapping waits count once; a wait inside one busy piece is 0% idle
    assert read(_ctx(host=[("search.wait", 120.0, 180.0),
                           ("search.wait", 130.0, 290.0)])) == 0.0
    assert read(_ctx(host=[("search.wait", 350.0, 550.0)])) == 100.0


@pytest.mark.parametrize("metric", ["wait_idle.open", "wait_idle.bulk"])
def test_wait_idle_stops_where_the_device_events_stop(metric):
    """Past the last recorded device operation (the profiler's buffer is
    full) a wait is not read as idle: [500, 700) of [500, 900) is read."""
    read = cell.reader(METRICS, metric)
    host = [("search.wait", 50.0, 250.0), ("search.wait", 500.0, 900.0),
            ("search.wait", 950.0, 1100.0)]
    assert read(_ctx(host=host)) == pytest.approx(100.0 * 150.0 / 400.0)
    assert read(_ctx(host=[("search.wait", 800.0, 1000.0)])) is None


@pytest.mark.parametrize("metric", ["idle_share.open", "idle_share.bulk",
                                    "idle_share.approx"])
def test_idle_share_stops_where_the_device_events_stop(metric):
    """Busy 250 ns of [0, 700): the record ends with the last device
    operation, not with the window at 1000; a device record that reaches
    past the window is read to the window's end."""
    read = cell.reader(METRICS, metric)
    ctx = _ctx()
    ctx["trace"]["busy_ns"] = 250.0
    assert read(ctx) == pytest.approx(100.0 * (1.0 - 250.0 / 700.0))
    ctx["trace"]["device"] = DEVICE + [("op", 900.0, 1200.0)]
    ctx["trace"]["busy_ns"] = 350.0
    assert read(ctx) == pytest.approx(100.0 * (1.0 - 350.0 / 1000.0))
    assert read({"trace": None}) is None
    assert read(_ctx(device=())) is None


@pytest.mark.parametrize("metric", ["wait_idle.open", "wait_idle.bulk"])
def test_wait_idle_has_nothing_to_read(metric):
    read = cell.reader(METRICS, metric)
    wait = [("search.wait", 50.0, 250.0)]
    assert read({"trace": None}) is None
    assert read(_ctx(host=wait, device=())) is None
    assert read(_ctx(host=[("serve.harvest", 50.0, 250.0)])) is None
    assert read(_ctx(host=[("search.wait", 2000.0, 2100.0)])) is None


def test_wait_idle_agrees_with_the_trace_reduction():
    """On a random timeline, the reader's covered time is what
    ``tracing.busy_ns`` gives inside each merged wait."""
    import numpy as np

    from bench import tracing
    rng = np.random.default_rng(3)
    s = np.sort(rng.uniform(0, 1e6, 3000))
    dev = [("op", float(a), float(a + d))
           for a, d in zip(s, rng.exponential(200.0, s.size))]
    host = [("search.wait", float(a), float(a + 5e3))
            for a in rng.uniform(0, 1e6, 80)]
    last = max(b for _, _, b in dev)
    waits = tracing.merge(((a, b) for _, a, b in host), 0.0, min(1e6, last))
    covered = sum(tracing.busy_ns(dev, a, b) for a, b in waits)
    want = 100.0 * (1.0 - covered / sum(b - a for a, b in waits))
    read = cell.reader(METRICS, "wait_idle.open")
    got = read(_ctx(host=host, device=dev, hi=1e6))
    assert got == pytest.approx(want, rel=1e-9)
