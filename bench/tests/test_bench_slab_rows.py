"""The reader of the leaf pass's rows (``slab_rows``), fed synthetic
``search.cascade`` spans: the mean per batch inside the window, and nothing
to read where no span carries the counts."""
import os

import pytest

from bench import cell
from bench.tests import tiny
from repro.obs.spans import Span

METRICS = os.path.join(tiny.BENCH, "metrics")


def _cascade(t0, rows_read=None, rows_held=1000):
    args = {"q": 64, "k": 1, "strategy": "scan"}
    if rows_read is not None:
        args.update(rows_read=rows_read, rows_held=rows_held)
    return Span("search.cascade", "search", t0, 0.001, 0, 1, args)


def _ctx(spans):
    return {"spans": list(spans), "window_pc": (10.0, 20.0), "trace": None}


def test_slab_rows_means_the_batches_inside_the_window():
    read = cell.reader(METRICS, "slab_rows.isax")
    spans = [_cascade(9.0, 9000),                     # before the window
             _cascade(10.0, 1400), _cascade(15.0, 1500),
             _cascade(20.0, 1600),                    # the window's ends count
             _cascade(20.5, 9000),                    # after it
             Span("search.wait", "search", 12.0, 1.0, 0, 1,
                  {"rows_read": 9000, "rows_held": 1000})]
    assert read(_ctx(spans)) == pytest.approx(1.5)


def test_slab_rows_has_nothing_to_read():
    read = cell.reader(METRICS, "slab_rows.isax")
    assert read(_ctx([])) is None
    # a program whose cascade span does not count the rows (a parent
    # commit, or a traced engine), and one outside the window
    assert read(_ctx([_cascade(12.0)])) is None
    assert read(_ctx([_cascade(25.0, 1450)])) is None
