"""The open and closed loop drivers on a tiny index, on the CPU."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import gen, loops
from bench.tests import tiny


@pytest.fixture(scope="module")
def served_index():
    return tiny.session()


def test_open_loop_answers_every_request(served_index):
    session, collection = served_index
    rng = np.random.default_rng(1)
    due = gen.poisson_arrivals(60.0, 0.5, rng)
    q = gen.make_queries(collection, len(due), 0.1, rng)
    t = np.asarray([0.9, 0.99])[rng.integers(0, 2, len(due))]
    s = loops.open_loop(session, q, due, t, k=1, max_batch=4,
                        max_wait=0.002, in_flight=1, seconds=0.5)
    assert s.answered.all()
    assert (s.done >= s.due).all()
    assert ((s.ids >= 0) & (s.ids < len(collection))).all()
    assert sum(b["n_valid"] for b in s.batches) == len(due)
    assert all(b["bucket"] in (1, 2, 4) for b in s.batches)
    assert s.end >= due[-1]


def test_closed_loop_keeps_requests_open(served_index):
    session, collection = served_index
    rng = np.random.default_rng(2)
    s = loops.closed_loop(session, lambda c: gen.make_queries(
        collection, c, 0.1, rng), k=3, outstanding=8, max_batch=4,
        seconds=0.3)
    n_ans = int(s.answered.sum())
    assert n_ans > 0 and n_ans % 4 == 0
    assert len(s.queries) == n_ans + 8          # every answer re-issues
    assert all(b["bucket"] == 4 for b in s.batches)
    assert (np.diff(s.dists[s.answered], axis=1) >= 0).all()


class _Recorder:
    """A session that records every call a loop makes to it and answers
    each query with row 0."""

    def __init__(self):
        self.calls = []
        self.lfi = SimpleNamespace(index=SimpleNamespace(n_leaves=1))

    @staticmethod
    def _answer(qb, k):
        q = len(qb)
        return SimpleNamespace(ids=np.zeros((q, k), np.int64),
                               dists=np.zeros((q, k), np.float32),
                               searched=np.ones(q))

    def search_exact(self, qb, k=1):
        self.calls.append(("search_exact", len(qb), {"k": k}))
        return self._answer(qb, k)

    def search(self, qb, quality_targets=None, k=1, record=True, **kw):
        self.calls.append(("search", len(qb), dict(
            quality_targets=quality_targets, k=k, record=record, **kw)))
        return self._answer(qb, k)


def _closed(session, draw_targets=None, seconds=0.05):
    rng = np.random.default_rng(4)
    return loops.closed_loop(
        session, lambda c: rng.standard_normal((c, 16)).astype(np.float32),
        k=2, outstanding=8, max_batch=4, seconds=seconds,
        draw_targets=draw_targets)


def test_exact_closed_loop_makes_the_same_calls():
    """Without targets every batch is one ``search_exact(qb, k=k)``."""
    rec = _Recorder()
    s = _closed(rec)
    assert s.targets is None
    assert rec.calls == [("search_exact", 4, {"k": 2})] * len(s.batches)


def test_closed_loop_returns_each_requests_target_in_order():
    rec, drawn = _Recorder(), []
    rng = np.random.default_rng(5)

    def draw_targets(c):
        t = rng.choice([0.9, 0.95, 0.99], c)
        drawn.extend(t)
        return t
    s = _closed(rec, draw_targets)
    np.testing.assert_array_equal(s.targets, drawn)
    n_ans = int(s.answered.sum())
    assert len(s.queries) == len(s.targets) == n_ans + 8
    assert s.answered[:n_ans].all()            # first in, first answered
    got = [c for c in rec.calls if c[0] == "search"]
    assert len(got) == len(rec.calls) == len(s.batches)
    np.testing.assert_array_equal(
        np.concatenate([c[2]["quality_targets"] for c in got]),
        s.targets[:n_ans])
    assert all(c[2]["k"] == 2 and c[2]["record"] is False for c in got)


def test_closed_loop_lowers_targets_to_offsets(served_index, monkeypatch):
    """Each batch's per-query targets reach the conformal tuner as one
    (Q,) array and come back as (Q, F) offset rows."""
    session, collection = served_index
    tuner, seen = session.lfi.tuner, []
    orig = tuner.offsets

    def offsets(t, *a, **kw):
        out = orig(t, *a, **kw)
        seen.append((np.array(t), out.shape))
        return out
    monkeypatch.setattr(tuner, "offsets", offsets)
    rng = np.random.default_rng(6)
    s = loops.closed_loop(
        session, lambda c: gen.make_queries(collection, c, 0.1, rng), k=1,
        outstanding=8, max_batch=4, seconds=0.3,
        draw_targets=lambda c: rng.choice([0.9, 0.99], c))
    n_ans = int(s.answered.sum())
    assert n_ans > 0 and len(seen) == len(s.batches)
    n_f = len(session.lfi.leaf_ids)
    assert all(t.shape == (4,) and shape == (4, n_f) for t, shape in seen)
    np.testing.assert_array_equal(np.concatenate([t for t, _ in seen]),
                                  s.targets[:n_ans])
    assert ((s.ids[s.answered] >= 0)
            & (s.ids[s.answered] < len(collection))).all()


def test_poisson_arrivals_cover_the_window():
    due = gen.poisson_arrivals(200.0, 2.0, np.random.default_rng(3))
    assert (np.diff(due) > 0).all() and due[-1] < 2.0
    assert 300 < len(due) < 500


def test_seeds_take_large_seeds():
    a = gen.seeds(2 ** 31 + 17, 6)
    assert a == gen.seeds(2 ** 31 + 17, 6) and len(set(a)) == 6
    assert all(0 <= s < 2 ** 31 for s in a)


def test_open_schedule_is_fixed_and_queries_are_fresh():
    from bench import cell
    collection = gen.make_collection(tiny.first_config(), 1)
    mix = tiny.first_traffic("open")
    a = cell._traffic_inputs(collection, mix, 1.0, 11)
    b = cell._traffic_inputs(collection, mix, 1.0, 12)
    np.testing.assert_array_equal(a[0], b[0])        # due times
    np.testing.assert_array_equal(a[2], b[2])        # targets
    assert not np.allclose(a[1], b[1])               # queries


def test_knee_is_below_the_first_rate_not_sustained():
    from bench import sweep
    row = {"answered": 10, "offered": 10, "growth": 1.0, "drain_s": 0.1}
    rows = [dict(row, rate_qps=r) for r in (90.0, 50.0, 70.0, 110.0)]
    rows.append(dict(row, rate_qps=100.0, drain_s=1.1))
    assert sweep.knee(rows) == 90.0
    assert sweep.knee([dict(row, rate_qps=50.0, growth=2.0)]) is None


def test_every_seed_indexes_the_same_rows_in_another_order():
    from repro.core import tree
    config = tiny.first_config()
    a = gen.make_collection(config, 2 ** 31 + 1)
    b = gen.make_collection(config, 2 ** 31 + 2)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a[np.lexsort(a.T)], b[np.lexsort(b.T)])
    cap = config["leafi"]["leaf_capacity"]
    assert tree.build_dstree(a, cap).n_leaves == \
        tree.build_dstree(b, cap).n_leaves
