"""The open and closed loop drivers on a tiny index, on the CPU."""
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
    collection = gen.make_collection(tiny.CONFIG, 1)
    a = cell._traffic_inputs(collection, tiny.OPEN, 1.0, 11)
    b = cell._traffic_inputs(collection, tiny.OPEN, 1.0, 12)
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
    a = gen.make_collection(tiny.CONFIG, 2 ** 31 + 1)
    b = gen.make_collection(tiny.CONFIG, 2 ** 31 + 2)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a[np.lexsort(a.T)], b[np.lexsort(b.T)])
    cap = tiny.CONFIG["leafi"]["leaf_capacity"]
    assert tree.build_dstree(a, cap).n_leaves == \
        tree.build_dstree(b, cap).n_leaves
