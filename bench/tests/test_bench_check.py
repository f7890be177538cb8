"""The check that decides ``correct``: its control (the reference one
precision lower, in the program's place) and the faults a run can have,
planted under the timed path, all come out as not correct."""
import dataclasses

import numpy as np
import pytest

from bench import cell, control, gen
from bench.tests import tiny


@pytest.fixture(scope="module")
def served_index():
    return tiny.session()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("workload", ["tiny-open", "tiny-closed"])
def test_control_is_not_correct(root, workload):
    r = control.control_run(root, workload, 2 ** 31 + 5, 1.0, 64)
    assert r["correct"] is False
    assert r["compared"]["dist_err"]["value"] > \
        3 * r["compared"]["dist_err"]["limit"]


def _half_left_out(orig):
    """Harvest/search whose answers for the second half of the batch are
    the first row's: half of the batch was left out."""
    def broken(*a, **kw):
        res = orig(*a, **kw)
        ids, dists = np.array(res.ids), np.array(res.dists)
        n = len(ids)
        ids[n // 2:], dists[n // 2:] = ids[0], dists[0]
        return dataclasses.replace(res, ids=ids, dists=dists)
    return broken


def _answer_altered(orig):
    """One answer's id changed where the answer is produced."""
    def broken(*a, **kw):
        res = orig(*a, **kw)
        ids = np.array(res.ids)
        ids[0, 0] = (ids[0, 0] + 1) % tiny.CONFIG["n"]
        return dataclasses.replace(res, ids=ids)
    return broken


FAULTS = {"half_left_out": _half_left_out, "answer_altered": _answer_altered}


def _served(session, collection, traffic, seconds):
    return cell._drive(session, collection, traffic, seconds, 11)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("traffic", ["open", "closed"])
def test_fault_is_not_correct(served_index, monkeypatch, fault, traffic):
    session, collection = served_index
    t = tiny.OPEN if traffic == "open" else tiny.CLOSED
    sound = _served(session, collection, t, 0.4)
    assert cell.passed(cell.check(collection, sound, tiny.CONFIG, t))
    name = "harvest" if traffic == "open" else "search_exact"
    monkeypatch.setattr(session, name, FAULTS[fault](getattr(session, name)))
    broken = _served(session, collection, t, 0.4)
    assert not cell.passed(cell.check(collection, broken, tiny.CONFIG, t))


def test_unanswered_request_is_not_correct(served_index):
    session, collection = served_index
    s = _served(session, collection, tiny.OPEN, 0.3)
    s.done[-1] = np.nan
    got = cell.check(collection, s, tiny.CONFIG, tiny.OPEN)
    assert got["unanswered"]["value"] == 1 and not cell.passed(got)


def test_recall_floor_is_the_stated_guarantee():
    lim = {"recall_sigmas": 3, "calib_queries": 60}
    f = cell.recall_floor(0.99, 400, lim)
    assert f == pytest.approx(0.99 - 3 * np.sqrt(0.99 * 0.01 *
                                                 (1 / 60 + 1 / 400)))
    assert gen.znormalize(np.ones((1, 4))).shape == (1, 4)
