"""The check that decides ``correct``, for every cell of BENCHMARK.json
through its tiny twin: its control (the reference one precision lower, in
the program's place) and the faults a run can have, planted under the
timed path, all come out as not correct."""
import dataclasses

import numpy as np
import pytest

from bench import cell, control, gen
from bench.tests import tiny
from repro.core import search

CELLS = tiny.cells()
OPEN_CELLS = [twin for twin, w in zip(CELLS, tiny.load()["workloads"])
              if tiny.load_traffic(w["traffic"])["loop"] == "open"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.fixture(scope="module")
def served_cell(root):
    """workload ↦ (session, collection, config, traffic) of its twin; one
    session per configuration."""
    sessions = {}

    def get(workload):
        spec = cell.load(root, workload)
        config = spec["config"]
        if config["name"] not in sessions:
            sessions[config["name"]] = tiny.session(config)
        return (*sessions[config["name"]], config, spec["traffic"])
    return get


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(root, workload):
    r = control.control_run(root, workload, 2 ** 31 + 5, 1.0, 64)
    assert r["correct"] is False
    assert r["compared"]["dist_err"]["value"] > \
        3 * r["compared"]["dist_err"]["limit"]


def _half_left_out(orig, n):
    """Answers whose second half of the batch are the first row's: half of
    the batch was left out."""
    def broken(*a, **kw):
        res = orig(*a, **kw)
        ids, dists = np.array(res.ids), np.array(res.dists)
        q = len(ids)
        ids[q // 2:], dists[q // 2:] = ids[0], dists[0]
        return dataclasses.replace(res, ids=ids, dists=dists)
    return broken


def _answer_altered(orig, n):
    """One answer's id changed where the answer is produced."""
    def broken(*a, **kw):
        res = orig(*a, **kw)
        ids = np.array(res.ids)
        ids[0, 0] = (ids[0, 0] + 1) % n
        return dataclasses.replace(res, ids=ids)
    return broken


FAULTS = {"half_left_out": _half_left_out, "answer_altered": _answer_altered}


def _served(session, collection, traffic, seconds):
    return cell._drive(session, collection, traffic, seconds, 11)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(served_cell, monkeypatch, fault, workload):
    """The fault is planted where every loop's answers are produced:
    ``PendingSearch.result``, which materializes a batch's answers."""
    session, collection, config, traffic = served_cell(workload)
    sound = _served(session, collection, traffic, 0.4)
    assert cell.passed(cell.check(collection, sound, config, traffic))
    monkeypatch.setattr(search.PendingSearch, "result", FAULTS[fault](
        search.PendingSearch.result, len(collection)))
    broken = _served(session, collection, traffic, 0.4)
    assert not cell.passed(cell.check(collection, broken, config, traffic))


@pytest.mark.parametrize("workload", OPEN_CELLS)
def test_unanswered_request_is_not_correct(served_cell, workload):
    """A request of an open loop that falls due and is never answered (a
    closed loop's request is due once taken into a batch, and every batch
    taken is answered)."""
    session, collection, config, traffic = served_cell(workload)
    s = _served(session, collection, traffic, 0.3)
    s.done[-1] = np.nan
    got = cell.check(collection, s, config, traffic)
    assert got["unanswered"]["value"] == 1 and not cell.passed(got)


def test_recall_floor_is_the_stated_guarantee():
    lim = {"recall_sigmas": 3, "calib_queries": 60}
    f = cell.recall_floor(0.99, 400, lim)
    assert f == pytest.approx(0.99 - 3 * np.sqrt(0.99 * 0.01 *
                                                 (1 / 60 + 1 / 400)))
    assert gen.znormalize(np.ones((1, 4))).shape == (1, 4)
