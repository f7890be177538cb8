"""The benchmark's load loops, on the wall clock.

Both drive the program's served path and own nothing of it but the clock:

* :func:`open_loop` — independent users: requests fall due on a schedule
  fixed before the window, whether or not earlier ones are answered.  The
  program's ``MicroBatcher`` forms the batches and ``ServingSession
  .dispatch``/``harvest`` answer them; up to ``in_flight`` batches are
  dispatched before the oldest is harvested.  A request's latency runs from
  when it fell due to when its answer is on the host.
* :func:`closed_loop` — callers that each wait on their answer:
  ``outstanding`` requests are open at all times; batches of up to
  ``max_batch`` are taken first in, first out, and every answer issues the
  caller's next request.  Exact requests go through
  ``ServingSession.search_exact``; requests with a recall target go
  through ``ServingSession.search`` with the batch's per-query targets
  and, as ``search_exact``, ``record=False``.

Each returns a :class:`Served` record of every request it issued.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Served:
    queries: np.ndarray            # (n, m) every query issued
    targets: Optional[np.ndarray]  # (n,) quality targets (None: exact)
    due: np.ndarray                # (n,) seconds after the loop started
    done: np.ndarray               # (n,) answer on the host (nan: never)
    ids: np.ndarray                # (n, k) original row ids
    dists: np.ndarray              # (n, k)
    searched: np.ndarray           # (n,) leaves searched
    n_leaves: int
    batches: List[dict]            # bucket, n_valid, dispatch_s per batch
    late: np.ndarray               # generator wake-up lateness, seconds
    seconds: float                 # the measured window
    end: float                     # when the loop stopped (last answer)

    @property
    def answered(self) -> np.ndarray:
        return np.isfinite(self.done)

    def latency_s(self) -> np.ndarray:
        """Latency of every request due in the window, from when it fell
        due to when its answer was on the host (inf: never answered)."""
        due = self.due < self.seconds
        return np.where(np.isfinite(self.done[due]),
                        self.done[due] - self.due[due], np.inf)


def _empty(n: int, k: int):
    return (np.full(n, np.nan), np.full((n, k), -1, np.int64),
            np.full((n, k), np.nan, np.float32), np.full(n, -1.0))


def open_loop(session, queries: np.ndarray, due: np.ndarray,
              targets: np.ndarray, *, k: int, max_batch: int,
              max_wait: float, in_flight: int, seconds: float,
              drain_s: float = 60.0,
              clock: Callable[[], float] = time.perf_counter) -> Served:
    """Serve requests falling due at ``due`` (seconds from the start)."""
    from repro.serving import MicroBatcher, Request

    n = len(due)
    done, ids, dists, searched = _empty(n, k)
    batcher = MicroBatcher(max_batch=max_batch, max_wait=max_wait)
    batches: List[dict] = []
    late: List[float] = []
    pending: deque = deque()
    n_leaves = session.lfi.index.n_leaves
    t0 = clock()

    def retire():
        pb, info = pending.popleft()
        res = session.harvest(pb)
        t = clock() - t0
        rows = np.asarray(pb.batch.rids)
        nv = pb.batch.n_valid
        done[rows] = t
        ids[rows] = np.asarray(res.ids)[:nv]
        dists[rows] = np.asarray(res.dists)[:nv]
        searched[rows] = np.asarray(res.searched)[:nv]
        info["done"] = t

    i = 0
    while True:
        t = clock() - t0
        if t > seconds + drain_s:
            break                               # the rest stays unanswered
        while i < n and due[i] <= t:
            batcher.submit(Request(rid=i, query=queries[i], k=k,
                                   quality_target=float(targets[i]),
                                   arrival=float(due[i])))
            i += 1
        formed = batcher.poll(t)
        for b in formed:
            while len(pending) >= in_flight:
                retire()
            d0 = clock()
            pb = session.dispatch(b)
            info = {"bucket": b.bucket, "n_valid": b.n_valid,
                    "formed": b.formed_at, "dispatch_s": clock() - d0}
            batches.append(info)
            pending.append((pb, info))
        if formed:
            continue
        if pending:
            retire()
            continue
        if i >= n and not batcher.pending:
            break
        nxt = min(batcher.next_deadline(), due[i] if i < n else np.inf)
        wait = nxt - (clock() - t0)
        if wait > 0:
            with TraceAnnotation("bench.wait"):
                time.sleep(wait)
            late.append(clock() - t0 - nxt)
    while pending:
        retire()
    return Served(queries=queries, targets=targets, due=due, done=done,
                  ids=ids, dists=dists, searched=searched,
                  n_leaves=n_leaves, batches=batches,
                  late=np.asarray(late), seconds=seconds,
                  end=clock() - t0)


def closed_loop(session, draw: Callable[[int], np.ndarray], *, k: int,
                outstanding: int, max_batch: int, seconds: float,
                draw_targets: Optional[Callable[[int], np.ndarray]] = None,
                clock: Callable[[], float] = time.perf_counter) -> Served:
    """Keep ``outstanding`` requests open for ``seconds``.

    ``draw(c)`` returns the next ``c`` queries and ``draw_targets(c)`` their
    recall targets; without ``draw_targets`` every request is exact.  No
    batch starts after ``seconds``; the window ends when the last one
    started is answered.
    """
    qs, ts, issued = [], [], []
    done_l, ids_l, dists_l, searched_l = [], [], [], []
    batches: List[dict] = []
    queue: deque = deque()
    n_leaves = session.lfi.index.n_leaves
    t0 = clock()

    def issue(t, c):
        if draw_targets is not None:
            ts.extend(draw_targets(c))
        for row in draw(c):
            queue.append(len(qs))
            qs.append(row)
            issued.append(t)
            done_l.append(np.nan)
            ids_l.append(None)
            dists_l.append(None)
            searched_l.append(-1.0)

    issue(0.0, outstanding)
    while clock() - t0 < seconds:
        take = [queue.popleft() for _ in range(min(max_batch, len(queue)))]
        qb = np.stack([qs[r] for r in take])
        d0 = clock()
        if draw_targets is None:
            with TraceAnnotation("bench.search_exact"):
                res = session.search_exact(qb, k=k)
        else:
            tb = np.asarray([ts[r] for r in take], np.float64)
            with TraceAnnotation("bench.search"):
                res = session.search(qb, quality_targets=tb, k=k,
                                     record=False)
        t = clock() - t0
        batches.append({"bucket": len(take), "n_valid": len(take),
                        "dispatch_s": t - (d0 - t0), "done": t})
        r_ids, r_d = np.asarray(res.ids), np.asarray(res.dists)
        r_s = np.asarray(res.searched)
        for pos, r in enumerate(take):
            done_l[r] = t
            ids_l[r], dists_l[r], searched_l[r] = r_ids[pos], r_d[pos], \
                r_s[pos]
        issue(t, len(take))
    n = len(qs)
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.nan, np.float32)
    for r in range(n):
        if ids_l[r] is not None:
            ids[r], dists[r] = ids_l[r], dists_l[r]
    targets = None if draw_targets is None else np.asarray(ts, np.float64)
    return Served(queries=np.stack(qs), targets=targets,
                  due=np.asarray(issued), done=np.asarray(done_l),
                  ids=ids, dists=dists, searched=np.asarray(searched_l),
                  n_leaves=n_leaves, batches=batches,
                  late=np.zeros(0), seconds=seconds, end=clock() - t0)
