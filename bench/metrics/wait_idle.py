"""Share (%) of the host's ``search.wait`` time in the traced window during
which no operation ran on the device: the ``search.wait`` annotations of the
profiler's host plane, clipped to the window and merged
(``tracing.merge``), less the part that the union of the device operations
covers (on any of the chips used).  High: the host waits on a chip that sits
idle.

The window is cut at the end of the last device operation in the trace: the
profiler stops recording device events once its buffer is full (a ``Trace
Buffers Dropped`` event on the plane's ``XLA TraceMe`` line), and a wait
after that point would read as idle.  Nothing to read where the trace holds
no device operation or no wait."""
import numpy as np

from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["device"]:
        return None
    busy, last = busy_until(tr["device"])
    waits = tracing.merge(((s, e) for n, s, e in tr["host"]
                           if n == "search.wait"), tr["lo"],
                          min(tr["hi"], last))
    if not waits:
        return None
    w = np.asarray(waits, np.float64)
    covered = float((busy(w[:, 1]) - busy(w[:, 0])).sum())
    return 100.0 * (1.0 - covered / float((w[:, 1] - w[:, 0]).sum()))


def busy_until(events):
    """(t ↦ nanoseconds before t in which at least one of ``events`` runs,
    vectorized over t; the end of the last event).  A traced window holds
    millions of events."""
    n = len(events)
    starts = np.fromiter((s for _, s, _ in events), np.float64, n)
    ends = np.fromiter((e for _, _, e in events), np.float64, n)
    o = np.argsort(starts, kind="stable")
    starts, run = starts[o], np.maximum.accumulate(ends[o])
    first = np.flatnonzero(np.r_[True, starts[1:] > run[:-1]])
    ps = starts[first]                            # the union's pieces
    pe = run[np.r_[first[1:] - 1, n - 1]]
    before = np.r_[0.0, np.cumsum(pe - ps)]       # length of pieces < i

    def busy(t):
        j = np.searchsorted(ps, t, side="right") - 1   # last piece begun
        jj = np.maximum(j, 0)
        part = np.clip(t - ps[jj], 0.0, pe[jj] - ps[jj])
        return np.where(j >= 0, before[jj] + part, 0.0)

    return busy, float(run[-1])
