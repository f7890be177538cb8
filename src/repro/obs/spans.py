"""Host-side span profiling: nested timers with
``jax.profiler.TraceAnnotation`` pass-through.

Spans answer "where did the wall-clock go" for the host-orchestrated
phases the device profiler cannot see — build-pipeline stages
(collect/train/calibrate), serving dispatch/harvest and the search path
inside them, checkpoint IO.  Each ``span(...)`` block records name,
category, nesting depth, thread lane, wall-clock ``(t0, dur)``, its own
``sid`` (a per-recorder counter) and the ``parent`` sid of the span that
encloses it on the same thread (−1 at the top), so the spans of one
served batch are grouped by cause, not by time.  :mod:`repro.obs.export`
renders the recorded list as Chrome trace-event JSON for Perfetto.

One clock with the device trace: every span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so the host plane of an
active profiler trace holds each span too.  That plane runs on
``time.time_ns()``; a span's ``t0`` runs on ``time.perf_counter()``.  Each
recorder reads both clocks once, at creation, and
:meth:`SpanRecorder.to_trace_ns` maps any ``t0`` onto the profiler's clock
with that one anchor — no second clock read per span.  A trace file's
planes count from their session's ``profile_start_time`` (a stat of its
``Task Environment`` plane): subtract it to place a span on a loaded
timeline.

Determinism contract: wall-clock readings stay inside the ``t0``/``dur``
fields (exported as Chrome ``ts``/``dur``); span names, categories, lanes,
sids and args must be derived from deterministic run state only — the
trace-determinism test masks exactly ``ts``/``dur`` and pins the rest.

Instrumented code calls the module-level :func:`span`, which records into
the installed default recorder (a bounded deque, enabled from the start so
ad-hoc profiling needs no setup).  ``with span(...) as h`` gives the open
span's handle: ``h.sid`` on entry, ``h.dur`` (seconds) after exit — None
where the recorder is disabled, which records and times nothing.  Drivers
that want an isolated capture install their own recorder via
``recording()``::

    with recording() as rec:
        run()
    export.write_chrome_trace(path, spans=rec.drain())
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple, Optional

try:  # pragma: no cover - import guard, exercised implicitly
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover - jax always present in this repo
    _TraceAnnotation = contextlib.nullcontext


class Span(NamedTuple):
    name: str
    cat: str
    t0: float          # wall-clock (time.perf_counter) — export as ts only
    dur: float         # wall-clock seconds — export as dur only
    lane: int          # small stable per-thread index (first-seen order)
    depth: int         # nesting depth within the lane
    args: dict         # deterministic metadata only (no wall-clock)
    sid: int = -1      # per-recorder span id, in entry order
    parent: int = -1   # sid of the enclosing span on this thread, or -1


class SpanHandle:
    """One span while it is open: ``sid`` and ``parent`` from entry,
    ``dur`` (seconds) once it has exited."""

    __slots__ = ("_rec", "_name", "_cat", "_args", "_ann", "_tls", "sid",
                 "parent", "depth", "t0", "dur")

    def __init__(self, rec: "SpanRecorder", name: str, cat: str,
                 args: dict):
        self._rec, self._name, self._cat, self._args = rec, name, cat, args
        self.dur: Optional[float] = None

    def __enter__(self) -> "SpanHandle":
        self._tls = tls = self._rec._tls
        stack = tls.stack
        self.depth = len(stack)
        self.parent = stack[-1] if stack else -1
        self.sid = sid = next(self._rec._sids)   # atomic under the GIL
        stack.append(sid)
        self._ann = ann = _TraceAnnotation(self._name)
        self.t0 = time.perf_counter()
        ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        try:
            self._ann.__exit__(*exc)
        finally:
            self.dur = time.perf_counter() - self.t0
            tls, rec = self._tls, self._rec
            tls.stack.pop()
            if tls.lane is None:
                tls.lane = rec._lane()  # before taking _lock: not reentrant
            span = Span(self._name, self._cat, self.t0, self.dur, tls.lane,
                        self.depth, self._args, self.sid, self.parent)
            with rec._lock:
                rec._spans.append(span)


class _Off:
    """The handle of a disabled recorder: records and times nothing."""

    sid = parent = -1
    dur = None

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None


_OFF = _Off()


class _Local(threading.local):
    """A recorder's per-thread state: the stack of open sids, and the
    thread's lane once it has recorded a span."""

    def __init__(self):
        self.stack: list = []
        self.lane: Optional[int] = None


class SpanRecorder:
    """Bounded, thread-safe span sink.

    ``maxlen`` bounds memory for long-lived processes (old spans fall off);
    per-thread nesting (depth and parent) is tracked thread-locally, and
    thread idents are normalized to dense ``lane`` indices in first-seen
    order so exports do not leak nondeterministic OS thread ids.
    """

    def __init__(self, maxlen: int = 65536, enabled: bool = True):
        self.enabled = enabled
        self._spans = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._lanes: dict = {}
        self._tls = _Local()
        self._sids = itertools.count()
        # the clock anchor: perf_counter bracketing one time_ns reading
        a = time.perf_counter()
        self._anchor_ns = time.time_ns()
        self._anchor_pc = 0.5 * (a + time.perf_counter())

    def to_trace_ns(self, t: float) -> int:
        """``t``, a ``time.perf_counter()`` reading such as a span's
        ``t0``, on the profiler's host clock (``time.time_ns()``)."""
        return self._anchor_ns + round((t - self._anchor_pc) * 1e9)

    def _lane(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            lane = self._lanes.get(ident)
            if lane is None:
                lane = self._lanes.setdefault(ident, len(self._lanes))
        return lane

    def span(self, name: str, cat: str = "host", **args):
        """A context manager timing one span; ``as`` gives its handle."""
        if not self.enabled:
            return _OFF
        return SpanHandle(self, name, cat, args)

    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    def drain(self) -> list:
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
            return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


_DEFAULT = SpanRecorder()
_current = _DEFAULT


def get_recorder() -> SpanRecorder:
    return _current


def set_recorder(recorder: Optional[SpanRecorder]) -> SpanRecorder:
    """Install ``recorder`` as the module-level sink (None → the built-in
    default); returns the previously installed one."""
    global _current
    prev = _current
    _current = recorder if recorder is not None else _DEFAULT
    return prev


@contextlib.contextmanager
def recording(recorder: Optional[SpanRecorder] = None):
    """Temporarily route :func:`span` into a fresh (or given) recorder."""
    rec = recorder if recorder is not None else SpanRecorder()
    prev = set_recorder(rec)
    try:
        yield rec
    finally:
        set_recorder(prev)


def span(name: str, cat: str = "host", **args):
    """Record a span into the currently installed recorder."""
    return _current.span(name, cat, **args)
