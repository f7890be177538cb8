"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and returns
plain ``(name, start_ns, end_ns)`` tuples: the device operations of each
device plane and the host threads' annotations.  The functions below are
pure and work on such tuples, so tests can feed them recorded or synthetic
events.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:"
CUSTOM_PREFIX = "/device:CUSTOM"      # runtime planes, not a chip's ops
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
COARSE_LINES = ("XLA Modules", "Steps")


def load(trace_dir: str) -> dict:
    """{"devices": {plane: [Event]}, "host": [Event]} from a trace dir.

    Device events are taken from each device plane's ``XLA Ops`` line (one
    event per operation executed), or from all its lines but the per-program
    and per-step ones where it has none; host events from every line of the
    host plane.
    """
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith(DEVICE_PREFIX) and \
                    not plane.name.startswith(CUSTOM_PREFIX):
                evs = devices.setdefault(plane.name, [])
                lines = list(plane.lines)
                ops = [ln for ln in lines if ln.name == OPS_LINE] or \
                    [ln for ln in lines if ln.name not in COARSE_LINES]
                for line in ops:
                    evs.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    host.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events)
    return {"devices": devices, "host": host}


def on_host_clock(events: Sequence[Event], lo: float,
                  hi: float) -> List[Event]:
    """Device events on the host's clock.

    Where none of them falls inside the host window [lo, hi], the device
    timeline runs on another base: it is shifted so that its first event
    starts at ``lo`` (the window opens right before its first dispatch, and
    the trace holds nothing but the window).
    """
    if not events or any(s < hi and e > lo for _, s, e in events):
        return list(events)
    shift = lo - min(s for _, s, _ in events)
    return [(n, s + shift, e + shift) for n, s, e in events]


def window(host: Sequence[Event], name: str) -> Tuple[float, float]:
    """(start_ns, end_ns) of the host annotation ``name`` (the first one)."""
    for n, s, e in host:
        if n == name:
            return s, e
    raise KeyError(f"no host annotation {name!r} in the trace")


def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pieces."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def recorded_until(events: Sequence[Event], hi: float) -> float:
    """Where the trace's device record of a window ending at ``hi`` ends:
    ``hi``, or the end of the last device event where that comes first.

    The profiler stops recording device events once its buffer is full (a
    ``Trace Buffers Dropped`` event on the plane's ``XLA TraceMe`` line);
    past that point the device is not idle, only unrecorded.
    """
    if not events:
        return hi
    return min(hi, max(e for _, _, e in events))


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which at least one event runs."""
    return sum(e - s for s, e in merge(((s, e) for _, s, e in events),
                                       lo, hi))


def top_ops(events: Sequence[Event], lo: float, hi: float,
            n: int = 10) -> List[list]:
    """The ``n`` operation names with the most device time in [lo, hi],
    as [name, seconds]."""
    by: Dict[str, float] = {}
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by[name] = by.get(name, 0.0) + (e - s)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


SHORT_GAP = "(gaps under 10 us)"


def idle_gaps(events: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float, n: int = 10,
              min_gap_ns: float = 10_000.0) -> List[list]:
    """Idle device time in [lo, hi], summed by what the host was doing.

    Each gap of at least ``min_gap_ns`` between busy pieces is named after
    the innermost (shortest) host event that covers its midpoint, or
    ``(no host event)``; shorter gaps (between the operations of one
    program) are summed under ``SHORT_GAP``.  Returns the ``n`` names with
    the most idle time, as [name, seconds].
    """
    busy = merge(((s, e) for _, s, e in events), lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    inside = sorted(((e - s, s, e, name) for name, s, e in host
                     if s < hi and e > lo))
    starts = np.array([s for _, s, _, _ in inside], np.float64)
    ends = np.array([e for _, _, e, _ in inside], np.float64)
    by: Dict[str, float] = {}
    for s, e in gaps:
        if e - s < min_gap_ns:
            by[SHORT_GAP] = by.get(SHORT_GAP, 0.0) + (e - s)
            continue
        mid = 0.5 * (s + e)
        cover = np.flatnonzero((starts <= mid) & (ends >= mid))
        label = inside[cover[0]][3] if cover.size else "(no host event)"
        by[label] = by.get(label, 0.0) + (e - s)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]
