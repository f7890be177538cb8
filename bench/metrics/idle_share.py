"""Share (%) of the traced window in which no operation ran on the device:
one minus the union of the device operations' intervals over the window,
averaged over the chips used.

The window is cut at the end of the last device operation in the trace
(``tracing.recorded_until``): the profiler stops recording device events
once its buffer is full, and the rest of the window would read as idle.
Nothing to read where the trace holds no device operation."""
from bench import tracing


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["device"]:
        return None
    hi = tracing.recorded_until(tr["device"], tr["hi"])
    return 100.0 * (1.0 - tr["busy_ns"] / (hi - tr["lo"]))
