"""Host milliseconds per batch that the program waits for the device: the
mean ``search.wait`` span (``PendingSearch.result``'s block on every output
it reads) whose start lies inside the window.  Nothing to read where the
trace holds no device operation: a wait on the CPU is no wait for a chip."""

CHIP_ONLY = True


def read(ctx):
    if not (ctx["trace"] and ctx["trace"]["device"]):
        return None
    lo, hi = ctx["window_pc"]
    d = [s.dur for s in ctx["spans"]
         if s.name == "search.wait" and lo <= s.t0 <= hi]
    return 1e3 * sum(d) / len(d) if d else None
