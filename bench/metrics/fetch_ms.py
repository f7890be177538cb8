"""Host milliseconds per batch in the program's ``search.fetch`` spans inside
the window: the device-to-host copies of the answer (the span's ``n_arrays``
and ``bytes``) and the id mapping.  Nothing to read where the trace holds no
device operation: on the CPU nothing is copied from a chip."""

CHIP_ONLY = True


def read(ctx):
    if not (ctx["trace"] and ctx["trace"]["device"]):
        return None
    lo, hi = ctx["window_pc"]
    d = [s.dur for s in ctx["spans"]
         if s.name == "search.fetch" and lo <= s.t0 <= hi]
    return 1e3 * sum(d) / len(d) if d else None
