"""Host milliseconds per batch in the program's ``search.offsets`` spans
inside the window: the conformal offsets of the batch's recall targets and
their upload.  Nothing to read where the trace holds no device operation,
or where no request carries a target (an exact loop has no such span)."""

CHIP_ONLY = True


def read(ctx):
    if not (ctx["trace"] and ctx["trace"]["device"]):
        return None
    lo, hi = ctx["window_pc"]
    d = [s.dur for s in ctx["spans"]
         if s.name == "search.offsets" and lo <= s.t0 <= hi]
    return 1e3 * sum(d) / len(d) if d else None
