"""Host milliseconds per batch in the program's ``serve.dispatch`` spans
inside the window."""


def read(ctx):
    lo, hi = ctx["window_pc"]
    d = [s.dur for s in ctx["spans"]
         if s.name == "serve.dispatch" and lo <= s.t0 <= hi]
    return 1e3 * sum(d) / len(d) if d else None
