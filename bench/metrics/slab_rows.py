"""Series rows the scan engine's leaf pass reads per batch over the rows the
index holds (×): the ``rows_read`` and ``rows_held`` args of the program's
``search.cascade`` spans whose start lies inside the window, averaged per
batch.  1 reads each row once; a pass that reads a full ``max_leaf``-row
slab for every leaf reads more where leaves hold fewer rows.  Nothing to
read where no such span carries those args (a program whose spans do not
count them)."""


def read(ctx):
    lo, hi = ctx["window_pc"]
    r = [s.args["rows_read"] / s.args["rows_held"] for s in ctx["spans"]
         if s.name == "search.cascade" and lo <= s.t0 <= hi
         and "rows_read" in s.args and s.args.get("rows_held")]
    return sum(r) / len(r) if r else None
