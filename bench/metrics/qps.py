"""Answered queries per second: every answer of the window over all of its
time, up to the answer of the last batch started in it."""


def read(ctx):
    s = ctx["served"]
    return float(s.answered.sum()) / s.end
