"""Seconds in the program's build spans (build.index, build.collect,
build.train, build.calibrate)."""

PHASES = ("build.index", "build.collect", "build.train", "build.calibrate")


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name in PHASES]
    return sum(s.dur for s in spans) if spans else None
