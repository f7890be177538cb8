"""Seconds the harness spent warming the cell's own programs: every
(bucket, k) shape, then a slice of the cell's traffic."""


def read(ctx):
    return ctx["warmup_s"]
