"""Seconds from the start of the process to the start of the window: data,
build, placement and warm-up (compilation included)."""


def read(ctx):
    return ctx["setup_s"]
