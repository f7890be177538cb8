"""The device allocator's peak bytes in use (GiB), read after the window
and before the reference is placed: the build's peak included."""


def read(ctx):
    return ctx["peak_bytes"] / 2 ** 30 if ctx["peak_bytes"] else None
