"""Programs made ready inside the window: compiled, or read back from the
persistent compilation cache (a jax.monitoring listener)."""


def read(ctx):
    return ctx["compiles"]
