"""Programs compiled, or loaded from the persistent cache, inside the
measured window (JAX monitoring events; probe.py).  Should be 0."""


def read(ctx):
    return ctx.window_built
