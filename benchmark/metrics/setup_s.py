"""Set-up: process start to the first timed operation (data made from
the seed, frames or resident tables built, the cell's shapes warmed)."""


def read(ctx):
    return ctx.setup_s
