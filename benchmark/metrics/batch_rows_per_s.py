"""Left-side input rows of every pipeline completed in the window, over
the seconds from the window's start to the last completion."""

import harness


def read(ctx):
    done = [r for r in ctx.records if r["ok"]]
    if not done:
        return None
    t0 = next(t for name, t, _ in ctx.spans.items if name == harness.WINDOW)
    return sum(r["rows"] for r in done) / (max(r["end"] for r in done) - t0)
