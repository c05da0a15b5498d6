"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the
window (trace_reduce.py), in percent."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * ctx.trace.idle_share()
