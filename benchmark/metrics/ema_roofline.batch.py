"""The exact EMA's share of its roofline on the device.

The least time the exact ``EMA`` work of a pipeline could take at the
chip's peaks (work_stats.py; memory bound), times the pipelines
completed in the traced window, over the device time of every exact-EMA
program in the trace: the XLA scan ``ema_exact``, the whole-series
kernel ``_ema_call`` and the carry-passing ``_ema_chunk_call``.
"""

import work as work_model
import work_stats

#: substrings of the exact EMA's program names in the trace
PROGRAMS = ("ema_exact", "_ema_call", "_ema_chunk_call")


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    n_emas = work_stats.exact_emas(ctx.cell.traffic)
    done = [r for r in ctx.records if r["ok"]]
    device_s = ctx.trace.time_matching(PROGRAMS)
    if not n_emas or not done or device_s <= 0:
        return None
    least = sum(work_model.least_seconds(work_stats.ema(r["rows"]),
                                         ctx.peaks)[0] for r in done)
    return 100.0 * n_emas * least / device_s
