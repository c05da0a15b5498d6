"""Range stats' share of their roofline on the device.

The least time the ``withRangeStats`` work of a pipeline could take at
the chip's peaks (work_stats.py; memory bound), times the pipelines
completed in the traced window, over the device time of every
range-stats program in the trace, whichever engine ran: the names
below, as the trace's ``XLA Modules`` line gives them (the whole-series
``windowed_stats`` and its window bounds, the lane-chunked
``range_stats_chunk``, the shifted and streaming forms).
"""

import work as work_model
import work_stats

#: substrings of the range-stats program names in the trace
PROGRAMS = ("windowed_stats", "range_window_bounds", "range_stats",
            "_stream_call", "_unrolled_call")


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    n_cols = work_stats.stats_columns(ctx.cell.traffic)
    done = [r for r in ctx.records if r["ok"]]
    device_s = ctx.trace.time_matching(PROGRAMS)
    if not n_cols or not done or device_s <= 0:
        return None
    least = sum(work_model.least_seconds(
        work_stats.range_stats(r["rows"], n_cols), ctx.peaks)[0]
        for r in done)
    return 100.0 * least / device_s
