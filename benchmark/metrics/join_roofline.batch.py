"""The AS-OF join's share of its roofline on the device.

The least time the join's required bytes and operations could take at
the chip's peaks (work.py; memory bound for every join of these cells),
times the joins completed in the traced window, over the device time of
the join's programs in the trace.  The join's programs are found by the
names below, as the trace's ``XLA Modules`` line gives them: the merge
kernels of ``ops/pallas_merge.py`` and ``ops/sortmerge.py`` and the
jitted entries that build their planes.
"""

import work as work_model

#: substrings of the join's program names in the trace
PROGRAMS = ("asof_merge", "asof_indices", "_merge_call", "_chunked_call",
            "merge_rank", "_asof_merge_explicit")


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    joins = sum(1 for r in ctx.records if r["ok"])
    device_s = ctx.trace.time_matching(PROGRAMS)
    if not joins or device_s <= 0:
        return None
    least, _bound = work_model.least_seconds(ctx.work["asof_join"],
                                             ctx.peaks)
    return 100.0 * joins * least / device_s
