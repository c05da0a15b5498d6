"""Host time of the frame ops, per completed pipeline, in ms.

The time inside the harness's spans around each call of a pipeline
(``bench.frame``, ``bench.<op>``, ``bench.df``) in which no operation
ran on the device: the trace's idle gaps, each put down to the
innermost span that covers it (trace_reduce.py), summed over the
window, over the pipelines completed.  This is the work the library
does on the host between its device programs: key encoding, sorting
and packing into planes, gathering the joined columns and building the
pandas frames.
"""

import trace_reduce


def read(ctx):
    done = sum(1 for r in ctx.records if r["ok"])
    if ctx.trace is None or not ctx.trace.devices or not done:
        return None
    idle = sum(s for name, s in ctx.trace.gap_s_by_span.items()
               if name != trace_reduce.NO_SPAN)
    return 1e3 * idle / done
