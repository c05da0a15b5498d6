"""Lanes the range-stats and exact-EMA programs computed per row and
column they answer: halo and padding overhead, 1.0 where there is none.

The frame ops record each engine call as a ``tempo.dispatch`` span
nested in the op's own ``tempo.dispatch``, whose ``rows`` are the lanes
that call computes, halos and pads included.  The lanes of those spans
in the window, under ``tempo.withRangeStats`` and ``tempo.EMA`` ops,
over the rows of those ops times the columns each op computes (the
mix's ``colsToSummarize``; one for the EMA).  None where the program
records no such span (program_spans.py).
"""

import program_spans
import work_stats

OPS = ("tempo.withRangeStats", "tempo.EMA")


def read(ctx):
    spans = program_spans.window_spans(ctx)
    if spans is None:
        return None
    cols = {"tempo.withRangeStats":
            work_stats.stats_columns(ctx.cell.traffic) or 1,
            "tempo.EMA": 1}
    by_id = {s.id: s for s in spans}
    lanes, answered, ops = 0, 0, set()
    for s in spans:
        outer = by_id.get(s.parent)
        op = by_id.get(s.root)
        if (s.name != "tempo.dispatch" or outer is None
                or outer.name != "tempo.dispatch" or op is None
                or op.name not in OPS):
            continue
        lanes += s.rows
        if op.id not in ops:
            ops.add(op.id)
            answered += op.rows * cols[op.name]
    return lanes / answered if answered else None
