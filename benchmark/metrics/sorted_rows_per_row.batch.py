"""Rows the layout sort handled per useful row: the ``rows`` of the
program's ``tempo.layout`` spans (which do not nest) that start in the
window, over the left-side rows of the pipelines completed there
(program_spans.py).  A join sorts both sides once; each op that builds
its frame's layout again adds a full sort."""

import program_spans


def read(ctx):
    rows = sum(r["rows"] for r in ctx.records if r["ok"])
    spans = program_spans.window_spans(ctx)
    if spans is None or not rows:
        return None
    return sum(s.rows for s in spans if s.name == "tempo.layout") / rows
