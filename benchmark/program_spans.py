"""The program's own spans, put on the trace's clock.

The frame ops open a span around each phase of their work
(``tempo_tpu.profiling.span``: key encoding, layout sort, packing,
dispatch, unpacking, pandas frames, each inside the op's own span) and
keep every span in an in-memory ring on ``time.perf_counter_ns``.  The
harness records ``bench.window`` on the same clock (``ctx.spans``) and
in the trace (``ctx.trace.spans``); the difference of its two starts
puts the program's spans on the trace's clock.

The device's idle gaps in the window are cut at every program span's
start and end, and each piece is put down to the innermost program span
that covers it (``trace_reduce.attribute``), so the idle time splits by
phase however few device programs the op runs.  Time under an op span
but outside its phases goes to the op; time outside every program span
to ``trace_reduce.NO_SPAN``.

Everything returns None where there is nothing to read: a program that
records no spans, a trace with no device, or a ring that no longer
holds the start of the window.
"""

import bisect
from collections import defaultdict

import harness
import trace_reduce

PHASES = ("tempo.keys", "tempo.layout", "tempo.pack", "tempo.dispatch",
          "tempo.unpack", "tempo.frame")


def _window(items):
    return next((s, e) for name, s, e in items if name == harness.WINDOW)


def window_spans(ctx):
    """The program's spans that start inside the window (times on
    ``perf_counter_ns``), or None."""
    from tempo_tpu import profiling

    recent = getattr(profiling, "recent_spans", None)
    if recent is None:
        return None
    spans, dropped = recent()
    t0, t1 = _window(ctx.spans.items)
    w0, w1 = round(t0 * 1e9), round(t1 * 1e9)
    if dropped and (not spans or min(s.start_ns for s in spans) > w0):
        return None
    return [s for s in spans if w0 <= s.start_ns <= w1]


def on_trace_clock(ctx, spans) -> list:
    """``(name, start ns, end ns)`` of each span on the trace's clock."""
    offset = (_window(ctx.trace.spans)[0]
              - round(_window(ctx.spans.items)[0] * 1e9))
    return [(s.name, s.start_ns + offset, s.end_ns + offset) for s in spans]


def cut(gaps, spans) -> list:
    """The gaps cut at every start and end of ``spans`` inside them."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    pieces = []
    for s, e in gaps:
        inner = edges[bisect.bisect_right(edges, s):
                      bisect.bisect_left(edges, e)]
        bounds = [s, *inner, e]
        pieces.extend(zip(bounds, bounds[1:]))
    return pieces


def idle_by_span(ctx):
    """Device-idle seconds of the window by innermost program span, or
    None."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    spans = window_spans(ctx)
    if spans is None:
        return None
    mapped = on_trace_clock(ctx, spans)
    pieces = cut(ctx.trace.gaps, mapped)
    idle = defaultdict(float)
    for (s, e), who in zip(pieces, trace_reduce.attribute(pieces, mapped)):
        idle[who] += (e - s) / 1e9
    return dict(idle)


def phase_ms(ctx, phase: str):
    """Device-idle ms whose innermost program span is ``phase``, per
    completed pipeline, or None."""
    done = sum(1 for r in ctx.records if r["ok"])
    idle = idle_by_span(ctx)
    if idle is None or not done:
        return None
    return 1e3 * idle.get(phase, 0.0) / done
