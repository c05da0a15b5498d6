"""The readers of the program's spans (program_spans.py).

A synthetic trace and a synthetic ring on a clock offset from the
trace's give hand-worked ms per phase; a ring that rolled over inside
the window gives nothing; a small CPU run of ``hhar.batch_join`` counts
the layout sort's rows exactly and, with no device in its trace, gives
no times."""

import time
import types

import pytest

import harness
import program_spans
import trace_reduce
from tempo_tpu import profiling

US = 1_000  # ns
#: the window on perf_counter (s) and on the trace's clock (ns)
PERF_W0 = 100.0
TRACE_W0 = 5 * US
TIME_READERS = ("host_keys_ms.batch", "host_layout_ms.batch",
                "host_pack_ms.batch", "dispatch_idle_ms.batch",
                "host_unpack_ms.batch", "host_frame_ms.batch")


def _rec(i, name, start_us, end_us, parent=None, root=None, rows=0):
    base = round(PERF_W0 * 1e9)
    return profiling.SpanRecord(i, parent, root or i, name,
                                base + start_us * US, base + end_us * US,
                                rows)


#: one pipeline's spans, in us from the window's start
RING = [
    _rec(1, "tempo.layout", -5, -1, rows=999),   # before the window
    _rec(3, "tempo.keys", 1, 3, parent=2, root=2),
    _rec(5, "tempo.pack", 4, 5, parent=4, root=2),
    _rec(6, "tempo.layout", 5.5, 5.8, parent=4, root=2, rows=30),
    _rec(4, "tempo.dispatch", 3, 8, parent=2, root=2),
    _rec(2, "tempo.asofJoin", 1, 9, rows=20),
    _rec(7, "tempo.frame", 9.5, 9.8),
]


def _ctx(ring, dropped=0, devices=1, done=2):
    # the device is busy [5, 6] us of the 10 us window: two idle gaps
    summary = trace_reduce.TraceSummary(
        window_s=10e-6, devices=devices, busy_s=1e-6, op_s={},
        module_s={}, gaps=[(TRACE_W0, TRACE_W0 + 5 * US),
                           (TRACE_W0 + 6 * US, TRACE_W0 + 10 * US)],
        gap_s_by_span={}, spans=[(harness.WINDOW, TRACE_W0,
                                  TRACE_W0 + 10 * US)])
    spans = types.SimpleNamespace(
        items=[(harness.WINDOW, PERF_W0, PERF_W0 + 10e-6)])
    records = [{"ok": True, "rows": 10}] * done
    ctx = harness.Context(cell=None, peaks={}, setup_s=0.0, records=records,
                          spans=spans, window_built=0, work={},
                          trace=summary)
    return ctx, (list(ring), dropped)


@pytest.fixture
def ring(monkeypatch):
    def install(ring, dropped=0, **kw):
        ctx, held = _ctx(ring, dropped, **kw)
        monkeypatch.setattr(profiling, "recent_spans", lambda: held)
        return ctx
    return install


def _read(name, ctx):
    return harness.plugin("metrics", name).read(ctx)


def test_idle_time_splits_by_innermost_program_span(ring):
    ctx = ring(RING)
    # idle [0, 5] and [6, 10] us: 0-1 no span, 1-3 keys, 3-4 dispatch,
    # 4-5 pack, 6-8 dispatch, 8-9 the op's own, 9-9.5 none, 9.5-9.8
    # frame, 9.8-10 none; the layout span lies in the busy time
    assert program_spans.idle_by_span(ctx) == pytest.approx({
        trace_reduce.NO_SPAN: 1.7e-6, "tempo.keys": 2e-6,
        "tempo.dispatch": 3e-6, "tempo.pack": 1e-6,
        "tempo.asofJoin": 1e-6, "tempo.frame": 0.3e-6})
    # per pipeline (two), in ms
    want = {"host_keys_ms.batch": 1e-3, "host_layout_ms.batch": 0.0,
            "host_pack_ms.batch": 0.5e-3, "dispatch_idle_ms.batch": 1.5e-3,
            "host_unpack_ms.batch": 0.0, "host_frame_ms.batch": 0.15e-3}
    for name in TIME_READERS:
        assert _read(name, ctx) == pytest.approx(want[name], abs=1e-12)
    # the layout rows of the window (30, not the 999 before it) over
    # the left rows of the two pipelines
    assert _read("sorted_rows_per_row.batch", ctx) == pytest.approx(1.5)


def test_gaps_are_cut_at_every_span_edge():
    spans = [("a", 2, 6), ("b", 3, 4), ("c", 20, 30)]
    assert program_spans.cut([(0, 5), (7, 10), (25, 40)], spans) == [
        (0, 2), (2, 3), (3, 4), (4, 5), (7, 10), (25, 30), (30, 40)]


def test_a_ring_that_rolled_over_inside_the_window_gives_none(ring):
    # the oldest span held starts after the window's start and older
    # ones were pushed out
    ctx = ring(RING[1:], dropped=5)
    assert program_spans.window_spans(ctx) is None
    for name in TIME_READERS + ("sorted_rows_per_row.batch",):
        assert _read(name, ctx) is None
    # a ring that dropped spans but still holds the window's start is
    # read as usual
    assert _read("host_keys_ms.batch", ring(RING, dropped=5)) == \
        pytest.approx(1e-3)


def test_no_device_in_the_trace_gives_no_times(ring):
    ctx = ring(RING, devices=0)
    for name in TIME_READERS:
        assert _read(name, ctx) is None
    assert _read("sorted_rows_per_row.batch", ctx) == pytest.approx(1.5)


SMALL_HHAR = {"phone_rows": 9 * 3000 + 4, "watch_rows": 9 * 800 + 7,
              "phone_rate_hz": 50}


def test_small_cpu_join_counts_both_sides_sorted_once():
    cell = harness.Cell("hhar.batch_join", 2**31 + 12345, 1.5, traced=True,
                        config=SMALL_HHAR)
    line = harness.run(
        cell, time.perf_counter(),
        require=lambda chips: {"platform": "cpu", "kind": "TPU v5 lite",
                               "count": chips},
        log=lambda msg: None)
    assert line["correct"], line["checks"]
    n_left, n_right = SMALL_HHAR["phone_rows"], SMALL_HHAR["watch_rows"]
    assert line["metrics"]["sorted_rows_per_row.batch"]["value"] == \
        (n_left + n_right) / n_left
    for name in TIME_READERS:
        assert name not in line["metrics"]
