"""Profiling / cost-probe / strategy-pick parity tests.

The strategy decision tree mirrors tsdf.py:482-509 (broadcast under a
30MiB side) and the merge dispatch conditions; compiled_cost exercises
XLA's post-compile analyses on the CPU backend."""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from tempo_tpu import profiling


def _df(n):
    return pd.DataFrame({"ts": np.arange(n), "v": np.random.default_rng(0).standard_normal(n)})


class TestStrategyPick:
    def test_broadcast_when_small_and_opted_in(self):
        small, big = _df(10), _df(10)
        assert profiling.pick_asof_strategy(small, big, True, False, 0) == "broadcast"

    def test_no_broadcast_without_opt_in(self):
        small = _df(10)
        assert profiling.pick_asof_strategy(small, small, False, False, 0) == "searchsorted"

    def test_merge_for_sequence_or_lookback(self):
        d = _df(10)
        assert profiling.pick_asof_strategy(d, d, False, True, 0) == "merge"
        assert profiling.pick_asof_strategy(d, d, False, False, 5) == "merge"

    def test_max_lookback_beats_broadcast(self, caplog):
        """ADVICE r3: the broadcast kernel has no row cap, so a
        user-supplied maxLookback must force the merge path even when
        sql_join_opt and the size threshold would pick broadcast —
        silently dropping the cap returns unbounded-lookback rows."""
        import logging

        small = _df(10)
        with caplog.at_level(logging.WARNING, logger="tempo_tpu.profiling"):
            got = profiling.pick_asof_strategy(small, small, True, False, 3)
        assert got == "merge"
        assert any("cannot bound lookback" in r.message
                   for r in caplog.records)

    def test_broadcast_threshold(self):
        # both sides over 30MiB -> no broadcast even when opted in
        big = pd.DataFrame({"v": np.zeros(5_000_000)})  # 40MB of float64
        assert profiling.host_bytes(big) > profiling.BROADCAST_BYTES_THRESHOLD
        assert profiling.pick_asof_strategy(big, big, True, False, 0) == "searchsorted"


class TestCostProbe:
    def test_compiled_cost_reports_something(self):
        def f(a, b):
            return (a @ b).sum()

        a = jnp.ones((64, 64), jnp.float32)
        out = profiling.compiled_cost(f, a, a)
        assert isinstance(out, dict)
        # the CPU backend reports flops for a matmul
        assert out["flops"] is None or out["flops"] > 0

    def test_trace_context(self, tmp_path):
        from jax.profiler import ProfileData

        with profiling.trace(str(tmp_path)):
            with profiling.span("unit-test-span"):
                jnp.ones((8,)).sum().block_until_ready()
        # the span is a host event of the trace
        found = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        assert found
        names = {ev.name
                 for plane in ProfileData.from_file(str(found[0])).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert "unit-test-span" in names


def _mine(name):
    """This test's records of span ``name``, oldest first."""
    spans, _ = profiling.recent_spans()
    return [s for s in spans if s.name == name]


class TestSpanRecorder:
    def test_nested_spans_carry_parent_and_root(self):
        with profiling.span("t.outer"):
            with profiling.span("t.middle"):
                with profiling.span("t.inner"):
                    pass
            with profiling.span("t.sibling"):
                pass
        outer, = _mine("t.outer")[-1:]
        middle, = _mine("t.middle")[-1:]
        inner, = _mine("t.inner")[-1:]
        sibling, = _mine("t.sibling")[-1:]
        assert outer.parent is None and outer.root == outer.id
        assert middle.parent == outer.id and middle.root == outer.id
        assert inner.parent == middle.id and inner.root == outer.id
        assert sibling.parent == outer.id and sibling.root == outer.id
        assert outer.start_ns <= middle.start_ns <= inner.start_ns
        assert inner.end_ns <= middle.end_ns <= sibling.start_ns
        assert sibling.end_ns <= outer.end_ns
        # the next outermost span starts a new root
        with profiling.span("t.outer"):
            pass
        again = _mine("t.outer")[-1]
        assert again.parent is None and again.root == again.id != outer.id

    def test_rows_given_or_set_inside(self):
        with profiling.span("t.rows", rows=7):
            pass
        with profiling.span("t.rows") as sp:
            sp.rows = 11
        assert [s.rows for s in _mine("t.rows")[-2:]] == [7, 11]

    def test_a_span_whose_body_raises_is_recorded(self):
        with pytest.raises(ZeroDivisionError):
            with profiling.span("t.raises", rows=3):
                1 / 0
        rec = _mine("t.raises")[-1]
        assert rec.rows == 3 and rec.end_ns >= rec.start_ns
        # the open span was closed: the next one is outermost again
        with profiling.span("t.after"):
            pass
        assert _mine("t.after")[-1].parent is None

    def test_ring_is_bounded_and_counts_what_it_dropped(self, monkeypatch):
        import collections
        import itertools

        monkeypatch.setattr(profiling, "_ring",
                            collections.deque(maxlen=4))
        monkeypatch.setattr(profiling, "_ring_slots", itertools.count())
        assert profiling.recent_spans() == ([], 0)
        for i in range(3):
            with profiling.span("t.ring", rows=i):
                pass
        spans, dropped = profiling.recent_spans()
        assert [s.rows for s in spans] == [0, 1, 2] and dropped == 0
        for i in range(3, 10):
            with profiling.span("t.ring", rows=i):
                pass
        spans, dropped = profiling.recent_spans()
        assert [s.rows for s in spans] == [6, 7, 8, 9] and dropped == 6

    def test_spans_on_two_threads_do_not_parent_each_other(self):
        import threading

        opened, closed = threading.Event(), threading.Event()

        def other():
            opened.wait(10)
            with profiling.span("t.thread_b"):
                with profiling.span("t.thread_b_inner"):
                    pass
            closed.set()

        worker = threading.Thread(target=other)
        worker.start()
        with profiling.span("t.thread_a"):
            opened.set()
            closed.wait(10)
        worker.join(10)
        a = _mine("t.thread_a")[-1]
        b = _mine("t.thread_b")[-1]
        inner = _mine("t.thread_b_inner")[-1]
        # b ran entirely inside a's time, on another thread
        assert a.start_ns < b.start_ns and b.end_ns < a.end_ns
        assert b.parent is None and b.root == b.id
        assert inner.parent == b.id and inner.root == b.id
        assert a.parent is None and a.root == a.id
