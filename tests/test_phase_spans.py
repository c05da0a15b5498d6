"""The frame ops record their phases as spans (tempo_tpu.profiling.span).

A small eager asofJoin -> withRangeStats -> EMA: each op's span holds
one span of every phase (key encoding, layout sort, packing, dispatch,
unpacking, pandas frames) under its root, and the join's layout sort
counts the rows of both sides."""

import numpy as np
import pandas as pd
import pytest

from tempo_tpu import TSDF, profiling

PHASES = ("tempo.keys", "tempo.layout", "tempo.pack", "tempo.dispatch",
          "tempo.unpack", "tempo.frame")
OPS = ("tempo.asofJoin", "tempo.withRangeStats", "tempo.EMA")


def _frame(rng, n, cols):
    users = np.array(["a", "b", "c"])
    return pd.DataFrame({
        "User": users[rng.integers(0, 3, n)],
        "event_ts": pd.Timestamp("2024-01-01")
        + pd.to_timedelta(np.sort(rng.integers(0, 60_000, n)), unit="ms"),
        **{c: rng.standard_normal(n) for c in cols},
    })


@pytest.fixture(scope="module")
def chain():
    rng = np.random.default_rng(7)
    phone = _frame(rng, 600, ["x", "y"])
    watch = _frame(rng, 150, ["x"])
    first = max((s.id for s in profiling.recent_spans()[0]), default=0)
    left = TSDF(phone, "event_ts", ["User"])
    right = TSDF(watch, "event_ts", ["User"])
    out = (left.asofJoin(right, right_prefix="watch")
           .withRangeStats(colsToSummarize=["x"], rangeBackWindowSecs=10)
           .EMA("x", exact=True))
    spans = [s for s in profiling.recent_spans()[0] if s.id > first]
    return spans, len(phone), len(watch), out


def test_each_op_has_its_span(chain):
    spans, n_left, _, out = chain
    ops = [s for s in spans if s.name in OPS]
    assert [s.name for s in sorted(ops, key=lambda s: s.start_ns)] == \
        list(OPS)
    for op in ops:
        assert op.parent is None and op.root == op.id
        assert op.rows == n_left
    assert len(out.df) == n_left


@pytest.mark.parametrize("op_name", OPS)
def test_each_op_span_holds_every_phase(chain, op_name):
    spans, _, _, _ = chain
    op, = [s for s in spans if s.name == op_name]
    under = [s for s in spans if s.root == op.id and s.id != op.id]
    assert {s.name for s in under} >= set(PHASES)
    for s in under:
        assert op.start_ns <= s.start_ns <= s.end_ns <= op.end_ns


def test_join_layout_sorts_both_sides_once(chain):
    spans, n_left, n_right, _ = chain
    op, = [s for s in spans if s.name == "tempo.asofJoin"]
    layout = [s for s in spans
              if s.root == op.id and s.name == "tempo.layout"]
    assert sum(s.rows for s in layout) == n_left + n_right


@pytest.mark.parametrize("presorted", [True, False],
                         ids=["presorted", "unsorted"])
def test_join_frame_spans_count_rows_copied(presorted):
    """The join's own ``tempo.frame`` spans count the rows they copy: a
    left side already in layout order is only wrapped (0 rows), one in
    another order is taken into layout order (every left row).  The
    right side is in layout order in both."""
    rng = np.random.default_rng(8)
    sort = ["User", "event_ts"]
    # keys first seen in the same order on both sides, so the series
    # ids, and with them the right side's layout, are the same
    phone = _frame(rng, 600, ["x", "y"]).sort_values(
        sort, ascending=[True, presorted], kind="stable")
    watch = _frame(rng, 150, ["x"]).sort_values(sort, kind="stable")
    first = max((s.id for s in profiling.recent_spans()[0]), default=0)
    TSDF(phone, "event_ts", ["User"]).asofJoin(
        TSDF(watch, "event_ts", ["User"]), right_prefix="watch")
    spans = [s for s in profiling.recent_spans()[0] if s.id > first]
    op, = [s for s in spans if s.name == "tempo.asofJoin"]
    frames = [s for s in spans
              if s.parent == op.id and s.name == "tempo.frame"]
    assert len(frames) == 2
    assert sum(s.rows for s in frames) == (0 if presorted else len(phone))
