"""The float64 references on hand-worked series."""

import numpy as np
import pandas as pd
import pytest

import EMA
import withRangeStats

SPEC = {"ts": "t", "partition": ["k"], "precision": {}}


def _frame(rows):
    return pd.DataFrame({"k": [r[0] for r in rows],
                         "t": pd.to_datetime([r[1] for r in rows], unit="ms"),
                         "x": [r[2] for r in rows]})


def test_range_window_is_whole_seconds_with_peers():
    # a 1 s window: rows in second 0 hold that second alone, the row at
    # 1.000 s holds seconds 0 and 1 with the later row of its own second,
    # 2.000 s drops second 0; series b is apart
    df = _frame([("a", 0, 1.0), ("a", 999, 2.0), ("a", 1000, 4.0),
                 ("a", 1999, 8.0), ("a", 2000, 16.0), ("b", 1500, 5.0)])
    out = withRangeStats.apply(
        df, None, {"colsToSummarize": ["x"], "rangeBackWindowSecs": 1}, SPEC)
    assert out["count_x"].tolist() == [2, 2, 4, 4, 3, 1]
    assert out["sum_x"].tolist() == [3, 3, 15, 15, 28, 5]
    assert out["min_x"].tolist() == [1, 1, 1, 1, 4, 5]
    assert out["max_x"].tolist() == [2, 2, 8, 8, 16, 5]
    assert out["mean_x"].tolist() == [1.5, 1.5, 3.75, 3.75, 28 / 3, 5]
    sd = np.std([1, 2, 4, 8], ddof=1)
    assert out["stddev_x"].iloc[2] == pytest.approx(sd, rel=1e-15)
    assert out["zscore_x"].iloc[3] == pytest.approx((8 - 3.75) / sd,
                                                    rel=1e-15)
    assert np.isnan(out["stddev_x"].iloc[5])


def test_range_window_skips_nulls_and_empty_seconds():
    df = _frame([("a", 0, np.nan), ("a", 5000, 3.0), ("a", 5500, np.nan)])
    out = withRangeStats.apply(
        df, None, {"colsToSummarize": ["x"], "rangeBackWindowSecs": 2}, SPEC)
    assert out["count_x"].tolist() == [0, 1, 1]
    assert np.isnan(out["mean_x"].iloc[0])
    assert out["mean_x"].tolist()[1:] == [3.0, 3.0]


def test_ema_recursion():
    df = _frame([("a", 0, 1.0), ("a", 1, 2.0), ("b", 0, 10.0),
                 ("a", 2, 4.0)])
    out = EMA.apply(df, None, {"colName": "x"}, SPEC)
    a1 = 0.2
    a2 = 0.2 * 2 + 0.8 * a1
    a3 = 0.2 * 4 + 0.8 * a2
    assert out["EMA_x"].tolist() == pytest.approx([a1, a2, a3, 2.0],
                                                  rel=1e-15)
