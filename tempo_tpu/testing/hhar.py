"""HHAR-shaped phone<->watch accelerometer frames, generated from a seed.

The reference quickstart (`Tempo QuickStart - Python.ipynb`, cell 3)
AS-OF joins 13,062,475 UCI HHAR phone readings against watch readings.
This generator keeps that shape: ``n_series`` integer ``user`` keys of
equal length (``n_rows`` rounded down to a multiple of ``n_series``),
1-2 Hz integer-second ticks on the left, a right side whose timestamps
lag by 0-2 s (so it is unsorted and has duplicate timestamps within a
series), standard-normal values, and 5% NaN in the right value column.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

#: rows of the reference quickstart's phone<->watch join
HHAR_ROWS = 13_062_475
#: series the rows are spread over: ~12.8k rows per series keeps the
#: merged join lanes inside one Pallas merge plan
HHAR_SERIES = 1024


def make_frames(n_rows: int = HHAR_ROWS, n_series: int = HHAR_SERIES,
                seed: int = 0):
    """(left, right, n): two pandas frames with columns ``user``,
    ``event_ts`` (datetime64[ns]) and ``x`` (left) / ``wx`` (right),
    and the row count of each side."""
    rng = np.random.default_rng(seed)
    per = n_rows // n_series
    n = per * n_series
    keys = np.repeat(np.arange(n_series), per)
    gaps = rng.integers(1, 3, size=(n_series, per)).astype(np.int64)
    secs = np.cumsum(gaps, axis=1).ravel()
    left = pd.DataFrame({
        "user": keys,
        "event_ts": pd.to_datetime(secs * np.int64(1_000_000_000)),
        "x": rng.standard_normal(n),
    })
    right = pd.DataFrame({
        "user": keys,
        "event_ts": pd.to_datetime(
            (secs - rng.integers(0, 3, size=n)) * np.int64(1_000_000_000)),
        "wx": np.where(rng.random(n) > 0.05, rng.standard_normal(n), np.nan),
    })
    return left, right, n
