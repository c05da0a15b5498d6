"""Range statistics over the trailing window in whole seconds, per series.

The reference library orders each series by its timestamp cast to whole
seconds and takes ``rangeBetween(-rangeBackWindowSecs, 0)``: a row's
window holds every row of its series whose second lies within
``rangeBackWindowSecs`` before the row's own second, both ends included,
rows later in the same second too.  For each row: mean, count, min, max,
sum and sample standard deviation of the column's non-null values over
that window, and the z-score of the row's own value.  Computed per
second of each series, then over the window's seconds.  Inputs and
results are rounded to the ``stats_compute`` precision the reference is
given: float64 for the reference, the one below the stated one for the
control.
"""

import numpy as np
import pandas as pd

from precision import round_to

NS_PER_S = 1_000_000_000


def _window(per_second: np.ndarray, width: int, how: str) -> np.ndarray:
    """``how`` over each second and the ``width - 1`` seconds before it."""
    roll = pd.Series(per_second).rolling(width, min_periods=1)
    return getattr(roll, how)().to_numpy()


def apply(df: pd.DataFrame, right, args: dict, spec: dict) -> pd.DataFrame:
    ts, part = spec["ts"], spec["partition"]
    prec = spec["precision"].get("stats_compute", "float64")
    width = int(args["rangeBackWindowSecs"]) + 1
    out = df.sort_values(part + [ts], kind="mergesort").reset_index(drop=True)
    sec = out[ts].to_numpy("datetime64[ns]").astype(np.int64) // NS_PER_S
    series = list(out.groupby(part, sort=False).indices.values())
    for col in args["colsToSummarize"]:
        x = round_to(out[col].to_numpy(), prec)
        stats = {k: np.full(len(out), np.nan)
                 for k in ("mean", "count", "min", "max", "sum", "stddev")}
        for idx in series:
            b = sec[idx] - sec[idx].min()
            v = x[idx]
            ok = ~np.isnan(v)
            n_sec = int(b.max()) + 1
            count = _window(np.bincount(b[ok], minlength=n_sec), width, "sum")
            total = _window(np.bincount(b[ok], weights=v[ok], minlength=n_sec),
                            width, "sum")
            sq = _window(np.bincount(b[ok], weights=v[ok] ** 2,
                                     minlength=n_sec), width, "sum")
            lo = np.full(n_sec, np.inf)
            hi = np.full(n_sec, -np.inf)
            np.minimum.at(lo, b[ok], v[ok])
            np.maximum.at(hi, b[ok], v[ok])
            lo, hi = _window(lo, width, "min"), _window(hi, width, "max")
            with np.errstate(invalid="ignore", divide="ignore"):
                mean = total / count
                var = (sq - total * mean) / (count - 1)
            has = count > 0
            stats["count"][idx] = count[b]
            stats["sum"][idx] = np.where(has, total, np.nan)[b]
            stats["mean"][idx] = np.where(has, mean, np.nan)[b]
            stats["min"][idx] = np.where(has, lo, np.nan)[b]
            stats["max"][idx] = np.where(has, hi, np.nan)[b]
            stats["stddev"][idx] = np.sqrt(np.where(count > 1, np.maximum(
                var, 0.0), np.nan))[b]
        for stat, values in stats.items():
            out[f"{stat}_{col}"] = round_to(values, prec)
        with np.errstate(invalid="ignore", divide="ignore"):
            z = (x - stats["mean"]) / stats["stddev"]
        out[f"zscore_{col}"] = round_to(z, prec)
    return out
