"""Exact exponential moving average, per series, by its recursion.

``ema[i] = a * x[i] + (1 - a) * ema[i - 1]`` from ``ema[-1] = 0`` in
time order within each series (rows of one time in table order; the
benchmark's EMA columns hold no nulls), run as a first-order linear
filter in float64.  Inputs and results are rounded to the
``stats_compute`` precision the reference is given (``precision.py``).
"""

import numpy as np
import pandas as pd
from scipy.signal import lfilter

from precision import round_to


def apply(df: pd.DataFrame, right, args: dict, spec: dict) -> pd.DataFrame:
    ts, part = spec["ts"], spec["partition"]
    prec = spec["precision"].get("stats_compute", "float64")
    col = args["colName"]
    a = float(args.get("exp_factor", 0.2))
    out = df.sort_values(part + [ts], kind="mergesort").reset_index(drop=True)
    x = round_to(out[col].to_numpy(), prec)
    ema = np.empty(len(out))
    for idx in out.groupby(part, sort=False).indices.values():
        ema[idx] = lfilter([a], [1.0, a - 1.0], x[idx])
    out[f"EMA_{col}"] = round_to(ema, prec)
    return out
