"""Measure f32-vs-f64 max abs error for the metric kernels at scale.

Produces the f32 numerics table (VERDICT r1 item 5): runs
withRangeStats (10s window), exact EMA, and linear interpolation under
``TEMPO_TPU_COMPUTE_DTYPE=float32`` and ``float64`` on the current
backend and reports per-stat max abs divergence at L = 2^13 .. 2^17
rows/series (standard-normal values, 1-2s ticks).

Run on the TPU for the shipped table (f64 there is exact-but-emulated,
so the comparison isolates the f32 compute policy):

    python tools/f32_error_table.py            # full sweep
    TEMPO_F32_TABLE_MAX=15 python tools/...    # cap exponent (CI smoke)
"""

import os
import sys

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempo_tpu  # noqa: E402
from tempo_tpu import TSDF  # noqa: E402

STATS = ("mean", "count", "min", "max", "sum", "stddev", "zscore")


def build(L: int, K: int = 2, seed: int = 0) -> TSDF:
    rng = np.random.default_rng(seed)
    secs = np.concatenate(
        [np.cumsum(rng.integers(1, 3, size=L)) for _ in range(K)]
    )
    n = K * L
    return TSDF(pd.DataFrame({
        "k": np.repeat(np.arange(K), L),
        "event_ts": pd.to_datetime(secs * 1_000_000_000),
        "x": rng.standard_normal(n),
        "gappy": np.where(rng.random(n) > 0.3, rng.standard_normal(n),
                          np.nan),
    }), "event_ts", ["k"])


def run(frame: TSDF, dtype: str):
    os.environ["TEMPO_TPU_COMPUTE_DTYPE"] = dtype
    stats = frame.withRangeStats(colsToSummarize=["x"],
                                 rangeBackWindowSecs=10).df
    ema = frame.EMA("x", exact=True).df["EMA_x"].to_numpy(float)
    interp = frame.interpolate(freq="5 seconds", func="mean",
                               target_cols=["gappy"],
                               method="linear").df["gappy"].to_numpy(float)
    return stats, ema, interp


def main():
    import jax

    max_exp = int(os.environ.get("TEMPO_F32_TABLE_MAX", "17"))
    print(f"backend: {jax.default_backend()}", file=sys.stderr)
    rows = []
    for exp in range(13, max_exp + 1):
        L = 1 << exp
        frame = build(L)
        s64, e64, i64_ = run(frame, "float64")
        s32, e32, i32_ = run(frame, "float32")
        errs = {}
        for stat in STATS:
            a = s32[f"{stat}_x"].to_numpy(float)
            b = s64[f"{stat}_x"].to_numpy(float)
            errs[stat] = float(np.nanmax(np.abs(a - b)))
        errs["ema"] = float(np.nanmax(np.abs(e32 - e64)))
        errs["linear"] = float(np.nanmax(np.abs(i32_ - i64_)))
        rows.append((L, errs))
        print(f"L=2^{exp} done", file=sys.stderr)

    cols = list(STATS) + ["ema", "linear"]
    print("| L | " + " | ".join(cols) + " |")
    print("|---" * (len(cols) + 1) + "|")
    for L, errs in rows:
        cells = " | ".join(f"{errs[c]:.1e}" for c in cols)
        print(f"| 2^{int(np.log2(L))} | {cells} |")


if __name__ == "__main__":
    main()
