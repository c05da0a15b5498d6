"""Capital-markets flow: skewed NBBO quotes <-> trades AS-OF join.

Mirrors bench configs 4-5 (the reference's capital-markets
reference architecture): a Zipf-skewed symbol universe where a handful
of tickers carry most of the volume — exactly the shape Spark needs the
``tsPartitionVal`` skew join for (reference tsdf.py:164-190).  Shows:

* the plain vs skew-partitioned asofJoin agreeing row-for-row,
* quote staleness audit via the joined quote timestamps,
* per-symbol VWAP bars on the trades.

Run: python examples/nbbo.py  (TPU or JAX_PLATFORMS=cpu)
"""

import os
import sys
import time

import numpy as np
import pandas as pd

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from tempo_tpu import TSDF

N_SYMBOLS = 50
N_QUOTES = 200_000
N_TRADES = 50_000


def make_tape(seed=7):
    rng = np.random.default_rng(seed)
    # Zipf-skewed symbol draw: symbol 0 carries ~100x symbol 49's flow
    weights = 1.0 / (np.arange(N_SYMBOLS) + 1.0)
    weights /= weights.sum()
    syms = np.array([f"SYM{i:03d}" for i in range(N_SYMBOLS)])

    def tape(n, cols):
        sym = rng.choice(N_SYMBOLS, size=n, p=weights)
        ts = (pd.Timestamp("2024-01-02 09:30").value
              + np.sort(rng.integers(0, 6.5 * 3600 * 1e9, size=n).astype(np.int64)))
        df = pd.DataFrame({"symbol": syms[sym],
                           "event_ts": pd.to_datetime(ts)})
        mid = 100.0 + sym * 2.0
        for c in cols:
            noise = rng.standard_normal(n)
            df[c] = mid + noise if c != "trade_qty" else rng.integers(1, 500, n)
        return df

    quotes = tape(N_QUOTES, ["bid_pr", "ask_pr"])
    trades = tape(N_TRADES, ["trade_pr", "trade_qty"])
    return quotes, trades


def main():
    quotes, trades = make_tape()
    q = TSDF(quotes, "event_ts", ["symbol"])
    t = TSDF(trades, "event_ts", ["symbol"])

    t0 = time.perf_counter()
    plain = t.asofJoin(q, right_prefix="quote")
    print(f"plain asofJoin: {len(plain.df)} rows in {time.perf_counter()-t0:.2f}s")

    t0 = time.perf_counter()
    skew = t.asofJoin(q, right_prefix="quote", tsPartitionVal=1800,
                      fraction=0.5, suppress_null_warning=True)
    print(f"skew  asofJoin: {len(skew.df)} rows in {time.perf_counter()-t0:.2f}s")

    both = plain.df.merge(skew.df, on=["symbol", "event_ts"], suffixes=("", "_skew"))
    same = (both["quote_bid_pr"].fillna(-1) == both["quote_bid_pr_skew"].fillna(-1)).all()
    print(f"plain == skew (where lookback covered): {bool(same)}")

    staleness = (plain.df["event_ts"] - plain.df["quote_event_ts"]).dt.total_seconds()
    print(f"median quote staleness at trade time: {staleness.median():.2f}s")

    vw = t.vwap(frequency="H", volume_col="trade_qty", price_col="trade_pr")
    print("hourly VWAP (head):")
    print(vw.df.head(5).to_string(index=False))


if __name__ == "__main__":
    main()
