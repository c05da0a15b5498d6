"""CPU denominators for the headline benchmark (VERDICT r2 item 3).

The north star (BASELINE.json) is ">=20x vs Spark local-mode" on the
AS-OF join + rolling-stats + EMA pipeline.  pyspark is not installed in
this image, so the denominator must be the strongest CPU implementation
of the same op set we can actually run.  This module measures EVERY
available oracle and reports the best; ``bench.py`` divides by the
strongest, not the friendliest.

Oracles (this image has ONE cpu — ``multiprocessing.cpu_count() == 1``
— so process-sharded pandas is pointless; duckdb/polars/numba are
absent, checked 2026-07-30):

* ``pandas`` — ``merge_asof(by=key)`` + groupby ``rolling('10s')``
  mean/std + groupby ``ewm(alpha).mean()``: the idiomatic single-node
  answer, and a *stronger* per-row baseline than Spark local-mode
  (pandas beats Spark local mode per row).
* ``numpy`` — a hand-vectorised implementation of the same ops:
  searchsorted + last-valid-scan AS-OF (the reference's
  ``__getLastRightRow`` semantics), prefix-sum windowed mean/std with
  searchsorted range bounds, and the exact adjusted EWM via two
  ``scipy.signal.lfilter`` IIR recurrences.  Typically 3-6x faster
  per row than pandas; its outputs are asserted against pandas on
  every run, so the speed is not bought with wrong answers.

Run directly for one JSON line: {"oracles": {...rows/sec},
"strongest": name}.
"""

import json
import time

import numpy as np

WINDOW_SECS = 10.0
EWM_ALPHA = 0.2


# ----------------------------------------------------------------------
# pandas oracle
# ----------------------------------------------------------------------

def pandas_pipeline(left, right):
    import pandas as pd

    joined = pd.merge_asof(left, right, on="ts", by="key")
    g = joined.sort_values(["key", "ts"]).set_index("ts").groupby("key")["x"]
    roll = g.rolling("10s")
    mean = roll.mean()
    std = roll.std()
    ewm = joined.groupby("key")["x"].transform(
        lambda s: s.ewm(alpha=EWM_ALPHA).mean()
    )
    return joined, mean, std, ewm


# ----------------------------------------------------------------------
# numpy/scipy oracle — same ops, vectorised
# ----------------------------------------------------------------------

def numpy_pipeline(l_ts, l_x, l_key_starts, r_ts, r_vals, r_key_starts):
    """Per-key-sorted flat arrays in, joined cols + mean/std/ewm out.

    ``*_key_starts`` are [K+1] offsets of each key's row range; both
    sides are time-sorted within each key (the merge_asof precondition).
    """
    from scipy.signal import lfilter

    n = len(l_ts)
    K = len(l_key_starts) - 1
    joined = np.empty((len(r_vals), n))
    mean = np.empty(n)
    std = np.empty(n)
    ewm = np.empty(n)
    one_minus = 1.0 - EWM_ALPHA
    b, a = [1.0], [1.0, -one_minus]
    w_ns = np.int64(WINDOW_SECS * 1e9)

    for k in range(K):
        ls, le = l_key_starts[k], l_key_starts[k + 1]
        rs, re = r_key_starts[k], r_key_starts[k + 1]
        lt = l_ts[ls:le]
        lx = l_x[ls:le]
        # AS-OF: last right row at-or-before each left row.  Row-based
        # (nulls included), matching pandas merge_asof exactly — the
        # TPU pipeline additionally does per-column last-non-null
        # (skipNulls), so this denominator does no MORE work than the
        # numerator.
        pos = np.searchsorted(r_ts[rs:re], lt, side="right") - 1
        for c in range(len(r_vals)):
            rv = r_vals[c][rs:re]
            joined[c, ls:le] = np.where(
                pos >= 0, rv[np.maximum(pos, 0)], np.nan
            )
        # rolling mean/std over the trailing 10s range window:
        # prefix sums + searchsorted bounds.  pandas time-based rolling
        # is closed='right' — the window is (t-10s, t], excluding the
        # left edge (Spark's rangeBetween includes it; the denominator
        # follows the pandas oracle it is checked against)
        s = np.searchsorted(lt, lt - w_ns, side="right")
        c1 = np.concatenate([[0.0], np.cumsum(lx)])
        c2 = np.concatenate([[0.0], np.cumsum(lx * lx)])
        e = np.arange(1, le - ls + 1)
        cnt = e - s
        s1 = c1[e] - c1[s]
        s2 = c2[e] - c2[s]
        m = s1 / cnt
        mean[ls:le] = m
        var = (s2 - s1 * s1 / cnt) / np.maximum(cnt - 1, 1)
        std[ls:le] = np.where(cnt > 1, np.sqrt(np.maximum(var, 0.0)),
                              np.nan)
        # adjusted EWM y_t = num_t / den_t, both first-order IIRs
        num = lfilter(b, a, lx * EWM_ALPHA)
        den = lfilter(b, a, np.full(le - ls, EWM_ALPHA))
        ewm[ls:le] = num / den
    return joined, mean, std, ewm


# ----------------------------------------------------------------------
# harness
# ----------------------------------------------------------------------

def _frames(data, sub):
    import pandas as pd

    l_ts, l_secs, x, valid, r_ts, r_valids, r_values = data
    L = l_ts.shape[1]
    ks = np.repeat(np.arange(sub), L)
    left = pd.DataFrame({
        "key": ks,
        "ts": pd.to_datetime(l_ts[:sub].ravel()),
        "x": x[:sub].ravel().astype(np.float64),
    })
    C = r_valids.shape[0]
    rv = [np.where(r_valids[c, :sub], r_values[c, :sub], np.nan).ravel()
          for c in range(C)]
    right = pd.DataFrame({
        "key": ks,
        "ts": pd.to_datetime(r_ts[:sub].ravel()),
        **{f"v{c}": rv[c] for c in range(C)},
    })
    left = left.sort_values(["ts", "key"], kind="stable")
    right = right.sort_values(["ts", "key"], kind="stable")
    return left, right


def measure(data, sub=32, reps=3):
    """rows/sec of every oracle on a ``sub``-series slice; asserts the
    numpy oracle agrees with pandas before trusting its speed."""
    l_ts, l_secs, x, valid, r_ts, r_valids, r_values = data
    L = l_ts.shape[1]
    left, right = _frames(data, sub)
    n_rows = sub * L

    best_pd = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        pd_out = pandas_pipeline(left, right)
        best_pd = min(best_pd, time.perf_counter() - t0)

    # flat per-key-sorted inputs for the numpy oracle (layout prep is
    # not timed for either oracle: pandas gets pre-sorted frames too)
    starts = np.arange(sub + 1, dtype=np.int64) * L
    nl_ts = l_ts[:sub].ravel()
    nl_x = x[:sub].ravel().astype(np.float64)
    nr_ts = r_ts[:sub].ravel()
    nr_vals = [np.where(r_valids[c, :sub], r_values[c, :sub],
                        np.nan).ravel().astype(np.float64)
               for c in range(r_valids.shape[0])]

    best_np = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np_out = numpy_pipeline(nl_ts, nl_x, starts, nr_ts, nr_vals,
                                starts)
        best_np = min(best_np, time.perf_counter() - t0)

    _check_agreement(pd_out, np_out, sub, L)
    return {
        "pandas": n_rows / best_pd,
        "numpy_vectorized": n_rows / best_np,
    }


def _check_agreement(pd_out, np_out, sub, L):
    joined_pd, mean_pd, std_pd, ewm_pd = pd_out
    joined_np, mean_np, std_np, ewm_np = np_out
    # pandas frames are (ts, key)-sorted; numpy flat arrays are
    # (key, ts)-sorted — compare in (key, ts) order
    order = np.lexsort((joined_pd["ts"].to_numpy(),
                        joined_pd["key"].to_numpy()))
    for c in range(joined_np.shape[0]):
        np.testing.assert_allclose(
            joined_pd[f"v{c}"].to_numpy()[order], joined_np[c],
            rtol=1e-9, atol=1e-12, equal_nan=True,
        )
    np.testing.assert_allclose(mean_pd.to_numpy(), mean_np,
                               rtol=1e-9, atol=1e-12, equal_nan=True)
    np.testing.assert_allclose(std_pd.to_numpy(), std_np,
                               rtol=1e-9, atol=1e-9, equal_nan=True)
    np.testing.assert_allclose(ewm_pd.to_numpy()[order], ewm_np,
                               rtol=1e-9, atol=1e-12, equal_nan=True)


def strongest(data, sub=32):
    rates = measure(data, sub)
    name = max(rates, key=rates.get)
    return name, rates[name], rates


if __name__ == "__main__":
    import bench

    data = bench.make_data()
    name, rate, rates = strongest(data)
    print(json.dumps({
        "oracles": {k: round(v) for k, v in rates.items()},
        "strongest": name,
    }))
