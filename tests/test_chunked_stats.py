"""Lane-chunked range stats and the carry-passing exact EMA against a
float64 numpy oracle of the reference's semantics.

The chunked forms take series longer than one VMEM block or chunk
block (ops/rolling.range_stats_chunk, driven by rolling.py;
ops/pallas_kernels.ema_chunked).  Here the chunk sizes are forced small
through the module constants, and the Pallas kernel runs in interpret
mode.  The oracle follows benchmark/reference/withRangeStats.py and
EMA.py: a row's window holds every row of its series whose whole second
lies within the window before its own, rows later in the same second
too; null values count in no stat and carry the EMA forward.
"""

import types

import numpy as np
import pandas as pd
import pytest

from tempo_tpu import TSDF, packing
from tempo_tpu import rolling
from tempo_tpu.ops import pallas_kernels as pk
from tempo_tpu.ops import rolling as rk
from tempo_tpu.ops import sortmerge as sm

STATS = ("mean", "count", "min", "max", "sum", "stddev", "zscore")


def _frame(lengths, rate_hz, seed, null_share=0.0, offset=9.8):
    """Flat sorted (key, ts_ns, x, valid): ``lengths[k]`` rows at about
    ``rate_hz`` rows a second, whole milliseconds with ties."""
    rng = np.random.default_rng(seed)
    keys, ts, xs = [], [], []
    for k, n in enumerate(lengths):
        span_ms = max(1, int(n * 1000 / rate_hz))
        ts.append(np.sort(rng.integers(0, span_ms, n)) * 1_000_000
                  + 1_600_000_000 * 10**9)
        keys.append(np.full(n, k))
        xs.append(offset + rng.normal(size=n))
    key = np.concatenate(keys).astype(np.int64)
    t = np.concatenate(ts).astype(np.int64)
    x = np.concatenate(xs)
    valid = rng.random(len(x)) >= null_share
    return key, t, np.where(valid, x, np.nan), valid


def _oracle_stats(key, ts_ns, x, valid, w):
    sec = ts_ns // 10**9
    out = {s: np.full(len(x), np.nan) for s in STATS}
    for k in np.unique(key):
        idx = np.flatnonzero(key == k)
        s = sec[idx]
        lo = np.searchsorted(s, s - w, side="left")
        hi = np.searchsorted(s, s, side="right")
        for j, (a, b) in enumerate(zip(lo, hi)):
            v = x[idx[a:b]][valid[idx[a:b]]]
            i = idx[j]
            out["count"][i] = len(v)
            if len(v):
                out["mean"][i] = v.mean()
                out["min"][i], out["max"][i] = v.min(), v.max()
                out["sum"][i] = v.sum()
            if len(v) > 1:
                out["stddev"][i] = v.std(ddof=1)
                if valid[i]:
                    out["zscore"][i] = (x[i] - v.mean()) / v.std(ddof=1)
    return out


def _chunked_stats(key, ts_ns, x, valid, w, n_series):
    layout = packing.build_layout_from_codes(key, ts_ns, None, n_series)
    tsdf = types.SimpleNamespace(
        layout=layout,
        numeric_flat=lambda c: (x[layout.order], valid[layout.order]))
    rb = packing.layout_rowbounds(layout, w)
    cols = rolling._range_stats_chunked(tsdf, ["x"], rb, w)
    # back to the input's row order
    back = np.empty_like(layout.order)
    back[layout.order] = np.arange(len(back))
    return {s: cols[f"{s}_x"][back] for s in STATS}, rb, layout


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunk blocks of at least 256 lanes, 1.5 halos long (cores
    shorter than a halo where the halo passes 512)."""
    monkeypatch.setattr(rk, "RANGE_BLOCK_LANES", 256)
    monkeypatch.setattr(rk, "RANGE_BLOCK_HALOS", 1.5)
    monkeypatch.setattr(pk, "EMA_CHUNK_LANES", 128)
    monkeypatch.setattr(pk, "EMA_CALL_CHUNKS", 2)


RANGE_CASES = {
    # name: (series lengths, rows a second, window s, null share)
    "one_chunk_edge": ([700], 20, 5, 0.0),
    "several_chunk_edges": ([3000], 20, 5, 0.0),
    "halo_longer_than_a_chunk": ([2500], 60, 10, 0.0),
    "ties_at_chunk_edges": ([2000], 150, 2, 0.0),
    "nulls": ([1500, 1200], 30, 6, 0.3),
    "ragged_series": ([900, 130, 2100, 3], 25, 4, 0.1),
    "series_shorter_than_a_chunk": ([40, 1800], 20, 3, 0.0),
}


@pytest.mark.parametrize("case", sorted(RANGE_CASES))
def test_chunked_range_stats_match_oracle(small_chunks, case):
    lengths, rate, w, nulls = RANGE_CASES[case]
    key, ts_ns, x, valid = _frame(lengths, rate, seed=len(case),
                                  null_share=nulls)
    got, rb, layout = _chunked_stats(key, ts_ns, x, valid, w, len(lengths))
    block, halo, _ = rk.range_chunk_plan(*rb)
    core = block - halo
    assert int(layout.lengths.max()) > core      # more than one chunk
    if case == "halo_longer_than_a_chunk":
        assert rb[0] > core
    want = _oracle_stats(key, ts_ns, x, valid, w)
    for s in STATS:
        np.testing.assert_allclose(got[s], want[s], rtol=1e-9, atol=1e-9,
                                   equal_nan=True, err_msg=s)


def test_chunked_range_stats_with_an_empty_series(small_chunks):
    """A layout whose middle series has no rows (its key is known, as
    a categorical's unused level is): that series takes no chunk."""
    key, ts_ns, x, valid = _frame([800, 600], 20, seed=7)
    key = np.where(key == 1, 2, key)                  # series 1 empty
    got, _, layout = _chunked_stats(key, ts_ns, x, valid, 5, 3)
    assert layout.lengths.tolist() == [800, 0, 600]
    want = _oracle_stats(key, ts_ns, x, valid, 5)
    for s in STATS:
        np.testing.assert_allclose(got[s], want[s], rtol=1e-9, atol=1e-9,
                                   equal_nan=True, err_msg=s)


@pytest.mark.parametrize("w", [0, 1, 7, 10**6])
def test_window_bounds_match_a_search_per_row(w):
    key, ts_ns, _, _ = _frame([500, 40, 1300], 90, seed=w % 97)
    layout = packing.build_layout_from_codes(key, ts_ns, None, 3)
    start, end = packing.layout_window_bounds(layout, w)
    sec = layout.ts_ns // 10**9
    behind = ahead = 0
    for k in range(3):
        a, b = layout.starts[k], layout.starts[k + 1]
        s = sec[a:b]
        lo = np.searchsorted(s, s - w, side="left")
        hi = np.searchsorted(s, s, side="right")
        assert (start[a:b] == lo).all() and (end[a:b] == hi).all()
        idx = np.arange(b - a)
        behind = max(behind, int((idx - lo).max()))
        ahead = max(ahead, int((hi - 1 - idx).max()))
    assert packing.layout_rowbounds(layout, w) == (behind, ahead)


def test_chunk_program_serves_every_series_length(small_chunks):
    """Two frames whose series differ in length but not in window reach
    run one compiled chunk program."""
    runs = []
    for lengths in ([2000], [3100, 900]):
        key, ts_ns, x, valid = _frame(lengths, 20, seed=3)
        _, rb, _ = _chunked_stats(key, ts_ns, x, valid, 5, len(lengths))
        runs.append((rk.range_chunk_plan(*rb),
                     rk.range_stats_chunk._cache_size()))
    assert runs[0][0] == runs[1][0]
    assert runs[1][1] == runs[0][1]


def _oracle_ema(x, valid, a):
    y = np.zeros(x.shape)
    prev = np.zeros(x.shape[0])
    for t in range(x.shape[1]):
        prev = np.where(valid[:, t], a * np.nan_to_num(x[:, t])
                        + (1 - a) * prev, prev)
        y[:, t] = prev
    return y


EMA_CASES = {
    # name: (series, lanes, null share)
    "one_chunk": (3, 100, 0.0),
    "one_call": (2, 256, 0.0),
    "several_calls": (9, 1300, 0.0),
    "nulls": (4, 700, 0.3),
    "more_series_than_a_block": (40, 300, 0.1),
    "empty": (2, 0, 0.0),
}


@pytest.mark.parametrize("case", sorted(EMA_CASES))
def test_chunked_ema_matches_oracle(small_chunks, case):
    K, L, nulls = EMA_CASES[case]
    rng = np.random.default_rng(L + K)
    x = (9.8 + rng.normal(size=(K, L))).astype(np.float32)
    valid = rng.random((K, L)) >= nulls
    got = pk.ema_chunked(np.where(valid, x, np.nan), valid, 0.2)
    assert got.shape == (K, L)
    np.testing.assert_allclose(got, _oracle_ema(x.astype(np.float64),
                                                valid, 0.2),
                               rtol=2e-6, atol=2e-6)


def test_chunked_ema_serves_every_series_length(small_chunks):
    """Two lengths, one compiled chunk program (the calls all have one
    shape)."""
    sizes = []
    for L in (300, 1000):
        x = np.ones((3, L), np.float32)
        pk.ema_chunked(x, np.ones((3, L), bool), 0.3)
        sizes.append(pk._ema_chunk_call._cache_size())
    assert sizes[0] == sizes[1]


def test_frame_chain_takes_the_chunked_forms(small_chunks, monkeypatch):
    """``TSDF.withRangeStats(...).EMA(..., exact=True)`` at a small size
    that puts both ops on their chunked forms."""
    monkeypatch.setattr(sm, "use_sort_kernels", lambda: True)
    monkeypatch.setattr(rk, "SHIFTED_MAX_ROWS", 0)
    monkeypatch.setattr(pk, "ema_chunked_ok", lambda x: True)
    engines = []
    pick = rolling.plan_range_engine

    def spy(*a, **k):
        got = pick(*a, **k)
        engines.append(got[0])
        return got
    monkeypatch.setattr(rolling, "plan_range_engine", spy)
    key, ts_ns, x, valid = _frame([1500, 700], 20, seed=11, null_share=0.1)
    df = pd.DataFrame({"sym": np.array(["a", "b"])[key],
                       "ts": pd.to_datetime(ts_ns), "x": x})
    df = df.sample(frac=1.0, random_state=0)        # unsorted input
    out = (TSDF(df, "ts", ["sym"])
           .withRangeStats(colsToSummarize=["x"], rangeBackWindowSecs=5)
           .EMA("x", exact=True).df)
    assert engines == ["chunked"]
    # the oracle over the output's own rows (rows tied to the
    # millisecond may come out in either order)
    key = (out["sym"] == "b").to_numpy().astype(np.int64)
    ts_ns = out["ts"].to_numpy("datetime64[ns]").astype(np.int64)
    x = out["x"].to_numpy(float)
    valid = ~np.isnan(x)
    want = _oracle_stats(key, ts_ns, x, valid, 5)
    for s in STATS:
        np.testing.assert_allclose(out[f"{s}_x"].to_numpy(float), want[s],
                                   rtol=1e-9, atol=1e-9, equal_nan=True,
                                   err_msg=s)
    for k in (0, 1):
        sel = key == k
        ema = _oracle_ema(np.nan_to_num(x[sel])[None], valid[sel][None],
                          0.2)[0]
        np.testing.assert_allclose(out["EMA_x"].to_numpy()[sel], ema,
                                   rtol=2e-6, atol=2e-6)
