"""Pallas scan kernels: interpret-mode correctness vs XLA/numpy oracles.

On CPU the kernels run through the Pallas interpreter; the compiled
path is TPU-only, and tests/test_tpu_compile.py compiles each kernel
for a v5e.  The ladder logic (roll + iota masking) is identical in both modes.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tempo_tpu.ops import pallas_kernels as pk
from tempo_tpu.ops import rolling as rk


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    K, L = 8, 256
    x = rng.standard_normal((K, L)).astype(np.float32)
    valid = rng.random((K, L)) > 0.25
    valid[3] = False          # an all-null series
    valid[4, :10] = False     # leading nulls
    return x, valid


def test_ema_scan_matches_associative_scan(data):
    x, valid = data
    y_pallas = np.asarray(pk.ema_scan(jnp.asarray(x), jnp.asarray(valid),
                                      0.2, interpret=True))
    y_xla = np.asarray(rk.ema_exact(jnp.asarray(x), jnp.asarray(valid), 0.2))
    np.testing.assert_allclose(y_pallas, y_xla, rtol=1e-5, atol=1e-6)


def test_ema_scan_recurrence_oracle(data):
    x, valid = data
    y = np.asarray(pk.ema_scan(jnp.asarray(x), jnp.asarray(valid),
                               0.3, interpret=True))
    K, L = x.shape
    expect = np.zeros((K, L), dtype=np.float64)
    for k in range(K):
        acc = 0.0
        for i in range(L):
            if valid[k, i]:
                acc = 0.7 * acc + 0.3 * float(x[k, i])
            expect[k, i] = acc
    np.testing.assert_allclose(y, expect, rtol=1e-4, atol=1e-5)


def test_last_valid_scan(data):
    x, valid = data
    val, has = pk.last_valid_scan(jnp.asarray(x), jnp.asarray(valid),
                                  interpret=True)
    val, has = np.asarray(val), np.asarray(has)
    idx = np.where(valid, np.arange(x.shape[1])[None, :], -1)
    idx = np.maximum.accumulate(idx, axis=1)
    has_o = idx >= 0
    assert np.array_equal(has, has_o)
    filled_o = np.where(
        has_o,
        np.take_along_axis(np.where(valid, x, 0.0), np.maximum(idx, 0), 1),
        0.0,
    )
    np.testing.assert_allclose(val, filled_o, rtol=1e-6)


def test_cumsum3_matches_numpy(data):
    x, valid = data
    s1, s2, c = pk.cumsum3(jnp.asarray(x), jnp.asarray(valid), interpret=True)
    xz = np.where(valid, x, 0.0).astype(np.float64)
    np.testing.assert_allclose(np.asarray(s1), np.cumsum(xz, -1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.cumsum(xz * xz, -1),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(c), np.cumsum(valid, -1))


def test_windowed_stats_max_window_cap(data):
    """Capped sparse tables must agree with the uncapped path when the
    bound really covers every window."""
    import jax.numpy as jnp2
    from tempo_tpu.ops import rolling as R

    x, valid = data
    K, L = x.shape
    secs = np.cumsum(np.random.default_rng(5).integers(1, 3, (K, L)), -1)
    start, end = R.range_window_bounds(jnp2.asarray(secs.astype(np.int32)),
                                       jnp2.asarray(np.int32(10)))
    max_w = int(np.max(np.asarray(end) - np.asarray(start)))
    full = R.windowed_stats(jnp2.asarray(x), jnp2.asarray(valid), start, end)
    capped = R.windowed_stats(jnp2.asarray(x), jnp2.asarray(valid), start, end,
                              max_window=1 << (max_w - 1).bit_length())
    for k in full:
        np.testing.assert_allclose(np.asarray(full[k]), np.asarray(capped[k]),
                                   rtol=1e-5, atol=1e-6, equal_nan=True,
                                   err_msg=k)


def test_index_scans_match_xla(data):
    _, valid = data
    from tempo_tpu.ops import window_utils as wu

    v = jnp.asarray(valid)
    last_p = np.asarray(pk.last_valid_index_scan(v, interpret=True))
    last_x = np.asarray(wu.last_valid_index_xla(v))
    assert np.array_equal(last_p, last_x)
    first_p = np.asarray(pk.first_valid_index_scan(v, interpret=True))
    first_x = np.asarray(wu.first_valid_index_xla(v))
    assert np.array_equal(first_p, first_x)


def test_range_query_f32_log2_misround():
    """floor(log2) in f32 rounds UP for lengths just below large powers
    of two (2^21-1 -> 21); the RMQ must decrement the level instead of
    reading out-of-window elements."""
    import jax.numpy as jnp
    from tempo_tpu.ops import rolling as R

    L = 2**21 + 8
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1, L)).astype(np.float32)
    i = 2**21 - 2                      # window [0, i] has length 2^21 - 1
    start = np.zeros((1, 1), np.int32)
    end = np.full((1, 1), i + 1, np.int32)
    table = R._sparse_table(jnp.asarray(x), jnp.float32(np.inf), jnp.minimum)
    got = float(np.asarray(R._range_query(table, jnp.asarray(start),
                                          jnp.asarray(end), jnp.minimum))[0, 0])
    assert got == float(x[0, : i + 1].min())


def test_huge_range_window_clamps():
    """rangeBackWindowSecs beyond the int32 rebased-seconds range must
    behave as 'unbounded preceding', not overflow."""
    import pandas as pd
    from tempo_tpu import TSDF

    df = pd.DataFrame({
        "k": ["a"] * 4,
        "event_ts": pd.to_datetime(
            ["2024-01-01", "2024-01-02", "2024-01-03", "2024-01-04"]),
        "v": [1.0, 2.0, 3.0, 4.0],
    })
    r = TSDF(df, "event_ts", ["k"]).withRangeStats(rangeBackWindowSecs=10**12)
    assert r.df["count_v"].tolist() == [1, 2, 3, 4]


def test_fallback_path_f64(data):
    """float64 input must take the XLA fallback and stay exact."""
    x, valid = data
    x64 = x.astype(np.float64)
    val, has = pk.last_valid_scan(jnp.asarray(x64), jnp.asarray(valid))
    assert np.asarray(val).dtype == np.float64


def test_odd_k_padding_plan():
    """K not divisible by any pow2>=8 block must be padded up, not run
    as one whole-array block that can blow the VMEM budget."""
    rng = np.random.default_rng(11)
    K, L = 13, 256
    x = rng.standard_normal((K, L)).astype(np.float32)
    valid = rng.random((K, L)) > 0.3
    y = np.asarray(pk.ema_scan(jnp.asarray(x), jnp.asarray(valid), 0.2,
                               interpret=True))
    y_ref = np.asarray(rk.ema_exact(jnp.asarray(x), jnp.asarray(valid), 0.2))
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
    s1, _, c = pk.cumsum3(jnp.asarray(x), jnp.asarray(valid), interpret=True)
    assert np.asarray(s1).shape == (K, L)
    np.testing.assert_allclose(np.asarray(c), np.cumsum(valid, -1))
    idx = np.asarray(pk.last_valid_index_scan(jnp.asarray(valid),
                                              interpret=True))
    assert idx.shape == (K, L)


def test_plan_feasibility():
    """_plan must refuse shapes whose minimum block exceeds the VMEM
    budget (the caller then stays on XLA), and always emit blocks that
    fit: bk * L * 4 * arrays <= budget."""
    assert pk._plan(1001, 2**17, arrays=12) is None      # [8, 131072] > 14M
    for K, L, arrays in [(1001, 8192, 12), (64, 8192, 16), (7, 128, 12),
                         (1024, 8192, 12), (3 * 1024, 8192, 16)]:
        plan = pk._plan(K, L, arrays=arrays)
        assert plan is not None
        grid, bk, K_pad = plan
        assert K_pad >= K and K_pad % bk == 0 and grid[0] * bk == K_pad
        if grid[0] > 1:
            assert bk % 8 == 0
            assert bk * L * 4 * arrays <= pk._VMEM_BUDGET
