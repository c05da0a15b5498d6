"""Bucket (resample) VMEM kernels: interpret-mode parity vs the XLA
windowed/segment forms and numpy oracles.

The compiled path is TPU-only: tests/test_tpu_compile.py compiles it
for a v5e, and no benchmark cell runs it on the chip yet.  The ladder
logic (segmented scan + tail broadcast, fused head/EMA) is identical in
interpret mode.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tempo_tpu.ops import rolling as rk
from tempo_tpu.ops.pallas_bucket import (
    bucket_stats_pallas, resample_ema_pallas,
)

STATS = ("mean", "count", "min", "max", "sum", "stddev", "zscore")


def _case(rng, K, L, gap_hi=3, step=60, masked=False):
    secs = np.cumsum(rng.integers(1, gap_hi, (K, L)), -1).astype(np.int64)
    x = rng.standard_normal((K, L)).astype(np.float32)
    valid = rng.random((K, L)) > (0.3 if masked else 0.0)
    bid = (secs // step).astype(np.int32)
    return secs, bid, x, valid


@pytest.mark.parametrize("K,L,masked", [(4, 256, False), (3, 512, True),
                                        (6, 128, True)])
def test_bucket_stats_matches_windowed(K, L, masked):
    """Oracle: windowed_stats with searchsorted bucket bounds — the
    XLA form the kernel replaces (dist.py:_bucket_heads semantics)."""
    rng = np.random.default_rng(K * 100 + L)
    secs, bid, x, valid = _case(rng, K, L, masked=masked)
    start = np.stack([np.searchsorted(bid[k], bid[k], "left")
                      for k in range(K)]).astype(np.int32)
    end = np.stack([np.searchsorted(bid[k], bid[k], "right")
                    for k in range(K)]).astype(np.int32)
    want = rk.windowed_stats(jnp.asarray(x), jnp.asarray(valid),
                             jnp.asarray(start), jnp.asarray(end))
    got = bucket_stats_pallas(jnp.asarray(bid), jnp.asarray(x),
                              jnp.asarray(valid), interpret=True)
    for k in STATS:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(want[k]), rtol=2e-5,
            atol=2e-5, equal_nan=True, err_msg=k,
        )


def test_bucket_stats_numpy_oracle():
    """Independent oracle: per-bucket numpy reductions."""
    rng = np.random.default_rng(0)
    K, L = 3, 256
    secs, bid, x, valid = _case(rng, K, L, masked=True)
    got = bucket_stats_pallas(jnp.asarray(bid), jnp.asarray(x),
                              jnp.asarray(valid), interpret=True)
    for k in range(K):
        for b in np.unique(bid[k]):
            sel = bid[k] == b
            win = x[k, sel & valid[k]].astype(np.float64)
            rows = np.flatnonzero(sel)
            cnt = np.asarray(got["count"])[k, rows]
            np.testing.assert_allclose(cnt, len(win), err_msg="count")
            if len(win):
                np.testing.assert_allclose(
                    np.asarray(got["mean"])[k, rows], win.mean(),
                    rtol=2e-5, atol=2e-5,
                )
                np.testing.assert_allclose(
                    np.asarray(got["min"])[k, rows], win.min(), rtol=1e-6
                )
                np.testing.assert_allclose(
                    np.asarray(got["max"])[k, rows], win.max(), rtol=1e-6
                )
            if len(win) > 1:
                np.testing.assert_allclose(
                    np.asarray(got["stddev"])[k, rows],
                    win.std(ddof=1), rtol=2e-4, atol=2e-4,
                )


def test_resample_ema_matches_xla_body():
    """Oracle: the exact XLA op sequence of bench config 3 (bucket
    change head + packed-in-place floor resample + exact EMA)."""
    from tempo_tpu.ops import rolling as rkops

    rng = np.random.default_rng(3)
    K, L, step, alpha = 5, 512, 60, 0.2
    secs, _, x, valid = _case(rng, K, L, masked=True, step=step)

    bucket = secs // step
    head = np.concatenate(
        [np.ones_like(bucket[:, :1], bool),
         bucket[:, 1:] != bucket[:, :-1]], axis=-1,
    ) & valid
    want_res = np.where(head, x, np.nan)
    want_ema = np.asarray(rkops.ema_exact(
        jnp.asarray(x), jnp.asarray(head), alpha
    ))

    res, ema = resample_ema_pallas(
        jnp.asarray(secs.astype(np.int32)), jnp.asarray(x),
        jnp.asarray(valid), step=step, alpha=alpha, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(res), want_res, equal_nan=True)
    np.testing.assert_allclose(np.asarray(ema), want_ema, rtol=2e-5,
                               atol=2e-6)


def test_resample_ema_rejects_non_integral_step():
    x = jnp.ones((1, 128), jnp.float32)
    s = jnp.zeros((1, 128), jnp.int32)
    v = jnp.ones((1, 128), bool)
    with pytest.raises(ValueError, match="integral step"):
        resample_ema_pallas(s, x, v, step=0.5, alpha=0.2, interpret=True)
    with pytest.raises(ValueError, match="integral step"):
        resample_ema_pallas(s, x, v, step=90.7, alpha=0.2, interpret=True)


def test_resample_ema_bucket_division_boundaries():
    """In-kernel bucketing is exact i32 division — including the range
    where the first revision's f32-reciprocal multiply misassigned
    rows one second below a bucket boundary (secs ≈ 10.2M+; the first
    failing value was 10_186_199, code-review r4)."""
    step = 60
    vals = np.array([0, 59, 60, 61, 119, 120, 10_186_199, 10_186_200,
                     2**24 - 64, 2**24 - 60, 2**30, 2**30 + 59,
                     2**31 - 128], np.int64)
    secs = np.sort(np.pad(vals, (0, 128 - len(vals)),
                          constant_values=2**31 - 100))[None, :]
    x = np.ones((1, 128), np.float32)
    valid = np.ones((1, 128), bool)
    res, _ = resample_ema_pallas(
        jnp.asarray(secs.astype(np.int32)), jnp.asarray(x),
        jnp.asarray(valid), step=step, alpha=0.2, interpret=True,
    )
    bucket = secs // step
    head = np.concatenate(
        [np.ones_like(bucket[:, :1], bool),
         bucket[:, 1:] != bucket[:, :-1]], axis=-1,
    )
    np.testing.assert_array_equal(~np.isnan(np.asarray(res)), head)


# ----------------------------------------------------------------------
# Multi-column packing + explicit DMA ring (ISSUE 6): bitwise identity
# against the single-column / BlockSpec forms.
# ----------------------------------------------------------------------

def test_bucket_packed_matches_single_column_bitwise():
    from tempo_tpu.ops.pallas_bucket import bucket_stats_packed

    rng = np.random.default_rng(31)
    K, L, C = 4, 256, 3
    _, bid, _, _ = _case(rng, K, L)
    xs = rng.standard_normal((C, K, L)).astype(np.float32)
    valids = rng.random((C, K, L)) > 0.3
    valids[2, 1] = False                     # a fully-null column row
    packed = bucket_stats_packed(jnp.asarray(bid), jnp.asarray(xs),
                                 jnp.asarray(valids), interpret=True)
    for c in range(C):
        single = bucket_stats_pallas(jnp.asarray(bid), jnp.asarray(xs[c]),
                                     jnp.asarray(valids[c]),
                                     interpret=True)
        for k in STATS:
            np.testing.assert_array_equal(
                np.asarray(packed[k][c]), np.asarray(single[k]),
                err_msg=f"c={c}:{k}")


def test_bucket_packed_width1_matches_single_column():
    """A [1, K, L] stack (bucket_pack_budget returns 1 for infeasible /
    single-column cases) must run — the dispatch squeezes to the rank-2
    form — and match the single-column call bitwise (code-review r5:
    the rank-2 spec path crashed at trace time on width-1 stacks)."""
    from tempo_tpu.ops.pallas_bucket import bucket_stats_packed

    rng = np.random.default_rng(41)
    K, L = 4, 256
    _, bid, x, valid = _case(rng, K, L, masked=True)
    packed = bucket_stats_packed(jnp.asarray(bid), jnp.asarray(x)[None],
                                 jnp.asarray(valid)[None],
                                 interpret=True)
    single = bucket_stats_pallas(jnp.asarray(bid), jnp.asarray(x),
                                 jnp.asarray(valid), interpret=True)
    for k in STATS:
        assert packed[k].shape == (1,) + single[k].shape
        np.testing.assert_array_equal(np.asarray(packed[k][0]),
                                      np.asarray(single[k]), err_msg=k)


def test_bucket_stats_multi_matches_per_column():
    """The production multi-column dispatcher (dist._bucket_stats_fn /
    _resample_fn reductions) must agree bitwise with per-column
    bucket_stats on any backend — including C=1 stacks."""
    rng = np.random.default_rng(43)
    K, L, C = 4, 256, 3
    _, bid, _, _ = _case(rng, K, L)
    xs = rng.standard_normal((C, K, L)).astype(np.float32)
    valids = rng.random((C, K, L)) > 0.3
    start = np.stack([np.searchsorted(bid[k], bid[k], "left")
                      for k in range(K)]).astype(np.int32)
    end = np.stack([np.searchsorted(bid[k], bid[k], "right")
                    for k in range(K)]).astype(np.int32)
    args = (jnp.asarray(bid), jnp.asarray(start), jnp.asarray(end))
    for width in (C, 1):
        multi = rk.bucket_stats_multi(args[0], jnp.asarray(xs[:width]),
                                      jnp.asarray(valids[:width]),
                                      args[1], args[2])
        for c in range(width):
            want = rk.bucket_stats(args[0], jnp.asarray(xs[c]),
                                   jnp.asarray(valids[c]),
                                   args[1], args[2])
            for k in STATS:
                np.testing.assert_array_equal(
                    np.asarray(multi[k][c]), np.asarray(want[k]),
                    err_msg=f"width={width} c={c}:{k}")


@pytest.mark.parametrize("depth", [3, 4])
def test_bucket_and_resample_ring_bitwise(monkeypatch, depth):
    from tempo_tpu.ops.pallas_bucket import bucket_stats_packed

    rng = np.random.default_rng(33)
    K, L = 5, 256
    secs, bid, x, valid = _case(rng, K, L, masked=True)
    monkeypatch.delenv("TEMPO_TPU_DMA_BUFFERS", raising=False)
    base_b = bucket_stats_pallas(jnp.asarray(bid), jnp.asarray(x),
                                 jnp.asarray(valid), interpret=True)
    base_r = resample_ema_pallas(
        jnp.asarray(secs.astype(np.int32)), jnp.asarray(x),
        jnp.asarray(valid), step=60, alpha=0.2, interpret=True)
    monkeypatch.setenv("TEMPO_TPU_DMA_BUFFERS", str(depth))
    ring_b = bucket_stats_pallas(jnp.asarray(bid), jnp.asarray(x),
                                 jnp.asarray(valid), interpret=True)
    ring_r = resample_ema_pallas(
        jnp.asarray(secs.astype(np.int32)), jnp.asarray(x),
        jnp.asarray(valid), step=60, alpha=0.2, interpret=True)
    for k in STATS:
        np.testing.assert_array_equal(np.asarray(ring_b[k]),
                                      np.asarray(base_b[k]), err_msg=k)
    for a, b, name in zip(ring_r, base_r, ("res", "ema")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
