"""f32 compute-policy numerics: quantified, not assumed (VERDICT r1 #5).

On TPU the metric kernels compute in float32 (f64 is ~25x emulated,
packing.compute_dtype); the reference computes in f64 on the JVM
(tsdf.py:709-718).  This tier runs the same frame-level ops under
``TEMPO_TPU_COMPUTE_DTYPE=float32`` against the f64 run and asserts
the divergence stays inside the documented bounds
(``tempo_tpu.testing.numerics.F32_BOUNDS``; ``tools/f32_error_table.py``
measures the table at L=2^13..2^17).

The bound model: prefix sums are mean-centred per series, so window
aggregates of W values drift like W * eps_f32 * |x| (not L * eps);
stddev inherits sqrt cancellation and is the loosest.
"""

import numpy as np
import pandas as pd
import pytest

from tempo_tpu import TSDF
from tempo_tpu.testing.numerics import F32_BOUNDS

L = 8192          # rows per key in this tier (the tool sweeps 2^13..2^17)
K = 4

# Asserted ceilings for standard-normal data at L=8192, 32-row windows
# (~10x headroom, so the tier is a tripwire for accumulation-order
# regressions, not noise).
BOUNDS = F32_BOUNDS


@pytest.fixture(scope="module")
def frame():
    rng = np.random.default_rng(42)
    n = K * L
    secs = np.concatenate(
        [np.cumsum(rng.integers(1, 3, size=L)) for _ in range(K)]
    )
    df = pd.DataFrame({
        "k": np.repeat(np.arange(K), L),
        "event_ts": pd.to_datetime(secs * 1_000_000_000),
        "x": rng.standard_normal(n),
        "gappy": np.where(rng.random(n) > 0.3, rng.standard_normal(n),
                          np.nan),
    })
    return TSDF(df, "event_ts", ["k"])


def _run(frame, monkeypatch, dtype):
    monkeypatch.setenv("TEMPO_TPU_COMPUTE_DTYPE", dtype)
    # packed caches key on dtype, so the same frame serves both runs
    stats = frame.withRangeStats(colsToSummarize=["x"],
                                 rangeBackWindowSecs=10).df
    ema = frame.EMA("x", exact=True).df
    interp = frame.interpolate(freq="5 seconds", func="mean",
                               target_cols=["gappy"], method="linear").df
    return stats, ema, interp


def test_f32_within_documented_bounds(frame, monkeypatch):
    s64, e64, i64_ = _run(frame, monkeypatch, "float64")
    s32, e32, i32_ = _run(frame, monkeypatch, "float32")

    for stat in ("mean", "count", "min", "max", "sum", "stddev", "zscore"):
        a = s32[f"{stat}_x"].to_numpy(float)
        b = s64[f"{stat}_x"].to_numpy(float)
        err = np.nanmax(np.abs(a - b)) if len(a) else 0.0
        assert err <= BOUNDS[stat], f"{stat}: {err} > {BOUNDS[stat]}"
        # and NaN patterns must agree exactly (null semantics are not
        # allowed to drift with precision)
        assert (np.isnan(a) == np.isnan(b)).all(), stat

    err = np.nanmax(np.abs(e32["EMA_x"].to_numpy(float)
                           - e64["EMA_x"].to_numpy(float)))
    assert err <= BOUNDS["ema"], f"ema: {err}"

    a = i32_["gappy"].to_numpy(float)
    b = i64_["gappy"].to_numpy(float)
    assert len(a) == len(b)
    err = np.nanmax(np.abs(a - b))
    assert err <= BOUNDS["linear"], f"linear: {err}"
    assert (np.isnan(a) == np.isnan(b)).all()


def test_f32_pallas_ladder_matches_xla_scan(frame, monkeypatch):
    """The Pallas Hillis-Steele ladder (interpret mode) and the XLA
    associative scan must agree in f32 — same reduction tree depth."""
    import jax.numpy as jnp

    from tempo_tpu.ops import pallas_kernels as pk
    from tempo_tpu.ops import rolling as rk

    monkeypatch.setenv("TEMPO_TPU_COMPUTE_DTYPE", "float32")
    v, m = frame.packed_numeric("x")
    assert v.dtype == np.float32
    y_ladder = np.asarray(pk.ema_scan(jnp.asarray(v), jnp.asarray(m), 0.2,
                                      interpret=True))
    y_scan = np.asarray(rk.ema_exact(jnp.asarray(v), jnp.asarray(m), 0.2))
    np.testing.assert_allclose(y_ladder, y_scan, rtol=2e-5, atol=2e-6)
