"""How far the float32 compute policy may drift from float64.

On TPU the metric kernels compute in float32 (``packing.compute_dtype``)
where the reference computes in float64.  ``F32_BOUNDS`` are the
absolute errors allowed per result for standard-normal data, about ten
times the measured drift: prefix sums are mean-centred per series, so
window aggregates drift like W * eps_f32 * |x| (not L * eps), and
stddev inherits sqrt cancellation.  tests/test_f32_numerics.py holds the
float32 policy to them, and chip_smoke.py holds the chip to them against
its float64 reference.
"""

F32_BOUNDS = {
    "mean": 5e-4,
    "sum": 5e-3,
    "count": 0.0,        # exact: integer accumulation in f32 < 2^24
    "min": 1e-6,         # selection, not accumulation (casting only)
    "max": 1e-6,
    "stddev": 5e-3,
    "zscore": 5e-2,      # divides by a small stddev: loosest
    "ema": 1e-4,
    "linear": 1e-5,      # interpolation is local arithmetic
}
