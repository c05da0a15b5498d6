"""Pallas TPU kernels for the scan-shaped hot ops.

The reference's rolling ops are Spark Window scans (tsdf.py:615-635 EMA;
interpol.py:197-222 ffill/bfill via ``last/first ignorenulls`` over
unbounded windows).  On TPU these are first-order recurrences along the
time axis; XLA's ``lax.associative_scan`` computes them in O(log L)
*separate fused loops*, each a full HBM read+write of the operand.  The
kernels here run the whole Hillis-Steele ladder inside one
``pallas_call`` with the operand resident in VMEM, so HBM is touched
exactly twice (one read, one write) regardless of L.

Mosaic cannot lower ``cumsum`` / dynamic gathers (probed on v5e), so the
ladder is built from the primitives it does support: ``pltpu.roll``
(static lane shift) + ``broadcasted_iota`` masks.

Kernels:

* ``ema_scan``  - y_t = (1-a) * y_{t-1} + a * x_t, invalid rows carry
  the previous EMA forward (exact infinite-horizon EMA; why the
  reference truncates and this stack never has to:
  resample.py:resample_ema, "Truncated-lag EMA — the canonical
  note").  Wired into the flagship fused pipeline (__graft_entry__).
* ``ema_chunked`` - the same recurrence over series too long for one
  VMEM block: a grid over (row block, lane chunk), the lane axis
  sequential, each chunk's ladder followed by ``y = v + D * carry``
  with the running ``y`` in VMEM scratch.  Host-driven in calls of a
  fixed shape that pass the carry on, so one compile serves every
  series length.
* ``last_valid_index_scan`` / ``first_valid_index_scan`` - running
  index of the last/next valid element, the engine under
  ``window_utils.last_valid_index``/``first_valid_index`` (which back
  ffill/bfill/linear interpolation scaffolds and skipNulls AS-OF);
  those wrappers dispatch here on TPU.
* ``last_valid_scan`` - forward-fill of the last valid *value* in one
  pass, for f32 packed-array pipelines that need filled values rather
  than indices.

Kernels engage for [K, L] blocks with L a multiple of 128 on TPU
(float32 for the value kernels; the index kernels are dtype-agnostic -
they only read the validity mask) and fall back to the XLA
implementations elsewhere (CPU-mesh tests, float64 golden runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
_BK = 32  # series rows per grid step; carries + roll temps + I/O double
          # buffers for a [32, 8192] f32 block stay under the 16M VMEM cap


def interpret_scope(interpret: bool):
    """Scope for CALLING an interpret-capable kernel wrapper: interpret
    mode inlines the pallas machinery into the caller's jaxpr and
    lowers it in the caller's config scope, so the whole call must run
    32-bit or the grid-loop constants come out i64 against the
    kernel's i32 jaxpr (verifier mismatch under the library's global
    x64).  Compiled mode needs no extra scope."""
    import contextlib

    return jax.enable_x64(False) if interpret else contextlib.nullcontext()


def _ladder_levels(L: int):
    spans = []
    s = 1
    while s < L:
        spans.append(s)
        s *= 2
    return spans


def _shift_with_identity(arr, span: int, identity):
    """arr shifted right by ``span`` along the lane axis; the first
    ``span`` lanes (which pltpu.roll wraps) become ``identity``."""
    # under jax_enable_x64 a python-int shift traces as i64, which
    # tpu.dynamic_rotate rejects
    rolled = pltpu.roll(arr, shift=jnp.int32(span), axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, arr.shape, dimension=1)
    return jnp.where(lane >= span, rolled, identity)


def _ema_kernel(alpha_ref, x_ref, valid_ref, out_ref):
    a = alpha_ref[0]
    valid = valid_ref[:]
    f0 = jnp.float32(0.0)
    f1 = jnp.float32(1.0)
    # linear recurrence y_i = d_i * y_{i-1} + v_i
    d = jnp.where(valid, f1 - a, f1)
    v = jnp.where(valid, a * x_ref[:], f0)
    for span in _ladder_levels(d.shape[1]):
        d_prev = _shift_with_identity(d, span, f1)
        v_prev = _shift_with_identity(v, span, f0)
        v = v + d * v_prev
        d = d * d_prev
    out_ref[:] = v


def _last_valid_kernel(x_ref, valid_ref, out_ref, outv_ref):
    f0 = jnp.float32(0.0)
    has = valid_ref[:].astype(jnp.float32)
    val = jnp.where(valid_ref[:], x_ref[:], f0)
    for span in _ladder_levels(has.shape[1]):
        has_prev = _shift_with_identity(has, span, f0)
        val_prev = _shift_with_identity(val, span, f0)
        val = jnp.where(has > 0, val, val_prev)
        has = jnp.maximum(has, has_prev)
    out_ref[:] = val
    outv_ref[:] = has > 0


def _shift_left_with_identity(arr, span: int, identity):
    """arr shifted left by ``span`` along the lane axis (for reverse
    scans); the last ``span`` lanes become ``identity``."""
    L = arr.shape[1]
    rolled = pltpu.roll(arr, shift=jnp.int32(L - span), axis=1)
    lane = jax.lax.broadcasted_iota(jnp.int32, arr.shape, dimension=1)
    return jnp.where(lane < L - span, rolled, identity)


def _last_valid_index_kernel(valid_ref, out_ref):
    L = valid_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, valid_ref.shape, dimension=1)
    cand = jnp.where(valid_ref[:], lane, -1)
    for span in _ladder_levels(L):
        cand = jnp.maximum(cand, _shift_with_identity(cand, span, -1))
    out_ref[:] = cand


def _first_valid_index_kernel(valid_ref, out_ref):
    L = valid_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, valid_ref.shape, dimension=1)
    cand = jnp.where(valid_ref[:], lane, L)
    for span in _ladder_levels(L):
        cand = jnp.minimum(cand, _shift_left_with_identity(cand, span, L))
    out_ref[:] = cand


def _cumsum3_kernel(x_ref, valid_ref, s1_ref, s2_ref, c_ref):
    """Inclusive prefix sums of (masked x, masked x^2, valid count) in
    one VMEM pass — the three scans behind windowed range stats."""
    valid = valid_ref[:]
    f0 = jnp.float32(0.0)
    xz = jnp.where(valid, x_ref[:], f0)
    s1 = xz
    s2 = xz * xz
    c = valid.astype(jnp.float32)
    for span in _ladder_levels(s1.shape[1]):
        s1 = s1 + _shift_with_identity(s1, span, f0)
        s2 = s2 + _shift_with_identity(s2, span, f0)
        c = c + _shift_with_identity(c, span, f0)
    s1_ref[:] = s1
    s2_ref[:] = s2
    c_ref[:] = c


@functools.partial(jax.jit, static_argnames=("interpret",))
def _cumsum3_call(x, valid, interpret=False):
    K, L = x.shape
    # three carries + three outputs live at once: a larger array budget
    grid, bk, K_pad = _plan(K, L, arrays=16, bk_max=16) or ((1,), K, K)
    x, valid = _pad_rows(x, K_pad), _pad_rows(valid, K_pad)
    with jax.enable_x64(False):
        spec = pl.BlockSpec((bk, L), lambda i: (i, 0), memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            _cumsum3_kernel,
            grid=grid,
            in_specs=[spec, spec],
            out_specs=[spec, spec, spec],
            out_shape=[jax.ShapeDtypeStruct((K_pad, L), jnp.float32)] * 3,
            interpret=interpret,
        )(x, valid)
    return tuple(o[:K] for o in out)


def cumsum3(x, valid, interpret: bool = False):
    """(cumsum(xz), cumsum(xz^2), cumsum(valid)) inclusive along lanes;
    Pallas on TPU/f32, XLA associative scans elsewhere."""
    x = jnp.asarray(x)
    valid = jnp.asarray(valid)
    if interpret or _supported(x, arrays=16, bk_max=16):
        with interpret_scope(interpret):
            return _cumsum3_call(x, valid, interpret=interpret)
    from tempo_tpu.ops import window_utils as wu

    xz = jnp.where(valid, x, 0.0)
    return (
        wu.cumsum(xz, axis=-1),
        wu.cumsum(xz * xz, axis=-1),
        wu.cumsum(valid.astype(x.dtype), axis=-1),
    )


def _supported(x: jax.Array, arrays: int = 12, bk_max: int = _BK) -> bool:
    return x.dtype == jnp.float32 and _index_supported(x, arrays, bk_max)


_VMEM_BUDGET = 14 * 2**20  # headroom under the 16M scoped-vmem limit


def _plan(K: int, L: int, arrays: int = 12, bk_max: int = _BK,
          budget: int = _VMEM_BUDGET):
    """(grid, bk, K_padded) row-blocking plan fitting the scoped-VMEM
    cap, or None when no legal block fits.  ``arrays`` is a conservative
    count of simultaneously-live [bk, L] f32 buffers (carries + roll
    temps + pipelined I/O).  A fixed block OOMs once L grows — [32,
    16384] f32 blew the 16M cap at 23.5M, measured.

    Mosaic requires the sublane block be a multiple of 8 or the whole
    array, so K that no power-of-two >= 8 divides is *padded up* to the
    chosen block (callers pad inputs / slice outputs); when even an
    8-row block exceeds the budget (huge L) there is no feasible plan
    and callers must stay on the XLA path.
    """
    if K * L * 4 * arrays <= budget:
        return (1,), K, K          # whole array in one block
    cap = budget // (L * 4 * arrays)
    if cap < 8:
        return None                # not even [8, L] fits: infeasible
    bk = 1 << min(bk_max, cap).bit_length() - 1
    K_pad = -(-K // bk) * bk
    return (K_pad // bk,), bk, K_pad


def _feasible(shape, arrays: int, bk_max: int) -> bool:
    return _plan(int(shape[0]), int(shape[1]), arrays, bk_max) is not None


def _pad_rows(arr, K_pad: int):
    """Pad the row axis (axis -2: [..., K, L] -> [..., K_pad, L])."""
    K = arr.shape[-2]
    if K_pad == K:
        return arr
    pad = [(0, 0)] * arr.ndim
    pad[-2] = (0, K_pad - K)
    return jnp.pad(arr, pad)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ema_call(x, valid, alpha, interpret=False):
    K, L = x.shape
    grid, bk, K_pad = _plan(K, L) or ((1,), K, K)
    x, valid = _pad_rows(x, K_pad), _pad_rows(valid, K_pad)
    # index maps must trace as i32: under the library's global x64 mode
    # they come out i64, which Mosaic's func.return rejects
    with jax.enable_x64(False):
        spec = pl.BlockSpec((bk, L), lambda i: (i, 0), memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            _ema_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                spec,
                spec,
            ],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((K_pad, L), jnp.float32),
            interpret=interpret,
        )(jnp.asarray([alpha], jnp.float32), x, valid)
    return out[:K]


#: lanes of one exact-EMA chunk at the most; the VMEM plan may give
#: fewer (:func:`ema_chunk_plan`)
EMA_CHUNK_LANES = 1 << 15
#: lane chunks per call of the carry-passing EMA program: every call
#: has the same shape, so one compile serves every series length
EMA_CALL_CHUNKS = 8
#: live [bk, Lc] buffers of the chunk kernel: measured 13.8 on v5e for
#: a [9, 32768] block (rows pad to whole 8-row tiles, validity to i32)
_EMA_ARRAYS = 16
#: VMEM the chunk plan budgets, under the kernel's raised limit
_EMA_VMEM_BUDGET = 32 * 2**20
_EMA_VMEM_LIMIT = 48 * 2**20


def _ema_chunk_kernel(alpha_ref, x_ref, valid_ref, carry_ref, out_ref,
                      carry_out_ref, y_ref):
    """One (row block, lane chunk) step: the chunk's own ladder gives
    ``v`` (the EMA from a zero start) and ``d`` (the chunk's cumulative
    decay); ``y = v + d * carry`` continues the running EMA, whose last
    lane is the next chunk's carry.  ``y_ref`` holds the carry broadcast
    over 128 lanes."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        y_ref[:] = carry_ref[:]

    a = alpha_ref[0]
    valid = valid_ref[:]
    f0 = jnp.float32(0.0)
    f1 = jnp.float32(1.0)
    d = jnp.where(valid, f1 - a, f1)
    v = jnp.where(valid, a * x_ref[:], f0)
    for span in _ladder_levels(d.shape[1]):
        d_prev = _shift_with_identity(d, span, f1)
        v_prev = _shift_with_identity(v, span, f0)
        v = v + d * v_prev
        d = d * d_prev
    carry = jnp.max(y_ref[:], axis=1, keepdims=True)
    y = v + d * carry
    out_ref[:] = y
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, dimension=1)
    last = jnp.sum(jnp.where(lane == y.shape[1] - 1, y, f0), axis=1,
                   keepdims=True)
    y_ref[:] = jnp.broadcast_to(last, y_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        carry_out_ref[:] = y_ref[:]


def ema_chunk_plan(K: int):
    """``(bk, K_pad, Lc)``: the row block, the padded row count and the
    chunk lanes of the carry-passing EMA over ``K`` series.  Up to
    ``_BK`` series share one block (the whole row axis, so none is
    padded); more take blocks of 8.  ``Lc`` is the largest power of
    two up to :data:`EMA_CHUNK_LANES` whose ``[bk, Lc]`` block, its
    rows rounded up to whole 8-row tiles, fits the VMEM budget."""
    K = max(int(K), 1)
    bk = K if K <= _BK else 8
    K_pad = -(-K // bk) * bk
    fit = _EMA_VMEM_BUDGET // (-(-bk // 8) * 8 * 4 * _EMA_ARRAYS)
    Lc = min(EMA_CHUNK_LANES, 1 << (max(fit, LANE).bit_length() - 1))
    return bk, K_pad, max(Lc, LANE)


def ema_chunked_ok(x) -> bool:
    """Whether the exact EMA of this [K, L] plane takes the chunked
    form: f32 on TPU where the whole-series block does not fit
    (:func:`_plan`)."""
    return (x.dtype == jnp.float32 and x.ndim == 2
            and jax.default_backend() == "tpu"
            and _plan(int(x.shape[0]), int(x.shape[1])) is None)


def ema_chunked_lanes(K: int, L: int) -> int:
    """Lanes the chunked EMA computes over a [K, L] plane, pads
    included."""
    _, K_pad, Lc = ema_chunk_plan(K)
    call = Lc * EMA_CALL_CHUNKS
    return K_pad * max(1, -(-int(L) // call)) * call


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ema_chunk_call(x, valid, carry, alpha, interpret=False):
    K_pad, Lcall = x.shape
    bk, _, Lc = ema_chunk_plan(K_pad)
    with jax.enable_x64(False):
        spec = pl.BlockSpec((bk, Lc), lambda i, j: (i, j),
                            memory_space=pltpu.VMEM)
        cspec = pl.BlockSpec((bk, LANE), lambda i, j: (i, 0),
                             memory_space=pltpu.VMEM)
        return pl.pallas_call(
            _ema_chunk_kernel,
            grid=(K_pad // bk, Lcall // Lc),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec,
                      cspec],
            out_specs=[spec, cspec],
            out_shape=[jax.ShapeDtypeStruct((K_pad, Lcall), jnp.float32),
                       jax.ShapeDtypeStruct((K_pad, LANE), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((bk, LANE), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_EMA_VMEM_LIMIT),
            interpret=interpret,
        )(alpha, x, valid, carry)


def ema_chunked(x, valid, alpha: float, interpret: bool = None):
    """Exact EMA of host [K, L] planes (f32 values, bool validity) as a
    host [K, L] f32 array.  The planes are padded to whole calls of
    ``EMA_CALL_CHUNKS`` chunks and the calls run in lane order, each
    starting from the carry the one before it ended with.  The same
    recurrence as :func:`ema_scan` and ``ops/rolling.ema_exact``,
    bracketed by chunk.  ``interpret`` defaults to the Pallas
    interpreter off TPU."""
    import numpy as np

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    x = np.asarray(x, np.float32)
    valid = np.asarray(valid, bool)
    K, L = x.shape
    _, K_pad, Lc = ema_chunk_plan(K)
    call = Lc * EMA_CALL_CHUNKS
    n_calls = max(1, -(-L // call))
    xp = np.zeros((K_pad, n_calls * call), np.float32)
    vp = np.zeros((K_pad, n_calls * call), bool)
    xp[:K, :L] = x
    vp[:K, :L] = valid
    a = jnp.asarray([alpha], jnp.float32)
    carry = jnp.zeros((K_pad, LANE), jnp.float32)
    parts = []
    with interpret_scope(interpret):
        for c in range(n_calls):
            lanes = slice(c * call, (c + 1) * call)
            y, carry = _ema_chunk_call(xp[:, lanes], vp[:, lanes], carry,
                                       a, interpret=interpret)
            parts.append(y)
    return np.concatenate(jax.device_get(parts), axis=1)[:K, :L]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _last_valid_call(x, valid, interpret=False):
    K, L = x.shape
    grid, bk, K_pad = _plan(K, L) or ((1,), K, K)
    x, valid = _pad_rows(x, K_pad), _pad_rows(valid, K_pad)
    with jax.enable_x64(False):
        spec = pl.BlockSpec((bk, L), lambda i: (i, 0), memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            _last_valid_kernel,
            grid=grid,
            in_specs=[spec, spec],
            out_specs=[spec, spec],
            out_shape=[
                jax.ShapeDtypeStruct((K_pad, L), jnp.float32),
                jax.ShapeDtypeStruct((K_pad, L), jnp.bool_),
            ],
            interpret=interpret,
        )(x, valid)
    return out[0][:K], out[1][:K]


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def _index_scan_call(valid, kernel, interpret=False):
    K, L = valid.shape
    grid, bk, K_pad = _plan(K, L, arrays=8) or ((1,), K, K)
    valid = _pad_rows(valid, K_pad)
    with jax.enable_x64(False):
        spec = pl.BlockSpec((bk, L), lambda i: (i, 0), memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((K_pad, L), jnp.int32),
            interpret=interpret,
        )(valid)
    return out[:K]


def _index_supported(valid: jax.Array, arrays: int = 8,
                     bk_max: int = _BK) -> bool:
    return (
        valid.ndim == 2
        and valid.shape[1] % LANE == 0
        and jax.default_backend() == "tpu"
        and _feasible(valid.shape, arrays, bk_max)
    )


def last_valid_index_scan(valid, interpret: bool = False):
    """Running index of the last True at-or-before each lane; -1 before
    the first.  Pallas on TPU, XLA cummax elsewhere."""
    valid = jnp.asarray(valid)
    if interpret or _index_supported(valid):
        with interpret_scope(interpret):
            return _index_scan_call(valid, _last_valid_index_kernel,
                                    interpret=interpret)
    from tempo_tpu.ops import window_utils as wu

    return wu.last_valid_index_xla(valid)


def first_valid_index_scan(valid, interpret: bool = False):
    """Index of the first True at-or-after each lane; L where none."""
    valid = jnp.asarray(valid)
    if interpret or _index_supported(valid):
        with interpret_scope(interpret):
            return _index_scan_call(valid, _first_valid_index_kernel,
                                    interpret=interpret)
    from tempo_tpu.ops import window_utils as wu

    return wu.first_valid_index_xla(valid)


def ema_scan(x, valid, alpha: float, interpret: bool = False):
    """Exact EMA over [K, L]; Pallas on TPU/f32, XLA scan otherwise."""
    x = jnp.asarray(x)
    valid = jnp.asarray(valid)
    if interpret or _supported(x):
        with interpret_scope(interpret):
            return _ema_call(x, valid, float(alpha), interpret=interpret)
    from tempo_tpu.ops import rolling as rk

    return rk.ema_exact(x, valid, alpha)


def last_valid_scan(x, valid, interpret: bool = False):
    """(ffilled values, any-valid-so-far mask) over [K, L]."""
    x = jnp.asarray(x)
    valid = jnp.asarray(valid)
    if interpret or _supported(x):
        with interpret_scope(interpret):
            return _last_valid_call(x, valid, interpret=interpret)
    # XLA fallback: the same scan via associative_scan
    def combine(c1, c2):
        h1, v1 = c1
        h2, v2 = c2
        return jnp.logical_or(h2, h1), jnp.where(h2, v2, v1)

    has, val = jax.lax.associative_scan(
        combine, (valid, jnp.where(valid, x, 0)), axis=1
    )
    return val, has
