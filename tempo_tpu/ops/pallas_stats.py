"""Pallas VMEM kernel for the shifted-window range stats (legacy).

Since the streaming window engine landed (ops/pallas_window.py — same
semantics, leaner per-pass math, runtime window widths), the shifted
dispatcher prefers that module's unrolled form; this kernel stays as
the TEMPO_TPU_WINDOW_ENGINE=legacy fallback and the parity baseline
its tests pin.

``ops/sortmerge.py:range_stats_shifted`` computes Spark's
rangeBetween(-window, 0) aggregates as W static shifted masked
accumulations.  XLA fuses the passes, but the operand still crosses HBM
several times per aggregate; here the whole pass structure runs on a
[bk, L] block resident in VMEM — one HBM read of (secs, x, valid), one
write of the eight outputs, with every shift a ``pltpu.roll``.

Engages for f32 values with an int32-expressible seconds axis (the
frame layer already rebases per series, packing.py:rebase_seconds; the
wrapper rebases otherwise) on lane-aligned blocks; the XLA form remains
for CPU/f64 and infeasible shapes.  Semantics identical to
``range_stats_shifted`` including the ``clipped`` truncation audit —
parity pinned in tests/test_pallas_stats.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tempo_tpu.ops import pallas_kernels as pk

_I32_BIG = 2**31 - 1  # python int: jnp scalars capture as consts in kernels


def _shift(p, j: int, fill, shape):
    """out[:, i] = p[:, i-j] (j<0 looks ahead); rolled lanes become
    ``fill`` (negative roll shifts SIGABRT Mosaic — use L-|j|)."""
    if j == 0:
        return p
    L = shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, dimension=1)
    if j > 0:
        rolled = pltpu.roll(p, shift=jnp.int32(j), axis=1)
        return jnp.where(lane >= j, rolled, fill)
    rolled = pltpu.roll(p, shift=jnp.int32(L + j), axis=1)
    return jnp.where(lane < L + j, rolled, fill)


def _make_kernel(max_behind: int, max_ahead: int):
    def kernel(w_ref, secs_ref, x_ref, valid_ref,
               mean_ref, cnt_ref, mn_ref, mx_ref, sum_ref, std_ref,
               z_ref, clip_ref):
        w = w_ref[0]
        secs = secs_ref[:]
        x = x_ref[:]
        valid = valid_ref[:]
        shape = secs.shape

        # bool planes cannot ride pltpu.roll: shift an f32 image
        f0 = jnp.float32(0.0)
        f1 = jnp.float32(1.0)
        validf = valid.astype(jnp.float32)
        xz = jnp.where(valid, x, f0)
        nv = jnp.sum(validf, axis=1, keepdims=True)
        center = jnp.sum(xz, axis=1, keepdims=True) / jnp.maximum(nv, f1)
        xc = jnp.where(valid, x - center, f0)

        lo = secs - w
        pinf = jnp.float32(jnp.inf)
        cnt = jnp.zeros(shape, jnp.float32)
        s1 = jnp.zeros(shape, jnp.float32)
        s2 = jnp.zeros(shape, jnp.float32)
        mn = jnp.full(shape, pinf)
        mx = jnp.full(shape, -pinf)
        for j in range(-max_ahead, max_behind + 1):
            sj = _shift(secs, j, _I32_BIG, shape)
            inw = (sj >= lo) & (sj <= secs) & (
                _shift(validf, j, f0, shape) > f0
            )
            xj = _shift(xc, j, f0, shape)
            xr = _shift(x, j, f0, shape)
            cnt = cnt + inw.astype(jnp.float32)
            s1 = s1 + jnp.where(inw, xj, f0)
            s2 = s2 + jnp.where(inw, xj * xj, f0)
            mn = jnp.minimum(mn, jnp.where(inw, xr, pinf))
            mx = jnp.maximum(mx, jnp.where(inw, xr, -pinf))

        nan = jnp.float32(jnp.nan)
        mean = jnp.where(cnt > 0, s1 / jnp.maximum(cnt, f1) + center, nan)
        total = s1 + cnt * center
        var = jnp.where(
            cnt > 1,
            (s2 - s1 * s1 / jnp.maximum(cnt, f1))
            / jnp.maximum(cnt - f1, f1),
            nan,
        )
        std = jnp.where(cnt > 1, jnp.sqrt(jnp.maximum(var, f0)), nan)

        # truncation audit: mirrors range_stats_shifted exactly
        L = shape[1]
        clipped = jnp.zeros(shape, jnp.bool_)
        for j in (min(max_behind + 1, L), -min(max_ahead + 1, L)):
            sj = _shift(secs, j, _I32_BIG, shape)
            clipped = clipped | (
                (sj >= lo) & (sj <= secs)
                & (valid | (_shift(validf, j, f0, shape) > f0))
            )

        mean_ref[:] = mean
        cnt_ref[:] = cnt
        mn_ref[:] = jnp.where(cnt > 0, mn, nan)
        mx_ref[:] = jnp.where(cnt > 0, mx, nan)
        sum_ref[:] = jnp.where(cnt > 0, total, nan)
        std_ref[:] = std
        z_ref[:] = jnp.where(valid, (x - mean) / std, nan)
        clip_ref[:] = clipped.astype(jnp.float32)

    return kernel


# Largest unrolled window the kernel may take.  Probed on v5e: W=64
# compiles and runs (43s, bk=16); W≈150 fits standalone at bk=8 but
# overflows VMEM by 7M once the bench's fori-loop wraps it, and W≈266
# exceeds by 20M even at the minimum block — Mosaic's live temporaries
# grow superlinearly in W, so the bound sits at the largest probed
# size with comfortable margin.  Beyond this the XLA shifted form
# (which can spill) takes over, up to the frame layer's
# SHIFTED_MAX_ROWS; past that, the prefix-scan+RMQ windowed form.
_PALLAS_STATS_MAX_W = 64


def _plan_arrays(max_behind: int, max_ahead: int) -> int:
    """Live-plane budget for the block plan.  The base term covers
    I/O double buffers + accumulators (calibrated at the r3 window,
    W≈28, bk=32); the per-shift term covers the temporaries Mosaic's
    scheduler keeps live across the unrolled shift passes — measured:
    W=64 at bk=32 overflowed VMEM by 29M (157M used), so the window
    length must shrink the block."""
    return 32 + max_behind + max_ahead


@functools.partial(
    jax.jit, static_argnames=("max_behind", "max_ahead", "interpret")
)
def _stats_call(secs, x, valid, window, max_behind, max_ahead,
                interpret=False):
    K, L = x.shape
    plan = pk._plan(K, L, arrays=_plan_arrays(max_behind, max_ahead),
                    bk_max=32, budget=90 * 2**20)
    if plan is None:
        # callers consult range_stats_supported first; a whole-array
        # block here would be strictly larger than the one the planner
        # just rejected
        raise ValueError(
            f"range-stats kernel infeasible at L={L}: even an [8, {L}] "
            f"block exceeds the VMEM budget; use the XLA shifted form"
        )
    grid, bk, K_pad = plan
    secs = pk._pad_rows(secs, K_pad)
    x, valid = pk._pad_rows(x, K_pad), pk._pad_rows(valid, K_pad)
    with jax.enable_x64(False):
        spec = pl.BlockSpec((bk, L), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
        out = pl.pallas_call(
            _make_kernel(max_behind, max_ahead),
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [spec] * 3,
            out_specs=[spec] * 8,
            out_shape=[jax.ShapeDtypeStruct((K_pad, L), jnp.float32)] * 8,
            # measured 18.9M at [8, 8192] blocks: over the 16M default
            # scoped cap; v5e has 128M physical VMEM (same treatment as
            # the merge kernel)
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024,
            ),
            interpret=interpret,
        )(jnp.asarray([window], jnp.int32), secs, x, valid)
    return tuple(o[:K] for o in out)


def pallas_block_feasible(K: int, L: int) -> bool:
    """Whether THIS kernel could take a [K, L] f32 shard at its window
    ceiling — the shard-shape part of :func:`range_stats_supported`,
    used by the auto-pick budget (ops/rolling.py:shifted_row_budget):
    the VMEM form's exemption from the XLA form's HBM bound only
    applies when the VMEM form is actually reachable."""
    return (
        int(L) % 128 == 0
        and jax.default_backend() == "tpu"
        and pk._plan(int(K), int(L),
                     arrays=_plan_arrays(_PALLAS_STATS_MAX_W, 0),
                     bk_max=32, budget=90 * 2**20) is not None
    )


def range_stats_supported(secs, x, valid, max_behind: int = 28,
                          max_ahead: int = 0) -> bool:
    return (
        x.dtype == jnp.float32
        and x.ndim == 2
        and x.shape[1] % 128 == 0
        and int(max_behind) + int(max_ahead) <= _PALLAS_STATS_MAX_W
        and jax.default_backend() == "tpu"
        and pk._plan(int(x.shape[0]), int(x.shape[1]),
                     arrays=_plan_arrays(int(max_behind), int(max_ahead)),
                     bk_max=32, budget=90 * 2**20) is not None
    )


def range_stats_pallas(secs, x, valid, window, max_behind: int,
                       max_ahead: int = 0, interpret: bool = False):
    """Drop-in VMEM form of ``range_stats_shifted``; same output dict.
    ``secs`` must fit int32 after the caller's per-series rebase (the
    wrapper in sortmerge casts and falls back when it cannot)."""
    with pk.interpret_scope(interpret):
        outs = _stats_call(
            secs.astype(jnp.int32), x, valid,
            jnp.asarray(window).astype(jnp.int32),
            max_behind=int(max_behind), max_ahead=int(max_ahead),
            interpret=interpret,
        )
    mean, cnt, mn, mx, total, std, z, clip = outs
    return {
        "mean": mean, "count": cnt, "min": mn, "max": mx, "sum": total,
        "stddev": std, "zscore": z,
        "clipped": jnp.sum(clip, axis=-1, keepdims=True),
    }
