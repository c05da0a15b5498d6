"""Streaming sliding-window engine: VMEM window sweeps at any width.

``ops/sortmerge.py:range_stats_shifted`` computes Spark's
rangeBetween(-window, 0) aggregates as W statically-unrolled shifted
passes; ``ops/pallas_stats.py`` runs that structure VMEM-resident but
inherits the unroll, so Mosaic's live-temporary growth caps it at
W<=64 rows (measured: W~150 overflowed VMEM by 7M, W~266 by 20M).
Wider frames used to fall back to the prefix-scan + RMQ form
(``ops/rolling.py:windowed_stats``), which is gather-bound on this
hardware (~96 ms per ``take_along_axis`` level at [1024, 8192]) — the
one regime where a TPU chip lost to a single CPU core (the pre-PR-1
chip bench's ``2b_range_stats_dense_50hz``: 8.0M rows/s vs 9.6M
numpy).

This module replaces that regime with a *streaming* kernel: the block
tiles through VMEM once (one HBM read of (secs, x, valid), one write
of the eight output planes — each element crosses HBM O(1) times) and
the window sweep runs as a ``fori_loop`` of dynamic-rotate passes with
O(1) live planes, so

* the window width is a **runtime scalar** (SMEM), not a compile-time
  unroll: one compiled program serves every window size at a given
  [K, L] — no recompiles across datasets, no Mosaic live-range blowup;
* per-pass work is cut vs the legacy kernel: validity is folded into
  the key planes once (single compare per pass instead of three
  compare/mask ops), and min/max accumulate on the mean-centred values
  (recovered exactly by adding the per-series center back), so a pass
  rolls 3 planes instead of 4;
* row- and range-based windows share one kernel: Spark's
  rangeBetween(-wb, +wa) is the generic form, and rowsBetween is the
  same sweep over an iota key (``rows_stats_stream``).

The in-window test per pass IS the monotone two-pointer sweep in
vectorised form: because keys ascend along lanes, ``secs[i-j] >=
secs[i] - w`` is exactly "j is before the back pointer", and the
folded key planes carry the inter-pass boundary state.

An ``unroll=True`` twin (static trip count, python-int rotate
amounts) exists for small windows where the legacy kernel used to
engage; the three-way auto-pick (``ops/rolling.pick_range_engine``)
chooses between shifted/VMEM-unrolled and streaming forms at row-extent
crossovers from the retired root benchmark harness.  No benchmark cell
runs these forms or has measured the crossovers on the chip (the chain
cells take ``windowed_stats`` and ``range_stats_chunk``);
tests/test_tpu_compile.py compiles both kernels for a v5e.

Both forms take an optional ``scale`` scalar that multiplies ``x``
inside the kernel — downstream consumers that previously re-streamed
the column through a separate elementwise pass (the autotuner's probe
bodies, fused pipelines) fold it here for free.

HBM-roofline mechanisms (PR 6 — the pre-PR-1 chip bench put these
kernels at 0.18-0.28 of the measured stream rate):

* **multi-column payload packing** (``range_stats_stream_packed`` /
  ``range_stats_unrolled_packed``): one kernel pass reduces a stacked
  [C, K, L] payload, reading the key planes (secs + per-column valids
  ride the payload) ONCE instead of streaming a tiled timestamp copy
  per metric column — the frame/mesh ``withRangeStats`` callers used
  to materialise C broadcast copies of ``secs``.  The pack width is
  sized by the same VMEM-budget folding the static analyzer applies
  (:func:`pack_cols_budget`, capped by ``TEMPO_TPU_PACK_COLS``);
  per-column math is the identical op sequence, so packed outputs are
  bitwise-equal to C single-column calls (tests pin this).
* **explicit DMA pipelining** (``TEMPO_TPU_DMA_BUFFERS`` > 2): the
  slab loop moves into the kernel and inputs stream through the
  N-deep ``pltpu.make_async_copy`` ring of ``ops/pallas_stream.py``,
  overlapping the copy of slab i+N-1 and the writeback of slab i-1
  with the compute of slab i.  Depth 2 (default) keeps Mosaic's
  implicit BlockSpec pipeline.
* **megacore partitioning**: the row-block grid axis is carry-free, so
  it is declared ``"parallel"`` (``pallas_stream.grid_semantics``)
  and Mosaic may split it across TensorCores on megacore parts.

Semantics are identical to ``range_stats_shifted`` including the
``clipped`` truncation audit; parity is pinned in
tests/test_pallas_window.py against both the XLA shifted form and a
brute-force numpy oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tempo_tpu.ops import pallas_kernels as pk
from tempo_tpu.ops import pallas_stream as psr

_I32_BIG = 2**31 - 1     # python ints: capture as consts inside kernels
_I32_MIN = -(2**31)

# Live-plane budgets for the block plan, in [bk, L] f32 plane units.
# The streaming form keeps O(1) temporaries per column whatever the
# window (folded keys + centred values + 5 accumulators + rotate
# temps); the unrolled form inherits the per-shift live-temporary
# growth measured on the legacy kernel (ops/pallas_stats._plan_arrays).
# Columns are processed sequentially inside the kernel, so only ONE
# column's temporaries are live at a time — the per-column cost is the
# pipelined I/O (x + valid in, 8 planes out), not the sweep state.
_COL_TEMPS = 20          # one column's live sweep temporaries
_COL_IO = 20             # (x + valid) in + 8 out, double-buffered


def _plan_arrays(n_cols: int, max_behind: int, max_ahead: int,
                 unroll: bool, depth: int) -> int:
    """Conservative count of simultaneously-live [bk, L] f32 planes for
    the block plan (``pallas_kernels._plan``).  The explicit DMA ring
    trades the BlockSpec pipeline's 2x I/O for ``depth`` input slots
    plus a double-buffered output stage — same formula, depth-scaled
    input term."""
    base = _COL_TEMPS + (max_behind + max_ahead if unroll else 4)
    if depth <= 2:
        return base + _COL_IO * n_cols
    in_planes = 1 + 2 * n_cols            # secs + (x, valid) per column
    return base + depth * in_planes + 16 * n_cols


_STREAM_ARRAYS = _plan_arrays(1, 0, 0, unroll=False, depth=2)   # == 44


def _unroll_arrays(max_behind: int, max_ahead: int) -> int:
    return _plan_arrays(1, max_behind, max_ahead, unroll=True, depth=2)


# Largest window the *unrolled* twin may take: beyond this the
# streaming form is the only VMEM path (the legacy kernel's probed
# ceiling — Mosaic live temporaries grow superlinearly in the unroll).
UNROLL_MAX_W = 64


def _stream_max_rows() -> int:
    """Row-extent ceiling for the streaming form.  The sweep is O(W)
    dynamic-rotate passes, so at SOME width the O(L log L) sort-based
    windowed form must win again; extrapolating the measured pass rate
    (~15us per [1024, 8192] rotate) against the measured RMQ-path
    floor (~1.05 s/iteration at that shape, a record older than the
    benchmark cells) puts the crossover above 20k rows; no benchmark
    cell has measured it on the chip.  Env unset falls back to
    the tuned-profile prior (tempo_tpu/tune — the autotuner's
    audit-gated winner: a candidate ceiling that flipped the engine
    pick changed result bits and was rejected at sweep time), then to
    the built-in 16384."""
    from tempo_tpu import config, tune

    n = config.get_int("TEMPO_TPU_STREAM_MAX_ROWS")
    if n is None:
        n = tune.knob_value("TEMPO_TPU_STREAM_MAX_ROWS")
    return 16384 if n is None else int(n)


def pack_cols_budget(K: int, L: int, n_cols: int,
                     max_behind: int = 0, max_ahead: int = 0,
                     unroll: bool = False) -> int:
    """Largest payload pack width (<= ``n_cols``, capped by
    ``TEMPO_TPU_PACK_COLS``) whose [C, bk, L] block plan still fits
    the VMEM budget (``pallas_stream.pack_budget`` over this module's
    plane counts) — consulted by the frame/mesh ``withRangeStats``
    packers before stacking metric columns."""
    depth = psr.dma_buffers()
    return psr.pack_budget(
        K, L, n_cols,
        lambda c: _plan_arrays(c, max_behind, max_ahead, unroll, depth))


def _window_math(max_behind: int, max_ahead: int, unroll: bool,
                 interpret: bool = False):
    """The window sweep as a function of *arrays*: one metric column's
    full pass, shared verbatim by every kernel form (single-column
    BlockSpec, multi-column packed, explicit DMA ring) — bitwise
    identity across the forms holds by construction because they trace
    this exact op sequence.  ``unroll=True`` bakes the trip counts
    (python-int rotate amounts, fully unrolled passes); otherwise the
    bounds ride in as runtime scalars and the sweep is a ``fori_loop``
    whose rotate amount is the loop index."""

    def _roll(p, shift):
        # interpret mode avoids roll_p: its fallback lowering re-derives
        # shape constants OUTSIDE the kernel's 32-bit scope and trips
        # the global-x64 i32/i64 verifier; jnp.roll traced here is
        # equivalent and stays in-scope
        if interpret:
            return jnp.roll(p, shift, axis=1)
        return pltpu.roll(p, shift=shift, axis=1)

    def math(w, wa, mb_r, ma_r, scale, secs, x, valid):
        x = x * scale
        shape = secs.shape
        L = shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, dimension=1)

        big = jnp.int32(_I32_BIG)
        lo = secs - w
        # forward bound, saturated one below the pad sentinel: clamped
        # pads carry key INT32_MAX, so an unsaturated `secs + wa` both
        # wraps at pad centers and lets the BIG-folded invalids below
        # tie `sj <= hi` — capping at BIG-1 closes both without a
        # per-pass validity compare (real keys sit >= window below the
        # pads by the rebase headroom contract, packing.rebase_seconds)
        hi = jnp.minimum(secs + jnp.minimum(wa, big - secs), big - 1)
        # validity folded into the key planes once: an invalid row's
        # key can never pass the single in-window compare of its
        # direction (MIN fails `sj >= lo`, BIG fails `sj <= hi`)
        s_lo = jnp.where(valid, secs, jnp.int32(_I32_MIN))
        s_hi = jnp.where(valid, secs, big)

        f0 = jnp.float32(0.0)
        f1 = jnp.float32(1.0)
        validf = valid.astype(jnp.float32)
        xz = jnp.where(valid, x, f0)
        nv = jnp.sum(validf, axis=1, keepdims=True)
        center = jnp.sum(xz, axis=1, keepdims=True) / jnp.maximum(nv, f1)
        xc = jnp.where(valid, x - center, f0)
        xc2 = xc * xc
        pinf = jnp.float32(jnp.inf)

        def accumulate(carry, inw, xj, xj2):
            cnt, s1, s2, mn, mx = carry
            return (cnt + inw.astype(jnp.float32),
                    s1 + jnp.where(inw, xj, f0),
                    s2 + jnp.where(inw, xj2, f0),
                    # min/max ride the centred values too (argmin is
                    # shift-invariant); the epilogue adds center back
                    jnp.minimum(mn, jnp.where(inw, xj, pinf)),
                    jnp.maximum(mx, jnp.where(inw, xj, -pinf)))

        def behind_step(j, carry):
            # keys ascend, so a row j back is in-window iff it is at or
            # after the back pointer: ONE compare (`<= hi` holds by
            # sortedness; wrapped lanes are masked by the iota)
            sj = _roll(s_lo, j)
            inw = (sj >= lo) & (lane >= j)
            return accumulate(carry,
                              inw,
                              _roll(xc, j),
                              _roll(xc2, j))

        def ahead_step(j, carry):
            # rows ahead are in-window iff within the forward bound
            # (`>= lo` holds by sortedness); rotate by L-j looks ahead
            # (negative rotate amounts SIGABRT Mosaic)
            sj = _roll(s_hi, L - j)
            inw = (sj <= hi) & (lane < L - j)
            return accumulate(carry,
                              inw,
                              _roll(xc, L - j),
                              _roll(xc2, L - j))

        # j = 0: the row itself (always inside its own frame).  The
        # square seeds s2 through a select (bitwise xc2: xc is +0 on
        # invalid rows) so no compiler contracts it with the first
        # accumulation into an FMA: XLA:CPU did so in the unrolled form,
        # where product and add share one fusion, and not in the loop
        # form, which rounded stddev 1 ulp apart
        carry = (validf, xc, jnp.where(valid, xc2, f0),
                 jnp.where(valid, xc, pinf), jnp.where(valid, xc, -pinf))
        if unroll:
            for j in range(1, max_behind + 1):
                carry = behind_step(j, carry)
            for j in range(1, max_ahead + 1):
                carry = ahead_step(j, carry)
            mb = jnp.int32(max_behind)
            ma = jnp.int32(max_ahead)
        else:
            mb = mb_r
            ma = ma_r
            # a bound >= L has no row beyond it; clamping also keeps
            # the rotate amounts inside [0, L)
            carry = jax.lax.fori_loop(
                jnp.int32(1), jnp.minimum(mb, L - 1) + 1,
                behind_step, carry)
            carry = jax.lax.fori_loop(
                jnp.int32(1), jnp.minimum(ma, L - 1) + 1,
                ahead_step, carry)
        cnt, s1, s2, mn, mx = carry

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

        # truncation audit (same contract as range_stats_shifted): a
        # row is clipped when the first row beyond either bound still
        # falls inside its frame's key range and either end is valid
        clipped = jnp.zeros(shape, jnp.bool_)
        for behind in (True, False):
            jb = jnp.minimum((mb if behind else ma) + 1, L)
            # jb == L rotates by 0 / L-jb == 0, but the lane mask is
            # then all-False (no row lies beyond the axis), so the
            # wrapped values never contribute
            shift = (jb % L) if behind else (L - jb)
            sj = _roll(secs, shift)
            vj = _roll(validf, shift)
            ok = (lane >= jb) if behind else (lane < L - jb)
            sj = jnp.where(ok, sj, jnp.int32(_I32_BIG))
            vj = jnp.where(ok, vj, f0)
            clipped = clipped | (
                (sj >= lo) & (sj <= hi) & (valid | (vj > f0))
            )

        return (mean, cnt,
                jnp.where(cnt > 0, mn + center, nan),
                jnp.where(cnt > 0, mx + center, nan),
                jnp.where(cnt > 0, total, nan),
                std,
                jnp.where(valid, (x - mean) / std, nan),
                clipped.astype(jnp.float32))

    return math


def _make_kernel(max_behind: int, max_ahead: int, unroll: bool,
                 interpret: bool = False, n_cols: int = 1):
    """BlockSpec-kernel factory over :func:`_window_math`.  With
    ``n_cols > 1`` the payload refs are [C, bk, L] stacks and the key
    planes are read once per block — columns run sequentially through
    the identical per-column op sequence."""
    math = _window_math(max_behind, max_ahead, unroll, interpret)

    def kernel(p_ref, scale_ref, secs_ref, x_ref, valid_ref,
               *out_refs):
        secs = secs_ref[:]
        if n_cols == 1:
            outs = math(p_ref[0], p_ref[1], p_ref[2], p_ref[3],
                        scale_ref[0], secs, x_ref[:], valid_ref[:])
            for r, o in zip(out_refs, outs):
                r[:] = o
            return
        for c in range(n_cols):
            outs = math(p_ref[0], p_ref[1], p_ref[2], p_ref[3],
                        scale_ref[c], secs, x_ref[c], valid_ref[c])
            for r, o in zip(out_refs, outs):
                r[c] = o

    return kernel


def _ring_math(max_behind: int, max_ahead: int, unroll: bool,
               interpret: bool, n_cols: int):
    """Per-slab math adapter for the explicit DMA ring
    (``pallas_stream.ring_call``): same :func:`_window_math` sequence,
    outputs restacked to the packed [C, bk, L] template."""
    math = _window_math(max_behind, max_ahead, unroll, interpret)

    def ring_math(scalar_refs, slabs):
        p_ref, scale_ref = scalar_refs
        secs, x, valid = slabs
        if n_cols == 1:
            return math(p_ref[0], p_ref[1], p_ref[2], p_ref[3],
                        scale_ref[0], secs, x, valid)
        per = [math(p_ref[0], p_ref[1], p_ref[2], p_ref[3],
                    scale_ref[c], secs, x[c], valid[c])
               for c in range(n_cols)]
        return tuple(jnp.stack([per[c][t] for c in range(n_cols)])
                     for t in range(8))

    return ring_math


def _call(secs, x, valid, params, scale, max_behind, max_ahead,
          unroll, depth, interpret):
    """Shared dispatch for every kernel form.  ``x``/``valid`` are
    [K, L] (single column) or [C, K, L] (packed); ``secs`` is always
    [K, L].  ``depth > 2`` streams the slabs through the explicit DMA
    ring where its plan is feasible, else the standard double-buffered
    BlockSpec pipeline with the row grid declared megacore-parallel."""
    if x.ndim == 3 and x.shape[0] == 1:
        # width-1 pack (a single summarized column, or the leftover of
        # a C % pack_cols_budget split): run the rank-2 single-column
        # form — the identical op sequence — and restack; the rank-2
        # spec paths below would otherwise trace rank-2 BlockSpecs over
        # the rank-3 operands
        outs = _call(secs, x[0], valid[0], params, scale, max_behind,
                     max_ahead, unroll, depth, interpret)
        return tuple(o[None] for o in outs)
    n_cols = 1 if x.ndim == 2 else x.shape[0]
    K, L = x.shape[-2], x.shape[-1]
    plan = psr.plan_with_ring(
        K, L, lambda d: _plan_arrays(n_cols, max_behind, max_ahead,
                                     unroll, d), depth)
    if plan is None:
        raise ValueError(
            f"streaming window kernel infeasible at L={L}, "
            f"n_cols={n_cols}: even an [8, {L}] block exceeds the VMEM "
            f"budget; use the XLA forms (or narrow the pack — "
            f"pack_cols_budget)"
        )
    grid, bk, K_pad, use_ring = plan
    secs = pk._pad_rows(secs, K_pad)
    x, valid = pk._pad_rows(x, K_pad), pk._pad_rows(valid, K_pad)

    if use_ring:
        out = psr.ring_call(
            _ring_math(max_behind, max_ahead, unroll, interpret,
                       n_cols),
            [params, scale], [secs, x, valid], n_out=8, out_like=1,
            bk=bk, depth=depth, interpret=interpret)
        return tuple(o[..., :K, :] for o in out)

    with jax.enable_x64(False):
        spec2 = pl.BlockSpec((bk, L), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
        if n_cols == 1:
            spec3 = spec2
            out_shape = (K_pad, L)
        else:
            spec3 = pl.BlockSpec((n_cols, bk, L), lambda i: (0, i, 0),
                                 memory_space=pltpu.VMEM)
            out_shape = (n_cols, K_pad, L)
        out = pl.pallas_call(
            _make_kernel(max_behind, max_ahead, unroll, interpret,
                         n_cols),
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
            + [spec2, spec3, spec3],
            out_specs=[spec3] * 8,
            out_shape=[jax.ShapeDtypeStruct(out_shape, jnp.float32)] * 8,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024,
                dimension_semantics=psr.grid_semantics(len(grid)),
            ),
            interpret=interpret,
        )(params, scale, secs, x, valid)
    return tuple(o[..., :K, :] for o in out)


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def _stream_call(secs, x, valid, params, scale, depth=2,
                 interpret=False):
    """ONE compiled program per [K, L] shape (and pack width): window
    size and row bounds are runtime scalars."""
    return _call(secs, x, valid, params, scale, 0, 0, unroll=False,
                 depth=depth, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("max_behind", "max_ahead", "depth", "interpret"),
)
def _unrolled_call(secs, x, valid, params, scale, max_behind, max_ahead,
                   depth=2, interpret=False):
    return _call(secs, x, valid, params, scale, max_behind, max_ahead,
                 unroll=True, depth=depth, interpret=interpret)


def _as_dict(outs):
    mean, cnt, mn, mx, total, std, z, clip = outs
    return {
        "mean": mean, "count": cnt, "min": mn, "max": mx, "sum": total,
        "stddev": std, "zscore": z,
        "clipped": jnp.sum(clip, axis=-1, keepdims=True),
    }


def _params(window, window_ahead, max_behind, max_ahead):
    # clamp the key windows so `secs - w` / `secs + wa` cannot wrap
    # int32 for rebased (non-negative) keys
    cap = jnp.int32(_I32_BIG // 2)
    w = jnp.minimum(jnp.asarray(window).astype(jnp.int32), cap)
    wa = jnp.minimum(jnp.asarray(window_ahead).astype(jnp.int32), cap)
    return jnp.stack([
        w, wa,
        jnp.asarray(max_behind).astype(jnp.int32),
        jnp.asarray(max_ahead).astype(jnp.int32),
    ])


def _scale(scale, n_cols: int = 1):
    if scale is None:
        return jnp.ones((n_cols,), jnp.float32)
    s = jnp.asarray(scale, jnp.float32).reshape(-1)
    if s.shape[0] == n_cols:
        return s
    return jnp.broadcast_to(s, (n_cols,))


def stream_supported(x, L_mult: int = 128) -> bool:
    """Gate for the streaming (runtime-width) form: f32 lane-aligned
    TPU blocks; feasibility is window-independent."""
    return (
        x.dtype == jnp.float32
        and x.ndim == 2
        and x.shape[1] % L_mult == 0
        and jax.default_backend() == "tpu"
        and pk._plan(int(x.shape[0]), int(x.shape[1]),
                     arrays=_STREAM_ARRAYS, bk_max=32,
                     budget=90 * 2**20) is not None
    )


def stream_block_feasible(K: int, L: int) -> bool:
    """Shape-only variant of :func:`stream_supported` for pickers that
    run before the arrays exist (frame/mesh auto-pick)."""
    return (
        int(L) % 128 == 0
        and jax.default_backend() == "tpu"
        and pk._plan(int(K), int(L), arrays=_STREAM_ARRAYS, bk_max=32,
                     budget=90 * 2**20) is not None
    )


def unrolled_supported(x, max_behind: int, max_ahead: int) -> bool:
    return (
        x.dtype == jnp.float32
        and x.ndim == 2
        and x.shape[1] % 128 == 0
        and int(max_behind) + int(max_ahead) <= UNROLL_MAX_W
        and jax.default_backend() == "tpu"
        and pk._plan(int(x.shape[0]), int(x.shape[1]),
                     arrays=_unroll_arrays(int(max_behind),
                                           int(max_ahead)),
                     bk_max=32, budget=90 * 2**20) is not None
    )


def range_stats_stream(secs, x, valid, window, max_behind, max_ahead,
                       window_ahead=0, scale=None,
                       interpret: bool = False):
    """Streaming rangeBetween(-window, +window_ahead) aggregates.

    Same output dict as ``range_stats_shifted`` (mean/count/min/max/
    sum/stddev/zscore + the [K, 1] ``clipped`` truncation audit).
    ``max_behind``/``max_ahead`` are *runtime* row bounds — derive them
    from the data exactly as for the shifted form; bounds too small
    truncate frames and the audit counts the affected rows.  ``secs``
    must be int32 (rebased, non-negative) and ascending per row;
    ``scale`` multiplies x inside the kernel (fold the elementwise
    pre-pass a caller would otherwise re-stream the column for)."""
    with pk.interpret_scope(interpret):
        outs = _stream_call(
            secs.astype(jnp.int32), x, valid,
            _params(window, window_ahead, max_behind, max_ahead),
            _scale(scale), depth=psr.dma_buffers(), interpret=interpret,
        )
    return _as_dict(outs)


def range_stats_unrolled(secs, x, valid, window, max_behind, max_ahead,
                         window_ahead=0, scale=None,
                         interpret: bool = False):
    """Statically-unrolled twin of :func:`range_stats_stream` for
    small windows (W <= UNROLL_MAX_W): same semantics, trip counts
    baked at compile time."""
    with pk.interpret_scope(interpret):
        outs = _unrolled_call(
            secs.astype(jnp.int32), x, valid,
            _params(window, window_ahead, max_behind, max_ahead),
            _scale(scale), max_behind=int(max_behind),
            max_ahead=int(max_ahead), depth=psr.dma_buffers(),
            interpret=interpret,
        )
    return _as_dict(outs)


def range_stats_stream_packed(secs, xs, valids, window, max_behind,
                              max_ahead, window_ahead=0, scales=None,
                              interpret: bool = False):
    """Multi-column :func:`range_stats_stream`: ``xs``/``valids`` are
    [C, K, L] stacks sharing one [K, L] key plane, reduced in ONE
    kernel pass — the key planes cross HBM once instead of once per
    column.  Outputs are [C, K, L] ([C, K, 1] for ``clipped``);
    per-column results are bitwise-equal to C single-column calls
    (identical op sequence — tests/test_pallas_window.py pins the
    matrix).  ``scales`` is None, a scalar, or a [C] vector.  Callers
    size C with :func:`pack_cols_budget`."""
    C = xs.shape[0]
    with pk.interpret_scope(interpret):
        outs = _stream_call(
            secs.astype(jnp.int32), xs, valids,
            _params(window, window_ahead, max_behind, max_ahead),
            _scale(scales, C), depth=psr.dma_buffers(),
            interpret=interpret,
        )
    return _as_dict(outs)


def range_stats_unrolled_packed(secs, xs, valids, window, max_behind,
                                max_ahead, window_ahead=0, scales=None,
                                interpret: bool = False):
    """Multi-column :func:`range_stats_unrolled` (see
    :func:`range_stats_stream_packed`)."""
    C = xs.shape[0]
    with pk.interpret_scope(interpret):
        outs = _unrolled_call(
            secs.astype(jnp.int32), xs, valids,
            _params(window, window_ahead, max_behind, max_ahead),
            _scale(scales, C), max_behind=int(max_behind),
            max_ahead=int(max_ahead), depth=psr.dma_buffers(),
            interpret=interpret,
        )
    return _as_dict(outs)


def rows_stats_stream(x, valid, rows_behind, rows_ahead=0, scale=None,
                      interpret: bool = False):
    """Row-based windows (Spark rowsBetween(-rows_behind, +rows_ahead))
    as the same streaming sweep over an iota key: key distance == row
    distance, so the range kernel computes exactly the row frame."""
    K, L = x.shape
    iota = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (K, L))
    return range_stats_stream(
        iota, x, valid, window=rows_behind, max_behind=rows_behind,
        max_ahead=rows_ahead, window_ahead=rows_ahead, scale=scale,
        interpret=interpret,
    )
