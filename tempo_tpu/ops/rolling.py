"""Rolling / windowed statistics kernels on packed [K, L] series.

Replaces the reference's Spark Window scans:

* ``withRangeStats`` (tsdf.py:673-721): rangeBetween(-secs, 0) over the
  timestamp cast to long seconds, six aggregates per metric column plus
  a derived zscore.  Here: per-row window bounds from two vmapped
  ``searchsorted`` calls, sums/counts from exclusive prefix sums
  (mean-centred for f32-safe accumulation), min/max from an O(L log L)
  log-doubling sparse table - all fused by XLA into one pass over HBM.
* EMA (tsdf.py:615-635): the reference builds ``window`` lag-column
  expressions (plan blowup); here it is a single causal depthwise
  convolution with weights e(1-e)^i - MXU-friendly - plus an *exact*
  infinite-horizon variant via ``lax.associative_scan`` that the
  reference cannot express.
* grouped stats (tsdf.py:723-759): epoch-aligned tumbling windows as
  flat segment reductions (jax.ops.segment_*), num_segments static per
  call via host-computed bucket boundaries.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from tempo_tpu.ops import window_utils as wu

LANE = 128

# The range-stats engines, and the property of the input that picks
# each one (``pick_range_engine``; the frame's row bounds come from
# ``packing.layout_rowbounds``, the shapes from the packed planes):
#
# 1. **shifted**: W static masked shifted passes
#    (ops/sortmerge.py:range_stats_shifted; VMEM-resident via the
#    unrolled ops/pallas_window.py kernel on TPU).  Picked when the row
#    extent W = max_behind + max_ahead is at most
#    :func:`shifted_row_budget`: its work and compile grow with W, and
#    its XLA form materialises shifted copies in HBM.
# 2. **stream**: the streaming VMEM sweep
#    (ops/pallas_window.py:range_stats_stream), O(W) rotate passes with
#    the width a runtime scalar.  Picked for wider extents, up to
#    TEMPO_TPU_STREAM_MAX_ROWS, where a whole [bk, L] series block fits
#    its VMEM plan (``stream_block_feasible``: TPU, f32, L % 128 == 0).
# 3. **chunked**: fixed lane chunks with halos
#    (:func:`range_stats_chunk`, driven by rolling.py): each chunk's
#    block carries ``max_behind`` lanes before its core and at least
#    ``max_ahead`` after, so every window lies inside its block; prefix
#    sums restart per block and min/max come from a sparse table capped
#    at the levels the row bound needs.  One program per (block, halo)
#    shape, whatever the series' length.  Picked when neither form
#    above takes the frame and its series are longer than one block
#    (:func:`range_chunk_plan`); the caller says so (``chunked_ok``).
# 4. **windowed**: the whole-series prefix-scan + RMQ form
#    (:func:`windowed_stats`).  Everything else: series no longer than
#    one chunk block, frames without row bounds (int64 spans, the CPU
#    backend, which keeps the search-and-gather forms), and the mesh
#    path's shard programs.
#
# TEMPO_TPU_WINDOW_ENGINE forces one of shifted | stream | windowed
# (legacy keeps the pre-streaming pallas_stats kernel on the shifted
# path).
SHIFTED_MAX_ROWS = 512


def window_engine_override() -> str:
    from tempo_tpu import config

    return (config.get("TEMPO_TPU_WINDOW_ENGINE") or "auto").lower()


def pick_range_engine(n_elems: int, max_behind: int, max_ahead: int,
                      pallas_small_ok: bool = False,
                      stream_ok: bool = False,
                      chunked_ok: bool = False) -> str:
    """'shifted' | 'stream' | 'chunked' | 'windowed' for a frame whose
    row extent is (max_behind, max_ahead) on a shard of ``n_elems``
    values.  ``pallas_small_ok``/``stream_ok``: the caller verified the
    respective VMEM kernels can take this shard shape/dtype.
    ``chunked_ok``: the caller runs the lane-chunked form and its series
    are longer than one chunk block (:func:`range_chunk_plan`).

    When the lazy planner replays a node whose engine was hoisted to
    plan time (tempo_tpu/plan/optimizer.py), the decision arrives as a
    hint and wins — skipping the knob read — but only while it still
    matches what the current shard's bounds would pick.  The engines
    differ in FMA/rounding order, so a cached plan replayed over
    different data (same shapes, different row bounds) must re-pick
    rather than force an engine eager execution would not choose —
    that would break the planned==eager bit-identity contract
    (MIGRATION.md v0.7).  Join hints have no such guard because every
    join engine is bit-identical to the others."""
    from tempo_tpu.ops import pallas_window as pw
    from tempo_tpu.plan import hints as plan_hints

    W = int(max_behind) + int(max_ahead)
    fits_shifted = W <= shifted_row_budget(n_elems, pallas_small_ok)
    fits_stream = stream_ok and W <= pw._stream_max_rows()
    hinted = plan_hints.get("range_engine")
    if hinted in ("shifted", "stream", "chunked", "windowed"):
        rule = _rule_pick(fits_shifted, fits_stream, chunked_ok)
        if hinted == rule:
            return hinted
        # the data moved out from under the hoisted decision: fall
        # through and re-pick (knob read included)
    forced = window_engine_override()
    if forced in ("shifted", "stream", "windowed"):
        return forced
    from tempo_tpu.plan import cost as plan_cost

    if plan_cost.enabled():
        # cost-decided, but over the BITWISE-SAFE candidate set only:
        # the engines differ in f32 rounding order, so the revalidation
        # lattice above admits exactly one engine per shape and a cost
        # argmin cannot drift from the rule pick — the cost numbers
        # surface in explain() via the plan-time hoist, not on this
        # per-call path (plan/cost.py:decide_range_engine documents the
        # contract)
        return plan_cost.decide_range_engine(W, n_elems, fits_shifted,
                                             fits_stream, chunked_ok)
    return _rule_pick(fits_shifted, fits_stream, chunked_ok)


def _rule_pick(fits_shifted: bool, fits_stream: bool,
               chunked_ok: bool) -> str:
    if fits_shifted:
        return "shifted"
    if fits_stream:
        return "stream"
    if chunked_ok:
        return "chunked"
    return "windowed"


def range_stats_streaming(secs, x, valid, window, max_behind, max_ahead,
                          scale=None):
    """Streaming-engine entry: the VMEM sweep on TPU/f32/int32 keys,
    the exact windowed (prefix-scan + RMQ) form elsewhere.  Returns the
    ``range_stats_shifted`` output dict including ``clipped`` (always
    zero on the fallback — the windowed form has no truncation)."""
    from tempo_tpu.ops import pallas_window as pw

    secs = jnp.asarray(secs)
    x = jnp.asarray(x)
    valid = jnp.asarray(valid)
    if (secs.dtype == jnp.int32 and pw.stream_supported(x)
            and window_engine_override() != "windowed"):
        return pw.range_stats_stream(secs, x, valid, window,
                                     max_behind, max_ahead, scale=scale)
    if scale is not None:
        x = x * jnp.asarray(scale, x.dtype)
    start, end = range_window_bounds(secs, range_window_width(secs, window))
    try:
        max_w = 1 << (int(max_behind) + int(max_ahead) + 1).bit_length()
    except TypeError:
        # traced bounds (the streaming kernel takes them as runtime
        # scalars): build every sparse-table level instead
        max_w = 0
    stats = dict(windowed_stats(x, valid, start, end, max_window=max_w))
    stats["clipped"] = jnp.zeros((x.shape[0], 1), x.dtype)
    return stats


def packed_column_dispatch(n_cols, scales, gate, packed_group,
                           single_col):
    """Shared group/fallback/concat loop of the ``*_packed``
    multi-column entry points (here and
    ``sortmerge.range_stats_shifted_packed``).  Walks the column axis:
    where ``gate(c0)`` holds, ``packed_group(c0, scales_vec)`` reduces
    a kernel-pack-sized group in one pass and returns ``(width,
    stats-dict of [width, ...] planes)``; elsewhere ``single_col(c0,
    scale)`` runs the single-column dispatcher (results bitwise-equal
    to unpacked calls either way — the packed kernels trace the
    identical per-column op sequence).  Returns [C, ...] planes."""
    scv = None if scales is None else \
        jnp.broadcast_to(jnp.asarray(scales, jnp.float32).reshape(-1),
                         (n_cols,))
    parts = []
    c0 = 0
    while c0 < n_cols:
        if gate(c0):
            width, part = packed_group(c0, scv)
        else:
            width = 1
            single = single_col(c0, None if scv is None else scv[c0])
            part = {k: v[None] for k, v in single.items()}
        parts.append(part)
        c0 += width
    if len(parts) == 1:
        return parts[0]
    return {k: jnp.concatenate([p[k] for p in parts]) for k in parts[0]}


def range_stats_streaming_packed(secs, xs, valids, window, max_behind,
                                 max_ahead, scales=None):
    """Multi-column :func:`range_stats_streaming`: ``xs``/``valids``
    are [C, K, L] stacks over one [K, L] key plane.  On TPU the
    columns run as packed kernel passes (``pack_cols_budget``-sized
    groups — the key planes cross HBM once per group instead of once
    per column); elsewhere, and for any residual infeasible group, a
    per-column loop of :func:`range_stats_streaming` whose results are
    bitwise-identical to the unpacked calls.  Output planes are
    [C, K, L] ([C, K, 1] for ``clipped``)."""
    from tempo_tpu.ops import pallas_window as pw

    secs = jnp.asarray(secs)
    xs = jnp.asarray(xs)
    valids = jnp.asarray(valids)
    C, K, L = xs.shape

    def gate(c0):
        return (secs.dtype == jnp.int32 and pw.stream_supported(xs[c0])
                and window_engine_override() != "windowed")

    def packed_group(c0, scv):
        width = pw.pack_cols_budget(K, L, C - c0)
        return width, pw.range_stats_stream_packed(
            secs, xs[c0:c0 + width], valids[c0:c0 + width], window,
            max_behind, max_ahead,
            scales=None if scv is None else scv[c0:c0 + width])

    def single_col(c0, scale):
        return range_stats_streaming(secs, xs[c0], valids[c0], window,
                                     max_behind, max_ahead, scale=scale)

    return packed_column_dispatch(C, scales, gate, packed_group,
                                  single_col)


def shifted_row_budget(n_elems: int, pallas_ok: bool = False) -> int:
    """Largest row extent the shifted form may take for a shard of
    ``n_elems`` values.  The XLA form materialises ~2.4 shifted operand
    copies per unrolled pass (measured on v5e at [1024, 8192]: W=512
    demanded 40.9G of the 15.75G HBM; W=139 fit), so the memory bound
    scales inversely with the shard's element count; 12G of the 15.75G
    is budgeted, with a 3x-per-pass margin over the measured 2.4.

    ``pallas_ok`` (the caller verified the VMEM kernel can take this
    shard shape/dtype — pallas_stats.pallas_block_feasible) floors the
    budget at that kernel's window ceiling: extents IT accepts never
    materialise shifted copies in HBM.  The floor must not apply
    otherwise — a shard the Pallas gate rejects for shape reasons
    falls to the XLA form, where the memory bound is real (code-review
    r4 finding)."""
    from tempo_tpu.ops.pallas_stats import _PALLAS_STATS_MAX_W
    from tempo_tpu.ops.pallas_window import UNROLL_MAX_W

    mem_rows = int(12e9 // max(n_elems * 4 * 3, 1))
    if pallas_ok:
        mem_rows = max(mem_rows, _PALLAS_STATS_MAX_W, UNROLL_MAX_W)
    return min(SHIFTED_MAX_ROWS, mem_rows)


def _sparse_table(arr: jnp.ndarray, fill, reducer, nlev: int = 0) -> jnp.ndarray:
    """Log-doubling table [K, L, nlev]: level k reduces the trailing 2^k
    elements ending at each position.  ``nlev`` caps the levels when the
    caller knows the maximum window length (levels beyond
    floor(log2(max_len)) are never queried)."""
    L = arr.shape[-1]
    full = max(1, (L - 1).bit_length() + 1)
    nlev = full if nlev <= 0 else min(nlev, full)
    levels = [arr]
    span = 1
    for _ in range(nlev - 1):
        prev = levels[-1]
        levels.append(reducer(prev, wu._shift_right(prev, span, fill)))
        span *= 2
    return jnp.stack(levels, axis=-1)  # [K, L, nlev]


def _range_query(table: jnp.ndarray, start: jnp.ndarray, end: jnp.ndarray, reducer):
    """Reduce table's base array over [start, end) per row; end > start.

    Classic two-overlapping-spans RMQ: with k = floor(log2(end-start)),
    combine the 2^k-span ending at end-1 and the one ending at
    start+2^k-1.  The (position, level) lookup is a single gather into
    the level-flattened table: the chained two-gather form sent XLA's
    compiler into a 2-minute pathological optimisation (136s vs 2.6s
    compile, measured on v5e).
    """
    K, L, nlev = table.shape
    flat = table.reshape(K, L * nlev)
    # f32 log2 avoids f64 emulation on TPU but can round UP for lengths
    # just below a large power of two (e.g. 2^21-1 -> 21); a level whose
    # span exceeds the window would read out-of-window elements, so
    # decrement k when that happens (the true floor is then exactly k-1)
    length = jnp.maximum(end - start, 1)
    k = jnp.floor(jnp.log2(length.astype(jnp.float32))).astype(jnp.int32)
    k = jnp.where((1 << k) > length, k - 1, k)
    k = jnp.minimum(k, nlev - 1)
    span = (1 << k).astype(start.dtype)
    p1 = (end - 1).astype(jnp.int32) * nlev + k
    p2 = (start + span - 1).astype(jnp.int32) * nlev + k
    return reducer(
        jnp.take_along_axis(flat, p1, axis=1),
        jnp.take_along_axis(flat, p2, axis=1),
    )


def range_window_width(ts_long: jnp.ndarray, window_secs) -> jnp.ndarray:
    """Exact window-width operand for :func:`range_window_bounds` over
    an INTEGER seconds axis.  Membership ``ts >= t - w`` with integer
    keys equals ``ts >= t - floor(w)`` (a fractional remainder can
    never be met exactly by integer timestamps), so every width folds
    to the axis dtype: no float compare — neither the weak-f64 bound
    arithmetic a bare ``jnp.asarray(w)`` mints under the library's
    global x64 mode (the compiled no-f64-leak contract class) nor the
    epoch-scale rounding a float32 cast would inflict (~128 s
    resolution at 1.7e9).  The ONE way dist.py / parallel/halo.py /
    rolling.py build the operand; fractional widths keep exact Spark
    ``rangeBetween`` semantics.  A traced (jit-operand) width floors
    in its own dtype before the integer cast."""
    import math

    if isinstance(window_secs, jax.core.Tracer):
        w = jnp.asarray(window_secs)
        if jnp.issubdtype(w.dtype, jnp.integer):
            return w.astype(ts_long.dtype)
        return jnp.floor(w).astype(ts_long.dtype)
    return jnp.asarray(ts_long.dtype.type(math.floor(float(window_secs))))


@jax.jit
def range_window_bounds(
    ts_long: jnp.ndarray, window_secs: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row [start, end) bounds of rangeBetween(-window_secs, 0) over a
    sorted long-seconds timestamp axis.  Note Spark range windows include
    *following* rows that share the current row's order-key value, hence
    end = upper_bound(ts[i]) not i+1."""
    start = wu.searchsorted_batched(ts_long, ts_long - window_secs, side="left")
    end = wu.searchsorted_batched(ts_long, ts_long, side="right")
    return start.astype(jnp.int32), end.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_window",))
def windowed_stats(
    x: jnp.ndarray,        # [K, L] float values
    valid: jnp.ndarray,    # [K, L] bool
    start: jnp.ndarray,    # [K, L] int32 window start (inclusive)
    end: jnp.ndarray,      # [K, L] int32 window end (exclusive)
    max_window: int = 0,   # static upper bound on end-start rows (0 = L)
) -> Dict[str, jnp.ndarray]:
    """mean/count/min/max/sum/stddev(sample)/zscore over per-row windows.

    Accumulations are mean-centred per series before the prefix sums so
    the sum-of-squares cancellation stays benign even in float32.  When
    the caller can bound the window length in rows (``max_window``), the
    min/max sparse tables only build the levels that bound can query —
    at a 10s window over ~1Hz data that is 4 levels instead of 14.
    Passing a bound smaller than a real window silently degrades min/max
    coverage, so callers must compute it from the actual bounds.
    """
    xz = jnp.where(valid, x, 0.0)
    n_valid = jnp.sum(valid, axis=-1, keepdims=True)
    center = jnp.sum(xz, axis=-1, keepdims=True) / jnp.maximum(n_valid, 1)
    xc = jnp.where(valid, x - center, 0.0)

    # inclusive prefix sums (one fused Pallas pass on TPU/f32); the
    # window query uses C[e-1] - C[s-1] with C[-1] = 0
    from tempo_tpu.ops import pallas_kernels as pk

    prefix = pk.cumsum3(xc, valid)
    nlev = (max(1, int(max_window)) - 1).bit_length() + 1 if max_window else 0
    return _stats_over_windows(x, valid, center, prefix, start, end, nlev,
                               x, valid)


def _stats_over_windows(x, valid, center, prefix, start, end, nlev,
                        x_row, valid_row) -> Dict[str, jnp.ndarray]:
    """The seven stats of each row from its window ``[start, end)`` over
    the lanes of ``x``: ``prefix`` holds the inclusive prefix sums of
    the centred values, their squares and the valid count; min and max
    come from sparse tables of ``nlev`` levels (0: every level).
    ``x_row``/``valid_row`` are the rows' own values, for the zscore,
    lane for lane with ``start``/``end``."""
    P1, P2, Pc = prefix

    def win(P):
        P = P.astype(x.dtype)
        hi = jnp.take_along_axis(P, jnp.maximum(end - 1, 0), axis=-1)
        hi = jnp.where(end > 0, hi, 0.0)
        lo = jnp.take_along_axis(P, jnp.maximum(start - 1, 0), axis=-1)
        lo = jnp.where(start > 0, lo, 0.0)
        return hi - lo

    s1, s2, cnt = win(P1), win(P2), win(Pc)
    mean = jnp.where(cnt > 0, s1 / jnp.maximum(cnt, 1) + center, jnp.nan)
    total = s1 + cnt * center
    var = jnp.where(
        cnt > 1, (s2 - s1 * s1 / jnp.maximum(cnt, 1)) / jnp.maximum(cnt - 1, 1), jnp.nan
    )
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    std = jnp.where(cnt > 1, std, jnp.nan)

    pinf = jnp.array(jnp.inf, x.dtype)
    tmin = _sparse_table(jnp.where(valid, x, pinf), pinf, jnp.minimum, nlev)
    tmax = _sparse_table(jnp.where(valid, x, -pinf), -pinf, jnp.maximum, nlev)
    wmin = _range_query(tmin, start, end, jnp.minimum)
    wmax = _range_query(tmax, start, end, jnp.maximum)
    wmin = jnp.where(cnt > 0, wmin, jnp.nan)
    wmax = jnp.where(cnt > 0, wmax, jnp.nan)

    zscore = (x_row - mean) / std
    return {
        "mean": mean,
        "count": cnt,
        "min": wmin,
        "max": wmax,
        "sum": jnp.where(cnt > 0, total, jnp.nan),
        "stddev": std,
        "zscore": jnp.where(valid_row, zscore, jnp.nan),
    }


#: lanes of a range-stats chunk block, halos included, at the least
RANGE_BLOCK_LANES = 1 << 17
#: a chunk block is at least this many halos long, so halos stay a
#: small share of the lanes computed
RANGE_BLOCK_HALOS = 8
#: chunk blocks per call of :func:`range_stats_chunk`: every call has
#: the same shape, so one compile serves every series length
RANGE_CHUNK_ROWS = 8
#: the stats of :func:`range_stats_chunk`'s output, in its order
CHUNK_STATS = ("count", "max", "mean", "min", "stddev", "sum", "zscore")


def range_chunk_plan(max_behind: int, max_ahead: int) -> Tuple[int, int, int]:
    """``(block, halo, nlev)`` of the lane-chunked range stats for a
    frame whose windows reach at most ``max_behind`` rows back and
    ``max_ahead`` tie rows ahead (``packing.layout_rowbounds``).

    ``halo`` is the power of two (at least 128) that holds both: a
    chunk's block starts ``max_behind`` lanes before its core and ends
    ``halo - max_behind >= max_ahead`` lanes after it, so every core
    row's window lies inside the block.  ``block`` is a multiple of 128,
    at least :data:`RANGE_BLOCK_LANES` and :data:`RANGE_BLOCK_HALOS`
    halos; the core is ``block - halo`` lanes.  ``nlev`` sparse-table
    levels reach a window of ``halo + 1`` rows.  One compiled program
    serves each plan, whatever the series' lengths."""
    reach = int(max_behind) + int(max_ahead)
    halo = max(LANE, 1 << max(reach - 1, 0).bit_length())
    block = max(RANGE_BLOCK_LANES, int(math.ceil(RANGE_BLOCK_HALOS * halo)),
                halo + LANE)
    block = -(-block // LANE) * LANE
    return block, halo, halo.bit_length()


@functools.partial(jax.jit, static_argnames=("nlev",))
def range_stats_chunk(x, valid, start, end, center, core_start, nlev: int):
    """The seven range stats of the core lanes of ``G`` chunk blocks, as
    one ``[7, G, Lc]`` array in :data:`CHUNK_STATS` order.

    ``x``/``valid`` are ``[G, B]`` blocks (pads invalid), ``start`` and
    ``end`` the ``[G, Lc]`` windows of the core lanes as block lanes,
    ``center`` the ``[G, 1]`` mean of each block's series, which is
    subtracted before the prefix sums as :func:`windowed_stats` does,
    and ``core_start`` the block lane where the core begins (a runtime
    scalar).  The prefix sums restart in every block, so their
    magnitudes stay those of one block whatever the series' length."""
    Lc = start.shape[-1]
    xc = jnp.where(valid, x - center, 0.0)
    prefix = (jax.lax.cumsum(xc, axis=1), jax.lax.cumsum(xc * xc, axis=1),
              jax.lax.cumsum(valid.astype(x.dtype), axis=1))
    x_row = jax.lax.dynamic_slice_in_dim(x, core_start, Lc, axis=1)
    valid_row = jax.lax.dynamic_slice_in_dim(valid, core_start, Lc, axis=1)
    stats = _stats_over_windows(x, valid, center, prefix, start, end, nlev,
                                x_row, valid_row)
    return jnp.stack([stats[k] for k in CHUNK_STATS])


def bucket_stats(bid, x, valid, start, end):
    """Tumbling-bucket aggregates broadcast to every row of the bucket
    (the resample/groupedStats reduction, reference resample.py:38-117
    / tsdf.py:723-759).  On TPU/f32 the whole reduction runs as ONE
    VMEM segmented-scan kernel (ops/pallas_bucket.py — no
    searchsorteds, no prefix-sum gathers, no RMQ tables); elsewhere the
    ``windowed_stats`` form over the precomputed [start, end) bucket
    bounds.  ``bid`` is the per-row int32 bucket id (non-decreasing;
    pad rows share a clamped id and form their own bucket — callers
    mask their outputs)."""
    from tempo_tpu.ops import pallas_bucket as pb

    if pb.bucket_stats_supported(x):
        return pb.bucket_stats_pallas(bid, x, valid)
    return windowed_stats(x, valid, start, end)


def bucket_stats_multi(bid, xs, valids, start, end):
    """Multi-column :func:`bucket_stats`: ``xs``/``valids`` are
    [C, K, L] stacks over one [K, L] bucket-id plane.  On TPU the
    columns run as packed kernel passes
    (``pallas_bucket.bucket_pack_budget``-sized groups — the id plane
    and its head/tail flag ladders cross HBM and the VPU once per group
    instead of once per column); elsewhere, and for any infeasible
    column, the single-column dispatch.  Returns [C, K, L] planes,
    bitwise-identical to C :func:`bucket_stats` calls."""
    from tempo_tpu.ops import pallas_bucket as pb

    xs = jnp.asarray(xs)
    valids = jnp.asarray(valids)
    C, K, L = xs.shape

    def gate(c0):
        return pb.bucket_stats_supported(xs[c0])

    def packed_group(c0, scv):
        width = pb.bucket_pack_budget(K, L, C - c0)
        return width, pb.bucket_stats_packed(
            bid, xs[c0:c0 + width], valids[c0:c0 + width])

    def single_col(c0, scale):
        return bucket_stats(bid, xs[c0], valids[c0], start, end)

    return packed_column_dispatch(C, None, gate, packed_group,
                                  single_col)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def segment_stats(
    x: jnp.ndarray,        # [n] flat values
    valid: jnp.ndarray,    # [n] bool
    seg_ids: jnp.ndarray,  # [n] int32 sorted segment ids
    num_segments: int,
) -> Dict[str, jnp.ndarray]:
    """Six grouped aggregates per segment (withGroupedStats tsdf.py:750-754)."""
    xz = jnp.where(valid, x, 0.0)
    cnt = jax.ops.segment_sum(valid.astype(x.dtype), seg_ids, num_segments)
    s1 = jax.ops.segment_sum(xz, seg_ids, num_segments)
    s2 = jax.ops.segment_sum(xz * xz, seg_ids, num_segments)
    pinf = jnp.array(jnp.inf, x.dtype)
    mn = jax.ops.segment_min(jnp.where(valid, x, pinf), seg_ids, num_segments)
    mx = jax.ops.segment_max(jnp.where(valid, x, -pinf), seg_ids, num_segments)
    mean = jnp.where(cnt > 0, s1 / jnp.maximum(cnt, 1), jnp.nan)
    var = jnp.where(
        cnt > 1, (s2 - s1 * s1 / jnp.maximum(cnt, 1)) / jnp.maximum(cnt - 1, 1), jnp.nan
    )
    std = jnp.where(cnt > 1, jnp.sqrt(jnp.maximum(var, 0.0)), jnp.nan)
    return {
        "mean": mean,
        "count": cnt,
        "min": jnp.where(cnt > 0, mn, jnp.nan),
        "max": jnp.where(cnt > 0, mx, jnp.nan),
        "sum": jnp.where(cnt > 0, s1, jnp.nan),
        "stddev": std,
    }


@functools.partial(jax.jit, static_argnames=("window",))
def ema_compat(x: jnp.ndarray, valid: jnp.ndarray, window: int, exp_factor: float) -> jnp.ndarray:
    """Reference-parity truncated EMA (tsdf.py:615-635):
    EMA_t = sum_{i=0}^{window-1} e(1-e)^i * x_{t-i}, null lags contribute 0.

    One causal depthwise convolution instead of `window` stacked Spark
    window expressions.
    """
    w = exp_factor * (1.0 - exp_factor) ** jnp.arange(window, dtype=x.dtype)
    xz = jnp.where(valid, x, 0.0)[:, None, :]                  # [K, 1, L]
    filt = w[::-1][None, None, :]                              # [1, 1, W]
    y = jax.lax.conv_general_dilated(
        xz, filt, window_strides=(1,), padding=[(window - 1, 0)],
        dimension_numbers=("NCH", "IOH", "NCH"),
    )
    return y[:, 0, :]


def ema_scan(x: jnp.ndarray, valid: jnp.ndarray, alpha,
             y0: jnp.ndarray = None):
    """Sequential (``lax.scan``) twin of :func:`ema_exact` with an
    explicit carry: ``(ys, y_end)`` where ``ys`` is the EMA at every
    position and ``y_end`` the carry after the last one.

    Same recurrence — ``y_t = decay_t * y_{t-1} + inp_t`` with
    ``decay = 1-a`` / ``inp = a*x`` at valid rows and ``1`` / ``0`` at
    null rows — but evaluated strictly left-to-right, ONE multiply-add
    per element.  That makes it **split-invariant bitwise**: feeding
    ``y_end`` back as ``y0`` across any batch boundary reproduces the
    unsplit run bit-for-bit, which is the contract the online serving
    engine is built on (``tempo_tpu/serve/state.py``).
    :func:`ema_exact`'s ``associative_scan`` computes the same values
    through a combine tree whose bracketing — and therefore f32
    rounding — depends on the total length, so it cannot be resumed
    mid-stream exactly.  ``y0=None`` starts from the zero carry, which
    matches the scan's implicit start exactly (``0*d + i == i``)."""
    a = jnp.asarray(alpha, x.dtype)
    one = jnp.asarray(1.0, x.dtype)
    zero = jnp.asarray(0.0, x.dtype)
    decay = jnp.where(valid, one - a, one)
    inp = jnp.where(valid, a * x, zero)
    if y0 is None:
        y0 = jnp.zeros(x.shape[:-1], x.dtype)

    def step(y, di):
        d, i = di
        y2 = d * y + i
        return y2, y2

    y_end, ys = jax.lax.scan(
        step, y0, (jnp.moveaxis(decay, -1, 0), jnp.moveaxis(inp, -1, 0)))
    return jnp.moveaxis(ys, 0, -1), y_end


@jax.jit
def ema_exact(x: jnp.ndarray, valid: jnp.ndarray, alpha: float) -> jnp.ndarray:
    """Exact infinite-horizon EMA y_t = (1-a) y_{t-1} + a x_t via an
    associative scan (the full story of the reference's truncated-lag
    approximation and this stack's exact forms:
    resample.py:resample_ema, "Truncated-lag EMA — the canonical
    note").  Null inputs carry the previous EMA forward."""
    a = jnp.asarray(alpha, x.dtype)
    decay = jnp.where(valid, 1.0 - a, 1.0)
    inp = jnp.where(valid, a * x, 0.0)

    def combine(c1, c2):
        d1, v1 = c1
        d2, v2 = c2
        return d1 * d2, v2 + d2 * v1

    d, y = jax.lax.associative_scan(combine, (decay, inp), axis=1)
    return y
