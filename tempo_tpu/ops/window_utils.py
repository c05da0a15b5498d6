"""Shared window primitives for packed [K, L] series kernels.

These replace Spark's Window-expression machinery (reference
python/tempo/tsdf.py:563-580 window builders): instead of a sorted
shuffle + streaming window scan per key, we use O(L log L) data-parallel
primitives (prefix scans, log-doubling range queries, searchsorted) that
map onto the TPU VPU and keep everything inside one fused XLA program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# Prefix sums go through associative_scan: its log-depth association is
# the rounding order the f32 numerics bounds were measured on.  Running
# max/min are exact in any order, so they use lax.cummax/cummin, which
# XLA rewrites into an efficient scan on every backend — on TPU the
# associative_scan form feeding a gather and a compare compiled
# superlinearly in the lane width (~100 s at 1024 lanes).


def cumsum(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jax.lax.associative_scan(jnp.add, x, axis=axis % x.ndim)


def cummax(x: jnp.ndarray, axis: int = -1, reverse: bool = False) -> jnp.ndarray:
    return jax.lax.cummax(x, axis=axis % x.ndim, reverse=reverse)


def cummin(x: jnp.ndarray, axis: int = -1, reverse: bool = False) -> jnp.ndarray:
    return jax.lax.cummin(x, axis=axis % x.ndim, reverse=reverse)


def last_valid_index_xla(valid: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    n = valid.shape[axis]
    idx = jnp.arange(n, dtype=jnp.int32)
    idx = jnp.broadcast_to(idx, valid.shape)
    cand = jnp.where(valid, idx, -1)
    return cummax(cand, axis=axis)


def last_valid_index(valid: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Running index of the last True up to and including each position.

    -1 where no valid element has been seen yet.  This is the vectorised
    equivalent of Spark's ``last(col, ignoreNulls=True)`` over an
    unbounded-preceding window (reference tsdf.py:139).  On TPU the
    [K, L] lane-aligned case runs as a fused Pallas VMEM scan.
    """
    if valid.ndim == 2 and axis in (-1, 1):
        from tempo_tpu.ops import pallas_kernels as pk

        if pk._index_supported(jnp.asarray(valid)):
            return pk.last_valid_index_scan(valid)
    return last_valid_index_xla(valid, axis)


def first_valid_index_xla(valid: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    n = valid.shape[axis]
    idx = jnp.arange(n, dtype=jnp.int32)
    idx = jnp.broadcast_to(idx, valid.shape)
    cand = jnp.where(valid, idx, n)
    return cummin(cand, axis=axis, reverse=True)


def first_valid_index(valid: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Index of the first True at or after each position; n where none.

    Equivalent of ``first(col, ignoreNulls=True)`` over a current-row-to-
    unbounded-following window (reference interpol.py:216-222).  On TPU
    the [K, L] lane-aligned case runs as a fused Pallas VMEM scan.
    """
    if valid.ndim == 2 and axis in (-1, 1):
        from tempo_tpu.ops import pallas_kernels as pk

        if pk._index_supported(jnp.asarray(valid)):
            return pk.first_valid_index_scan(valid)
    return first_valid_index_xla(valid, axis)


def _shift_right(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """Shift along last axis: out[..., i] = x[..., i-k] (fill for i<k)."""
    if k == 0:
        return x
    pad = jnp.full(x.shape[:-1] + (k,), fill, dtype=x.dtype)
    return jnp.concatenate([pad, x[..., :-k]], axis=-1)


def windowed_max_last(x: jnp.ndarray, window: int) -> jnp.ndarray:
    """max over the trailing ``window`` elements (inclusive) per position.

    Log-doubling sparse-table construction: O(L log W) work, fully
    vectorised - the TPU-friendly replacement for Spark's
    ``rowsBetween(-W+1, 0)`` max scan (scala asofJoin.scala:64-88
    maxLookback window).
    """
    if window <= 0:
        raise ValueError("window must be >= 1")
    # a window covering the whole axis equals the axis length (and
    # _shift_right cannot represent longer shifts): callers may pass
    # caps larger than the data (asofJoin maxLookback)
    window = min(int(window), int(x.shape[-1]))
    neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    # doubling table: level k covers 2^k trailing elements
    levels = [x]
    span = 1
    while span < window:
        prev = levels[-1]
        levels.append(jnp.maximum(prev, _shift_right(prev, span, neg)))
        span *= 2
    if span == window:
        return levels[-1]
    # combine two overlapping power-of-two spans covering exactly `window`
    k = len(levels) - 1
    half = 1 << (k - 1)
    lo = levels[k - 1]
    return jnp.maximum(lo, _shift_right(lo, window - half, neg))


def windowed_last_valid(has: jnp.ndarray, val: jnp.ndarray, window: int,
                        min_pos: jnp.ndarray = None):
    """(value at the last ``has``-True position within the trailing
    ``window`` elements inclusive, found flag) per position.

    The bounded-lookback sibling of the unbounded forward-fill scan:
    the same log-doubling construction as :func:`windowed_max_last`
    (argmax is idempotent, so two overlapping power-of-two spans
    combine exactly) carrying the value as an argmax payload.  This is
    the engine of Scala's ``maxLookback`` rowsBetween(-W+1, 0) merged-
    stream cap (scala asofJoin.scala:64-88) in packed form.

    ``min_pos`` (broadcastable int32, the per-position segment-head
    lane) fences the window at segment boundaries for bin-packed rows:
    the found flag additionally requires the winning position to sit
    at-or-after it.  The fence is exact post-hoc because segments are
    contiguous and the ladder takes the *largest* has-position — a
    cross-segment candidate (strictly before the head, so a strictly
    smaller position) can only win when no same-segment candidate
    exists in the window.
    """
    if window <= 0:
        raise ValueError("window must be >= 1")
    # a window covering the whole axis is equivalent to the axis length
    # (and _shift_right cannot represent longer shifts)
    window = min(int(window), int(has.shape[-1]))
    lane = jnp.broadcast_to(
        jnp.arange(has.shape[-1], dtype=jnp.int32), has.shape
    )
    pos = jnp.where(has, lane, -1)

    def combine(p, v, ps, vs):
        take = ps > p
        return jnp.where(take, ps, p), jnp.where(take, vs, v)

    levels = [(pos, val)]
    span = 1
    while span < window:
        p, v = levels[-1]
        levels.append(combine(p, v, _shift_right(p, span, -1),
                              _shift_right(v, span, jnp.zeros((), v.dtype))))
        span *= 2
    p, v = levels[-1]
    if span != window:
        k = len(levels) - 1
        half = 1 << (k - 1)
        p, v = levels[k - 1]
        p, v = combine(p, v, _shift_right(p, window - half, -1),
                       _shift_right(v, window - half,
                                    jnp.zeros((), v.dtype)))
    floor = 0 if min_pos is None else jnp.maximum(min_pos, 0)
    return v, p >= floor


def searchsorted_batched(sorted_keys: jnp.ndarray, queries: jnp.ndarray, side: str = "left") -> jnp.ndarray:
    """Batched searchsorted over the leading (series) axis.

    API CONTRACT: ``queries`` MUST be ascending along the last axis (per
    row), in addition to ``sorted_keys``.  On TPU this dispatches to the
    sort-and-scan merge (:func:`tempo_tpu.ops.sortmerge.merge_rank`) —
    measured ~25x faster than binary search there, which lowers to a
    per-step dynamic gather — and the merge returns ranks in
    sorted-query order: unsorted queries get silently wrong ranks for
    the whole row, not an error.  Every tempo-tpu caller passes
    shifted/bucketed versions of an already-sorted time axis.  CPU keeps
    the vmapped binary search (fast native searchsorted, no sort cost),
    which happens to tolerate unsorted queries — do not rely on that.
    """
    from tempo_tpu.ops import sortmerge as sm

    if sorted_keys.ndim == 2 and queries.ndim == 2 and sm.use_sort_kernels():
        from tempo_tpu.ops import pallas_merge as pm

        if pm.merge_rank_supported(sorted_keys, queries):
            # one VMEM pass (merge + count + unmerge) instead of
            # merge_rank's two lax.sort ladders
            return pm.merge_rank_pallas(sorted_keys, queries, side=side)
        return sm.merge_rank(sorted_keys, queries, side=side)
    fn = lambda a, v: jnp.searchsorted(a, v, side=side)
    return jax.vmap(fn)(sorted_keys, queries)


def segment_bounds_from_sorted(ids: np.ndarray, n_segments: int) -> np.ndarray:
    """Host helper: start offsets [n_segments+1] of each id-run in a sorted
    id array (ids must be non-decreasing)."""
    counts = np.bincount(ids, minlength=n_segments)
    starts = np.zeros(n_segments + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    return starts
