"""Sort-and-scan kernels: the TPU-native form of search-and-gather.

Measured on one v5e chip before this code's first PR, shapes
[1024, 8192]: a single dynamic gather
(``take_along_axis``) costs ~96 ms and a vmapped ``jnp.searchsorted``
1.4 s (f32) to 4.0 s (i64) — while a full-width lane *sort* costs 14-17
ms and an associative scan 6-11 ms.  The reference leans on Spark's
sort-based shuffle for exactly this reason (tsdf.py:111-162: union,
sort, running ``last``); the TPU analog is ``lax.sort`` + scans, not
binary search.  This module provides the three hot primitives in that
form:

* :func:`merge_rank` — batched searchsorted of sorted queries into
  sorted keys via two stable sorts and a prefix count.  O((Lk+Lq) log)
  comparisons, zero gathers.
* :func:`asof_merge_values` — the AS-OF join producing joined *values*
  directly: one multi-operand merge sort, one batched forward-fill
  scan, one routing sort.  Replaces searchsorted + per-column index
  gathers + value gathers (the reference's whole
  ``__getLastRightRow`` contract, tsdf.py:111-162, including
  skipNulls and the sequence-number tie-break of tsdf.py:117-121).
* :func:`range_stats_shifted` — ``withRangeStats`` (tsdf.py:673-721)
  for row-bounded windows as W shifted masked accumulations: for a 10 s
  window over ~1 Hz data that is ~32 cheap elementwise passes (0.6 ms
  total) instead of prefix-sum boundary gathers and sparse-table RMQ
  lookups (~1 s).

All three are pure jittable functions usable inside shard_map blocks.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _icumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the last axis (log-depth scan)."""
    return jax.lax.associative_scan(jnp.add, x, axis=x.ndim - 1)


@functools.partial(jax.jit, static_argnames=("side",))
def merge_rank(
    sorted_keys: jnp.ndarray,     # [K, Lk], ascending per row
    sorted_queries: jnp.ndarray,  # [K, Lq], ascending per row
    side: str = "left",
) -> jnp.ndarray:
    """``searchsorted`` of each query row into each key row, computed by
    merging rather than searching.

    REQUIRES both inputs ascending along the last axis (every packed-
    layout caller satisfies this: timestamps ascend and ``TS_PAD`` pads
    sort to the end with headroom, packing.py:33-41).  Matches
    ``np.searchsorted(keys[k], queries[k], side)`` exactly.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    K, Lk = sorted_keys.shape
    Lq = sorted_queries.shape[-1]
    dt = jnp.promote_types(sorted_keys.dtype, sorted_queries.dtype)

    vals = jnp.concatenate(
        [sorted_keys.astype(dt), sorted_queries.astype(dt)], axis=-1
    )
    # tie order decides left/right bound: side='left' -> queries sort
    # before equal keys (rank counts strictly-smaller keys); 'right' ->
    # after (rank counts keys <= query)
    tq, tk = (0, 1) if side == "left" else (1, 0)
    tie = jnp.concatenate(
        [jnp.full((K, Lk), tk, jnp.int32), jnp.full((K, Lq), tq, jnp.int32)],
        axis=-1,
    )
    is_key = jnp.concatenate(
        [jnp.ones((K, Lk), jnp.int32), jnp.zeros((K, Lq), jnp.int32)],
        axis=-1,
    )
    _, _, is_key_s = jax.lax.sort(
        (vals, tie, is_key), dimension=-1, num_keys=2, is_stable=True
    )
    nkeys = _icumsum(is_key_s)  # at a query slot: #keys at-or-before it
    # route query results back to original query order: queries were
    # sorted, so a stable sort on (is_key) puts them first, in order
    _, rank = jax.lax.sort(
        (is_key_s, nkeys), dimension=-1, num_keys=1, is_stable=True
    )
    return rank[..., :Lq]


def _ffill_scan(has: jnp.ndarray, val: jnp.ndarray, axis: int = -1):
    """Batched last-valid carry: at each position, the most recent
    ``val`` where ``has`` was True (and whether any was seen)."""

    def combine(a, b):
        ha, va = a
        hb, vb = b
        return ha | hb, jnp.where(hb, vb, va)

    return jax.lax.associative_scan(
        combine, (has, val), axis=axis % has.ndim
    )


def asof_merge_values(
    l_ts: jnp.ndarray,            # [K, Ll] int64 ns (TS_PAD padded)
    r_ts: jnp.ndarray,            # [K, Lr] int64 ns
    r_valids: jnp.ndarray,        # [C, K, Lr] bool
    r_values: jnp.ndarray,        # [C, K, Lr] float
    l_seq: Optional[jnp.ndarray] = None,   # [K, Ll] sortable seq key
    r_seq: Optional[jnp.ndarray] = None,   # [K, Lr]
    skip_nulls: bool = True,
    max_lookback: int = 0,        # merged-stream row cap; 0 = unbounded
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """AS-OF join returning values directly: ``(vals [C, K, Ll],
    found [C, K, Ll], last_row_idx [K, Ll])``.

    Semantics mirror the reference's union-sort-scan
    (tsdf.py:111-162): per left row, the last right row at-or-before it
    in (ts [, seq], side) order, right rows winning full ties
    (rec_ind -1 < 1, tsdf.py:119,546); ``skip_nulls`` takes each
    column's last *non-null* value independently (tsdf.py:139), else
    all columns come from the single last right row, nulls included
    (tsdf.py:123-136).  Sequence keys, when given, order with Spark's
    NULLS FIRST via the caller mapping nulls to -inf.

    One merge sort (ts [, seq], side) carrying C value planes, one
    batched forward-fill scan, one routing sort.  No gathers.

    Dispatches OUTSIDE jit so the ``TEMPO_TPU_NAN_ASOF`` opt-in (a
    leaner NaN-encoded variant whose fused pipeline once took over
    30 min to compile, so it is off by default) takes effect per call,
    not per first-trace.

    On TPU every f32 shape of the join — including the sequence
    tie-break (extra kernel key planes) and skipNulls=False (lockstep
    keyed fill) since round 4 — runs as ONE Pallas kernel: bitonic
    *merge* network + ffill ladder + routing sort, all VMEM-resident
    (``ops/pallas_merge.py``) — measured 7.5x this module's lax.sort
    form at [1024, 8192]: the sort ladders pay an HBM round-trip per
    compare-exchange stage, the kernel touches HBM twice total.
    """
    from tempo_tpu.ops import pallas_merge as pm

    if not max_lookback:
        # f64 seq planes re-encode (f32 / int64) before the kernel gate
        # — the TPU X64 rewriter has no 64-bit bitcast (seq_kernel_form)
        l_seq_k = pm.seq_kernel_form(l_seq)
        r_seq_k = pm.seq_kernel_form(r_seq)
        expressible = (l_seq is None or l_seq_k is not None) and \
            (r_seq is None or r_seq_k is not None)
        if expressible and not _forced_bitonic() \
                and pm.merge_join_supported(
                l_ts, r_ts, r_values, l_seq_k, r_seq_k, skip_nulls):
            return pm.asof_merge_values_pallas(
                l_ts, r_ts, r_valids, r_values, l_seq=l_seq_k,
                r_seq=r_seq_k, skip_nulls=skip_nulls,
            )
        if expressible and _oversize_bitonic(l_ts, r_ts, r_values,
                                             l_seq_k, r_seq_k):
            # past the lax.sort compiler ceiling (and the VMEM plan):
            # the XLA bitonic network joins at O(log Lc) full-array
            # stages instead of O(log^2), tracer-safe — the per-shard
            # oversize engine of the mesh paths (dist.py, parallel/halo)
            return pm.asof_merge_values_bitonic(
                l_ts, r_ts, r_valids, r_values, l_seq=l_seq_k,
                r_seq=r_seq_k, skip_nulls=skip_nulls,
            )
    if not max_lookback and skip_nulls \
            and jnp.issubdtype(r_values.dtype, jnp.floating) \
            and _nan_encoding_enabled():
        return _asof_merge_nan_encoded(l_ts, r_ts, r_valids, r_values,
                                       l_seq, r_seq)
    return _asof_merge_explicit(l_ts, r_ts, r_valids, r_values,
                                l_seq, r_seq, skip_nulls=skip_nulls,
                                max_lookback=int(max_lookback))


def _forced_bitonic() -> bool:
    from tempo_tpu import profiling

    return profiling.join_engine_override() == "bitonic"


def _oversize_bitonic(l_ts, r_ts, r_values, l_seq, r_seq) -> bool:
    """Whether the merged width sits in the regime where the lax.sort
    ladders OOM-kill the XLA compiler (~205K merged lanes, round-3 chip
    notes) and the f32 bitonic network should run instead.  Forced on/off
    by TEMPO_TPU_JOIN_ENGINE=bitonic / single|bracket (the forced form
    also suppresses the single-plan Pallas branch at the call sites —
    the knob must measure the engine it names)."""
    from tempo_tpu import profiling, resilience
    from tempo_tpu.ops import pallas_merge as pm

    if not pm.merge_join_bitonic_supported(l_ts, r_ts, r_values,
                                           l_seq, r_seq):
        return False
    forced = profiling.join_engine_override()
    if forced == "bitonic":
        return True
    if forced in ("single", "bracket"):
        return False
    limit = resilience.max_merged_lanes()
    return 0 < limit < int(l_ts.shape[-1]) + int(r_ts.shape[-1])


def _merge_sides(l_ts, r_ts, l_seq, r_seq):
    """Shared merged sort-key construction: (ts [, seq], side), right
    rows sorting before left rows on full ties (rec_ind -1 < 1), null
    seq sides riding the dtype minimum (NULLS FIRST)."""
    K, Ll = l_ts.shape
    Lr = r_ts.shape[-1]
    ts = jnp.concatenate([l_ts, r_ts], axis=-1)
    is_left = jnp.concatenate(
        [jnp.ones((K, Ll), jnp.int32), jnp.zeros((K, Lr), jnp.int32)],
        axis=-1,
    )
    keys = [ts]
    if l_seq is not None or r_seq is not None:
        sdt = (l_seq if l_seq is not None else r_seq).dtype
        neg = (
            jnp.finfo(sdt).min
            if jnp.issubdtype(sdt, jnp.floating)
            else jnp.iinfo(sdt).min
        )
        ls = l_seq if l_seq is not None else jnp.full((K, Ll), neg, sdt)
        rs = r_seq if r_seq is not None else jnp.full((K, Lr), neg, sdt)
        keys.append(jnp.concatenate([ls, rs], axis=-1))
    keys.append(is_left)
    return keys, is_left


@functools.partial(jax.jit,
                   static_argnames=("skip_nulls", "max_lookback"))
def _asof_merge_explicit(l_ts, r_ts, r_valids, r_values, l_seq=None,
                         r_seq=None, skip_nulls=True,
                         l_sid=None, r_sid=None, max_lookback=0):
    """Default form: validity rides as explicit bool planes.  With
    ``l_sid``/``r_sid`` (bin-packed rows) the series id leads the sort
    keys and the fill is fenced at series boundaries — for every fill
    flavour: the unbounded scan turns segmented, and the
    ``max_lookback`` windowed argmax ladder (Scala's
    rowsBetween(-maxLookback, 0) on the union stream,
    asofJoin.scala:64-88) rejects candidates before the series' own
    segment head (contiguous series + positional argmax make the
    post-hoc fence exact: a cross-segment candidate only wins when no
    same-segment one exists, window_utils.windowed_last_valid).
    """
    C = int(r_values.shape[0])
    K, Ll = l_ts.shape
    Lr = r_ts.shape[-1]
    Lc = Ll + Lr
    vdt = r_values.dtype

    keys, is_left = _merge_sides(l_ts, r_ts, l_seq, r_seq)
    if l_sid is not None:
        sid = jnp.concatenate(
            [l_sid.astype(jnp.int32), r_sid.astype(jnp.int32)], axis=-1
        )
        keys = [sid] + keys

    ridx = jnp.concatenate(
        [
            jnp.full((K, Ll), -1, jnp.int32),
            jnp.broadcast_to(jnp.arange(Lr, dtype=jnp.int32), (K, Lr)),
        ],
        axis=-1,
    )

    # value/valid planes: left slots carry zeros (never read — the scan
    # only consumes right-tagged slots)
    zeros_l = jnp.zeros((C, K, Ll), vdt)
    planes = jnp.concatenate([zeros_l, r_values], axis=-1)      # [C, K, Lc]
    falses_l = jnp.zeros((C, K, Ll), jnp.bool_)
    vplanes = jnp.concatenate([falses_l, r_valids], axis=-1)    # [C, K, Lc]

    ops = tuple(keys) + (ridx,) + tuple(planes[c] for c in range(C)) \
        + tuple(vplanes[c] for c in range(C))
    sorted_ops = jax.lax.sort(
        ops, dimension=-1, num_keys=len(keys), is_stable=True
    )
    nk = len(keys)
    is_left_s = sorted_ops[nk - 1]
    ridx_s = sorted_ops[nk]
    planes_s = jnp.stack(sorted_ops[nk + 1: nk + 1 + C]) if C else \
        jnp.zeros((0, K, Lc), vdt)
    vplanes_s = jnp.stack(sorted_ops[nk + 1 + C:]) if C else \
        jnp.zeros((0, K, Lc), jnp.bool_)
    is_right_s = is_left_s == 0

    if l_sid is not None:
        sid_s = sorted_ops[0]
        head = jnp.concatenate(
            [jnp.ones((K, 1), jnp.bool_),
             sid_s[:, 1:] != sid_s[:, :-1]], axis=-1
        )
    else:
        head = None

    def fill(has, val):
        """Unbounded ffill (segmented over bin-packed series), or the
        windowed argmax ladder when the merged-stream row cap is active
        (fenced at the series' segment head for bin-packed rows)."""
        if max_lookback:
            from tempo_tpu.ops import window_utils as wu

            min_pos = None
            if head is not None:
                lane = jnp.broadcast_to(
                    jnp.arange(Lc, dtype=jnp.int32), (K, Lc)
                )
                min_pos = _ffill_scan(head, jnp.where(head, lane, 0))[1]
            val_f, has_f = wu.windowed_last_valid(
                has, val, max_lookback + 1, min_pos=min_pos
            )
            return has_f, val_f
        if head is not None:
            _, has_f, val_f = _ffill_scan_seg(
                jnp.broadcast_to(head, has.shape), has, val
            )
            return has_f, val_f
        return _ffill_scan(has, val)

    # batched forward fill: stack [C+1] problems and scan once.
    # channel C is the last-right-row index (validity = any right row)
    if skip_nulls:
        has = jnp.concatenate(
            [is_right_s[None] & vplanes_s,
             jnp.broadcast_to(is_right_s, (1, K, Lc))], axis=0
        )
        val = jnp.concatenate(
            [jnp.where(vplanes_s, planes_s, 0.0),
             ridx_s[None].astype(vdt)], axis=0
        )
        has_f, val_f = fill(has, val)
        vals_sorted = val_f[:C]
        found_sorted = has_f[:C]
        idx_sorted = jnp.where(has_f[C], val_f[C].astype(jnp.int32), -1)
    else:
        # all columns ride the single last right row: fill (value,
        # validity) pairs keyed on is_right only
        has = jnp.broadcast_to(is_right_s, (2 * C + 1, K, Lc))
        val = jnp.concatenate(
            [planes_s, vplanes_s.astype(vdt), ridx_s[None].astype(vdt)],
            axis=0,
        )
        has_f, val_f = fill(has, val)
        vals_sorted = val_f[:C]
        found_sorted = has_f[:C] & (val_f[C: 2 * C] > 0.5)
        idx_sorted = jnp.where(has_f[2 * C], val_f[2 * C].astype(jnp.int32),
                               -1)

    # route left rows back to original order: stable sort on is_left
    # descending (left first).  Left rows were originally ascending in
    # the same total order, so their merged relative order IS the
    # original order.
    route = tuple([1 - is_left_s, idx_sorted]
                  + [vals_sorted[c] for c in range(C)]
                  + [found_sorted[c] for c in range(C)])
    routed = jax.lax.sort(route, dimension=-1, num_keys=1, is_stable=True)
    idx_l = routed[1][..., :Ll]
    vals_l = jnp.stack([routed[2 + c][..., :Ll] for c in range(C)]) if C \
        else jnp.zeros((0, K, Ll), vdt)
    found_l = jnp.stack([routed[2 + C + c][..., :Ll] for c in range(C)]) \
        if C else jnp.zeros((0, K, Ll), jnp.bool_)
    vals_l = jnp.where(found_l, vals_l, jnp.nan)
    return vals_l, found_l, idx_l


def asof_merge_values_binpacked(l_ts, r_ts, r_valids, r_values,
                                l_sid, r_sid, skip_nulls: bool = True,
                                max_lookback: int = 0,
                                l_seq=None, r_seq=None):
    """AS-OF join over *bin-packed* rows: each [K, L] lane row holds
    several series back-to-back, identified by the non-decreasing
    ``sid`` planes (packing.py:bin_pack_series).  Right rows win full
    ties — the same contract as :func:`asof_merge_values` including
    ``skip_nulls``, the ``max_lookback`` merged-row cap (both fenced
    at series boundaries) and, since round 6, the sequence tie-break
    (REQUIRES the packed runs sorted by (ts, seq) per series — what
    join.py's layouts guarantee when a seq plane is packed), with
    ``last_row_idx`` a within-lane-row position.  The TPU answer to
    Zipf-skewed key distributions (the reference's tsPartitionVal
    machinery, tsdf.py:164-190): instead of padding every series to
    the longest (96% padding on NBBO-shaped data, round-2 verdict),
    short series share lane rows at ~full occupancy and one compiled
    program serves every skew shape.
    """
    from tempo_tpu.ops import pallas_merge as pm

    l_seq_k = pm.seq_kernel_form(l_seq)
    r_seq_k = pm.seq_kernel_form(r_seq)
    expressible = (l_seq is None or l_seq_k is not None) and \
        (r_seq is None or r_seq_k is not None)
    if not max_lookback and expressible and not _forced_bitonic() \
            and pm.merge_join_supported(
            l_ts, r_ts, r_values, l_seq_k, r_seq_k, skip_nulls,
            segmented=True):
        return pm.asof_merge_values_pallas(l_ts, r_ts, r_valids,
                                           r_values, l_sid, r_sid,
                                           l_seq=l_seq_k, r_seq=r_seq_k,
                                           skip_nulls=skip_nulls)
    if not max_lookback and expressible and _oversize_bitonic(
            l_ts, r_ts, r_values, l_seq_k, r_seq_k):
        return pm.asof_merge_values_bitonic(
            l_ts, r_ts, r_valids, r_values, l_sid, r_sid,
            l_seq=l_seq_k, r_seq=r_seq_k, skip_nulls=skip_nulls)
    return _asof_merge_explicit(l_ts, r_ts, r_valids, r_values,
                                l_seq=l_seq, r_seq=r_seq,
                                l_sid=l_sid, r_sid=r_sid,
                                skip_nulls=skip_nulls,
                                max_lookback=int(max_lookback))


def asof_indices_binpacked(l_ts, r_ts, r_valids, l_sid, r_sid,
                           max_lookback: int = 0, r_seq=None):
    """Index-returning bin-packed join: same layout contract as
    :func:`asof_merge_values_binpacked`, position-encoded payloads.
    Returns ``(last_row_idx, per_col_idx)`` as WITHIN-LANE-ROW
    positions (-1 none); callers convert to per-series indices with
    the offsets they packed with (join.py does)."""
    C, K, Lr = r_valids.shape
    vdt = jnp.float32 if use_sort_kernels() else jnp.float64
    pos = jnp.broadcast_to(jnp.arange(Lr, dtype=vdt), (K, Lr))
    planes = jnp.broadcast_to(pos[None], (C, K, Lr))
    vals, found, last_idx = asof_merge_values_binpacked(
        l_ts, r_ts, r_valids, planes, l_sid, r_sid,
        max_lookback=max_lookback, r_seq=r_seq,
    )
    per_col = jnp.where(found, vals, -1).astype(jnp.int32)
    return last_idx, per_col


def _ffill_scan_seg(f, has, val, axis: int = -1):
    """Segmented last-valid carry (Blelloch segmented-scan monoid):
    ``f`` flags segment heads; fills never cross a head."""

    def combine(a, b):
        fa, ha, va = a
        fb, hb, vb = b
        h = jnp.where(fb, hb, ha | hb)
        v = jnp.where(fb, vb, jnp.where(hb, vb, va))
        return fa | fb, h, v

    return jax.lax.associative_scan(combine, (f, has, val),
                                    axis=axis % has.ndim)


def asof_merge_indices(l_ts, r_ts, r_valids):
    """Index-returning sibling of :func:`asof_merge_values` (same
    skipNulls semantics): returns ``(last_row_idx [K, Ll],
    per_col_idx [C, K, Ll])``, -1 for no match.  On TPU this runs as
    the Pallas merge kernel with position-encoded payloads
    (ops/pallas_merge.py); the XLA form below merges with 3+C operands
    and forward-fills the row-index channel per column.  REQUIRES
    ``l_ts`` ascending per row (the packed-layout invariant)."""
    from tempo_tpu.ops import pallas_merge as pm

    if not _forced_bitonic() and pm.merge_indices_supported(
            l_ts, r_ts, r_valids):
        return pm.asof_merge_indices_pallas(l_ts, r_ts, r_valids)
    if _oversize_bitonic(l_ts, r_ts,
                         jnp.zeros((0,), jnp.float32), None, None):
        return pm.asof_merge_indices_bitonic(l_ts, r_ts, r_valids)
    return _asof_merge_indices_xla(l_ts, r_ts, r_valids)


@jax.jit
def _asof_merge_indices_xla(l_ts, r_ts, r_valids):
    C, K, Lr = r_valids.shape
    Ll = l_ts.shape[-1]
    Lc = Ll + Lr

    keys, is_left = _merge_sides(l_ts, r_ts, None, None)
    ridx = jnp.concatenate(
        [jnp.full((K, Ll), -1, jnp.int32),
         jnp.broadcast_to(jnp.arange(Lr, dtype=jnp.int32), (K, Lr))],
        axis=-1,
    )
    vplanes = jnp.concatenate(
        [jnp.zeros((C, K, Ll), jnp.bool_), r_valids], axis=-1
    )
    ops = tuple(keys) + (ridx,) + tuple(vplanes[c] for c in range(C))
    sorted_ops = jax.lax.sort(
        ops, dimension=-1, num_keys=len(keys), is_stable=True
    )
    nk = len(keys)
    is_right_s = sorted_ops[nk - 1] == 0
    ridx_s = sorted_ops[nk]
    vplanes_s = jnp.stack(sorted_ops[nk + 1:]) if C else \
        jnp.zeros((0, K, Lc), jnp.bool_)

    has = jnp.concatenate(
        [is_right_s[None] & vplanes_s,
         jnp.broadcast_to(is_right_s, (1, K, Lc))], axis=0
    )
    val = jnp.broadcast_to(ridx_s, (C + 1, K, Lc))
    has_f, val_f = _ffill_scan(has, jnp.where(has, val, 0))
    idx_sorted = jnp.where(has_f, val_f, -1)

    route = (1 - sorted_ops[nk - 1],) + tuple(idx_sorted[i]
                                              for i in range(C + 1))
    routed = jax.lax.sort(route, dimension=-1, num_keys=1, is_stable=True)
    per_col = jnp.stack([routed[1 + c][..., :Ll] for c in range(C)]) if C \
        else jnp.zeros((0, K, Ll), jnp.int32)
    last_idx = routed[1 + C][..., :Ll]
    return last_idx, per_col


def _nan_encoding_enabled() -> bool:
    from tempo_tpu import config

    return (config.get("TEMPO_TPU_NAN_ASOF") or "0") not in ("0", "false",
                                                             "no")


@jax.jit
def _asof_merge_nan_encoded(l_ts, r_ts, r_valids, r_values, l_seq=None,
                            r_seq=None):
    """skipNulls float fast path of :func:`asof_merge_values`: null and
    not-found states are NaN inside the value planes themselves, so the
    merge and routing sorts move C+1 payload operands instead of 2C+2.
    Requires valid slots to hold finite values (the packing invariant:
    NaN source values are null by definition)."""
    C = int(r_values.shape[0])
    K, Ll = l_ts.shape
    Lr = r_ts.shape[-1]
    vdt = r_values.dtype

    keys, is_left = _merge_sides(l_ts, r_ts, l_seq, r_seq)

    planes = jnp.concatenate(
        [jnp.full((C, K, Ll), jnp.nan, vdt),
         jnp.where(r_valids, r_values, jnp.nan)], axis=-1,
    )
    ridx_f = jnp.concatenate(
        [jnp.full((K, Ll), jnp.nan, vdt),
         jnp.broadcast_to(jnp.arange(Lr, dtype=vdt), (K, Lr))],
        axis=-1,
    )

    ops = tuple(keys) + tuple(planes[c] for c in range(C)) + (ridx_f,)
    sorted_ops = jax.lax.sort(
        ops, dimension=-1, num_keys=len(keys), is_stable=True
    )
    nk = len(keys)
    is_left_s = sorted_ops[nk - 1]
    payload = jnp.stack(sorted_ops[nk:])          # [C+1, K, Lc]

    has = ~jnp.isnan(payload)
    has_f, val_f = _ffill_scan(has, jnp.where(has, payload, 0.0))
    filled = jnp.where(has_f, val_f, jnp.nan)     # NaN == never found

    route = (1 - is_left_s,) + tuple(filled[i] for i in range(C + 1))
    routed = jax.lax.sort(route, dimension=-1, num_keys=1, is_stable=True)
    vals_l = jnp.stack([routed[1 + c][..., :Ll] for c in range(C)]) if C \
        else jnp.zeros((0, K, Ll), vdt)
    idx_f = routed[1 + C][..., :Ll]
    found_l = ~jnp.isnan(vals_l)
    idx_l = jnp.where(jnp.isnan(idx_f), -1, idx_f).astype(jnp.int32)
    return vals_l, found_l, idx_l


def _shift_back(x: jnp.ndarray, j: int, fill) -> jnp.ndarray:
    """out[..., i] = x[..., i - j] (j may be negative = look ahead)."""
    if j == 0:
        return x
    if j > 0:
        pad = jnp.full(x.shape[:-1] + (j,), fill, dtype=x.dtype)
        return jnp.concatenate([pad, x[..., :-j]], axis=-1)
    pad = jnp.full(x.shape[:-1] + (-j,), fill, dtype=x.dtype)
    return jnp.concatenate([x[..., -j:], pad], axis=-1)


def range_stats_shifted(
    secs: jnp.ndarray,       # [K, L] sorted window-order key (int)
    x: jnp.ndarray,          # [K, L] float values
    valid: jnp.ndarray,      # [K, L] bool
    window: jnp.ndarray,     # scalar window size in key units
    max_behind: int,         # static bound: rows any window reaches back
    max_ahead: int = 0,      # static bound: longest tie run ahead
    scale=None,              # optional scalar folded onto x in-kernel
) -> Dict[str, jnp.ndarray]:
    """Dispatcher: on TPU with int32 keys and f32 values the whole
    shifted-pass structure runs VMEM-resident as one Pallas kernel —
    the streamlined ops/pallas_window.py unrolled form by default
    (fewer rotate/mask ops per pass; TEMPO_TPU_WINDOW_ENGINE=legacy
    keeps the original ops/pallas_stats.py kernel) — an int32 ``secs``
    dtype is the caller's assertion that per-series key spans fit
    (rebase_seconds or equivalent); int64 keys keep the XLA form
    below.  ``scale``, when given, multiplies ``x`` inside the kernel
    (consumers fold the elementwise pre-pass they would otherwise
    re-stream the column for)."""
    from tempo_tpu.ops import pallas_stats as ps
    from tempo_tpu.ops import pallas_window as pw
    from tempo_tpu.ops.rolling import window_engine_override

    if secs.dtype == jnp.int32:
        if window_engine_override() != "legacy" and pw.unrolled_supported(
                x, max_behind, max_ahead):
            return pw.range_stats_unrolled(
                secs, x, valid, window, max_behind, max_ahead,
                scale=scale)
        if ps.range_stats_supported(secs, x, valid, max_behind,
                                    max_ahead):
            if scale is not None:
                x = x * jnp.asarray(scale, x.dtype)
            return ps.range_stats_pallas(secs, x, valid, window,
                                         max_behind, max_ahead)
    if scale is not None:
        x = x * jnp.asarray(scale, x.dtype)
    return _range_stats_shifted_xla(secs, x, valid, window,
                                    max_behind=max_behind,
                                    max_ahead=max_ahead)


def range_stats_shifted_packed(secs, xs, valids, window, max_behind,
                               max_ahead, scales=None):
    """Multi-column :func:`range_stats_shifted`: ``xs``/``valids`` are
    [C, K, L] stacks over one [K, L] key plane.  On TPU, packable
    groups run through the unrolled pallas_window kernel in single
    passes that read the key planes once
    (``pallas_window.range_stats_unrolled_packed``, group width from
    ``pack_cols_budget``); every other configuration (legacy kernel,
    XLA form, int64 keys) loops the single-column dispatcher, so the
    per-column results are bitwise-identical to unpacked calls either
    way.  Output planes are [C, K, L] ([C, K, 1] for ``clipped``)."""
    from tempo_tpu.ops import pallas_window as pw
    from tempo_tpu.ops.rolling import (packed_column_dispatch,
                                       window_engine_override)

    secs = jnp.asarray(secs)
    xs = jnp.asarray(xs)
    valids = jnp.asarray(valids)
    C, K, L = xs.shape

    def gate(c0):
        return (secs.dtype == jnp.int32
                and window_engine_override() != "legacy"
                and pw.unrolled_supported(xs[c0], max_behind,
                                          max_ahead))

    def packed_group(c0, scv):
        width = pw.pack_cols_budget(K, L, C - c0,
                                    max_behind=int(max_behind),
                                    max_ahead=int(max_ahead),
                                    unroll=True)
        return width, pw.range_stats_unrolled_packed(
            secs, xs[c0:c0 + width], valids[c0:c0 + width], window,
            max_behind, max_ahead,
            scales=None if scv is None else scv[c0:c0 + width])

    def single_col(c0, scale):
        return dict(range_stats_shifted(
            secs, xs[c0], valids[c0], window, max_behind, max_ahead,
            scale=scale))

    return packed_column_dispatch(C, scales, gate, packed_group,
                                  single_col)


@functools.partial(jax.jit, static_argnames=("max_behind", "max_ahead"))
def _range_stats_shifted_xla(
    secs: jnp.ndarray,
    x: jnp.ndarray,
    valid: jnp.ndarray,
    window: jnp.ndarray,
    max_behind: int,
    max_ahead: int = 0,
) -> Dict[str, jnp.ndarray]:
    """``withRangeStats`` for row-bounded windows, gather-free.

    Spark's rangeBetween(-window, 0) frame at row i contains exactly the
    rows j with ``secs[j] in [secs[i]-window, secs[i]]`` — preceding
    rows within the window plus following rows tied with secs[i]
    (tsdf.py:575-576 via the long cast).  When the caller can bound the
    frame extent in *rows* (``max_behind`` back, ``max_ahead`` ties
    ahead — compute both from the data as the frame layer does), the
    frame is a union of static shifts, and each aggregate is a masked
    accumulation over those shifts: O(W·KL) elementwise work, no
    searchsorted, no prefix-sum boundary gathers, no RMQ tables.  Sums
    accumulate mean-centred per series (f32-safe).

    Bounds too small TRUNCATE frames; the returned ``clipped`` entry
    ([K, 1] per-series count of rows whose true frame extends past
    ``max_behind``/``max_ahead``) audits exactly that — the same
    contract as the halo layer's clipped counts (parallel/halo.py).
    Callers derive bounds from real data and assert the audit is zero
    (the frame layer's deferred collect-time audit).
    """
    dt = x.dtype
    xz = jnp.where(valid, x, 0.0)
    n_valid = jnp.sum(valid, axis=-1, keepdims=True)
    center = jnp.sum(xz, axis=-1, keepdims=True) / jnp.maximum(n_valid, 1)
    xc = jnp.where(valid, x - center, 0.0).astype(dt)

    big = jnp.iinfo(secs.dtype).max
    lo = secs - window.astype(secs.dtype)
    pinf = jnp.array(jnp.inf, dt)

    cnt = jnp.zeros_like(x, dt)
    s1 = jnp.zeros_like(x, dt)
    s2 = jnp.zeros_like(x, dt)
    mn = jnp.full_like(x, pinf)
    mx = jnp.full_like(x, -pinf)
    for j in range(-max_ahead, max_behind + 1):
        sj = _shift_back(secs, j, big)
        inw = (sj >= lo) & (sj <= secs) & _shift_back(valid, j, False)
        xj = _shift_back(xc, j, jnp.array(0.0, dt))
        xr = _shift_back(x, j, jnp.array(0.0, dt))
        cnt = cnt + inw.astype(dt)
        s1 = s1 + jnp.where(inw, xj, 0.0)
        s2 = s2 + jnp.where(inw, xj * xj, 0.0)
        mn = jnp.minimum(mn, jnp.where(inw, xr, pinf))
        mx = jnp.maximum(mx, jnp.where(inw, xr, -pinf))

    mean = jnp.where(cnt > 0, s1 / jnp.maximum(cnt, 1) + center, jnp.nan)
    total = s1 + cnt * center
    var = jnp.where(
        cnt > 1,
        (s2 - s1 * s1 / jnp.maximum(cnt, 1)) / jnp.maximum(cnt - 1, 1),
        jnp.nan,
    )
    std = jnp.where(cnt > 1, jnp.sqrt(jnp.maximum(var, 0.0)), jnp.nan)
    zscore = (x - mean) / std

    # truncation audit: a row is clipped when the first row beyond
    # either static bound still falls inside its frame's key range and
    # either end of that extension is a valid row.  Requiring only the
    # *beyond* row valid would undercount when a null row sits exactly
    # at the boundary with valid rows behind it; requiring neither
    # would count all-pad tie runs (pads share one clamped key, so a
    # pad "extends ahead" into its neighbour pad).  Real-row false
    # positives from pads are impossible: pad keys sit >= window above
    # any real key (TS_PAD / INT32_MAX headroom), so real rows fail
    # ``sj >= lo`` against them and pads ahead fail ``sj <= secs``.
    # Shifts are clamped to the row length (a bound >= L has nothing
    # beyond it — shifting further is all-fill, and _shift_back cannot
    # represent |j| > L).
    L = secs.shape[-1]
    clipped = jnp.zeros_like(x, jnp.bool_)
    for j in (min(max_behind + 1, L), -min(max_ahead + 1, L)):
        sj = _shift_back(secs, j, big)
        clipped = clipped | (
            (sj >= lo) & (sj <= secs)
            & (valid | _shift_back(valid, j, False))
        )
    return {
        "mean": mean,
        "count": cnt,
        "min": jnp.where(cnt > 0, mn, jnp.nan),
        "max": jnp.where(cnt > 0, mx, jnp.nan),
        "sum": jnp.where(cnt > 0, total, jnp.nan),
        "stddev": std,
        "zscore": jnp.where(valid, zscore, jnp.nan),
        "clipped": jnp.sum(clipped, axis=-1, keepdims=True).astype(dt),
    }


def use_sort_kernels() -> bool:
    """Whether the sort-and-scan forms should replace search-and-gather
    on the current backend (TPU: yes — see module docstring timings;
    override with TEMPO_TPU_SORT_KERNELS=0/1)."""
    from tempo_tpu import config

    env = config.get("TEMPO_TPU_SORT_KERNELS")
    if env is not None:
        return env not in ("0", "false", "no")
    return jax.default_backend() == "tpu"
