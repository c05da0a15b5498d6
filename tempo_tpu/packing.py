"""Ragged->padded packing: the foundational layout transform of tempo-tpu.

The reference (dbl-tempo) represents a collection of time series as a lazy
Spark DataFrame partitioned by key columns (``Window.partitionBy`` /
``groupBy``); Spark's shuffle dynamically routes rows of one key to one
task (see /root/reference/python/tempo/tsdf.py:121,571).  XLA wants static
shapes, so tempo-tpu instead *packs* the ragged per-key row groups into
dense ``[num_series, padded_len]`` arrays with validity masks.  Every
kernel in ``tempo_tpu.ops`` consumes this layout and is ``vmap``-ed over
the leading (series) axis, which is also the axis we shard across a TPU
mesh (see ``tempo_tpu.parallel``).

Time is canonicalised to int64 nanoseconds (``ts_ns``); a float64 seconds
view is derived where the reference semantics are defined in seconds
(range windows cast timestamps to long seconds, tsdf.py:567; skew
bracketing casts to double seconds, tsdf.py:169-178).  We document the
divergence: int64 ns is exact where Spark's double cast is not.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

from tempo_tpu import native
from tempo_tpu.profiling import span

NS_PER_S = 1_000_000_000

# Sentinel used in padded slots of the time axis: larger than any real
# timestamp so sorted-order based kernels (searchsorted, merges) naturally
# ignore padding.  We keep headroom so small arithmetic offsets cannot
# overflow int64.
TS_PAD = np.int64(2**62)

# Any ts at or above this is a sentinel, not data (real ns timestamps
# stay far below 2^61 ≈ year 2043 in ns); window/halo kernels use it to
# tell real rows from padding with headroom on both sides.
TS_REAL_MAX = np.int64(2**61)

# Canonical name/order of the per-column aggregates withRangeStats
# emits (`<stat>_<col>`, Spark's six plus the derived zscore).  The
# stats kernels (ops/sortmerge, ops/pallas_window), the frame/mesh
# unpack loops, and the planner's schema inference + fused program
# (tempo_tpu/plan) must all agree on this tuple — define it once.
RANGE_STATS = ("mean", "count", "min", "max", "sum", "stddev", "zscore")


def compute_dtype() -> np.dtype:
    """Floating dtype for on-device metric math.

    TPU has no native f64 — emulation is ~25x slower than f32 (measured
    5.4s vs ms-scale for a 1M-row withRangeStats) — so the TPU backend
    computes in float32 (kernels mean-centre accumulations to keep f32
    benign) and frame-level outputs are cast back to float64 at the host
    boundary.  CPU (the golden-parity test platform) keeps full float64.
    Override with TEMPO_TPU_COMPUTE_DTYPE=float64|float32.
    """
    from tempo_tpu import config

    env = config.get("TEMPO_TPU_COMPUTE_DTYPE")
    if env:
        return np.dtype(env)
    import jax

    return np.dtype(np.float32 if jax.default_backend() == "tpu" else np.float64)


def rebase_seconds(ts_sec: np.ndarray, pad_mask: Optional[np.ndarray] = None):
    """Per-series rebase of a [K, L] seconds axis to small offsets.

    64-bit integer compares are also emulated on TPU, so range-window
    kernels take int32 seconds-from-series-start instead of absolute
    unix seconds when every span allows it.  Padded slots (``pad_mask``
    True) clamp to INT32_MAX so sorted-order kernels keep ignoring them.
    Returns (rebased int32 [K, L], ok) — ok False means some span
    overflows int32 and the caller must stay on int64.
    """
    if ts_sec.size == 0:
        return ts_sec.astype(np.int32), True
    with span("tempo.pack", rows=ts_sec.size):
        offsets = ts_sec - ts_sec[:, :1]
        if pad_mask is not None:
            offsets = np.where(pad_mask, 0, offsets)
        if offsets.max(initial=0) >= 2**31 - 2:
            return ts_sec.astype(np.int64), False
        out = offsets.astype(np.int32)
        if pad_mask is not None:
            out = np.where(pad_mask, np.int32(2**31 - 1), out)
        return out, True


def series_to_ns(values: "pd.Series | np.ndarray") -> np.ndarray:
    """Convert a timestamp-like column to canonical int64 nanoseconds.

    datetime64 -> ns since epoch; integers -> value interpreted as seconds
    (matching Spark's ``cast("double")`` of numeric ts cols, which yields
    the raw value in 'seconds' units for windowing math); floats -> seconds
    scaled to ns.
    """
    with span("tempo.keys", rows=len(values)):
        if isinstance(values, pd.Series) and isinstance(
            values.dtype, pd.DatetimeTZDtype
        ):
            # tz-aware columns canonicalise through UTC (Spark stores
            # session-local timestamps as UTC micros the same way)
            values = values.dt.tz_convert("UTC").dt.tz_localize(None)
        arr = (values.to_numpy() if isinstance(values, pd.Series)
               else np.asarray(values))
        if np.issubdtype(arr.dtype, np.datetime64):
            return arr.astype("datetime64[ns]").astype(np.int64)
        if np.issubdtype(arr.dtype, np.integer):
            return arr.astype(np.int64) * NS_PER_S
        if np.issubdtype(arr.dtype, np.floating):
            return np.round(arr * NS_PER_S).astype(np.int64)
        raise TypeError(f"Unsupported timestamp dtype: {arr.dtype}")


def ns_to_original(ns: np.ndarray, like_dtype):
    """Map canonical ns back to the dtype the user supplied."""
    if isinstance(like_dtype, pd.DatetimeTZDtype):
        utc = pd.Series(ns.astype("datetime64[ns]")).dt.tz_localize("UTC")
        return utc.dt.tz_convert(like_dtype.tz).to_numpy()
    if np.issubdtype(like_dtype, np.datetime64):
        return ns.astype("datetime64[ns]")
    if np.issubdtype(like_dtype, np.integer):
        return (ns // NS_PER_S).astype(like_dtype)
    if np.issubdtype(like_dtype, np.floating):
        return (ns / NS_PER_S).astype(like_dtype)
    raise TypeError(f"Unsupported timestamp dtype: {like_dtype}")


def encode_keys(
    df: pd.DataFrame, partition_cols: List[str]
) -> Tuple[np.ndarray, pd.DataFrame]:
    """Factorize the partition-key tuple into dense int32 series ids.

    Equivalent role to Spark's hash-shuffle routing on partition columns
    (tsdf.py:121): decides which logical series each row belongs to.
    Returns (key_ids [n_rows], key_frame [n_series x partition_cols]).
    Key order is order of first appearance (stable), so round-trips keep
    a deterministic layout.
    """
    with span("tempo.keys", rows=len(df)):
        return _factorize_keys(df, partition_cols)


def _factorize_keys(df: pd.DataFrame, partition_cols: List[str]):
    if not partition_cols:
        key_ids = np.zeros(len(df), dtype=np.int64)
        key_frame = pd.DataFrame(index=[0])
        return key_ids, key_frame
    if len(partition_cols) == 1:
        codes, uniques = pd.factorize(df[partition_cols[0]], use_na_sentinel=False)
        key_frame = pd.DataFrame({partition_cols[0]: uniques})
        return codes.astype(np.int64), key_frame
    # tuple-key factorization via a MultiIndex
    mi = pd.MultiIndex.from_frame(df[partition_cols])
    codes, uniques = pd.factorize(mi, use_na_sentinel=False)
    key_frame = pd.DataFrame(
        [list(t) for t in uniques], columns=partition_cols
    )
    return codes.astype(np.int64), key_frame


def encode_keys_joint(
    df_left: pd.DataFrame, df_right: pd.DataFrame, partition_cols: List[str]
) -> Tuple[np.ndarray, np.ndarray, pd.DataFrame]:
    """Factorize partition keys over the *union* of two frames so both
    sides share one series-id space - the packed analog of Spark
    co-partitioning both join inputs on the same keys (tsdf.py:121)."""
    nl = len(df_left)
    if not partition_cols:
        return (
            np.zeros(nl, dtype=np.int64),
            np.zeros(len(df_right), dtype=np.int64),
            pd.DataFrame(index=[0]),
        )
    with span("tempo.keys", rows=nl + len(df_right)):
        both = pd.concat(
            [df_left[partition_cols], df_right[partition_cols]],
            ignore_index=True)
        codes, key_frame = _factorize_keys(both, partition_cols)
    return codes[:nl], codes[nl:], key_frame


@dataclasses.dataclass
class FlatLayout:
    """Sorted flat (row-major) layout of a series collection.

    Rows are globally sorted by (key_id, ts_ns, seq) - the total order the
    reference only *promises* (tsdf.py:37-39 'ordering is promised, not
    enforced') but that every windowed op implicitly requires.  We enforce
    it once at ingest so kernels can assume sortedness.
    """

    key_ids: np.ndarray       # int64 [n]
    ts_ns: np.ndarray         # int64 [n]
    order: np.ndarray         # int64 [n]  (positions into the user's df)
    starts: np.ndarray        # int64 [K+1] row offsets per series
    key_frame: pd.DataFrame   # [K x partition_cols]

    @property
    def n_rows(self) -> int:
        return int(self.ts_ns.shape[0])

    @property
    def n_series(self) -> int:
        return int(self.starts.shape[0] - 1)

    @property
    def lengths(self) -> np.ndarray:
        return self.starts[1:] - self.starts[:-1]


def build_flat_layout(
    df: pd.DataFrame,
    ts_col: str,
    partition_cols: List[str],
    sequence_col: Optional[str] = None,
) -> FlatLayout:
    key_ids, key_frame = encode_keys(df, partition_cols)
    ts_ns = series_to_ns(df[ts_col])
    with span("tempo.layout", rows=len(key_ids)):
        # keep integer sequence columns exact: int64 ids above 2^53 must
        # not round through float64 before the tie-break sort
        seq = (pd.to_numeric(df[sequence_col]).to_numpy() if sequence_col
               else None)
        n_series = len(key_frame)
        order, starts = _sort_layout(key_ids, ts_ns, seq, n_series)
        return FlatLayout(
            key_ids=take(key_ids, order),
            ts_ns=take(ts_ns, order),
            order=order,
            starts=starts,
            key_frame=key_frame,
        )


def _sort_layout(
    key_ids: np.ndarray,
    ts_ns: np.ndarray,
    seq: Optional[np.ndarray],
    n_series: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """(order, starts) of the (key, ts, seq) total order; dispatches to
    the C++ engine (tempo_tpu/native) when built, numpy otherwise."""
    native_ok = native.available()
    if native_ok and seq is not None:
        dt = np.asarray(seq).dtype
        if np.issubdtype(dt, np.unsignedinteger):
            # uint64 ids above 2^63 would wrap negative through the C
            # ABI's int64; keep those on the exact numpy path
            native_ok = seq.size == 0 or int(seq.max()) <= np.iinfo(np.int64).max
    if native_ok:
        return native.sort_layout(key_ids, ts_ns, seq, n_series)
    if seq is not None:
        order = np.lexsort((seq, ts_ns, key_ids))
    else:
        order = np.lexsort((ts_ns, key_ids))
    counts = np.bincount(key_ids, minlength=n_series)
    starts = np.zeros(n_series + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    return order, starts


def take(values: np.ndarray, order: np.ndarray) -> np.ndarray:
    """``values[order]`` through the multithreaded native gather when the
    engine is built and the dtype has a fixed itemsize."""
    if values.dtype != object and native.available():
        return native.take(values, order)
    return values[order]


def build_layout_from_codes(
    key_ids: np.ndarray,
    ts_ns: np.ndarray,
    seq: Optional[np.ndarray],
    n_series: int,
) -> FlatLayout:
    """Like :func:`build_flat_layout` but with externally-assigned series
    ids (joint join encodings, skew bracket composition)."""
    with span("tempo.layout", rows=len(key_ids)):
        order, starts = _sort_layout(key_ids, ts_ns, seq, n_series)
        return FlatLayout(
            key_ids=take(key_ids, order),
            ts_ns=take(ts_ns, order),
            order=order,
            starts=starts,
            key_frame=pd.DataFrame(index=range(n_series)),
        )


def pad_length(max_len: int, multiple: int = 8) -> int:
    """Pad series length to a lane-friendly multiple (TPU sublane=8)."""
    if max_len <= 0:
        return multiple
    return int(-(-max_len // multiple) * multiple)


def pack_column(
    values: np.ndarray,
    layout: FlatLayout,
    padded_len: Optional[int] = None,
    fill=0,
) -> np.ndarray:
    """Scatter a flat (already key/ts-sorted) column into [K, L] dense form."""
    if padded_len is None:
        padded_len = pad_length(int(layout.lengths.max(initial=0)))
    with span("tempo.pack", rows=layout.n_rows):
        if values.dtype != object and native.available():
            return native.pack(values, layout.starts, int(padded_len), fill)
        out = np.full((layout.n_series, padded_len), fill, dtype=values.dtype)
        pos = (np.arange(layout.n_rows, dtype=np.int64)
               - layout.starts[layout.key_ids])
        out[layout.key_ids, pos] = values
        return out


def unpack_column(packed: np.ndarray, layout: FlatLayout) -> np.ndarray:
    """Gather [K, L] padded form back into the sorted flat layout."""
    with span("tempo.unpack", rows=layout.n_rows):
        if packed.dtype != object and native.available():
            return native.unpack(packed, layout.starts)
        pos = (np.arange(layout.n_rows, dtype=np.int64)
               - layout.starts[layout.key_ids])
        return packed[layout.key_ids, pos]


def row_mask(layout: FlatLayout, padded_len: int) -> np.ndarray:
    """Boolean [K, L] mask of real (non-padding) rows."""
    with span("tempo.pack", rows=layout.n_rows):
        return np.arange(padded_len)[None, :] < layout.lengths[:, None]


def _window_runs(layout: "FlatLayout", window_secs: float):
    """The rangeBetween(-window_secs, 0) windows of a layout, one per
    (series, second) run, or None when a series' seconds span plus the
    window would overflow int32 (see :func:`layout_rowbounds`).

    Rows of one series that share a second share a window, so the
    windows are found per run: one ``searchsorted`` over the runs, not
    one per row.  Returns ``(first, lo, end)``: each run's first row,
    the first row of its window and one past its window's last row (the
    run's own end: ties to the second are in), all global row indices.
    Cached per (layout, window): chained frames sharing a layout reuse
    them."""
    cache = layout.__dict__.setdefault("_window_run_cache", {})
    key = float(window_secs)
    if key not in cache:
        with span("tempo.pack", rows=layout.n_rows):
            cache[key] = _find_window_runs(layout, np.int64(window_secs))
    return cache[key]


def _find_window_runs(layout: "FlatLayout", w: np.int64):
    n = layout.n_rows
    if n == 0:
        empty = np.zeros(0, np.int64)
        return empty, empty, empty
    secs = layout.ts_ns // NS_PER_S
    kid = layout.key_ids
    new = np.empty(n, dtype=bool)
    new[0] = True
    np.not_equal(secs[1:], secs[:-1], out=new[1:])
    new[1:] |= kid[1:] != kid[:-1]
    first = np.flatnonzero(new)
    end = np.append(first[1:], n)
    run_key = kid[first]
    # seconds from the series' first second: the window test compares
    # within a series only
    rel = secs[first] - secs[layout.starts[run_key]]
    if int(rel.max()) + int(w) >= 2**31 - 2:
        return None
    # (series, second) as one ascending int64 key; a window reaching
    # before its series' first second clamps to it
    stride = np.int64(int(rel.max()) + max(int(w), 0) + 1)
    run_pos = run_key * stride + rel
    lo = np.searchsorted(run_pos,
                         run_key * stride + np.maximum(rel - w, 0),
                         side="left")
    # a negative width gives an empty window (Spark's rangeBetween with
    # its start after its end)
    lo_row = np.minimum(first[np.minimum(lo, len(first) - 1)], end)
    lo_row = np.where(lo < len(first), lo_row, end)
    return first, lo_row, end


def layout_window_bounds(layout: "FlatLayout", window_secs: float):
    """Each row's rangeBetween(-window_secs, 0) window as ``(start,
    end)`` int64 positions within its series (``start`` inclusive,
    ``end`` exclusive), or None where :func:`layout_rowbounds` is."""
    runs = _window_runs(layout, window_secs)
    if runs is None:
        return None
    first, lo, end = runs
    with span("tempo.pack", rows=layout.n_rows):
        lens = np.diff(np.append(first, layout.n_rows))
        base = layout.starts[layout.key_ids]
        return (np.repeat(lo, lens) - base, np.repeat(end, lens) - base)


def layout_rowbounds(layout: "FlatLayout", window_secs: float):
    """Static (max rows back, max tie rows ahead) any
    rangeBetween(-window_secs, 0) frame spans over this layout, or
    None when a per-series seconds span + window would overflow the
    int32 rebased keys the shifted/VMEM kernels compare (the pads
    clamp to INT32_MAX and the truncation audit's pad-immunity needs
    >= window of headroom above every real key).  Cached per (layout,
    window) — chained frames sharing a layout reuse the bounds.
    Shared by the host frame auto-pick (rolling.with_range_stats) and
    the mesh path (dist._window_rowbounds)."""
    runs = _window_runs(layout, window_secs)
    if runs is None:
        return None
    first, lo, end = runs
    if len(first) == 0:
        return 0, 0
    # a run's farthest reach back is from its last row, its farthest
    # tie ahead from its first
    behind = max(0, int((end - 1 - lo).max()))
    ahead = max(0, int((end - 1 - first).max()))
    return behind, ahead


SID_PAD = np.int32(2**31 - 1)


@dataclasses.dataclass
class BinPackLayout:
    """Assignment of series to shared lane rows (bin packing).

    The [K, max_len] one-series-per-row layout wastes its lanes on
    Zipf-skewed key distributions (a real NBBO day is ~96% padding —
    round-2 verdict); the reference handles the same skew by dynamic
    Spark partitioning + tsPartitionVal brackets (tsdf.py:164-190).
    Here short series share lane rows back-to-back: ``row[s]`` is the
    lane row of series ``s`` and ``l_off[s]``/``r_off[s]`` its starting
    lane on the left/right side.  Within a row, series sit in ascending
    series-id order and pads only at the tail (sid = SID_PAD), the
    layout the segmented merge kernels require
    (ops/pallas_merge.py, ops/sortmerge.py:asof_merge_values_binpacked).
    """

    row: np.ndarray     # [S] int32 lane row per series
    l_off: np.ndarray   # [S] int32 starting lane, left side
    r_off: np.ndarray   # [S] int32 starting lane, right side
    n_rows: int
    l_width: int
    r_width: int

    def occupancy(self, l_lengths, r_lengths) -> float:
        return float(
            (np.sum(l_lengths) + np.sum(r_lengths))
            / (self.n_rows * (self.l_width + self.r_width))
        )


def bin_pack_series(
    l_lengths: np.ndarray,
    r_lengths: np.ndarray,
    l_width: int,
    r_width: int,
) -> BinPackLayout:
    """First-fit-decreasing packing of series into lane rows with two
    capacities (left and right side must both fit).  Series keep
    ascending id order *within* each row by a final per-row reorder.
    """
    l_lengths = np.asarray(l_lengths, np.int64)
    r_lengths = np.asarray(r_lengths, np.int64)
    S = len(l_lengths)
    if np.any(l_lengths > l_width) or np.any(r_lengths > r_width):
        raise ValueError("a series exceeds the lane-row width")
    sev = np.maximum(
        l_lengths / max(l_width, 1), r_lengths / max(r_width, 1)
    )
    order = np.argsort(-sev, kind="stable")
    l_rem: list = []
    r_rem: list = []
    row = np.zeros(S, np.int32)
    for s in order:
        placed = False
        for b in range(len(l_rem)):
            if l_rem[b] >= l_lengths[s] and r_rem[b] >= r_lengths[s]:
                row[s] = b
                l_rem[b] -= l_lengths[s]
                r_rem[b] -= r_lengths[s]
                placed = True
                break
        if not placed:
            row[s] = len(l_rem)
            l_rem.append(l_width - int(l_lengths[s]))
            r_rem.append(r_width - int(r_lengths[s]))
    # lay series out in ascending id order within each row (the
    # non-decreasing-sid contract of the segmented kernels)
    l_off = np.zeros(S, np.int32)
    r_off = np.zeros(S, np.int32)
    l_cur = np.zeros(len(l_rem), np.int64)
    r_cur = np.zeros(len(l_rem), np.int64)
    for s in range(S):
        b = row[s]
        l_off[s] = l_cur[b]
        r_off[s] = r_cur[b]
        l_cur[b] += l_lengths[s]
        r_cur[b] += r_lengths[s]
    return BinPackLayout(row=row, l_off=l_off, r_off=r_off,
                         n_rows=len(l_rem), l_width=int(l_width),
                         r_width=int(r_width))


def binpack_rows(
    src: np.ndarray,
    lengths: np.ndarray,
    row: np.ndarray,
    off: np.ndarray,
    n_rows: int,
    width: int,
    fill,
    dtype=None,
) -> np.ndarray:
    """Scatter per-series leading segments of ``src [S, Lsrc]`` into the
    bin-packed [n_rows, width] grid."""
    out = np.full((n_rows, width), fill, dtype=dtype or src.dtype)
    for s in range(len(lengths)):
        n = int(lengths[s])
        out[row[s], off[s]: off[s] + n] = src[s, :n]
    return out


def binpack_dest(starts: np.ndarray, row: np.ndarray, off: np.ndarray,
                 width: int) -> np.ndarray:
    """Flat destination slot of every row of a flat per-series-sorted
    column in the bin-packed [n_rows, width] grid — computed once and
    reused for every plane (one vectorised scatter per plane instead of
    a Python per-series loop).  The dense [K, width] layout is the
    packing with series ``s`` alone in row ``s`` from lane 0."""
    base = np.asarray(row, np.int64) * width + off - starts[:-1]
    return (np.arange(int(starts[-1]), dtype=np.int64)
            + np.repeat(base, np.diff(starts)))


def binpack_scatter(flat: np.ndarray, dest: np.ndarray, n_rows: int,
                    width: int, fill, dtype=None) -> np.ndarray:
    """One fancy-index scatter of a flat column into the bin-packed
    grid (``dest`` from :func:`binpack_dest`)."""
    out = np.full(n_rows * width, fill, dtype=dtype or flat.dtype)
    out[dest] = flat
    return out.reshape(n_rows, width)


def binpack_rows_flat(
    flat: np.ndarray,
    starts: np.ndarray,
    row: np.ndarray,
    off: np.ndarray,
    n_rows: int,
    width: int,
    fill,
    dtype=None,
) -> np.ndarray:
    """Scatter a flat per-series-sorted column (``starts`` offsets, the
    FlatLayout form) into the bin-packed [n_rows, width] grid."""
    dest = binpack_dest(starts, row, off, width)
    return binpack_scatter(flat, dest, n_rows, width, fill, dtype)


def binpack_sid(
    lengths: np.ndarray, row: np.ndarray, off: np.ndarray,
    n_rows: int, width: int,
) -> np.ndarray:
    """The series-id plane of a bin-packed grid (SID_PAD at pad slots)."""
    out = np.full((n_rows, width), SID_PAD, np.int32)
    for s in range(len(lengths)):
        n = int(lengths[s])
        out[row[s], off[s]: off[s] + n] = s
    return out


# ----------------------------------------------------------------------
# Lane-chunked AS-OF layout (the streaming merge kernel's host planner)
# ----------------------------------------------------------------------

@dataclasses.dataclass
class AsofChunkPlan:
    """Merge-path split of packed AS-OF sides into VMEM-sized chunks.

    The streaming merge kernel (ops/pallas_merge.py chunked form) grids
    over the merged-lane axis: chunk ``c`` of a lane row holds merged
    rows [c*S, (c+1)*S) of that row's (sid?, ts, seq?, side) total
    order.  Both sides arrive sorted, so each chunk holds one contiguous
    run of each side, and the plan is just the run bounds:
    ``l_bounds[k, c]`` left rows of row ``k`` lie among its first
    ``c*S`` merged rows, ``r_bounds[k, c] = min(c*S, n_k) - l_bounds``
    right rows (both ``[K, n_chunks + 1]``, the last column the row's
    counts).  The chunk-major ``[K, n_chunks * Cm]`` layout, ``Cm = 2 *
    S`` lanes per chunk, is ``[left run (ascending) | pad | right run
    (reversed)]`` — a bitonic sequence per chunk, like the single-plan
    layout per full row — built by slice copies
    (:func:`chunk_layout_plane`).  Greedy packing guarantees every chunk
    before a non-empty one is full, so a real slot's global merged
    position is ``c * S + lane`` (what the maxLookback horizon counts).

    ``chunk_pad_sid`` is the per-(row, chunk) series id given to pad
    lanes so the segmented fill flows into the chunk tail and the
    cross-chunk carry can be read at the last lane (SID_PAD when the
    chunk is empty).  ``r_pos``, each right row's global merged
    position (the psrc planes of the maxLookback form), is derived on
    first read."""

    n_chunks: int
    chunk_rows: int                 # S = real merged rows per full chunk
    merged_lanes: int               # Cm = 2 * S (power of two)
    l_bounds: np.ndarray            # [K, n_chunks + 1] int64
    r_bounds: np.ndarray            # [K, n_chunks + 1] int64
    chunk_pad_sid: Optional[np.ndarray]   # [K, n_chunks] int32 or None
    # flat composite merge keys (sid?, ts, seq?), major first, per side
    merge_keys: Tuple[list, list] = dataclasses.field(repr=False)
    l_width: int                    # Ll
    r_width: int                    # Lr

    @functools.cached_property
    def r_pos(self) -> np.ndarray:           # [K, Lr] int64, -1 pads
        """Right row ``b`` of chunk ``c`` follows exactly the left rows
        ordered before it, and those are bounded by the chunk's left
        run: one bisection inside that run, per right row."""
        c = _lane_chunks(self.r_bounds, self.r_width)
        K, Lr = c.shape
        k, b = np.nonzero(c >= 0)
        cc = c[k, b]
        l_keys, r_keys = self.merge_keys
        rf = k * Lr + b
        before = _bisect(
            self.l_bounds[k, cc], self.l_bounds[k, cc + 1],
            lambda sel, i: _left_first(l_keys, r_keys,
                                       k[sel] * self.l_width + i, rf[sel]))
        out = np.full((K, Lr), -1, np.int64)
        out[k, b] = b + before
        return out


def _bisect(lo, hi, first) -> np.ndarray:
    """Vectorised bisection: per element ``e``, the least ``i`` in
    ``[lo[e], hi[e])`` where the monotone predicate ``first(sel, i)``
    (True…True False…False along ``i``, evaluated for the elements
    ``sel``) is False, ``hi[e]`` where it never is."""
    lo = np.array(lo, np.int64)
    hi = np.array(hi, np.int64)
    act = np.flatnonzero(lo < hi)
    while act.size:
        mid = (lo[act] + hi[act]) >> 1
        t = first(act, mid)
        lo[act[t]] = mid[t] + 1
        hi[act[~t]] = mid[~t]
        act = act[lo[act] < hi[act]]
    return lo


def _left_first(l_keys, r_keys, lf, rf) -> np.ndarray:
    """Whether left row ``lf`` precedes right row ``rf`` (flat lanes) in
    the merged order: its composite key is strictly smaller — a full
    tie puts the right row first."""
    lt = np.zeros(len(lf), bool)
    eq = np.ones(len(lf), bool)
    for lk, rk in zip(l_keys, r_keys):
        a, b = lk[lf], rk[rf]
        lt |= eq & (a < b)
        eq &= a == b
    return lt


def _per_lane(bounds: np.ndarray, width: int, per_chunk, tail) -> np.ndarray:
    """A per-(row, chunk) value spread over every lane of that chunk's
    run of a packed ``[K, width]`` side (``tail`` past the side's real
    rows): one repeat, flat ``[K * width]``."""
    K = bounds.shape[0]
    counts = np.concatenate(
        [np.diff(bounds, axis=1), width - bounds[:, -1:]], axis=1)
    vals = np.concatenate(
        [np.broadcast_to(per_chunk, (K, bounds.shape[1] - 1)),
         np.full((K, 1), tail, np.int64)], axis=1)
    return np.repeat(vals.ravel(), counts.ravel())


def _lane_chunks(bounds: np.ndarray, width: int) -> np.ndarray:
    """Chunk index of every lane of a packed ``[K, width]`` side, -1
    past the side's real rows."""
    K, n1 = bounds.shape
    c = _per_lane(bounds, width, np.arange(n1 - 1, dtype=np.int64), -1)
    return c.reshape(K, width)


def _run_last(side: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    """Per (row, chunk): the side's value at the run's last row, -1
    where the chunk holds none of the side."""
    end = bounds[:, 1:]
    has = end > bounds[:, :-1]
    if not has.any():
        return np.full(end.shape, -1, np.int64)
    last = np.take_along_axis(side, np.where(has, end - 1, 0), axis=1)
    return np.where(has, last.astype(np.int64), -1)


def _seq_merge_sides_np(l_seq, r_seq, K, Ll, Lr):
    """Numpy mirror of the kernels' ``_seq_sides`` synthesis: the None
    side rides the promoted dtype's minimum (above the -inf null-seq
    encoding, below any real value — Spark ASC NULLS FIRST + rec_ind)."""
    sdt = (l_seq if l_seq is not None else r_seq).dtype
    neg = (np.finfo(sdt).min if np.issubdtype(sdt, np.floating)
           else np.iinfo(sdt).min)
    ls = l_seq if l_seq is not None else np.full((K, Ll), neg, sdt)
    rs = r_seq if r_seq is not None else np.full((K, Lr), neg, sdt)
    pdt = np.promote_types(ls.dtype, rs.dtype)
    return ls.astype(pdt, copy=False), rs.astype(pdt, copy=False)


def asof_chunk_plan(
    l_ts: np.ndarray,               # [K, Ll] int64 ns, TS_PAD padded
    r_ts: np.ndarray,               # [K, Lr] int64 ns
    merged_lanes: int,              # Cm (power of two); S = Cm // 2
    l_sid: Optional[np.ndarray] = None,
    r_sid: Optional[np.ndarray] = None,
    l_seq: Optional[np.ndarray] = None,
    r_seq: Optional[np.ndarray] = None,
) -> AsofChunkPlan:
    """Split packed AS-OF sides along each row's merged stream.

    REQUIRES the packed-layout invariant (real rows lead, ascending in
    (sid?, ts, seq); TS_PAD tails).  The merged order — lexicographic
    (sid?, ts, seq, side) with right rows before left on full ties,
    stable within a side — must match the kernels' key-plane order
    exactly or chunk boundaries would disagree with the fill.  No row
    is sorted or searched: each side's real-row count and each chunk
    boundary's left count is one merge-path bisection, vectorised over
    every (row, boundary)."""
    l_ts = np.asarray(l_ts)
    r_ts = np.asarray(r_ts)
    K, Ll = l_ts.shape
    Lr = r_ts.shape[1]
    Cm = int(merged_lanes)
    if Cm < 2 or Cm & (Cm - 1):
        raise ValueError(f"merged_lanes must be a power of two, got {Cm}")
    S = Cm // 2
    segmented = l_sid is not None
    if l_seq is not None or r_seq is not None:
        l_seq, r_seq = _seq_merge_sides_np(
            np.asarray(l_seq) if l_seq is not None else None,
            np.asarray(r_seq) if r_seq is not None else None, K, Ll, Lr)

    flat = lambda a: np.ascontiguousarray(a).reshape(-1)
    l_flat, r_flat = flat(l_ts), flat(r_ts)
    l_keys, r_keys = [l_flat], [r_flat]
    if segmented:
        l_keys.insert(0, flat(l_sid))
        r_keys.insert(0, flat(r_sid))
    if l_seq is not None:
        l_keys.append(flat(l_seq))
        r_keys.append(flat(r_seq))

    # real rows lead each side: the count is where ts reaches the pads
    rows = np.arange(K, dtype=np.int64)
    zero = np.zeros(K, np.int64)
    l_counts = _bisect(zero, np.full(K, Ll, np.int64),
                       lambda sel, i: l_flat[sel * Ll + i] < TS_REAL_MAX)
    r_counts = _bisect(zero, np.full(K, Lr, np.int64),
                       lambda sel, i: r_flat[sel * Lr + i] < TS_REAL_MAX)
    n = l_counts + r_counts
    n_chunks = max(int(-(-int(n.max(initial=0)) // S)), 1)

    # merge path: the first p merged rows of row k hold i left rows,
    # i the least in [p - nr, min(p, nl)] whose left row does not
    # precede right row p - 1 - i
    p = np.minimum(np.arange(n_chunks + 1, dtype=np.int64)[None, :] * S,
                   n[:, None])
    k = np.broadcast_to(rows[:, None], p.shape).ravel()
    pf = p.ravel()
    l_bounds = _bisect(
        np.maximum(pf - r_counts[k], 0), np.minimum(pf, l_counts[k]),
        lambda sel, i: _left_first(l_keys, r_keys, k[sel] * Ll + i,
                                   k[sel] * Lr + pf[sel] - 1 - i),
    ).reshape(p.shape)
    r_bounds = p - l_bounds

    pad_sid = None
    if segmented:
        # sid ascends along a packed row: a chunk's largest series id
        # is at the last row of one of its two runs
        pad_sid = np.maximum(_run_last(np.asarray(l_sid), l_bounds),
                             _run_last(np.asarray(r_sid), r_bounds))
        pad_sid = np.where(pad_sid < 0, np.int64(SID_PAD),
                           pad_sid).astype(np.int32)
    return AsofChunkPlan(
        n_chunks=n_chunks, chunk_rows=S, merged_lanes=Cm,
        l_bounds=l_bounds, r_bounds=r_bounds, chunk_pad_sid=pad_sid,
        merge_keys=(l_keys, r_keys), l_width=Ll, r_width=Lr,
    )


def chunk_layout_plane(plan: AsofChunkPlan, left, right, fill,
                       dtype) -> np.ndarray:
    """One ``[K, n_chunks * Cm]`` plane of the chunk layout, written
    chunk by chunk with slice copies: the left run of ``left [K, Ll]``
    ascending at the chunk's head, the right run of ``right [K, Lr]``
    reversed at its tail, ``fill`` between — a scalar, or a ``[K,
    n_chunks]`` array of one value per chunk.  ``left=None`` leaves the
    head to ``fill`` as well (the payload planes, which only the right
    side feeds).  Sources are cast on assignment, never copied whole."""
    K = plan.l_bounds.shape[0]
    nc, Cm = plan.n_chunks, plan.merged_lanes
    out = np.empty((K, nc, Cm), dtype)
    per_chunk = np.ndim(fill) == 2
    l_b, r_b = plan.l_bounds.tolist(), plan.r_bounds.tolist()
    for k in range(K):
        lk, rk, ok = l_b[k], r_b[k], out[k]
        fk = fill[k] if per_chunk else None
        for c in range(nc):
            a0, a1 = (lk[c], lk[c + 1]) if left is not None else (0, 0)
            b0, b1 = rk[c], rk[c + 1]
            if lk[c] == lk[nc] and b0 == rk[nc]:   # every row placed
                ok[c:] = fk[c:, None] if per_chunk else fill
                break
            ch = ok[c]
            tail = Cm - (b1 - b0)
            if a1 > a0:
                ch[:a1 - a0] = left[k, a0:a1]
            ch[a1 - a0:tail] = fk[c] if per_chunk else fill
            if b1 > b0:
                ch[tail:] = right[k, b0:b1][::-1]
    return out.reshape(K, nc * Cm)


def chunk_take_index(plan: AsofChunkPlan, l_lane: np.ndarray) -> np.ndarray:
    """Flat position, in the kernel's [K, n_chunks * S] outputs, of every
    left row whose flat lane in the packed [K, Ll] left side is
    ``l_lane`` (from :func:`binpack_dest`; real lanes only): built once
    per join, so each output channel reaches left-row order in one
    ``np.take``.  Left row ``a`` of chunk ``c`` of row ``k`` sits at
    ``k*nc*S + c*S + a - l_bounds[k, c]``, an offset constant over the
    chunk's run: one repeat of the per-chunk offsets, one take."""
    K, n1 = plan.l_bounds.shape
    Ll, S = plan.l_width, plan.chunk_rows
    shift = (np.arange(K, dtype=np.int64)[:, None] * ((n1 - 1) * S - Ll)
             + np.arange(n1 - 1, dtype=np.int64) * S - plan.l_bounds[:, :-1])
    per_lane = _per_lane(plan.l_bounds, Ll, shift, 0)
    take = np.take(per_lane, l_lane)
    take += l_lane
    return take


def unpack_ragged(
    packed: np.ndarray, lengths: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten a [K, L] array with per-series valid ``lengths`` into a flat
    array plus the key_id of each row.  Used to materialise op outputs whose
    per-series row counts differ from the input (resample, interpolate)."""
    K, L = packed.shape[0], packed.shape[1]
    mask = np.arange(L)[None, :] < lengths[:, None]
    key_ids = np.repeat(np.arange(K, dtype=np.int64), lengths.astype(np.int64))
    return packed[mask], key_ids
