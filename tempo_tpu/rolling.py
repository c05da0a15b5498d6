"""Frame-level rolling & grouped statistics, EMA, VWAP, lookback features.

Reference surface reproduced here:
* ``withRangeStats``  - tsdf.py:673-721
* ``withGroupedStats`` - tsdf.py:723-759
* ``EMA``             - tsdf.py:615-635 (plus an exact scan-based mode)
* ``vwap``            - scala TSDF.scala:378-401 (the Scala version is
  the working spec; the Python one calls builtin ``sum``/``max`` on
  Columns - tsdf.py:608-610 - and cannot run)
* ``withLookbackFeatures`` - tsdf.py:637-671 (incl. the exactSize=True
  bare-DataFrame quirk)
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np
import pandas as pd

from tempo_tpu import packing
from tempo_tpu.freq import freq_to_seconds, UNIT_SECONDS
from tempo_tpu.ops import rolling as rk
from tempo_tpu.profiling import span

import jax
import jax.numpy as jnp


def _packed_metric_stack(tsdf, cols: List[str]):
    """Stack metric columns into [C, K, L] values + valids."""
    with span("tempo.pack", rows=tsdf.layout.n_rows * len(cols)):
        vals, valids = [], []
        for c in cols:
            v, m = tsdf.packed_numeric(c)
            vals.append(v)
            valids.append(m)
        return np.stack(vals), np.stack(valids)


def plan_range_engine(tsdf, cols: List[str], rangeBackWindowSecs: int):
    """``(engine, rowbounds, ts_long, w)`` the host ``withRangeStats``
    three-way pick will choose for this frame/window — ONE function so
    the eager path below and the lazy planner's plan-time hoist
    (tempo_tpu/plan/optimizer.py) can never diverge.  ``rowbounds`` is
    None when the static-shift forms cannot vouch for the frame (spans
    past int32, no sort kernels) and the prefix+RMQ windowed form must
    run.  ``ts_long``/``w`` (the rebased per-series seconds and the
    clamped window) ride along so the eager caller does not redo the
    O(K*L) packing work the pick already paid for."""
    from tempo_tpu.ops import pallas_stats as _ps
    from tempo_tpu.ops import pallas_window as _pw
    from tempo_tpu.ops import sortmerge as sm

    layout = tsdf.layout
    if layout.n_rows == 0 or not cols:
        return "windowed", None, None, None
    # Spark cast-to-long seconds; 64-bit compares are emulated on TPU,
    # so rebase to per-series int32 seconds when spans allow (range
    # windows only ever compare within a series, so a per-series
    # origin is safe)
    with span("tempo.pack", rows=layout.n_rows):
        ts_long = tsdf.packed_ts() // packing.NS_PER_S
        ts_long, _ = packing.rebase_seconds(ts_long, ~tsdf.packed_mask())
        # a window larger than any rebased span is equivalent to
        # 'unbounded preceding'; clamp so huge windows cannot overflow
        # the int32 path
        w = min(int(rangeBackWindowSecs),
                int(np.iinfo(ts_long.dtype).max) // 2)
        rb = (packing.layout_rowbounds(layout, w)
              if ts_long.dtype == np.int32 and sm.use_sort_kernels()
              else None)
    K, L = ts_long.shape
    f32 = np.dtype(packing.compute_dtype()) == np.float32
    # feasibility and the HBM budget are per COLUMN since the packed
    # rewire: the pallas engines block [C<=pack, bk, L] (columns
    # sequenced inside the kernel, pack width folded separately by
    # pack_cols_budget) and the XLA fallbacks loop single [K, L]
    # columns — the old C*K flattened gate modeled the tiled layout
    # that no longer runs.  This matches the mesh path's per-column
    # pick (dist._pick_range_engine_for_shard).
    pallas_ok = f32 and _ps.pallas_block_feasible(K, L)
    stream_ok = f32 and _pw.stream_block_feasible(K, L)
    # the lane-chunked form takes series longer than one chunk's core
    chunked_ok = False
    if rb is not None:
        block, halo, _ = rk.range_chunk_plan(*rb)
        chunked_ok = int(layout.lengths.max()) > block - halo
    engine = ("windowed" if rb is None else rk.pick_range_engine(
        K * L, rb[0], rb[1], pallas_ok, stream_ok, chunked_ok))
    return engine, rb, ts_long, w


def with_range_stats(tsdf, type: str = "range", colsToSummarize=None,
                     rangeBackWindowSecs: int = 1000):
    from tempo_tpu.frame import TSDF

    cols = colsToSummarize or tsdf.summarizable_columns()
    layout = tsdf.layout
    with span("tempo.frame", rows=layout.n_rows):
        out = tsdf.df.iloc[layout.order].reset_index(drop=True)
    if not cols:
        # reference adds zero stat columns in this case (tsdf.py:691-721)
        return TSDF(out, tsdf.ts_col, tsdf.partitionCols, tsdf.sequence_col or None)
    if layout.n_rows == 0:
        # empty frame: emit the stat schema (Spark yields the columns
        # with zero rows) without dispatching zero-size reductions
        for c in cols:
            for stat in packing.RANGE_STATS:
                out[f"{stat}_{c}"] = np.zeros(
                    0, dtype=np.int64 if stat == "count" else np.float64
                )
        return TSDF(out, tsdf.ts_col, tsdf.partitionCols, tsdf.sequence_col or None)

    # the engine pick (ops/rolling.py lists the engines and what picks
    # each): row-boundable frames take the static-shift form, wider
    # ones the streaming VMEM sweep, series longer than one chunk block
    # the lane-chunked form; the prefix-scan + RMQ form covers whatever
    # remains.  Same picker as the mesh path (dist.withRangeStats);
    # under the lazy planner the decision is hoisted to plan time and
    # arrives here as a hint (plan_range_engine +
    # ops/rolling.pick_range_engine)
    engine, rb, ts_long, w = plan_range_engine(tsdf, cols,
                                               rangeBackWindowSecs)
    if engine == "chunked":
        flat = _range_stats_chunked(tsdf, cols, rb, w)
    else:
        flat = _range_stats_whole(tsdf, cols, engine, rb, ts_long, w)
    with span("tempo.frame", rows=layout.n_rows):
        for name, col in flat.items():
            out[name] = col
        return TSDF(out, tsdf.ts_col, tsdf.partitionCols,
                    tsdf.sequence_col or None)


def _stat_columns(cols, stats):
    """Output columns from ``stats[stat][ci]`` flat rows: Spark emits
    DoubleType stats regardless of input width, counts as long."""
    return {f"{stat}_{c}": stats[stat][ci].astype(
                np.int64 if stat == "count" else np.float64)
            for ci, c in enumerate(cols) for stat in packing.RANGE_STATS}


def _range_stats_whole(tsdf, cols, engine, rb, ts_long, w):
    """Range stats by an engine that holds whole series: [C, K, L]
    planes up, one stacked fetch, then unpacked to flat rows."""
    layout = tsdf.layout
    vals, valids = _packed_metric_stack(tsdf, cols)
    C, K, L = vals.shape
    with span("tempo.dispatch") as fetched:
        # the lanes the engine computes (the nested span's rows)
        with span("tempo.dispatch", rows=C * K * L):
            stats = _whole_engine_call(engine, tsdf, vals, valids, rb,
                                       ts_long, w)
        # one stacked device->host transfer instead of one per stat: each
        # transfer pays a fixed latency.  The shifted path's
        # truncation-audit scalar piggybacks as one extra element on the
        # same flattened buffer.
        clip = stats.pop("clipped", None)
        names = sorted(stats)
        planes = jnp.stack([stats[k] for k in names]).reshape(-1)
        if clip is not None:
            planes = jnp.concatenate(
                [planes, jnp.sum(clip).reshape(1).astype(planes.dtype)]
            )
        buf = np.asarray(planes)
        fetched.rows = buf.size
    if clip is not None:
        clipped_total = float(buf[-1])
        buf = buf[:-1]
        if clipped_total:  # pragma: no cover - bound-derivation bug guard
            raise AssertionError(
                f"withRangeStats: {clipped_total} rows exceeded the "
                f"derived row bounds {rb}; this is a tempo-tpu bug"
            )
    # packed engines yield [C, K, L] planes, the windowed fallback
    # [C*K, L] — the element order is identical either way
    stacked = buf.reshape(len(names), C, K, L)
    with span("tempo.unpack", rows=layout.n_rows * len(cols)):
        stats = {k: [packing.unpack_column(stacked[i][ci], layout)
                     for ci in range(C)] for i, k in enumerate(names)}
        return _stat_columns(cols, stats)


def _whole_engine_call(engine, tsdf, vals, valids, rb, ts_long, w):
    """The stats planes of one whole-series engine, on the device."""
    from tempo_tpu.ops import sortmerge as sm

    if engine == "shifted":
        # multi-column payload packing: the [C, K, L] metric stack
        # shares ONE [K, L] key plane — the packed kernels read it once
        # per pack where the seed path materialised a C-wide broadcast
        # copy of the timestamps (`tile`) and streamed it per column
        return dict(sm.range_stats_shifted_packed(
            jnp.asarray(ts_long), jnp.asarray(vals), jnp.asarray(valids),
            jnp.asarray(np.int32(w)),
            max_behind=int(rb[0]), max_ahead=int(rb[1]),
        ))
    if engine == "stream":
        return dict(rk.range_stats_streaming_packed(
            jnp.asarray(ts_long), jnp.asarray(vals), jnp.asarray(valids),
            jnp.asarray(np.int32(w)),
            max_behind=int(rb[0]), max_ahead=int(rb[1]),
        ))
    ts_arr = jnp.asarray(ts_long)
    start, end = rk.range_window_bounds(
        ts_arr, rk.range_window_width(ts_arr, w)
    )
    # static row bound for the min/max sparse tables: a 10s window
    # over 1Hz data needs 4 levels, not log2(L); bucket to a power
    # of two so distinct datasets reuse the compiled kernel.
    # Padded slots all share the clamped sentinel timestamp, so
    # their windows span the whole pad run — mask them out of the
    # bound or ragged series inflate it toward L
    real = jnp.asarray(tsdf.packed_mask())
    max_w = max(1, int(jax.device_get(
        jnp.max(jnp.where(real, end - start, 0)))))
    max_w = 1 << (max_w - 1).bit_length()
    C, K, L = vals.shape
    flat = lambda a: jnp.asarray(a).reshape(C * K, L)
    tile = lambda a: jnp.broadcast_to(a[None], (C, K, L)).reshape(C * K, L)
    return rk.windowed_stats(
        flat(vals), flat(valids), tile(start), tile(end),
        max_window=max_w
    )


def _range_stats_chunked(tsdf, cols, rb, w):
    """Range stats over fixed lane chunks (``ops/rolling.
    range_stats_chunk``): each series is cut into cores of ``Lc`` lanes,
    and each core's block carries ``max_behind`` lanes before it and
    at least ``max_ahead`` after, so every window lies inside its block
    and the stats are exact.  The blocks are built on the host from the
    flat sorted columns, with each row's window as block lanes
    (``packing.layout_window_bounds``), and run ``RANGE_CHUNK_ROWS``
    blocks a call; the calls all have one shape.  Returns the output
    columns, flat rows."""
    layout = tsdf.layout
    block, halo, nlev = rk.range_chunk_plan(*rb)
    behind, core = int(rb[0]), block - halo
    G = rk.RANGE_CHUNK_ROWS
    dt = packing.compute_dtype()
    with span("tempo.pack", rows=layout.n_rows):
        plan = _ChunkPlan(layout, w, core, behind, halo, G)
    blocks = []
    for c in cols:
        v, m = tsdf.numeric_flat(c)
        with span("tempo.pack", rows=layout.n_rows):
            blocks.append(plan.blocks(v.astype(dt), m))
    n_lanes = len(cols) * plan.rows * block
    core_start = np.int32(behind)
    with span("tempo.dispatch") as fetched:
        with span("tempo.dispatch", rows=n_lanes):
            parts = [[rk.range_stats_chunk(
                x[g:g + G], ok[g:g + G], plan.start[g:g + G],
                plan.end[g:g + G], center[g:g + G], core_start, nlev=nlev)
                for g in range(0, plan.rows, G)]
                for x, ok, center in blocks]
        got = jax.device_get(parts)
        fetched.rows = sum(p.size for col in got for p in col)
    with span("tempo.unpack", rows=layout.n_rows * len(cols)):
        stats = {k: [] for k in rk.CHUNK_STATS}
        for col in got:
            planes = np.concatenate(col, axis=1)    # [7, rows, Lc]
            for i, k in enumerate(rk.CHUNK_STATS):
                stats[k].append(packing.take(planes[i].reshape(-1),
                                             plan.out_index))
        flat = _stat_columns(cols, stats)
        # free the chunk buffers (GBs at full size) inside the phase,
        # not after it in the op's own time
        del plan, blocks, parts, got, planes, stats
        return flat


class _ChunkPlan:
    """Where each row of a layout sits in the chunk blocks of
    :func:`_range_stats_chunked`.

    Series ``k`` of length ``n_k`` takes ``ceil(n_k / Lc)`` chunk rows,
    one after another (ragged series pad only their last chunk); the
    rows are padded to a whole number of calls.  Chunk ``c`` of series
    ``k`` covers series lanes ``[c*Lc - behind, c*Lc - behind +
    block)``; lanes outside the series are invalid pads.  ``start`` /
    ``end`` hold each core lane's window as block lanes, ``out_index``
    each flat row's place in the ``[rows, Lc]`` outputs."""

    def __init__(self, layout, w, core, behind, halo, G):
        lens = layout.lengths
        n_ch = -(-lens // core)
        ch_base = np.concatenate([[0], np.cumsum(n_ch)[:-1]])
        used = int(n_ch.sum())
        self.rows = max(G, -(-used // G) * G)
        self.core, self.block = core, core + halo
        starts = layout.starts[:-1]
        key = layout.key_ids
        pos = np.arange(layout.n_rows, dtype=np.int64) - starts[key]
        self.out_index = np.repeat(ch_base * core - starts, lens) + \
            np.arange(layout.n_rows, dtype=np.int64)
        # each series' stretch of the block buffer: ``behind`` pads,
        # its rows, then pads up to whole cores with at least one halo
        # after the last core, so chunk c's block starts at the
        # stretch's start plus c cores
        extra = -(-halo // core)
        seg = np.where(n_ch > 0, (n_ch + extra) * core, 0)
        seg_base = np.concatenate([[0], np.cumsum(seg)[:-1]])
        self.buf_len = int(seg.sum())
        self.buf_index = np.repeat(seg_base + behind - starts, lens) + \
            np.arange(layout.n_rows, dtype=np.int64)
        self.block_row = (np.repeat(seg_base // core, n_ch)
                          + np.arange(used) - np.repeat(ch_base, n_ch))
        start, end = packing.layout_window_bounds(layout, w)
        lane0 = pos - pos % core - behind      # series lane of block lane 0
        bounds = np.zeros((2, self.rows * core), np.int32)
        bounds[0, self.out_index] = start - lane0
        bounds[1, self.out_index] = end - lane0
        self.start = bounds[0].reshape(self.rows, core)
        self.end = bounds[1].reshape(self.rows, core)
        self.series_rows = n_ch
        self.key = key

    def blocks(self, values, valid):
        """``([rows, block] values, [rows, block] validity, [rows, 1]
        centre)`` of one column (flat sorted rows)."""
        from numpy.lib.stride_tricks import sliding_window_view

        dt = values.dtype
        x = np.where(valid, values, 0).astype(dt)
        n = np.bincount(self.key, weights=valid,
                        minlength=len(self.series_rows))
        total = np.bincount(self.key, weights=x,
                            minlength=len(self.series_rows))
        center = (total / np.maximum(n, 1)).astype(dt)
        out = []
        for arr in (x, valid):
            buf = np.zeros(self.buf_len + self.block, arr.dtype)
            buf[self.buf_index] = arr
            win = sliding_window_view(buf, self.block)[::self.core]
            blk = np.zeros((self.rows, self.block), arr.dtype)
            blk[:len(self.block_row)] = win[self.block_row]
            out.append(blk)
        c = np.zeros((self.rows, 1), dt)
        c[:len(self.block_row), 0] = np.repeat(center, self.series_rows)
        return out[0], out[1], c


def _bucket_ns(ts_ns: np.ndarray, freq_sec: int) -> np.ndarray:
    """Epoch-aligned tumbling window start (Spark f.window semantics)."""
    step = np.int64(freq_sec) * packing.NS_PER_S
    return (ts_ns // step) * step


def _segments(layout, bucket: np.ndarray):
    """Contiguous (series, bucket) runs over the sorted flat layout."""
    n = layout.n_rows
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int64), np.zeros(0, np.int64)
    change = np.ones(n, dtype=bool)
    change[1:] = (layout.key_ids[1:] != layout.key_ids[:-1]) | (
        bucket[1:] != bucket[:-1]
    )
    seg_ids = np.cumsum(change) - 1
    first_row = np.flatnonzero(change)
    return seg_ids.astype(np.int32), first_row, bucket[first_row]


def with_grouped_stats(tsdf, metricCols=None, freq: Optional[str] = None):
    from tempo_tpu.frame import TSDF

    cols = metricCols or tsdf.summarizable_columns()
    freq_sec = freq_to_seconds(freq)

    layout = tsdf.layout
    bucket = _bucket_ns(layout.ts_ns, freq_sec)
    seg_ids, first_row, seg_bucket = _segments(layout, bucket)
    n_seg = len(first_row)
    n_seg_padded = max(8, 1 << (n_seg - 1).bit_length()) if n_seg else 8

    out = {}
    sorted_df = tsdf.df.iloc[layout.order].reset_index(drop=True)
    for c in tsdf.partitionCols:
        out[c] = sorted_df[c].to_numpy()[first_row]
    out[tsdf.ts_col] = packing.ns_to_original(seg_bucket, tsdf.ts_dtype())

    dt = packing.compute_dtype()
    for c in cols:
        v, m = tsdf.numeric_flat(c)
        stats = rk.segment_stats(
            jnp.asarray(v.astype(dt)), jnp.asarray(m), jnp.asarray(seg_ids),
            n_seg_padded,
        )
        for stat in ("mean", "count", "min", "max", "sum", "stddev"):
            arr = np.asarray(stats[stat])[:n_seg]
            if stat == "count":
                arr = arr.astype(np.int64)
            else:
                arr = arr.astype(np.float64)
            out[f"{stat}_{c}"] = arr
    return TSDF(pd.DataFrame(out), tsdf.ts_col, tsdf.partitionCols)


def ema(tsdf, colName: str, window: int = 30, exp_factor: float = 0.2,
        exact: bool = False, inclusive_window: bool = False):
    """``inclusive_window=True`` reproduces the Scala lag range 0..window
    (EMA.scala:31, one more tap than the Python 0..window-1 range,
    tsdf.py:627 - the divergence tabled in SURVEY.md §2.4)."""
    from tempo_tpu.frame import TSDF

    layout = tsdf.layout
    v, m = tsdf.packed_numeric(colName)
    n_taps = int(window) + (1 if inclusive_window else 0)
    with span("tempo.dispatch") as fetched:
        if exact:
            from tempo_tpu.ops import pallas_kernels as pk

            # series too long for one VMEM block take the carry-passing
            # chunked kernel, in calls of one shape; the rest the whole
            # series at once
            chunked = pk.ema_chunked_ok(v)
            lanes = (pk.ema_chunked_lanes(*v.shape) if chunked
                     else v.size)
            with span("tempo.dispatch", rows=lanes):
                y = (pk.ema_chunked(v, m, exp_factor) if chunked else
                     pk.ema_scan(jnp.asarray(v), jnp.asarray(m),
                                 exp_factor))
        else:
            y = rk.ema_compat(jnp.asarray(v), jnp.asarray(m), n_taps,
                              float(exp_factor))
        # the frame is built while the device runs
        with span("tempo.frame", rows=layout.n_rows):
            out = tsdf.df.iloc[layout.order].reset_index(drop=True)
        y = np.asarray(y)
        fetched.rows = y.size
    with span("tempo.unpack", rows=layout.n_rows):
        ema_col = packing.unpack_column(y, layout).astype(np.float64)
    with span("tempo.frame", rows=layout.n_rows):
        out["EMA_" + colName] = ema_col
        return TSDF(out, tsdf.ts_col, tsdf.partitionCols,
                    tsdf.sequence_col or None)


_VWAP_TRUNC = {"m": "min", "H": "hr", "D": "day"}


def vwap(tsdf, frequency: str = "m", volume_col: str = "volume",
         price_col: str = "price"):
    """Scala-spec VWAP (TSDF.scala:378-401): truncate the ts to the
    given frequency, then per (partition, time group):
    dllr_value = sum(price*volume), volume = sum(volume),
    max_<price> = max(price), vwap = dllr_value / volume."""
    from tempo_tpu.frame import TSDF

    if frequency not in _VWAP_TRUNC:
        raise ValueError("vwap frequency must be one of 'm', 'H', 'D'")
    freq_sec = UNIT_SECONDS[_VWAP_TRUNC[frequency]]

    layout = tsdf.layout
    bucket = _bucket_ns(layout.ts_ns, freq_sec)
    seg_ids, first_row, seg_bucket = _segments(layout, bucket)
    n_seg = len(first_row)
    n_seg_padded = max(8, 1 << (n_seg - 1).bit_length()) if n_seg else 8

    dt = packing.compute_dtype()
    price, p_ok = tsdf.numeric_flat(price_col)
    vol, v_ok = tsdf.numeric_flat(volume_col)
    price, vol = price.astype(dt), vol.astype(dt)
    d_ok = p_ok & v_ok

    seg = jnp.asarray(seg_ids)
    s_d = rk.segment_stats(jnp.asarray(price * vol), jnp.asarray(d_ok), seg, n_seg_padded)
    s_v = rk.segment_stats(jnp.asarray(vol), jnp.asarray(v_ok), seg, n_seg_padded)
    s_p = rk.segment_stats(jnp.asarray(price), jnp.asarray(p_ok), seg, n_seg_padded)

    sorted_df = tsdf.df.iloc[layout.order].reset_index(drop=True)
    out = {}
    for c in tsdf.partitionCols:
        out[c] = sorted_df[c].to_numpy()[first_row]
    out[tsdf.ts_col] = packing.ns_to_original(seg_bucket, tsdf.ts_dtype())
    dllr_sum = np.asarray(s_d["sum"])[:n_seg].astype(np.float64)
    vol_sum = np.asarray(s_v["sum"])[:n_seg].astype(np.float64)
    out["dllr_value"] = dllr_sum
    out[volume_col] = vol_sum
    out["max_" + price_col] = np.asarray(s_p["max"])[:n_seg].astype(np.float64)
    out["vwap"] = dllr_sum / vol_sum
    return TSDF(pd.DataFrame(out), tsdf.ts_col, tsdf.partitionCols)


def with_lookback_features(tsdf, featureCols: List[str], lookbackWindowSize: int,
                           exactSize: bool = True, featureColName: str = "features"):
    """Parity: tsdf.py:637-671.  Builds, per row, the [w, n_features]
    array of the previous ``lookbackWindowSize`` observations
    (rowsBetween(-N, -1)); rows nearer the series start get shorter
    arrays unless exactSize filters them.

    Returns a bare DataFrame when exactSize=True (reference quirk,
    tsdf.py:668-669), else a TSDF.
    """
    from tempo_tpu.frame import TSDF

    layout = tsdf.layout
    sorted_df = tsdf.df.iloc[layout.order].reset_index(drop=True)
    n = len(sorted_df)
    w = int(lookbackWindowSize)

    # heavy lifting on device: the dense [K, L, w, F] shifted stack (the
    # same path lookback_tensor exposes), fetched once — the per-row
    # Python slicing loop this replaces crawled at quickstart scale
    tensor, _ = lookback_tensor(tsdf, featureCols, w)
    # flatten packed rows back to the sorted flat layout: [n, w, F]
    pos = np.arange(n, dtype=np.int64) - layout.starts[layout.key_ids]
    flat = np.asarray(tensor, dtype=np.float64)[layout.key_ids, pos]
    # rows nearer their series start have only pos valid lookback
    # entries, sitting at the *end* of the window axis
    cnt = np.minimum(pos, w)

    out = sorted_df.copy()
    if exactSize:
        keep = cnt == w
        out = out[keep].reset_index(drop=True)
        # single C-level materialisation of the object lists
        out[featureColName] = pd.Series(
            flat[keep].tolist(), index=out.index, dtype=object
        )
        return out
    nested = flat.tolist()
    out[featureColName] = pd.Series(
        [nested[i][w - cnt[i]:] for i in range(n)], dtype=object
    )
    return TSDF(out, tsdf.ts_col, tsdf.partitionCols, tsdf.sequence_col or None)


def lookback_stack(x, m, w: int):
    """[K, L, F] (values, mask) -> [K, L, w, F] shifted stacks: window
    slot j holds observation t - w + j (oldest first), zero/False
    where absent.  The single definition of the lookback-window
    semantics — shared by the host path below and the shard_map kernel
    (dist.py:_lookback_tensor_fn)."""
    L = x.shape[1]
    sh = lambda a, j: jnp.pad(a, ((0, 0), (j, 0), (0, 0)))[:, :L, :]
    return (jnp.stack([sh(x, j) for j in range(w, 0, -1)], axis=2),
            jnp.stack([sh(m, j) for j in range(w, 0, -1)], axis=2))


def lookback_tensor(tsdf, featureCols: List[str], lookbackWindowSize: int):
    """TPU-native variant: the dense [K, L, w, F] lookback tensor as a
    jax array (zero-padded, with a validity mask), suitable for feeding
    models directly without object-array materialisation."""
    vals, valids = _packed_metric_stack(tsdf, featureCols)   # [F, K, L]
    x = jnp.asarray(vals).transpose(1, 2, 0)                 # [K, L, F]
    m = jnp.asarray(valids).transpose(1, 2, 0)
    return lookback_stack(x, m, int(lookbackWindowSize))
