"""DistributedTSDF: the device mesh wired into the frame-level API.

In the reference every op is distributed *by construction* because
``Window.partitionBy``/shuffle is the execution substrate
(/root/reference/python/tempo/tsdf.py:121,571).  This module gives
tempo-tpu the same property: ``TSDF.on_mesh(...)`` packs the frame once
into mesh-sharded ``jax.Array``s and returns a :class:`DistributedTSDF`
whose op methods (``asofJoin`` / ``withRangeStats`` / ``EMA`` /
``resample``) run as shard_map programs over the mesh — data parallel
over the ``series`` axis, sequence parallel with halo exchange over the
``time`` axis — with results staying device-resident across chained
ops.  ``collect()`` materialises back to a host :class:`TSDF` with ONE
stacked device->host transfer.

This is also the single-chip device-residency mechanism: on a 1-device
mesh a chain of N ops performs exactly one pack and one unpack
(``_PACK_EVENTS`` / ``_FETCH_EVENTS`` count them for the tests), where
the host frame path would re-pack per op.

Design notes:

* Shard boundaries on the ``time`` axis are positional (each packed row
  is ascending reals then ``TS_PAD`` pads), and lookback windows read
  their history through a trailing neighbor halo
  (:mod:`tempo_tpu.parallel.halo`).  For the AS-OF join this mirrors
  the reference's ``tsPartitionVal`` contract exactly: a match further
  back than the halo yields a null plus a *deferred audit* warning (the
  reference's missing-lookback warning, tsdf.py:150-159) — audits are
  device scalars fetched at ``collect()`` so chains stay sync-free.
* Timestamps compute in int64 ns on device.  The joined right
  timestamp column is carried through the value-gather path as three
  21-bit chunk planes (each exact in float32) and recomposed to exact
  int64 ns at collect.
* Non-numeric columns stay on host and re-join the frame at collect
  (they are untouched by the device ops, like Spark columns that no
  expression references).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tempo_tpu import packing
from tempo_tpu.freq import (
    freq_to_seconds, validateFuncExists, floor, ceiling, average,
    min_func, max_func,
)
from tempo_tpu.ops import asof as asof_ops
from tempo_tpu.ops import rolling as rk
from tempo_tpu.ops.sortmerge import use_sort_kernels as _use_sort_kernels
from tempo_tpu.parallel import halo as ph
from tempo_tpu.parallel.halo import shard_map
from tempo_tpu.parallel.mesh import make_mesh

logger = logging.getLogger(__name__)

# transfer-count instrumentation: a chain of N ops must do 1 pack + 1
# fetch (tests assert this; the host frame path re-packs per op)
_PACK_EVENTS = 0
_FETCH_EVENTS = 0


@dataclasses.dataclass(frozen=True)
class DistCol:
    """One device-resident column: values + validity, with
    materialisation hints."""

    values: jax.Array          # [K_dev, L] compute dtype
    valid: jax.Array           # [K_dev, L] bool
    int64: bool = False        # cast to int64 at collect (counts)
    # (target ts column, bit shift): this col is one 21-bit chunk of an
    # int64-ns timestamp — three such planes recompose the ts EXACTLY
    # at collect even when the compute dtype is float32 (2^21 < 2^24)
    ts_chunk: Optional[Tuple[str, int]] = None
    # (flat host values [n_right_rows], right starts [K_r+1], perm
    # [K_dev] left->right series map): ``values`` holds matched right
    # ROW indices (f32-exact below 2^24) and collect() gathers the
    # host-resident (non-numeric) data — device never sees object dtypes
    host_gather: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None


def _spec(mesh: Mesh, series_axis, time_axis: Optional[str],
          ndim: int = 2) -> P:
    lead = [None] * (ndim - 2)
    return P(*(lead + [series_axis, time_axis]))


def _ns(mesh: Mesh, spec: P) -> NamedSharding:
    """NamedSharding for a stage-boundary declaration: every chained
    shard_map program below jits with explicit ``in_shardings`` /
    ``out_shardings`` built from its own shard specs, so stage N's
    output layout IS stage N+1's input layout by construction — a
    mis-laid operand raises at dispatch instead of compiling an
    implicit reshard (the zero-undeclared-collectives contract of the
    mesh chain, checked compiled-side by the stage-sharding-match rule
    in tools/analysis/compiled)."""
    return NamedSharding(mesh, spec)


def stream_mesh(n_devices: Optional[int] = None,
                stream_axis: str = "streams") -> Mesh:
    """A 1-D mesh whose single axis is the cohort STREAM axis — the
    fleet-serving layout (serve/cohort.py): scale-out is
    stream-parallel, so the whole device budget goes to one axis and
    every cohort state array shards its leading [S] dim across it."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    return Mesh(np.asarray(devs[:n]).reshape(n), (stream_axis,))


def stream_shardings(mesh: Mesh, stream_axis: str, tree):
    """Same-structure tree of ``NamedSharding(mesh, P(stream_axis))``
    for every leaf of ``tree`` (avals or arrays): axis 0 — the cohort
    stream axis — sharded, everything else replicated per shard.  The
    cohort step programs jit with this as BOTH ``in_shardings`` and
    ``out_shardings`` (:func:`serve.state.cohort_push_jitted`), the
    PR 10 pre-partitioned handoff: the compiled loop's output layout
    is its own input layout, so the steady state never implies a
    reshard and the compiled HLO carries zero collectives
    (``profiling.collective_counts_from_compiled`` — asserted by the
    ``serve.cohort_push`` compiled contract and the fleet bench)."""
    sh = _ns(mesh, P(stream_axis))
    return jax.tree_util.tree_map(lambda _: sh, tree)


class DistributedTSDF:
    """A TSDF whose packed cache is a sharded ``jax.Array`` on a device
    mesh and whose ops run distributed (SURVEY.md §2.3)."""

    def __init__(self, mesh: Mesh, series_axis: str,
                 time_axis: Optional[str], ts, mask,
                 cols: Dict[str, DistCol], layout, ts_col: str,
                 partition_cols: List[str], ts_dtype, source_df,
                 host_cols: Dict[str, str], halo_fraction: float,
                 audits: Optional[List[Tuple[str, jax.Array]]] = None,
                 resampled: bool = False, seq=None, seq_col: str = "",
                 resample_freq: Optional[str] = None):
        self.mesh = mesh
        self.series_axis = series_axis
        self.time_axis = time_axis
        self.ts = ts                      # [K_dev, L] int64 ns, TS_PAD pads
        self.mask = mask                  # [K_dev, L] bool (real rows)
        self.cols = cols
        self.layout = layout
        self.ts_col = ts_col
        self.partitionCols = list(partition_cols)
        self._ts_dtype = ts_dtype
        self._source_df = source_df
        self.host_cols = dict(host_cols)   # output name -> source column
        self.halo_fraction = halo_fraction
        self.audits = list(audits or [])
        self.resampled = resampled
        self.seq = seq                    # [K_dev, L] sort key or None
        self.seq_col = seq_col
        self._resample_freq = resample_freq

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------

    @property
    def n_time(self) -> int:
        return self.mesh.shape[self.time_axis] if self.time_axis else 1

    @property
    def n_series_shards(self) -> int:
        # a series-LOCAL re-laid frame (reshard_frame) shards its K axis
        # jointly over ('series', 'time'): the axis name is a tuple and
        # the shard count is the product
        if isinstance(self.series_axis, tuple):
            return int(np.prod([self.mesh.shape[a]
                                for a in self.series_axis]))
        return self.mesh.shape[self.series_axis]

    @property
    def L(self) -> int:
        return int(self.ts.shape[1])

    @property
    def K_dev(self) -> int:
        return int(self.ts.shape[0])

    def _sharding(self, ndim: int = 2) -> NamedSharding:
        return NamedSharding(
            self.mesh, _spec(self.mesh, self.series_axis, self.time_axis, ndim)
        )

    @classmethod
    def from_tsdf(cls, tsdf, mesh: Optional[Mesh] = None,
                  series_axis: str = "series",
                  time_axis: Optional[str] = None,
                  halo_fraction: float = 0.5) -> "DistributedTSDF":
        """Pack + shard a host TSDF onto the mesh (the ingest boundary —
        the analog of Spark's shuffle-on-partition-cols).  ONE
        host->device transfer for the whole frame."""
        global _PACK_EVENTS
        mesh = mesh if mesh is not None else make_mesh()
        if time_axis is not None and time_axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis named {time_axis!r}")
        n_s = mesh.shape[series_axis]
        n_t = mesh.shape[time_axis] if time_axis else 1

        layout = tsdf.layout
        K_dev, L, n_s, n_t = _mesh_packed_geometry(
            layout, mesh, series_axis, time_axis)

        dt = packing.compute_dtype()
        ts_p = packing.pack_column(layout.ts_ns, layout, L, fill=packing.TS_PAD)
        mask_p = packing.row_mask(layout, L)
        ts_p = _pad_k(ts_p, K_dev, packing.TS_PAD)
        mask_p = _pad_k(mask_p, K_dev, False)

        cols: Dict[str, DistCol] = {}
        host_cols: Dict[str, str] = {}
        structural = {tsdf.ts_col, *tsdf.partitionCols}
        seq_p = None
        if tsdf.sequence_col:
            structural.add(tsdf.sequence_col)
            # the sequence column is both an output column (it rides the
            # host row-identity path like any structural col) and a
            # device-resident join sort key.  A null RIGHT sequence
            # sorts FIRST (-inf in the float total order) per Spark's
            # ASC NULLS FIRST (tsdf.py:117-121), matching the host merge
            # path (join.py); values beyond 2^24 lose exactness under
            # the f32 policy.
            host_cols[tsdf.sequence_col] = tsdf.sequence_col
            sv, sm_ = tsdf.numeric_flat(tsdf.sequence_col)
            sv = np.where(sm_, sv, -np.inf).astype(dt)
            seq_p = _pad_k(
                packing.pack_column(sv, layout, L, fill=np.inf),
                K_dev, np.inf,
            )
        for c in tsdf.df.columns:
            if c in structural:
                continue
            dtype = tsdf.df[c].dtype
            if pd.api.types.is_numeric_dtype(dtype) and not \
                    pd.api.types.is_bool_dtype(dtype):
                vals, valid = tsdf.numeric_flat(c)
                if pd.api.types.is_integer_dtype(dtype) and valid.any() \
                        and np.abs(vals[valid]).max() >= 2.0 ** 53:
                    # integers beyond float64's exact range (2^53) can't
                    # ride the float compute planes without corruption —
                    # they stay host-resident (exact row-identity /
                    # join-index gather), like non-numeric columns
                    host_cols[c] = c
                    continue
                pv = packing.pack_column(vals.astype(dt), layout, L, fill=np.nan)
                pm = packing.pack_column(valid, layout, L, fill=False)
                cols[c] = DistCol(_pad_k(pv, K_dev, np.nan),
                                  _pad_k(pm, K_dev, False))
            else:
                host_cols[c] = c

        sharding = NamedSharding(mesh, _spec(mesh, series_axis, time_axis))
        put = _put_global(sharding)
        ts_d = put(ts_p)
        mask_d = put(mask_p)
        cols_d = {
            c: DistCol(put(col.values), put(col.valid))
            for c, col in cols.items()
        }
        seq_d = put(seq_p) if seq_p is not None else None
        _PACK_EVENTS += 1
        return cls(mesh, series_axis, time_axis, ts_d, mask_d, cols_d,
                   layout, tsdf.ts_col, tsdf.partitionCols,
                   tsdf.ts_dtype(), tsdf.df, host_cols, halo_fraction,
                   seq=seq_d, seq_col=tsdf.sequence_col or "")

    def _plan_record(self, op: str, others=(), params=None, objs=None):
        """Record a deferred plan node over this (already packed) mesh
        frame instead of executing (``TEMPO_TPU_PLAN=1``); the lazy
        wrapper's ``collect()`` optimizes + executes through the plan
        executable cache (tempo_tpu/plan/)."""
        from tempo_tpu.plan import lazy as plan_lazy

        return plan_lazy.record(self, op, others, params, objs)

    def explain(self, cost: bool = False) -> str:
        """Render this frame's query plan (bare mesh source when
        eager; the lazy wrappers show recorded chains + optimizer
        rewrites)."""
        from tempo_tpu.plan import ir, render

        text = render.explain_text(ir.Node("dist_source", payload=self),
                                   cost=cost)
        print(text)
        return text

    def _with(self, **kw) -> "DistributedTSDF":
        base = dict(
            mesh=self.mesh, series_axis=self.series_axis,
            time_axis=self.time_axis, ts=self.ts, mask=self.mask,
            cols=self.cols, layout=self.layout, ts_col=self.ts_col,
            partition_cols=self.partitionCols, ts_dtype=self._ts_dtype,
            source_df=self._source_df, host_cols=self.host_cols,
            halo_fraction=self.halo_fraction, audits=self.audits,
            resampled=self.resampled, seq=self.seq, seq_col=self.seq_col,
            resample_freq=self._resample_freq,
        )
        base.update(kw)
        return DistributedTSDF(**base)

    def numeric_columns(self) -> List[str]:
        return [c for c, col in self.cols.items()
                if col.ts_chunk is None and col.host_gather is None]

    def _window_rowbounds(self, window_secs: float) -> Optional[Tuple[int, int]]:
        """Static (max rows back, max tie rows ahead) any rangeBetween
        (-window_secs, 0) frame spans, from the host layout.  Cached per
        window size; O(n) numpy per series.

        Returns None when the layout's timestamps cannot vouch for the
        device timestamps — resampled frames (device ts are bucket
        floors, layout still holds raw ts) and ingest-assembled frames
        (layout carries offsets only, ts_ns is empty) — so callers fall
        back to the data-independent exact kernels."""
        lay = self.layout
        if (self.resampled or lay.n_rows == 0
                or int(lay.starts[-1]) != lay.n_rows):
            return None
        return packing.layout_rowbounds(lay, window_secs)

    def _halo(self, L: int) -> int:
        shard = L // self.n_time
        return max(1, min(shard, int(shard * self.halo_fraction)))

    def _range_engine_choice(self, window_secs: float):
        """``(engine, rowbounds, sort_kernels)`` — the three-way
        range-stats engine decision for this frame's shard shape, shared
        by the eager :meth:`withRangeStats`, the plan optimizer's
        plan-time hoist (via :func:`plan_range_engine_choice`), and the
        fused-chain executor (plan/fused.py).  On TPU, row-boundable
        windows run gather-free as shifted masked accumulations
        (ops/sortmerge.py); bounds come from the host layout once per
        window size."""
        sort_kernels = _use_sort_kernels()
        if not sort_kernels:
            return "shifted", None, sort_kernels
        rb = self._window_rowbounds(window_secs)
        # per-device shard element count bounds the unrolled form's
        # HBM footprint (ops/rolling.py:shifted_row_budget); on the
        # exact strategy the kernel computes over series-local FULL
        # rows (the a2a layout switch), so the shard is K/devices
        # by the full L.  Same three-way pick as the host frame
        # (ops/rolling.pick_range_engine): shifted / streaming VMEM
        # sweep / prefix+RMQ fallback.
        shard_k = self.K_dev // (self.n_series_shards
                                 * max(self.n_time, 1))
        engine, rowbounds = _pick_range_engine_for_shard(shard_k, self.L,
                                                         rb)
        return engine, rowbounds, sort_kernels

    # ------------------------------------------------------------------
    # withRangeStats (tsdf.py:673-721)
    # ------------------------------------------------------------------

    def withRangeStats(self, colsToSummarize=None,
                       rangeBackWindowSecs: int = 1000,
                       strategy: str = "exact") -> "DistributedTSDF":
        """Distributed rolling range stats.  On a time-sharded mesh:

        * ``strategy="exact"`` (default) — switch to a series-local
          layout with one all_to_all each way and compute the exact
          Spark rangeBetween semantics regardless of window size.
        * ``strategy="halo"`` — stay time-sharded and read the lookback
          through a trailing neighbor-halo exchange (O(halo) comm
          instead of O(L)); windows longer than the halo truncate, and
          a deferred audit (collect-time warning) counts affected rows
          — the reference's own tsPartitionVal trade-off
          (tsdf.py:164-190).
        """
        if strategy not in ("exact", "halo"):
            raise ValueError("strategy must be 'exact' or 'halo'")
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("range_stats", params=dict(
                colsToSummarize=tuple(colsToSummarize)
                if colsToSummarize else None,
                rangeBackWindowSecs=rangeBackWindowSecs,
                strategy=strategy))
        if strategy == "exact" and self.n_time > 1:
            # exact stats on a time-sharded mesh: ONE explicit
            # whole-frame reshard to the series-local layout
            # (reshard_frame — the same program the planner's
            # plan-placed reshard nodes run), the SAME local stats
            # program every series-local frame runs, and one switch
            # back.  The former in-kernel all_to_all sandwich
            # (_range_stats_a2a_packed) compiled the collectives INTO
            # the stats program, and XLA's FMA-contraction decisions
            # around the cancellation-sensitive var/stddev math
            # drifted in the last ulp vs the series-local program —
            # which would have broken the plan optimizer's
            # reshard-elimination bitwise contract (planned chains
            # elide the interior switches and so MUST run the
            # series-local program).
            local = reshard_frame(self, RESHARD_SERIES_LOCAL)
            out = local.withRangeStats(
                colsToSummarize=colsToSummarize,
                rangeBackWindowSecs=rangeBackWindowSecs,
                strategy=strategy)
            return reshard_frame(out, RESHARD_TIME_SHARDED)
        cols = colsToSummarize or self.numeric_columns()
        w = float(rangeBackWindowSecs)
        new_cols = dict(self.cols)
        audits = list(self.audits)
        if strategy == "exact":
            engine, rowbounds, sort_kernels = self._range_engine_choice(w)
        else:
            engine, rowbounds, sort_kernels = \
                "shifted", None, _use_sort_kernels()
        if cols and (strategy == "exact" or self.n_time <= 1):
            # (a single-shard "halo" strategy has no halo to exchange —
            # it runs the local path exactly like the seed did)
            # multi-column payload packing: ONE shard_map program over
            # the [C, K, L] column stack — the timestamp planes stream
            # once per kernel pack instead of once per column, and the
            # per-op dispatch cost stops scaling with C.  Per-column
            # results are bitwise-identical to the per-column programs
            # (_range_stats_block_packed).
            xs = jnp.stack([self.cols[c].values for c in cols])
            vs = jnp.stack([self.cols[c].valid for c in cols])
            stats, rb_clipped = _range_stats_local_packed(
                self.mesh, self.series_axis, w, rowbounds,
                sort_kernels, engine,
            )(self.ts, xs, vs)
            for ci, c in enumerate(cols):
                if strategy == "exact" and rowbounds is not None:
                    # deferred truncation audit of the shifted-window
                    # form: the host-derived row bounds must cover
                    # every frame (they do by construction — this
                    # catches bound-derivation bugs and device/layout
                    # ts divergence)
                    audits.append((
                        f"withRangeStats({c}): %d rows had window "
                        f"frames extending past the static row bounds "
                        f"{rowbounds}; this is a tempo-tpu bug — "
                        f"please report it", rb_clipped[ci],
                    ))
                for stat in packing.RANGE_STATS:
                    new_cols[f"{stat}_{c}"] = DistCol(
                        stats[stat][ci], self.mask,
                        int64=(stat == "count"),
                    )
            return self._with(cols=new_cols, audits=audits)
        for c in cols:
            col = self.cols[c]
            halo = self._halo(self.L)
            stats, clipped = _range_stats_halo(
                self.mesh, self.series_axis, self.time_axis, w, halo,
            )(self.ts, col.values, col.valid)
            audits.append((
                f"withRangeStats({c}): %d rows had windows truncated "
                f"at the time-shard halo ({halo} rows); increase the "
                f"halo_fraction or shard count", clipped,
            ))
            for stat in packing.RANGE_STATS:
                new_cols[f"{stat}_{c}"] = DistCol(
                    stats[stat], self.mask, int64=(stat == "count"),
                )
        return self._with(cols=new_cols, audits=audits)

    rangeStats = withRangeStats

    # ------------------------------------------------------------------
    # EMA (tsdf.py:615-635; exact scan form)
    # ------------------------------------------------------------------

    def EMA(self, colName: str, window: int = 30, exp_factor: float = 0.2,
            exact: bool = False,
            inclusive_window: bool = False) -> "DistributedTSDF":
        """Distributed EMA.  Defaults mirror ``TSDF.EMA`` (truncated-lag
        reference parity, tsdf.py:615-635) so the same call gives the
        same numbers on or off the mesh.  The exact infinite-horizon
        scan composes across time shards (associative carry stitch); the
        truncated-lag approximation does not, so time-sharded meshes
        require ``exact=True``."""
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("ema", params=dict(
                colName=colName, window=window, exp_factor=exp_factor,
                exact=exact, inclusive_window=inclusive_window))
        col = self.cols[colName]
        if self.n_time > 1:
            if not exact:
                raise ValueError(
                    "truncated-lag EMA does not cross time shards; use "
                    "exact=True (or a series-only mesh)"
                )
            y = ph.ema_time_sharded(self.mesh, col.values, col.valid,
                                    float(exp_factor),
                                    time_axis=self.time_axis,
                                    series_axis=self.series_axis)
        else:
            n_taps = int(window) + (1 if inclusive_window else 0)
            y = _ema_local(self.mesh, self.series_axis, float(exp_factor),
                           bool(exact), n_taps)(col.values, col.valid)
        new_cols = dict(self.cols)
        new_cols["EMA_" + colName] = DistCol(y, self.mask)
        return self._with(cols=new_cols)

    # ------------------------------------------------------------------
    # asofJoin (tsdf.py:463-560, fast path)
    # ------------------------------------------------------------------

    def asofJoin(self, right: "DistributedTSDF",
                 left_prefix: Optional[str] = None,
                 right_prefix: str = "right",
                 tsPartitionVal: Optional[int] = None,
                 fraction: float = 0.5,
                 skipNulls: bool = True,
                 sql_join_opt: bool = False,
                 suppress_null_warning: bool = False,
                 maxLookback: int = 0) -> "DistributedTSDF":
        """Distributed AS-OF join.  The right frame is aligned to the
        left's series-id space with one device gather (the
        co-partitioning shuffle analog), then joined shard-locally with
        a trailing halo on time-sharded meshes.

        Right-side non-numeric (host-resident) columns join by carrying
        the matched right *row index* as a value plane (exact in f32 up
        to 2^24 rows/series) and gathering the strings host-side at
        ``collect()`` — the device never touches object data.

        Sequence-number tie-break runs device-resident when the RIGHT
        frame was built with a ``sequence_col`` — only the right's
        sequence orders the merge, mirroring the reference (left rows
        carry NULL in it and sort first on ties, tsdf.py:117-121).
        ``maxLookback`` > 0 caps the fill at the trailing maxLookback+1
        merged (left+right) rows, Scala's rowsBetween window on the
        union stream (asofJoin.scala:64-88), computed device-side via
        the windowed argmax ladder.

        ``tsPartitionVal``/``fraction``/``sql_join_opt`` are accepted
        for migration compatibility and ignored: they tune Spark's skew
        brackets and broadcast-range fast path (tsdf.py:463-509), both
        of which this join replaces — the packed layout is skew-free by
        construction and the merge join is already shuffle-free."""
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("asof_join", (right,), dict(
                left_prefix=left_prefix, right_prefix=right_prefix,
                tsPartitionVal=tsPartitionVal, fraction=fraction,
                skipNulls=skipNulls, sql_join_opt=sql_join_opt,
                suppress_null_warning=suppress_null_warning,
                maxLookback=maxLookback))
        if tsPartitionVal is not None:
            logger.info(
                "asofJoin: tsPartitionVal ignored on the mesh — the "
                "packed layout needs no skew brackets"
            )
        if right.mesh is not self.mesh and right.mesh != self.mesh:
            raise ValueError("both frames must live on the same mesh")
        if self.partitionCols != right.partitionCols:
            raise ValueError(
                "left and right dataframe partition columns should have same name in same order"
            )

        # host-side key-space alignment (K-sized metadata only)
        perm, ok = _key_perm(self.layout.key_frame, right.layout.key_frame,
                             self.partitionCols, self.K_dev)
        align2 = _align_fn(self.mesh, self.series_axis, self.time_axis)

        # every device-resident right column joins — plain numerics,
        # ts-chunk planes from earlier joins, and host-gather index
        # planes from earlier joins (chained a.asofJoin(b.asofJoin(c))
        # must not lose the inner join's columns)
        r_recs = list(right.cols.items())
        h_names = [c for c in right.host_cols
                   if right._source_df is not None]
        r_ts_al = align2(right.ts, perm, ok, packing.TS_PAD)

        dt = packing.compute_dtype()
        sharding_r = right._sharding(2)
        # value stack layout (offsets named below):
        #   [0, n)              right col values (all kinds)
        #   [n, n+3)            right ts as three 21-bit ns chunks (f32-exact)
        #   skipNulls=True:
        #     [n+3, n+3+H)      host-col row-index planes (validity = the
        #                       host col's non-null mask -> per-col ffill)
        #   skipNulls=False:
        #     [n+3, 2n+3)       per-col validity planes (to recover nulls)
        #     [2n+3, 2n+3+H)    host-col row-index planes (validity = mask)
        #     [2n+3+H, 2n+3+2H) host-col non-null planes
        planes = [col.values for _, col in r_recs]
        valid_planes = [col.valid for _, col in r_recs]
        planes.extend(ts_chunk_planes(right.ts, dt))

        host_flat: Dict[str, np.ndarray] = {}
        h_notna_dev = []
        if h_names:
            ridx_plane = jnp.broadcast_to(
                jnp.arange(right.L, dtype=dt), (right.K_dev, right.L)
            )
            for c in h_names:
                src = right.host_cols[c]
                flat = right._source_df[src].to_numpy()[right.layout.order]
                host_flat[c] = flat
                pm = packing.pack_column(
                    ~pd.isna(flat), right.layout, right.L, fill=False
                )
                h_notna_dev.append(jax.device_put(
                    _pad_k(pm, right.K_dev, False), sharding_r
                ))
        if skipNulls:
            if h_names:
                planes.extend([ridx_plane] * len(h_names))
            vstack = jnp.stack(valid_planes + [right.mask] * 3
                               + h_notna_dev)
        else:
            planes.extend(v.astype(dt) for v in valid_planes)
            if h_names:
                planes.extend([ridx_plane] * len(h_names))
                planes.extend(v.astype(dt) for v in h_notna_dev)
            vstack = jnp.stack([right.mask] * len(planes))
        pstack = jnp.stack(planes)

        # pstack/vstack are freshly-stacked temporaries and the output
        # shape matches when the packed K agrees — donate their HBM to
        # the aligned copies (align2's operands are frame-owned: never
        # donated).  The layouts must also agree: a series-LOCAL left
        # frame (plan-placed reshard) aligning a time-sharded right
        # stack has different per-device buffer shapes, so XLA could
        # not apply the alias and would silently keep both live.
        align3 = _align3_fn(self.mesh, self.series_axis, self.time_axis,
                            donate=(right.K_dev == self.K_dev
                                    and right.series_axis
                                    == self.series_axis
                                    and right.time_axis
                                    == self.time_axis))
        pstack = align3(pstack, perm, ok, np.nan)
        vstack = align3(vstack, perm, ok, False)

        sort_kernels = _use_sort_kernels()
        # per-shard engine note (round 6): the a2a layout switch hands
        # each device FULL series rows, so the shard-local merge width
        # is the full merged width — past the single-program ceiling
        # (resilience.max_merged_lanes) the sortmerge dispatch inside
        # the shard kernels routes to the XLA bitonic network
        # (ops/pallas_merge.py:asof_merge_values_bitonic, O(log Lc)
        # stages — the lax.sort ladder's unrolled network OOM-killed
        # the compiler at ~205K lanes), governed by the same
        # TEMPO_TPU_JOIN_ENGINE knob as the host join.  The host-built
        # lane-chunked layout cannot cross shard_map, so chunked stays
        # a host-path engine.
        from tempo_tpu import resilience as _resilience

        _merged_full = int(self.L) + int(right.L)
        _limit = _resilience.max_merged_lanes()
        if 0 < _limit < _merged_full:
            logger.info(
                "asofJoin(mesh): merged width %d exceeds the "
                "single-program limit %d — shard-local joins use the "
                "XLA bitonic oversize engine", _merged_full, _limit,
            )
        # sequence-number tie-break (tsdf.py:117-121): the reference
        # sorts the merged stream by (combined_ts, RIGHT's sequence col
        # ASC NULLS FIRST, rec_ind).  Left rows carry NULL in the
        # right's seq column; a tied-ts NON-null-seq right row sorts
        # after them (invisible to them), while a tied-ts NULL-seq right
        # row (packed as -inf, from_tsdf) ties on seq and wins via
        # rec_ind — visible to the tied left rows.  The left frame's own
        # sequence never orders the merge.
        ml = int(maxLookback or 0)
        # resampled (bucket-head) frames keep real-looking ts at masked
        # lane rows; maxLookback must count real rows only, so those
        # lanes are sort-compacted to the lane tail inside the kernel —
        # on the right (carrying every value plane along) and, since
        # round 4, on the LEFT too (outputs route back through the
        # recorded source-lane plane, _uncompact_left).  The mask
        # planes are only consulted when a compaction is active.
        compact = bool(ml and right.resampled)
        compact_left = bool(ml and self.resampled)
        r_mask_al = (align2(right.mask, perm, ok, False) if compact
                     else r_ts_al < packing.TS_REAL_MAX)
        has_seq = right.seq is not None
        # stage donation applies only when the join outputs (left lane
        # width) can alias the aligned right stacks (right lane width)
        _donate_join = int(self.L) == int(right.L)
        if has_seq:
            # left rows ride the kernel-synthesized seq fill
            # (finfo.min in _merge_sides — above the -inf null-seq
            # encoding, below any real seq, so the order is
            # right-null < left < right-non-null on ts ties) — no
            # constant plane to shard or transpose
            r_seq_al = align2(right.seq, perm, ok, np.inf)
            if self.n_time > 1:
                vals, found = _asof_a2a_seq(self.mesh, self.series_axis,
                                            self.time_axis, ml,
                                            compact_left,
                                            donate=_donate_join)(
                    self.ts, self.mask, r_ts_al, r_seq_al, vstack, pstack
                )
            else:
                vals, found = _asof_local_seq(self.mesh, self.series_axis,
                                              ml, compact_left,
                                              donate=_donate_join)(
                    self.ts, self.mask, r_ts_al, r_seq_al, vstack, pstack
                )
        elif self.n_time > 1:
            # joins are *global* per series (unbounded lookback), so the
            # time-sharded layout switches to series-local full rows
            # with one all_to_all each way (reshard.py pattern), joins
            # exactly, and switches back — no halo approximation
            vals, found = _asof_a2a(self.mesh, self.series_axis,
                                    self.time_axis, sort_kernels, ml,
                                    compact, compact_left,
                                    donate=_donate_join)(
                self.ts, self.mask, r_ts_al, r_mask_al, vstack, pstack
            )
        else:
            vals, found = _asof_local(self.mesh, self.series_axis,
                                      sort_kernels, ml, compact,
                                      compact_left,
                                      donate=_donate_join)(
                self.ts, self.mask, r_ts_al, r_mask_al, vstack, pstack
            )
        audits = list(self.audits)

        rename = (lambda c: f"{left_prefix}_{c}") if left_prefix else (lambda c: c)
        new_cols = {rename(c): col for c, col in self.cols.items()}
        new_host = {rename(c): src for c, src in self.host_cols.items()}
        n = len(r_recs)
        H = len(h_names)
        hidx_off = (n + 3) if skipNulls else (2 * n + 3)
        for i, (c, rcol) in enumerate(r_recs):
            if skipNulls:
                v, f = vals[i], found[i]
            else:
                v = vals[i]
                f = found[i] & (vals[n + 3 + i] > 0.5)
            if rcol.ts_chunk is not None:
                # a joined-timestamp chunk from an earlier join: re-target
                # its recompose name under this join's prefix
                target, shift = rcol.ts_chunk
                nt = f"{right_prefix}_{target}"
                j = {42: 0, 21: 1, 0: 2}[shift]
                new_cols[f"__{nt}__c{j}"] = DistCol(v, f, ts_chunk=(nt, shift))
            elif rcol.host_gather is not None:
                # an earlier join's host-col index plane: compose this
                # join's series permutation into its gather map
                fv, st, pm = rcol.host_gather
                pm2 = pm[np.clip(perm, 0, max(len(pm) - 1, 0))]
                new_cols[f"{right_prefix}_{c}"] = DistCol(
                    v, f, host_gather=(fv, st, pm2)
                )
            else:
                new_cols[f"{right_prefix}_{c}"] = DistCol(
                    jnp.where(f, v, jnp.nan), f, int64=rcol.int64
                )
        rts_name = f"{right_prefix}_{right.ts_col}"
        for j, shift in enumerate((42, 21, 0)):
            new_cols[f"__{rts_name}__c{j}"] = DistCol(
                vals[n + j], found[n + j], ts_chunk=(rts_name, shift)
            )
        for i, c in enumerate(h_names):
            if skipNulls:
                v, f = vals[hidx_off + i], found[hidx_off + i]
            else:
                v = vals[hidx_off + i]
                f = found[hidx_off + i] & (vals[hidx_off + H + i] > 0.5)
            new_cols[f"{right_prefix}_{c}"] = DistCol(
                v, f, host_gather=(
                    host_flat[c], right.layout.starts, perm,
                ),
            )
        # the left ts column itself is the frame's time axis (renamed
        # when left_prefix is set, tsdf.py:529-531).  The join result
        # has no sequence column (the host path returns a TSDF without
        # one, join.py:285) — chained joins must not re-apply the
        # tie-break, and the left seq stays available as a data column
        # via host_cols.
        return self._with(cols=new_cols, audits=audits,
                          host_cols=new_host, ts_col=rename(self.ts_col),
                          seq=None, seq_col="")

    # ------------------------------------------------------------------
    # resample (resample.py:38-117), device-resident representation
    # ------------------------------------------------------------------

    def resample(self, freq: str, func: str,
                 metricCols=None) -> "DistributedTSDF":
        """Distributed downsample.  The result keeps the packed [K, L]
        shape as a *bucket-head view*: each row's ts becomes its bucket
        start, only the first row of each bucket is valid, and column
        values hold the bucket aggregate at head rows.  ``collect()``
        compacts the view; chained device ops (EMA, range stats) treat
        it like any masked frame.  On a time-sharded mesh the rows are
        switched to a series-local layout with one all_to_all each way
        (the reshard analog of the reference's groupBy shuffle).
        """
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("resample", params=dict(
                freq=freq, func=func,
                metricCols=tuple(metricCols) if metricCols else None))
        validateFuncExists(func)
        if self.n_time > 1:
            # time-sharded mesh: explicit whole-frame reshard + the
            # series-local kernel + switch back (see withRangeStats —
            # the mean aggregates are accumulation-sensitive, so the
            # plan-placed reshard elimination requires the eager path
            # to run the SAME series-local program)
            local = reshard_frame(self, RESHARD_SERIES_LOCAL)
            out = local.resample(freq, func, metricCols=metricCols)
            return reshard_frame(out, RESHARD_TIME_SHARDED)
        step = freq_to_seconds(freq) * packing.NS_PER_S
        cols = metricCols or self.numeric_columns()
        fkey = {floor: 0, ceiling: 1, average: 2, min_func: 3, max_func: 4}[
            _canon_func(func)
        ]

        kernel = _resample_fn(self.mesh, self.series_axis, self.time_axis,
                              int(step), fkey, len(cols),
                              _use_sort_kernels())
        vals = jnp.stack([self.cols[c].values for c in cols])
        valids = jnp.stack([self.cols[c].valid for c in cols])
        new_ts, head, out_vals, out_valid = kernel(self.ts, self.mask,
                                                   vals, valids)
        new_cols = {
            c: DistCol(out_vals[i], out_valid[i]) for i, c in enumerate(cols)
        }
        return self._with(ts=new_ts, mask=head, cols=new_cols,
                          resampled=True, seq=None, seq_col="",
                          resample_freq=freq)

    def calc_bars(self, freq: str, func=None, metricCols=None,
                  fill=None) -> "DistributedTSDF":
        """OHLC bars (tsdf.py:813-826) device-resident.  The reference
        runs four resamples and joins them on key+ts; here the four
        resample results land on identical bucket grids (bucket heads
        depend only on ts and freq), so their columns combine by name
        with no join.  Each resample still runs its own kernel — on a
        time-sharded mesh that is four a2a round-trips where a fused
        four-aggregate kernel would need one; fuse if bars become hot.

        ``fill=True`` upsamples the merged bars to each series' dense
        bucket grid with zero-filled numerics (resample.py:102-116) —
        realised as the device interpolate's zero fill over the merged
        bucket-head view (round 4; the four grids are identical, so
        fill-then-merge and merge-then-fill commute)."""
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("calc_bars", params=dict(
                freq=freq, func=func,
                metricCols=tuple(metricCols) if metricCols else None,
                fill=fill))
        with plan.suspended():
            # eager-only op whose body chains recorded methods
            # (resample/interpolate): those must not re-enter planning
            mc = metricCols or self.numeric_columns()
            new_cols: Dict[str, DistCol] = {}
            base = None
            for prefix, f in (("open", "floor"), ("low", "min"),
                              ("high", "max"), ("close", "ceil")):
                r = self.resample(freq, f, metricCols=mc)
                base = r
                for c in mc:
                    new_cols[f"{prefix}_{c}"] = r.cols[c]
            # host column order parity: prefixed metrics sorted by name
            # (resample.py:calc_bars sorts the non-partition columns)
            new_cols = {c: new_cols[c] for c in sorted(new_cols)}
            bars = base._with(cols=new_cols)
            if fill:
                bars = bars.interpolate(method="zero")
            return bars

    # ------------------------------------------------------------------
    # withGroupedStats (tsdf.py:723-759) / vwap (TSDF.scala:378-401)
    # ------------------------------------------------------------------

    def withGroupedStats(self, metricCols=None,  # plan-ok: eager-only
                         freq: str = None) -> "DistributedTSDF":
        """Distributed tumbling-window grouped statistics: six
        aggregates per metric column per epoch-aligned bucket, emitted
        as a bucket-head view (one valid row per bucket, ts = bucket
        start — the reference's groupBy output shape)."""
        step = freq_to_seconds(freq) * packing.NS_PER_S
        cols = metricCols or self.numeric_columns()
        kernel = _bucket_stats_fn(self.mesh, self.series_axis,
                                  self.time_axis, int(step), len(cols),
                                  _use_sort_kernels())
        vals = jnp.stack([self.cols[c].values for c in cols])
        valids = jnp.stack([self.cols[c].valid for c in cols])
        new_ts, head, stats = kernel(self.ts, self.mask, vals, valids)
        new_cols = {}
        for i, c in enumerate(cols):
            for j, stat in enumerate(("mean", "count", "min", "max",
                                      "sum", "stddev")):
                new_cols[f"{stat}_{c}"] = DistCol(
                    stats[j, i], head, int64=(stat == "count")
                )
        return self._with(ts=new_ts, mask=head, cols=new_cols,
                          resampled=True, seq=None, seq_col="",
                          resample_freq=freq)

    def vwap(self, frequency: str = "m", volume_col: str = "volume",  # plan-ok: eager-only
             price_col: str = "price") -> "DistributedTSDF":
        """Distributed VWAP (Scala spec): per (series, truncated-ts)
        bucket — dllr_value = sum(price*volume), total volume,
        max price, vwap = dllr_value / volume."""
        from tempo_tpu.freq import UNIT_SECONDS
        from tempo_tpu.rolling import _VWAP_TRUNC

        if frequency not in _VWAP_TRUNC:
            raise ValueError("vwap frequency must be one of 'm', 'H', 'D'")
        step = UNIT_SECONDS[_VWAP_TRUNC[frequency]] * packing.NS_PER_S
        price = self.cols[price_col]
        vol = self.cols[volume_col]
        both = price.valid & vol.valid
        vals = jnp.stack([
            jnp.where(both, price.values * vol.values, 0.0),
            vol.values, price.values,
        ])
        valids = jnp.stack([both, vol.valid, price.valid])
        kernel = _bucket_stats_fn(self.mesh, self.series_axis,
                                  self.time_axis, int(step), 3,
                                  _use_sort_kernels())
        new_ts, head, stats = kernel(self.ts, self.mask, vals, valids)
        dllr = stats[4, 0]     # sum of price*volume
        vsum = stats[4, 1]     # sum of volume
        pmax = stats[3, 2]     # max price
        new_cols = {
            "dllr_value": DistCol(dllr, head),
            volume_col: DistCol(vsum, head),
            "max_" + price_col: DistCol(pmax, head),
            "vwap": DistCol(dllr / vsum, head),
        }
        bucket_freq = {"m": "1 minute", "H": "1 hour", "D": "1 day"}[frequency]
        return self._with(ts=new_ts, mask=head, cols=new_cols,
                          resampled=True, seq=None, seq_col="",
                          resample_freq=bucket_freq)

    # ------------------------------------------------------------------
    # interpolate (interpol.py; tsdf.py:778-811)
    # ------------------------------------------------------------------

    def interpolate(self, freq: str = None, func: str = None,
                    method: str = None, target_cols=None,
                    show_interpolated: bool = False) -> "DistributedTSDF":
        """Distributed resample + gap fill.  Aggregates to ``freq``
        buckets (device resample), then generates each series' dense
        bucket grid [min_bucket, max_bucket] and fills missing values
        with ``method`` (zero / null / ffill / bfill / linear) — the
        prev/next scaffolds are two gather-free merge joins of the grid
        against the bucket heads (ops/sortmerge.py), with linear weights
        computed on exact f32 bucket indices.

        The result is a NEW dense frame (series-sharded; a time-sharded
        input is regathered series-local first).  ``show_interpolated``
        adds the reference's ``is_ts_interpolated`` /
        ``is_interpolated_<col>`` flag columns (interpol.py:330-364).
        """
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("interpolate", params=dict(
                freq=freq, func=func, method=method,
                target_cols=tuple(target_cols) if target_cols else None,
                show_interpolated=show_interpolated))
        if method not in ("zero", "null", "ffill", "bfill", "linear"):
            raise ValueError(
                f"Please select from one of the following fill options: "
                f"['zero', 'null', 'bfill', 'ffill', 'linear']: got {method}"
            )
        if self.n_time > 1:
            # the result is a NEW dense series-local frame even on a
            # time-sharded mesh — reshard the inputs once (explicit
            # program, same as the planner's reshard node), no switch
            # back; the linear-fill lerp is FMA-sensitive, so the
            # series-local kernel must be the one program both eager
            # and planned chains run
            return reshard_frame(self, RESHARD_SERIES_LOCAL).interpolate(
                freq=freq, func=func, method=method,
                target_cols=target_cols,
                show_interpolated=show_interpolated)
        if self.resampled:
            freq = freq or self._resample_freq
            if freq != self._resample_freq:
                raise ValueError(
                    f"interpolate freq {freq!r} must match the resample "
                    f"freq {self._resample_freq!r} on a resampled frame"
                )
        if freq is None:
            raise ValueError("interpolate requires freq")
        cols = target_cols or self.numeric_columns()
        if not self.resampled:
            validateFuncExists(func)
        res = self if self.resampled else self.resample(
            freq, func, metricCols=cols
        )
        step = int(freq_to_seconds(freq) * packing.NS_PER_S)

        # static grid bound: bucket span from the host layout when it
        # can vouch for the device ts, else one tiny [K] device fetch
        lay = self.layout
        if lay.n_rows > 0 and int(lay.starts[-1]) == lay.n_rows:
            spans = []
            for k in range(lay.n_series):
                s = lay.ts_ns[lay.starts[k]: lay.starts[k + 1]]
                if len(s):
                    spans.append(int(s[-1] - s[0]))
            span = max(spans, default=0)
        else:
            first = jnp.min(jnp.where(res.mask, res.ts, packing.TS_PAD),
                            axis=1)
            last = jnp.max(jnp.where(res.mask, res.ts, -1), axis=1)
            span = int(np.asarray(jnp.max(
                jnp.where(last >= 0, last - first, 0)
            )))
        G = span // step + 2
        G = max(8, -(-G // 8) * 8)

        mkey = ("zero", "null", "ffill", "bfill", "linear").index(method)
        kernel = _interp_fn(self.mesh, res.series_axis, res.time_axis,
                            step, G, mkey, len(cols),
                            bool(show_interpolated))
        vals = jnp.stack([res.cols[c].values for c in cols])
        valids = jnp.stack([res.cols[c].valid for c in cols])
        out = kernel(res.ts, res.mask, vals, valids)
        grid_ts, grid_mask, out_vals, out_valid = out[:4]
        new_cols = {
            c: DistCol(out_vals[i], out_valid[i]) for i, c in enumerate(cols)
        }
        if show_interpolated:
            ts_interp, col_interp = out[4], out[5]
            new_cols["is_ts_interpolated"] = DistCol(
                ts_interp.astype(vals.dtype), grid_mask, int64=True
            )
            for i, c in enumerate(cols):
                new_cols[f"is_interpolated_{c}"] = DistCol(
                    col_interp[i].astype(vals.dtype), grid_mask, int64=True
                )
        # interpolated frames are dense series-local grids: the time
        # axis (if any) was consumed by the regather inside the kernel,
        # and on a time-sharded mesh the outputs are JOINTLY sharded
        # over ('series', 'time') — record that as the frame's series
        # axis so downstream stages (whose jits now declare explicit
        # in_shardings) see the true layout instead of compiling an
        # implicit reshard against a stale P(series, None) claim
        out_series_axis = ((res.series_axis, res.time_axis)
                           if res.time_axis is not None
                           else res.series_axis)
        return self._with(ts=grid_ts, mask=grid_mask, cols=new_cols,
                          series_axis=out_series_axis,
                          time_axis=None, resampled=True,
                          seq=None, seq_col="", resample_freq=freq)

    # ------------------------------------------------------------------
    # describe (tsdf.py:384-431) / autocorr (tsdf.py:192-316)
    # ------------------------------------------------------------------

    def describe(self) -> pd.DataFrame:
        """Distributed describe: numeric columns reduce device-resident
        (XLA partitions the sharded sums/mins/maxes and inserts the
        cross-shard collectives; only [C, 5] scalars leave the device);
        host-resident columns (strings, huge ints) and the table
        assembly share the host implementation (describe.py)."""
        from tempo_tpu.describe import (
            assemble_table, classify_granularity, col_describe_series,
        )

        names = self.numeric_columns()
        secs = self.ts / packing.NS_PER_S
        vals = (jnp.stack([self.cols[c].values for c in names]) if names
                else jnp.zeros((0,) + self.ts.shape,
                               packing.compute_dtype()))
        valids = (jnp.stack([self.cols[c].valid for c in names]) if names
                  else jnp.zeros((0,) + self.ts.shape, bool))
        r = {k: np.asarray(v) for k, v in _describe_reduce()(
            self.ts, self.mask, secs, vals, valids).items()}

        n = int(r["n_rows"])
        gran = classify_granularity(r["has_frac"], r["sub_min"],
                                    r["sub_hr"], r["sub_day"])
        unique_ts = (len(self.layout.key_frame)
                     if self.partitionCols else 1)
        fmt = lambda x: None if x is None or (isinstance(x, float)
                                              and np.isnan(x)) else str(x)

        def reduced_stats(cnt, s1, s2, mn, mx):
            cnt = int(cnt)
            if cnt == 0:
                return {"count": "0", "mean": None, "stddev": None,
                        "min": None, "max": None}
            mean = s1 / cnt
            var = (s2 - s1 ** 2 / cnt) / max(cnt - 1, 1)
            return {
                "count": str(cnt),
                "mean": fmt(float(mean)),
                "stddev": fmt(float(np.sqrt(max(var, 0.0))))
                if cnt > 1 else None,
                "min": fmt(float(mn)),
                "max": fmt(float(mx)),
            }

        host_names = [c for c in self.host_cols
                      if self._source_df is not None
                      and not self.resampled]
        stat_cols = list(self.partitionCols) + names + host_names \
            + [self.ts_col + "_dbl"]
        stats = {}
        missing = {}
        kf = self.layout.key_frame
        lengths = self.layout.lengths
        for c in self.partitionCols:
            sv = kf[c].dropna().astype(str)
            na_rows = int(lengths[kf[c].isna().to_numpy()].sum()) \
                if len(kf) else 0
            stats[c] = {"count": str(n - na_rows), "mean": None,
                        "stddev": None,
                        "min": fmt(sv.min()) if len(sv) else None,
                        "max": fmt(sv.max()) if len(sv) else None}
            missing[c] = 100.0 * na_rows / max(n, 1)
        for i, c in enumerate(names):
            stats[c] = reduced_stats(r["count"][i], r["sum"][i],
                                     r["sumsq"][i], r["min"][i],
                                     r["max"][i])
            missing[c] = 100.0 * (n - int(r["count"][i])) / max(n, 1)
        for c in host_names:
            s = pd.Series(
                self._source_df[self.host_cols[c]].to_numpy()
                [self.layout.order]
            )
            stats[c] = col_describe_series(s)
            missing[c] = 100.0 * float(s.isna().sum()) / max(n, 1)
        stats[self.ts_col + "_dbl"] = reduced_stats(
            n, r["ts_sum"], r["ts_sumsq"], r["ts_min"], r["ts_max"]
        )
        missing[self.ts_col + "_dbl"] = 0.0

        min_ts = packing.ns_to_original(np.int64(r["min_ts"]),
                                        self._ts_dtype)
        max_ts = packing.ns_to_original(np.int64(r["max_ts"]),
                                        self._ts_dtype)
        if np.issubdtype(np.asarray(min_ts).dtype, np.datetime64):
            min_ts, max_ts = pd.Timestamp(min_ts), pd.Timestamp(max_ts)
        return assemble_table(stat_cols, stats, missing, unique_ts,
                              min_ts, max_ts, gran)

    def autocorr(self, col: str, lag: int = 1) -> pd.DataFrame:
        """Distributed lag-k autocorrelation per series (reference
        tsdf.py:192-316 semantics via the host kernel's pair rule).
        Returns a bare DataFrame (host parity); only [K] scalars leave
        the device.  Bucket-head views (resampled frames) compact their
        scattered valid rows with one stable lane sort first, so the
        physical lag pairing sees consecutive observations."""
        dcol = self.cols[col]
        if self.n_time > 1:
            # positions must be series-contiguous for the lag pairing
            fwd = _to_series_local_fn(self.mesh, self.series_axis,
                                      self.time_axis, 3)
            v, ok, mask = fwd(dcol.values, dcol.valid, self.mask)
        else:
            v, ok, mask = dcol.values, dcol.valid, self.mask
        ac, cnt, lengths = _autocorr_fn(int(lag), bool(self.resampled))(
            v, ok, mask
        )
        K = self.layout.n_series
        ac_h = _to_host(ac).astype(np.float64)[:K]
        cnt_h = _to_host(cnt)[:K]
        len_h = _to_host(lengths)[:K]
        # a series only yields a row when the numerator join is non-empty
        # (reference tsdf.py:248-253 inner joins drop pairless series)
        present = (len_h > lag) & (cnt_h > lag)
        out = self.layout.key_frame.copy()
        if not self.partitionCols:
            out = pd.DataFrame({"_dummy_group_col": ["dummy"]})
        out[f"autocorr_lag_{lag}"] = ac_h
        return out[present].reset_index(drop=True)

    def fourier_transform(self, timestep: float, valueCol: str):
        """Fourier transform, device-resident (round 4; the reference
        ships every group's rows to Python workers over Arrow —
        applyInPandas, tsdf.py:865-899 — and earlier rounds mirrored
        that with a collect()).  Each series' exact n-point DFT runs as
        one batched Bluestein program at the frame's lane width
        (ops/fft.py:bluestein_dft; time-sharded meshes switch to
        series-local rows around it), and ``freq`` is the fftfreq grid
        of each series' true length.  Output column surface matches the
        host path: partition/ts/[seq] + value + freq/ft_real/ft_imag
        (spectral.py:104-112).

        Bucket-head (resampled) views keep the host fallback — their
        real rows are not front-packed, which the batched DFT
        requires."""
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("fourier", params=dict(
                timestep=timestep, valueCol=valueCol))
        matches = [c for c in self.cols if c.lower() == valueCol.lower()
                   and self.cols[c].ts_chunk is None
                   and self.cols[c].host_gather is None]
        if self.resampled or not matches:
            # bucket-head views (rows not front-packed) and columns
            # without a plain device plane (host-resident ints/strings,
            # join-produced gather/ts-chunk columns) keep the
            # collect-based path — spectral.py resolves any frame
            # column, including raising the reference's error for a
            # truly absent one
            logger.warning(
                "fourier_transform(%r): materialization barrier — the "
                "mesh chain silently collects to host here (%s) and "
                "re-packs afterwards; under TEMPO_TPU_PLAN=1 explain() "
                "marks this barrier in the plan", valueCol,
                "bucket-head (resampled) view" if self.resampled
                else "no plain device plane for the column")
            with plan.suspended():
                host = self.collect().fourier_transform(timestep, valueCol)
                s_ax, t_ax = self.series_axis, self.time_axis
                if isinstance(s_ax, tuple):
                    # joint series-LOCAL frames (reshard_frame /
                    # interpolate output) re-pack onto the plain series
                    # axis: from_tsdf packs fresh from the host, so
                    # there is no layout to preserve — and it cannot
                    # look a tuple axis up in mesh.shape
                    s_ax, t_ax = s_ax[0], None
                return host.on_mesh(self.mesh, series_axis=s_ax,
                                    time_axis=t_ax)
        if self.n_time > 1:
            # explicit reshard sandwich (see withRangeStats): the
            # Bluestein DFT's accumulations must run the same
            # series-local program eager and planned
            local = reshard_frame(self, RESHARD_SERIES_LOCAL)
            out = local.fourier_transform(timestep, valueCol)
            return reshard_frame(out, RESHARD_TIME_SHARDED)
        vc = matches[0]
        col = self.cols[vc]
        freq, ftr, fti = _fourier_fn(self.mesh, self.series_axis,
                                     self.time_axis, float(timestep))(
            col.values, self.mask
        )
        new_cols = {
            vc: col,
            "freq": DistCol(freq, self.mask),
            "ft_real": DistCol(ftr, self.mask),
            "ft_imag": DistCol(fti, self.mask),
        }
        keep_host = {c: src for c, src in self.host_cols.items()
                     if c == self.seq_col}
        return self._with(cols=new_cols, host_cols=keep_host)

    def withLookbackFeatures(self, featureCols, lookbackWindowSize: int,
                             exactSize: bool = True,
                             featureColName: str = "features"):
        """Lookback feature tensors via the host frame path.  The
        reference materialises these as array-of-array columns through
        a shuffle (collect_list, tsdf.py:637-671) — inherently a
        row-materialisation op — so the distributed form collects once
        and runs the device shifted-stack path; the dense device-side
        form is :meth:`lookback_tensor`."""
        from tempo_tpu import plan

        if plan.recording():
            return self._plan_record("lookback_features", params=dict(
                featureCols=tuple(featureCols),
                lookbackWindowSize=lookbackWindowSize,
                exactSize=exactSize, featureColName=featureColName))
        logger.warning(
            "withLookbackFeatures: materialization barrier — the mesh "
            "chain silently collects to host here (collect_list "
            "semantics materialise rows); use lookback_tensor for the "
            "device-resident dense form, or TEMPO_TPU_PLAN=1 explain() "
            "to see the barrier in the plan")
        with plan.suspended():
            return self.collect().withLookbackFeatures(
                featureCols, lookbackWindowSize, exactSize, featureColName
            )

    def lookback_tensor(self, featureCols, lookbackWindowSize: int):
        """Dense ``([K, L, w, F] values, [K, L, w, F] validity)``
        lookback tensor as DEVICE arrays, series-sharded — the
        TPU-native model-feeding form of ``withLookbackFeatures``
        (round 4; host analog ``tempo_tpu.rolling.lookback_tensor``),
        with no object-array materialisation and no host round trip.
        Window axis is oldest-first (row t's slot j holds observation
        t - w + j), zero-padded with the mask False where no
        observation exists.  On a time-sharded mesh the rows switch to
        a series-local layout first (the shifts cross shard
        boundaries), so the result is sharded over all devices along
        the series axis.

        Plain numeric device columns only (join-index/ts-chunk planes
        hold row positions, not values), and not on bucket-head
        (resampled) views — their real rows are interspersed with
        masked lanes, so a physical-slot window would not be the w
        previous observations; collect() + ``withLookbackFeatures``
        compacts first."""
        if self.resampled:
            raise ValueError(
                "lookback_tensor on a resampled (bucket-head) view "
                "would window over physical lane slots, not the "
                "previous w buckets; collect() and use "
                "withLookbackFeatures (which compacts rows first)"
            )
        cols = list(featureCols)
        eligible = set(self.numeric_columns())
        bad = [c for c in cols if c not in eligible]
        if bad:
            raise ValueError(
                f"lookback_tensor needs plain numeric device columns; "
                f"{bad} are missing or host/join-resident "
                f"(available: {sorted(eligible)})"
            )
        vals = jnp.stack([self.cols[c].values for c in cols])
        valids = jnp.stack([self.cols[c].valid for c in cols])
        return _lookback_tensor_fn(
            self.mesh, self.series_axis, self.time_axis,
            int(lookbackWindowSize), len(cols)
        )(vals, valids)

    # ------------------------------------------------------------------
    # Materialisation
    # ------------------------------------------------------------------

    def collect(self):  # plan-ok: eager-only
        """ONE stacked device->host transfer -> host TSDF."""
        global _FETCH_EVENTS
        from tempo_tpu.frame import TSDF

        names = list(self.cols)
        # single stacked fetch: float cols as one [C, K, L] f64 block
        if names:
            stacked = _to_host(
                jnp.stack([self.cols[c].values.astype(jnp.float64)
                           for c in names]
                          + [self.cols[c].valid.astype(jnp.float64)
                             for c in names])
            )
            val_block = stacked[: len(names)]
            ok_block = stacked[len(names):] > 0.5
        ts_h = _to_host(self.ts)
        mask_h = _to_host(self.mask)
        _FETCH_EVENTS += 1

        for msg, count in self.audits:
            n = int(_to_host(count))
            if n > 0:
                logger.warning(msg, n) if "%d" in msg else logger.warning(msg)
        K = self.layout.n_series
        mask_h = mask_h[:K]
        ts_h = ts_h[:K]

        lengths = mask_h.sum(axis=1).astype(np.int64)
        key_ids = np.repeat(np.arange(K, dtype=np.int64), lengths)
        flat = lambda a: a[:K][mask_h]

        out = {}
        kf = self.layout.key_frame
        for c in self.partitionCols:
            out[c] = kf[c].to_numpy()[key_ids]
        out[self.ts_col] = packing.ns_to_original(flat(ts_h), self._ts_dtype)
        ts_parts: Dict[str, dict] = {}
        for i, c in enumerate(names):
            col = self.cols[c]
            v = flat(val_block[i])
            okv = flat(ok_block[i])
            if col.ts_chunk is not None:
                target, shift = col.ts_chunk
                part = ts_parts.setdefault(target, {"ns": 0, "ok": okv})
                part["ns"] = part["ns"] + (
                    np.round(np.where(okv, v, 0.0)).astype(np.int64) << shift
                )
            elif col.host_gather is not None:
                flat_vals, r_starts, perm = col.host_gather
                ridx = np.round(np.where(okv, v, 0.0)).astype(np.int64)
                pos = r_starts[perm[key_ids]] + ridx
                pos = np.clip(pos, 0, max(len(flat_vals) - 1, 0))
                if len(flat_vals) and np.issubdtype(flat_vals.dtype,
                                                    np.integer):
                    # integer host col (e.g. a joined sequence column):
                    # keep int exactness — values near 2^63 must not
                    # round through float64; unmatched rows are NA
                    # (Spark nullable int join output)
                    g = flat_vals[pos].astype(np.int64)
                    arr = pd.array(g, dtype="Int64")
                    arr[~okv] = pd.NA
                    out[c] = arr
                    continue
                if len(flat_vals) and np.issubdtype(flat_vals.dtype,
                                                    np.number):
                    out[c] = np.where(okv,
                                      flat_vals[pos].astype(np.float64),
                                      np.nan)
                    continue
                gathered = (flat_vals[pos] if len(flat_vals)
                            else np.full(len(pos), None, object))
                res = np.empty(len(pos), dtype=object)
                res[:] = gathered
                res[~okv] = None
                out[c] = res
            elif col.int64:
                out[c] = np.where(okv, v, 0).astype(np.int64)
            else:
                out[c] = np.where(okv, v, np.nan)
        for target, part in ts_parts.items():
            tsv = packing.ns_to_original(part["ns"], self._ts_dtype)
            if np.issubdtype(np.asarray(tsv).dtype, np.datetime64):
                tsv = np.where(part["ok"], tsv, np.datetime64("NaT"))
            out[target] = tsv
        if not self.resampled:
            # host-resident (non-numeric) columns rejoin by row identity
            for c, src in self.host_cols.items():
                out[c] = self._source_df[src].to_numpy()[self.layout.order]
        return TSDF(pd.DataFrame(out), self.ts_col, self.partitionCols)

    def to_pandas(self) -> pd.DataFrame:
        return self.collect().df

    def count(self) -> int:
        return int(np.asarray(jnp.sum(self.mask)))

    def show(self, n: int = 20, truncate: bool = True) -> None:
        """Materialise and display (host TSDF.show semantics)."""
        self.collect().show(n, truncate)

    def __repr__(self) -> str:
        axes = dict(self.mesh.shape)
        return (
            f"DistributedTSDF(mesh={axes}, series={self.layout.n_series}, "
            f"packed=[{self.K_dev}, {self.L}], "
            f"cols={self.numeric_columns()}, host_cols={list(self.host_cols)}, "
            f"ts_col={self.ts_col!r}, partition_cols={self.partitionCols})"
        )


def _mesh_packed_geometry(layout, mesh, series_axis: str,
                          time_axis: Optional[str]):
    """``(K_dev, L, n_series_shards, n_time)`` — the packed geometry
    :meth:`DistributedTSDF.from_tsdf` will realise for this layout on
    this mesh.  The series dim is a multiple of every mesh axis so
    layout-switching collectives (the all_to_all resample path) stay
    legal.  Shared with the plan optimizer's engine hoist, which must
    reason about shard shapes BEFORE the frame is packed."""
    n_s = mesh.shape[series_axis]
    n_t = mesh.shape[time_axis] if time_axis else 1
    k_mult = n_s * n_t
    K_dev = max(1, -(-layout.n_series // k_mult)) * k_mult
    L = packing.pad_length(int(layout.lengths.max(initial=0)),
                           multiple=8 * n_t)
    return K_dev, L, n_s, n_t


def _pick_range_engine_for_shard(shard_k: int, L: int, rb):
    """The shifted/stream/windowed pick for one shard shape + static
    row bounds (None = unboundable -> the data-independent windowed
    form).  One function so the realized-frame pick
    (:meth:`DistributedTSDF._range_engine_choice`) and the pre-packing
    plan-time pick (:func:`plan_range_engine_choice`) can never
    diverge — a hoisted hint that disagreed with the run-time pick
    would silently change which kernel (and which float rounding) a
    planned chain runs.  The lane-chunked form is not a candidate here
    (``chunked_ok`` stays False): it is driven from the host in calls
    of one shape, while a mesh shard's stats run inside one device
    program over its planes."""
    from tempo_tpu.ops import pallas_stats as _ps
    from tempo_tpu.ops import pallas_window as _pw

    f32 = packing.compute_dtype() == np.float32
    pallas_ok = f32 and _ps.pallas_block_feasible(max(shard_k, 1), L)
    stream_ok = f32 and _pw.stream_block_feasible(max(shard_k, 1), L)
    engine = "shifted"
    rowbounds = None
    if rb is not None:
        engine = rk.pick_range_engine(max(shard_k, 1) * L, rb[0], rb[1],
                                      pallas_ok, stream_ok)
        if engine != "windowed":
            rowbounds = rb
    return engine, rowbounds


def plan_range_engine_choice(layout, mesh, series_axis: str,
                             time_axis: Optional[str],
                             window_secs: float):
    """``(engine, rowbounds, sort_kernels)`` a frame packed from
    ``layout`` onto ``mesh`` will choose in
    :meth:`DistributedTSDF._range_engine_choice` — computed WITHOUT
    packing, for the plan optimizer's plan-time hoist."""
    sort_kernels = _use_sort_kernels()
    if not sort_kernels:
        return "shifted", None, sort_kernels
    K_dev, L, n_s, n_t = _mesh_packed_geometry(layout, mesh,
                                               series_axis, time_axis)
    rb = (packing.layout_rowbounds(layout, window_secs)
          if layout.n_rows > 0 and int(layout.starts[-1]) == layout.n_rows
          else None)
    shard_k = K_dev // (n_s * max(n_t, 1))
    engine, rowbounds = _pick_range_engine_for_shard(shard_k, L, rb)
    return engine, rowbounds, sort_kernels


def _put_global(sharding):
    """Host->device placement that works across processes.  Ingest is
    replicated-host (every process packed the same frame, the standard
    multi-controller SPMD pattern), so each device's shard is a slice
    of the local array — ``make_array_from_callback`` places exactly
    those slices.  Multi-process ``device_put`` would work too but
    value-checks the array across processes with an equality that
    fails on NaN payloads (jax multihost_utils.assert_equal; NaN !=
    NaN), which every packed value plane contains."""
    if jax.process_count() > 1:
        def put(arr):
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx]
            )

        return put
    return lambda arr: jax.device_put(arr, sharding)


def _to_host(arr) -> np.ndarray:
    """Device->host fetch that also works across processes: a
    multi-controller frame's arrays are not fully addressable (each
    host owns its mesh slice), so ``np.asarray`` would raise —
    ``process_allgather`` rebuilds the global value on every host
    instead (DCN), which is exactly collect()'s dense contract.
    Single-process arrays take the plain fetch."""
    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr,
                                                            tiled=True))
    return np.asarray(arr)


def _pad_k(arr: np.ndarray, K_dev: int, fill) -> np.ndarray:
    K = arr.shape[0]
    if K == K_dev:
        return arr
    pad = np.full((K_dev - K,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _canon_func(func: str) -> str:
    from tempo_tpu.freq import CLOSEST_LEAD, MEAN_LEAD, MIN_LEAD, MAX_LEAD

    return {CLOSEST_LEAD: floor, MEAN_LEAD: average, MIN_LEAD: min_func,
            MAX_LEAD: max_func}.get(func, func)


def ts_chunk_planes(ts, dt):
    """An int64 ns timestamp plane as three planes of ``dt`` that hold
    it exactly: bits 63..42 (arithmetic shift, so pre-epoch timestamps
    keep their sign), 41..21 and 20..0.  ``collect`` adds them back."""
    mask = jnp.int64((1 << 21) - 1)
    return [(ts >> 42).astype(dt), ((ts >> 21) & mask).astype(dt),
            (ts & mask).astype(dt)]


def _key_perm(left_kf: pd.DataFrame, right_kf: pd.DataFrame,
              pcols: List[str], K_dev: int):
    """For each left series id, the right series id with the same
    partition-key tuple (-1 when absent).  Host numpy (K-sized metadata
    consumed both by the jitted align fns and collect-time gathers)."""
    if not pcols:
        perm = np.zeros(K_dev, np.int32)
        ok = np.zeros(K_dev, bool)
        ok[0] = len(right_kf.index) > 0
        return perm, ok
    rk_idx = right_kf.reset_index().rename(columns={"index": "__rid__"})
    merged = left_kf.merge(rk_idx, on=pcols, how="left")
    rid = merged["__rid__"].to_numpy()
    ok = ~pd.isna(rid)
    perm = np.where(ok, rid, 0).astype(np.int32)
    perm = np.concatenate([perm, np.zeros(K_dev - len(perm), np.int32)])
    okp = np.concatenate([ok, np.zeros(K_dev - len(ok), bool)])
    return perm, okp


# ----------------------------------------------------------------------
# Cached shard_map program builders (compile once per mesh/params/shape)
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _range_stats_halo(mesh, series_axis, time_axis, window_secs, halo):
    def fn(ts, x, valid):
        secs = ts // packing.NS_PER_S
        return ph.range_stats_time_sharded(
            mesh, secs, x, valid, window_secs, halo,
            time_axis=time_axis, series_axis=series_axis,
        )

    return fn


def _range_stats_block_packed(ts, xs, valids, w, rowbounds,
                              engine="shifted"):
    """Shard-local range stats over a multi-column stack:
    ``xs``/``valids`` are [C, K, L] planes sharing the shard's
    timestamp plane, reduced with the key planes read ONCE per kernel
    pack instead of once per column
    (ops/rolling.range_stats_streaming_packed /
    sortmerge.range_stats_shifted_packed); shifted gather-free form
    when static row bounds are known (TPU), the streaming VMEM sweep
    for wider bounded frames (``engine="stream"``), else bounds +
    prefix/RMQ form.  Per-column results are bitwise-identical to C
    single-column calls — the packed kernels trace the identical
    per-column op sequence and the fallbacks ARE the single-column
    paths — which is what keeps the eager chain, the planner replay,
    and the fused single program (plan/fused.py) in exact agreement.
    Returns (stats dict of [C, ...] planes, clipped [C] int64) —
    clipped is the window kernels' truncation audit (zero by
    construction for the exact form)."""
    from tempo_tpu.ops import sortmerge as sm

    C = xs.shape[0]
    secs = ts // packing.NS_PER_S
    if rowbounds is not None:
        behind, ahead = rowbounds
        # per-series int32 rebase for the VMEM kernel.  _window_rowbounds
        # guarantees span + window < 2^31 host-side, so the window casts
        # exactly (no narrowing clamp — one would silently shrink
        # frames) and the INT32_MAX pad clamp keeps >= window of
        # headroom above every real key (the truncation audit's
        # pad-immunity condition)
        rb = jnp.minimum(secs - secs[:, :1], 2**31 - 1).astype(jnp.int32)
        w32 = jnp.asarray(w).astype(jnp.int32)
        if engine == "stream":
            stats = rk.range_stats_streaming_packed(
                rb, xs, valids, w32,
                max_behind=int(behind), max_ahead=int(ahead))
        else:
            stats = sm.range_stats_shifted_packed(
                rb, xs, valids, w32,
                max_behind=int(behind), max_ahead=int(ahead))
        clipped = jnp.sum(stats.pop("clipped"),
                          axis=(1, 2)).astype(jnp.int64)
        return stats, clipped
    # window operand: over integer seconds ANY width folds to an exact
    # integer compare (rk.range_window_width) — the bare jnp.asarray(w)
    # this replaces minted weak-f64 bound arithmetic under the f32
    # policy (caught by the compiled no-f64-leak contract,
    # tools/analyze.py --compiled)
    start, end = rk.range_window_bounds(secs,
                                        rk.range_window_width(secs, w))
    per = [rk.windowed_stats(xs[c], valids[c], start, end)
           for c in range(C)]
    stats = {k: jnp.stack([p[k] for p in per]) for k in per[0]}
    return stats, jnp.zeros((C,), jnp.int64)


@functools.lru_cache(maxsize=256)
def _range_stats_local_packed(mesh, series_axis, window_secs,
                              rowbounds=None, sort_kernels=False,
                              engine="shifted"):
    """Series-sharded range stats over the whole column stack: ONE
    shard_map program computes every summarized column ([C, K, L]
    stacks) — C-1 fewer dispatches and the timestamp planes stream
    once.  Replaces the former per-column ``_range_stats_local`` (a
    width-1 stack reproduces it exactly)."""
    sp = _spec(mesh, series_axis, None)
    sp3 = _spec(mesh, series_axis, None, ndim=3)
    w = window_secs

    def kernel(ts, xs, valids):
        stats, clipped = _range_stats_block_packed(ts, xs, valids, w,
                                                   rowbounds, engine)
        return stats, jax.lax.psum(clipped, series_axis)

    stats_spec = {k: sp3 for k in packing.RANGE_STATS}
    # the [C, K, L] value stack is a fresh jnp.stack at every call site
    # (withRangeStats packs frame columns per call) and each f32 stats
    # plane matches its shape/dtype — donate it so the packed stats
    # reuse the stack's HBM instead of doubling the stage's working
    # set.  The bool validity stack has no bool-shaped output and the
    # ts plane is frame-owned: neither is donatable.
    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(sp, sp3, sp3),
                             out_specs=(stats_spec, P())),
                   in_shardings=(_ns(mesh, sp), _ns(mesh, sp3),
                                 _ns(mesh, sp3)),
                   out_shardings=({k: _ns(mesh, sp3)
                                   for k in packing.RANGE_STATS},
                                  _ns(mesh, P())),
                   donate_argnums=(1,))


@functools.lru_cache(maxsize=256)
def _ema_local(mesh, series_axis, alpha, exact, window):
    sp = _spec(mesh, series_axis, None)

    def kernel(x, valid):
        if exact:
            from tempo_tpu.ops import pallas_kernels as pk

            return pk.ema_scan(x, valid, alpha)
        return rk.ema_compat(x, valid, window, alpha)

    # no donation: the EMA's value operand is the frame-OWNED column
    # plane (the result frame shares it via ``_with``), unlike the
    # join/stats stages whose operands are per-call stacks
    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(sp, sp),
                             out_specs=sp),
                   in_shardings=(_ns(mesh, sp), _ns(mesh, sp)),
                   out_shardings=_ns(mesh, sp))


def _compact_right_lanes(r_ts, r_mask, vstack, pstack):
    """Stable per-row sort pushing non-existent (masked-out) right rows
    to the lane tail as TS_PAD, restoring the ascending packed
    invariant that bucket-head (resample) views lack.  Needed only when
    maxLookback counts merged-stream rows: a masked lane row with a
    real-looking ts would consume a window slot Spark's stream never
    contains.  One multi-operand lax.sort carrying every plane."""
    nv = int(vstack.shape[0])
    key = jnp.where(r_mask, r_ts, packing.TS_PAD)
    ops = jax.lax.sort(
        (key,) + tuple(vstack[i] for i in range(nv))
        + tuple(pstack[i] for i in range(int(pstack.shape[0]))),
        dimension=-1, num_keys=1, is_stable=True,
    )
    return ops[0], jnp.stack(ops[1: 1 + nv]), jnp.stack(ops[1 + nv:])


def _compact_left_rows(l_ts, l_mask):
    """Stable sort pushing masked-out LEFT rows to the lane tail as
    TS_PAD (they would otherwise consume maxLookback merged-stream
    window slots Spark's stream never contains — the left-side mirror
    of ``_compact_right_lanes``).  Returns the compacted keys and the
    original-lane plane whose inverse routes outputs back."""
    K, L = l_ts.shape
    iota = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (K, L))
    key = jnp.where(l_mask, l_ts, packing.TS_PAD)
    return jax.lax.sort((key, iota), dimension=-1, num_keys=1,
                        is_stable=True)


def _uncompact_left(src, vals, found):
    """Route [C, K, L] join outputs back to the original left lanes:
    sorting on the carried source-lane plane inverts the compaction
    permutation."""
    C = int(vals.shape[0])
    ops = (src,) + tuple(vals[c] for c in range(C)) \
        + tuple(found[c] for c in range(C))
    routed = jax.lax.sort(ops, dimension=-1, num_keys=1, is_stable=True)
    vals2 = jnp.stack(routed[1:1 + C]) if C else vals
    found2 = jnp.stack(routed[1 + C:]) if C else found
    return vals2, found2


def _asof_planes(l_ts, r_ts, r_valids, r_values, sort_kernels,
                 max_lookback=0):
    """Per-plane AS-OF fill: on TPU the sort-and-scan join (no gathers,
    ops/sortmerge.py timings); elsewhere searchsorted + index gathers.
    ``max_lookback`` > 0 caps the merged-stream fill (Scala
    asofJoin.scala:64-88)."""
    from tempo_tpu.ops import sortmerge as sm

    if sort_kernels:
        vals, found, _ = sm.asof_merge_values(
            l_ts, r_ts, r_valids, r_values, max_lookback=max_lookback
        )
        return vals, found
    if max_lookback:
        _, col_idx = asof_ops.asof_indices_merge(
            l_ts, None, r_ts, None, r_valids,
            n_cols=int(r_values.shape[0]), max_lookback=int(max_lookback),
        )
    else:
        _, col_idx = asof_ops.asof_indices_searchsorted(
            l_ts, r_ts, r_valids, n_cols=int(r_values.shape[0])
        )
    found = col_idx >= 0
    vals = jnp.take_along_axis(r_values, jnp.maximum(col_idx, 0), axis=-1)
    return jnp.where(found, vals, jnp.nan), found


@functools.lru_cache(maxsize=256)
def _asof_local(mesh, series_axis, sort_kernels=False, max_lookback=0,
                compact=False, compact_left=False, donate=True):
    sp2 = _spec(mesh, series_axis, None)
    sp3 = _spec(mesh, series_axis, None, ndim=3)

    def kernel(l_ts, l_mask, r_ts, r_mask, r_valids, r_values):
        if compact:
            r_ts, r_valids, r_values = _compact_right_lanes(
                r_ts, r_mask, r_valids, r_values
            )
        if compact_left:
            l_ts, src = _compact_left_rows(l_ts, l_mask)
        vals, found = _asof_planes(l_ts, r_ts, r_valids, r_values,
                                   sort_kernels, max_lookback)
        if compact_left:
            vals, found = _uncompact_left(src, vals, found)
        return vals, found

    # whole-chain donation: the aligned validity/plane stacks are
    # per-call temporaries (built by asofJoin, already donated once
    # through _align3_fn) whose shapes/dtypes exactly match the
    # ``found``/``vals`` outputs — each join stage reuses its consumed
    # stage-N-1 buffers instead of doubling the chain's working set
    # (verified compiled-side by the donation-applied contract rule).
    # ``donate=False`` when the left/right lane widths differ: the
    # outputs are left-width [P, K, Ll] and XLA could never alias a
    # [P, K, Lr] stack onto them (it would warn and keep both live).
    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(sp2, sp2, sp2, sp2, sp3, sp3),
                             out_specs=(sp3, sp3)),
                   in_shardings=(_ns(mesh, sp2),) * 4
                   + (_ns(mesh, sp3),) * 2,
                   out_shardings=(_ns(mesh, sp3), _ns(mesh, sp3)),
                   donate_argnums=(4, 5) if donate else ())


@functools.lru_cache(maxsize=256)
def _asof_local_seq(mesh, series_axis, max_lookback=0,
                    compact_left=False, donate=True):
    """AS-OF with sequence tie-break: the merge join is the only exact
    form (reference union-sort semantics, tsdf.py:117-121), so it runs
    on every backend.  (A resampled RIGHT frame never has a sequence
    column — resample drops it — so only the left compaction exists
    here.)"""
    from tempo_tpu.ops import sortmerge as sm

    sp2 = _spec(mesh, series_axis, None)
    sp3 = _spec(mesh, series_axis, None, ndim=3)

    def kernel(l_ts, l_mask, r_ts, r_seq, r_valids, r_values):
        if compact_left:
            l_ts, src = _compact_left_rows(l_ts, l_mask)
        vals, found, _ = sm.asof_merge_values(
            l_ts, r_ts, r_valids, r_values, r_seq=r_seq,
            max_lookback=max_lookback,
        )
        if compact_left:
            vals, found = _uncompact_left(src, vals, found)
        return vals, found

    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(sp2, sp2, sp2, sp2, sp3, sp3),
                             out_specs=(sp3, sp3)),
                   in_shardings=(_ns(mesh, sp2),) * 4
                   + (_ns(mesh, sp3),) * 2,
                   out_shardings=(_ns(mesh, sp3), _ns(mesh, sp3)),
                   donate_argnums=(4, 5) if donate else ())


@functools.lru_cache(maxsize=256)
def _asof_a2a_seq(mesh, series_axis, time_axis, max_lookback=0,
                  compact_left=False, donate=True):
    from tempo_tpu.ops import sortmerge as sm

    sp2 = _spec(mesh, series_axis, time_axis)
    sp3 = _spec(mesh, series_axis, time_axis, 3)

    def kernel(l_ts, l_mask, r_ts, r_seq, r_valids, r_values):
        fwd = lambda a: jax.lax.all_to_all(
            a, time_axis, split_axis=a.ndim - 2, concat_axis=a.ndim - 1,
            tiled=True)
        rev = lambda a: jax.lax.all_to_all(
            a, time_axis, split_axis=a.ndim - 1, concat_axis=a.ndim - 2,
            tiled=True)
        l_full = fwd(l_ts)
        if compact_left:
            l_full, src = _compact_left_rows(l_full, fwd(l_mask))
        vals, found, _ = sm.asof_merge_values(
            l_full, fwd(r_ts), fwd(r_valids), fwd(r_values),
            r_seq=fwd(r_seq), max_lookback=max_lookback,
        )
        if compact_left:
            vals, found = _uncompact_left(src, vals, found)
        return rev(vals), rev(found)

    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(sp2, sp2, sp2, sp2, sp3, sp3),
                             out_specs=(sp3, sp3)),
                   in_shardings=(_ns(mesh, sp2),) * 4
                   + (_ns(mesh, sp3),) * 2,
                   out_shardings=(_ns(mesh, sp3), _ns(mesh, sp3)),
                   donate_argnums=(4, 5) if donate else ())


@functools.lru_cache(maxsize=256)
def _asof_a2a(mesh, series_axis, time_axis, sort_kernels=False,
              max_lookback=0, compact=False, compact_left=False,
              donate=True):
    """Exact AS-OF join on a time-sharded mesh: switch both sides to a
    series-local layout (full rows per device, one ``all_to_all`` per
    array), join locally, switch the [n_cols, K, Ll] results back."""
    sp2 = _spec(mesh, series_axis, time_axis)
    sp3 = _spec(mesh, series_axis, time_axis, 3)

    def kernel(l_ts, l_mask, r_ts, r_mask, r_valids, r_values):
        fwd = lambda a: jax.lax.all_to_all(
            a, time_axis, split_axis=a.ndim - 2, concat_axis=a.ndim - 1,
            tiled=True)
        rev = lambda a: jax.lax.all_to_all(
            a, time_axis, split_axis=a.ndim - 1, concat_axis=a.ndim - 2,
            tiled=True)
        l_full, r_full = fwd(l_ts), fwd(r_ts)
        rv_full, rx_full = fwd(r_valids), fwd(r_values)
        if compact:
            r_full, rv_full, rx_full = _compact_right_lanes(
                r_full, fwd(r_mask), rv_full, rx_full
            )
        if compact_left:
            l_full, src = _compact_left_rows(l_full, fwd(l_mask))
        vals, found = _asof_planes(l_full, r_full, rv_full, rx_full,
                                   sort_kernels, max_lookback)
        if compact_left:
            vals, found = _uncompact_left(src, vals, found)
        return rev(vals), rev(found)

    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(sp2, sp2, sp2, sp2, sp3, sp3),
                             out_specs=(sp3, sp3)),
                   in_shardings=(_ns(mesh, sp2),) * 4
                   + (_ns(mesh, sp3),) * 2,
                   out_shardings=(_ns(mesh, sp3), _ns(mesh, sp3)),
                   donate_argnums=(4, 5) if donate else ())


@functools.lru_cache(maxsize=256)
def _align_fn(mesh, series_axis, time_axis):
    """Gather a right-frame [K_r, L] array into the left key order along
    the sharded series axis (XLA plans the cross-device movement)."""
    sharding = NamedSharding(mesh, _spec(mesh, series_axis, time_axis))

    def fn(arr, perm, ok, fill):
        g = jnp.take(arr, jnp.clip(perm, 0, arr.shape[0] - 1), axis=0)
        return jnp.where(ok[:, None], g, jnp.asarray(fill, arr.dtype))

    return jax.jit(fn, out_shardings=sharding, static_argnums=(3,))


@functools.lru_cache(maxsize=256)
def _align3_fn(mesh, series_axis, time_axis, donate=False):
    """``donate=True`` (caller asserts the left/right packed K match,
    so input and output shapes are equal) donates the plane stack: the
    aligned copy reuses the pre-alignment stack's HBM instead of
    doubling the join's biggest transient.  The donation-applied
    compiled contract (plan/contracts.py) verifies the input-output
    alias on the compiled executable."""
    sharding = NamedSharding(mesh, _spec(mesh, series_axis, time_axis, 3))

    def fn(arr, perm, ok, fill):
        g = jnp.take(arr, jnp.clip(perm, 0, arr.shape[1] - 1), axis=1)
        return jnp.where(ok[None, :, None], g, jnp.asarray(fill, arr.dtype))

    return jax.jit(fn, out_shardings=sharding, static_argnums=(3,),
                   donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=256)
def _to_series_local_fn(mesh, series_axis, time_axis, n_arrays):
    """[K, L] arrays -> series-local full rows (each device owns
    K/(ns*nt) whole series), via one all_to_all per array.  Keyed on
    arity so the jitted callable is built (and compiled) once."""
    sp_in = _spec(mesh, series_axis, time_axis)
    sp_out = P((series_axis, time_axis), None)

    def kernel(*arrays):
        a2a = lambda a: jax.lax.all_to_all(
            a, time_axis, split_axis=a.ndim - 2, concat_axis=a.ndim - 1,
            tiled=True)
        return tuple(a2a(a) for a in arrays)

    return jax.jit(shard_map(
        kernel, mesh=mesh, in_specs=(sp_in,) * n_arrays,
        out_specs=(sp_out,) * n_arrays,
    ))


# ----------------------------------------------------------------------
# Plan-placed resharding: the executor of the planner's first-class
# ``reshard`` IR node (tempo_tpu/plan/optimizer.py)
# ----------------------------------------------------------------------

#: targets of :func:`reshard_frame`: ``series_local`` re-lays a
#: time-sharded frame so every device owns whole series (K sharded
#: jointly over ('series', 'time'), rows unsplit) — the layout every
#: per-series kernel wants; ``time_sharded`` is the inverse.
RESHARD_SERIES_LOCAL = "series_local"
RESHARD_TIME_SHARDED = "time_sharded"


def reshard_frame(d: "DistributedTSDF", target: str) -> "DistributedTSDF":
    """Explicit whole-frame layout switch — ONE jitted shard_map
    program moving every device plane with ``lax.all_to_all`` (the
    reshard.py collectives, fused across the frame's planes), instead
    of each downstream op paying its own per-op all_to_all pair.  The
    global logical [K, L] arrays are bit-identical before and after
    (the collective moves bytes, computes nothing), which is what lets
    the plan optimizer place/eliminate these nodes without breaking
    the planned==eager bitwise contract.  A no-op when the frame is
    already in the target layout.

    Deliberately WHOLE-frame: untouched columns cross the wire too.
    A partial relayout (move only the consulted planes) would leave
    the frame mixed-layout, breaking the uniform-sharding invariant
    every stage's explicit ``in_shardings`` now declares; the
    planner's dead-column pruning is the sanctioned way to shrink the
    moved set (it drops dead columns BEFORE packing, so they never
    reach the reshard)."""
    if target == RESHARD_SERIES_LOCAL:
        if d.time_axis is None:
            return d
        s_ax, t_ax = d.series_axis, d.time_axis
        new_series, new_time = (s_ax, t_ax), None
    elif target == RESHARD_TIME_SHARDED:
        if d.time_axis is not None or not (
                isinstance(d.series_axis, tuple)
                and len(d.series_axis) == 2):
            return d
        s_ax, t_ax = d.series_axis
        new_series, new_time = s_ax, t_ax
    else:
        raise ValueError(f"unknown reshard target {target!r}")
    names = list(d.cols)
    fn = _relayout_fn(d.mesh, s_ax, t_ax,
                      forward=(target == RESHARD_SERIES_LOCAL),
                      with_cols=bool(names), has_seq=d.seq is not None)
    ops = [d.ts, d.mask]
    if names:
        ops.append(jnp.stack([d.cols[c].values for c in names]))
        ops.append(jnp.stack([d.cols[c].valid for c in names]))
    if d.seq is not None:
        ops.append(d.seq)
    outs = list(fn(*ops))
    ts2, mask2 = outs[0], outs[1]
    i = 2
    new_cols = dict(d.cols)
    if names:
        vals2, valids2 = outs[2], outs[3]
        i = 4
        new_cols = {
            c: dataclasses.replace(col, values=vals2[j], valid=valids2[j])
            for j, (c, col) in enumerate(d.cols.items())
        }
    seq2 = outs[i] if d.seq is not None else None
    return d._with(ts=ts2, mask=mask2, cols=new_cols, seq=seq2,
                   series_axis=new_series, time_axis=new_time)


def relayout_comm_bytes(K_dev: int, L: int, n_cols: int, n_shards: int,
                        has_seq: bool = False) -> int:
    """Modeled per-shard all_to_all bytes of one :func:`reshard_frame`
    call: every plane's per-shard element count (K*L / total shards)
    times its itemsize — int64 ts + bool mask + n_cols x (compute
    dtype value + bool validity) [+ seq].  The explain() annotation
    and the reshard.plan_node compiled contract both read this model;
    ``profiling.comm_bytes_from_compiled`` is the measured side."""
    val_itemsize = np.dtype(packing.compute_dtype()).itemsize
    elems = (K_dev * L) // max(n_shards, 1)
    per_elem = 8 + 1 + n_cols * (val_itemsize + 1)
    if has_seq:
        per_elem += val_itemsize
    return int(elems * per_elem)


@functools.lru_cache(maxsize=256)
def _relayout_fn(mesh, series_axis, time_axis, forward=True,
                 with_cols=True, has_seq=False):
    """The jitted relayout program: P(series, time) <-> the joint
    P((series, time), None) series-local layout, every plane in one
    program (ts/mask [K, L]; value/validity stacks [C, K, L]; optional
    seq plane).  No donation: the input and output PER-DEVICE buffer
    shapes differ by construction (that is the point of a layout
    switch), so XLA could never apply an alias."""
    joint = (series_axis, time_axis)
    if forward:
        sp2_in, sp2_out = P(series_axis, time_axis), P(joint, None)
        sp3_in = P(None, series_axis, time_axis)
        sp3_out = P(None, joint, None)
    else:
        sp2_in, sp2_out = P(joint, None), P(series_axis, time_axis)
        sp3_in = P(None, joint, None)
        sp3_out = P(None, series_axis, time_axis)

    def kernel(*ops):
        if forward:
            a2a = lambda a: jax.lax.all_to_all(
                a, time_axis, split_axis=a.ndim - 2,
                concat_axis=a.ndim - 1, tiled=True)
        else:
            a2a = lambda a: jax.lax.all_to_all(
                a, time_axis, split_axis=a.ndim - 1,
                concat_axis=a.ndim - 2, tiled=True)
        return tuple(a2a(a) for a in ops)

    in_specs = [sp2_in, sp2_in]
    out_specs = [sp2_out, sp2_out]
    if with_cols:
        in_specs += [sp3_in, sp3_in]
        out_specs += [sp3_out, sp3_out]
    if has_seq:
        in_specs.append(sp2_in)
        out_specs.append(sp2_out)
    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=tuple(in_specs),
                             out_specs=tuple(out_specs)),
                   in_shardings=tuple(_ns(mesh, s) for s in in_specs),
                   out_shardings=tuple(_ns(mesh, s) for s in out_specs))


@functools.lru_cache(maxsize=8)
def _describe_reduce():
    """Jitted global reductions for describe(); cached so repeated
    describe() calls retrace nothing."""

    @jax.jit
    def reduce_cols(ts, mask, secs, vals, valids):
        out = {}
        out["min_ts"] = jnp.min(jnp.where(mask, ts, packing.TS_PAD))
        out["max_ts"] = jnp.max(jnp.where(mask, ts, jnp.int64(-2 ** 62)))
        out["n_rows"] = jnp.sum(mask)
        s = jnp.where(mask, secs, 0.0)
        out["has_frac"] = jnp.any(mask & (s - jnp.floor(s) > 0))
        out["sub_min"] = jnp.any(mask & (jnp.mod(s, 60) != 0))
        out["sub_hr"] = jnp.any(mask & (jnp.mod(s, 3600) != 0))
        out["sub_day"] = jnp.any(mask & (jnp.mod(s, 86400) != 0))
        ok = valids & mask[None]
        v = jnp.where(ok, vals, 0.0)
        out["count"] = jnp.sum(ok, axis=(1, 2))
        out["sum"] = jnp.sum(v, axis=(1, 2))
        out["sumsq"] = jnp.sum(v * v, axis=(1, 2))
        out["min"] = jnp.min(jnp.where(ok, vals, jnp.inf), axis=(1, 2))
        out["max"] = jnp.max(jnp.where(ok, vals, -jnp.inf), axis=(1, 2))
        # seconds view of the ts column (tsdf.py:393-400)
        out["ts_sum"] = jnp.sum(jnp.where(mask, secs, 0.0))
        out["ts_sumsq"] = jnp.sum(jnp.where(mask, secs * secs, 0.0))
        out["ts_min"] = jnp.min(jnp.where(mask, secs, jnp.inf))
        out["ts_max"] = jnp.max(jnp.where(mask, secs, -jnp.inf))
        return out

    return reduce_cols


@functools.lru_cache(maxsize=64)
def _autocorr_fn(lag, compact):
    """Jitted per-series lag-k autocorrelation; ``compact`` stable-sorts
    scattered valid rows (bucket-head views) to the front first so the
    physical lag pairing matches the host path's compacted layout."""

    @jax.jit
    def per_series(v, ok, mask):
        ok = ok & mask
        if compact:
            # stable sort by (invalid, position): valid rows keep order
            # at the front; the frame's row set becomes the valid rows
            key = (~ok).astype(jnp.int32)
            _, v, ok = jax.lax.sort(
                (key, v, ok), dimension=-1, num_keys=1, is_stable=True
            )
            mask2 = ok
        else:
            mask2 = mask
        Lh = v.shape[-1]
        cnt = jnp.sum(ok, axis=-1)
        mean = jnp.sum(jnp.where(ok, v, 0.0), axis=-1) \
            / jnp.maximum(cnt, 1)
        sub = jnp.where(ok, v - mean[:, None], 0.0)
        denom = jnp.sum(sub * sub, axis=-1)
        lengths = jnp.sum(mask2, axis=-1)
        if lag >= Lh:
            return jnp.full(denom.shape, jnp.nan), cnt, lengths
        left = sub[:, :-lag]
        right = sub[:, lag:]
        pos = jnp.arange(Lh - lag)
        keep = (
            (pos[None, :] + 1 <= cnt[:, None] - lag)
            & (pos[None, :] + lag < lengths[:, None])
            & ok[:, :-lag] & ok[:, lag:]
        )
        num = jnp.sum(jnp.where(keep, left * right, 0.0), axis=-1)
        any_pair = jnp.any(keep, axis=-1)
        ac = jnp.where(any_pair, num, jnp.nan) / denom
        return ac, cnt, lengths

    return per_series


def _bucket_heads(ts, mask, step_ns):
    """Shared tumbling-bucket scaffolding: absolute bucket key ``b``,
    bucket-head mask, and per-row [start, end) row bounds of the row's
    bucket (used by resample, grouped stats, and vwap).

    The searchsorted bounds run over ``b_all`` (every row's bucket,
    masked rows included) and NOT the TS_PAD-masked ``b``: a masked row
    *between* two real rows of one bucket (any bucket-head view — e.g.
    a chained resample) would make ``b`` non-monotone, and the TPU
    sort-based searchsorted silently returns garbage on unsorted keys
    (round-4 fix; the masked rows inside a range are harmless — their
    validity planes are False).  ``head`` compares each real row's
    bucket against the previous REAL row's bucket (a cummax carry —
    buckets are monotone over the sorted ts), not the physically
    previous row: comparing against a masked neighbour flagged every
    real row after a gap as a head, duplicating buckets in chained
    resamples (round-4 fix)."""
    step = jnp.int64(step_ns)
    b_all = (ts // step) * step
    b = jnp.where(mask, b_all, packing.TS_PAD)
    neg = jnp.int64(-(2**62))
    last_real = rk.wu.cummax(jnp.where(mask, b_all, neg))
    prev_real = jnp.concatenate(
        [jnp.full_like(b[:, :1], neg), last_real[:, :-1]], axis=-1
    )
    head = mask & (b_all != prev_real)
    start = rk.wu.searchsorted_batched(b_all, b_all,
                                       side="left").astype(jnp.int32)
    end = rk.wu.searchsorted_batched(b_all, b_all + step,
                                     side="left").astype(jnp.int32)
    # per-row rebased i32 bucket id for the VMEM segmented-reduction
    # kernel (rk.bucket_stats); pads clamp to i32-max and form their
    # own trailing bucket, masked downstream like the bound form
    rel = (b_all - b_all[:, :1]) // step
    bid = jnp.minimum(rel, 2**31 - 1).astype(jnp.int32)
    return b, head, start, end, bid


@functools.lru_cache(maxsize=256)
def _bucket_stats_fn(mesh, series_axis, time_axis, step_ns, n_cols,
                     sort_kernels=False):
    """Six aggregates per epoch-aligned tumbling bucket, emitted at
    bucket-head rows (withGroupedStats tsdf.py:723-759 / vwap
    aggregation).  Time-sharded meshes switch to a series-local layout
    around the bucket reduction, like _resample_fn."""
    n_t = mesh.shape[time_axis] if time_axis else 1
    sp2 = _spec(mesh, series_axis, time_axis)
    sp3 = _spec(mesh, series_axis, time_axis, 3)

    def local(ts, mask, vals, valids):
        b, head, start, end, bid = _bucket_heads(ts, mask, step_ns)
        # packed passes share the bucket-id plane across the column
        # stack (bucket_pack_budget-sized groups); bitwise-identical to
        # the per-column loop it replaced
        stats = rk.bucket_stats_multi(bid, vals, valids, start, end)
        new_ts = jnp.where(mask, b, packing.TS_PAD)
        # [6, n_cols, K, L]
        return new_ts, head, jnp.stack([
            stats["mean"], stats["count"], stats["min"], stats["max"],
            stats["sum"], stats["stddev"],
        ])

    def kernel(ts, mask, vals, valids):
        if n_t > 1:
            a2a_in = lambda a: jax.lax.all_to_all(
                a, time_axis, split_axis=a.ndim - 2, concat_axis=a.ndim - 1,
                tiled=True)
            a2a_out = lambda a: jax.lax.all_to_all(
                a, time_axis, split_axis=a.ndim - 1, concat_axis=a.ndim - 2,
                tiled=True)
            ts, mask, vals, valids = (a2a_in(a) for a in
                                      (ts, mask, vals, valids))
            new_ts, head, stats = local(ts, mask, vals, valids)
            return a2a_out(new_ts), a2a_out(head), a2a_out(stats)
        return local(ts, mask, vals, valids)

    sp_stats = _spec(mesh, series_axis, time_axis, 4)
    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(sp2, sp2, sp3, sp3),
                             out_specs=(sp2, sp2, sp_stats)))


@functools.lru_cache(maxsize=256)
def _interp_fn(mesh, series_axis, time_axis, step_ns, G, mkey, n_cols,
               flags):
    """Dense-grid gap fill (interpol.py semantics): generate each
    series' bucket grid and fill via prev/next merge joins.

    Inputs are a bucket-head resample view [K, L]; outputs are dense
    [K, G] grids, series-sharded (``P(series, None)``) — on a
    time-sharded mesh the inputs regather series-local first (the grid
    length G has no relation to the input shard width)."""
    from tempo_tpu.ops import sortmerge as sm

    n_t = mesh.shape[time_axis] if time_axis else 1
    # interpolate() reshards time-sharded frames through reshard_frame
    # BEFORE building this kernel, so only series-local (or degenerate
    # size-1 time axis) frames reach here
    assert n_t == 1, "interpolate kernels are series-local by contract"
    sp2_in = _spec(mesh, series_axis, time_axis)
    sp3_in = _spec(mesh, series_axis, time_axis, 3)
    sp2_out = _spec(mesh, series_axis, None)
    sp3_out = _spec(mesh, series_axis, None, 3)

    def kernel(ts, head, vals, valids):
        step = jnp.int64(step_ns)
        dt = vals.dtype

        ts_j = jnp.where(head, ts, packing.TS_PAD)
        first_b = jnp.min(ts_j, axis=1, keepdims=True)         # [K, 1]
        last_b = jnp.max(jnp.where(head, ts, jnp.int64(-1)), axis=1,
                         keepdims=True)
        # the merge joins below receive ``ts`` (sorted), NOT the
        # TS_PAD-masked ``ts_j``: a bucket-head view has interior
        # head=False rows, and masking them to TS_PAD breaks the
        # ascending-per-row contract of the TPU merge kernels (silent
        # wrong results; round-4 fix).  Non-head rows are excluded by
        # their validity planes instead — identical semantics, the
        # per-column fill skips invalid rows.
        has_any = last_b >= 0
        gridj = jnp.arange(G, dtype=jnp.int64)[None, :]        # [1, G]
        grid_ts = jnp.where(
            has_any, first_b + gridj * step, packing.TS_PAD
        )
        grid_mask = has_any & (grid_ts <= last_b)
        grid_ts = jnp.where(grid_mask, grid_ts, packing.TS_PAD)

        # per-col planes: value + exact bucket index; plus one row plane
        bidx = jnp.where(head, (ts - jnp.where(has_any, first_b, 0))
                         // step, -1).astype(dt)
        planes = jnp.concatenate([
            vals,
            jnp.broadcast_to(bidx, (n_cols,) + bidx.shape),
            bidx[None],
        ])
        pvalid = jnp.concatenate([
            valids, valids, head[None],
        ])
        prev_v, prev_f, _ = sm.asof_merge_values(
            grid_ts, ts, pvalid, planes
        )
        neg = lambda a: -a[..., ::-1]
        flip = lambda a: a[..., ::-1]
        next_v_r, next_f_r, _ = sm.asof_merge_values(
            neg(grid_ts), neg(ts), flip(pvalid), flip(planes)
        )
        next_v = flip(next_v_r)
        next_f = flip(next_f_r)

        gj = gridj.astype(dt)
        out_vals = []
        out_valid = []
        col_interp = []
        for i in range(n_cols):
            pv, pf = prev_v[i], prev_f[i]
            pi = prev_v[n_cols + i]
            nv, nf = next_v[i], next_f[i]
            ni = next_v[n_cols + i]
            exact = pf & (pi == gj)
            if mkey == 0:        # zero
                filled = jnp.where(exact, pv, 0.0)
                ok = grid_mask
            elif mkey == 1:      # null
                filled = jnp.where(exact, pv, jnp.nan)
                ok = grid_mask & exact
            elif mkey == 2:      # ffill
                filled = jnp.where(pf, pv, jnp.nan)
                ok = grid_mask & pf
            elif mkey == 3:      # bfill
                filled = jnp.where(nf, nv, jnp.nan)
                ok = grid_mask & nf
            else:                # linear
                both = pf & nf & (ni > pi)
                w = jnp.where(both, (gj - pi) / jnp.maximum(ni - pi, 1), 0.0)
                lerp = pv + (nv - pv) * w
                filled = jnp.where(exact, pv,
                                   jnp.where(both, lerp, jnp.nan))
                ok = grid_mask & (exact | both)
            out_vals.append(jnp.where(grid_mask, filled, jnp.nan))
            out_valid.append(ok)
            col_interp.append(grid_mask & ~exact)

        row_pi = prev_v[2 * n_cols]
        row_pf = prev_f[2 * n_cols]
        ts_interp = grid_mask & ~(row_pf & (row_pi == gj))
        out = (grid_ts, grid_mask, jnp.stack(out_vals),
               jnp.stack(out_valid))
        if flags:
            out = out + (ts_interp, jnp.stack(col_interp))
        return out

    out_specs = (sp2_out, sp2_out, sp3_out, sp3_out)
    if flags:
        out_specs = out_specs + (sp2_out, sp3_out)
    return jax.jit(shard_map(kernel, mesh=mesh,
                             in_specs=(sp2_in, sp2_in, sp3_in, sp3_in),
                             out_specs=out_specs))


@functools.lru_cache(maxsize=256)
def _lookback_tensor_fn(mesh, series_axis, time_axis, w, n_cols):
    """[F, K, L] planes -> ([K, L, w, F] values, mask) shifted stacks
    (rolling.lookback_tensor semantics: slot j = observation t-w+j,
    zero/False where absent).  Time-sharded meshes regather
    series-local rows first — the output stays series-local over all
    devices, like the interpolate grid outputs."""
    n_t = mesh.shape[time_axis] if time_axis else 1
    sp_in = _spec(mesh, series_axis, time_axis, 3)
    if n_t > 1:
        sp_out = P((series_axis, time_axis), None, None, None)
    else:
        sp_out = P(series_axis, None, None, None)

    def kernel(vals, valids):
        from tempo_tpu.rolling import lookback_stack

        if n_t > 1:
            a2a = lambda a: jax.lax.all_to_all(
                a, time_axis, split_axis=a.ndim - 2, concat_axis=a.ndim - 1,
                tiled=True)
            vals, valids = a2a(vals), a2a(valids)
        return lookback_stack(vals.transpose(1, 2, 0),
                              valids.transpose(1, 2, 0), w)

    return jax.jit(shard_map(kernel, mesh=mesh, in_specs=(sp_in, sp_in),
                             out_specs=(sp_out, sp_out)))


@functools.lru_cache(maxsize=256)
def _fourier_fn(mesh, series_axis, time_axis, timestep):
    """Per-series exact-length DFT planes (freq, ft_real, ft_imag) on
    front-packed [K, L] rows; one Bluestein program at the lane width
    serves every length mix (ops/fft.py).  Time-sharded meshes switch
    to series-local full rows around the transform."""
    from tempo_tpu.ops import fft as fft_ops

    n_t = mesh.shape[time_axis] if time_axis else 1
    # fourier_transform() reshards time-sharded frames through
    # reshard_frame BEFORE building this kernel
    assert n_t == 1, "fourier kernels are series-local by contract"
    sp2 = _spec(mesh, series_axis, time_axis)

    def local(vals, mask):
        L = vals.shape[-1]
        n = jnp.sum(mask, axis=-1)                       # [K]
        x = jnp.where(mask, vals, 0.0).astype(vals.dtype)
        # the Bluestein bucket must be a power of two (its internal
        # convolution length is 2*bucket); the frame's lane width is
        # only 8-aligned — zero-pad up and slice back
        B2 = 1 << max(int(L) - 1, 1).bit_length()
        if B2 != L:
            x = jnp.pad(x, ((0, 0), (0, B2 - L)))
        re, im = fft_ops.bluestein_dft(x, jnp.maximum(n, 1), B2)
        re, im = re[:, :L], im[:, :L]
        j = jnp.arange(L)[None, :]
        n_ = jnp.maximum(n[:, None], 1)
        # np.fft.fftfreq order: [0 .. (n-1)//2, -(n//2) .. -1] / (n d)
        jj = jnp.where(j <= (n_ - 1) // 2, j, j - n_)
        freq = jj.astype(vals.dtype) / (
            n_.astype(vals.dtype) * vals.dtype.type(timestep)
        )
        ok = j < n[:, None]
        nan = vals.dtype.type(jnp.nan)
        return (jnp.where(ok, freq, nan),
                jnp.where(ok, re.astype(vals.dtype), nan),
                jnp.where(ok, im.astype(vals.dtype), nan))

    return jax.jit(shard_map(local, mesh=mesh, in_specs=(sp2, sp2),
                             out_specs=(sp2, sp2, sp2)))


@functools.lru_cache(maxsize=256)
def _resample_fn(mesh, series_axis, time_axis, step_ns, fkey, n_cols,
                 sort_kernels=False):
    """Bucket-head resample kernel.  On a time-sharded mesh the blocks
    all_to_all to a series-local layout (full rows per device), compute,
    and switch back — the reference's groupBy shuffle as two ICI
    collectives (reshard.py pattern)."""
    n_t = mesh.shape[time_axis] if time_axis else 1
    # resample() reshards time-sharded frames through reshard_frame
    # BEFORE building this kernel (dist.resample)
    assert n_t == 1, "resample kernels are series-local by contract"
    sp2 = _spec(mesh, series_axis, time_axis)
    sp3 = _spec(mesh, series_axis, time_axis, 3)

    def local(ts, mask, vals, valids):
        b, head, start, end, bid = _bucket_heads(ts, mask, step_ns)

        if fkey == 1:
            # ceil reads each bucket's last REAL row: a bucket-head
            # view can end its physical [start, end) run on a masked
            # row, so the gather index comes from a segmented
            # last-real-lane scan, not end-1 itself (round-4 fix;
            # identical to end-1 on dense frames)
            from tempo_tpu.ops.sortmerge import _ffill_scan_seg

            K_l, L_l = mask.shape
            lane = jnp.broadcast_to(
                jnp.arange(L_l, dtype=jnp.int32), (K_l, L_l)
            )
            fence = jnp.concatenate(
                [jnp.ones((K_l, 1), jnp.bool_),
                 bid[:, 1:] != bid[:, :-1]], axis=-1
            )
            _, has_real, last_lane = _ffill_scan_seg(fence, mask, lane)
            last_phys = jnp.maximum(end - 1, 0)
            idx = jnp.take_along_axis(last_lane, last_phys, axis=-1)
            has = jnp.take_along_axis(has_real, last_phys, axis=-1)
            last = jnp.maximum(idx, 0)

        if fkey >= 2:              # mean/min/max: one packed reduction
            stats = rk.bucket_stats_multi(bid, vals, valids, start, end)
            key = {2: "mean", 3: "min", 4: "max"}[fkey]
        outs = []
        oks = []
        for i in range(n_cols):
            x, v = vals[i], valids[i]
            if fkey == 0:          # floor: first record of the bucket
                outs.append(x)
                oks.append(head & v)
            elif fkey == 1:        # ceil: last record of the bucket
                outs.append(jnp.take_along_axis(x, last, axis=-1))
                oks.append(head & has
                           & jnp.take_along_axis(v, last, axis=-1))
            else:
                outs.append(stats[key][i])
                oks.append(head & (stats["count"][i] > 0))
        new_ts = jnp.where(mask, b, packing.TS_PAD)
        return new_ts, head, jnp.stack(outs), jnp.stack(oks)

    return jax.jit(shard_map(local, mesh=mesh,
                             in_specs=(sp2, sp2, sp3, sp3),
                             out_specs=(sp2, sp2, sp3, sp3)))
