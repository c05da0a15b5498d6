"""Time-axis sharding with neighbor halo exchange (sequence parallelism).

The reference handles "too many rows per key" with overlapping time
brackets: round ts into ``tsPartitionVal``-second buckets and duplicate
the trailing ``fraction`` of each bucket into the next so windowed
lookbacks see their history, then drop the duplicates
(/root/reference/python/tempo/tsdf.py:164-190, consumed at :549-558;
scala asofJoin.scala:91-116).  That is a blockwise halo scheme executed
through Spark's shuffle.

Here the same algebra becomes a *device* layout: the packed time axis
``[K, L]`` is sharded over a ``'time'`` mesh axis, and each shard
receives a trailing halo of ``H`` rows from its left neighbor over ICI
via ``lax.ppermute`` inside ``shard_map``.  Compute then runs on the
halo-extended block with the ordinary single-device kernels and the
halo region is dropped from outputs — compute-local, communication =
one neighbor exchange of ``H`` rows.

Correctness contract (same as the reference's): the halo must cover the
lookback — ``H`` rows must span at least ``window_secs`` (or the AS-OF
lookback) of history.  Like the reference's missing-value audit
(tsdf.py:141-159), kernels return a ``clipped`` count of rows whose
window may have been truncated at the halo boundary instead of failing.

Key layout fact that makes the halo concatenation sound: a packed row
is non-decreasing along the full time axis (real timestamps ascending,
then ``TS_PAD`` padding), so [left-neighbor's last H columns | local
chunk] is a contiguous slice of that row and stays non-decreasing —
``searchsorted`` remains valid with no re-sort.  The first shard's halo
is synthesized as ``TS_NEG``/invalid ("nothing before the beginning").
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map_raw
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tempo_tpu.ops import asof as asof_ops
from tempo_tpu.ops import rolling as rk

from tempo_tpu.packing import RANGE_STATS, TS_PAD, TS_REAL_MAX


def shard_map(f, *, mesh, in_specs, out_specs):
    return _shard_map_raw(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# sentinel smaller than any real ns timestamp, with headroom so
# subtracting a window width cannot underflow int64 (mirror of TS_PAD)
TS_NEG = np.int64(-TS_REAL_MAX)
# right-halo fill on the last shard: larger than any real timestamp so
# the extended row stays sorted and no window ever includes it — the
# same sentinel packed rows already use for padding
TS_POS = TS_PAD


def _specs(mesh: Mesh, ndim: int, time_axis: str, series_axis: str):
    """PartitionSpec for a [..., K, L] array: series axis (if present on
    the mesh) on dim -2, time axis on dim -1."""
    s = series_axis if series_axis in mesh.axis_names else None
    lead = [None] * (ndim - 2)
    return P(*(lead + [s, time_axis]))


def _halo_from_left(
    arr: jnp.ndarray, halo: int, n_shards: int, time_axis: str, fill
) -> jnp.ndarray:
    """Return this shard's left halo: the last ``halo`` columns of the
    left neighbor's block (``fill`` on the first shard)."""
    tail = arr[..., -halo:]
    if n_shards == 1:
        return jnp.full_like(tail, fill)
    perm = [(i, i + 1) for i in range(n_shards - 1)]
    recv = jax.lax.ppermute(tail, time_axis, perm)
    ti = jax.lax.axis_index(time_axis)
    return jnp.where(ti == 0, jnp.full_like(tail, fill), recv)


def _halo_from_right(
    arr: jnp.ndarray, halo: int, n_shards: int, time_axis: str, fill
) -> jnp.ndarray:
    """Return this shard's right halo: the first ``halo`` columns of the
    right neighbor's block (``fill`` on the last shard).  Needed because
    a Spark range window's frame includes *following* rows that share
    the current row's order-key value (see range_window_bounds), and
    such ties can straddle a shard boundary."""
    head = arr[..., :halo]
    if n_shards == 1:
        return jnp.full_like(head, fill)
    perm = [(i + 1, i) for i in range(n_shards - 1)]
    recv = jax.lax.ppermute(head, time_axis, perm)
    ti = jax.lax.axis_index(time_axis)
    return jnp.where(ti == n_shards - 1, jnp.full_like(head, fill), recv)


def _check_halo(mesh: Mesh, L: int, halo: int, time_axis: str) -> int:
    n_time = mesh.shape[time_axis]
    if L % n_time != 0:
        raise ValueError(f"time axis {L} not divisible by mesh axis {n_time}")
    if not (0 < halo <= L // n_time):
        raise ValueError(f"halo {halo} must be in (0, {L // n_time}]")
    return n_time


def range_stats_time_sharded(
    mesh: Mesh,
    ts_long: jnp.ndarray,   # [K, L] int64 seconds (sorted per row)
    x: jnp.ndarray,         # [K, L] float values
    valid: jnp.ndarray,     # [K, L] bool
    window_secs: float,
    halo: int,
    time_axis: str = "time",
    series_axis: str = "series",
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """``withRangeStats`` (tsdf.py:673-721 semantics) over a time-sharded
    series batch.  Returns (stats dict of [K, L] arrays, clipped count).

    ``clipped`` counts rows whose window start hit the halo boundary on a
    non-first shard — i.e. rows whose true window may extend past the H
    rows of halo (the reference's skew-join warning analog).
    """
    _check_halo(mesh, int(ts_long.shape[-1]), halo, time_axis)
    fn = _build_range_stats(mesh, float(window_secs), int(halo),
                            time_axis, series_axis)
    return fn(ts_long, x, valid)


@functools.lru_cache(maxsize=256)
def _build_range_stats(
    mesh: Mesh, window_secs: float, halo: int,
    time_axis: str, series_axis: str,
):
    """Jitted program builder, cached so chained frame-level pipelines
    compile each (mesh, window, halo) combination once."""
    spec2 = _specs(mesh, 2, time_axis, series_axis)
    n_time = mesh.shape[time_axis]

    def kernel(ts_l, x_l, v_l):
        # left halo (lookback history) + right halo (following rows that
        # tie on the order key - Spark's range frame includes them, see
        # range_window_bounds' upper_bound end)
        h_ts = _halo_from_left(ts_l, halo, n_time, time_axis, TS_NEG)
        h_x = _halo_from_left(x_l, halo, n_time, time_axis, jnp.zeros((), x_l.dtype))
        h_v = _halo_from_left(v_l, halo, n_time, time_axis, False)
        r_ts = _halo_from_right(ts_l, halo, n_time, time_axis, TS_POS)
        r_x = _halo_from_right(x_l, halo, n_time, time_axis, jnp.zeros((), x_l.dtype))
        r_v = _halo_from_right(v_l, halo, n_time, time_axis, False)
        # TS_NEG / TS_POS fills keep the extended row sorted end to end
        ext_ts = jnp.concatenate([h_ts, ts_l, r_ts], axis=-1)
        ext_x = jnp.concatenate([h_x, x_l, r_x], axis=-1)
        ext_v = jnp.concatenate([h_v, v_l, r_v], axis=-1)
        L_ext = ext_ts.shape[-1]
        Ll = ts_l.shape[-1]

        # exact integer window compare for any width — no weak-f64 op
        # under the f32 compute policy (the compiled no-f64-leak
        # contract) and no float rounding at epoch-scale seconds
        start, end = rk.range_window_bounds(
            ext_ts, rk.range_window_width(ext_ts, window_secs))
        stats = rk.windowed_stats(ext_x, ext_v, start, end)
        out = {k: v[..., halo:halo + Ll] for k, v in stats.items()}

        ti = jax.lax.axis_index(time_axis)
        # audit both truncation sides: lookback fell off the left halo,
        # or the tie run continued past the right halo
        s_loc = start[..., halo:halo + Ll]
        e_loc = end[..., halo:halo + Ll]
        local_clip = jnp.sum(
            ((s_loc == 0) & v_l & (ti > 0))
            | ((e_loc == L_ext) & v_l & (ti < n_time - 1)),
            dtype=jnp.int32,
        )
        axes = (time_axis, series_axis) if series_axis in mesh.axis_names else (time_axis,)
        clipped = jax.lax.psum(local_clip, axes)
        return out, clipped

    out_stats_spec = {k: spec2 for k in RANGE_STATS}
    fn = shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec2, spec2, spec2),
        out_specs=(out_stats_spec, P()),
    )
    return jax.jit(fn)


def ema_time_sharded(
    mesh: Mesh,
    x: jnp.ndarray,        # [K, L] float
    valid: jnp.ndarray,    # [K, L] bool
    alpha: float,
    time_axis: str = "time",
    series_axis: str = "series",
) -> jnp.ndarray:
    """Exact infinite-horizon EMA across a time-sharded axis.

    The EMA recurrence is an associative (decay, value) monoid, so each
    shard scans locally and the cross-shard carry is an exclusive scan
    of per-shard totals, realised with one small ``all_gather`` over the
    time axis — O(L/n) compute + O(n) stitch, vs the reference's
    truncated-lag approximation that cannot cross partitions at all
    (tsdf.py:615-635).
    """
    if x.shape[-1] % mesh.shape[time_axis] != 0:
        raise ValueError(
            f"time axis {x.shape[-1]} not divisible by {mesh.shape[time_axis]}"
        )
    fn = _build_ema(mesh, float(alpha), time_axis, series_axis)
    return fn(x, valid)


@functools.lru_cache(maxsize=256)
def _build_ema(mesh: Mesh, alpha: float, time_axis: str, series_axis: str):
    spec2 = _specs(mesh, 2, time_axis, series_axis)
    n_time = mesh.shape[time_axis]

    def kernel(x_l, v_l):
        a = jnp.asarray(alpha, x_l.dtype)
        decay = jnp.where(v_l, 1.0 - a, 1.0)
        inp = jnp.where(v_l, a * x_l, 0.0)

        def combine(c1, c2):
            d1, v1 = c1
            d2, v2 = c2
            return d1 * d2, v2 + d2 * v1

        d, y = jax.lax.associative_scan(combine, (decay, inp), axis=-1)
        if n_time > 1:
            d_tot, v_tot = d[..., -1], y[..., -1]                  # [K]
            dg = jax.lax.all_gather(d_tot, time_axis)              # [n, K]
            vg = jax.lax.all_gather(v_tot, time_axis)
            ti = jax.lax.axis_index(time_axis)
            carry_d = jnp.ones_like(d_tot)
            carry_v = jnp.zeros_like(v_tot)
            for j in range(n_time):                                # static
                take = j < ti
                nd, nv = combine((carry_d, carry_v), (dg[j], vg[j]))
                carry_d = jnp.where(take, nd, carry_d)
                carry_v = jnp.where(take, nv, carry_v)
            y = y + d * carry_v[..., None]
        return y

    fn = shard_map(
        kernel, mesh=mesh, in_specs=(spec2, spec2), out_specs=spec2,
    )
    return jax.jit(fn)


def asof_time_sharded(
    mesh: Mesh,
    l_ts: jnp.ndarray,       # [K, Ll] int64, time-sharded
    r_ts: jnp.ndarray,       # [K, Lr] int64, time-sharded
    r_valids: jnp.ndarray,   # [n_cols, K, Lr] bool per-column non-null
                             # (False on padding rows — the carry
                             # relies on that invariant)
    r_values: jnp.ndarray,   # [n_cols, K, Lr] float column values
    halo: int,
    time_axis: str = "time",
    series_axis: str = "series",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """AS-OF join over time-sharded left/right with *unbounded* lookback.

    Shard-local matching handles rows whose match lives in the same
    time shard; matches any distance further back ride a cross-shard
    **carry**: each shard publishes its last non-null value per column
    (one [n_cols, K] vector), an exclusive combine over an
    ``all_gather`` of those supplies the latest preceding value to rows
    with no local match — the associative-scan form of the reference's
    ``last(col, ignoreNulls) over unboundedPreceding`` (tsdf.py:139),
    so lookback depth is unlimited, unlike the reference's
    ``tsPartitionVal`` bracket which nulls beyond the overlap.  The
    trailing ``halo`` from the *right* neighbor covers Spark's
    equal-timestamp tie rule (a tie run straddling the boundary).

    Precondition (value-aligned shards): for every shard *i*, every
    right row in shards j < i must be at-or-before every left row in
    shard *i* — true when both sides share a time grid (telemetry
    joins, the driver dryrun) or were bracket-packed against common
    boundaries.  For independently-packed sides use the exact
    all-to-all layout-switch join instead
    (``tempo_tpu.dist._asof_a2a``, what ``DistributedTSDF.asofJoin``
    dispatches to); under misalignment this kernel's carry can surface
    a *later* right value than the true as-of match.

    Returns (values [n_cols, K, Ll], found [n_cols, K, Ll] bool,
    clipped count) — ``clipped`` counts left rows whose equal-ts tie run
    may continue past the right halo (audit, tsdf.py:150-159 analog).
    """
    n_time = _check_halo(mesh, int(r_ts.shape[-1]), halo, time_axis)
    if l_ts.shape[-1] % n_time != 0:
        raise ValueError(f"left time axis {l_ts.shape[-1]} not divisible by {n_time}")
    from tempo_tpu.ops.sortmerge import use_sort_kernels

    fn = _build_asof(mesh, int(halo), time_axis, series_axis,
                     use_sort_kernels())
    return fn(l_ts, r_ts, r_valids, r_values)


@functools.lru_cache(maxsize=256)
def _build_asof(mesh: Mesh, halo: int, time_axis: str, series_axis: str,
                sort_kernels: bool = False):
    spec2 = _specs(mesh, 2, time_axis, series_axis)
    spec3 = _specs(mesh, 3, time_axis, series_axis)
    n_time = mesh.shape[time_axis]

    def kernel(lts, rts, rval, rx):
        # right halo only: right rows in the next shard that tie a left
        # row's timestamp are the true AS-OF match (last right row with
        # r_ts <= l_ts — equal ts included, tsdf.py:111-162), and a tie
        # run can straddle the boundary.  History older than this shard
        # arrives via the carry below, not a halo.
        g_ts = _halo_from_right(rts, halo, n_time, time_axis, TS_POS)
        g_val = _halo_from_right(rval, halo, n_time, time_axis, False)
        g_x = _halo_from_right(rx, halo, n_time, time_axis, jnp.zeros((), rx.dtype))
        ext_ts = jnp.concatenate([rts, g_ts], axis=-1)
        ext_val = jnp.concatenate([rval, g_val], axis=-1)
        ext_x = jnp.concatenate([rx, g_x], axis=-1)
        L_ext = ext_ts.shape[-1]

        if sort_kernels:
            # gather-free shard-local join (the value gather below is
            # the single most expensive op on TPU — sortmerge.py).
            # Engine cascade per shard (round 6): single-plan VMEM
            # merge when the halo-extended width fits its plan, the
            # XLA bitonic network past the single-program ceiling —
            # so a time-sharded join whose SHARD width exceeds ~205K
            # merged lanes no longer OOMs the compiler; the time
            # sharding itself is the distributed form of lane
            # chunking (shard = chunk, the cross-shard carry below =
            # the chunked kernel's carried ffill state).
            from tempo_tpu.ops import sortmerge as sm

            vals, found, last_idx = sm.asof_merge_values(
                lts, ext_ts, ext_val, ext_x
            )
        else:
            last_idx, col_idx = asof_ops.asof_indices_searchsorted(
                lts, ext_ts, ext_val
            )
            found = col_idx >= 0
            safe = jnp.maximum(col_idx, 0)
            vals = jnp.take_along_axis(ext_x, safe, axis=-1)

        if n_time > 1:
            # cross-shard carry: this shard's last non-null value per
            # (col, series) — from the LOCAL block only — combined
            # exclusively across the time axis (latest prior shard wins)
            lv = jnp.max(
                jnp.where(rval, jnp.arange(rts.shape[-1], dtype=jnp.int32),
                          -1),
                axis=-1,
            )                                             # [n_cols, K]
            has_local = lv >= 0
            v_local = jnp.take_along_axis(
                rx, jnp.maximum(lv, 0)[..., None], axis=-1
            )[..., 0]
            hg = jax.lax.all_gather(has_local, time_axis)  # [n_t, C, K]
            vg = jax.lax.all_gather(v_local, time_axis)
            ti = jax.lax.axis_index(time_axis)
            carry_has = jnp.zeros_like(has_local)
            carry_val = jnp.zeros_like(v_local)
            for j in range(n_time):                        # static
                take = (j < ti) & hg[j]
                carry_has = jnp.where(take, True, carry_has)
                carry_val = jnp.where(take, vg[j], carry_val)
            vals = jnp.where(found, vals, carry_val[..., None])
            found = found | carry_has[..., None]
        vals = jnp.where(found, vals, jnp.nan)

        # audit: left rows whose equal-ts tie run may continue past the
        # right halo (their match could be an even later tied right row)
        l_real = lts < TS_REAL_MAX  # not TS_PAD padding
        ti2 = jax.lax.axis_index(time_axis)
        local_clip = jnp.sum(
            (last_idx == L_ext - 1) & l_real & (ti2 < n_time - 1),
            dtype=jnp.int32,
        )
        axes = (time_axis, series_axis) if series_axis in mesh.axis_names else (time_axis,)
        clipped = jax.lax.psum(local_clip, axes)
        return vals, found, clipped

    fn = shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec2, spec2, spec3, spec3),
        out_specs=(spec3, spec3, P()),
    )
    return jax.jit(fn)
