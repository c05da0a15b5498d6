"""Single-program execution of a mesh ``asofJoin -> withRangeStats
[-> EMA]`` chain.

The eager mesh chain runs one jitted program per op (join, stats, EMA)
plus the alignment programs between them — every dispatch pays the
launch latency and re-reads its inputs from HBM.  The optimizer
rewrites the chain onto this module (``fused_asof_stats_ema`` node),
which traces the SAME shard-local kernels the eager ops use
(``dist._asof_planes``, ``dist._range_stats_block_packed``,
``pallas_kernels.ema_scan`` / ``ops.rolling.ema_compat``) into ONE
jitted program: one dispatch, results bitwise-identical to the
op-by-op chain (identical kernel functions over identical inputs),
XLA free to fuse across the op boundaries.

Guards: the fused program covers the plain fast path — series-only
mesh, ``skipNulls=True``, no sequence tie-break, no ``maxLookback``,
no host-resident / resampled / join-derived planes.  ``run`` returns
None when a run-time guard fails and the executor replays the chain
op-by-op instead (still planned + cached, just not single-program).
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from tempo_tpu import packing
from tempo_tpu.plan import ir

logger = logging.getLogger(__name__)

_STATS = packing.RANGE_STATS


def _fusible_frames(dl, dr) -> bool:
    from tempo_tpu.dist import DistributedTSDF

    if not (isinstance(dl, DistributedTSDF)
            and isinstance(dr, DistributedTSDF)):
        return False
    if dl.mesh is not dr.mesh and dl.mesh != dr.mesh:
        return False
    if any(size != 1 for name, size in dl.mesh.shape.items()
           if name != dl.series_axis):
        return False
    if dl.time_axis is not None or dr.time_axis is not None:
        return False
    if dl.partitionCols != dr.partitionCols:
        return False
    if dr.seq is not None or dl.resampled or dr.resampled:
        return False
    if dr.host_cols:
        return False
    plain = lambda cols: all(c.ts_chunk is None and c.host_gather is None
                             for c in cols.values())
    return (plain(dl.cols) and plain(dr.cols)
            and len(dl.cols) > 0 and len(dr.cols) > 0)


def run(dl, dr, node: ir.Node):
    """Execute the fused node over two DistributedTSDFs, or None when a
    run-time guard fails (executor falls back to op-by-op)."""
    if not _fusible_frames(dl, dr):
        return None
    from tempo_tpu import dist
    from tempo_tpu.dist import DistCol

    p = node.param
    lp = p("j_left_prefix")
    rp = p("j_right_prefix") or "right"
    rename = (lambda c: f"{lp}_{c}") if lp else (lambda c: c)

    l_names = list(dl.cols)
    r_names = list(dr.cols)
    joined = {rename(c): ("l", i) for i, c in enumerate(l_names)}
    joined.update({f"{rp}_{c}": ("r", i) for i, c in enumerate(r_names)})

    s_cols = list(p("s_cols") or joined)   # default: all numeric planes
    srcs = []
    for c in s_cols:
        if c not in joined:
            return None
        srcs.append(joined[c])
    ema_src = None
    if p("has_ema"):
        e_col = p("e_col")
        if e_col not in joined:
            return None
        ema_src = joined[e_col]

    w = float(p("s_window", 1000))
    engine, rowbounds, sort_kernels = dl._range_engine_choice(w)
    perm, ok = dist._key_perm(dl.layout.key_frame, dr.layout.key_frame,
                              dl.partitionCols, dl.K_dev)

    from tempo_tpu import resilience

    merged = int(dl.L) + int(dr.L)
    limit = resilience.max_merged_lanes()
    if 0 < limit < merged:
        logger.info(
            "asofJoin(plan-fused): merged width %d exceeds the "
            "single-program limit %d — shard-local joins use the XLA "
            "bitonic oversize engine", merged, limit)

    n_taps = int(p("e_window", 30) or 0) + (1 if p("e_inclusive") else 0)
    program = _fused_program(
        dl.mesh, dl.series_axis, tuple(srcs), w, rowbounds, engine,
        sort_kernels, ema_src, float(p("e_exp_factor", 0.2) or 0.2),
        bool(p("e_exact", False)), n_taps)

    lvals = jnp.stack([dl.cols[c].values for c in l_names])
    lvalids = jnp.stack([dl.cols[c].valid for c in l_names])
    rvals = jnp.stack([dr.cols[c].values for c in r_names])
    rvalids = jnp.stack([dr.cols[c].valid for c in r_names])
    planes, vstack = _right_stacks(dr.ts, dr.mask, rvals, rvalids)
    out = program(dl.ts, lvals, lvalids, dr.ts, planes, vstack,
                  jnp.asarray(perm), jnp.asarray(ok))
    vals, found, stats, clips, ema_y = out

    n = len(r_names)
    new_cols = {rename(c): col for c, col in dl.cols.items()}
    new_host = {rename(c): src for c, src in dl.host_cols.items()}
    for i, c in enumerate(r_names):
        # the null mask is applied OUTSIDE the program, exactly like
        # the eager join does on its program's outputs
        new_cols[f"{rp}_{c}"] = DistCol(
            jnp.where(found[i], vals[i], jnp.nan), found[i],
            int64=dr.cols[c].int64)
    rts_name = f"{rp}_{dr.ts_col}"
    for j, shift in enumerate((42, 21, 0)):
        new_cols[f"__{rts_name}__c{j}"] = DistCol(
            vals[n + j], found[n + j], ts_chunk=(rts_name, shift))
    audits = list(dl.audits)
    for si, c in enumerate(s_cols):
        if rowbounds is not None:
            audits.append((
                f"withRangeStats({c}): %d rows had window frames "
                f"extending past the static row bounds {rowbounds}; "
                f"this is a tempo-tpu bug — please report it",
                clips[si],
            ))
        for ki, stat in enumerate(_STATS):
            new_cols[f"{stat}_{c}"] = DistCol(
                stats[si, ki], dl.mask, int64=(stat == "count"))
    if ema_src is not None:
        new_cols["EMA_" + p("e_col")] = DistCol(ema_y, dl.mask)
    return dl._with(cols=new_cols, audits=audits, host_cols=new_host,
                    ts_col=rename(dl.ts_col), seq=None, seq_col="")


#: ``donate_argnums`` of the fused program — the right-side payload
#: plane stack and its validity stack, freshly built per call by
#: :func:`_right_stacks` (never frame-owned), whose HBM buffers XLA
#: reuses for the equal-shaped ``raw``/``found`` outputs.  A single
#: source of truth: the jit declaration below AND the donation-applied
#: compiled contract (tempo_tpu/plan/contracts.py) both read it.
DONATE_ARGNUMS = (4, 5)


def _right_stacks(r_ts, r_mask, rvals, rvalids):
    """The right side's [n+3, K, L] payload-plane stack (values + the
    three 21-bit ts-chunk planes) and its validity stack.  Built
    OUTSIDE the fused program so both can be donated: each is exactly
    the shape/dtype of a program output (``raw``/``found``), so the
    two biggest input buffers of the chain are reused for the two
    biggest outputs instead of doubling the working set.  Integer
    shift/concat ops only — bitwise identical to the former in-program
    construction."""
    from tempo_tpu import dist

    dt = rvals.dtype
    ts_chunks = jnp.stack(dist.ts_chunk_planes(r_ts, dt))
    planes = jnp.concatenate([rvals, ts_chunks])
    vstack = jnp.concatenate(
        [rvalids, jnp.broadcast_to(r_mask[None], (3,) + r_mask.shape)])
    return planes, vstack


@functools.lru_cache(maxsize=64)
def _fused_program(mesh, series_axis: str, stats_srcs: Tuple,
                   w: float, rowbounds, engine: str, sort_kernels: bool,
                   ema_src, alpha: float, exact: bool, n_taps: int):
    """One jitted program for the whole chain.  The global section
    (key-space alignment) and the shard_map'd local section (join
    fill, range stats, EMA scan) compile together; on a series mesh
    the collective-free kernels partition trivially.  The right-side
    stacks arrive pre-built (:func:`_right_stacks`) and DONATED
    (:data:`DONATE_ARGNUMS`): their buffers alias the ``raw``/``found``
    outputs in the compiled executable — verified against the compiled
    HLO by the donation-applied contract rule."""
    from tempo_tpu import dist
    from tempo_tpu.ops import pallas_kernels as pk
    from tempo_tpu.ops import rolling as rk
    from tempo_tpu.parallel.halo import shard_map

    sp2 = dist._spec(mesh, series_axis, None)
    sp3 = dist._spec(mesh, series_axis, None, ndim=3)
    sp4 = dist._spec(mesh, series_axis, None, ndim=4)
    n_stats = len(stats_srcs)

    def local(l_ts, lvals, lvalids, r_ts_al, vstack, pstack):
        raw, found = dist._asof_planes(l_ts, r_ts_al, vstack, pstack,
                                       sort_kernels, 0)
        n = raw.shape[0] - 3
        # op-boundary pinning — the planned==eager contract is BITWISE:
        # the eager chain materialises the join program's outputs
        # between dispatches, and ``raw``/``found`` must leave THIS
        # program in that same raw form (returned below) or XLA re-fuses
        # the join into the downstream stats arithmetic and the
        # FMA-contraction decisions drift in the last ulp at
        # cancellation-sensitive windows.  The barriers pin the stats
        # inputs/outputs to the same cluster roots the op-by-op chain
        # has.  (The fused program still saves the per-op dispatches
        # and the alignment round trips.)
        right_vals, found_b = jax.lax.optimization_barrier(
            (jnp.where(found[:n], raw[:n], jnp.nan), found[:n]))

        def plane(src):
            side, i = src
            if side == "l":
                return lvals[i], lvalids[i]
            return right_vals[i], found_b[i]

        # multi-column payload packing: ONE packed range-stats pass
        # over the [S, K, L] source stack — the timestamp planes cross
        # HBM once per kernel pack instead of once per summarized
        # column.  The packed block fn is the SAME function the eager
        # mesh chain now runs (dist.withRangeStats — per-column math
        # bitwise-identical to the unpacked kernels), so the
        # planned==eager bit-identity contract is preserved by
        # construction.
        planes_sv = [plane(src) for src in stats_srcs]
        xs = jnp.stack([x for x, _ in planes_sv])
        vs = jnp.stack([v for _, v in planes_sv])
        # pin the stats INPUTS too: in the eager chain (ts, xs, vs)
        # are program inputs of the packed stats program — their own
        # cluster roots.  Without this barrier the input-output
        # aliasing that donation declares (DONATE_ARGNUMS) reshapes
        # the stats fusion clusters and the var/stddev FMA-contraction
        # decisions drift in the last ulp, breaking the bitwise
        # planned==eager contract.
        s_ts, xs, vs = jax.lax.optimization_barrier((l_ts, xs, vs))
        st, clipped = dist._range_stats_block_packed(s_ts, xs, vs, w,
                                                     rowbounds, engine)
        # pin the op boundary: in the eager chain the packed stats
        # dict is a program OUTPUT (its own fusion-cluster root); the
        # [S, 7, K, L] stack below would otherwise reshape the
        # clusters and flip FMA-contraction decisions in the
        # var/stddev math — visible as last-ulp drift exactly at the
        # cancellation-sensitive windows
        st = jax.lax.optimization_barrier(st)
        stats = jnp.stack([jnp.stack([st[k][si] for k in _STATS])
                           for si in range(n_stats)])  # [S, 7, K, L]
        clips = jax.lax.psum(clipped, series_axis)     # [S]
        if ema_src is not None:
            x, v = plane(ema_src)
            ema_y = (pk.ema_scan(x, v, alpha) if exact
                     else rk.ema_compat(x, v, n_taps, alpha))
            ema_y = jax.lax.optimization_barrier(ema_y)
        else:
            ema_y = jnp.zeros_like(l_ts, dtype=lvals.dtype)
        return raw, found, stats, clips, ema_y

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(sp2, sp3, sp3, sp2, sp3, sp3),
        out_specs=(sp3, sp3, sp4, jax.sharding.PartitionSpec(None),
                   sp2))

    def fn(l_ts, lvals, lvalids, r_ts, planes, vstack, perm, ok):
        # key-space alignment (dist._align_fn / _align3_fn bodies)
        r_ts_al = jnp.where(
            ok[:, None],
            jnp.take(r_ts, jnp.clip(perm, 0, r_ts.shape[0] - 1), axis=0),
            jnp.asarray(packing.TS_PAD, r_ts.dtype))
        clip2 = jnp.clip(perm, 0, planes.shape[1] - 1)
        pstack = jnp.where(
            ok[None, :, None], jnp.take(planes, clip2, axis=1),
            jnp.asarray(np.nan, planes.dtype))
        vstack = jnp.where(
            ok[None, :, None], jnp.take(vstack, clip2, axis=1), False)
        return sharded(l_ts, lvals, lvalids, r_ts_al, vstack, pstack)

    # explicit stage shardings: operands arrive exactly as the frames
    # hold them (series-sharded planes, replicated K-sized alignment
    # metadata) and outputs leave pinned to the frame layout — a
    # mis-laid operand raises instead of compiling an implicit reshard
    ns = lambda s: jax.sharding.NamedSharding(mesh, s)
    repl = ns(jax.sharding.PartitionSpec())
    return jax.jit(
        fn,
        in_shardings=(ns(sp2), ns(sp3), ns(sp3), ns(sp2), ns(sp3),
                      ns(sp3), repl, repl),
        out_shardings=(ns(sp3), ns(sp3), ns(sp4), repl, ns(sp2)),
        donate_argnums=DONATE_ARGNUMS)


def compiled_cost(dl, dr, node: ir.Node):
    """XLA cost/memory analysis of the fused program over these frames
    (the ``explain(cost=True)`` numbers)."""
    if not _fusible_frames(dl, dr):
        return None
    from tempo_tpu import dist, profiling

    p = node.param
    lp = p("j_left_prefix")
    rp = p("j_right_prefix") or "right"
    rename = (lambda c: f"{lp}_{c}") if lp else (lambda c: c)
    joined = {rename(c): ("l", i) for i, c in enumerate(dl.cols)}
    joined.update({f"{rp}_{c}": ("r", i) for i, c in enumerate(dr.cols)})
    s_cols = list(p("s_cols") or joined)
    if any(c not in joined for c in s_cols):
        return None
    srcs = tuple(joined[c] for c in s_cols)
    ema_src = joined.get(p("e_col")) if p("has_ema") else None
    if p("has_ema") and ema_src is None:
        return None
    w = float(p("s_window", 1000))
    engine, rowbounds, sort_kernels = dl._range_engine_choice(w)
    perm, ok = dist._key_perm(dl.layout.key_frame, dr.layout.key_frame,
                              dl.partitionCols, dl.K_dev)
    n_taps = int(p("e_window", 30) or 0) + (1 if p("e_inclusive") else 0)
    program = _fused_program(
        dl.mesh, dl.series_axis, srcs, w, rowbounds, engine,
        sort_kernels, ema_src, float(p("e_exp_factor", 0.2) or 0.2),
        bool(p("e_exact", False)), n_taps)
    lvals = jnp.stack([c.values for c in dl.cols.values()])
    lvalids = jnp.stack([c.valid for c in dl.cols.values()])
    rvals = jnp.stack([c.values for c in dr.cols.values()])
    rvalids = jnp.stack([c.valid for c in dr.cols.values()])
    planes, vstack = _right_stacks(dr.ts, dr.mask, rvals, rvalids)
    return profiling.compiled_cost(
        program, dl.ts, lvals, lvalids, dr.ts, planes, vstack,
        jnp.asarray(perm), jnp.asarray(ok))
