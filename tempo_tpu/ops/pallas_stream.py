"""Explicit DMA pipelining for the HBM-stream-bound kernels.

The streaming kernels (``ops/pallas_window.py``, ``ops/pallas_bucket.py``)
ran at 0.18-0.28 of the *measured* 675 GB/s stream rate (the
``roofline`` of the pre-PR-1 chip bench): every grid step's HBM->VMEM
block copy rode Mosaic's implicit BlockSpec pipeline, which is fixed at
double buffering and couples the copy granularity to the compute
granularity.  This module
provides the two mechanisms BlockSpecs cannot express:

* :func:`ring_call` — an **N-deep input ring**: the operands stay in
  HBM (``memory_space=ANY``) and the kernel streams row slabs through
  ``pltpu.make_async_copy`` into a ``TEMPO_TPU_DMA_BUFFERS``-slot VMEM
  ring, so the copy of slab *i+N-1* overlaps the compute of slab *i*
  (depth-2 is exactly the implicit pipeline's overlap; deeper rings
  smooth slabs whose compute time varies).  Outputs stage through a
  double-buffered VMEM slab pair and DMA out asynchronously, so the
  write of slab *i* overlaps the compute of slab *i+1* — the implicit
  pipeline serialises the final writeback of each step.  The slab loop
  is a *python* loop (static trip count, static ring slots): no
  dynamic-slot indexing for Mosaic to spill, at the cost of a
  per-slab-count compile (bounded by :data:`MAX_RING_SLABS`).
* :func:`grid_semantics` — megacore grid partitioning: carry-free grid
  axes are declared ``"parallel"`` so Mosaic splits them across both
  TensorCores on megacore parts (v4/v5p; a no-op on single-core v5e).
  Axes with cross-step carry state (the chunked merge's fill scratch,
  any manual ring) MUST stay ``"arbitrary"`` — a parallel split would
  hand half the sequential carry chain to each core.  Callers name
  their carry axes; this function never guesses.

Both knobs are registered in ``tempo_tpu/config.py`` and documented in
BUILDING.md ("Roofline methodology"); bitwise identity of the ring
path against the BlockSpec path is pinned in
tests/test_pallas_window.py / test_pallas_bucket.py.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tempo_tpu.ops import pallas_kernels as pk

#: Ring slab-loop ceiling: the loop is python-unrolled (static slots —
#: Mosaic never sees a dynamic ring index), so the trace grows linearly
#: with the slab count; past this the BlockSpec pipeline path wins on
#: compile time and callers must fall back.
MAX_RING_SLABS = 256


def dma_buffers() -> int:
    """``TEMPO_TPU_DMA_BUFFERS`` — HBM->VMEM buffer depth.  2 (the
    default) keeps the implicit double-buffered BlockSpec pipeline;
    3..8 engage the explicit ring.  Clamped to [2, 8]: one buffer
    cannot overlap anything, and past 8 the ring's VMEM share starves
    the compute planes.  Env unset falls back to the tuned-profile
    prior (tempo_tpu/tune — the autotuner's measured winner for this
    device kind), then to the built-in 2."""
    from tempo_tpu import config, tune

    n = config.get_int("TEMPO_TPU_DMA_BUFFERS")
    if n is None:
        n = tune.knob_value("TEMPO_TPU_DMA_BUFFERS") or 2
    return max(2, min(int(n), 8))


def megacore_enabled() -> bool:
    """``TEMPO_TPU_MEGACORE`` — declare carry-free grid axes
    ``"parallel"`` (default on; harmless on single-core chips).  Env
    unset falls back to the tuned-profile prior, then on."""
    from tempo_tpu import config, tune

    val = config.get("TEMPO_TPU_MEGACORE")
    if val is None:
        tuned = tune.knob_value("TEMPO_TPU_MEGACORE")
        return True if tuned is None else bool(int(tuned))
    return config.get_bool("TEMPO_TPU_MEGACORE", True)


def grid_semantics(n_axes: int, carry_axes: Sequence[int] = ()):
    """``dimension_semantics`` for an ``n_axes`` grid whose
    ``carry_axes`` hold cross-step state (VMEM scratch carries, manual
    DMA rings).  Carry axes are always ``"arbitrary"`` — that is a
    legality rule, not a preference: Mosaic's megacore split hands each
    TensorCore a contiguous sub-range of a ``"parallel"`` axis, and a
    carry chain cut in half computes garbage on the second core.  The
    knob only widens/narrows the *remaining* axes."""
    if n_axes <= 0:
        return None
    on = megacore_enabled()
    return tuple(
        "arbitrary" if (i in carry_axes or not on) else "parallel"
        for i in range(n_axes)
    )


def ring_plan(K_pad: int, bk: int, depth: int):
    """(n_slabs, depth) of a feasible ring over ``K_pad`` padded rows in
    ``bk``-row slabs, or None when the ring cannot help (fewer than two
    slabs: nothing to overlap) or cannot compile cheaply (slab count
    past :data:`MAX_RING_SLABS` — the loop is python-unrolled)."""
    n_slabs = K_pad // bk
    if n_slabs < 2 or n_slabs > MAX_RING_SLABS:
        return None
    return n_slabs, max(2, min(depth, n_slabs))


def plan_with_ring(K: int, L: int, arrays_fn, depth: int,
                   bk_max: int = 32, budget: int = 90 * 2**20):
    """(grid, bk, K_pad, use_ring): block plan at the requested DMA
    depth, falling back to the implicit depth-2 BlockSpec pipeline
    when the N-deep ring's larger plane budget — ``arrays_fn(depth)``
    in [bk, L] f32 units — or the slab ring itself is infeasible.  The
    feasibility gates (``stream_supported`` & co) budget for depth 2,
    so a gated call must never crash merely because the
    ``TEMPO_TPU_DMA_BUFFERS`` knob is set high for a near-boundary
    shape.  Returns None only when even the depth-2 plan fails."""
    if depth > 2:
        p = pk._plan(K, L, arrays=arrays_fn(depth), bk_max=bk_max,
                     budget=budget)
        if p is not None and ring_plan(p[2], p[1], depth) is not None:
            return (*p, True)
    p = pk._plan(K, L, arrays=arrays_fn(2), bk_max=bk_max,
                 budget=budget)
    return None if p is None else (*p, False)


def pack_cols_cap() -> int:
    """``TEMPO_TPU_PACK_COLS`` — cap on the payload pack width; unset
    = the tuned-profile prior (tempo_tpu/tune), then the VMEM folding
    alone (bounded at 8: past that the per-step block shrinks below a
    sublane and the grid overhead eats the saved key reads)."""
    from tempo_tpu import config, tune

    n = config.get_int("TEMPO_TPU_PACK_COLS")
    if n is None:
        n = tune.knob_value("TEMPO_TPU_PACK_COLS")
    return max(1, min(int(n), 8)) if n else 8


def pack_budget(K: int, L: int, n_cols: int, arrays_fn,
                bk_max: int = 32, budget: int = 90 * 2**20) -> int:
    """Largest pack width c <= min(``n_cols``, :func:`pack_cols_cap`)
    whose [c, bk, L] block plan — ``arrays_fn(c)`` in [bk, L] f32
    plane units — fits the VMEM budget: the dynamic twin of the static
    analyzer's vmem-budget folding, shared by the window and bucket
    packers so their cap/clamp semantics cannot diverge.  Returns at
    least 1 (a single column either fits or the caller's per-column
    gate already rejected the shape)."""
    c = min(int(n_cols), pack_cols_cap())
    while c > 1:
        if pk._plan(int(K), int(L), arrays=arrays_fn(c), bk_max=bk_max,
                    budget=budget) is not None:
            return c
        c -= 1
    return 1


def _slab(ref, i: int, bk: int):
    """HBM slice of row slab ``i``: rank-2 planes block over rows,
    rank-3 (column-packed) planes over the middle axis."""
    if len(ref.shape) == 2:
        return ref.at[pl.ds(i * bk, bk)]
    return ref.at[:, pl.ds(i * bk, bk)]


def _make_ring_kernel(math, n_scalar: int, n_in: int, n_out: int,
                      bk: int, n_slabs: int, depth: int, as_bool=()):
    """Kernel closure running ``math`` over every row slab with the
    N-deep input ring and double-buffered output staging.  ``math``
    takes (scalar_refs_tuple, slab_arrays_list) and returns ``n_out``
    f32 arrays shaped like the out-template slab.  Inputs listed in
    ``as_bool`` ride the ring as int32 (Mosaic DMAs no bool) and reach
    ``math`` as bool again."""

    def kernel(*refs):
        scalar_refs = refs[:n_scalar]
        in_refs = refs[n_scalar:n_scalar + n_in]
        out_refs = refs[n_scalar + n_in:n_scalar + n_in + n_out]
        sc = n_scalar + n_in + n_out
        rings = refs[sc:sc + n_in]
        stages = refs[sc + n_in:sc + n_in + n_out]
        in_sem = refs[sc + n_in + n_out]
        out_sem = refs[sc + n_in + n_out + 1]

        def in_dma(i: int, j: int):
            return pltpu.make_async_copy(
                _slab(in_refs[j], i, bk),
                rings[j].at[i % depth],
                in_sem.at[i % depth, j],
            )

        def out_dma(i: int, t: int):
            return pltpu.make_async_copy(
                stages[t].at[i % 2],
                _slab(out_refs[t], i, bk),
                out_sem.at[i % 2, t],
            )

        # warm-up: keep depth-1 slab copies in flight ahead of compute
        for i in range(min(depth - 1, n_slabs)):
            for j in range(n_in):
                in_dma(i, j).start()
        for i in range(n_slabs):
            slot = i % depth
            nxt = i + depth - 1
            if nxt < n_slabs:
                for j in range(n_in):
                    in_dma(nxt, j).start()
            for j in range(n_in):
                in_dma(i, j).wait()
            slabs = [rings[j][slot] for j in range(n_in)]
            outs = math(scalar_refs, [a != 0 if j in as_bool else a
                                      for j, a in enumerate(slabs)])
            # the stage pair is reused every other slab: the write of
            # slab i-2 must have landed before slab i overwrites it
            if i >= 2:
                for t in range(n_out):
                    out_dma(i - 2, t).wait()
            for t in range(n_out):
                stages[t][i % 2] = outs[t]
                out_dma(i, t).start()
        for i in range(max(n_slabs - 2, 0), n_slabs):
            for t in range(n_out):
                out_dma(i, t).wait()

    return kernel


def ring_call(math, scalars: Sequence, planes: Sequence, n_out: int,
              out_like: int, bk: int, depth: int,
              interpret: bool = False) -> Tuple:
    """Run ``math`` over row slabs of ``planes`` through the explicit
    DMA ring.  ``scalars`` ride SMEM; ``planes`` ([K_pad, L] or
    column-packed [C, K_pad, L], K_pad a multiple of ``bk``) stay in
    HBM and stream slab-by-slab; the ``n_out`` outputs are f32 arrays
    shaped like ``planes[out_like]``.  Callers are responsible for the
    VMEM plan (ring + stage + math temporaries must fit — the static
    analyzer's vmem-budget rule folds the declared ring/stage scratch
    at its full N-deep shape) and for checking :func:`ring_plan`."""
    planes = [jnp.asarray(p) for p in planes]
    as_bool = tuple(j for j, p in enumerate(planes) if p.dtype == jnp.bool_)
    planes = [p.astype(jnp.int32) if j in as_bool else p
              for j, p in enumerate(planes)]
    K_pad = planes[0].shape[-2]
    plan = ring_plan(K_pad, bk, depth)
    if plan is None:
        raise ValueError(
            f"no feasible DMA ring at K_pad={K_pad}, bk={bk}: "
            f"ring_plan returned None — use the BlockSpec path")
    n_slabs, depth = plan
    n_scalar = len(scalars)
    n_in = len(planes)
    slab_shape = lambda p: p.shape[:-2] + (bk, p.shape[-1])
    out_tpl = planes[out_like]
    scratch = (
        [pltpu.VMEM((depth,) + slab_shape(p), p.dtype) for p in planes]
        + [pltpu.VMEM((2,) + slab_shape(out_tpl), jnp.float32)
           for _ in range(n_out)]
        + [pltpu.SemaphoreType.DMA((depth, n_in)),
           pltpu.SemaphoreType.DMA((2, n_out))]
    )
    kernel = _make_ring_kernel(math, n_scalar, n_in, n_out, bk,
                               n_slabs, depth, as_bool)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * n_scalar
            + [pl.BlockSpec(memory_space=pl.ANY)] * n_in,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_out,
            out_shape=[jax.ShapeDtypeStruct(out_tpl.shape, jnp.float32)]
            * n_out,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024,
            ),
            interpret=interpret,
        )(*scalars, *planes)
    return tuple(out)
