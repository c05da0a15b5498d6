"""The registered knob space the autotuner sweeps.

Every axis here is a ``TEMPO_TPU_*`` knob declared in
``tempo_tpu/config.py`` whose value is a *performance* choice — the
sweep measures each candidate in a child process and the bitwise audit
gate decides whether the candidate is even admissible:

* ``bitwise_neutral=True`` axes (DMA ring depth, pack width, megacore
  partitioning, serve micro-batch rows, join chunk width) carry a
  kernel-identity CONTRACT: every value must produce bit-identical
  results (pinned by the round-6/3/12 test matrices).  A digest
  mismatch on such an axis is an identity REGRESSION — the sweep
  records it and ``python -m tempo_tpu.tune --smoke`` exits nonzero.
* ``bitwise_neutral=False`` axes (``TEMPO_TPU_STREAM_MAX_ROWS``) gate
  which engine is *legal* for a shape; a candidate that flips the
  engine changes f32 rounding order and is *rejected by the audit* —
  that is the gate working, not a failure.  Such an axis can never
  crown a winner either: a same-bits candidate left the engine pick
  unchanged and the ceiling is unread inside the chosen engine, so any
  measured win is child noise — and a shipped ceiling could flip the
  engine at shapes the probe never ran.  The axis rides the sweep
  purely as the audit surface; the default ceiling always stands.

Shape classes mirror the engine regimes: the dense/medium streaming
stats kernels (50 Hz and 10 Hz ticks), the column-packed streaming
kernel, the fused join+stats+EMA chain (``__graft_entry__``'s step), the lane-chunked AS-OF join (TPU-only: the Mosaic kernel),
the serving micro-batch executor, and the PR 17 dispatch-floor planes
(the slab-pipeline ring depth, the whole-chain stitch length, and the
cohort dispatch-coalescing window).  Each knob has exactly ONE
owning class (``owns``) whose winner feeds the profile's merged knob
set — the other classes sweeping the same knob are cross-checks whose
results are recorded but never merged.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple


class Axis(NamedTuple):
    """One knob ladder, walked in declared order from the default
    (``values[0]`` — the incumbent, measured once as the class
    baseline).  ``None`` as a value means "unset" (the knob's automatic
    choice).  ``smoke_values`` is the clipped ladder of the CI smoke
    sweep."""

    knob: str
    values: Tuple
    smoke_values: Tuple
    bitwise_neutral: bool = True


class ShapeClass(NamedTuple):
    name: str
    probe: str              # tools/tune_probe.py <probe>
    axes: Tuple[Axis, ...]
    owns: Tuple[str, ...]   # knobs whose winner feeds profile["knobs"]
    requires_tpu: bool = False
    doc: str = ""


SPACE: Tuple[ShapeClass, ...] = (
    ShapeClass(
        "stream_dense", "stream_dense",
        axes=(
            Axis("TEMPO_TPU_DMA_BUFFERS", (2, 3, 4, 6, 8), (2, 4)),
            Axis("TEMPO_TPU_MEGACORE", (1, 0), (1, 0)),
        ),
        owns=("TEMPO_TPU_DMA_BUFFERS", "TEMPO_TPU_MEGACORE"),
        doc="~50 Hz density: the streaming window engine's "
            "home regime — owns the DMA ring depth + megacore knobs"),
    ShapeClass(
        "stream_medium", "stream_medium",
        axes=(
            Axis("TEMPO_TPU_DMA_BUFFERS", (2, 3, 4, 6, 8), (2, 4)),
            Axis("TEMPO_TPU_STREAM_MAX_ROWS", (16384, 8192, 32768),
                 (16384, 32768), bitwise_neutral=False),
        ),
        owns=("TEMPO_TPU_STREAM_MAX_ROWS",),
        doc="~10 Hz density near the engine crossover — owns the "
            "stream-engine row ceiling (audit-gated: a value that flips "
            "the engine changes bits and is rejected; same-bits values "
            "never win either, so the default ceiling always ships)"),
    ShapeClass(
        "packed_stream", "packed_stream",
        axes=(
            Axis("TEMPO_TPU_PACK_COLS", (None, 8, 4, 2, 1), (None, 2)),
        ),
        owns=("TEMPO_TPU_PACK_COLS",),
        doc="C=4 column-packed streaming stats (one key-plane read per "
            "pack) — owns the pack-width cap"),
    ShapeClass(
        "fused_chain", "fused_chain",
        axes=(
            Axis("TEMPO_TPU_DMA_BUFFERS", (2, 4), (2, 4)),
        ),
        owns=(),
        doc="the fused asof+stats+EMA composite — a cross-check that "
            "the stream-class winners hold on the whole chain (owns "
            "nothing; its sweep is recorded, never merged)"),
    ShapeClass(
        "join_chunk", "join_chunk",
        axes=(
            Axis("TEMPO_TPU_JOIN_CHUNK_LANES",
                 (None, 4096, 8192, 16384, 32768), (None, 4096)),
        ),
        owns=("TEMPO_TPU_JOIN_CHUNK_LANES",),
        requires_tpu=True,
        doc="the lane-chunked streaming AS-OF join (Mosaic kernel) — "
            "TPU-only; on other backends the class is recorded "
            "hardware-gated, not faked"),
    ShapeClass(
        "serve_batch", "serve_batch",
        axes=(
            Axis("TEMPO_TPU_SERVE_BATCH_ROWS", (64, 16, 32, 128, 256),
                 (64, 32)),
        ),
        owns=("TEMPO_TPU_SERVE_BATCH_ROWS",),
        doc="the serving micro-batch executor under a deterministic "
            "tick load — owns the per-series micro-batch row cap"),
    ShapeClass(
        "ingest_sweep", "ingest_sweep",
        axes=(
            Axis("TEMPO_TPU_INGEST_RING", (2, 1, 4, 8), (2, 4)),
        ),
        owns=("TEMPO_TPU_INGEST_RING",),
        doc="the three-stage slab pipeline (io/ingest.sweep_slabs: "
            "decode thread / in-order compute / drain thread) — owns "
            "the slab-buffer ring depth; any depth is bitwise "
            "identical by construction (in-order consumption), so a "
            "digest mismatch is an ordering regression"),
    ShapeClass(
        "stitched_chain", "stitched_chain",
        axes=(
            Axis("TEMPO_TPU_STITCH_MAX_OPS", (8, 1, 4, 16), (8, 1)),
        ),
        owns=("TEMPO_TPU_STITCH_MAX_OPS",),
        doc="the whole-chain program stitcher (plan/stitch.py) on a "
            "resample->EMA->range_stats planned chain — owns the max "
            "stitch run length; every value is bitwise (stitch "
            "boundaries are optimization_barrier-pinned, so stitched "
            "== per-op chain bit-for-bit)"),
    ShapeClass(
        "serve_cohort", "serve_cohort",
        axes=(
            Axis("TEMPO_TPU_SERVE_COALESCE_S",
                 (0.002, 0.0, 0.001, 0.004, 0.008), (0.002, 0.0)),
        ),
        owns=("TEMPO_TPU_SERVE_COALESCE_S",),
        doc="the cohort executor's dispatch-coalescing window under a "
            "deterministic Poisson tick load — owns the only "
            "float-valued knob (profile.FLOAT_KNOBS); the window only "
            "moves the micro-batch split, never per-(slot,row) state "
            "math, so every value is bitwise"),
)


def classes(names=None, smoke: bool = False):
    """The shape classes to sweep: all of them, or the named subset.
    The smoke sweep defaults to one stream class + the serve class —
    the CI gate's 'tiny shape' coverage of both probe families."""
    if names:
        by_name = {c.name: c for c in SPACE}
        unknown = [n for n in names if n not in by_name]
        if unknown:
            raise KeyError(
                f"unknown shape class(es) {unknown}: "
                f"known = {[c.name for c in SPACE]}")
        return tuple(by_name[n] for n in names)
    if smoke:
        return tuple(c for c in SPACE
                     if c.name in ("stream_medium", "serve_batch"))
    return SPACE


def axis_values(axis: Axis, smoke: bool = False) -> Tuple:
    return axis.smoke_values if smoke else axis.values
