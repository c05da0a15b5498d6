"""Cost-based plan decisions: engine picks, fusion, reshard placement.

Until round 11 every planner decision was *rule-based*: hand-set
thresholds (``TEMPO_TPU_STREAM_MAX_ROWS``, ``TEMPO_TPU_JOIN_CHUNK_LANES``,
the ~205K merged-lane ceiling) decided which engine ran, fusion always
fired when its guards held, and reshard placement always placed.  This
module is the Catalyst-style cost layer over the same decisions: every
choice is an argmin over *estimated seconds* computed from

* **byte models** — the same per-plane accounting the compiled tier
  audits (``profiling.comm_bytes_from_compiled`` byte-exact on the CPU
  mesh, padding headroom from ``profiling.COLLECTIVE_TOLERANCE``) and
  the roofline bytes-minimal math (``profiling.window_roofline``);
* **measured rates** — the single-chip stream rate the bench measures
  (BENCH r5: ~675 GB/s achieved on the streaming kernels) as the prior,
  overridable per-process by :func:`set_measured` (the bench and the
  round-12 autotuner feed re-measured rates back in);
* **demoted thresholds** — the old knob values survive as *priors*
  (feasibility bounds and default chunk widths), not laws: they gate
  which engines are candidates, the cost decides among candidates.

**The bitwise contract bounds what cost may decide.**  A cost-decided
plan must stay bitwise-identical to its rule-based twin, so the argmin
runs over the *bitwise-equal candidate set* only:

* AS-OF join engines (single / chunked / bracket) are all bit-identical
  to each other (round 3), so the join argmin is free within resource
  feasibility — this is the pick that genuinely flips when the cost
  inputs change.
* The range-stats engines (shifted / stream / chunked / windowed)
  differ in f32 rounding order, so the revalidation lattice from round
  5 (``ops/rolling.pick_range_engine``: shifted iff it fits, else
  stream iff it fits, else chunked iff the series outgrow one chunk
  block, else windowed) admits exactly ONE bitwise-safe engine
  per shape — the cost numbers are computed and rendered
  (``explain()``), but the argmin is over that singleton by
  construction.
* Fusing the mesh chain into one program and plan-placed resharding
  are both bitwise-identical to their unfused/declarative twins
  (rounds 5 and 10 pin this), so both decisions are free to flip.

``TEMPO_TPU_COST_MODEL=0`` switches every consumer back to the pure
rule-based decisions.  :func:`fingerprint` folds the active cost inputs
into the executable-cache key, so flipping an input re-plans instead of
replaying a stale decision.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
import threading
from typing import Dict, Optional, Tuple

#: Per-merged-lane traffic of an AS-OF join engine pass: i64 key read,
#: f32 payload read + bool validity, f32 result write.  One shared
#: constant — the engines move the same compulsory bytes, they differ
#: in rate and per-chunk overhead.
JOIN_LANE_BYTES = 17

#: Per-row traffic of a range-stats pass (i64 key + f32 value + bool
#: validity in, 7 f32 stat planes out) — window_roofline's
#: bytes-minimal accounting at one summarized column.
STATS_ROW_BYTES = 8 + 4 + 1 + 7 * 4

#: Cost priors.  Rates are bytes/sec, overheads are seconds.  The
#: stream rate is the measured single-chip figure (BENCH r5 streaming
#: kernels); the host rate is the measured pandas-bracket order of
#: magnitude; the windowed penalty is the measured shifted/windowed
#: ratio from the rolling_crossover record (175M vs 8M rows/s).
#: :func:`set_measured` overlays any of these with fresher numbers.
PRIORS: Dict[str, float] = {
    "hbm_stream_rate": 675e9,
    "join_single_rate": 675e9,
    "join_chunked_rate": 675e9,
    "host_bracket_rate": 0.5e9,
    "ici_rate": 45e9,
    "dispatch_overhead_s": 50e-6,
    "chunk_overhead_s": 15e-6,
    "fused_overhead_s": 0.0,
    # prior 0: the mesh-scaling bench measured no per-dispatch penalty
    # for a placed reshard program vs in-op pairs, so under the priors
    # placement wins whenever it moves no MORE bytes than the internal
    # pairs it eliminates (ties place — today's rule); a measured
    # override charges the dispatch and can flip whole-plan placement
    "reshard_dispatch_s": 0.0,
    "windowed_gather_penalty": 20.0,
    # VMEM-resident shifted/stream passes re-touch their slab once per
    # window row at roughly this multiple of the HBM stream rate — the
    # term that makes wide windows expensive for the pass-based
    # engines (and reproduces the measured crossover where the
    # W-independent windowed RMQ form eventually wins)
    "vmem_pass_rate_multiple": 50.0,
}

_lock = threading.Lock()
_measured: Dict[str, float] = {}  # guarded-by: _lock

#: build-time pin: the executor snapshots the active inputs ONCE when
#: it computes the cache key and installs them here for the whole
#: optimize/build, so a concurrent ``set_measured`` (a live autotuner
#: feeding rates while the query service builds) can never bake
#: decisions into an executable cached under the OLD fingerprint.
_PINNED: contextvars.ContextVar[Optional[Dict[str, float]]] = \
    contextvars.ContextVar("tempo_tpu_cost_pinned", default=None)


@contextlib.contextmanager
def pinned(snapshot: Optional[Dict[str, float]]):
    """Run a block with the cost inputs pinned to ``snapshot`` (a
    :func:`params` result; None = no-op, for the cost-model-off
    path).  Every ``params()`` read inside the block — the optimizer
    passes, the engine picks they call — sees the snapshot."""
    if snapshot is None:
        yield
        return
    token = _PINNED.set(dict(snapshot))
    try:
        yield
    finally:
        _PINNED.reset(token)


def enabled() -> bool:
    """``TEMPO_TPU_COST_MODEL`` (default on).  Off = every consumer
    (``pick_join_engine``, the optimizer's fusion and reshard passes)
    returns to the pure rule-based decision."""
    from tempo_tpu import config

    return config.get_bool("TEMPO_TPU_COST_MODEL", True)


def set_measured(**inputs: float) -> None:
    """Overlay measured cost inputs over the priors (process-wide).
    Unknown names raise — the input space is the documented
    :data:`PRIORS` set plus the ``join_chunk_lanes`` demoted
    threshold.  ``TEMPO_TPU_STREAM_MAX_ROWS`` is deliberately NOT a
    cost input: it gates which range engine is *bitwise-legal* (the
    engines differ in f32 rounding), so overriding it here could flip
    result bits — widen the knob itself instead."""
    known = set(PRIORS) | {"join_chunk_lanes"}
    for name in inputs:
        if name not in known:
            raise KeyError(
                f"unknown cost input {name!r}: known inputs are "
                f"{sorted(known)}")
    with _lock:
        _measured.update({k: float(v) for k, v in inputs.items()})


def clear_measured() -> None:
    with _lock:
        _measured.clear()


def params() -> Dict[str, float]:
    """The active cost inputs: priors, then the tuned-profile overlay
    (tempo_tpu/tune — the autotuner's MEASURED rates for this image,
    e.g. the real saxpy stream rate instead of the BENCH r5 TPU
    figure), the demoted thresholds (read from their knobs — they are
    priors now, not laws), and any :func:`set_measured` overlay on
    top.  A loaded profile also contributes ``tune_profile_crc`` — an
    inert-to-the-arithmetic stamp that rides :func:`fingerprint` into
    the executable-cache key, so swapping profiles (which can change
    the kernel-structure knobs the rates don't cover) re-plans instead
    of replaying.  Inside a :func:`pinned` block the snapshot wins
    outright (build-time consistency)."""
    pin = _PINNED.get()
    if pin is not None:
        return dict(pin)
    from tempo_tpu import config, tune

    out = dict(PRIORS)
    out.update(tune.measured())
    crc = tune.stamp()
    if crc is not None:
        out["tune_profile_crc"] = crc
    # 32768 is the auto chunk-width CEILING of the streaming join's
    # VMEM plan (pallas_merge._plan_chunk_lanes doubles while
    # Cm <= 1 << 15) — a wider prior would undercount the per-chunk
    # overhead of chunk plans the engine can never actually run
    lanes = config.get_int("TEMPO_TPU_JOIN_CHUNK_LANES")
    if lanes is None:
        lanes = tune.knob_value("TEMPO_TPU_JOIN_CHUNK_LANES")
    out["join_chunk_lanes"] = float(lanes or 32768)
    with _lock:
        out.update(_measured)
    return out


def snapshot() -> Optional[Dict[str, float]]:
    """The active inputs as a build-time pin (None when the model is
    off): the executor keys the cache with
    ``fingerprint(snapshot)`` and optimizes under ``pinned(snapshot)``
    so key and decisions can never diverge mid-build."""
    return params() if enabled() else None


def fingerprint(snap: Optional[Dict[str, float]] = None) -> tuple:
    """Hashable digest of the cost inputs (``snap`` when given, else
    the live ones), folded into the executable-cache key
    (plan/executor.py): flipping an input must re-plan, never replay a
    decision made under the other inputs."""
    if snap is None:
        if not enabled():
            from tempo_tpu import tune

            crc = tune.stamp()
            # the tuned profile changes kernel-structure knobs (DMA
            # depth, pack width) even with the cost model off — its
            # stamp must still key the cache so a swap re-plans
            return ("cost-off",) if crc is None else ("cost-off", crc)
        snap = params()
    return tuple(sorted(snap.items()))


# ----------------------------------------------------------------------
# AS-OF join engines — the bitwise-free argmin
# ----------------------------------------------------------------------

def join_costs(est_lanes: int, limit: int,
               chunked_ok: bool) -> Dict[str, Optional[float]]:
    """Estimated seconds per join engine at ``est_lanes`` merged lanes;
    ``None`` marks an engine outside its resource feasibility (the old
    thresholds, now acting as candidate gates): ``single`` past the
    compiler ceiling, ``chunked`` where the Mosaic kernel cannot run."""
    p = params()
    nbytes = float(est_lanes) * JOIN_LANE_BYTES
    out: Dict[str, Optional[float]] = {
        "single": None, "chunked": None, "bracket": None}
    if limit <= 0 or est_lanes <= limit:
        out["single"] = nbytes / p["join_single_rate"] \
            + p["dispatch_overhead_s"]
    if chunked_ok:
        n_chunks = max(1, math.ceil(est_lanes / p["join_chunk_lanes"]))
        out["chunked"] = nbytes / p["join_chunked_rate"] \
            + p["dispatch_overhead_s"] + n_chunks * p["chunk_overhead_s"]
    out["bracket"] = nbytes / p["host_bracket_rate"] \
        + p["dispatch_overhead_s"]
    return out


def decide_join_engine(est_lanes: int, limit: int, chunked_ok: bool) -> str:
    """Cheapest feasible join engine.  All three engines are
    bit-identical (round 3), so the argmin is unconstrained within
    feasibility; under the default priors it reproduces the rule-based
    pick exactly (single under the ceiling, chunked past it, bracket
    last), and a measured rate/overhead override flips it — the
    flip-under-cost-inputs the round-11 acceptance demonstrates."""
    costs = join_costs(est_lanes, limit, chunked_ok)
    order = ("single", "chunked", "bracket")   # rule-order tie-break
    best = min((e for e in order if costs[e] is not None),
               key=lambda e: costs[e])
    return best


# ----------------------------------------------------------------------
# Range-stats engines — argmin over the bitwise-safe singleton
# ----------------------------------------------------------------------

def range_costs(W: int, n_elems: int) -> Dict[str, float]:
    """Estimated seconds per range-stats engine over ``n_elems`` rows
    with a (max_behind + max_ahead) row extent of ``W`` — the numbers
    the plan-time hoist (``optimizer._hoist_engines``) attaches to
    range_stats nodes for ``explain()`` to render next to the engine
    choice (host chains with derivable rowbounds).  Models:
    shifted/stream cross HBM once (roofline-minimal) but re-touch the
    VMEM-resident slab once per window row at
    ``vmem_pass_rate_multiple`` × the stream rate (stream pays one
    extra dispatch for its scalar prologue); windowed pays the
    measured RMQ gather penalty but is W-independent (prefix scans +
    log-doubling RMQ) — so the estimates reproduce the measured
    crossover where wide windows eventually favour the windowed
    form."""
    p = params()
    base = float(n_elems) * STATS_ROW_BYTES / p["hbm_stream_rate"]
    per_pass = (float(n_elems) * 4.0
                / (p["hbm_stream_rate"] * p["vmem_pass_rate_multiple"]))
    passes = max(1, int(W)) * per_pass
    return {
        "shifted": base + passes + p["dispatch_overhead_s"],
        "stream": base + passes + 2 * p["dispatch_overhead_s"],
        "windowed": base * p["windowed_gather_penalty"]
        + p["dispatch_overhead_s"],
    }


def decide_range_engine(W: int, n_elems: int, fits_shifted: bool,
                        fits_stream: bool, fits_chunked: bool = False) -> str:
    """Cheapest *bitwise-safe* range engine.  The engines differ in f32
    rounding order (MIGRATION v0.7), so the candidate set is the
    revalidation lattice's singleton — shifted iff it fits, else stream
    iff it fits, else the lane-chunked form iff the caller runs it and
    the series outgrow one chunk block, else windowed — and a cost
    argmin over one candidate can never flip the engine away from the
    rule-based pick (the bitwise contract wins over the cost model by
    design).  The :func:`range_costs` estimates are therefore NOT
    computed on this per-call path; they surface once per plan via the
    optimizer's engine hoist, which annotates the node for
    ``explain()``.  ``W`` and ``n_elems`` stay in the signature as the
    decision's cost-model inputs — a future bitwise-equal engine pair
    would argmin over them."""
    del W, n_elems                       # singleton candidate set
    if fits_shifted:
        return "shifted"
    if fits_stream:
        return "stream"
    if fits_chunked:
        return "chunked"
    return "windowed"


# ----------------------------------------------------------------------
# Fusion and reshard placement — bitwise-equal program shapes
# ----------------------------------------------------------------------

def fusion_worthwhile(n_ops: int, est_bytes: int) -> Tuple[bool, dict]:
    """Should a mesh ``asofJoin -> withRangeStats [-> EMA]`` run fuse
    into ONE jitted program (plan/fused.py)?  Both shapes are
    bitwise-identical (the fused program pins its op boundaries with
    optimization_barriers), so the decision is free: fused saves
    ``n_ops - 1`` dispatches and the between-op HBM re-reads; the
    ``fused_overhead_s`` input charges whatever a measured profile says
    one-program execution costs extra (0 under the priors — fusion
    always wins, today's rule)."""
    p = params()
    re_read = float(est_bytes) / p["hbm_stream_rate"]
    cost_chain = n_ops * p["dispatch_overhead_s"] + (n_ops - 1) * re_read
    cost_fused = p["dispatch_overhead_s"] + p["fused_overhead_s"]
    return cost_fused <= cost_chain, {
        "fused_s": cost_fused, "chain_s": cost_chain, "n_ops": n_ops}


def stitch_worthwhile(n_ops: int, est_bytes: int) -> Tuple[bool, dict]:
    """Should a maximal run of ``n_ops`` adjacent series-local planned
    ops (resample / interpolate / EMA / range stats / calc_bars) stitch
    into ONE jitted program (plan/stitch.py)?  Same shape as
    :func:`fusion_worthwhile` — both forms are bitwise-identical (the
    stitched program pins every op boundary with
    ``jax.lax.optimization_barrier``), so the decision is free: the
    op-by-op chain pays ``n_ops`` dispatches plus the between-op HBM
    re-reads of the intermediate frame; the stitched program pays one
    dispatch plus ``fused_overhead_s`` (0 under the priors — stitching
    always wins, and a measured profile can charge it)."""
    p = params()
    re_read = float(est_bytes) / p["hbm_stream_rate"]
    cost_chain = n_ops * p["dispatch_overhead_s"] + (n_ops - 1) * re_read
    cost_stitched = p["dispatch_overhead_s"] + p["fused_overhead_s"]
    return cost_stitched <= cost_chain, {
        "stitched_s": cost_stitched, "chain_s": cost_chain,
        "n_ops": n_ops}


def reshard_decision(n_placed: int, placed_bytes: Optional[int],
                     n_internal: int,
                     internal_bytes: Optional[int]) -> Tuple[bool, dict]:
    """Should the optimizer place explicit ``reshard`` plan nodes
    around this plan's series-local runs (vs leaving each op its
    internal all_to_all pair — ``declarative`` execution)?  Both
    placements are bitwise-identical (round 10's elimination contract),
    so the decision is free: per-switch comm seconds from the relayout
    byte model over the ICI rate, plus ``reshard_dispatch_s`` for each
    placed node (a separate program dispatch; internal pairs ride
    inside the op's own program).  Byte models unavailable (geometry
    not derivable at plan time) fall back to switch counts.  Under the
    priors placement wins whenever it eliminates at least one switch —
    today's rule."""
    p = params()
    if placed_bytes is not None and internal_bytes is not None:
        placed_s = placed_bytes / p["ici_rate"] \
            + n_placed * p["reshard_dispatch_s"]
        internal_s = internal_bytes / p["ici_rate"]
    else:
        # count-only fallback: a nominal 1 MiB per switch (the byte
        # model is unavailable, the *ratio* of switch counts decides)
        per_switch = float(1 << 20) / p["ici_rate"]
        placed_s = n_placed * (per_switch + p["reshard_dispatch_s"])
        internal_s = n_internal * per_switch
    return placed_s <= internal_s, {
        "placed_s": placed_s, "declarative_s": internal_s,
        "n_placed": n_placed, "n_internal_switches": n_internal}
