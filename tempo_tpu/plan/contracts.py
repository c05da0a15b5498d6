"""Compiled-artifact contracts: the guarantees a production program
makes about what XLA *actually compiled*, declared next to the
programs and machine-checked by the compiled-contract analyzer tier
(``python tools/analyze.py --compiled``, ``tools/analysis/compiled``).

The AST tier (``tools/analysis``) reasons about source; everything the
rebuild promises *about executables* — bitwise identity across
backends (no f64 creep under the f32 policy), comm-bytes models,
donation, stage-chained shardings, zero host round-trips — lives in
the lowered/compiled artifact and can drift without any source-level
symptom.  ``profiling.comm_bytes_from_compiled`` proved compiled-HLO
introspection works (the dryrun's comm audit); this module promotes it
to a first-class tier:

* :class:`Contract` — the declared guarantees of one program:
  collective inventory (modeled bytes per kind, checked within
  :data:`tempo_tpu.profiling.COLLECTIVE_TOLERANCE`), ``donate_argnums``
  that must appear as input-output aliases, f64/host-transfer
  allowances.
* :func:`register` — a builder per production program, compiling it at
  small representative shapes (``TEMPO_TPU_CONTRACT_LANES`` is the
  compile-shape budget) on the current backend — on CPU that is the
  dryrun-style virtual mesh, with the TPU kernel forms
  (``sort_kernels=True``, f32 planes) so the checked artifact is the
  production shape of the program, not the golden-parity shape.
* :class:`Chain` — declared stage wiring of multi-program pipelines:
  stage N's out-sharding must equal stage N+1's in-sharding (the
  static precondition of sharding-matched program chaining, ROADMAP
  item 2).

Registry coverage map (program -> production user):

==============================  =======================================
``fused.asof_stats_ema``        the planner's ONE-program chain
                                (plan/fused.py; executor.py replays the
                                rest through the dist factories below)
``dist.align3`` /               the eager + executor-replayed mesh
``dist.asof_local`` /           asofJoin -> withRangeStats -> EMA chain
``dist.range_stats_local`` /    (dist.py shard_map factories; also the
``dist.ema_local``              ``plan.mesh_chain`` sharding chain —
                                join/stats now DONATE their consumed
                                stage-N-1 stacks, round 10)
``dist.range_stats_windowed``   the data-independent windowed fallback
``halo.range_stats`` /          the time-sharded halo kernels
``halo.asof`` / ``halo.ema``    (parallel/halo.py; dryrun audit twin)
``reshard.series_to_time`` /    the explicit all_to_all layout
``reshard.time_to_series``      switches (parallel/reshard.py)
``reshard.plan_node``           the planner's first-class reshard node
                                executor (dist.reshard_frame: the
                                whole-frame series-local switch the
                                eager time-sharded stats/resample/
                                fourier/interpolate paths now share)
``engine.join_single`` /        the ``pick_join_engine`` /
``engine.join_bitonic`` /       ``pick_range_engine`` XLA engine forms
``engine.range_shifted`` /      (ops/sortmerge.py, ops/pallas_merge.py
``engine.range_windowed``       bitonic network, ops/rolling.py RMQ)
``serve.step``                  the online serving engine's
                                steady-state push step
                                (tempo_tpu/serve/state.py: AS-OF +
                                EMA + window carries, donated)
``serve.cohort_push`` /         the fleet-serving cohort engine's
``serve.cohort_query``          mesh-sharded step programs
                                (serve/cohort.py: [S, ...] stream-axis
                                state, whole-state donation, ZERO
                                collectives — stream-parallel by
                                construction) + the ``serve.cohort_
                                loop`` chain pinning that the step's
                                out-shardings ARE its own (and the
                                query's) in-shardings.  The tiered
                                member-state spill (spill_dir +
                                resident_budget) adds NO device
                                program: spill/fault-in are host-side
                                slot copies around the same
                                ``serve.cohort_push`` step, so its
                                contracts cover the spilling cohort
                                unchanged
``service.dispatch_stats`` /    the query service's steady-state
``service.dispatch_ema``        dispatch programs: the cached planner
                                executables (plan/fused.py) at the
                                service bench's two canonical mesh
                                query shapes (stats-only and
                                stats+EMA), donation + alignment
                                collectives pinned
``standing.step``               the standing-query engine's
                                incremental EMA step: the serving push
                                program at the canonical standing
                                config (EMA carry, no window/lookback
                                planes — query/standing.py's shared
                                subscription plane), donated retired
                                state, zero per-push collectives
``standing.unified_scan``       the ``ema_stream`` batch kernel
                                (query/split.py:eval_ema_stream):
                                the sequential split-invariant EMA
                                scan the unified history+live path and
                                every catch-up replay verify against,
                                f32 pinned (the serving carry's
                                precision), packed input donated
==============================  =======================================

The Mosaic-lowered engines (lane-chunked join, streaming window
kernels) have no registry entry: tests/test_tpu_compile.py compiles
their device programs for a described v5e, and their carry/identity
behaviour stays pinned by the interpret-mode suites
(tests/test_chunked_join.py, test_pallas_window.py).

Suppression reuses the AST tier's convention: a
``# lint-ok: <rule>: <reason>`` comment on (or next to) the builder's
``@register`` line silences that rule for that program.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Callable, Dict, List, Optional, Sequence, Tuple

#: series count of every representative shape: one series per device
#: of the 8-way dryrun-style mesh (divides smaller meshes too).
CONTRACT_SERIES = 8

#: static row bounds used by the shifted-engine artifacts (the graft
#: entry's bench-shaped bounds: ticks every 1-2s, 10s window).
CONTRACT_ROWBOUNDS = (20, 8)

_WINDOW_SECS = 10.0


def contract_lanes() -> int:
    """``TEMPO_TPU_CONTRACT_LANES`` — the compile-shape budget: padded
    per-series row count L of every representative shape (default 32,
    clamped [16, 4096]; larger shapes compile slower but sit closer to
    production extents)."""
    from tempo_tpu import config

    n = config.get_int("TEMPO_TPU_CONTRACT_LANES", 32) or 32
    return max(16, min(int(n), 4096))


@dataclasses.dataclass(frozen=True)
class Contract:
    """Declared compiled-artifact guarantees of one program.

    * ``collectives`` — REQUIRED collective kinds with their modeled
      per-shard bytes: the compiled HLO must contain each kind with
      ``model <= measured <= tol * model`` (tol from
      ``profiling.COLLECTIVE_TOLERANCE``, overridable per kind via
      ``tolerances``); a declared kind that vanished compiled away
      real comm the model says must exist, and fails too.
    * ``incidental`` — kinds allowed up to a byte ceiling without a
      model (scalar audit reductions: the clipped-count psum).
      Any kind in the HLO that is neither modeled nor incidental is an
      UNMODELED collective — the class the dryrun audit can only see
      at whole-program grain.
    * ``donate_argnums`` — parameters that must appear as input-output
      aliases in the compiled executable (declared donation that XLA
      silently dropped is exactly the HBM-doubling drift this catches).
      Indices are into the COMPILED executable's flat parameter list —
      the same convention as :class:`Link` — which diverges from the
      python signature when jit drops unused/static args; declare the
      compiled index when the spaces differ.
    * ``allow_f64`` — f64 ops tolerated (golden/f64-policy programs
      only; production TPU-shaped artifacts must stay f64-free).
    * ``host_transfer_ok`` — a declared materialization-barrier reason
      string; None bans infeed/outfeed/callback custom-calls outright.
    """

    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    incidental: Dict[str, int] = dataclasses.field(default_factory=dict)
    tolerances: Dict[str, float] = dataclasses.field(default_factory=dict)
    donate_argnums: Tuple[int, ...] = ()
    allow_f64: bool = False
    host_transfer_ok: Optional[str] = None


@dataclasses.dataclass
class CompiledProgram:
    """One built registry entry: the compiled artifact + its contract
    (+ the builder's source location, for ``# lint-ok`` suppression
    lookup)."""

    name: str
    compiled: object                  # jax.stages.Compiled
    contract: Contract
    source_file: str = ""
    source_line: int = 0
    _hlo_text: Optional[str] = dataclasses.field(default=None, repr=False)

    def hlo_text(self) -> str:
        """The optimized-HLO dump, serialized ONCE and shared by every
        rule (``as_text()`` is the dominant per-program cost after the
        compile itself — four rules re-dumping it quadrupled the
        tier's runtime)."""
        if self._hlo_text is None:
            self._hlo_text = self.compiled.as_text()
        return self._hlo_text


@dataclasses.dataclass(frozen=True)
class Link:
    """One declared stage boundary: flat output ``out_idx`` of
    ``producer`` feeds flat input ``in_idx`` of ``consumer`` (flat =
    ``jax.tree_util`` leaf order).  ``drop_leading`` leading axes of
    the producer value are consumed by host-side slicing before the
    next stage (they must be unsharded — a sharded dropped axis would
    change ownership in flight)."""

    producer: str
    out_idx: int
    consumer: str
    in_idx: int
    drop_leading: int = 0


@dataclasses.dataclass
class Chain:
    """Declared stage wiring; ``source_file``/``source_line`` are
    stamped by the registry (the declaring builder's ``@register``
    site) so chain-level findings honour the same ``# lint-ok``
    suppression as program-level ones."""

    name: str
    links: Tuple[Link, ...]
    source_file: str = ""
    source_line: int = 0


# ----------------------------------------------------------------------
# Registry machinery
# ----------------------------------------------------------------------

_BUILDERS: Dict[str, Callable] = {}
_BUILDER_META: Dict[str, dict] = {}


def register(name: str, requires_devices: int = 1):
    """Declare a compiled-contract builder.  The builder returns
    ``(programs, chains)`` (lists; a bare CompiledProgram also works)
    and is invoked lazily by :func:`build_all`."""

    def deco(fn):
        _BUILDERS[name] = fn
        _BUILDER_META[name] = dict(requires_devices=requires_devices)
        return fn

    return deco


def names() -> List[str]:
    return list(_BUILDERS)


def _normalize(name: str, result) -> Tuple[List[CompiledProgram],
                                           List[Chain]]:
    if isinstance(result, CompiledProgram):
        programs, chains = [result], []
    else:
        programs, chains = result
    fn = _BUILDERS[name]
    try:
        src = inspect.getsourcefile(fn) or ""
        line = inspect.getsourcelines(fn)[1]
    except (OSError, TypeError):  # builders defined in a REPL/exec
        src, line = "", 0
    for p in programs:
        p.source_file, p.source_line = src, line
    for c in chains:
        c.source_file, c.source_line = src, line
    return list(programs), list(chains)


def build_all(only: Optional[Sequence[str]] = None):
    """Build the registry (or the named subset).  Returns
    ``(programs, chains, skipped, errors)`` where ``skipped`` maps
    name -> reason (unmet device-count requirement) and ``errors`` maps
    name -> exception string (a build failure is a finding, not a
    crash — the runner turns it into the build-error exit bit).

    Preconditions the driver must arrange BEFORE jax initialises:
    ``TEMPO_TPU_COMPUTE_DTYPE=float32`` and
    ``TEMPO_TPU_SORT_KERNELS=1`` (the artifacts must be the TPU
    production forms — checking the f64 golden forms for f64 would be
    vacuous), plus >= ``CONTRACT_SERIES`` devices (real or
    ``--xla_force_host_platform_device_count``)."""
    import jax

    from tempo_tpu import packing
    from tempo_tpu.ops.sortmerge import use_sort_kernels

    import numpy as np

    if packing.compute_dtype() != np.float32:
        raise RuntimeError(
            "compiled contracts check the TPU production artifacts: "
            "set TEMPO_TPU_COMPUTE_DTYPE=float32 (the driver "
            "tools/analyze.py --compiled does) before building")
    if not use_sort_kernels():
        raise RuntimeError(
            "compiled contracts check the TPU production artifacts: "
            "set TEMPO_TPU_SORT_KERNELS=1 (the driver "
            "tools/analyze.py --compiled does) before building")

    n_dev = len(jax.devices())
    wanted = list(only) if only else names()
    unknown = [n for n in wanted if n not in _BUILDERS]
    if unknown:
        raise KeyError(f"unknown contract program(s): {unknown} "
                       f"(known: {sorted(_BUILDERS)})")

    programs: List[CompiledProgram] = []
    chains: List[Chain] = []
    skipped: Dict[str, str] = {}
    errors: Dict[str, str] = {}
    for name in wanted:
        meta = _BUILDER_META[name]
        if n_dev < meta["requires_devices"]:
            skipped[name] = (f"needs {meta['requires_devices']} devices, "
                             f"have {n_dev} (set --xla_force_host_"
                             f"platform_device_count)")
            continue
        try:
            ps, cs = _normalize(name, _BUILDERS[name]())
        except Exception as e:  # noqa: BLE001 - reported as build-error
            errors[name] = f"{type(e).__name__}: {e}"
            continue
        programs.extend(ps)
        chains.extend(cs)
    return programs, chains, skipped, errors


# ----------------------------------------------------------------------
# Shared builder plumbing
# ----------------------------------------------------------------------

def _nbytes(*arrays) -> int:
    return int(sum(a.size * a.dtype.itemsize for a in arrays))


def _series_mesh():
    from tempo_tpu.parallel import make_mesh

    return make_mesh({"series": CONTRACT_SERIES})


def _grid_mesh():
    from tempo_tpu.parallel import make_mesh

    return make_mesh({"series": CONTRACT_SERIES // 2, "time": 2})


def _mesh_arrays(mesh, series_axis="series", time_axis=None, n_cols=2,
                 seed=0):
    """The representative sharded operand set of the mesh chain:
    [K, L] int64 ts (1-2s ticks — CONTRACT_ROWBOUNDS-compatible),
    f32 value planes + bool validity, [C, K, L] right stacks."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    K, L = CONTRACT_SERIES, contract_lanes()
    rng = np.random.default_rng(seed)
    secs = np.cumsum(rng.integers(1, 3, size=(K, L)), axis=-1)
    ts = secs.astype(np.int64) * np.int64(1_000_000_000)
    x = rng.standard_normal((K, L)).astype(np.float32)
    valid = np.ones((K, L), dtype=bool)
    rv = rng.standard_normal((n_cols, K, L)).astype(np.float32)
    rvd = rng.random((n_cols, K, L)) > 0.1
    s2 = NamedSharding(mesh, P(series_axis, time_axis))
    s3 = NamedSharding(mesh, P(None, series_axis, time_axis))
    put2 = lambda a: jax.device_put(jnp.asarray(a), s2)
    put3 = lambda a: jax.device_put(jnp.asarray(a), s3)
    return dict(ts=put2(ts), x=put2(x), valid=put2(valid),
                rvals=put3(rv), rvalids=put3(rvd),
                perm=jnp.arange(K), ok=jnp.ones((K,), bool))


# ----------------------------------------------------------------------
# The production-program registry
# ----------------------------------------------------------------------

@register("fused.asof_stats_ema", requires_devices=CONTRACT_SERIES)
def _build_fused():
    """The planner's ONE-program chain (plan/fused.py), with its
    donation (DONATE_ARGNUMS) and its key-alignment all-gathers
    modeled: the gathers move the full right stacks once."""
    import jax.numpy as jnp

    from tempo_tpu.plan import fused

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    program = fused._fused_program(
        mesh, "series", (("l", 0), ("r", 0), ("r", 1)), _WINDOW_SECS,
        CONTRACT_ROWBOUNDS, "shifted", True, ("l", 0), 0.2, True, 31)
    lvals = a["x"][None]
    lvalids = a["valid"][None]
    planes, vstack = fused._right_stacks(a["ts"], a["valid"],
                                         a["rvals"], a["rvalids"])
    compiled = program.lower(a["ts"], lvals, lvalids, a["ts"], planes,
                             vstack, a["perm"], a["ok"]).compile()
    n_stats = 3
    contract = Contract(
        collectives={
            # key-space alignment: r_ts + the two right stacks are
            # gathered to full rows once each (per-shard result bytes)
            "all-gather": _nbytes(a["ts"], planes, vstack),
        },
        incidental={
            # clipped-count psum: [S] s64 audit scalars
            "all-reduce": n_stats * 8 * 4,
        },
        donate_argnums=fused.DONATE_ARGNUMS,
    )
    return CompiledProgram("fused.asof_stats_ema", compiled, contract)


@register("plan.mesh_chain", requires_devices=CONTRACT_SERIES)
def _build_mesh_chain():
    """The eager/executor-replayed mesh chain as FOUR compiled stages
    (align3 -> asof_local -> range_stats_local_packed -> ema_local)
    plus the declared stage-boundary sharding links — the static
    precondition for chaining them without implicit resharding."""
    import jax.numpy as jnp

    from tempo_tpu import dist
    from tempo_tpu.plan import fused

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    planes, vstack = fused._right_stacks(a["ts"], a["valid"],
                                         a["rvals"], a["rvalids"])

    align3 = dist._align3_fn(mesh, "series", None, donate=True)
    align_c = align3.lower(planes, a["perm"], a["ok"], float("nan")) \
        .compile()
    align_contract = Contract(
        collectives={"all-gather": _nbytes(planes)},
        donate_argnums=(0,),
    )

    join = dist._asof_local(mesh, "series", sort_kernels=True)
    join_c = join.lower(a["ts"], a["valid"], a["ts"], a["valid"],
                        vstack, planes).compile()
    # whole-chain donation (round 10): the join donates its consumed
    # aligned stacks (python args 4/5; the unused l/r masks are
    # dropped by jit, so the COMPILED parameter indices are 2/3) onto
    # its equal-shaped found/vals outputs, and the packed stats donate
    # the per-call [C, K, L] value stack (compiled index 1) onto a
    # stats plane — each stage of the chain reuses the buffers of the
    # stage it consumed.
    join_contract = Contract(donate_argnums=(2, 3))

    stats = dist._range_stats_local_packed(
        mesh, "series", _WINDOW_SECS, CONTRACT_ROWBOUNDS, True,
        "shifted")
    xs = a["rvals"]
    stats_c = stats.lower(a["ts"], xs, a["rvalids"]).compile()
    stats_contract = Contract(
        incidental={"all-reduce": xs.shape[0] * 8 * 4},
        donate_argnums=(1,),
    )

    ema = dist._ema_local(mesh, "series", 0.2, True, 31)
    ema_c = ema.lower(a["x"], a["valid"]).compile()

    programs = [
        CompiledProgram("dist.align3", align_c, align_contract),
        CompiledProgram("dist.asof_local", join_c, join_contract),
        CompiledProgram("dist.range_stats_local", stats_c,
                        stats_contract),
        CompiledProgram("dist.ema_local", ema_c, Contract()),
    ]
    chain = Chain("plan.mesh_chain", (
        # aligned plane stack -> the join's r_values operand.  Flat
        # indices refer to the COMPILED executable's parameters: jit
        # drops unused args (the l/r masks under compact=False), so
        # the join's 6 python operands compile to 4 inputs.
        Link("dist.align3", 0, "dist.asof_local", 3),
        # join vals/found -> the packed stats' xs/vs operands
        Link("dist.asof_local", 0, "dist.range_stats_local", 1),
        Link("dist.asof_local", 1, "dist.range_stats_local", 2),
        # a [K, L] stats plane (leading C axis sliced host-side,
        # unsharded) -> the EMA's value operand
        Link("dist.range_stats_local", 0, "dist.ema_local", 0,
             drop_leading=1),
    ))
    return programs, [chain]


@register("serve.step")
def _build_serve_step():
    """The steady-state serving push step (serve/state.py): ONE jitted
    program advancing the AS-OF join carry, the EMA carry and the
    ring-buffer window state per micro-batch.  Contract: every retired
    state buffer is donated (the steady state must update in place —
    a dropped donation doubles serving HBM per tick), no f64 creep
    (f32 value planes, integer timestamp/position math), no host
    transfers (the executor loop may never bounce through python
    mid-tick)."""
    from tempo_tpu.serve import state as serve_state

    cfg = serve_state.StreamConfig(
        n_series=CONTRACT_SERIES, n_cols=2, skip_nulls=True,
        max_lookback=16, window_ns=serve_state.window_ns(_WINDOW_SECS),
        rows_bound=8, ema_alpha=0.2)
    Lb = 8
    fn, n_state = serve_state.push_jitted(cfg, Lb)
    compiled = fn.lower(*serve_state.push_avals(cfg, Lb)).compile()
    # donation is backend-gated (serve_state.donate_serve_steps: off on
    # XLA:CPU where the virtual-device host platform corrupts donated
    # serve buffers); the contract pins whatever the builder declared
    donate = (tuple(range(n_state))
              if serve_state.donate_serve_steps() else ())
    contract = Contract(donate_argnums=donate)
    return CompiledProgram("serve.step", compiled, contract)


@register("serve.cohort_step", requires_devices=CONTRACT_SERIES)
def _build_cohort_step():
    """The fleet-serving cohort engine's mesh-sharded step programs
    (serve/cohort.py): ONE push and ONE query program for S streams
    sharing a shape bucket, the [S, ...] stream axis sharded across the
    mesh.  Contracts: every retired cohort state buffer donated (a
    dropped donation doubles FLEET HBM per tick), no f64 creep, no
    host transfers, and — the fleet-scaling claim itself — ZERO
    collectives: nothing in the step mixes streams, so an empty
    collective inventory is the declared model and ANY collective in
    the compiled HLO fails as unmodeled.  The ``serve.cohort_loop``
    chain declares the steady-state wiring: the push step's state
    out-shardings are its own in-shardings (the pre-partitioned pjit
    handoff) and feed the query step's carry inputs — jit drops the
    query's two unused lock planes under skipNulls, so the query-side
    indices are COMPILED parameter positions."""
    import jax

    from tempo_tpu import dist
    from tempo_tpu.serve import state as serve_state

    S = 2 * CONTRACT_SERIES
    cfg = serve_state.StreamConfig(
        n_series=4, n_cols=2, skip_nulls=True, max_lookback=16,
        window_ns=serve_state.window_ns(_WINDOW_SECS), rows_bound=8,
        ema_alpha=0.2)
    Lb = 8
    mesh = dist.stream_mesh(CONTRACT_SERIES)
    push_fn, n_state = serve_state.cohort_push_jitted(cfg, S, Lb, mesh)
    push_c = push_fn.lower(
        *serve_state.cohort_push_avals(cfg, S, Lb)).compile()
    query_fn = serve_state.cohort_query_jitted(cfg, S, Lb, mesh)
    query_c = query_fn.lower(
        *serve_state.cohort_query_avals(cfg, S, Lb)).compile()
    # the query reads 7 of its 9 python operands (skipNulls drops
    # lock_val/lock_valid), so python arg 7 (the donated n_merged
    # carry) lands at COMPILED parameter index 5.  Donation is
    # backend-gated (serve_state.donate_serve_steps: off on XLA:CPU)
    donating = serve_state.donate_serve_steps()
    programs = [
        CompiledProgram("serve.cohort_push", push_c,
                        Contract(donate_argnums=(
                            tuple(range(n_state)) if donating else ()))),
        CompiledProgram("serve.cohort_query", query_c,
                        Contract(donate_argnums=(
                            (5,) if donating else ()))),
    ]
    # flat output order of the push step: the state tuple's n_state
    # leaves precede the emission dict, so state i is out_idx i; the
    # query's compiled inputs are the 7 used operands in python order
    links = [Link("serve.cohort_push", i, "serve.cohort_push", i)
             for i in range(n_state)]
    links += [
        Link("serve.cohort_push", out_i, "serve.cohort_query", in_i)
        for out_i, in_i in
        # last_val, last_src, lock_src, last_ridx, r_count, n_merged
        ((0, 0), (1, 1), (4, 2), (5, 3), (6, 4), (7, 5))
    ]
    chain = Chain("serve.cohort_loop", tuple(links))
    return programs, [chain]


@register("service.dispatch", requires_devices=CONTRACT_SERIES)
def _build_service_dispatch():
    """The query service's steady-state dispatch programs
    (tempo_tpu/service/): a cached query IS a planner executable, and
    the service's canonical mesh queries dispatch the fused chain
    program (plan/fused.py) at two shapes the existing
    ``fused.asof_stats_ema`` entry does not pin — stats over ONE right
    column without EMA, and stats over both right columns with EMA
    riding a right plane.  Contracts: the right stacks stay donated
    (the service's shared cache would otherwise double every
    concurrent query's HBM), alignment all-gathers within the byte
    model, no f64 creep, no host transfer mid-dispatch."""
    from tempo_tpu.plan import fused

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    lvals = a["x"][None]
    lvalids = a["valid"][None]
    planes, vstack = fused._right_stacks(a["ts"], a["valid"],
                                         a["rvals"], a["rvalids"])
    shapes = (
        ("service.dispatch_stats", (("r", 0),), None),
        ("service.dispatch_ema", (("r", 0), ("r", 1)), ("r", 0)),
    )
    programs = []
    for name, srcs, ema_src in shapes:
        program = fused._fused_program(
            mesh, "series", srcs, _WINDOW_SECS, CONTRACT_ROWBOUNDS,
            "shifted", True, ema_src, 0.2, True, 31)
        compiled = program.lower(a["ts"], lvals, lvalids, a["ts"],
                                 planes, vstack, a["perm"],
                                 a["ok"]).compile()
        # both canonical shapes summarize RIGHT columns only, so jit
        # drops the unused left value/validity stacks (python args
        # 1/2) and the donated right stacks (fused.DONATE_ARGNUMS =
        # python 4/5) land at COMPILED parameter indices 2/3 — the
        # Link convention (see Contract.donate_argnums)
        contract = Contract(
            collectives={
                "all-gather": _nbytes(a["ts"], planes, vstack),
            },
            incidental={"all-reduce": len(srcs) * 8 * 4},
            donate_argnums=(2, 3),
        )
        programs.append(CompiledProgram(name, compiled, contract))
    return programs, []


@register("dist.range_stats_windowed", requires_devices=CONTRACT_SERIES)
def _build_stats_windowed():
    """The data-independent windowed fallback (rowbounds unknowable:
    resampled/ingest-assembled frames) — the artifact that leaked
    weak-f64 window-bound arithmetic before round 8."""
    from tempo_tpu import dist

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    fn = dist._range_stats_local_packed(mesh, "series", _WINDOW_SECS,
                                        None, True, "windowed")
    compiled = fn.lower(a["ts"], a["rvals"], a["rvalids"]).compile()
    contract = Contract(
        incidental={"all-reduce": a["rvals"].shape[0] * 8 * 4},
        donate_argnums=(1,),
    )
    return CompiledProgram("dist.range_stats_windowed", compiled,
                           contract)


def _halo_params():
    halo = 4
    return halo


@register("halo.range_stats", requires_devices=CONTRACT_SERIES)
def _build_halo_range_stats():
    """Time-sharded halo range stats (parallel/halo.py) on the
    series x time grid mesh — the dryrun audit's program, with the
    same ppermute model (left+right halos of ts/x/valid)."""
    from tempo_tpu.parallel import halo as ph

    mesh = _grid_mesh()
    a = _mesh_arrays(mesh, time_axis="time")
    halo = _halo_params()
    K_loc = CONTRACT_SERIES // mesh.shape["series"]
    fn = ph._build_range_stats(mesh, 8.0, halo, "time", "series")
    secs = (a["ts"] // 1_000_000_000)
    compiled = fn.lower(secs, a["x"], a["valid"]).compile()
    model = 2 * K_loc * halo * (8 + 4 + 1)   # s64 secs + f32 x + bool
    contract = Contract(
        collectives={"collective-permute": model},
        incidental={"all-reduce": 16},       # clipped-count psum
    )
    return CompiledProgram("halo.range_stats", compiled, contract)


@register("halo.asof", requires_devices=CONTRACT_SERIES)
def _build_halo_asof():
    """Time-sharded halo AS-OF join: right-halo ppermutes + the
    cross-shard carry all_gathers (the dryrun audit's second
    program)."""
    from tempo_tpu.parallel import halo as ph

    mesh = _grid_mesh()
    a = _mesh_arrays(mesh, time_axis="time")
    halo = _halo_params()
    n_time = mesh.shape["time"]
    K_loc = CONTRACT_SERIES // mesh.shape["series"]
    C = a["rvals"].shape[0]
    fn = ph._build_asof(mesh, halo, "time", "series", sort_kernels=False)
    compiled = fn.lower(a["ts"], a["ts"], a["rvalids"],
                        a["rvals"]).compile()
    model_cp = K_loc * halo * (8 + C * (1 + 4))
    model_ag = n_time * C * K_loc * (1 + 4)
    contract = Contract(
        collectives={"collective-permute": model_cp,
                     "all-gather": model_ag},
        incidental={"all-reduce": 16},
    )
    return CompiledProgram("halo.asof", compiled, contract)


@register("halo.ema", requires_devices=CONTRACT_SERIES)
def _build_halo_ema():
    """Time-sharded EMA: the associative carry stitch's collectives."""
    from tempo_tpu.parallel import halo as ph

    mesh = _grid_mesh()
    a = _mesh_arrays(mesh, time_axis="time")
    n_time = mesh.shape["time"]
    K_loc = CONTRACT_SERIES // mesh.shape["series"]
    fn = ph._build_ema(mesh, 0.2, "time", "series")
    compiled = fn.lower(a["x"], a["valid"]).compile()
    # carry stitch: the per-shard (scale, offset) f32 carry pair is
    # all-gathered across the time axis
    model_ag = n_time * K_loc * 2 * 4
    contract = Contract(collectives={"all-gather": model_ag})
    return CompiledProgram("halo.ema", compiled, contract)


@register("reshard.series_to_time", requires_devices=CONTRACT_SERIES)
def _build_reshard_s2t():
    """The explicit all_to_all layout switch
    (reshard.all_to_all_series_to_time's kernel and specs verbatim —
    the eager wrapper jits internally, so the contract rebuilds the
    same shard_map to get a lowerable handle), modeled at its
    per-shard result bytes."""
    import jax
    from jax.sharding import PartitionSpec as P

    from tempo_tpu.parallel import halo as ph

    mesh = _grid_mesh()
    a = _mesh_arrays(mesh, time_axis="time")
    x = a["x"]
    n_s, n_t = mesh.shape["series"], mesh.shape["time"]

    def kernel(block):
        return jax.lax.all_to_all(block, "time", split_axis=0,
                                  concat_axis=1, tiled=True)

    fn = jax.jit(ph.shard_map(kernel, mesh=mesh,
                              in_specs=(P("series", "time"),),
                              out_specs=P(("series", "time"), None)))
    compiled = fn.lower(x).compile()
    shard_bytes = (x.shape[0] // (n_s * n_t)) * x.shape[1] * 4
    contract = Contract(collectives={"all-to-all": shard_bytes})
    return CompiledProgram("reshard.series_to_time", compiled, contract)


@register("reshard.time_to_series", requires_devices=CONTRACT_SERIES)
def _build_reshard_t2s():
    """The inverse layout switch
    (reshard.all_to_all_time_to_series): full-row joint-sharded blocks
    back to P(series, time) — same per-shard element count as the
    forward switch."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tempo_tpu.parallel import halo as ph

    mesh = _grid_mesh()
    a = _mesh_arrays(mesh, time_axis="time")
    n_s, n_t = mesh.shape["series"], mesh.shape["time"]
    x = jax.device_put(a["x"],
                       NamedSharding(mesh, P(("series", "time"), None)))

    def kernel(block):
        return jax.lax.all_to_all(block, "time", split_axis=1,
                                  concat_axis=0, tiled=True)

    fn = jax.jit(ph.shard_map(kernel, mesh=mesh,
                              in_specs=(P(("series", "time"), None),),
                              out_specs=P("series", "time")))
    compiled = fn.lower(x).compile()
    shard_bytes = (x.shape[0] // (n_s * n_t)) * x.shape[1] * 4
    contract = Contract(collectives={"all-to-all": shard_bytes})
    return CompiledProgram("reshard.time_to_series", compiled, contract)


@register("reshard.plan_node", requires_devices=CONTRACT_SERIES)
def _build_reshard_plan_node():
    """The planner's first-class ``reshard`` node executor
    (dist.reshard_frame / dist._relayout_fn): the whole-frame
    series-local layout switch as ONE program — ts + mask + the
    [C, K, L] value/validity stacks each ride one ``lax.all_to_all``
    — modeled byte-exactly by ``dist.relayout_comm_bytes`` (the same
    model explain() renders on placed reshard nodes and the
    --only-mesh-scaling bench asserts).  No donation by construction:
    a layout switch changes every per-device buffer shape, so no
    input/output alias can exist."""
    from tempo_tpu import dist

    mesh = _grid_mesh()
    a = _mesh_arrays(mesh, time_axis="time")
    fn = dist._relayout_fn(mesh, "series", "time", forward=True,
                           with_cols=True, has_seq=False)
    compiled = fn.lower(a["ts"], a["valid"], a["rvals"],
                        a["rvalids"]).compile()
    K, L = a["ts"].shape
    model = dist.relayout_comm_bytes(K, L, a["rvals"].shape[0],
                                     CONTRACT_SERIES, has_seq=False)
    contract = Contract(collectives={"all-to-all": model})
    return CompiledProgram("reshard.plan_node", compiled, contract)


@register("engine.join_single")
def _build_engine_join_single():
    """pick_join_engine's 'single' engine: the sort-and-scan AS-OF
    merge (ops/sortmerge.py) jitted at a representative [K, L]."""
    import jax

    from tempo_tpu.ops import sortmerge as sm

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    fn = jax.jit(lambda lts, rts, rvd, rv: sm.asof_merge_values(
        lts, rts, rvd, rv))
    compiled = fn.lower(a["ts"], a["ts"], a["rvalids"],
                        a["rvals"]).compile()
    return CompiledProgram("engine.join_single", compiled, Contract())


@register("engine.join_bitonic")
def _build_engine_join_bitonic():
    """The XLA bitonic oversize engine (asof_merge_values_bitonic) —
    the in-shard_map route past the single-program lane ceiling."""
    import jax

    from tempo_tpu.ops import pallas_merge as pm

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    fn = jax.jit(lambda lts, rts, rvd, rv: pm.asof_merge_values_bitonic(
        lts, rts, rvd, rv))
    compiled = fn.lower(a["ts"], a["ts"], a["rvalids"],
                        a["rvals"]).compile()
    return CompiledProgram("engine.join_bitonic", compiled, Contract())


@register("engine.range_shifted")
def _build_engine_range_shifted():
    """pick_range_engine's 'shifted' engine: statically-unrolled masked
    shifted passes over int32 rebased seconds (the graft entry's
    flagship form)."""
    import jax
    import jax.numpy as jnp

    from tempo_tpu.ops import sortmerge as sm

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    secs32 = (a["ts"] // 1_000_000_000).astype(jnp.int32)
    fn = jax.jit(lambda s, x, v: sm.range_stats_shifted(
        s, x, v, jnp.asarray(int(_WINDOW_SECS)).astype(jnp.int32),
        max_behind=CONTRACT_ROWBOUNDS[0], max_ahead=CONTRACT_ROWBOUNDS[1]))
    compiled = fn.lower(secs32, a["x"], a["valid"]).compile()
    return CompiledProgram("engine.range_shifted", compiled, Contract())


@register("engine.range_windowed")
def _build_engine_range_windowed():
    """pick_range_engine's 'windowed' (prefix+RMQ) engine — the
    unbounded-window fallback."""
    import jax
    import jax.numpy as jnp

    from tempo_tpu.ops import rolling as rk

    mesh = _series_mesh()
    a = _mesh_arrays(mesh)
    secs = a["ts"] // 1_000_000_000

    def fn(s, x, v):
        start, end = rk.range_window_bounds(
            s, rk.range_window_width(s, _WINDOW_SECS))
        return rk.windowed_stats(x, v, start, end)

    compiled = jax.jit(fn).lower(secs, a["x"], a["valid"]).compile()
    return CompiledProgram("engine.range_windowed", compiled, Contract())


@register("standing.step")
def _build_standing_step():
    """The standing-query engine's incremental EMA step
    (query/standing.py): subscriptions in delta-EMA mode share a
    serving-plane cohort whose push program IS serve/state.py's step at
    the canonical standing config — EMA carry only, no window or
    lookback planes (max_lookback=0, window off), one value column.
    Contracts: retired state donated (input_output_aliases — the
    standing fleet's steady state must update in place), no f64 creep
    (the standing==batch bitwise contract is an f32 contract), no host
    transfers, and zero per-push collectives (nothing in the step
    mixes subscriptions)."""
    from tempo_tpu.serve import state as serve_state

    cfg = serve_state.StreamConfig(
        n_series=CONTRACT_SERIES, n_cols=1, skip_nulls=True,
        max_lookback=0, window_ns=None, rows_bound=8, ema_alpha=0.3)
    Lb = 8
    fn, n_state = serve_state.push_jitted(cfg, Lb)
    compiled = fn.lower(*serve_state.push_avals(cfg, Lb)).compile()
    donate = (tuple(range(n_state))
              if serve_state.donate_serve_steps() else ())
    return CompiledProgram("standing.step", compiled,
                           Contract(donate_argnums=donate))


@register("standing.unified_scan")
def _build_standing_unified_scan():
    """The ``ema_stream`` batch kernel (query/split.py:
    eval_ema_stream): the sequential split-invariant EMA scan over the
    packed unified history+live layout — the program every standing
    catch-up replay, resume rebuild and batch twin run through.
    Contracts: f32 end to end (the serving carry's precision — an f64
    creep here would break the standing==batch bitwise identity, not
    just the no-f64 policy), packed value plane donated (the scan's
    output has the input's shape; the replay never needs the raw plane
    back), no collectives, no host transfers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tempo_tpu.ops import rolling as ops_rolling

    L = contract_lanes()
    x = jax.ShapeDtypeStruct((CONTRACT_SERIES, L), jnp.float32)
    valid = jax.ShapeDtypeStruct((CONTRACT_SERIES, L), jnp.bool_)
    fn = jax.jit(
        lambda v, m: ops_rolling.ema_scan(v, m, np.float32(0.3)),
        donate_argnums=(0,))
    compiled = fn.lower(x, valid).compile()
    donate = (0,) if _donate_landed(compiled) else ()
    return CompiledProgram("standing.unified_scan", compiled,
                           Contract(donate_argnums=donate))


def _donate_landed(compiled) -> bool:
    """XLA:CPU sometimes declines a requested donation (no
    input_output_alias in the artifact); the contract pins what the
    backend actually honoured, mirroring serve_state.donate_serve_steps
    gating."""
    try:
        return "input_output_alias" in compiled.as_text()
    except Exception:  # pragma: no cover - backend-specific
        return False
