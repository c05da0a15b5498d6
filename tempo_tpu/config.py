"""Central registry of every ``TEMPO_TPU_*`` environment knob.

The knobs grew one module at a time (each engine added its own
override) and by round 6 two of them (``TEMPO_TPU_WAREHOUSE``,
``TEMPO_TPU_BINPACK``) had silently drifted out of BUILDING.md's knob
table.  This module is the single source of truth: every knob the
package reads is declared here with its type, default, owning module
and one-line contract, and *all* ``os.environ`` access inside
``tempo_tpu/`` goes through the accessors below.  The static analyzer
(``tools/analysis`` — the ``env-knobs`` rule) enforces both halves:

* ``os.environ`` / ``os.getenv`` anywhere in ``tempo_tpu/`` outside
  this file is a lint violation;
* the registry, the ``TEMPO_TPU_*`` string literals in the code, and
  BUILDING.md's knob table must agree exactly (no undeclared reads, no
  dead documentation).

Keep this module import-light (stdlib ``os`` only): it is imported by
``tempo_tpu/__init__`` *before* jax, while the process environment is
still being inspected.
"""

from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional


class Knob(NamedTuple):
    """One declared environment knob.

    ``type`` is documentation-grade ("bool", "int", "enum(...)",
    "path", "dtype"): the owning modules keep their historical parsing
    (tri-state bools, backend-dependent defaults), so the registry
    records intent rather than re-implementing coercion.  ``default``
    is the rendered default shown to humans; ``None`` means
    "unset = automatic choice"."""

    name: str
    type: str
    default: Optional[str]
    owner: str
    doc: str


def _knobs(*knobs: Knob) -> Dict[str, Knob]:
    return {k.name: k for k in knobs}


#: Every TEMPO_TPU_* knob the codebase reads, in BUILDING.md table
#: order.  Adding an ``os.environ`` read without declaring it here
#: fails ``python tools/analyze.py`` (env-knobs rule).
KNOBS: Dict[str, Knob] = _knobs(
    Knob("TEMPO_TPU_NATIVE", "bool", "1", "tempo_tpu/native",
         "0 forces the pure-numpy ingest path over the self-built C++ "
         "packer"),
    Knob("TEMPO_TPU_NATIVE_THREADS", "int", "cpu_count", "tempo_tpu/native",
         "thread-pool bound for the native packer"),
    Knob("TEMPO_TPU_COMPUTE_DTYPE", "dtype", None, "tempo_tpu/packing",
         "float64|float32 override of the per-backend metric-math "
         "dtype policy"),
    Knob("TEMPO_TPU_SORT_KERNELS", "bool", None, "tempo_tpu/ops/sortmerge",
         "force/forbid the sort-and-scan kernel forms (default: on for "
         "TPU, off elsewhere)"),
    Knob("TEMPO_TPU_PALLAS_ASOF", "bool", "1", "tempo_tpu/ops/pallas_merge",
         "0 kills the VMEM merge-join kernels"),
    Knob("TEMPO_TPU_NAN_ASOF", "bool", "0", "tempo_tpu/ops/sortmerge",
         "opt into the NaN-encoded XLA AS-OF variant"),
    Knob("TEMPO_TPU_WINDOW_ENGINE", "enum(auto|shifted|stream|windowed|legacy)",
         "auto", "tempo_tpu/ops/rolling",
         "force one of the rolling range-stats engines"),
    Knob("TEMPO_TPU_STREAM_MAX_ROWS", "int", "16384",
         "tempo_tpu/ops/pallas_window",
         "row-extent ceiling of the streaming window engine"),
    Knob("TEMPO_TPU_DMA_BUFFERS", "int", "2",
         "tempo_tpu/ops/pallas_stream",
         "HBM->VMEM buffer depth of the streaming kernels: 2 = the "
         "implicit double-buffered BlockSpec pipeline; >2 = the "
         "explicit N-deep DMA ring (copy/semaphore scratch)"),
    Knob("TEMPO_TPU_PACK_COLS", "int", None,
         "tempo_tpu/ops/pallas_stream",
         "cap on metric columns packed into one window-kernel pass; "
         "unset = largest width the VMEM budget folding admits"),
    Knob("TEMPO_TPU_MEGACORE", "bool", "1",
         "tempo_tpu/ops/pallas_stream",
         "0 disables megacore grid partitioning (carry-free grid axes "
         "marked 'parallel' so Mosaic splits them across TensorCores)"),
    Knob("TEMPO_TPU_STRICT_SQL", "bool", "0", "tempo_tpu/frame",
         "make selectExpr/filter re-raise instead of falling back to "
         "pandas eval/query"),
    Knob("TEMPO_TPU_SQL_STRICT", "bool", "0", "tempo_tpu/frame",
         "strict compiled-SQL mode: any fallback from the compiled "
         "surface to a host-pandas engine raises StrictSqlFallback by "
         "name (supersedes the legacy TEMPO_TPU_STRICT_SQL alias; "
         "per-call strict= wins over both)"),
    Knob("TEMPO_TPU_JOIN_ENGINE", "enum(single|chunked|bracket|bitonic)",
         None, "tempo_tpu/profiling",
         "force one AS-OF merge engine; unset = auto"),
    Knob("TEMPO_TPU_JOIN_CHUNK_LANES", "int", None,
         "tempo_tpu/ops/pallas_merge",
         "merged-lane chunk width of the streaming join engine "
         "(power of two >= 256); unset = largest feasible"),
    Knob("TEMPO_TPU_MAX_MERGED_LANES", "int", "196608",
         "tempo_tpu/resilience",
         "single-program merged-lane ceiling (under the measured ~205K "
         "XLA-sort compiler OOM)"),
    Knob("TEMPO_TPU_BINPACK", "bool", None, "tempo_tpu/join",
         "force/forbid the bin-packed (segmented) join layout; unset = "
         "engage below 0.35 slot occupancy"),
    Knob("TEMPO_TPU_WAREHOUSE", "path", "tempo_tpu_warehouse",
         "tempo_tpu/io/writer",
         "base directory of the partitioned Parquet/Delta warehouse"),
    Knob("TEMPO_TPU_NO_STDERR_FILTER", "bool", "0", "__graft_entry__",
         "1 disables the benign XLA:CPU AOT stderr filter of the "
         "multichip dryrun"),
    Knob("TEMPO_TPU_PLAN", "bool", "0", "tempo_tpu/plan",
         "1 turns on the lazy query planner: recorded op chains are "
         "optimized (kernel fusion, engine hoisting, column pruning) "
         "and executed at collect(); eager is the default"),
    Knob("TEMPO_TPU_RESHARD_PLACEMENT", "enum(auto|declarative|explicit)",
         "auto", "tempo_tpu/plan/optimizer",
         "plan-placed resharding of time-sharded mesh chains: auto = "
         "explicit reshard nodes around maximal series-local op runs "
         "(interior all_to_all pairs eliminated, reshard-back sunk "
         "until a blocker); explicit = reshard around every such op, "
         "never eliminated; declarative = no plan nodes, each op keeps "
         "its internal all_to_all pair"),
    Knob("TEMPO_TPU_PLAN_CACHE_SIZE", "int", "64", "tempo_tpu/plan/cache",
         "LRU bound of the planner's compiled-executable cache "
         "(entries keyed by plan signature + shapes + mesh; 0 disables "
         "caching)"),
    Knob("TEMPO_TPU_CONTRACT_LANES", "int", "32",
         "tempo_tpu/plan/contracts",
         "compile-shape budget of the compiled-contract tier (tools/"
         "analyze.py --compiled): per-series padded row count L of the "
         "representative shapes the production-program registry is "
         "compiled at (clamped [16, 4096]; bigger = slower, closer to "
         "production extents)"),
    Knob("TEMPO_TPU_SERVE_BATCH_ROWS", "int", "64",
         "tempo_tpu/serve/executor",
         "per-series row cap of one serving micro-batch: the executor "
         "cuts a coalesced run when any series reaches it, bounding "
         "the padded-bucket ladder (and therefore the cached-"
         "executable set) the steady state cycles through"),
    Knob("TEMPO_TPU_SERVE_QUEUE_DEPTH", "int", "1024",
         "tempo_tpu/serve/executor",
         "bound of the serving executor's tick queue; a full queue "
         "blocks submit() — the backpressure signal"),
    Knob("TEMPO_TPU_SERVE_CKPT_EVERY", "int", "0",
         "tempo_tpu/serve/stream",
         "snapshot the serving StreamState every N acked events "
         "(CRC'd keep-last-K via checkpoint.save_state; 0 disables "
         "automatic snapshots — snapshot() stays available)"),
    Knob("TEMPO_TPU_SERVE_COHORT_SLOTS", "int", "1024",
         "tempo_tpu/serve/cohort",
         "initial stream-slot capacity of each cohort shape-bucket "
         "group (grown by doubling when full; rounded up to the "
         "mesh's stream-axis size on sharded cohorts — a capacity "
         "change recompiles, so size it to the expected fleet)"),
    Knob("TEMPO_TPU_SERVE_COHORT_CKPT_EVERY", "int", "0",
         "tempo_tpu/serve/cohort",
         "snapshot the whole cohort (ONE kind=\"cohort_state\" "
         "artifact, per-stream acked cursors in the manifest) every N "
         "total acked events; 0 disables automatic snapshots — "
         "StreamCohort.snapshot() stays available"),
    Knob("TEMPO_TPU_STANDING_QUEUE_DEPTH", "int", "1024",
         "tempo_tpu/query/standing",
         "bound of each standing subscription's notification queue; a "
         "full queue drops the OLDEST notification (counted on "
         "Subscription.dropped) so one slow consumer never stalls the "
         "push path — result() stays exact regardless of drops"),
    Knob("TEMPO_TPU_STANDING_REMAINDER_EVERY", "int", "64",
         "tempo_tpu/query/standing",
         "push-boundary cadence at which remainder-mode standing "
         "queries (plans with no incremental carry) re-run the full "
         "canonical plan over the unified scan and emit a refresh "
         "notification; result() always re-runs regardless"),
    Knob("TEMPO_TPU_STANDING_PUSH_PERIOD", "float", "0",
         "tempo_tpu/query/standing",
         "delivery-worker coalescing window in seconds: pushes "
         "admitted within one period merge into fewer delivery "
         "boundaries (fewer, larger cohort dispatches); 0 (default) "
         "delivers every push as its own boundary"),
    Knob("TEMPO_TPU_COST_MODEL", "bool", "1", "tempo_tpu/plan/cost",
         "0 reverts engine picks, fusion and reshard placement to the "
         "pure rule-based decisions; on (default) they are argmins "
         "over estimated cost, with the legacy thresholds demoted to "
         "feasibility priors"),
    Knob("TEMPO_TPU_SERVICE_WORKERS", "int", "4",
         "tempo_tpu/service/service",
         "worker-thread count of the multi-tenant query service "
         "(concurrent plan executions; clamped >= 1)"),
    Knob("TEMPO_TPU_SERVICE_TENANT_QUOTA", "int", "64",
         "tempo_tpu/service/service",
         "per-tenant pending-query bound: a tenant at quota blocks in "
         "submit() — the per-tenant backpressure signal (the bounded-"
         "queue pattern of serve/executor.py, applied per tenant)"),
    Knob("TEMPO_TPU_SERVICE_VMEM_BUDGET", "int", None,
         "tempo_tpu/service/admission",
         "per-query VMEM admission budget in bytes; unset = the "
         "kernel planners' scoped budget (pallas_kernels._VMEM_BUDGET),"
         " explicit 0 admits nothing. A query whose projected "
         "worst-case per-step block exceeds it is REJECTED with "
         "AdmissionError (it could never run)"),
    Knob("TEMPO_TPU_SERVICE_HBM_BUDGET", "int", None,
         "tempo_tpu/service/admission",
         "total HBM admission budget in bytes (default: the first "
         "device's reported memory limit, else 2 GiB; explicit 0 "
         "admits nothing): a query whose projected "
         "footprint exceeds the whole budget is REJECTED; one that "
         "merely exceeds the currently-free share is QUEUED until "
         "running queries release theirs"),
    Knob("TEMPO_TPU_SERVE_DEADLINE_S", "float", None,
         "tempo_tpu/serve/executor",
         "default end-to-end deadline (seconds) for serving tickets: "
         "a tick still queued when its budget dies fails fast with a "
         "stage-named DeadlineExceeded instead of waiting forever; "
         "unset/0 = no default deadline (per-submit deadlines stay "
         "available)"),
    Knob("TEMPO_TPU_SERVICE_DEADLINE_S", "float", None,
         "tempo_tpu/service/service",
         "default end-to-end deadline (seconds) for submitted "
         "queries, carried through quota wait, admission wait and "
         "dispatch; unset/0 = no default deadline"),
    Knob("TEMPO_TPU_BREAKER_THRESHOLD", "int", "3",
         "tempo_tpu/resilience",
         "consecutive failures of one key (plan signature / stream "
         "member) that OPEN its circuit breaker: further work on the "
         "key fails fast with QuarantinedError instead of burning "
         "retry budgets"),
    Knob("TEMPO_TPU_BREAKER_COOLDOWN_S", "float", "5.0",
         "tempo_tpu/resilience",
         "quarantine cooldown: after this many seconds an open "
         "circuit admits ONE half-open probe — success closes it, "
         "failure re-opens it for another cooldown"),
    Knob("TEMPO_TPU_SERVE_DONATE", "bool", None, "tempo_tpu/serve/state",
         "force (1) / forbid (0) donation of the serve/cohort step "
         "programs' retired state buffers; unset = backend-automatic: "
         "ON for accelerators (in-place steady state, pinned by the "
         "serve.step/serve.cohort_step compiled contracts), OFF on "
         "XLA:CPU where the virtual multi-device host platform "
         "corrupts donated serve buffers (use-after-free: garbage "
         "emissions / heap aborts observed on jaxlib 0.4.36)"),
    Knob("TEMPO_TPU_SERVE_COHORT_DIFF", "bool", "0",
         "tempo_tpu/serve/cohort",
         "1 makes automatic cohort snapshots differential: only "
         "bucket groups dirty since the previous snapshot are "
         "written, chained to the last full artifact by CRC'd "
         "manifests (resume walks the chain; bytes per snapshot "
         "scale with dirty state, not fleet size)"),
    Knob("TEMPO_TPU_CKPT_PLACEMENT", "enum(auto|off)", "auto",
         "tempo_tpu/plan/checkpoints",
         "placement of first-class checkpoint barrier nodes on "
         "planned chains run inside plan.checkpoints.checkpointed(): "
         "auto places signed step barriers at materialization/reshard "
         "boundaries (every-th op boundary + the final pre-collect "
         "frame); off disables plan barriers (run_resumable keeps "
         "working)"),
    Knob("TEMPO_TPU_INGEST_DEADLINE_S", "float", None,
         "tempo_tpu/io/ingest",
         "default end-to-end deadline (seconds) for from_parquet: ONE "
         "wall-clock budget across validation, census and every "
         "streaming/placement stage, dying with a stage-named "
         "DeadlineExceeded; unset/0 = no deadline (the per-call "
         "retry-policy deadlines still bound individual IO retries)"),
    Knob("TEMPO_TPU_TUNE_PROFILE", "path|off", None, "tempo_tpu/tune",
         "tuned-knob profile source: a path to a harness-produced "
         "profile, 'off' to disable profile loading, unset = the "
         "checked-in per-device-kind profile under tempo_tpu/tune/"
         "profiles/.  Tuned values are PRIORS: an explicitly-set env "
         "knob always wins; a corrupt or foreign-fingerprint profile "
         "is refused by name with fallback to the built-in defaults"),
    Knob("TEMPO_TPU_STORE_SEGMENT_ROWS", "int", "1048576",
         "tempo_tpu/store/engine",
         "target rows per clustered segment of one store generation "
         "(the transactional write-back chunk: each segment commits "
         "with a chained CRC'd sidecar; compaction merges into 8x "
         "this by default)"),
    Knob("TEMPO_TPU_STORE_KEEP_GENERATIONS", "int", "2",
         "tempo_tpu/store/engine",
         "generation retention of store tables (min 1 = current "
         "only); >= 2 keeps the previous generation on disk so "
         "readers opened on it stay bitwise-correct while the next "
         "one commits"),
    Knob("TEMPO_TPU_STORE_COMPACT_MIN_SEGMENTS", "int", "2",
         "tempo_tpu/store/compact",
         "segment count below which store.compact() is a no-op (the "
         "table is already compact)"),
    Knob("TEMPO_TPU_STITCH_MAX_OPS", "int", "8",
         "tempo_tpu/plan/optimizer",
         "longest run of adjacent series-local planned ops stitched "
         "into ONE jitted executable (optimization_barrier pins every "
         "op boundary, so stitched == op-by-op bitwise); 1 or 0 "
         "disables stitching"),
    Knob("TEMPO_TPU_INGEST_RING", "int", "2",
         "tempo_tpu/io/ingest",
         "slab-buffer ring depth of the out-of-core pipelines "
         "(io.ingest.sweep_slabs + the from_parquet shard loop): "
         "decode of slab N+1 and D2H of slab N-1 overlap compute of "
         "slab N behind a bounded ring; 1 = fully serial (identical "
         "loop, same bits by construction)"),
    Knob("TEMPO_TPU_SERVE_COALESCE_S", "float", "0.002",
         "tempo_tpu/serve/executor",
         "dispatch coalescing window (seconds) of the serving "
         "executors: ticks arriving within it batch into one device "
         "dispatch (the batched cohort path scatters the whole window "
         "on-device); per-constructor coalesce_s overrides win"),
    Knob("TEMPO_TPU_SERVE_COHORT_RESIDENT", "int", "0",
         "tempo_tpu/serve/cohort",
         "LRU resident-member budget of a StreamCohort with a "
         "spill_dir: members beyond it spill their slot state to "
         "CRC'd kind=\"cohort_member\" artifacts and fault back in "
         "on their next tick; 0 = unlimited (no spill)"),
)

#: Non-TEMPO_TPU environment variables the package legitimately reads
#: (foreign contracts: jax's platform selection, Databricks runtime
#: detection).  ``env_external`` refuses anything not listed, so new
#: foreign reads are declared here or fail loudly.
EXTERNAL_VARS = (
    "JAX_PLATFORMS",
    "DATABRICKS_RUNTIME_VERSION",
)


def get(name: str, default: Optional[str] = None) -> Optional[str]:
    """Raw string value of a *declared* knob (``KeyError`` on an
    undeclared name — declare it in :data:`KNOBS` first).  ``None``
    when unset and no ``default`` given; owning modules keep their
    historical parsing on top of this."""
    if name not in KNOBS:
        raise KeyError(
            f"undeclared knob {name!r}: add it to tempo_tpu.config.KNOBS "
            f"(and BUILDING.md's knob table) before reading it")
    return os.environ.get(name, default)


def get_bool(name: str, default: bool = False) -> bool:
    """Common falsy-string parse: unset/''/'0'/'false'/'no'/'off' →
    False-ish side of ``default``; anything else → True.  Knobs with
    tri-state semantics (forced on / forced off / auto) read
    :func:`get` and decide themselves."""
    val = get(name)
    if val is None or val.strip().lower() in ("", "0", "false", "no", "off"):
        return False if val is not None else default
    return True


def get_int(name: str, default: Optional[int] = None) -> Optional[int]:
    """Integer knob; unset or empty → ``default``."""
    val = get(name)
    if val is None or not val.strip():
        return default
    return int(val)


def get_float(name: str, default: Optional[float] = None) -> Optional[float]:
    """Float knob (seconds budgets etc.); unset or empty → ``default``."""
    val = get(name)
    if val is None or not val.strip():
        return default
    return float(val)


def child_env(overrides: Optional[Dict[str, Optional[str]]] = None
              ) -> Dict[str, str]:
    """Snapshot of the process environment for CHILD processes (the
    autotuner's probe children, bench subprocesses), with
    ``overrides`` applied: value ``None`` removes the name, anything
    else is stringified.  Lives here so the env-knobs lint keeps its
    single-owner guarantee — ``os.environ`` access stays inside the
    registry module even for subprocess plumbing."""
    env = dict(os.environ)
    for name, value in (overrides or {}).items():
        if value is None:
            env.pop(name, None)
        else:
            env[name] = str(value)
    return env


def env_external(name: str, default: Optional[str] = None) -> Optional[str]:
    """Sanctioned read of a non-``TEMPO_TPU`` environment variable
    (:data:`EXTERNAL_VARS`); the env-knobs lint bans direct
    ``os.environ`` use everywhere else in the package."""
    if name not in EXTERNAL_VARS:
        raise KeyError(
            f"{name!r} is not a declared external env var: add it to "
            f"tempo_tpu.config.EXTERNAL_VARS")
    return os.environ.get(name, default)
