"""Profiling, cost probes, and strategy picking.

The reference's only introspection hook is a driver-side size probe: it
parses ``explain cost`` output to read the optimizer's ``sizeInBytes``
estimate and uses it to pick the broadcast join strategy
(python/tempo/tsdf.py:433-461, consumed at :482-509).  Observability
beyond that is delegated to the Spark UI.

The TPU-native equivalents:

* :func:`trace` — a context manager around ``jax.profiler`` producing
  TensorBoard-loadable traces (the Spark-UI analog).
* :class:`span` — a named host span inside an operator: written to the
  profiler's trace and kept in a bounded in-memory ring
  (:func:`recent_spans`), so the host's time splits by phase.
* :func:`compiled_cost` — XLA's own post-compilation cost/memory
  analysis for a jitted function, the compiler-backed version of the
  ``sizeInBytes`` scrape.
* :func:`host_bytes` — cheap driver-side size estimate of a frame
  (used by the join planner, tempo_tpu/join.py).
* :func:`pick_asof_strategy` — the size-probe -> algorithm decision in
  one audited place.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import logging
import time
from typing import Dict, List, Optional, Tuple

import jax
import pandas as pd

logger = logging.getLogger(__name__)

# tsdf.py:491 uses 30MiB as the broadcast cutoff
BROADCAST_BYTES_THRESHOLD = 30 * 1024 * 1024

#: spans the ring keeps; older ones are pushed out and counted
SPAN_RING = 1 << 17

SpanRecord = collections.namedtuple(
    "SpanRecord", "id parent root name start_ns end_ns rows")

_ring: collections.deque = collections.deque(maxlen=SPAN_RING)
_ring_slots = itertools.count()
_span_ids = itertools.count(1)
#: (id, root) of the innermost open span of this thread or task
_open_span: contextvars.ContextVar = contextvars.ContextVar(
    "tempo_tpu_open_span", default=None)
_Annotation = jax.profiler.TraceAnnotation
_clock_ns = time.perf_counter_ns


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False):
    """Profile everything inside the block to ``log_dir``.

    Usage::

        with profiling.trace("/tmp/tempo-trace"):
            tsdf.asofJoin(other).df
    """
    jax.profiler.start_trace(log_dir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class span:
    """A named host span around one phase of an operator::

        with profiling.span("tempo.layout", rows=n):
            ...

    Each span is written to the profiler as a ``TraceAnnotation`` (so it
    shows on the device trace's clock inside a :func:`trace` block) and
    kept as a :data:`SpanRecord` in a bounded in-memory ring, even when
    its body raises.  A record holds the span's ``id``, its enclosing
    span's (``parent``, None at the outermost), the outermost span's
    (``root``: one operator call and everything under it), ``name``,
    ``start_ns``/``end_ns`` on ``time.perf_counter_ns`` and ``rows``,
    the amount of work the phase did (settable inside the block).
    Nesting follows the thread or task that opens the span.  Recording
    is always on; :func:`recent_spans` reads the ring."""

    __slots__ = ("name", "rows", "_id", "_outer", "_token", "_note", "_t0")

    def __init__(self, name: str, rows: int = 0):
        self.name = name
        self.rows = rows

    def __enter__(self) -> "span":
        sid = self._id = next(_span_ids)
        # (parent, root) of this span
        outer = self._outer = _open_span.get() or (None, sid)
        self._token = _open_span.set((sid, outer[1]))
        # an annotation costs more than the rest of the span: make one
        # only while the profiler records
        if _Annotation.is_enabled():
            note = self._note = _Annotation(self.name)
            note.__enter__()
        else:
            self._note = None
        self._t0 = _clock_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = _clock_ns()
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        _open_span.reset(self._token)
        # the ring's slot number first, so the ring itself says how
        # many spans ended before the oldest it holds
        parent, root = self._outer
        _ring.append((next(_ring_slots), self._id, parent, root, self.name,
                      self._t0, t1, self.rows))
        return False


def recent_spans() -> Tuple[List[SpanRecord], int]:
    """``(spans, dropped)``: the spans the ring holds, in the order they
    ended, and how many older ones it has pushed out since the process
    started."""
    held = list(_ring)
    dropped = min(r[0] for r in held) if held else 0
    return [SpanRecord._make(r[1:]) for r in held], dropped


def compiled_cost(fn, *args, **kwargs) -> Dict[str, Optional[float]]:
    """Compile ``fn`` for the current backend and return XLA's cost and
    memory analysis: flops, transcendentals, bytes accessed, and
    per-space buffer sizes.  Values are ``None`` where a backend does
    not report them."""
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    out: Dict[str, Optional[float]] = {
        "flops": None,
        "bytes_accessed": None,
        "output_bytes": None,
        "temp_bytes": None,
        "argument_bytes": None,
        "generated_code_bytes": None,
    }
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost:
            out["flops"] = cost.get("flops")
            out["bytes_accessed"] = cost.get("bytes accessed")
    except Exception as e:  # pragma: no cover - backend-specific
        logger.debug("cost_analysis unavailable: %s", e)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["output_bytes"] = getattr(mem, "output_size_in_bytes", None)
            out["temp_bytes"] = getattr(mem, "temp_size_in_bytes", None)
            out["argument_bytes"] = getattr(mem, "argument_size_in_bytes", None)
            out["generated_code_bytes"] = getattr(
                mem, "generated_code_size_in_bytes", None
            )
    except Exception as e:  # pragma: no cover - backend-specific
        logger.debug("memory_analysis unavailable: %s", e)
    return out


def window_roofline(
    n_rows: int,
    read_bytes_per_row: float,
    write_bytes_per_row: float,
    restream_bytes_per_row: float = 0.0,
    t_iter: Optional[float] = None,
    stream_bytes_per_sec: Optional[float] = None,
    n_cols: int = 1,
    key_bytes_per_row: float = 0.0,
) -> Dict[str, float]:
    """Roofline accounting for a windowed/streaming config: bytes-moved
    vs bytes-minimal, and their fractions of a *measured* stream rate.

    * ``bytes_minimal`` — the compulsory traffic of an ideal
      implementation: every input column read ONCE, every output plane
      written ONCE.  ``minimal_frac`` answers "how close is this config
      to the fastest any implementation could possibly be".
    * ``bytes_moved`` — what the current implementation actually
      streams, including re-streamed intermediates (e.g. a cast or
      scale pass that writes a converted copy the kernel then re-reads:
      ``restream_bytes_per_row``).  ``achieved_frac`` answers "what
      fraction of the machine's stream capability is this config
      driving" — the utilization number the hbm-stream bound compares.
    * ``stream_efficiency`` = minimal/moved — 1.0 means no byte is
      moved twice; below 1.0 quantifies exactly the re-streaming that
      kernel fusion (scale/jitter scalars riding SMEM,
      ops/pallas_window.py / ops/pallas_bucket.py) removes.

    **Column packing** (``n_cols`` > 1): the shared key planes
    (``key_bytes_per_row`` — timestamps/bucket ids) are compulsory
    traffic ONCE per pass, while ``read_bytes_per_row`` /
    ``write_bytes_per_row`` count one *column's* payload and scale by
    ``n_cols``.  An unpacked implementation re-streams the keys per
    column — model that by putting the extra (n_cols-1) x key bytes
    into ``restream_bytes_per_row``; the packed kernels
    (ops/pallas_window.py ``range_stats_*_packed``) reclaim exactly
    that term.  ``n_rows`` stays the per-column row count; the
    per-row figures below are per base row.
    """
    per_row_min = key_bytes_per_row + n_cols * (
        read_bytes_per_row + write_bytes_per_row)
    bytes_min = float(n_rows) * per_row_min
    bytes_moved = bytes_min + float(n_rows) * restream_bytes_per_row
    out: Dict[str, float] = {
        "bytes_minimal_per_row": per_row_min,
        "bytes_moved_per_row": bytes_moved / max(n_rows, 1),
        "stream_efficiency": round(bytes_min / max(bytes_moved, 1.0), 3),
    }
    if n_cols > 1:
        out["packed_cols"] = n_cols
    if t_iter and stream_bytes_per_sec:
        out["achieved_frac"] = round(
            bytes_moved / t_iter / stream_bytes_per_sec, 3)
        out["minimal_frac"] = round(
            bytes_min / t_iter / stream_bytes_per_sec, 3)
    return out


def join_engine_override() -> Optional[str]:
    """``TEMPO_TPU_JOIN_ENGINE``: force one AS-OF merge engine —
    ``single`` (the one-shot VMEM plan; expert, may exceed the
    compiler ceiling), ``chunked`` (the lane-chunked streaming VMEM
    kernel), ``bracket`` (legacy host time-bracketing), or ``bitonic``
    (the XLA log-stage network, the tracer-context oversize engine).
    Unset/unknown = auto."""
    from tempo_tpu import config

    env = (config.get("TEMPO_TPU_JOIN_ENGINE") or "").strip().lower()
    if env == "vmem":
        env = "single"
    return env if env in ("single", "chunked", "bracket", "bitonic") \
        else None


def pick_join_engine(est_lanes: int, limit: int,
                     chunked_ok: bool) -> str:
    """'single' | 'chunked' | 'bracket' — the three-way oversize
    decision of the host AS-OF join (join.py):

    * ``single``: the estimated merged-lane width fits one device
      program (the single-shot VMEM merge plan, or the XLA ladders
      under the measured ~205K-lane compiler ceiling,
      resilience.max_merged_lanes);
    * ``chunked``: past the ceiling, the lane-chunked streaming VMEM
      kernel (ops/pallas_merge.py) joins on-chip at any length — the
      default oversize engine since round 6;
    * ``bracket``: host time-bracketing with exact carries — the last
      resort when the streaming engine cannot run (non-TPU backend,
      >= 2^24 merged rows).

    ``TEMPO_TPU_JOIN_ENGINE`` forces a specific engine (the
    ``bitonic`` value is a device-dispatch knob — the host path treats
    it as ``single`` and the sortmerge layer routes to the XLA bitonic
    network).  A plan-time hoisted decision (tempo_tpu/plan/hints.py)
    wins while the planner replays the node — skipping the knob read —
    but only when the caller's freshly-probed bounds still admit it
    (a cached 'single' plan replayed past the compiler ceiling, or
    'chunked' on a backend where the streaming kernel is unavailable,
    falls through and re-picks).

    With the cost model on (``TEMPO_TPU_COST_MODEL``, default on —
    tempo_tpu/plan/cost.py) the unforced decision is an argmin over
    estimated engine cost with the thresholds above demoted to
    feasibility priors; all three engines are bit-identical, so a
    measured cost input flipping the pick never changes a result bit.
    Under the default priors the argmin reproduces the rule exactly."""
    from tempo_tpu.plan import hints as plan_hints

    hinted = plan_hints.get("join_engine")
    if hinted == "single" and (limit <= 0 or est_lanes <= limit):
        return "single"
    if hinted == "chunked" and chunked_ok:
        return "chunked"
    if hinted == "bracket":
        return "bracket"
    forced = join_engine_override()
    if forced == "bitonic":
        return "single"
    if forced is not None:
        return forced
    from tempo_tpu.plan import cost as plan_cost

    if plan_cost.enabled():
        return plan_cost.decide_join_engine(est_lanes, limit, chunked_ok)
    if limit <= 0 or est_lanes <= limit:
        return "single"
    return "chunked" if chunked_ok else "bracket"


_COLLECTIVE_OPS = ("collective-permute", "all-to-all", "all-gather",
                   "all-reduce")

#: Per-collective tolerance of a modeled-vs-compiled comm-bytes audit:
#: ``model <= measured <= tol * model``.  ONE table shared by the
#: dryrun multichip audit (__graft_entry__.py) and the
#: collective-inventory compiled-contract rule
#: (tools/analysis/compiled/), so "how much XLA padding is
#: acceptable" is decided once.  The CPU-mesh measurements are
#: byte-exact (ratio 1.0: tests/test_mesh_scaling.py and the compiled
#: contract baselines); no chip mesh has measured it, and the headroom
#: is meant for XLA padding/fusion round-up on real ICI, and all-reduce gets extra slack because scalar audit
#: reductions ride tuple-combined all-reduces whose shapes XLA may
#: widen.
COLLECTIVE_TOLERANCE: Dict[str, float] = {
    "collective-permute": 1.25,
    "all-to-all": 1.25,
    "all-gather": 1.25,
    "all-reduce": 2.0,
}
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}


def _collective_instructions(text: str):
    """The collective instructions of an optimized-HLO dump, yielded as
    ``(kind, op, rhs)`` per instruction line — the ONE parser behind
    both :func:`comm_bytes_from_compiled` and
    :func:`collective_counts_from_compiled` (a second copy of the
    which-line-is-a-collective logic would silently skew one audit
    when the other is taught a new op kind).

    e.g.  ``%all-to-all.1 = f32[4,16]{1,0} all-to-all(...)``
          ``ROOT %cp = (f32[2,4]{...}, u32[]) collective-permute(...)``
    Async decompositions count at the '-done' op (its result IS the
    received data; the '-start' result is a bundle whose tuple would
    double-count the operand)."""
    import re

    for line in text.splitlines():
        stripped = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        for k in _COLLECTIVE_OPS:
            for suffix in ("", "-done"):
                if re.search(rf"\b{k}{suffix}\(", rhs):
                    yield k, k + suffix, rhs
                    break
            else:
                continue
            break


def comm_bytes_from_compiled(compiled,
                             text: Optional[str] = None) -> Dict[str, int]:
    """Per-kind ICI/DCN communication bytes of a compiled program, read
    from its optimized HLO: every collective instruction's result shape
    (per-shard, SPMD) summed by op kind.  The measured side of the
    dryrun's ``comm_bytes=model:measured`` audit — XLA's
    ``cost_analysis`` does not break out collective traffic, the HLO
    does."""
    import re

    if text is None:
        text = compiled.as_text()
    out: Dict[str, int] = {}
    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    for kind, op, rhs in _collective_instructions(text):
        # result type is everything before the op name: one shape, or a
        # tuple of shapes
        type_part = rhs.split(op + "(")[0]
        nbytes = 0
        for dt, dims in shape_re.findall(type_part):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + nbytes
    return out


def collective_counts_from_compiled(compiled,
                                    text: Optional[str] = None
                                    ) -> Dict[str, int]:
    """Per-kind collective INSTRUCTION counts of a compiled program
    (same :func:`_collective_instructions` parser as
    :func:`comm_bytes_from_compiled`, counting ops instead of result
    bytes).  The dryrun's per-stage reshard report reads the
    ``all-to-all`` entry: each layout switch is one all_to_all
    instruction per plane group."""
    if text is None:
        text = compiled.as_text()
    out: Dict[str, int] = {}
    for kind, _, _ in _collective_instructions(text):
        out[kind] = out.get(kind, 0) + 1
    return out


def donated_params_from_compiled(compiled,
                                 text: Optional[str] = None) -> set:
    """Parameter indices the compiled executable aliases to outputs —
    the *applied* side of ``donate_argnums``, read from the
    ``input_output_alias={ {out}: (param, {}, may-alias) }`` header of
    the optimized HLO.  A declared donation XLA could not match (shape/
    dtype mismatch with every output) does NOT appear here — exactly
    the drift the donation-applied compiled contract exists to catch."""
    import re

    if text is None:
        text = compiled.as_text()
    start = text.find("input_output_alias={")
    if start < 0:
        return set()
    # scan to the matching close brace (entries nest one level:
    # ``{ {out_idx}: (param, {}, may-alias), ... }``) — no length cap:
    # a truncated window would silently drop aliases and mint false
    # 'declared donation NOT applied' findings
    i = text.index("{", start)
    depth = 0
    close = None
    for j in range(i, len(text)):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                close = j
                break
    if close is None:  # malformed header: no aliases rather than
        return set()   # scanning arbitrary HLO for ': (N,' matches
    body = text[i:close + 1]
    return {int(p) for p in re.findall(r":\s*\((\d+),", body)}


#: HLO markers of a device->host (or host->device) transfer inside a
#: compiled program: infeed/outfeed, send/recv pairs, and the python
#: callback custom-calls (io_callback / pure_callback / debug prints).
_HOST_TRANSFER_MARKERS = (
    " infeed(", " outfeed(", " send(", " recv(", " send-done(",
    " recv-done(", "xla_python_cpu_callback", "xla_ffi_python_cpu_callback",
    "xla_python_gpu_callback", "CustomCallWithHostTransfer",
)


def host_transfers_from_compiled(compiled,
                                 text: Optional[str] = None) -> list:
    """The host-transfer instructions of a compiled program (op line
    snippets), empty for a clean device-resident program.  The
    no-host-transfer compiled contract asserts this is empty outside
    declared materialization barriers."""
    out = []
    if text is None:
        text = compiled.as_text()
    for line in text.splitlines():
        stripped = line.strip()
        if any(m in stripped for m in _HOST_TRANSFER_MARKERS):
            out.append(stripped[:160])
    return out


def plan_cache_stats() -> Dict[str, object]:
    """Hit/miss/evict/build counters of the lazy planner's executable
    cache (tempo_tpu/plan/cache.py; LRU bound
    ``TEMPO_TPU_PLAN_CACHE_SIZE``), including the ``by_signature`` and
    ``by_tenant`` breakdowns (round 11: the query service attributes
    traffic per tenant via ``cache.tenant_scope``).  The serving-loop
    health metric: a steady-state query mix should be all hits — every
    miss re-runs the optimizer and may compile, and the breakdowns pin
    WHICH query shape or client caused it."""
    from tempo_tpu.plan.cache import CACHE

    return CACHE.stats()


def host_bytes(df: pd.DataFrame) -> int:
    """Driver-side in-memory size of a frame — the packed-columnar analog
    of the reference's ``explain cost`` sizeInBytes scrape."""
    return int(df.memory_usage(deep=True).sum())


def pick_asof_strategy(
    left_df: pd.DataFrame,
    right_df: pd.DataFrame,
    sql_join_opt: bool,
    has_sequence: bool,
    max_lookback: int,
) -> str:
    """'broadcast' | 'merge' | 'searchsorted' — mirrors the reference's
    decision tree (tsdf.py:482-509 fast path; the union/sort algorithm
    otherwise, with the merge variant when a sequence tie-break or row
    cap forces merged-stream coordinates).

    ``maxLookback`` wins over the broadcast fast path: the broadcast
    kernel has no row cap, and Scala — the source of maxLookback
    (asofJoin.scala:64-88) — has no broadcast path to mirror, so
    honouring the cap is the only semantics-preserving choice
    (ADVICE r3: the old order silently dropped the cap).

    This picks the *algorithm*; the orthogonal oversize *engine*
    decision (single-plan VMEM / lane-chunked streaming / host
    brackets) is :func:`pick_join_engine`, consulted by join.py once
    the merged-lane estimate is known."""
    if max_lookback and max_lookback > 0:
        if sql_join_opt:
            logger.warning(
                "asofJoin: sql_join_opt is ignored when maxLookback is "
                "set — the broadcast fast path cannot bound lookback"
            )
        return "merge"
    if sql_join_opt and (
        host_bytes(left_df) < BROADCAST_BYTES_THRESHOLD
        or host_bytes(right_df) < BROADCAST_BYTES_THRESHOLD
    ):
        return "broadcast"
    if has_sequence:
        return "merge"
    return "searchsorted"
