"""Failure detection, classification, retry/backoff, and resumable
pipelines.

The driver spec for this rebuild names "failure detection,
checkpoint/resume" as first-class (quoted in checkpoint.py:10).  The
save/load half lives in :mod:`tempo_tpu.checkpoint`; this module adds
the other half — the part Spark gives the reference for free through
task re-run recovery (SURVEY.md §5) and that a JAX-native stack must
supply itself:

* **Failure taxonomy** — :class:`FailureKind` plus :func:`classify`,
  mapping an arbitrary exception to the recovery action it admits.  A
  flaky NFS read (transient-io) is retryable; a checksum mismatch
  (corrupted-artifact) is not — it needs an older checkpoint; an XLA
  RESOURCE_EXHAUSTED (compile-oom) needs a smaller program, which the
  join planner arranges (join.py oversize bracketing).
* **Bounded retry** — :class:`RetryPolicy` (exponential backoff,
  jitter, attempt cap, wall-clock deadline) and :func:`retrying`, the
  wrapper the fallible host-side paths ride: Parquet ingest
  (io/ingest.py), checkpoint IO (checkpoint.py), multi-host init
  (parallel/multihost.py).
* **Resumable pipelines** — :func:`run_resumable` chains device ops
  with periodic checkpoints and, on restart, resumes from the newest
  *intact* checkpoint (corrupt ones are detected by checksum and
  skipped), recomputing only the steps after it.
* **Fault-domain primitives** — the serving executors (``serve/``) and
  the query service (``service/``) build their availability story from
  the pieces here: :class:`Deadline` (one wall-clock budget carried
  submit -> queue -> admission -> dispatch, dying with a *stage-named*
  :class:`DeadlineExceeded`), :class:`Cancelled` /
  :class:`ShutdownError` (a ticket always resolves — cancelled work
  never reaches a worker, a closed/dead plane fails its backlog by
  name instead of hanging callers), and :class:`CircuitBreaker` /
  :class:`QuarantinedError` (per-key quarantine of repeat offenders
  with half-open probes, so one poison pill cannot burn every retry
  budget).

Fault-injection coverage for all three lives in
:mod:`tempo_tpu.testing.faults` and the ``chaos``-marked test suite.
"""

from __future__ import annotations

import dataclasses
import enum
import errno
import functools
import logging
import os
import random
import re
import threading
import time
import zipfile
from typing import Callable, FrozenSet, Optional, Sequence

logger = logging.getLogger(__name__)


# ----------------------------------------------------------------------
# Failure taxonomy
# ----------------------------------------------------------------------

class FailureKind(enum.Enum):
    """What an exception *means* for recovery, independent of which
    library raised it."""

    TRANSIENT_IO = "transient-io"            # retry with backoff
    CORRUPTED_ARTIFACT = "corrupted-artifact"  # fall back to older data
    COMPILE_OOM = "compile-oom"              # shrink the program
    DEVICE_LOSS = "device-loss"              # re-init runtime / new mesh
    DEADLINE = "deadline"                    # give up, surface diagnostics
    PERMANENT = "permanent"                  # a bug or bad input: raise


class CheckpointError(ValueError):
    """A checkpoint could not be used: missing, corrupt (checksum or
    container failure), or written by a newer format version.  Carries
    the :class:`FailureKind` so retry wrappers know not to retry
    corruption (an older checkpoint is the recovery, not a re-read)."""

    def __init__(self, message: str,
                 kind: FailureKind = FailureKind.CORRUPTED_ARTIFACT):
        super().__init__(message)
        self.failure_kind = kind


class DeadlineExceeded(TimeoutError):
    """A wall-clock budget died: a retry loop ran past
    ``RetryPolicy.deadline_s``, or a serving/query ticket's
    :class:`Deadline` expired at a named plane stage (``stage`` says
    which one — queue wait, admission, dispatch...)."""

    failure_kind = FailureKind.DEADLINE

    def __init__(self, message: str, stage: Optional[str] = None):
        super().__init__(message)
        self.stage = stage


class Cancelled(RuntimeError):
    """A ticket was cancelled before a worker processed it.  Cancelled
    work releases its quota/queue slot and never reaches a worker; the
    caller's ``result()`` re-raises this by name.  Deliberate — never
    retried."""

    failure_kind = FailureKind.PERMANENT


class ShutdownError(RuntimeError):
    """The plane (executor / query service) shut down — or died — with
    this ticket still outstanding.  Every pending ticket is failed with
    this named error instead of hanging its caller forever on
    ``result()``."""

    failure_kind = FailureKind.PERMANENT


class QuarantinedError(RuntimeError):
    """Work was refused because its circuit breaker is OPEN: the same
    key (plan signature / stream member) failed
    ``TEMPO_TPU_BREAKER_THRESHOLD`` consecutive times and is
    quarantined until a half-open probe (one admission after
    ``TEMPO_TPU_BREAKER_COOLDOWN_S``) succeeds.  Fail-fast by design:
    a poison pill must not burn every retry budget in the plane."""

    failure_kind = FailureKind.PERMANENT

    def __init__(self, message: str, key=None,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.key = key
        self.retry_after_s = retry_after_s


# ----------------------------------------------------------------------
# End-to-end deadlines
# ----------------------------------------------------------------------

class Deadline:
    """A wall-clock budget carried end to end through the serving and
    query planes: created at ``submit``, checked by name at every stage
    the ticket crosses (queue wait, admission wait, build, dispatch) so
    the caller learns *where* the budget died, not just that it did.

    Monotonic-clock based; ``None`` budgets are represented by the
    absence of a Deadline (``Deadline.after(None) is None``), so hot
    paths pay nothing when deadlines are off."""

    __slots__ = ("budget_s", "expires_at", "_clock")

    def __init__(self, budget_s: float,
                 clock: Callable[[], float] = time.monotonic):
        self.budget_s = float(budget_s)
        self._clock = clock
        self.expires_at = clock() + self.budget_s

    @classmethod
    def after(cls, budget_s, clock: Callable[[], float] = time.monotonic
              ) -> "Optional[Deadline]":
        """``None``/non-positive = no deadline; a :class:`Deadline`
        passes through unchanged (so call sites can take either)."""
        if budget_s is None:
            return None
        if isinstance(budget_s, Deadline):
            return budget_s
        if budget_s <= 0:
            return None
        return cls(budget_s, clock=clock)

    def remaining(self) -> float:
        return self.expires_at - self._clock()

    def expired(self) -> bool:
        return self._clock() >= self.expires_at

    def check(self, stage: str) -> None:
        """Raise :class:`DeadlineExceeded` naming ``stage`` when the
        budget is gone."""
        rem = self.remaining()
        if rem <= 0:
            raise DeadlineExceeded(
                f"deadline exceeded at stage {stage!r}: the "
                f"{self.budget_s:.3f}s budget ran out "
                f"{-rem:.3f}s ago", stage=stage)

    def __repr__(self) -> str:
        return (f"Deadline(budget_s={self.budget_s:.3f}, "
                f"remaining={self.remaining():.3f})")


# ----------------------------------------------------------------------
# Circuit breaker (per-key quarantine with half-open probes)
# ----------------------------------------------------------------------

class CircuitBreaker:  # thread-shared
    """Per-key failure quarantine for the serving/query planes.

    Keys are whatever identifies a repeat offender — a plan signature
    in the query service, a stream-member name in the cohort executor.
    ``threshold`` consecutive failures OPEN the circuit for that key:
    :meth:`allow` then raises :class:`QuarantinedError` immediately
    (fail-fast — the poison pill stops burning worker time and retry
    budgets).  After ``cooldown_s`` the circuit goes HALF-OPEN: exactly
    one probe is admitted; its success closes the circuit (counters
    reset), its failure re-opens it for another cooldown.  Thread-safe;
    the planes call it from submit paths and worker threads."""

    def __init__(self, threshold: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        from tempo_tpu import config

        if threshold is None:
            threshold = config.get_int("TEMPO_TPU_BREAKER_THRESHOLD", 3)
        if cooldown_s is None:
            cooldown_s = config.get_float(
                "TEMPO_TPU_BREAKER_COOLDOWN_S", 5.0)
        self.threshold = max(1, int(threshold))
        self.cooldown_s = float(cooldown_s)
        self._clock = clock
        self._lock = threading.Lock()
        # key -> [consecutive_failures, opened_at | None, probing]
        self._st = {}  # guarded-by: self._lock
        self.quarantined_total = 0  # guarded-by: self._lock
        self.trips = 0  # guarded-by: self._lock

    def state(self, key) -> str:
        """``"closed"`` / ``"open"`` / ``"half-open"`` for ``key``."""
        with self._lock:
            st = self._st.get(key)
            if st is None or st[1] is None:
                return "closed"
            if st[2] or self._clock() - st[1] >= self.cooldown_s:
                return "half-open"
            return "open"

    def allow(self, key, label: str = "work") -> None:
        """Admit or refuse ``key``.  Raises :class:`QuarantinedError`
        while the circuit is open (and while a half-open probe is
        already in flight); admits the single probe once the cooldown
        has elapsed."""
        with self._lock:
            st = self._st.get(key)
            if st is None or st[1] is None:
                return
            elapsed = self._clock() - st[1]
            if not st[2] and elapsed >= self.cooldown_s:
                st[2] = True        # this caller IS the half-open probe
                return
            self.quarantined_total += 1
            wait = max(0.0, self.cooldown_s - elapsed)
            raise QuarantinedError(
                f"{label} {key!r} is quarantined: {st[0]} consecutive "
                f"failures opened its circuit breaker"
                + (f"; half-open probe already in flight" if st[2]
                   else f"; next half-open probe in {wait:.2f}s"),
                key=key, retry_after_s=wait)

    def record(self, key, ok: bool) -> None:
        """Record one outcome for ``key`` (success closes a half-open
        circuit and resets counters; failure counts toward the
        threshold / re-opens a probing circuit)."""
        with self._lock:
            st = self._st.setdefault(key, [0, None, False])
            if ok:
                if st[0] or st[1] is not None:
                    self._st[key] = [0, None, False]
                return
            st[0] += 1
            if st[1] is not None or st[0] >= self.threshold:
                if st[1] is None:
                    self.trips += 1
                st[1] = self._clock()   # (re)open; probe slot resets
                st[2] = False

    def abandon(self, key) -> None:
        """The in-flight half-open probe for ``key`` will never report
        an outcome (cancelled / deadline-dead before dispatch): free
        the probe slot so the next :meth:`allow` can probe again —
        without this a vanished probe would quarantine the key
        forever.  No-op when ``key`` is not probing."""
        with self._lock:
            st = self._st.get(key)
            if st is not None and st[1] is not None and st[2]:
                st[2] = False

    def stats(self) -> dict:
        with self._lock:
            open_keys = [k for k, st in self._st.items()
                         if st[1] is not None]
            return {"open": sorted(map(str, open_keys)),
                    "trips": self.trips,
                    "quarantined_total": self.quarantined_total}


# errnos that indicate a transient environment problem, not a bug
_TRANSIENT_ERRNOS = frozenset(
    getattr(errno, name)
    for name in (
        "EAGAIN", "EINTR", "EBUSY", "ETIMEDOUT", "ECONNRESET",
        "ECONNABORTED", "ECONNREFUSED", "ENETRESET", "ENETUNREACH",
        "EHOSTUNREACH", "EPIPE", "EIO", "ESTALE",
    )
    if hasattr(errno, name)
)

# message heuristics for exceptions that arrive as bare RuntimeError /
# XlaRuntimeError strings (XLA does not export a typed hierarchy)
_OOM_PAT = re.compile(
    r"resource[ _]exhausted|out of memory|\boom\b|cannot allocate memory"
    r"|allocation .* (failed|exceeds)|exceeds the limit in memory",
    re.IGNORECASE,
)
_DEVICE_PAT = re.compile(
    r"device (?:lost|halted|failure|unavailable)|DEVICE_LOST"
    r"|data[ _]loss|chip (?:reboot|halt)|\bnccl\b|ici (?:link|failure)",
    re.IGNORECASE,
)
_DEADLINE_PAT = re.compile(
    r"deadline[ _]exceeded|timed[ _]?out|timeout", re.IGNORECASE
)
_TRANSIENT_PAT = re.compile(
    r"\bunavailable\b|connection (?:reset|refused|aborted)"
    r"|temporarily|try again|broken pipe",
    re.IGNORECASE,
)


def classify(exc: BaseException) -> FailureKind:
    """Map an exception to its :class:`FailureKind`.

    Precedence: an explicit ``failure_kind`` attribute on the exception
    wins (our own errors and injected faults self-describe); then typed
    checks (OSError errno, TimeoutError, zip/EOF container failures);
    then message heuristics for the string-typed XLA/runtime errors;
    then ``PERMANENT`` — unknown failures must surface, not retry."""
    kind = getattr(exc, "failure_kind", None)
    if isinstance(kind, FailureKind):
        return kind
    # errno before the TimeoutError type check: Python surfaces
    # OSError(ETIMEDOUT) AS TimeoutError, and a socket/NFS timeout is
    # transient weather (retry), unlike a logical deadline (give up)
    if isinstance(exc, OSError) and exc.errno in _TRANSIENT_ERRNOS:
        return FailureKind.TRANSIENT_IO
    if isinstance(exc, TimeoutError):
        return FailureKind.DEADLINE
    if isinstance(exc, (zipfile.BadZipFile, EOFError)):
        return FailureKind.CORRUPTED_ARTIFACT
    if isinstance(exc, MemoryError):
        return FailureKind.COMPILE_OOM
    if isinstance(exc, ConnectionError):
        return FailureKind.TRANSIENT_IO
    if isinstance(exc, OSError) and exc.errno == errno.ENOENT:
        return FailureKind.PERMANENT
    msg = str(exc)
    if _OOM_PAT.search(msg):
        return FailureKind.COMPILE_OOM
    if _DEVICE_PAT.search(msg):
        return FailureKind.DEVICE_LOSS
    if _DEADLINE_PAT.search(msg):
        return FailureKind.DEADLINE
    if _TRANSIENT_PAT.search(msg):
        return FailureKind.TRANSIENT_IO
    return FailureKind.PERMANENT


# ----------------------------------------------------------------------
# Retry / backoff
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff with jitter and a wall-clock deadline.

    ``retry_on`` is the set of :class:`FailureKind` worth re-attempting;
    everything else re-raises immediately (retrying a checksum mismatch
    or a real bug only hides it).  ``deadline_s`` caps the *total* time
    the retry loop may consume — the loop never starts a sleep that
    would cross it."""

    max_attempts: int = 4
    base_delay_s: float = 0.1
    max_delay_s: float = 30.0
    multiplier: float = 2.0
    jitter: float = 0.5            # fraction of each delay randomized away
    deadline_s: Optional[float] = None
    retry_on: FrozenSet[FailureKind] = frozenset({FailureKind.TRANSIENT_IO})

    def delay_s(self, prior_failures: int, rng: random.Random) -> float:
        raw = min(self.max_delay_s,
                  self.base_delay_s * self.multiplier ** prior_failures)
        return raw * (1.0 - self.jitter * rng.random())


#: Default policy for host-side file IO (checkpoint + Parquet ingest).
DEFAULT_IO_POLICY = RetryPolicy(
    max_attempts=4, base_delay_s=0.05, max_delay_s=2.0, deadline_s=60.0,
)


def retrying(
    policy: Optional[RetryPolicy] = None,
    label: Optional[str] = None,
    sleep: Callable[[float], None] = time.sleep,
    clock: Callable[[], float] = time.monotonic,
    rng: Optional[random.Random] = None,
):
    """Decorator/wrapper giving a callable bounded retry semantics.

    Catches ``Exception`` only: simulated-kill faults
    (:class:`tempo_tpu.testing.faults.SimulatedKill`) and real signals
    derive from ``BaseException`` and always propagate.  Each retry is
    logged at WARNING with the classified kind; exhaustion logs at
    ERROR and re-raises the last failure (or raises
    :class:`DeadlineExceeded` when the wall clock, not the attempt
    count, ran out)."""
    pol = policy or DEFAULT_IO_POLICY
    _rng = rng or random.Random()

    def deco(fn):
        name = label or getattr(fn, "__qualname__", repr(fn))

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            start = clock()
            failures = 0
            while True:
                try:
                    return fn(*args, **kwargs)
                except Exception as exc:
                    kind = classify(exc)
                    failures += 1
                    if kind not in pol.retry_on:
                        raise
                    if failures >= pol.max_attempts:
                        logger.error(
                            "%s: giving up after %d attempt(s) (%s: %s)",
                            name, failures, kind.value, exc,
                        )
                        raise
                    delay = pol.delay_s(failures - 1, _rng)
                    elapsed = clock() - start
                    if pol.deadline_s is not None and \
                            elapsed + delay > pol.deadline_s:
                        logger.error(
                            "%s: retry deadline %.1fs exhausted after %d "
                            "attempt(s) (%s: %s)",
                            name, pol.deadline_s, failures, kind.value, exc,
                        )
                        raise DeadlineExceeded(
                            f"{name}: {elapsed:.1f}s elapsed of "
                            f"{pol.deadline_s:.1f}s retry deadline "
                            f"(last failure: {exc})"
                        ) from exc
                    logger.warning(
                        "%s: attempt %d/%d failed (%s: %s); retrying in "
                        "%.2fs", name, failures, pol.max_attempts,
                        kind.value, exc, delay,
                    )
                    sleep(delay)

        return wrapper

    return deco


def call_with_retry(fn, *args, policy: Optional[RetryPolicy] = None,
                    label: Optional[str] = None, **kwargs):
    """One-shot form of :func:`retrying` for call sites that don't want
    a decorated helper."""
    return retrying(policy, label=label)(fn)(*args, **kwargs)


# ----------------------------------------------------------------------
# Graceful degradation knobs (consumed by join.py)
# ----------------------------------------------------------------------

#: Merged-lane ceiling above which the AS-OF join degrades to the host
#: time-bracketing path instead of handing XLA a program it cannot
#: compile.  The measured failure: the lax.sort merge ladder OOM-killed
#: the compiler at ~205K merged lanes (the round-3 chip notes, VERDICT.md
#: missing #1); 192K leaves headroom below that cliff.
DEFAULT_MAX_MERGED_LANES = 196_608


def max_merged_lanes() -> int:
    """Merged-lane limit for a single AS-OF merge program.  Override
    with ``TEMPO_TPU_MAX_MERGED_LANES`` (ints only; smaller values force
    the bracketing fallback earlier, 0/negative disables the guard)."""
    from tempo_tpu import config

    env = config.get_int("TEMPO_TPU_MAX_MERGED_LANES")
    if env is not None:
        return env
    return DEFAULT_MAX_MERGED_LANES


# ----------------------------------------------------------------------
# Resumable pipelines
# ----------------------------------------------------------------------

def _apply_step(state, step):
    """A step is a callable ``frame -> frame``, a method name, or a
    ``(method_name, kwargs)`` tuple."""
    if callable(step):
        return step(state)
    if isinstance(step, str):
        return getattr(state, step)()
    name = step[0]
    kwargs = step[1] if len(step) > 1 else {}
    return getattr(state, name)(**kwargs)


def _step_label(step) -> str:
    if callable(step):
        return getattr(step, "__name__", repr(step))
    if isinstance(step, str):
        return step
    return str(step[0])


def _sig_canon(value) -> str:
    """Process-stable canonical string of one step kwarg: scalars by
    value (numpy scalars unwrapped — by type alone, two pipelines
    differing only in an np.int64 kwarg would collide and resume each
    other's state), containers recursively, everything else by TYPE
    only.  A bare ``repr`` would fold memory addresses into the
    signature for objects without a stable ``__repr__`` (a TSDF
    operand, say) — a restarted process would then refuse its OWN
    checkpoints."""
    import numpy as np

    if isinstance(value, np.generic) and value.shape == ():
        value = value.item()
    if value is None or isinstance(value, (bool, int, float, str)):
        return repr(value)
    if isinstance(value, (list, tuple)):
        return "[" + ",".join(_sig_canon(v) for v in value) + "]"
    if isinstance(value, dict):
        items = sorted(value.items(), key=lambda kv: str(kv[0]))
        return "{" + ",".join(f"{k}:{_sig_canon(v)}" for k, v in items) + "}"
    return f"<{type(value).__name__}>"


def pipeline_signature(steps: Sequence) -> str:
    """Stable signature of a ``run_resumable`` step chain, stamped into
    every step manifest so resume can refuse FOREIGN state by name
    (the silent-restore hazard: a stale ``ckpt_dir`` from a different
    pipeline restoring cleanly into this one).

    Covers step count, method names and canonical kwargs
    (:func:`_sig_canon` — stable across process restarts).  Callables
    canonicalize to their *position* only (two closures compiled from
    the same source are not provably the same step, and instrumented
    re-wraps of the same pipeline must keep resuming), so two
    all-callable chains of equal length collide — the hazard this
    guards is cross-pipeline shape drift, which always shows up in
    length or in the named steps."""
    import hashlib

    parts = []
    for step in steps:
        if callable(step):
            parts.append("<callable>")
        elif isinstance(step, str):
            parts.append(f"method:{step}")
        else:
            kwargs = step[1] if len(step) > 1 else {}
            parts.append(f"method:{step[0]}:{_sig_canon(dict(kwargs))}")
    h = hashlib.sha1(repr((len(parts), parts)).encode())
    return h.hexdigest()[:16]


def resume_signature(frame, steps: Sequence) -> str:
    """The signature :func:`run_resumable` stamps by default: the step
    chain (:func:`pipeline_signature`) PLUS the input frame's content
    fingerprint.  Steps alone would let a reused ``ckpt_dir`` restore
    a PREVIOUS run's retained final checkpoint when the same chain is
    re-run over new data — zero steps re-run, yesterday's output
    returned as today's.  The content fingerprint is the same one the
    plan barriers stamp (:func:`tempo_tpu.plan.checkpoints.
    source_fingerprint` — memoized, stable across restarts), so a
    crash-resumed pipeline re-fed the same bytes still matches its own
    checkpoints."""
    import hashlib

    from tempo_tpu.plan import checkpoints as plan_ckpt

    return hashlib.sha1(
        f"{pipeline_signature(steps)}|"
        f"{plan_ckpt.source_fingerprint(frame)}".encode()
    ).hexdigest()[:16]


def run_resumable(
    frame,
    steps: Sequence,
    ckpt_dir: str,
    every: int = 1,
    keep_last: int = 2,
    sharded: bool = False,
    signature: Optional[str] = None,
):
    """Run a chain of device ops with periodic checkpoints and
    crash-resume — the eager wrapper over the same signed-barrier
    machinery the plan executor's checkpoint nodes use
    (:mod:`tempo_tpu.plan.checkpoints`).

    ``steps`` is a sequence of callables ``frame -> frame`` (or
    ``(method_name, kwargs)`` tuples resolved against the frame).  After
    every ``every``-th step — and always after the last — the
    intermediate frame is checkpointed to ``ckpt_dir/step_NNNNN`` via
    :func:`tempo_tpu.checkpoint.save` (atomic, checksummed), its
    manifest stamped with the pipeline signature
    (:func:`resume_signature` — steps + input-frame content; or the
    caller's ``signature``) and the predecessor checkpoint's manifest
    CRC-32 (the chained-manifest scheme); older checkpoints beyond
    ``keep_last`` are pruned.

    On restart with the same ``ckpt_dir``, the newest intact,
    chain-consistent checkpoint STAMPED BY THIS PIPELINE is restored
    and only the steps after it re-run
    (:func:`tempo_tpu.checkpoint.resolve_step`): corrupt/truncated
    candidates and broken chain links fall back to older ones with a
    warning, but a checkpoint stamped by a *different* pipeline raises
    :class:`CheckpointError` by name instead of silently restoring
    foreign state.  Steps must be deterministic for the resumed result
    to be bit-identical to an uninterrupted run; all tempo-tpu device
    ops are.

    Checkpoint IO needs no extra wrapping here: every read/write
    primitive inside :mod:`tempo_tpu.checkpoint` already retries
    transient faults under :data:`DEFAULT_IO_POLICY` — one retry
    altitude, not nested loops."""
    from tempo_tpu import checkpoint

    if every < 1:
        raise ValueError(f"every must be >= 1, got {every}")
    os.makedirs(ckpt_dir, exist_ok=True)
    sig = signature or resume_signature(frame, steps)
    mesh = getattr(frame, "mesh", None)
    series_axis = getattr(frame, "series_axis", "series")
    time_axis = getattr(frame, "time_axis", None)

    state, done = frame, 0
    prev = None          # (step, manifest CRC) of the chain predecessor
    below = None
    while True:
        # resolve cheaply (manifest-only), verify the arrays ONCE in
        # load below; an intact-on-disk checkpoint this process cannot
        # load (corrupt arrays, a sharded save resumed single-process)
        # falls back to the next-older candidate
        hit = checkpoint.resolve_step(ckpt_dir, signature=sig,
                                      max_step=len(steps), verify=False,
                                      below_step=below)
        if hit is None:
            break
        step_no, path, _man = hit
        try:
            state = checkpoint.load(path, mesh=mesh,
                                    series_axis=series_axis,
                                    time_axis=time_axis)
        except (CheckpointError, ValueError) as e:
            logger.warning(
                "run_resumable: checkpoint %s unusable (%s); falling "
                "back to an older one", path, e)
            state, below = frame, step_no
            continue
        done = step_no
        prev = (step_no, checkpoint.manifest_crc(path))
        logger.info(
            "run_resumable: resumed after step %d/%d from %s",
            done, len(steps), path,
        )
        break

    for i in range(done, len(steps)):
        state = _apply_step(state, steps[i])
        if (i + 1) % every == 0 or i + 1 == len(steps):
            path = os.path.join(ckpt_dir, f"step_{i + 1:05d}")
            meta = {"pipeline_signature": sig, "step": i + 1,
                    "step_label": _step_label(steps[i])}
            if prev is not None:
                meta["prev_step"], meta["prev_manifest_crc"] = prev
            checkpoint.save(state, path, sharded=sharded, meta=meta)
            prev = (i + 1, checkpoint.manifest_crc(path))
            logger.info(
                "run_resumable: step %d/%d (%s) checkpointed to %s",
                i + 1, len(steps), _step_label(steps[i]), path,
            )
            checkpoint.prune(ckpt_dir, keep_last=keep_last)
    return state
