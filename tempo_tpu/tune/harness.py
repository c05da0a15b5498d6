"""The sweep harness: measure the knob space, gate on bitwise audits,
persist the winners as a profile.

Every candidate point runs in a **child process** (``python
tools/tune_probe.py <probe>`` with the candidate knobs in the child's
environment): a Mosaic OOM, an infeasible ring depth or a compiler hang
kills the child, never the tuner.  Each
probe reports a rate AND a CRC-32 digest of the full kernel outputs on
deterministic data; the **bitwise value-audit gate** compares every
candidate's digest against the all-defaults baseline and rejects any
mismatch — a knob setting that changes result bits is *rejected*, not
just slow.  Mismatches on ``bitwise_neutral`` axes are additionally
recorded as audit FAILURES (a kernel-identity regression; the smoke CLI
exits nonzero on them).

The walk is per-class coordinate descent with **dominated-point
pruning**: axes are swept in declared order from the all-defaults
incumbent; a ladder is abandoned after :data:`PRUNE_AFTER` consecutive
candidates that fail to beat the best point by :data:`MARGIN` (the
ladders are monotone resource knobs — once deeper rings/wider packs
stop paying, the rest of the ladder is dominated).  This keeps the
sweep at O(sum of ladder lengths) probes instead of the cartesian
product.

Child-to-child timing noise is biased AGAINST flapping the profile:
the baseline rate is the MAX of two probes and a would-be winner must
beat it by the margin on the MIN of two probes (its own confirmation
re-probe included), so a knob that is structurally inert on this
backend keeps its default even when scheduler noise hands one child a
lucky run — the defaults stay the incumbent unless the win reproduces.

The harness itself never starts a JAX backend: a chip belongs to one
process, so a parent holding it would starve every probe child.  The
children decide what their backend can run — a class whose kernels need
a TPU comes back ``hardware_gated`` with the reason, never faked — and
report the device they measured on, which keys the profile.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

from tempo_tpu.tune import profile as tune_profile
from tempo_tpu.tune import space as tune_space

logger = logging.getLogger(__name__)

#: a candidate must beat the incumbent by this fraction to win (noise
#: guard: sub-2% wiggles must not flap the checked-in profile)
MARGIN = 0.02

#: consecutive non-winning candidates before a ladder is pruned
PRUNE_AFTER = 2


def _probe_path() -> str:
    import tempo_tpu

    root = os.path.dirname(os.path.dirname(
        os.path.abspath(tempo_tpu.__file__)))
    return os.path.join(root, "tools", "tune_probe.py")


def run_probe(probe: str, knobs: Dict[str, object],
              smoke: bool = False,
              timeout: Optional[float] = None) -> Dict:
    """One measurement child: ``python tools/tune_probe.py <probe>``
    with exactly ``knobs`` applied (every other tunable knob cleared —
    an inherited env knob must not contaminate the baseline) and
    profile loading off (the sweep measures raw knob values).  Returns
    the probe's JSON record, or ``{"error": ...}`` when the child died
    — the caller treats a dead child as an infeasible point."""
    from tempo_tpu import config

    overrides: Dict[str, Optional[str]] = {
        k: None for k in tune_profile.TUNABLE_KNOBS}
    for k, v in knobs.items():
        if v is not None:
            overrides[k] = str(v)
    overrides["TEMPO_TPU_TUNE_PROFILE"] = "off"
    # set OR clear: an inherited TEMPO_BENCH_SMOKE must not shrink a
    # full sweep's probes to smoke shapes (the profile would be
    # measured on tiny data yet stamped "smoke": false)
    overrides["TEMPO_BENCH_SMOKE"] = "1" if smoke else None
    env = config.child_env(overrides)
    if timeout is None:
        timeout = 300 if smoke else 1200
    try:
        proc = subprocess.run(
            [sys.executable, _probe_path(), probe],
            capture_output=True, text=True, timeout=timeout, env=env,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"probe {probe} timed out after {timeout}s"}
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-3:]
        return {"error": f"probe {probe} child rc={proc.returncode}: "
                         f"{' | '.join(tail)}"}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as e:
        return {"error": f"probe {probe} emitted no JSON record "
                         f"({type(e).__name__}: {e})"}


def sweep_class(cls: tune_space.ShapeClass, smoke: bool = False,
                probe_fn=run_probe) -> Tuple[Dict, List[Dict]]:
    """Sweep one shape class; returns (class record, audit failures).
    ``probe_fn`` is injectable for the harness unit tests."""
    t0 = time.time()
    assign: Dict[str, object] = {}
    base = probe_fn(cls.probe, assign, smoke=smoke)
    if "hardware_gated" in base:
        logger.info("tune: class %s hardware-gated: %s", cls.name,
                    base["hardware_gated"])
        return {"hardware_gated": base["hardware_gated"],
                "fingerprint": base.get("fingerprint")}, []
    if "error" in base:
        return {"error": f"baseline probe failed: {base['error']}"}, []
    digest0 = base["digest"]
    # incumbent bias: the baseline rate is the max of TWO probes (an
    # unlucky-slow baseline child must not hand every candidate a
    # fake win); the digest comes from the first, and only the first
    # measures the saxpy stream rate (the marker rides the child env
    # like the knobs do — the re-probe's copy would be discarded)
    base2 = probe_fn(cls.probe, {"TEMPO_BENCH_TUNE_NO_SAXPY": 1},
                     smoke=smoke)
    probes = 2
    if "error" not in base2:
        if base2.get("digest") != digest0:
            # the default-knob kernel itself is nondeterministic: every
            # candidate audit against digest0 would be meaningless (a
            # bits-changing knob could match one baseline run and a
            # legitimate one could miss) — fail the class loudly, never
            # sweep on a baseline the harness has already seen flap
            reason = (f"baseline nondeterminism: two default-knob "
                      f"probes of class {cls.name} disagree (digests "
                      f"{digest0} vs {base2.get('digest')}) — the "
                      f"kernel output is not deterministic and no "
                      f"candidate can be audited against it")
            return {"error": reason}, [
                {"class": cls.name, "knobs": {}, "reason": reason}]
        base["rows_per_sec"] = max(base["rows_per_sec"],
                                   base2["rows_per_sec"])
    best = dict(base)
    best_knobs: Dict[str, object] = {}
    rejected: List[Dict] = []
    failures: List[Dict] = []
    for axis in cls.axes:
        misses = 0
        for v in tune_space.axis_values(axis, smoke)[1:]:
            if misses >= PRUNE_AFTER:
                logger.info(
                    "tune: %s ladder %s pruned after %d dominated "
                    "points", cls.name, axis.knob, misses)
                break
            cand = {k: x for k, x in {**assign, axis.knob: v}.items()
                    if x is not None}
            rec = probe_fn(cls.probe, cand, smoke=smoke)
            probes += 1
            if "error" in rec:
                rejected.append({"knobs": cand, "reason": rec["error"]})
                misses += 1
                continue
            if rec["digest"] != digest0:
                reason = (f"bitwise-audit: output digest {rec['digest']} "
                          f"!= default-knob digest {digest0}")
                rejected.append({"knobs": cand, "reason": reason})
                if axis.bitwise_neutral:
                    # a contract-bitwise knob changed result bits: an
                    # identity regression, not a legitimate rejection
                    failures.append({"class": cls.name, "knobs": cand,
                                     "reason": reason})
                continue
            if rec["rows_per_sec"] > best["rows_per_sec"] * (1 + MARGIN):
                if not axis.bitwise_neutral:
                    # a legality-ceiling axis can never legitimately
                    # win: a same-bits candidate left the engine pick
                    # unchanged, and the ceiling is unread inside the
                    # chosen engine — the measured "win" is child
                    # scheduler noise.  Shipping a changed ceiling
                    # could flip the engine (and the f32 rounding
                    # order) at shapes the probe never ran, so the
                    # default stands; the axis rides the sweep purely
                    # as the audit surface that proves bits-changing
                    # values get rejected.
                    rejected.append({
                        "knobs": cand,
                        "reason": "legality-ceiling axis: same-bits "
                                  "candidate is performance-inert at "
                                  "the probe shape (the measured win "
                                  "is noise) and a changed ceiling "
                                  "could flip the engine at unprobed "
                                  "shapes — the default stands"})
                    misses += 1
                    continue
                # confirmation re-probe: the win must REPRODUCE (min
                # of the two candidate rates still beats by margin) or
                # it is scheduler noise and the incumbent stands
                rec2 = probe_fn(cls.probe, cand, smoke=smoke)
                probes += 1
                confirmed = ("error" not in rec2
                             and rec2.get("digest") == digest0
                             and min(rec["rows_per_sec"],
                                     rec2["rows_per_sec"])
                             > best["rows_per_sec"] * (1 + MARGIN))
                if not confirmed:
                    misses += 1
                    continue
                rec = dict(rec)
                rec["rows_per_sec"] = min(rec["rows_per_sec"],
                                          rec2["rows_per_sec"])
                best = rec
                assign[axis.knob] = v
                best_knobs = {k: x for k, x in assign.items()
                              if x is not None}
                misses = 0
            else:
                misses += 1
    record = {
        "knobs": best_knobs,
        "rows_per_sec": best["rows_per_sec"],
        "default_rows_per_sec": base["rows_per_sec"],
        "speedup": round(best["rows_per_sec"]
                         / max(base["rows_per_sec"], 1e-9), 3),
        "t_iter": best.get("t_iter"),
        "bytes_per_iter": best.get("bytes_per_iter"),
        "probes": probes,
        "rejected": rejected,
        "sweep_seconds": round(time.time() - t0, 1),
        "audit": "bitwise (every kept candidate's output digest == "
                 "the default-knob digest on deterministic data)",
        "fingerprint": base.get("fingerprint"),
    }
    if base.get("stream_gbps"):
        record["stream_gbps"] = base["stream_gbps"]
    return record, failures


def sweep(class_names=None, smoke: bool = False,
          out_path: Optional[str] = None,
          probe_fn=run_probe) -> Tuple[Dict, List[Dict]]:
    """Run the whole sweep and assemble the profile document.  Returns
    ``(payload, audit_failures)``; the payload is written to
    ``out_path`` when given (CRC stamped by :func:`profile.write`)."""
    classes = tune_space.classes(class_names, smoke=smoke)
    records: Dict[str, Dict] = {}
    failures: List[Dict] = []
    for cls in classes:
        logger.info("tune: sweeping class %s (%s)", cls.name, cls.doc)
        rec, fails = sweep_class(cls, smoke=smoke, probe_fn=probe_fn)
        records[cls.name] = rec
        failures.extend(fails)

    merged: Dict[str, object] = {}
    for cls in classes:
        rec = records.get(cls.name) or {}
        for knob in cls.owns:
            if knob in (rec.get("knobs") or {}):
                merged[knob] = rec["knobs"][knob]

    measured: Dict[str, float] = {}
    for name in ("stream_dense", "stream_medium"):
        gbps = (records.get(name) or {}).get("stream_gbps")
        if gbps:
            # the image's real saxpy stream rate replaces the BENCH r5
            # TPU prior — the cost model's decisions (all bitwise-free)
            # then argmin over what THIS image can actually move
            measured["hbm_stream_rate"] = float(gbps) * 1e9
            break
    jc = records.get("join_chunk") or {}
    if jc.get("t_iter") and jc.get("bytes_per_iter"):
        measured["join_chunked_rate"] = (
            float(jc["bytes_per_iter"]) / float(jc["t_iter"]))

    # the device the probe children measured on keys the profile
    fingerprint = next((rec.pop("fingerprint") for rec in records.values()
                        if rec.get("fingerprint")), None)
    for rec in records.values():
        rec.pop("fingerprint", None)
    payload = {
        "format_version": tune_profile.FORMAT_VERSION,
        "fingerprint": fingerprint,
        "created_unix": int(time.time()),
        "smoke": bool(smoke),
        "margin": MARGIN,
        "classes": records,
        "knobs": merged,
        "measured": measured,
    }
    if failures:
        payload["audit_failures"] = failures
    if out_path:
        tune_profile.write(payload, out_path)
        logger.info("tune: profile written to %s", out_path)
    return payload, failures
