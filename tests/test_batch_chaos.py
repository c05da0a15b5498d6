"""Batch-plane fault-domain chaos: the signed-barrier machinery behind
run_resumable (foreign refusal, chained manifests) and the campaign
smoke (tempo_tpu/testing/chaos.py::run_pipeline_campaign — bench
config 16's body at tiny sizes)."""

import os

import numpy as np
import pandas as pd
import pytest

from tempo_tpu import TSDF, checkpoint, resilience
from tempo_tpu.resilience import CheckpointError
from tempo_tpu.testing import chaos, faults

pytestmark = pytest.mark.chaos


@pytest.fixture
def host_frame():
    rng = np.random.default_rng(4)
    n = 120
    return TSDF(pd.DataFrame({
        "sym": rng.choice(["a", "b"], n),
        "event_ts": pd.to_datetime(
            np.sort(rng.integers(0, 500, n)) * 1_000_000_000),
        "px": rng.standard_normal(n),
    }), "event_ts", ["sym"])


STEPS = [("EMA", {"colName": "px", "exact": True}),
         ("withRangeStats", {"colsToSummarize": ["px"],
                             "rangeBackWindowSecs": 60})]


# ----------------------------------------------------------------------
# run_resumable: signed, chained step manifests
# ----------------------------------------------------------------------

def test_step_manifests_are_signed_and_chained(host_frame, tmp_path):
    d = str(tmp_path / "signed")
    resilience.run_resumable(host_frame, STEPS, d, every=1, keep_last=5)
    metas = {s: checkpoint.read_meta(p)
             for s, p in checkpoint.list_steps(d)}
    sig = resilience.resume_signature(host_frame, STEPS)
    assert all(m["pipeline_signature"] == sig for m in metas.values())
    assert metas[2]["prev_step"] == 1
    assert metas[2]["prev_manifest_crc"] == checkpoint.manifest_crc(
        os.path.join(d, "step_00001"))


def test_same_steps_different_data_refused(host_frame, tmp_path):
    """A reused ckpt_dir must not hand a re-run over NEW data the
    previous data's retained final checkpoint (zero steps re-run,
    yesterday's output returned as today's) — the default signature
    folds the input frame's content fingerprint."""
    d = str(tmp_path / "stale")
    resilience.run_resumable(host_frame, STEPS, d, every=1)
    df2 = host_frame.df.copy()
    df2["px"] = df2["px"] + 1.0
    from tempo_tpu import TSDF

    other = TSDF(df2, "event_ts", ["sym"])
    with pytest.raises(CheckpointError, match="DIFFERENT pipeline"):
        resilience.run_resumable(other, STEPS, d, every=1)


def test_foreign_pipeline_resume_refused_by_name(host_frame, tmp_path):
    """The silent foreign-resume hazard: a stale ckpt_dir written by a
    DIFFERENT pipeline must refuse by name, not restore cleanly."""
    d = str(tmp_path / "foreign")
    resilience.run_resumable(host_frame, STEPS, d, every=1)
    other = STEPS + [("EMA", {"colName": "px", "exact": False})]
    with pytest.raises(CheckpointError, match="DIFFERENT pipeline"):
        resilience.run_resumable(host_frame, other, d, every=1)


def test_unstamped_legacy_checkpoint_still_resumes(host_frame, tmp_path,
                                                   caplog):
    """Pre-signing checkpoints (no stamped signature) keep resuming,
    with a warning — compatibility, not a refusal."""
    import logging

    d = str(tmp_path / "legacy")
    out = resilience.run_resumable(host_frame, STEPS, d, every=1)
    # strip the stamp from the newest manifest (simulate a pre-round
    # checkpoint)
    import json

    mp = os.path.join(d, "step_00002", "manifest.json")
    with open(mp) as f:
        man = json.load(f)
    man["meta"] = {}
    with open(mp, "w") as f:
        json.dump(man, f)
    with caplog.at_level(logging.WARNING, logger="tempo_tpu"):
        again = resilience.run_resumable(host_frame, STEPS, d, every=1)
    assert any("no pipeline signature" in r.message
               for r in caplog.records)
    pd.testing.assert_frame_equal(again.df, out.df, check_exact=True)


def test_broken_chain_link_falls_back(host_frame, tmp_path, caplog):
    """A rewritten predecessor breaks the newest step's chain link:
    resume falls back (warned) instead of trusting the chain head."""
    import logging

    d = str(tmp_path / "chain")
    resilience.run_resumable(host_frame, STEPS, d, every=1, keep_last=5)
    # rewrite step 1's manifest bytes -> step 2's recorded link breaks
    mp = os.path.join(d, "step_00001", "manifest.json")
    with open(mp, "a") as f:
        f.write(" ")
    ran = []

    def counted(i, name, kwargs):
        def step(f):
            ran.append(i)
            return getattr(f, name)(**kwargs)
        return step

    steps = [counted(i, n, k) for i, (n, k) in enumerate(STEPS)]
    sig = resilience.resume_signature(host_frame, STEPS)
    with caplog.at_level(logging.WARNING, logger="tempo_tpu"):
        resilience.run_resumable(host_frame, steps, d, every=1,
                                 keep_last=5, signature=sig)
    assert any("chained predecessor" in r.message for r in caplog.records)
    assert ran == [1], ran     # fell back to step 1, re-ran only step 2


def test_pipeline_signature_stability():
    a = resilience.pipeline_signature(STEPS)
    assert a == resilience.pipeline_signature(list(STEPS))
    assert a != resilience.pipeline_signature(STEPS[:1])
    assert a != resilience.pipeline_signature(
        STEPS + [("EMA", {"colName": "px"})])
    # callables canonicalize by position: instrumented re-wraps of the
    # same chain keep resuming
    f1, f2 = (lambda x: x), (lambda x: x)
    assert resilience.pipeline_signature([f1, f1]) == \
        resilience.pipeline_signature([f2, f2])


def test_pipeline_signature_distinguishes_numpy_scalar_kwargs():
    """np.int64 kwargs canonicalize by VALUE (unwrapped), not by type
    — two pipelines differing only in a numpy-typed window must never
    share a signature (they would resume each other's state)."""
    sig = lambda w: resilience.pipeline_signature(
        [("withRangeStats", {"rangeBackWindowSecs": w})])
    assert sig(np.int64(60)) != sig(np.int64(120))
    # and a numpy scalar equals its plain-python twin (a restarted
    # process may build the same kwargs either way)
    assert sig(np.int64(60)) == sig(60)


def test_pipeline_signature_stable_for_reprless_kwargs():
    """Kwarg values without a stable __repr__ (a TSDF operand, say)
    canonicalize by type, not by memory address — a restarted process
    must match its OWN checkpoints' signature."""

    class Operand:       # default object repr carries the address
        pass

    sigs = {resilience.pipeline_signature(
        [("asofJoin", {"right": Operand()})]) for _ in range(3)}
    assert len(sigs) == 1
    # but the step NAME still distinguishes pipelines
    assert resilience.pipeline_signature(
        [("asofJoin", {"right": Operand()})]) != \
        resilience.pipeline_signature([("EMA", {"right": Operand()})])


# ----------------------------------------------------------------------
# The campaign smoke (the batch-plane campaign at tiny sizes)
# ----------------------------------------------------------------------

def test_pipeline_campaign_smoke(tmp_path):
    rep = chaos.run_pipeline_campaign(
        str(tmp_path), rows_total=40_000, physical_rows=10_000,
        n_keys=16, seed=31, n_windows=2, ckpt_every=2)
    assert rep["ingest_resume"]["reread_committed_shards"] == 0
    assert rep["quarantine"]["named_error"] is True
    assert rep["plan_barriers"]["zero_builds_after_resume"] is True
    assert rep["plan_barriers"]["pre_barrier_ops_rerun"] == 0
    assert rep["sweep"]["builds_after_resume"] == 0
    assert rep["sweep"]["replayed_slabs"] >= 1
    assert all(rep["foreign_signature_refused"].values())
    assert "bitwise" in rep["tail_audit"]
