"""The persistent autotuner (tempo_tpu/tune, ISSUE 15).

Load-bearing guarantees:

* profile lifecycle — a harness-produced profile roundtrips; a corrupt
  or foreign-fingerprint profile is REFUSED BY NAME with fallback to
  the built-in defaults (never half-applied);
* priority — an explicitly-set env knob always wins over the profile;
  the profile wins over the built-in default; ``set_measured`` wins
  over the profile's measured cost inputs;
* bitwise — chains run with a tuned profile loaded are bit-identical
  to the default-knob runs (tuning never changes result bits);
* cache key — the profile CRC rides ``cost.fingerprint()``: swapping
  profiles re-plans (a stale executable built under the other
  profile's knobs never replays), swapping back HITS the old entry;
* harness — coordinate descent keeps only audit-clean winners, merges
  only owned knobs, prunes dominated ladders, marks TPU-only classes
  hardware-gated on this backend, and flags bitwise-audit failures on
  contract-neutral axes.
"""

import json

import numpy as np
import pandas as pd
import pytest

from tempo_tpu import TSDF, profiling, tune
from tempo_tpu.plan import cache as plan_cache
from tempo_tpu.plan import cost
from tempo_tpu.tune import harness, space
from tempo_tpu.tune import profile as tp


@pytest.fixture(autouse=True)
def _clean_tune_state():
    tune.reload()
    cost.clear_measured()
    plan_cache.CACHE.clear()
    yield
    tune.reload()
    cost.clear_measured()
    plan_cache.CACHE.clear()


def _write_profile(path, knobs=None, measured=None, classes=None,
                   fingerprint=None):
    payload = {
        "format_version": tp.FORMAT_VERSION,
        "fingerprint": fingerprint or tp.runtime_fingerprint(),
        "created_unix": 0, "smoke": True, "margin": 0.02,
        "classes": classes or {},
        "knobs": knobs or {},
        "measured": measured or {},
    }
    return tp.write(payload, str(path))


def _frame(cols, K=4, L=64, seed=0):
    rng = np.random.default_rng(seed)
    secs = np.cumsum(rng.integers(1, 3, size=(K, L)), axis=-1)
    data = {"sym": np.repeat(np.arange(K), L),
            "event_ts": secs.ravel().astype(np.int64)}
    for c in cols:
        data[c] = rng.standard_normal(K * L)
    return TSDF(pd.DataFrame(data), "event_ts", ["sym"])


# ----------------------------------------------------------------------
# profile lifecycle: roundtrip, priority, refusal by name
# ----------------------------------------------------------------------

def test_profile_roundtrip_and_reader_priority(tmp_path, monkeypatch):
    p = _write_profile(
        tmp_path / "prof.json",
        knobs={"TEMPO_TPU_DMA_BUFFERS": 4, "TEMPO_TPU_PACK_COLS": 2,
               "TEMPO_TPU_SERVE_BATCH_ROWS": 16,
               "TEMPO_TPU_STREAM_MAX_ROWS": 32768})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    from tempo_tpu.ops import pallas_stream as ps
    from tempo_tpu.ops import pallas_window as pw

    assert tune.load() is not None
    assert tune.active_path() == p
    # profile beats the built-in defaults...
    assert ps.dma_buffers() == 4
    assert ps.pack_cols_cap() == 2
    assert pw._stream_max_rows() == 32768
    # ...and an explicit env knob beats the profile
    monkeypatch.setenv("TEMPO_TPU_DMA_BUFFERS", "3")
    monkeypatch.setenv("TEMPO_TPU_PACK_COLS", "8")
    monkeypatch.setenv("TEMPO_TPU_STREAM_MAX_ROWS", "8192")
    assert ps.dma_buffers() == 3
    assert ps.pack_cols_cap() == 8
    assert pw._stream_max_rows() == 8192


def test_serve_executor_batch_rows_from_profile(tmp_path, monkeypatch):
    from tempo_tpu.serve import MicroBatchExecutor, StreamingTSDF

    p = _write_profile(
        tmp_path / "prof.json",
        classes={"serve_batch": {
            "knobs": {"TEMPO_TPU_SERVE_BATCH_ROWS": 16}}},
        knobs={"TEMPO_TPU_SERVE_BATCH_ROWS": 16})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    stream = StreamingTSDF(["s0"], ["v"], window_secs=5.0,
                           window_rows_bound=8)
    ex = MicroBatchExecutor(stream)
    try:
        assert ex.batch_rows == 16
    finally:
        ex.close()
    # env knob wins
    monkeypatch.setenv("TEMPO_TPU_SERVE_BATCH_ROWS", "32")
    ex2 = MicroBatchExecutor(stream)
    try:
        assert ex2.batch_rows == 32
    finally:
        ex2.close()


def test_off_and_unset_resolution(monkeypatch):
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", "off")
    assert tune.load() is None
    assert tune.knob_value("TEMPO_TPU_DMA_BUFFERS") is None
    assert tune.measured() == {}
    assert tune.stamp() is None


def test_corrupt_profile_refused_by_name(tmp_path, monkeypatch):
    p = _write_profile(tmp_path / "prof.json",
                       knobs={"TEMPO_TPU_DMA_BUFFERS": 4})
    raw = json.load(open(p))
    raw["knobs"]["TEMPO_TPU_DMA_BUFFERS"] = 8   # CRC now stale
    json.dump(raw, open(p, "w"))
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    from tempo_tpu.ops import pallas_stream as ps

    # non-strict: falls back to the built-in defaults
    assert tune.load() is None
    assert ps.dma_buffers() == 2
    # strict: refused BY NAME (path + reason)
    with pytest.raises(tp.TuneProfileError, match="CRC mismatch"):
        tune.load(strict=True)
    with pytest.raises(tp.TuneProfileError, match="prof.json"):
        tune.load(strict=True)


def test_foreign_fingerprint_refused_by_name(tmp_path, monkeypatch):
    fp = tp.runtime_fingerprint()
    fp["device_kind"] = "tpu-v99"
    p = _write_profile(tmp_path / "foreign.json",
                       knobs={"TEMPO_TPU_DMA_BUFFERS": 8},
                       fingerprint=fp)
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    assert tune.load() is None                   # fallback to defaults
    with pytest.raises(tp.TuneProfileError) as ei:
        tune.load(strict=True)
    msg = str(ei.value)
    assert "foreign fingerprint" in msg
    assert "tpu-v99" in msg and "foreign.json" in msg


def test_foreign_jaxlib_refused(tmp_path, monkeypatch):
    fp = tp.runtime_fingerprint()
    fp["jaxlib"] = "9.9.99"
    p = _write_profile(tmp_path / "j.json", fingerprint=fp)
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    with pytest.raises(tp.TuneProfileError, match="jaxlib"):
        tune.load(strict=True)


def test_missing_explicit_path_refused(tmp_path, monkeypatch):
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE",
                       str(tmp_path / "nope.json"))
    assert tune.load() is None
    with pytest.raises(tp.TuneProfileError, match="does not exist"):
        tune.load(strict=True)


def test_undeclared_knob_and_measured_input_refused(tmp_path,
                                                    monkeypatch):
    p = _write_profile(tmp_path / "bad.json",
                       knobs={"TEMPO_TPU_PLAN": 1})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    with pytest.raises(tp.TuneProfileError, match="not a tunable knob"):
        tune.load(strict=True)
    p2 = _write_profile(tmp_path / "bad2.json",
                        measured={"not_a_cost_input": 1.0})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p2)
    with pytest.raises(tp.TuneProfileError,
                       match="not a cost-model input"):
        tune.load(strict=True)


def test_malformed_knob_value_refused_by_name(tmp_path, monkeypatch):
    """A non-integer knob value is refused at VALIDATE time (by name,
    never half-applied) — not discovered later as a ValueError inside a
    knob reader mid-kernel-build."""
    from tempo_tpu.ops import pallas_stream as ps

    for bad in ("on", 3.5, True, None):
        p = _write_profile(tmp_path / "badval.json",
                           knobs={"TEMPO_TPU_MEGACORE": bad})
        monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
        tune.reload()
        assert tune.load() is None          # fallback to defaults
        assert ps.megacore_enabled() in (True, False)   # reader safe
        with pytest.raises(tp.TuneProfileError,
                           match="TEMPO_TPU_MEGACORE"):
            tune.load(strict=True)
    p2 = _write_profile(tmp_path / "badmeas.json",
                        measured={"hbm_stream_rate": "fast"})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p2)
    tune.reload()
    assert tune.load() is None
    with pytest.raises(tp.TuneProfileError, match="non-numeric"):
        tune.load(strict=True)


def test_measured_join_chunk_lanes_refused(tmp_path, monkeypatch):
    """cost.params() recomputes join_chunk_lanes from env -> profile
    KNOBS -> default AFTER the measured overlay, so a measured
    join_chunk_lanes would validate and then be silently clobbered —
    it must be refused up front (the knobs section is its channel)."""
    p = _write_profile(tmp_path / "jcm.json",
                       measured={"join_chunk_lanes": 4096.0})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    assert tune.load() is None
    with pytest.raises(tp.TuneProfileError,
                       match="not a cost-model input"):
        tune.load(strict=True)


# ----------------------------------------------------------------------
# cost-model consumption: measured overlay, fingerprint, priority
# ----------------------------------------------------------------------

def test_measured_overlay_and_fingerprint(tmp_path, monkeypatch):
    fp_off = cost.fingerprint()
    p = _write_profile(tmp_path / "m.json",
                       measured={"hbm_stream_rate": 123e9})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    params = cost.params()
    assert params["hbm_stream_rate"] == 123e9
    assert params["tune_profile_crc"] == float(tune.load()["crc"])
    assert cost.fingerprint() != fp_off
    # set_measured still wins over the profile overlay
    cost.set_measured(hbm_stream_rate=9e9)
    assert cost.params()["hbm_stream_rate"] == 9e9
    # cost-model-off fingerprint still carries the profile stamp (the
    # profile changes kernel-structure knobs even with the model off)
    monkeypatch.setenv("TEMPO_TPU_COST_MODEL", "0")
    assert cost.fingerprint() == ("cost-off", float(tune.load()["crc"]))


def test_join_chunk_lanes_priority(tmp_path, monkeypatch):
    from tempo_tpu.ops import pallas_merge as pm

    p = _write_profile(tmp_path / "jc.json",
                       knobs={"TEMPO_TPU_JOIN_CHUNK_LANES": 4096})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    assert pm.join_chunk_lanes_override() == 4096
    assert cost.params()["join_chunk_lanes"] == 4096.0
    monkeypatch.setenv("TEMPO_TPU_JOIN_CHUNK_LANES", "8192")
    assert pm.join_chunk_lanes_override() == 8192
    assert cost.params()["join_chunk_lanes"] == 8192.0


# ----------------------------------------------------------------------
# bitwise: tuned-profile chains == default-knob chains (configs 2/3/7)
# ----------------------------------------------------------------------

def _chain_237(seed):
    """The config 2/3/7 op surface on one small mesh chain: AS-OF join
    + range stats + resample + EMA, collected to pandas."""
    from tempo_tpu.parallel import make_mesh

    left = _frame(["x"], seed=seed)
    right = _frame(["v0", "v1"], seed=seed + 1)
    mesh = make_mesh({"series": 1})
    return (left.on_mesh(mesh).asofJoin(right.on_mesh(mesh))
            .withRangeStats(colsToSummarize=["x"],
                            rangeBackWindowSecs=10)
            .EMA("x", exact=True)
            .collect().df)


def test_tuned_vs_default_bitwise_identity(tmp_path, monkeypatch):
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", "off")
    want = _chain_237(7)
    p = _write_profile(
        tmp_path / "t.json",
        knobs={"TEMPO_TPU_DMA_BUFFERS": 4, "TEMPO_TPU_PACK_COLS": 2,
               "TEMPO_TPU_STREAM_MAX_ROWS": 32768,
               "TEMPO_TPU_SERVE_BATCH_ROWS": 16},
        measured={"hbm_stream_rate": 7e9})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    assert tune.load() is not None
    got = _chain_237(7)
    pd.testing.assert_frame_equal(want, got, check_exact=True)


def test_tuned_vs_default_bitwise_host_resample_chain(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", "off")
    frame = _frame(["x"], K=3, L=96, seed=11)
    want = frame.resampleEMA("30 sec", "x").df
    p = _write_profile(tmp_path / "t2.json",
                       knobs={"TEMPO_TPU_PACK_COLS": 1,
                              "TEMPO_TPU_DMA_BUFFERS": 8})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", p)
    got = frame.resampleEMA("30 sec", "x").df
    pd.testing.assert_frame_equal(want, got, check_exact=True)


# ----------------------------------------------------------------------
# profile-in-cache-key: swap -> re-plan, never a stale replay
# ----------------------------------------------------------------------

def test_profile_swap_replans_through_cache(tmp_path, monkeypatch):
    from tempo_tpu.parallel import make_mesh

    monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
    left = _frame(["x"], seed=3)
    right = _frame(["v"], seed=4)
    mesh = make_mesh({"series": 2})
    chain = (left.on_mesh(mesh).asofJoin(right.on_mesh(mesh))
             .withRangeStats(colsToSummarize=["x"],
                             rangeBackWindowSecs=10))
    pa = _write_profile(tmp_path / "a.json",
                        knobs={"TEMPO_TPU_DMA_BUFFERS": 4})
    pb = _write_profile(tmp_path / "b.json",
                        knobs={"TEMPO_TPU_DMA_BUFFERS": 6})
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", pa)
    out_a = chain.collect().df
    st = profiling.plan_cache_stats()
    assert (st["builds"], st["hits"]) == (1, 0)
    chain.collect()
    assert profiling.plan_cache_stats()["hits"] == 1

    # swap: different CRC -> different cache key -> fresh build
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", pb)
    out_b = chain.collect().df
    st = profiling.plan_cache_stats()
    assert st["builds"] == 2, (
        f"profile swap replayed a stale executable: {st}")
    pd.testing.assert_frame_equal(out_a, out_b, check_exact=True)

    # swap back: the original entry must still HIT (no rebuild)
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", pa)
    chain.collect()
    st = profiling.plan_cache_stats()
    assert st["builds"] == 2 and st["hits"] >= 2, st


# ----------------------------------------------------------------------
# harness: descent, pruning, audit gate, hardware gating, merge rules
# ----------------------------------------------------------------------

def _cls(axes, owns=(), requires_tpu=False, name="c", probe="p"):
    return space.ShapeClass(name, probe, axes=tuple(axes),
                            owns=tuple(owns), requires_tpu=requires_tpu)


def _fake_probe(rates, digests=None, calls=None):
    """probe_fn stub: rates/digests keyed by the frozen knob dict."""
    def fn(probe, knobs, smoke=False, timeout=None):
        key = tuple(sorted(knobs.items()))
        if calls is not None:
            calls.append(key)
        if key in (rates or {}) and rates[key] is None:
            return {"error": "child died"}
        rate = (rates or {}).get(key, 1000.0)
        digest = (digests or {}).get(key, 42)
        return {"class": probe, "rows_per_sec": rate, "t_iter": 0.001,
                "bytes_per_iter": 100, "digest": digest,
                "fingerprint": tp.runtime_fingerprint()}
    return fn


def test_harness_picks_winner_and_merges_owned_knobs():
    ax = space.Axis("TEMPO_TPU_DMA_BUFFERS", (2, 3, 4), (2, 3, 4))
    rates = {(): 1000.0,
             (("TEMPO_TPU_DMA_BUFFERS", 3),): 1500.0,
             (("TEMPO_TPU_DMA_BUFFERS", 4),): 1400.0}
    cls = _cls([ax], owns=["TEMPO_TPU_DMA_BUFFERS"])
    rec, fails = harness.sweep_class(cls, probe_fn=_fake_probe(rates))
    assert not fails
    assert rec["knobs"] == {"TEMPO_TPU_DMA_BUFFERS": 3}
    assert rec["rows_per_sec"] == 1500.0
    assert rec["speedup"] == 1.5


def test_harness_merges_only_owned_knobs(monkeypatch):
    ax = space.Axis("TEMPO_TPU_DMA_BUFFERS", (2, 4), (2, 4))
    owner = _cls([ax], owns=["TEMPO_TPU_DMA_BUFFERS"], name="owner")
    cross = _cls([ax], owns=[], name="cross")
    monkeypatch.setattr(space, "SPACE", (owner, cross))
    rates = {(("TEMPO_TPU_DMA_BUFFERS", 4),): 2000.0}
    payload, fails = harness.sweep(probe_fn=_fake_probe(rates))
    assert not fails
    assert payload["knobs"] == {"TEMPO_TPU_DMA_BUFFERS": 4}
    assert payload["classes"]["cross"]["knobs"] == {
        "TEMPO_TPU_DMA_BUFFERS": 4}   # recorded, but not merged twice


def test_harness_bitwise_audit_rejects_and_flags_neutral_axes():
    ax = space.Axis("TEMPO_TPU_DMA_BUFFERS", (2, 4), (2, 4))
    digests = {(("TEMPO_TPU_DMA_BUFFERS", 4),): 999}   # bits moved!
    rates = {(("TEMPO_TPU_DMA_BUFFERS", 4),): 99999.0}
    cls = _cls([ax], owns=["TEMPO_TPU_DMA_BUFFERS"])
    rec, fails = harness.sweep_class(
        cls, probe_fn=_fake_probe(rates, digests))
    # the faster-but-wrong candidate must NOT win
    assert rec["knobs"] == {}
    assert rec["rejected"] and \
        "bitwise-audit" in rec["rejected"][0]["reason"]
    # a neutral axis changing bits is an identity regression
    assert fails and fails[0]["class"] == "c"


def test_harness_nonneutral_axis_rejection_is_not_a_failure():
    ax = space.Axis("TEMPO_TPU_STREAM_MAX_ROWS", (16384, 32768),
                    (16384, 32768), bitwise_neutral=False)
    digests = {(("TEMPO_TPU_STREAM_MAX_ROWS", 32768),): 7}
    cls = _cls([ax], owns=["TEMPO_TPU_STREAM_MAX_ROWS"])
    rec, fails = harness.sweep_class(
        cls, probe_fn=_fake_probe({}, digests))
    assert rec["rejected"] and not fails     # the gate working as built
    assert rec["knobs"] == {}


def test_harness_nonneutral_axis_never_crowns_a_winner():
    """A legality-ceiling axis whose candidate keeps the bits is
    performance-inert at the probe shape — a measured rate win is
    scheduler noise and must NOT ship a ceiling that could flip the
    engine (and the bits) at unprobed shapes."""
    ax = space.Axis("TEMPO_TPU_STREAM_MAX_ROWS", (16384, 32768),
                    (16384, 32768), bitwise_neutral=False)
    # same digest as the baseline, wildly faster: pure noise by
    # construction — the ceiling is unread inside the chosen engine
    rates = {(("TEMPO_TPU_STREAM_MAX_ROWS", 32768),): 99999.0}
    cls = _cls([ax], owns=["TEMPO_TPU_STREAM_MAX_ROWS"])
    rec, fails = harness.sweep_class(
        cls, probe_fn=_fake_probe(rates))
    assert not fails
    assert rec["knobs"] == {}
    assert rec["rows_per_sec"] == rec["default_rows_per_sec"]
    assert rec["rejected"] and \
        "legality-ceiling" in rec["rejected"][0]["reason"]


def test_harness_baseline_nondeterminism_fails_loudly():
    """If two default-knob probes disagree on the output digest, every
    candidate audit would be meaningless — the class must error (and
    flag an audit failure so --smoke exits nonzero), never sweep."""
    digests = iter([42, 43, 42, 42])

    def flappy(probe, knobs, smoke=False, timeout=None):
        return {"class": probe, "rows_per_sec": 1000.0, "t_iter": 1e-3,
                "bytes_per_iter": 100, "digest": next(digests)}

    cls = _cls([space.Axis("TEMPO_TPU_DMA_BUFFERS", (2, 4), (2, 4))],
               owns=["TEMPO_TPU_DMA_BUFFERS"])
    rec, fails = harness.sweep_class(cls, probe_fn=flappy)
    assert "error" in rec and "nondeterminism" in rec["error"]
    assert fails and "nondeterminism" in fails[0]["reason"]


def test_harness_prunes_dominated_ladder():
    ax = space.Axis("TEMPO_TPU_DMA_BUFFERS", (2, 3, 4, 6, 8),
                    (2, 3, 4, 6, 8))
    calls = []
    cls = _cls([ax], owns=["TEMPO_TPU_DMA_BUFFERS"])
    rec, _ = harness.sweep_class(
        cls, probe_fn=_fake_probe({}, calls=calls))
    # baseline (probed twice: incumbent bias) + 2 dominated
    # candidates, then the ladder is pruned
    assert len(calls) == 2 + harness.PRUNE_AFTER
    assert rec["knobs"] == {}


def test_harness_hardware_gates_what_the_child_cannot_run():
    """The probe child decides whether its backend runs the class (the
    parent never starts a backend); a gated baseline gates the class
    without probing any candidate."""
    calls = []

    def gated(probe, knobs, smoke=False, timeout=None):
        calls.append(knobs)
        return {"class": probe, "hardware_gated": "requires the TPU "
                "backend", "fingerprint": tp.runtime_fingerprint()}

    cls = _cls([space.Axis("TEMPO_TPU_JOIN_CHUNK_LANES", (None, 4096),
                           (None, 4096))],
               owns=["TEMPO_TPU_JOIN_CHUNK_LANES"], requires_tpu=True)
    rec, fails = harness.sweep_class(cls, probe_fn=gated)
    assert "hardware_gated" in rec and "TPU" in rec["hardware_gated"]
    assert not fails and len(calls) == 1


def test_harness_parent_never_starts_a_backend(monkeypatch):
    """A chip belongs to one process: the sweep's parent must leave the
    backend to its probe children, and key the profile by theirs."""
    import jax

    def no_backend(*a, **k):
        raise AssertionError("the tune parent started a JAX backend")

    monkeypatch.setattr(jax, "devices", no_backend)
    monkeypatch.setattr(jax, "default_backend", no_backend)
    ax = space.Axis("TEMPO_TPU_SERVE_BATCH_ROWS", (64, 16), (64, 16))
    cls = _cls([ax], owns=["TEMPO_TPU_SERVE_BATCH_ROWS"],
               name="serve_batch")
    monkeypatch.setattr(space, "SPACE", (cls,))
    fp = {"device_kind": "TPU v5 lite", "jaxlib": "0.9.0"}

    def child(probe, knobs, smoke=False, timeout=None):
        return {"class": probe, "rows_per_sec": 1000.0, "t_iter": 0.001,
                "bytes_per_iter": 100, "digest": 7, "fingerprint": fp}

    payload, fails = harness.sweep(probe_fn=child)
    assert not fails and payload["fingerprint"] == fp


def test_harness_baseline_error_records_class_error():
    cls = _cls([space.Axis("TEMPO_TPU_DMA_BUFFERS", (2, 4), (2, 4))])
    rec, fails = harness.sweep_class(
        cls, probe_fn=_fake_probe({(): None}))
    assert "error" in rec and not fails


def test_smoke_cli_fails_on_errored_class(monkeypatch, capsys):
    """The CI gate (--smoke) must exit nonzero when a shape class
    errors — a sweep whose probe children all die must not pass the
    'autotuner gate' green just because no bitwise audit ever ran."""
    from tempo_tpu.tune import __main__ as tune_main

    def broken_sweep(class_names=None, smoke=False, out_path=None,
                     probe_fn=None):
        return {"classes": {"stream_medium": {
            "error": "baseline probe failed: child rc=1"}}}, []

    monkeypatch.setattr(harness, "sweep", broken_sweep)
    assert tune_main.main(["--smoke"]) != 0
    assert "SWEEP BROKEN" in capsys.readouterr().err
    # a FULL sweep tolerates one errored class when others measured...
    def partial_sweep(class_names=None, smoke=False, out_path=None,
                      probe_fn=None):
        return {"classes": {
            "stream_medium": {"error": "child rc=1"},
            "serve_batch": {"rows_per_sec": 5000.0,
                            "default_rows_per_sec": 5000.0,
                            "speedup": 1.0, "knobs": {}, "probes": 3,
                            "rejected": []},
        }}, []

    monkeypatch.setattr(harness, "sweep", partial_sweep)
    assert tune_main.main(["--out", "/dev/null"]) == 0
    # ...but fails when NO class measured anything
    monkeypatch.setattr(harness, "sweep", broken_sweep)
    assert tune_main.main(["--out", "/dev/null"]) != 0


def test_sweep_payload_roundtrips_through_profile(tmp_path,
                                                  monkeypatch):
    ax = space.Axis("TEMPO_TPU_SERVE_BATCH_ROWS", (64, 16), (64, 16))
    cls = _cls([ax], owns=["TEMPO_TPU_SERVE_BATCH_ROWS"],
               name="serve_batch")
    monkeypatch.setattr(space, "SPACE", (cls,))
    rates = {(("TEMPO_TPU_SERVE_BATCH_ROWS", 16),): 5000.0}
    out = tmp_path / "swept.json"
    payload, fails = harness.sweep(probe_fn=_fake_probe(rates),
                                   out_path=str(out))
    assert not fails and out.exists()
    monkeypatch.setenv("TEMPO_TPU_TUNE_PROFILE", str(out))
    prof = tune.load(strict=True)
    assert prof["knobs"] == {"TEMPO_TPU_SERVE_BATCH_ROWS": 16}
    assert tune.knob_value("TEMPO_TPU_SERVE_BATCH_ROWS",
                           "serve_batch") == 16


def test_space_registry_is_well_formed():
    from tempo_tpu import config

    names = [c.name for c in space.SPACE]
    assert len(names) == len(set(names))
    for cls in space.SPACE:
        for axis in cls.axes:
            assert axis.knob in tp.TUNABLE_KNOBS
            assert axis.knob in config.KNOBS        # declared knob
            assert axis.values[0] == axis.smoke_values[0], (
                "ladders must start at the default (the incumbent the "
                "baseline probe measures)")
        for knob in cls.owns:
            assert any(a.knob == knob for a in cls.axes)
    # every knob has at most ONE owning class
    owned = [k for c in space.SPACE for k in c.owns]
    assert len(owned) == len(set(owned))
    # smoke classes cover both probe families
    smoke_names = {c.name for c in space.classes(smoke=True)}
    assert smoke_names == {"stream_medium", "serve_batch"}
    with pytest.raises(KeyError, match="unknown shape class"):
        space.classes(["nope"])
