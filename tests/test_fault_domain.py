"""Fault-domain runtime: deadlines, cancellation, supervised recovery,
circuit breakers, differential cohort snapshots, and the chaos
campaign smoke.

The serving/query planes' availability contracts (ISSUE 13): a ticket
ALWAYS resolves — with its result or a NAMED error (stage-named
``DeadlineExceeded``, ``Cancelled``, ``QuarantinedError``,
``ShutdownError``) — the planes outlive worker death (supervisor) and
poison pills (per-key breakers with half-open probes), and cohort
failover is incremental: differential snapshots chained by CRC'd
manifests, resumed base-first, replayed tails bitwise.
"""

import os
import time

import numpy as np
import pytest

from tempo_tpu import checkpoint, resilience
from tempo_tpu.resilience import (Cancelled, CircuitBreaker, Deadline,
                                  DeadlineExceeded, QuarantinedError,
                                  ShutdownError)
from tempo_tpu.serve import CohortExecutor, StreamCohort
from tempo_tpu.testing import chaos, faults

pytestmark = pytest.mark.chaos

W = dict(window_secs=9.0, window_rows_bound=8, ema_alpha=0.2)


def _mk(S=3, **kw):
    cohort = StreamCohort(("px",), max_lookback=5, slots=max(2, S),
                          **W, **kw)
    members = [cohort.add_stream(f"m{s}", ["s0"]) for s in range(S)]
    return cohort, members


def _push_tick(m, t, v=1.0):
    return ("right", m, "s0", t * 10**9, {"px": np.float32(v)}, None)


# ----------------------------------------------------------------------
# Primitives
# ----------------------------------------------------------------------

def test_deadline_after_and_stage_named_check():
    assert Deadline.after(None) is None
    assert Deadline.after(0) is None
    dl = Deadline.after(60.0)
    assert Deadline.after(dl) is dl         # passthrough
    assert not dl.expired() and dl.remaining() > 0
    dl.check("anywhere")                    # within budget: no raise
    fake = {"t": 0.0}
    dead = Deadline(0.5, clock=lambda: fake["t"])
    fake["t"] = 1.0
    assert dead.expired()
    with pytest.raises(DeadlineExceeded) as ei:
        dead.check("admission queue")
    assert ei.value.stage == "admission queue"
    assert "admission queue" in str(ei.value)
    # classified as DEADLINE (it is a TimeoutError subtype with a kind)
    assert resilience.classify(ei.value) is resilience.FailureKind.DEADLINE


def test_circuit_breaker_threshold_halfopen_probe_and_abandon():
    clock = {"t": 0.0}
    br = CircuitBreaker(threshold=3, cooldown_s=10.0,
                        clock=lambda: clock["t"])
    for _ in range(2):
        br.record("k", ok=False)
    br.allow("k")                           # 2 < threshold: closed
    br.record("k", ok=False)                # 3rd consecutive: OPEN
    assert br.state("k") == "open"
    with pytest.raises(QuarantinedError) as ei:
        br.allow("k", label="stream member")
    assert ei.value.key == "k" and ei.value.retry_after_s > 0
    clock["t"] = 10.5                       # cooldown elapsed
    br.allow("k")                           # the single half-open probe
    assert br.state("k") == "half-open"
    with pytest.raises(QuarantinedError):
        br.allow("k")                       # second probe refused
    br.record("k", ok=False)                # failed probe: re-open
    assert br.state("k") == "open"
    clock["t"] = 21.0
    br.allow("k")                           # next probe
    br.record("k", ok=True)                 # success closes + resets
    assert br.state("k") == "closed"
    br.allow("k")
    # a vanished probe must not quarantine the key forever
    for _ in range(3):
        br.record("k", ok=False)
    clock["t"] = 32.0
    br.allow("k")                           # probe admitted...
    br.abandon("k")                         # ...but never reports
    br.allow("k")                           # a fresh probe is admitted
    assert br.stats()["trips"] >= 2


def test_delay_on_call_records_and_passes_through():
    calls = {"n": 0}

    class T:
        def f(self):
            calls["n"] += 1
            return calls["n"]

    t = T()
    with faults.FaultInjector() as fi:
        fi.delay_on_call(T, "f", seconds=0.05, call_no=2)
        t0 = time.perf_counter()
        assert t.f() == 1                   # untouched
        fast = time.perf_counter() - t0
        t0 = time.perf_counter()
        assert t.f() == 2                   # delayed, then passes
        slow = time.perf_counter() - t0
    assert slow >= 0.05 > fast
    assert [r.action for r in fi.records] == ["pass", "delay"]
    assert t.f() == 3                       # patch restored


# ----------------------------------------------------------------------
# Executor plane: deadlines, cancel, shutdown, supervision, quarantine
# ----------------------------------------------------------------------

def test_ticket_deadline_dies_in_queue_stage_named():
    """Latency injection holds the dispatch; a tick queued behind it
    with a smaller budget fails with DeadlineExceeded naming the
    queue stage — and was never folded (its retry lands cleanly)."""
    cohort, (m,) = _mk(1)
    with CohortExecutor(cohort, coalesce_s=0.0) as ex:
        with faults.FaultInjector() as fi:
            fi.delay_on_call(StreamCohort, "dispatch", seconds=0.4,
                             call_no=1)
            first = ex.submit(m, "right", "s0", 10**9,
                              {"px": np.float32(1)})
            t0 = time.perf_counter()
            while not any(r.action == "delay" for r in fi.records):
                assert time.perf_counter() - t0 < 30
                time.sleep(0.002)
            doomed = ex.submit(m, "right", "s0", 2 * 10**9,
                               {"px": np.float32(2)}, deadline=0.1)
            with pytest.raises(DeadlineExceeded) as ei:
                doomed.result(timeout=60)
            assert ei.value.stage == "serve queue"
            first.result(timeout=60)
        assert ex.deadline_failures == 1
        # the doomed tick was never dispatched: its retry is not late
        retry = ex.submit(m, "right", "s0", 2 * 10**9,
                          {"px": np.float32(2)})
        retry.result(timeout=60)
        assert m.acked == 2


def test_ticket_cancel_never_reaches_the_stream():
    cohort, (m,) = _mk(1)
    with CohortExecutor(cohort, coalesce_s=0.0) as ex:
        with faults.FaultInjector() as fi:
            fi.delay_on_call(StreamCohort, "dispatch", seconds=0.3,
                             call_no=1)
            ex.submit(m, "right", "s0", 10**9, {"px": np.float32(1)})
            t0 = time.perf_counter()
            while not any(r.action == "delay" for r in fi.records):
                assert time.perf_counter() - t0 < 30
                time.sleep(0.002)
            victim = ex.submit(m, "right", "s0", 2 * 10**9,
                               {"px": np.float32(2)})
            assert victim.cancel() is True
            with pytest.raises(Cancelled):
                victim.result(timeout=60)
    assert m.acked == 1                     # the cancelled tick never ran


def test_close_timeout_fails_pending_with_shutdown_error():
    """The satellite fix: a close() whose drain deadline expires fails
    every still-pending ticket with ShutdownError instead of leaving
    callers blocked on result() forever."""
    cohort, (m,) = _mk(1)
    ex = CohortExecutor(cohort, coalesce_s=0.0)
    with faults.FaultInjector() as fi:
        fi.delay_on_call(StreamCohort, "dispatch", seconds=1.5,
                         call_no=1)
        slow = ex.submit(m, "right", "s0", 10**9, {"px": np.float32(1)})
        t0 = time.perf_counter()
        while not any(r.action == "delay" for r in fi.records):
            assert time.perf_counter() - t0 < 30
            time.sleep(0.002)
        stuck = ex.submit(m, "right", "s0", 2 * 10**9,
                          {"px": np.float32(2)})
        t0 = time.perf_counter()
        ex.close(timeout=0.2)               # one shared drain deadline
        assert time.perf_counter() - t0 < 1.2
        with pytest.raises(ShutdownError):
            stuck.result(timeout=60)
        # the IN-FLIGHT tick resolves too — with its result or the
        # shutdown error, whichever wins the race (a timed-out drain
        # is a kill: in-flight work is indeterminate BY NATURE, the
        # contract is only that no caller hangs)
        try:
            slow.result(timeout=60)
        except ShutdownError:
            pass
    with pytest.raises(ShutdownError):
        ex.submit(m, "right", "s0", 3 * 10**9, {"px": np.float32(3)})


def test_supervisor_restarts_drain_thread_after_plane_fault():
    cohort, (m,) = _mk(1)
    with CohortExecutor(cohort, coalesce_s=0.0) as ex:
        with faults.FaultInjector() as fi:
            fi.flaky(CohortExecutor, "_split", failures=1)
            bad = ex.submit(m, "right", "s0", 10**9,
                            {"px": np.float32(1)})
            with pytest.raises(faults.InjectedFault):
                bad.result(timeout=60)
        t0 = time.perf_counter()
        while ex.restarts < 1:
            assert time.perf_counter() - t0 < 30
            time.sleep(0.002)
        # the restarted plane serves the retry
        ok = ex.submit(m, "right", "s0", 10**9, {"px": np.float32(1)})
        ok.result(timeout=60)
    assert ex.restarts == 1 and m.acked == 1


def test_simulated_kill_fails_all_outstanding_and_closes_the_plane():
    cohort, members = _mk(3)
    ex = CohortExecutor(cohort, coalesce_s=0.0)
    with faults.FaultInjector() as fi:
        fi.kill_on_call(StreamCohort, "dispatch", call_no=1)
        tickets = ex.submit_many([_push_tick(m, 1) for m in members])
        for t in tickets:
            with pytest.raises(ShutdownError):
                t.result(timeout=60)
    assert isinstance(ex.fatal, faults.SimulatedKill)
    with pytest.raises(ShutdownError):
        ex.submit(members[0], "right", "s0", 10**9,
                  {"px": np.float32(1)})
    ex.close(timeout=5)


def test_member_quarantine_and_halfopen_probe():
    cohort, (mi, mj) = _mk(2)
    br = CircuitBreaker(threshold=2, cooldown_s=0.3)
    with CohortExecutor(cohort, coalesce_s=0.0, breaker=br) as ex:
        for _ in range(2):                  # poison: unknown series
            t = ex.submit(mi, "right", "nope", 10**9,
                          {"px": np.float32(1)})
            with pytest.raises(ValueError):
                t.result(timeout=60)
        assert br.state(mi.name) == "open"
        q = ex.submit(mi, "right", "s0", 10**9, {"px": np.float32(1)})
        assert q.done()                     # fail-fast: pre-resolved
        with pytest.raises(QuarantinedError):
            q.result()
        # the healthy member is untouched by its neighbour's breaker
        ok = ex.submit(mj, "right", "s0", 10**9, {"px": np.float32(2)})
        ok.result(timeout=60)
        time.sleep(0.35)
        probe = ex.submit(mi, "right", "s0", 10**9,
                          {"px": np.float32(1)})
        probe.result(timeout=60)            # success closes the circuit
        assert br.state(mi.name) == "closed"
        assert br.stats()["trips"] == 1


# ----------------------------------------------------------------------
# Query-service plane
# ----------------------------------------------------------------------

def _service_bits():
    import pandas as pd

    from tempo_tpu import TSDF
    from tempo_tpu.service import lazy_frame

    rng = np.random.default_rng(3)
    n = 64
    frame = TSDF(pd.DataFrame({
        "sym": np.repeat(np.arange(2), n // 2),
        "event_ts": np.tile(np.arange(n // 2, dtype=np.int64), 2),
        "x": rng.standard_normal(n),
    }), "event_ts", ["sym"])
    return lambda: lazy_frame(frame).EMA("x", exact=True)


def test_service_deadline_cancel_quarantine_supervision():
    """The query plane's whole gauntlet in one deterministic pass
    (single worker): poison signature quarantined at submit and probed
    half-open, stage-named deadline death for a queued query, a
    cancellation that never runs, and a supervised worker restart —
    while good queries keep completing."""
    from tempo_tpu.plan import executor as plan_executor
    from tempo_tpu.plan import ir
    from tempo_tpu.service import QueryService

    good = _service_bits()
    poison = ir.Node("chaos_poison")
    br = CircuitBreaker(threshold=2, cooldown_s=0.3)
    svc = QueryService(workers=1, breaker=br)
    try:
        svc.submit("good", good()).result(timeout=120)
        # ---- quarantine
        for _ in range(2):
            with pytest.raises(ValueError):
                svc.submit("evil", poison).result(timeout=120)
        with pytest.raises(QuarantinedError):
            svc.submit("evil", poison)
        time.sleep(0.35)
        with pytest.raises(ValueError):     # the half-open probe runs
            svc.submit("evil", poison).result(timeout=120)
        assert br.state(ir.signature(poison)) == "open"  # probe failed
        # ---- supervision
        with faults.FaultInjector() as fi:
            fi.flaky(QueryService, "_pick", failures=1)
            svc.submit("good", good()).result(timeout=120)
            assert any(r.action == "raise" for r in fi.records)
        assert svc.restarts >= 1
        # ---- deadline + cancel behind a delayed execution
        with faults.FaultInjector() as fi:
            fi.delay_on_call(plan_executor, "execute", seconds=0.4,
                             call_no=1)
            slow = svc.submit("good", good())
            t0 = time.perf_counter()
            while not any(r.action == "delay" for r in fi.records):
                assert time.perf_counter() - t0 < 30
                time.sleep(0.002)
            doomed = svc.submit("good", good(), deadline_s=0.1)
            victim = svc.submit("good", good())
            assert victim.cancel() is True
            with pytest.raises(Cancelled):
                victim.result(timeout=120)
            with pytest.raises(DeadlineExceeded) as ei:
                doomed.result(timeout=120)
            assert ei.value.stage in ("admission queue", "dispatch")
            slow.result(timeout=120)
        st = svc.stats()
        c = st["tenants"]["good"]
        assert c["cancelled"] == 1
        assert st["tenants"]["evil"]["quarantined"] == 1
        assert st["restarts"] >= 1
    finally:
        svc.close(timeout=30)


# ----------------------------------------------------------------------
# Differential snapshots + chain resume
# ----------------------------------------------------------------------

def _feed(members, lo, hi, k=lambda s: 0):
    for t in range(lo, hi):
        m = members[t % len(members)]
        m.push([m.series[k(t)]], [t * 10**9],
               {"px": np.float32([float(t)])})


def _state_fingerprint(cohort):
    out = {}
    for bucket in sorted(cohort._groups):
        g = cohort._groups[bucket]
        g._host()
        for name, arr in sorted(g.state.items()):
            out[f"g{bucket}.{name}"] = np.asarray(arr).tobytes()
        out[f"g{bucket}.wm"] = (g.wm_ts.tobytes() + g.wm_seq.tobytes()
                                + g.wm_side.tobytes())
    out["members"] = sorted(
        (m.name, m._group.bucket, m.slot, tuple(m.series), m.acked)
        for m in cohort._members.values())
    out["acked_total"] = cohort.acked_total
    return out


def test_differential_chain_bytes_and_byte_identical_resume(tmp_path):
    """The acceptance scenario: a full -> diff -> diff chain writes
    bytes that scale with DIRTY buckets, and a kill + resume restores
    state byte-identical to a single full snapshot of the same
    moment."""
    d_chain = str(tmp_path / "chain")
    d_full = str(tmp_path / "single_full")
    cohort = StreamCohort(("px",), max_lookback=5, slots=2,
                          checkpoint_dir=d_chain, **W)
    m_small = cohort.add_stream("small", ["s0"])          # bucket 1
    m_big = cohort.add_stream("big", ["b0", "b1", "b2"])  # bucket 4
    members = [m_small, m_big]
    _feed(members, 1, 9)
    p_full = cohort.snapshot()
    # dirty ONLY the small bucket
    _feed([m_small], 9, 13)
    p_d1 = cohort.snapshot(differential=True)
    # dirty ONLY the big bucket
    _feed([m_big], 13, 17)
    p_d2 = cohort.snapshot(differential=True)
    du = lambda p: sum(
        os.path.getsize(os.path.join(r, f))
        for r, _, fs in os.walk(p) for f in fs)
    assert du(p_d1) < du(p_d2) < du(p_full)   # bytes ~ dirty buckets
    assert StreamCohort._snapshot_mode(p_d2)["mode"] == "differential"
    # a single full snapshot of the same moment, into a separate family
    cohort.checkpoint_dir = d_full
    cohort._last_snapshot = None
    p_ref = cohort.snapshot()
    want = _state_fingerprint(StreamCohort.resume(d_full))
    # "kill": a fresh process resumes the chain base-first
    got = _state_fingerprint(StreamCohort.resume(d_chain))
    assert got == want
    # and the resumed cohort continues bitwise: same next emission
    r = StreamCohort.resume(d_chain)
    a = r.stream("small").push(["s0"], [100 * 10**9],
                               {"px": np.float32([7.0])})
    b = StreamCohort.resume(d_full).stream("small").push(
        ["s0"], [100 * 10**9], {"px": np.float32([7.0])})
    for key in b:
        assert np.asarray(a[key]).tobytes() == \
            np.asarray(b[key]).tobytes(), key


def test_broken_chain_link_falls_back_to_older_intact_state(tmp_path):
    d = str(tmp_path / "chain")
    cohort = StreamCohort(("px",), max_lookback=5, slots=2,
                          checkpoint_dir=d, **W)
    m = cohort.add_stream("m", ["s0"])
    _feed([m], 1, 5)
    cohort.snapshot()
    _feed([m], 5, 9)
    p_d1 = cohort.snapshot(differential=True)
    _feed([m], 9, 13)
    cohort.snapshot(differential=True)
    # corrupt the MIDDLE link's manifest: the newest head's chain is
    # broken (its recorded predecessor CRC no longer matches), so
    # resume must fall back to the intact prefix — never stitch
    # through a corrupt link
    faults.flip_byte(os.path.join(p_d1, "manifest.json"), 10)
    r = StreamCohort.resume(d)
    assert r.stream("m").acked == 4         # the base full's state
    # and a fully-corrupt family raises by name
    for _, path in checkpoint.list_steps(d):
        faults.truncate_file(os.path.join(path, "manifest.json"), 0.1)
    with pytest.raises(checkpoint.CheckpointError,
                       match="no intact cohort snapshot chain"):
        StreamCohort.resume(d)


def test_chain_prune_keeps_diffs_reachable(tmp_path):
    """Retention counts FULL snapshots; a diff is never orphaned from
    its base by pruning."""
    d = str(tmp_path / "chain")
    cohort = StreamCohort(("px",), max_lookback=5, slots=2,
                          checkpoint_dir=d, keep_last=1, **W)
    m = cohort.add_stream("m", ["s0"])
    _feed([m], 1, 4)
    cohort.snapshot()
    for i in range(3):
        _feed([m], 4 + 3 * i, 7 + 3 * i)
        cohort.snapshot(differential=True)
    steps = checkpoint.list_steps(d)
    assert len(steps) == 4                  # 1 full + 3 diffs, all kept
    r = StreamCohort.resume(d)
    assert r.stream("m").acked == 12


# ----------------------------------------------------------------------
# Campaign smoke (the serving and service campaigns at test scale)
# ----------------------------------------------------------------------

def test_serving_campaign_smoke(tmp_path):
    rep = chaos.run_serving_campaign(
        str(tmp_path / "ck"), n_streams=8, events_per_stream=12,
        seed=23, ckpt_every=16)
    assert rep["no_hung_tickets"] and rep["zero_builds_after_recovery"]
    assert rep["injected"]["kills"] == 1
    assert rep["outcomes"]["deadline"] >= 1
    assert rep["outcomes"]["quarantined"] >= 1
    assert rep["restarts"] >= 1
    assert rep["snapshot_bytes"]["diff_vs_full"] < 1.0
    assert "bitwise" in rep["tail_audit"]


def test_service_campaign_smoke():
    rep = chaos.run_service_campaign(n_queries=6, seed=29)
    assert rep["no_hung_tickets"]
    assert rep["outcomes"]["quarantined"] >= 1
    assert rep["outcomes"]["deadline"] >= 1
    assert rep["outcomes"]["cancelled"] >= 1
    assert rep["restarts"] >= 1
