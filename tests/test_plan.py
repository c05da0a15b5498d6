"""Lazy query planner (tempo_tpu/plan/): recording, optimizer
rewrites, executable cache, explain(), and the bitwise planned==eager
contract.

The load-bearing guarantee: with ``TEMPO_TPU_PLAN=1`` a recorded chain
must produce BIT-IDENTICAL results to the same chain executed eagerly
— across the randomized op-chain matrix (seq / skipNulls / maxLookback
x stats / EMA / resample orderings), on both the fused single-program
path and the op-by-op fallback.  The one deliberate exception is the
resampleEMA fusion rewrite, which by design produces exactly
``TSDF.resampleEMA``'s output (bit-identical to the fused entry point;
the unfused chain differs from it in float rounding — MIGRATION.md).
"""

import logging

import numpy as np
import pandas as pd
import pytest

import tempo_tpu  # noqa: F401  (jax config side effects)
import jax

from tempo_tpu import TSDF, packing, profiling
from tempo_tpu.parallel import make_mesh
from tempo_tpu.plan import cache as plan_cache
from tempo_tpu.plan import hints as plan_hints
from tempo_tpu.plan import ir, lazy, optimizer

K, L = 3, 48
WINDOW = 10


def make_frames(seed=0, nulls=False, seq=False, rows=L):
    rng = np.random.default_rng(seed)
    secs = np.cumsum(rng.integers(1, 3, size=(K, rows)).astype(np.int64),
                     axis=-1)
    syms = np.repeat([f"s{i}" for i in range(K)], rows)
    x = rng.standard_normal(K * rows)
    df_l = pd.DataFrame({"sym": syms, "event_ts": secs.ravel(), "x": x})
    r_secs = np.cumsum(rng.integers(1, 3, size=(K, rows)).astype(np.int64),
                       axis=-1)
    v0 = rng.standard_normal(K * rows)
    v1 = rng.standard_normal(K * rows)
    if nulls:
        v0[rng.random(K * rows) < 0.15] = np.nan
    df_r = pd.DataFrame({"sym": syms, "event_ts": r_secs.ravel(),
                         "v0": v0, "v1": v1})
    seq_col = None
    if seq:
        df_r["seq"] = rng.integers(0, 5, size=K * rows)
        seq_col = "seq"
    return (TSDF(df_l, "event_ts", ["sym"]),
            TSDF(df_r, "event_ts", ["sym"], sequence_col=seq_col))


@pytest.fixture
def plan_on(monkeypatch):
    monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
    plan_cache.CACHE.clear()
    yield
    plan_cache.CACHE.clear()


@pytest.fixture
def plan_off(monkeypatch):
    monkeypatch.delenv("TEMPO_TPU_PLAN", raising=False)


# ----------------------------------------------------------------------
# Recording / laziness basics
# ----------------------------------------------------------------------

def test_eager_remains_default(plan_off):
    lt, rt = make_frames()
    out = lt.asofJoin(rt)
    assert isinstance(out, TSDF)          # no lazy wrapper without knob


def test_recording_returns_lazy_wrappers(plan_on):
    lt, rt = make_frames()
    j = lt.asofJoin(rt)
    assert isinstance(j, lazy.LazyTSDF)
    m = lt.on_mesh(make_mesh({"series": 1}))
    assert isinstance(m, lazy.LazyDistributedTSDF)
    chain = m.asofJoin(rt.on_mesh(make_mesh({"series": 1})))
    assert isinstance(chain, lazy.LazyDistributedTSDF)
    ops = [n.op for n in chain.plan.walk() if not n.is_source()]
    assert ops == ["on_mesh", "on_mesh", "asof_join"]


def test_signature_is_structural_not_identity(plan_on):
    lt, rt = make_frames(seed=1)
    lt2, rt2 = make_frames(seed=2)
    a = lt.asofJoin(rt).plan
    b = lt2.asofJoin(rt2).plan
    assert ir.signature(a) == ir.signature(b)
    assert ir.state_key(a) == ir.state_key(b)    # same shapes+schema
    c = lt.asofJoin(rt, maxLookback=5).plan
    assert ir.signature(a) != ir.signature(c)


def test_non_recorded_op_materialises_and_delegates(plan_on):
    lt, rt = make_frames()
    desc = lt.asofJoin(rt).describe()            # describe is eager-only
    assert isinstance(desc, pd.DataFrame)


# ----------------------------------------------------------------------
# Bitwise planned == eager across the op-chain matrix
# ----------------------------------------------------------------------

def _mesh(): return make_mesh({"series": 1})


MESH_CHAINS = {
    "join_stats_ema": lambda dl, dr: dl.asofJoin(dr)
    .withRangeStats(colsToSummarize=["x"], rangeBackWindowSecs=WINDOW)
    .EMA("x", exact=True),
    "join_ema_stats": lambda dl, dr: dl.asofJoin(dr)
    .EMA("right_v0", exact=True)
    .withRangeStats(colsToSummarize=["right_v0"],
                    rangeBackWindowSecs=WINDOW),
    "join_all_stats": lambda dl, dr: dl.asofJoin(dr)
    .withRangeStats(rangeBackWindowSecs=WINDOW),
    "stats_only": lambda dl, dr: dl.withRangeStats(
        colsToSummarize=["x"], rangeBackWindowSecs=WINDOW),
    "join_resample": lambda dl, dr: dl.asofJoin(dr)
    .resample("1 minute", "mean", metricCols=["x"]),
    "resample_interp": lambda dl, dr: dl.resample(
        "1 minute", "mean", metricCols=["x"])
    .interpolate(method="linear"),
}


@pytest.mark.parametrize("chain", sorted(MESH_CHAINS))
@pytest.mark.parametrize("variant", ["plain", "nulls", "seq"])
def test_mesh_chain_bitwise_vs_eager(monkeypatch, chain, variant):
    if chain in ("join_resample", "resample_interp") and variant != "plain":
        pytest.skip("resample tails only need one data variant")
    lt, rt = make_frames(seed=7, nulls=(variant == "nulls"),
                         seq=(variant == "seq"))
    fn = MESH_CHAINS[chain]

    monkeypatch.delenv("TEMPO_TPU_PLAN", raising=False)
    eager = fn(lt.on_mesh(_mesh()), rt.on_mesh(_mesh())).collect().df
    monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
    plan_cache.CACHE.clear()
    planned = fn(lt.on_mesh(_mesh()), rt.on_mesh(_mesh())).collect().df
    pd.testing.assert_frame_equal(eager, planned, check_exact=True)


@pytest.mark.parametrize("skip_nulls,max_lookback",
                         [(True, 0), (True, 3), (False, 0), (False, 3)])
def test_join_flag_matrix_bitwise(monkeypatch, skip_nulls, max_lookback):
    lt, rt = make_frames(seed=11, nulls=True)

    def fn(dl, dr):
        return (dl.asofJoin(dr, skipNulls=skip_nulls,
                            maxLookback=max_lookback)
                .withRangeStats(colsToSummarize=["x"],
                                rangeBackWindowSecs=WINDOW)
                .EMA("x", exact=True))

    monkeypatch.delenv("TEMPO_TPU_PLAN", raising=False)
    eager = fn(lt.on_mesh(_mesh()), rt.on_mesh(_mesh())).collect().df
    monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
    plan_cache.CACHE.clear()
    planned = fn(lt.on_mesh(_mesh()), rt.on_mesh(_mesh())).collect().df
    pd.testing.assert_frame_equal(eager, planned, check_exact=True)


HOST_CHAINS = {
    "join_select": lambda lt, rt: lt.asofJoin(rt)
    .select(["event_ts", "sym", "x", "right_v0"]),
    # the skew ladder's bracketed rungs: the explicit tsPartitionVal
    # brackets, and the oversize auto-bracketing (lane limit below)
    "join_ts_partition_stats": lambda lt, rt: lt.asofJoin(
        rt, tsPartitionVal=12).withRangeStats(
        colsToSummarize=["x"], rangeBackWindowSecs=WINDOW),
    "join_auto_bracket_stats": lambda lt, rt: lt.asofJoin(rt)
    .withRangeStats(colsToSummarize=["x"], rangeBackWindowSecs=WINDOW),
    "stats_ema": lambda lt, rt: lt.withRangeStats(
        colsToSummarize=["x"], rangeBackWindowSecs=WINDOW)
    .EMA("x", exact=False),
    "resample_mean": lambda lt, rt: lt.resample(
        "1 minute", "mean", metricCols=["x"]),
    "resample_interp": lambda lt, rt: lt.resample(
        "1 minute", "mean", metricCols=["x"]).interpolate("linear"),
    "with_column": lambda lt, rt: lt.withColumn("x2", 2).EMA("x"),
}


@pytest.mark.parametrize("chain", sorted(HOST_CHAINS))
def test_host_chain_bitwise_vs_eager(monkeypatch, chain):
    lt, rt = make_frames(seed=3)
    if chain == "resample_interp":
        # the host interpolate service requires a datetime ts column
        dfs = []
        for t in (lt, rt):
            df = t.df.copy()
            df["event_ts"] = pd.to_datetime(df["event_ts"], unit="s")
            dfs.append(df)
        lt = TSDF(dfs[0], "event_ts", ["sym"])
        rt = TSDF(dfs[1], "event_ts", ["sym"])
    if chain == "join_auto_bracket_stats":
        # under the ~96 merged lanes a series: the join brackets
        monkeypatch.setenv("TEMPO_TPU_MAX_MERGED_LANES", "64")
    fn = HOST_CHAINS[chain]
    monkeypatch.delenv("TEMPO_TPU_PLAN", raising=False)
    eager = fn(lt, rt).df
    monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
    plan_cache.CACHE.clear()
    planned = fn(lt, rt).df
    pd.testing.assert_frame_equal(eager, planned, check_exact=True)


def test_packed_mesh_stats_matches_per_column(plan_off):
    """The multi-column payload packing (ISSUE 6): one packed
    withRangeStats program over every summarized column must produce
    per-column values bitwise-equal to C single-column programs —
    the invariant that lets the planner's fused program and the eager
    chain share the packed block fn without breaking the
    planned==eager contract."""
    lt, rt = make_frames(seed=17, nulls=True)
    dl = lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
    multi = dl.withRangeStats(rangeBackWindowSecs=WINDOW).collect().df
    cols = [c for c in ("x", "right_v0", "right_v1")
            if any(col.startswith(f"mean_{c}") for col in multi.columns)]
    assert len(cols) >= 2, multi.columns
    for c in cols:
        single = dl.withRangeStats(
            colsToSummarize=[c], rangeBackWindowSecs=WINDOW,
        ).collect().df
        stat_cols = [col for col in single.columns
                     if col.endswith(f"_{c}")
                     and col.split("_")[0] in packing.RANGE_STATS]
        assert stat_cols
        pd.testing.assert_frame_equal(
            multi[stat_cols], single[stat_cols], check_exact=True)


def test_randomized_chain_matrix_bitwise(monkeypatch):
    """Randomized composition: draw op sequences over the mesh and
    check each against eager, bit for bit."""
    rng = np.random.default_rng(99)
    step_pool = [
        lambda d: d.withRangeStats(colsToSummarize=["x"],
                                   rangeBackWindowSecs=WINDOW),
        lambda d: d.EMA("x", exact=True),
        lambda d: d.EMA("x", exact=False),
    ]
    for trial in range(4):
        lt, rt = make_frames(seed=100 + trial, nulls=bool(trial % 2),
                             seq=(trial == 3))
        steps = [step_pool[i] for i in
                 rng.choice(len(step_pool), size=2, replace=False)]
        join_first = bool(trial % 2)

        def fn(dl, dr):
            out = dl.asofJoin(dr) if join_first else dl
            for s in steps:
                out = s(out)
            return out

        monkeypatch.delenv("TEMPO_TPU_PLAN", raising=False)
        eager = fn(lt.on_mesh(_mesh()), rt.on_mesh(_mesh())).collect().df
        monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
        plan_cache.CACHE.clear()
        planned = fn(lt.on_mesh(_mesh()),
                     rt.on_mesh(_mesh())).collect().df
        pd.testing.assert_frame_equal(eager, planned, check_exact=True)


# ----------------------------------------------------------------------
# Optimizer rewrites
# ----------------------------------------------------------------------

def test_fused_mesh_chain_rewrite_fires(plan_on):
    lt, rt = make_frames()
    lz = (lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
          .withRangeStats(colsToSummarize=["x"], rangeBackWindowSecs=WINDOW)
          .EMA("x", exact=True))
    opt = optimizer.optimize(lz.plan)
    ops = [n.op for n in opt.walk() if not n.is_source()]
    assert "fused_asof_stats_ema" in ops
    assert "asof_join" not in ops and "range_stats" not in ops \
        and "ema" not in ops
    fused = [n for n in opt.walk() if n.op == "fused_asof_stats_ema"][0]
    assert fused.param("has_ema") is True
    assert fused.param("e_col") == "x"


def test_fused_rewrite_guards(plan_on):
    lt, rt = make_frames(seq=True)   # sequence col blocks the fusion
    lz = (lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
          .withRangeStats(colsToSummarize=["x"],
                          rangeBackWindowSecs=WINDOW))
    ops = [n.op for n in optimizer.optimize(lz.plan).walk()]
    assert "fused_asof_stats_ema" not in ops
    lt2, rt2 = make_frames()
    lz2 = (lt2.on_mesh(_mesh())
           .asofJoin(rt2.on_mesh(_mesh()), maxLookback=2)
           .withRangeStats(colsToSummarize=["x"],
                           rangeBackWindowSecs=WINDOW))
    ops2 = [n.op for n in optimizer.optimize(lz2.plan).walk()]
    assert "fused_asof_stats_ema" not in ops2


def test_resample_ema_fusion_matches_fused_entry_point(monkeypatch):
    lt, _ = make_frames(seed=5)
    monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
    plan_cache.CACHE.clear()
    lz = lt.resample("1 minute", "floor", metricCols=["x"]).EMA(
        "x", exact=True)
    opt = optimizer.optimize(lz.plan)
    assert [n.op for n in opt.walk() if not n.is_source()] \
        == ["resample_ema"]
    planned = lz.df
    monkeypatch.delenv("TEMPO_TPU_PLAN", raising=False)
    fused_ref = lt.resampleEMA("1 minute", "x").df
    # the rewrite IS the fused entry point — bit-identical to it
    pd.testing.assert_frame_equal(planned, fused_ref, check_exact=True)
    # ... and numerically equivalent to the unfused chain (float
    # rounding differs: the fused kernel reads the column once)
    chained = lt.resample("1 minute", "floor", metricCols=["x"]).EMA(
        "x", exact=True).df
    np.testing.assert_allclose(planned["EMA_x"], chained["EMA_x"],
                               rtol=1e-5, atol=1e-7)


def test_resample_ema_fusion_guards(plan_on):
    lt, _ = make_frames()
    # exact=False is a different operator (truncated-lag EMA) — no fuse
    lz = lt.resample("1 minute", "floor", metricCols=["x"]).EMA("x")
    ops = [n.op for n in optimizer.optimize(lz.plan).walk()]
    assert "resample_ema" not in ops
    # mean resample is not the floor sample — no fuse
    lz2 = lt.resample("1 minute", "mean", metricCols=["x"]).EMA(
        "x", exact=True)
    ops2 = [n.op for n in optimizer.optimize(lz2.plan).walk()]
    assert "resample_ema" not in ops2


def test_prune_columns_before_packing(plan_on):
    lt, rt = make_frames()
    lz = lt.asofJoin(rt).select(["event_ts", "sym", "right_v0"])
    opt = optimizer.optimize(lz.plan)
    pruned = {n.payload.df.columns[-1]: n.ann.get("pruned")
              for n in opt.walk() if n.op == "source"}
    assert ("x",) in pruned.values()       # left value col never packs
    assert ("v1",) in pruned.values()      # unused right col never packs


def test_count_terminal_prunes_all_value_columns(plan_on):
    lt, rt = make_frames()
    lz = lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
    node = ir.Node("count", inputs=(lz.plan,))
    opt = optimizer.optimize(node)
    for n in opt.walk():
        if n.op == "source":
            assert set(n.ann.get("pruned", ())) >= {"x"} or \
                set(n.ann.get("pruned", ())) >= {"v0", "v1"}
    assert lz.count() == K * L


def test_engine_hoist_annotations(plan_on):
    lt, rt = make_frames()
    lz = (lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
          .withRangeStats(colsToSummarize=["x"],
                          rangeBackWindowSecs=WINDOW))
    opt = optimizer.optimize(lz.plan)
    fused = [n for n in opt.walk() if n.op == "fused_asof_stats_ema"]
    assert fused and fused[0].ann["join_engine"] in (
        "single", "chunked", "bracket")
    assert fused[0].ann["range_engine"] in ("shifted", "stream",
                                            "windowed")
    assert fused[0].ann["merged_lanes_est"] > 0


def test_barrier_marking(plan_on):
    lt, _ = make_frames()
    lz = (lt.on_mesh(_mesh())
          .resample("1 minute", "mean", metricCols=["x"])
          .fourier_transform(1.0, "x"))
    opt = optimizer.optimize(ir.Node("collect", inputs=(lz.plan,)))
    barriers = {n.op: n.ann.get("barrier") for n in opt.walk()
                if "barrier" in n.ann}
    assert "collect" in barriers
    assert "fourier" in barriers            # resampled -> host fallback
    lz2 = lt.on_mesh(_mesh()).withLookbackFeatures(["x"], 4)
    opt2 = optimizer.optimize(lz2.plan)
    assert any("barrier" in n.ann for n in opt2.walk()
               if n.op == "lookback_features")


def test_range_engine_hint_wins(plan_on):
    from tempo_tpu.ops import rolling as rk

    # a hint the data still admits (bounds past every unrolled form)
    # is replayed without a re-pick
    with plan_hints.installed({"range_engine": "windowed"}):
        assert rk.pick_range_engine(10**9, 10**6, 10**6) == "windowed"
    with plan_hints.installed({"join_engine": "chunked"}):
        assert profiling.pick_join_engine(10, 10**9, True) == "chunked"
        # ... but a hint the fresh probes no longer admit is dropped:
        assert profiling.pick_join_engine(10, 10**9, False) == "single"
    with plan_hints.installed({"join_engine": "single"}):
        # a cached 'single' plan must not replay past the ceiling
        assert profiling.pick_join_engine(10**6, 10**3, True) == "chunked"


def test_range_engine_hint_revalidated_against_data(plan_on):
    """The three stats engines differ in FMA/rounding order, so a
    cached plan replayed over different data (same shapes, different
    row bounds) must re-pick exactly as eager would — a stale hint
    forcing a different kernel would break planned==eager
    bit-identity (MIGRATION.md v0.7)."""
    from tempo_tpu.ops import rolling as rk

    # current bounds admit the shifted form: a stale 'windowed' or
    # 'stream' hint falls through to the eager pick
    with plan_hints.installed({"range_engine": "windowed"}):
        assert rk.pick_range_engine(1024, 1, 1, True, True) == "shifted"
    with plan_hints.installed({"range_engine": "stream"}):
        assert rk.pick_range_engine(1024, 1, 1, True, True) == "shifted"
    # a 'shifted' hint past the current budget re-picks too
    with plan_hints.installed({"range_engine": "shifted"}):
        assert rk.pick_range_engine(
            10**9, 10**6, 10**6, False, False) == "windowed"


# ----------------------------------------------------------------------
# Executable cache
# ----------------------------------------------------------------------

def _run_chain(lt, rt):
    return (lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
            .withRangeStats(colsToSummarize=["x"],
                            rangeBackWindowSecs=WINDOW)
            .EMA("x", exact=True).collect().df)


def test_cache_hit_on_repeat_and_miss_on_shape_change(plan_on):
    lt, rt = make_frames(seed=21)
    _run_chain(lt, rt)
    st = plan_cache.CACHE.stats()
    assert (st["misses"], st["hits"], st["builds"]) == (1, 0, 1)
    _run_chain(lt, rt)
    st = plan_cache.CACHE.stats()
    assert (st["misses"], st["hits"], st["builds"]) == (1, 1, 1)
    # same schema, same chain, DIFFERENT rows -> shape change -> miss
    lt2, rt2 = make_frames(seed=22, rows=L + 8)
    _run_chain(lt2, rt2)
    st = plan_cache.CACHE.stats()
    assert (st["misses"], st["builds"]) == (2, 2)


def test_cache_serves_new_same_shape_frames(plan_on, monkeypatch):
    """The serving pattern: fresh frames, same schema+shapes — the
    cached executable runs them without re-planning, and the results
    are exactly the per-frame eager results."""
    lt, rt = make_frames(seed=31)
    _run_chain(lt, rt)
    lt2, rt2 = make_frames(seed=32)       # different data, same shapes
    planned = _run_chain(lt2, rt2)
    assert plan_cache.CACHE.stats()["hits"] == 1
    monkeypatch.delenv("TEMPO_TPU_PLAN")
    eager = _run_chain(lt2, rt2)
    monkeypatch.setenv("TEMPO_TPU_PLAN", "1")
    pd.testing.assert_frame_equal(planned, eager, check_exact=True)


def test_cached_executable_drops_source_payloads(plan_on):
    """run() binds the caller's frames positionally, so the cached
    optimized plan must not pin the build-time frames — up to
    max_size() full DataFrames/device buffers would otherwise live
    until eviction."""
    lt, rt = make_frames(seed=51)
    lt.asofJoin(rt).df
    (exe,) = plan_cache.CACHE._entries.values()
    assert all(s.payload is None for s in exe.plan.sources())


def test_numpy_scalar_params_stay_cacheable(plan_on):
    """np.int64 window widths out of pandas/numpy arithmetic are
    routine; they must canonicalise like their Python spellings, not
    poison the plan as uncacheable (which would re-trace per call)."""
    assert ir.canon(np.int64(7)) == 7
    assert ir.canon(np.float64(0.5)) == 0.5
    assert ir.canon(np.bool_(True)) is True
    assert not ir.is_opaque(ir.canon((np.int32(3), "x")))
    lt, _ = make_frames(seed=61)
    lt.withRangeStats(colsToSummarize=["x"],
                      rangeBackWindowSecs=WINDOW).df
    lt.withRangeStats(colsToSummarize=["x"],
                      rangeBackWindowSecs=np.int64(WINDOW)).df
    st = plan_cache.CACHE.stats()
    assert st["uncacheable"] == 0
    assert (st["hits"], st["builds"]) == (1, 1)


def test_cache_lru_eviction(plan_on, monkeypatch):
    monkeypatch.setenv("TEMPO_TPU_PLAN_CACHE_SIZE", "2")
    lt, rt = make_frames(seed=41)
    _run_chain(lt, rt)                                     # entry A
    lt.asofJoin(rt).df                                     # entry B
    lt.withRangeStats(colsToSummarize=["x"]).df            # entry C -> A out
    st = plan_cache.CACHE.stats()
    assert st["size"] == 2 and st["evictions"] == 1
    _run_chain(lt, rt)                                     # A again: miss
    assert plan_cache.CACHE.stats()["misses"] == 4


def test_second_run_is_compile_free(plan_on):
    """Repeat invocation with identical shapes performs zero new XLA
    compiles: the plan cache returns the executable, and every program
    builder underneath hits its shape-keyed cache."""
    lt, rt = make_frames(seed=51, rows=L + 16)   # unique shape

    compiles = []

    class Trap(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "Compiling" in msg:
                compiles.append(msg)

    trap = Trap()
    names = ("jax._src.dispatch", "jax._src.interpreters.pxla",
             "jax._src.pjit", "jax._src.compiler")
    loggers = [logging.getLogger(n) for n in names]
    jax.config.update("jax_log_compiles", True)
    for lg in loggers:
        lg.addHandler(trap)
    try:
        _run_chain(lt, rt)
        first = len(compiles)
        compiles.clear()
        _run_chain(lt, rt)
        second = len(compiles)
    finally:
        jax.config.update("jax_log_compiles", False)
        for lg in loggers:
            lg.removeHandler(trap)
    if first == 0:
        pytest.skip("jax_log_compiles emitted nothing in this "
                    "environment — compile counting unavailable")
    assert second == 0, f"second run recompiled: {compiles}"
    assert plan_cache.CACHE.stats()["hits"] == 1


def test_uncacheable_plan_still_runs(plan_on):
    lt, _ = make_frames(seed=61)
    planned = lt.withColumn("y", lambda df: df.x * 2).EMA("y").df
    st = plan_cache.CACHE.stats()
    assert st["uncacheable"] >= 1
    assert "y" in planned.columns and "EMA_y" in planned.columns


def test_plan_cache_stats_via_profiling(plan_on):
    st = profiling.plan_cache_stats()
    assert set(st) >= {"size", "max_size", "hits", "misses",
                      "evictions", "builds"}


# ----------------------------------------------------------------------
# explain()
# ----------------------------------------------------------------------

def test_explain_sections_and_engines(plan_on, capsys):
    lt, rt = make_frames()
    lz = (lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
          .withRangeStats(colsToSummarize=["x"],
                          rangeBackWindowSecs=WINDOW)
          .EMA("x", exact=True))
    text = lz.explain()
    assert "== Logical plan ==" in text
    assert "== Optimized plan ==" in text
    assert "fused_asof_stats_ema" in text
    assert "engine[join]=" in text and "engine[stats]=" in text
    assert "barriers:" in text
    assert text in capsys.readouterr().out


def test_explain_cost_reports_xla_numbers(plan_on):
    lt, rt = make_frames()
    lz = (lt.on_mesh(_mesh()).asofJoin(rt.on_mesh(_mesh()))
          .withRangeStats(colsToSummarize=["x"],
                          rangeBackWindowSecs=WINDOW))
    text = lz.explain(cost=True)
    assert "== Compiled cost (XLA) ==" in text
    assert "fused_asof_stats_ema:" in text
    assert "host_bytes=" in text


def test_eager_frame_explain_is_bare_source(plan_off):
    lt, _ = make_frames()
    text = lt.explain()
    assert "source[host]" in text


def test_eager_mesh_barrier_ops_warn(plan_off, caplog):
    """The dist.py host-fallback ops announce the silent collect (the
    same style as the selectExpr engine-fallback logging): the eager
    user learns the chain left the device."""
    lt, _ = make_frames()
    dl = lt.on_mesh(_mesh())
    with caplog.at_level(logging.WARNING, logger="tempo_tpu.dist"):
        dl.withLookbackFeatures(["x"], 4)
    assert any("materialization barrier" in r.message
               for r in caplog.records)
    caplog.clear()
    resampled = dl.resample("1 minute", "mean", metricCols=["x"])
    with caplog.at_level(logging.WARNING, logger="tempo_tpu.dist"):
        resampled.fourier_transform(1.0, "x")
    assert any("materialization barrier" in r.message
               for r in caplog.records)
