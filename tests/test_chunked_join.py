"""Lane-chunked streaming AS-OF merge: chunked vs single-plan vs
host-bracket oracle across the full flag matrix.

The chunked engine (ops/pallas_merge.py:asof_merge_indices_chunked) must
be bit-identical to the XLA sort-and-scan oracle — and therefore to the
single-plan kernel and the host time-bracketing path, which are pinned
against the same oracle — for every flag combination, with chunk
boundaries forced INSIDE the data (small TEMPO_TPU_JOIN_CHUNK_LANES /
``chunk_lanes``), so every cross-chunk mechanism is exercised: the
carried forward-fill state, the carried series id, the maxLookback
horizon in global merged positions, and seq ties straddling a boundary.

The fuzz matrix covers all 16 (seq x skipNulls x binpack x maxLookback)
combinations with its own seed each, tallied per combination (VERDICT
r5 "Next round" #7).
"""

import logging

import numpy as np
import jax.numpy as jnp
import pandas as pd
import pytest

from tempo_tpu import profiling
from tempo_tpu.ops import pallas_merge as pm
from tempo_tpu.ops import sortmerge as sm
from tempo_tpu.packing import TS_PAD

from tests.test_pallas_merge import _binpacked_case, _rand_case

CHUNK = 256  # merged lanes per chunk; S = 128 real rows -> boundaries
             # land inside every case below


def _chunked_indices(l_ts, r_ts, r_valids, **kw):
    """The chunked engine's ``(last_idx [K, Ll], per_col_idx [C, K,
    Ll])`` int32, -1 for none and at pad lanes — the form of
    ``asof_merge_indices_pallas``: the last-row channel and the
    per-column channels, each taken to the real left lanes."""
    K, Ll = l_ts.shape
    real = np.flatnonzero(l_ts.ravel() < TS_PAD)

    def lanes(skip):
        take, planes = pm.asof_merge_indices_chunked(
            l_ts, r_ts, r_valids, real, skip_nulls=skip, **kw)
        out = np.full((len(planes), K * Ll), -1, np.int32)
        for o, p in zip(out, planes):
            got = np.take(p, take)
            o[real] = np.where(np.isnan(got), -1, got)
        return out.reshape(-1, K, Ll)

    return lanes(False)[0], lanes(True)


def _chunked_values(l_ts, r_ts, r_valids, r_values, **kw):
    """``(vals, found, idx)`` as the values engines return them: the
    chunked positions with each column's right value gathered at its
    last valid row on the host, NaN where there is none."""
    last, per_col = _chunked_indices(l_ts, r_ts, r_valids, **kw)
    rows = np.arange(l_ts.shape[0])[:, None]
    vals = np.stack([np.where(p >= 0, np.asarray(v, np.float32)[rows, p],
                              np.float32(np.nan))
                     for p, v in zip(per_col, r_values)])
    return vals, ~np.isnan(vals), last


def _check_real(got, want, l_ts, label, idx_too=True):
    real = l_ts < TS_PAD
    np.testing.assert_array_equal(
        np.asarray(got[1])[:, real], np.asarray(want[1])[:, real],
        err_msg=f"{label} found")
    np.testing.assert_allclose(
        np.asarray(got[0])[:, real], np.asarray(want[0])[:, real],
        equal_nan=True, err_msg=f"{label} vals")
    if idx_too:
        np.testing.assert_array_equal(
            np.asarray(got[2])[real], np.asarray(want[2])[real],
            err_msg=f"{label} idx")


# ----------------------------------------------------------------------
# Targeted cross-chunk properties
# ----------------------------------------------------------------------

def test_nan_run_longer_than_a_chunk_carries_across():
    """A null run wider than a whole chunk: the carried per-column fill
    state must bridge several all-null chunks exactly."""
    rng = np.random.default_rng(0)
    K, L = 2, 640          # 5 chunks of 128 merged rows per side pair
    l_ts = np.sort(rng.integers(0, 4 * L, (K, L))).astype(np.int64) * 10**9
    r_ts = np.sort(rng.integers(0, 4 * L, (K, L))).astype(np.int64) * 10**9
    r_values = rng.standard_normal((2, K, L)).astype(np.float32)
    r_valids = np.ones((2, K, L), bool)
    r_valids[0, :, 8:520] = False          # ~4 chunks of nulls
    r_valids[1, 0, :] = False              # a never-valid column/series
    want = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values))
    got = _chunked_values(
        l_ts, r_ts, r_valids, r_values, chunk_lanes=CHUNK, interpret=True)
    _check_real(got, want, l_ts, "nan-run")


@pytest.mark.parametrize(
    "ml",
    [1, 127, 129, 1000]
    + [pytest.param(v, marks=pytest.mark.slow) for v in (100, 128, 250)],
)
def test_lookback_straddles_chunk_boundaries(ml):
    """maxLookback horizons below, at, and across the 128-row chunk
    step: the carried source positions must measure the merged-stream
    distance exactly across boundaries."""
    rng = np.random.default_rng(ml)
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, 3, 384, 384, 2,
                                                tie_heavy=True)
    want = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), max_lookback=ml)
    got = _chunked_values(
        l_ts, r_ts, r_valids, r_values, max_lookback=ml,
        chunk_lanes=CHUNK, interpret=True)
    _check_real(got, want, l_ts, f"ml={ml}")


def test_seq_ties_at_chunk_edges():
    """One long equal-ts run spanning several chunks, ordered only by
    (seq, side): the straddling tie must resolve identically to the
    single-stream oracle (rights before lefts, later seq wins)."""
    K, L = 1, 512
    T = 10**9
    l_ts = np.full((K, L), 5 * T, np.int64)
    r_ts = np.full((K, L), 5 * T, np.int64)
    rng = np.random.default_rng(3)
    r_seq = np.sort(rng.integers(-4, 5, (K, L)).astype(np.float64), -1)
    r_seq[0, :40] = -np.inf                 # null seqs sort first
    l_seq = None
    r_values = rng.standard_normal((1, K, L)).astype(np.float32)
    r_valids = rng.random((1, K, L)) > 0.3
    want = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), r_seq=jnp.asarray(r_seq))
    got = _chunked_values(
        l_ts, r_ts, r_valids, r_values, l_seq=l_seq, r_seq=r_seq,
        chunk_lanes=CHUNK, interpret=True)
    _check_real(got, want, l_ts, "seq-ties")


def test_binpacked_series_straddling_chunks():
    """Bin-packed lane rows cut by chunk boundaries: the carried series
    id must fence the carry at every straddle."""
    case = _binpacked_case(seed=13, S=23, Lmax=80)
    (l_ts, r_ts, r_valids, r_values, llen, rlen, bp,
     lt2, rt2, lsid, rsid, rv2, rm2) = case
    C, S, _ = r_values.shape
    want_v, want_f, _ = (np.asarray(a) for a in sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values)))
    got = _chunked_values(lt2, rt2, rm2, rv2, l_sid=lsid, r_sid=rsid,
                          chunk_lanes=CHUNK, interpret=True)
    gv, gf = np.asarray(got[0]), np.asarray(got[1])
    for s in range(S):
        r0, o0 = bp.row[s], bp.l_off[s]
        sl = slice(o0, o0 + llen[s])
        np.testing.assert_array_equal(
            gf[:, r0, sl], want_f[:, s, :llen[s]], err_msg=f"s={s}")
        np.testing.assert_allclose(
            gv[:, r0, sl], want_v[:, s, :llen[s]], equal_nan=True,
            err_msg=f"s={s}")


def test_chunked_equals_single_plan_and_bitonic_bitwise():
    """The three engines run the same network: real-lane positions are
    bit-identical (fills select values, they never compute)."""
    rng = np.random.default_rng(21)
    l_ts, r_ts, r_valids, _ = _rand_case(rng, 4, 256, 256, 2,
                                         tie_heavy=True)
    dev = [jnp.asarray(a) for a in (l_ts, r_ts, r_valids)]
    a = pm.asof_merge_indices_pallas(*dev, interpret=True)
    b = _chunked_indices(l_ts, r_ts, r_valids, chunk_lanes=CHUNK,
                         interpret=True)
    c = pm.asof_merge_indices_bitonic(*dev)
    real = l_ts < TS_PAD
    for other, label in ((b, "chunked"), (c, "bitonic")):
        np.testing.assert_array_equal(
            np.asarray(a[0])[real], np.asarray(other[0])[real],
            err_msg=f"{label} last-row index not bitwise-identical")
        np.testing.assert_array_equal(
            np.asarray(a[1])[:, real], np.asarray(other[1])[:, real],
            err_msg=f"{label} per-column index not bitwise-identical")


def test_chunked_rejects_tracers():
    l_ts, r_ts, r_valids, _ = _rand_case(
        np.random.default_rng(0), 2, 128, 128, 1)
    real = np.flatnonzero(l_ts.ravel() < TS_PAD)

    def f(a, b, c):
        return pm.asof_merge_indices_chunked(a, b, c, real)[0]

    import jax

    with pytest.raises(TypeError, match="bitonic"):
        jax.jit(f)(jnp.asarray(l_ts), jnp.asarray(r_ts),
                   jnp.asarray(r_valids))


# ----------------------------------------------------------------------
# Engine picker + knobs
# ----------------------------------------------------------------------

def test_pick_join_engine(monkeypatch):
    monkeypatch.delenv("TEMPO_TPU_JOIN_ENGINE", raising=False)
    assert profiling.pick_join_engine(100, 1000, True) == "single"
    assert profiling.pick_join_engine(2000, 1000, True) == "chunked"
    assert profiling.pick_join_engine(2000, 1000, False) == "bracket"
    assert profiling.pick_join_engine(2000, 0, False) == "single"
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "bracket")
    assert profiling.pick_join_engine(100, 1000, True) == "bracket"
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "chunked")
    assert profiling.pick_join_engine(100, 1000, False) == "chunked"
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "vmem")
    assert profiling.pick_join_engine(9**9, 10, True) == "single"
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "bitonic")
    assert profiling.pick_join_engine(9**9, 10, True) == "single"
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "nonsense")
    assert profiling.pick_join_engine(2000, 1000, True) == "chunked"


def test_chunk_lanes_knob_validation(monkeypatch):
    with pytest.raises(ValueError, match="power of two"):
        pm._plan_chunk_lanes(4, 4, override=300)
    with pytest.raises(ValueError, match="power of two"):
        pm._plan_chunk_lanes(4, 4, override=128)
    assert pm._plan_chunk_lanes(4, 4, override=512) == 512
    # auto plan shrinks as the plane count grows, never below 256
    small = pm._plan_chunk_lanes(40, 6)
    big = pm._plan_chunk_lanes(3, 4)
    assert small is not None and big is not None and small <= big
    monkeypatch.setenv("TEMPO_TPU_JOIN_CHUNK_LANES", "1024")
    assert pm.join_chunk_lanes_override() == 1024


def test_chunked_available_gates(monkeypatch):
    # CPU backend: unavailable unless the pallas kill-switch says TPU
    assert not pm.chunked_join_available(10_000, 2)
    monkeypatch.setattr(pm, "_pallas_enabled", lambda: True)
    assert pm.chunked_join_available(10_000, 2)
    # f32 position exactness bound
    assert not pm.chunked_join_available(1 << 24, 2)
    # unmappable f64 seq
    bad = jnp.asarray(np.array([[0.1 + 2.0**40]]))
    assert not pm.chunked_join_available(10_000, 2, r_seq=bad)
    ok = jnp.asarray(np.array([[1.0, 2.0, -np.inf]]))
    assert pm.chunked_join_available(10_000, 2, r_seq=ok)


def test_chunked_enforces_f32_position_bound():
    """A forced TEMPO_TPU_JOIN_ENGINE=chunked must not silently round
    f32 positions past 2^24 merged rows — the wrapper itself raises,
    not just the availability gate."""
    l_ts = np.full((1, (1 << 23) + 64), TS_PAD, np.int64)
    r_ts = np.full((1, (1 << 23) + 64), TS_PAD, np.int64)
    with pytest.raises(ValueError, match="2\\^24"):
        pm.build_chunked_planes(
            l_ts, r_ts, np.zeros((0, 1, l_ts.shape[1]), bool),
            np.zeros((0, 1, l_ts.shape[1]), np.float32))


def test_forced_bitonic_wins_over_single_plan(monkeypatch):
    """TEMPO_TPU_JOIN_ENGINE=bitonic must measure the engine it names
    even where the single-plan Pallas kernel is supported (forced-open
    backend gate)."""
    monkeypatch.setattr(pm, "_pallas_enabled", lambda: True)
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "bitonic")
    calls = []
    real = pm.asof_merge_values_bitonic

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(pm, "asof_merge_values_bitonic", spy)
    rng = np.random.default_rng(4)
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, 2, 128, 128, 1)
    assert pm.merge_join_supported(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_values),
        None, None, True)
    want = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values))
    got = sm.asof_merge_values(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values))
    assert calls, "forced bitonic ran the single-plan kernel instead"
    _check_real(got, want, l_ts, "forced-bitonic")


def test_oversize_dispatch_routes_to_bitonic(monkeypatch):
    """Inside jit (the dist/halo shard kernels), oversize widths route
    to the bitonic network instead of the lax.sort ladder — pinned by
    forcing the ceiling under the test shape and comparing outputs."""
    rng = np.random.default_rng(9)
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, 3, 256, 256, 2)
    want = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values))
    monkeypatch.setenv("TEMPO_TPU_MAX_MERGED_LANES", "256")
    assert sm._oversize_bitonic(jnp.asarray(l_ts), jnp.asarray(r_ts),
                                jnp.asarray(r_values), None, None)
    got = sm.asof_merge_values(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values))
    _check_real(got, want, l_ts, "oversize-bitonic")
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "single")
    assert not sm._oversize_bitonic(jnp.asarray(l_ts), jnp.asarray(r_ts),
                                    jnp.asarray(r_values), None, None)


# ----------------------------------------------------------------------
# Frame-level fuzz matrix: 16 combinations x 1 seed each, plus the
# host-bracket oracle, with per-combination counts
# ----------------------------------------------------------------------

_MATRIX = [
    (seq, skip, binpack, ml)
    for seq in (False, True)
    for skip in (True, False)
    for binpack in (False, True)
    for ml in (0, 5)
]
# tier-1 runs a pairwise-covering half-fraction (every flag pair
# appears); the other half rides the full (slow-inclusive) suite
_FAST = {
    (False, True, False, 0), (False, True, True, 5),
    (False, False, False, 5), (False, False, True, 0),
    (True, True, False, 5), (True, True, True, 0),
    (True, False, False, 0), (True, False, True, 5),
}
_MATRIX_PARAMS = [
    (c if c in _FAST else pytest.param(*c, marks=pytest.mark.slow))
    for c in _MATRIX
]
_matrix_runs = {}


def _matrix_frames(seed, with_seq):
    rng = np.random.default_rng(seed)
    n = m = 150
    syms = [f"s{i}" for i in range(8)]
    p = 1.0 / np.arange(1, 9) ** 1.1
    p /= p.sum()
    lt = pd.DataFrame({
        "sym": rng.choice(syms, n, p=p),
        "event_ts": pd.to_datetime(
            rng.integers(0, 120, n).astype("int64") * 10**9),
        "x": rng.standard_normal(n),
    })
    rt = pd.DataFrame({
        "sym": rng.choice(syms, m, p=p),
        "event_ts": pd.to_datetime(
            rng.integers(0, 120, m).astype("int64") * 10**9),
        "v": np.where(rng.random(m) > 0.3, rng.standard_normal(m),
                      np.nan),
    })
    if with_seq:
        seqv = rng.integers(0, 4, m).astype(float)
        seqv[rng.random(m) < 0.25] = np.nan
        rt["seq"] = seqv
    from tempo_tpu import TSDF

    L = TSDF(lt, "event_ts", ["sym"])
    R = (TSDF(rt, "event_ts", ["sym"], sequence_col="seq") if with_seq
         else TSDF(rt, "event_ts", ["sym"]))
    return L, R


@pytest.mark.parametrize("seq,skip,binpack,ml", _MATRIX_PARAMS)
def test_flag_matrix_chunked_vs_default_vs_bracket(
        monkeypatch, seq, skip, binpack, ml):
    seed = 1000 + 17 * len(_matrix_runs)
    L, R = _matrix_frames(seed, seq)
    kwargs = dict(skipNulls=skip, maxLookback=ml)
    monkeypatch.delenv("TEMPO_TPU_JOIN_ENGINE", raising=False)
    monkeypatch.setenv("TEMPO_TPU_BINPACK", "1" if binpack else "0")
    want = L.asofJoin(R, **kwargs).df
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "chunked")
    monkeypatch.setenv("TEMPO_TPU_JOIN_CHUNK_LANES", str(CHUNK))
    got = L.asofJoin(R, **kwargs).df
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    if ml == 0 and skip and not binpack:
        # the host-bracket oracle (exact cross-bracket carries) — the
        # engine the chunked kernel replaces — on a representative
        # slice of the matrix (its full-matrix parity is pinned in
        # test_join_degrade); maxLookback cannot ride brackets, hence
        # the unbracketed oracle above covers it
        monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "bracket")
        monkeypatch.setenv("TEMPO_TPU_MAX_MERGED_LANES", "64")
        bracket = L.asofJoin(R, **kwargs).df
        pd.testing.assert_frame_equal(bracket, want, check_exact=True)
    _matrix_runs[(seq, skip, binpack, ml)] = \
        _matrix_runs.get((seq, skip, binpack, ml), 0) + 1


def test_flag_matrix_per_combination_counts():
    """Per-combination tally of the (seq x skipNulls x binpack x
    maxLookback) matrix (VERDICT r5 #7): the tier-1 half-fraction must
    all have run (covering every flag pair), and a slow-inclusive run
    covers all 16 combinations, each with its own seed."""
    missing_fast = [c for c in _FAST if _matrix_runs.get(c, 0) < 1]
    assert not missing_fast, \
        f"fast-tier matrix combinations never exercised: {missing_fast}"
    if len(_matrix_runs) > len(_FAST):       # slow-inclusive run
        missing = [c for c in _MATRIX if _matrix_runs.get(c, 0) < 1]
        assert not missing, \
            f"matrix combinations never exercised: {missing}"
    for dim in range(4):
        seen = {c[dim] for c in _matrix_runs}
        assert len(seen) == 2, f"flag dimension {dim} single-valued"
    logging.getLogger(__name__).info(
        "chunked fuzz matrix counts: %s",
        {str(k): v for k, v in sorted(_matrix_runs.items())})


# ----------------------------------------------------------------------
# Unpacking: one flat take per channel the join reads
# ----------------------------------------------------------------------

def _take_frames(seed):
    """Ragged series: 'e' only on the right (an empty left series), 'f'
    only on the left (no right rows), lengths spanning several 128-row
    chunks, rows given out of order, and a right column that is never
    valid (an all-NaN channel)."""
    rng = np.random.default_rng(seed)

    def side(counts):
        syms = np.repeat(list(counts), list(counts.values()))
        n = len(syms)
        return pd.DataFrame({
            "sym": syms,
            "event_ts": pd.to_datetime(
                rng.integers(0, 400, n).astype("int64") * 10**9),
        }).sample(frac=1, random_state=seed).reset_index(drop=True)

    lt = side({"a": 300, "b": 37, "c": 410, "f": 25})
    lt["x"] = rng.standard_normal(len(lt))
    rt = side({"a": 200, "b": 90, "c": 333, "e": 40})
    rt["v"] = np.where(rng.random(len(rt)) > 0.3,
                       rng.standard_normal(len(rt)), np.nan)
    rt["never"] = np.nan
    rt["w"] = rng.standard_normal(len(rt))
    from tempo_tpu import TSDF

    return TSDF(lt, "event_ts", ["sym"]), TSDF(rt, "event_ts", ["sym"])


def _force_chunked(monkeypatch, binpack):
    monkeypatch.setenv("TEMPO_TPU_BINPACK", "1" if binpack else "0")
    monkeypatch.setenv("TEMPO_TPU_JOIN_ENGINE", "chunked")
    monkeypatch.setenv("TEMPO_TPU_JOIN_CHUNK_LANES", str(CHUNK))


def _plane_chain(out, l_out, l_layout, r_layout, bp, skip):
    """The former unpack, kept as the oracle: every channel gathered into
    a [K, Ll] plane through the lexsort oracle's destinations ``l_out``,
    -1 for no match, then read at each left row's (lane row, lane) plus
    its series' right start."""
    rows = np.arange(l_out.shape[0])[:, None]
    coded = [np.where((l_out >= 0) & ~np.isnan(g), g, -1).astype(np.int32)
             for g in (np.asarray(o)[rows, l_out] for o in out)]
    C = len(coded) - 1
    k = l_layout.key_ids
    pos = np.arange(l_layout.n_rows) - l_layout.starts[k]
    res = []
    for p in (coded[:C] if skip else coded[C:]):
        if bp is None:
            ridx = p[k, pos]
            ok = ridx >= 0
            flat = r_layout.starts[k] + np.where(ok, ridx, 0)
        else:
            ridx = p[bp.row[k], bp.l_off[k] + pos]
            ok = ridx >= 0
            flat = r_layout.starts[k] + np.where(ok, ridx - bp.r_off[k], 0)
        res.append((flat, ok))
    return res


@pytest.mark.parametrize("skip", [True, False])
@pytest.mark.parametrize("binpack", [False, True])
def test_flat_take_matches_plane_chain(monkeypatch, binpack, skip):
    """The per-join take index and one take per channel give the same
    (flat, ok) as the [K, Ll] plane chain, dense and bin-packed, and the
    same joined frame as the default engine.  Where ok is false the
    flat row is never read (``_gather`` masks it; the bin-packed chain
    left it at the series start), so it is compared where ok only."""
    from tempo_tpu import join

    L, R = _take_frames(5 + 2 * binpack + skip)
    monkeypatch.setenv("TEMPO_TPU_BINPACK", "1" if binpack else "0")
    monkeypatch.delenv("TEMPO_TPU_JOIN_ENGINE", raising=False)
    want = L.asofJoin(R, skipNulls=skip).df

    seen = {"rows": []}
    real_build, real_call, real_starts, real_rows = (
        pm.build_chunked_planes, pm._chunked_call, join._right_starts,
        join._right_rows)

    def build_spy(*a, **k):
        seen["build"] = (a, k, real_build(*a, **k))
        return seen["build"][2]

    def call_spy(*a, **k):
        seen["out"] = real_call(*a, **k)
        return seen["out"]

    def starts_spy(*a):
        seen["layouts"] = a
        return real_starts(*a)

    def rows_spy(*a):
        seen["rows"].append(real_rows(*a))
        return seen["rows"][-1]

    monkeypatch.setattr(pm, "build_chunked_planes", build_spy)
    monkeypatch.setattr(pm, "_chunked_call", call_spy)
    monkeypatch.setattr(join, "_right_starts", starts_spy)
    monkeypatch.setattr(join, "_right_rows", rows_spy)
    _force_chunked(monkeypatch, binpack)
    got = L.asofJoin(R, skipNulls=skip).df
    pd.testing.assert_frame_equal(got, want, check_exact=True)

    (l_ts, r_ts, *_), kw, (_, _, plan, _) = seen["build"]
    l_layout, _, bp = seen["layouts"]
    assert (bp is not None) == binpack
    assert plan.n_chunks >= 3                  # left runs cross chunk edges
    assert 0 in l_layout.lengths               # the empty left series
    l_out = _lexsort_plan(l_ts, r_ts, plan.merged_lanes, kw["l_sid"],
                          kw["r_sid"])["l_out"]
    oracle = _plane_chain(seen["out"], l_out, *seen["layouts"], skip)
    assert len(seen["rows"]) == len(oracle) == (4 if skip else 1)
    for i, ((flat, ok), (wflat, wok)) in enumerate(zip(seen["rows"],
                                                       oracle)):
        np.testing.assert_array_equal(ok, wok, err_msg=f"channel {i} ok")
        np.testing.assert_array_equal(flat[ok], wflat[wok],
                                      err_msg=f"channel {i} flat")
        assert flat.dtype == np.int64 and len(flat) == l_layout.n_rows
    if skip:                                   # the all-NaN channel
        never = list(R.df.columns).index("never") - 1
        assert not seen["rows"][never][1].any()
        assert all(ok.any() for i, (_, ok) in enumerate(seen["rows"])
                   if i != never)


@pytest.mark.parametrize("skip", [True, False])
@pytest.mark.parametrize("binpack", [False, True])
def test_chunked_join_reads_host_planes_of_read_channels(
        monkeypatch, binpack, skip):
    """The chunked index path hands the join host numpy arrays (no
    device round trip), and only the channels the join reads are
    unpacked: the per-column channels under skipNulls, else the
    last-row channel alone — counted from the ``tempo.unpack`` rows,
    left rows per channel taken."""
    import jax

    L, R = _take_frames(11 + binpack)
    calls = []
    real = pm.asof_merge_indices_chunked

    def spy(*a, **k):
        calls.append(real(*a, **k))
        return calls[-1]

    monkeypatch.setattr(pm, "asof_merge_indices_chunked", spy)
    _force_chunked(monkeypatch, binpack)
    first = max((s.id for s in profiling.recent_spans()[0]), default=0)
    L.asofJoin(R, skipNulls=skip)
    spans = [s for s in profiling.recent_spans()[0] if s.id > first]

    n_read = len(R.df.columns) - 1 if skip else 1
    (take, planes), = calls
    for a in (take, *planes):
        assert type(a) is np.ndarray and not isinstance(a, jax.Array)
    assert len(planes) == n_read
    op, = [s for s in spans if s.name == "tempo.asofJoin"]
    unpacked = sum(s.rows for s in spans
                   if s.root == op.id and s.name == "tempo.unpack")
    assert unpacked == n_read * len(L.df)


def test_chunked_ring_depth_bitwise(monkeypatch):
    """TEMPO_TPU_DMA_BUFFERS > 2 streams the payload planes through
    the explicit chunk-axis prefetch ring (ISSUE 6) — outputs must be
    IDENTICAL to the BlockSpec-pipelined kernel, including across the
    cross-chunk carry (the ring must never outrun the fill state)."""
    from tempo_tpu.ops import pallas_merge as pm

    rng = np.random.default_rng(41)
    K, L = 8, 1024
    l_ts = np.cumsum(rng.integers(1, 3, (K, L)).astype(np.int64),
                     axis=-1) * 1_000_000
    r_ts = np.cumsum(rng.integers(1, 3, (K, L)).astype(np.int64),
                     axis=-1) * 1_000_000
    r_values = rng.standard_normal((2, K, L)).astype(np.float32)
    r_valids = rng.random((2, K, L)) > 0.1
    r_valids[0, 3] = False                  # NaN runs straddle chunks
    monkeypatch.delenv("TEMPO_TPU_DMA_BUFFERS", raising=False)
    base = _chunked_values(
        l_ts, r_ts, r_valids, r_values, chunk_lanes=512, interpret=True)
    for depth in (3, 4):
        monkeypatch.setenv("TEMPO_TPU_DMA_BUFFERS", str(depth))
        ring = _chunked_values(
            l_ts, r_ts, r_valids, r_values, chunk_lanes=512,
            interpret=True)
        for a, b, name in zip(base, ring, ("vals", "found", "idx")):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"depth={depth}:{name}")


# ----------------------------------------------------------------------
# The chunk plan: per-chunk run bounds against the per-row lexsort
# ----------------------------------------------------------------------

def _lexsort_plan(l_ts, r_ts, Cm, l_sid=None, r_sid=None, l_seq=None,
                  r_seq=None):
    """The former planner, kept as the oracle: one lexsort of every
    row's merged stream, per-row destinations from each row's merged
    position, the pad sid a maximum over every merged row."""
    from tempo_tpu import packing

    K, Ll = l_ts.shape
    Lr = r_ts.shape[1]
    S = Cm // 2
    segmented = l_sid is not None
    if l_seq is not None or r_seq is not None:
        l_seq, r_seq = packing._seq_merge_sides_np(l_seq, r_seq, K, Ll, Lr)
    l_counts = (l_ts < packing.TS_REAL_MAX).sum(axis=1)
    r_counts = (r_ts < packing.TS_REAL_MAX).sum(axis=1)
    n_chunks = max(int(-(-int((l_counts + r_counts).max(initial=0)) // S)),
                   1)
    l_dest = np.full((K, Ll), -1, np.int64)
    r_dest = np.full((K, Lr), -1, np.int64)
    l_out = np.full((K, Ll), -1, np.int64)
    r_pos = np.full((K, Lr), -1, np.int64)
    pad_sid = np.full((K, n_chunks), -1, np.int64) if segmented else None
    for k in range(K):
        nl, nr = int(l_counts[k]), int(r_counts[k])
        n = nl + nr
        if n == 0:
            continue
        side = np.concatenate([np.ones(nl, np.int8), np.zeros(nr, np.int8)])
        lex = [side]
        if l_seq is not None:
            lex.append(np.concatenate([l_seq[k, :nl], r_seq[k, :nr]]))
        lex.append(np.concatenate([l_ts[k, :nl], r_ts[k, :nr]]))
        if segmented:
            lex.append(np.concatenate([l_sid[k, :nl], r_sid[k, :nr]]))
        order = np.lexsort(tuple(lex))
        mpos = np.empty(n, np.int64)
        mpos[order] = np.arange(n, dtype=np.int64)
        l_mpos, r_mpos = mpos[:nl], mpos[nl:]
        lc, rc = l_mpos // S, r_mpos // S
        l_rank = np.arange(nl) - np.searchsorted(l_mpos, lc * S)
        r_rank = np.arange(nr) - np.searchsorted(r_mpos, rc * S)
        l_dest[k, :nl] = lc * Cm + l_rank
        r_dest[k, :nr] = rc * Cm + (2 * S - 1 - r_rank)
        l_out[k, :nl] = lc * S + l_rank
        r_pos[k, :nr] = r_mpos
        if segmented:
            sid_sorted = np.concatenate(
                [l_sid[k, :nl], r_sid[k, :nr]])[order]
            np.maximum.at(pad_sid[k], np.arange(n, dtype=np.int64) // S,
                          sid_sorted.astype(np.int64))
    if segmented:
        pad_sid = np.where(pad_sid < 0, np.int64(packing.SID_PAD),
                           pad_sid).astype(np.int32)
    return dict(n_chunks=n_chunks, l_dest=l_dest, r_dest=r_dest,
                l_out=l_out, r_pos=r_pos, chunk_pad_sid=pad_sid)


def _scatter_planes(plan, l_ts, r_ts, r_valids, r_values, l_sid, r_sid,
                    ls, rs, skip, ml):
    """The former plane build, kept as the oracle: every plane a 2-D
    fancy scatter through the oracle plan's per-row destinations."""
    K = l_ts.shape[0]
    Lr = r_ts.shape[1]
    Cm = CHUNK
    W = plan["n_chunks"] * Cm
    imax = np.int32(2**31 - 1)

    def scatter(base, src, dest):
        rows = np.broadcast_to(np.arange(K)[:, None], dest.shape)
        m = dest >= 0
        base[rows[m], dest[m]] = src[m]
        return base

    keys = []
    if l_sid is not None:
        sid_pl = np.repeat(plan["chunk_pad_sid"], Cm, axis=1).astype(np.int32)
        scatter(sid_pl, l_sid.astype(np.int32), plan["l_dest"])
        keys.append(scatter(sid_pl, r_sid.astype(np.int32), plan["r_dest"]))
    def split(ts):
        ts = ts.astype(np.int64)
        return [(ts >> 32).astype(np.int32),
                ((ts & 0xFFFFFFFF) - (1 << 31)).astype(np.int32)]

    def seq_planes(seq):
        if seq.dtype == np.int32:
            return [seq]
        if seq.dtype == np.int64:
            return split(seq)
        b = seq.view(np.int32)
        return [np.where(b >= 0, b.astype(np.int64),
                         np.int64(-(2**31)) - b.astype(np.int64)
                         ).astype(np.int32)]

    pairs = list(zip(split(l_ts), split(r_ts)))
    if ls is not None:
        pairs += list(zip(seq_planes(ls), seq_planes(rs)))
    for a, b in pairs:
        p = np.full((K, W), imax, np.int32)
        scatter(p, a, plan["l_dest"])
        keys.append(scatter(p, b, plan["r_dest"]))

    def rscat(src):
        return scatter(np.full((K, W), np.nan, np.float32),
                       src.astype(np.float32), plan["r_dest"])

    val_srcs = [np.where(r_valids[c], r_values[c].astype(np.float32),
                         np.float32(np.nan)) for c in range(len(r_values))]
    planes = [rscat(src) for src in val_srcs]
    planes.append(rscat(np.broadcast_to(np.arange(Lr, dtype=np.float32),
                                        (K, Lr))))
    if ml:
        rpos = plan["r_pos"].astype(np.float32)
        if not skip:
            planes.append(rscat(rpos))
        else:
            planes.extend(rscat(np.where(np.isnan(s), np.float32(np.nan),
                                         rpos)) for s in val_srcs)
            planes.append(rscat(rpos))
    return keys, planes


def _sorted_side(rng, K, L, lengths, span, base):
    ts = np.full((K, L), TS_PAD, np.int64)
    for k in range(K):
        ts[k, :lengths[k]] = np.sort(
            base[k] + rng.integers(0, span, lengths[k]) * 10**9)
    return ts


def _seq_side(rng, ts, dtype):
    """Seq per row ascending within each ts run, nulls (the dtype's
    floor: -inf for floats) leading some runs."""
    K, L = ts.shape
    floating = np.issubdtype(dtype, np.floating)
    lo, hi = ((-np.inf, np.inf) if floating
              else (np.iinfo(dtype).min, np.iinfo(dtype).max))
    seq = np.full((K, L), hi, dtype)
    for k in range(K):
        n = int((ts[k] < TS_PAD).sum())
        raw = rng.integers(-3, 4, n)
        null = rng.random(n) < 0.2
        # ascending (ts, seq): nulls first inside every equal-ts run
        order = np.lexsort((raw, ~null, ts[k, :n]))
        seq[k, :n] = np.where(null[order], lo, raw[order])
    return seq


def _segmented_rows(rng, rows):
    """Bin-packed sides from per-row lists of (left, right) series
    lengths: series ids ascend along each row, then pads."""
    from tempo_tpu.packing import SID_PAD

    def side(j):
        width = max(8, -(-max(sum(sp[j] for sp in r) for r in rows) // 8) * 8)
        ts = np.full((len(rows), width), TS_PAD, np.int64)
        sid = np.full((len(rows), width), SID_PAD, np.int32)
        s = 0
        for k, r in enumerate(rows):
            at = 0
            for sp in r:
                n = sp[j]
                ts[k, at:at + n] = np.sort(rng.integers(0, 30, n)) * 10**9
                sid[k, at:at + n] = s
                at += n
                s += 1
        return ts, sid

    (l_ts, l_sid), (r_ts, r_sid) = side(0), side(1)
    return l_ts, r_ts, l_sid, r_sid


def _plan_case(name):
    """(l_ts, r_ts, r_valids, r_values, l_sid, r_sid, l_seq, r_seq)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    S = CHUNK // 2
    if name == "binpacked":
        case = _binpacked_case(seed=13, S=23, Lmax=80)
        lt2, rt2, lsid, rsid, rv2, rm2 = case[7:]
        return lt2, rt2, rm2, rv2, lsid, rsid, None, None
    if name == "segmented_edges":
        # left-only and right-only series ending chunk runs, a chunk
        # of left rows alone before the first right rows
        l_ts, r_ts, l_sid, r_sid = _segmented_rows(rng, [
            [(300, 0), (0, 90), (50, 60), (70, 0), (0, 40)],
            [(130, 3), (5, 140), (60, 60)]])
        Lr = r_ts.shape[1]
        r_values = rng.standard_normal((2, 2, Lr)).astype(np.float32)
        r_valids = (rng.random((2, 2, Lr)) > 0.3) & (r_ts < TS_PAD)
        return l_ts, r_ts, r_valids, r_values, l_sid, r_sid, None, None
    if name == "disjoint_runs":
        # one side's rows all before the other's: chunks holding one
        # side alone, then the other
        K, L = 2, 3 * S
        l_ts = _sorted_side(rng, K, L, [2 * S + 5, S + 20], 10,
                            np.array([0, 100 * 10**9]))
        r_ts = _sorted_side(rng, K, L, [S + 30, 2 * S + 1], 10,
                            np.array([100 * 10**9, 0]))
    elif name == "ties_at_edges":
        # equal ts on both sides across and at every chunk edge
        K, L = 3, 5 * S
        lens = np.array([L, 3 * S + 17, 2 * S])
        base = np.zeros(K, np.int64)
        l_ts = _sorted_side(rng, K, L, lens, 6, base)
        r_ts = _sorted_side(rng, K, L, lens[::-1], 6, base)
    elif name == "ragged_empty":
        # an empty row, a left-only row, a right-only row, a ragged one
        K, L = 4, 3 * S
        l_ts = _sorted_side(rng, K, L, [0, 2 * S + 5, 0, 3 * S], 40,
                            np.zeros(K, np.int64))
        r_ts = _sorted_side(rng, K, L, [0, 0, 3 * S, S + 3], 40,
                            np.zeros(K, np.int64))
    elif name == "exact_fill":
        # row 0 fills its last chunk exactly, row 1 ends one row short
        K, L = 2, 4 * S
        l_ts = _sorted_side(rng, K, L, [2 * S + 7, 2 * S], 30,
                            np.zeros(K, np.int64))
        r_ts = _sorted_side(rng, K, L, [S - 7, 2 * S - 1], 30,
                            np.zeros(K, np.int64))
    else:                                   # seq_<dtype>[_both]
        K, L = 3, 3 * S
        lens = np.array([3 * S, 2 * S + 9, S])
        base = np.zeros(K, np.int64)
        l_ts = _sorted_side(rng, K, L, lens, 5, base)
        r_ts = _sorted_side(rng, K, L, lens[::-1], 5, base)
    C = 2
    Lr = r_ts.shape[1]
    r_values = rng.standard_normal((C, r_ts.shape[0], Lr)).astype(np.float32)
    r_valids = (rng.random((C, r_ts.shape[0], Lr)) > 0.3) & (r_ts < TS_PAD)
    l_seq = r_seq = None
    if name.startswith("seq_"):
        dt = np.dtype(name.split("_")[1])
        r_seq = _seq_side(rng, r_ts, dt)
        if name.endswith("_both"):
            l_seq = _seq_side(rng, l_ts, dt)
    return l_ts, r_ts, r_valids, r_values, None, None, l_seq, r_seq


_PLAN_CASES = ["ties_at_edges", "ragged_empty", "exact_fill",
               "disjoint_runs", "binpacked", "segmented_edges", "seq_int32",
               "seq_int64", "seq_float32", "seq_float32_both"]


@pytest.mark.parametrize("ml,skip", [(0, True), (5, True), (5, False)])
@pytest.mark.parametrize("name", _PLAN_CASES)
def test_chunk_plan_matches_lexsort_oracle(name, ml, skip):
    """Run bounds from the merge-path bisection give the lexsort
    planner's layout exactly: chunk count, the right rows' merged
    positions, the pad sids, the take index (the left rows' output
    destinations) and every uploaded plane bitwise."""
    from tempo_tpu import packing

    (l_ts, r_ts, r_valids, r_values, l_sid, r_sid,
     l_seq, r_seq) = _plan_case(name)
    keys, planes, plan, _ = pm.build_chunked_planes(
        l_ts, r_ts, r_valids, r_values, l_sid=l_sid, r_sid=r_sid,
        l_seq=l_seq, r_seq=r_seq, skip_nulls=skip, max_lookback=ml,
        chunk_lanes=CHUNK)
    ls = rs = None
    if l_seq is not None or r_seq is not None:
        K, Ll, Lr = l_ts.shape[0], l_ts.shape[1], r_ts.shape[1]
        ls, rs = packing._seq_merge_sides_np(
            np.asarray(pm.seq_kernel_form(jnp.asarray(l_seq)))
            if l_seq is not None else None,
            np.asarray(pm.seq_kernel_form(jnp.asarray(r_seq)))
            if r_seq is not None else None, K, Ll, Lr)
    want = _lexsort_plan(l_ts, r_ts, CHUNK, l_sid, r_sid, ls, rs)
    assert plan.n_chunks == want["n_chunks"]
    assert plan.n_chunks >= 2, "the case must cross a chunk edge"
    np.testing.assert_array_equal(plan.r_pos, want["r_pos"],
                                  err_msg="r_pos")
    if l_sid is None:
        assert plan.chunk_pad_sid is None
    else:
        np.testing.assert_array_equal(plan.chunk_pad_sid,
                                      want["chunk_pad_sid"])

    real = np.flatnonzero((l_ts < TS_PAD).ravel())
    K = l_ts.shape[0]
    base = np.arange(K, dtype=np.int64)[:, None] * (plan.n_chunks * CHUNK // 2)
    np.testing.assert_array_equal(
        packing.chunk_take_index(plan, real),
        (want["l_out"] + base).ravel()[real], err_msg="take index")

    want_keys, want_planes = _scatter_planes(
        want, l_ts, r_ts, r_valids, r_values, l_sid, r_sid, ls, rs,
        skip, ml)
    assert len(keys) == len(want_keys) + 1      # + the side/pos plane
    assert len(planes) == len(want_planes)
    for i, (a, b) in enumerate(zip(keys, want_keys)):
        assert a.dtype == np.int32 and a.flags.c_contiguous
        np.testing.assert_array_equal(a, b, err_msg=f"key plane {i}")
    for i, (a, b) in enumerate(zip(planes, want_planes)):
        assert a.dtype == np.float32 and a.flags.c_contiguous
        np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32),
                                      err_msg=f"payload plane {i}")


def test_chunk_plan_sorts_no_row(monkeypatch):
    """The planner reads bounds by bisection: no sort, search or
    scatter-maximum over rows may run inside it, and its bounds are
    ``n_chunks + 1`` non-decreasing columns ending at the counts."""
    import types

    from tempo_tpu import packing

    def boom(*a, **k):
        raise AssertionError("per-row sort or search in the chunk plan")

    class _NoAt:
        def __call__(self, *a, **k):
            return np.maximum(*a, **k)

        at = staticmethod(boom)

    cases = [_plan_case(n) for n in
             ("ties_at_edges", "segmented_edges", "seq_float32_both")]
    guarded = types.SimpleNamespace(**vars(np))
    for f in ("lexsort", "argsort", "sort", "searchsorted"):
        setattr(guarded, f, boom)
    guarded.maximum = _NoAt()
    monkeypatch.setattr(packing, "np", guarded)
    for l_ts, r_ts, _, _, l_sid, r_sid, l_seq, r_seq in cases:
        plan = packing.asof_chunk_plan(l_ts, r_ts, CHUNK, l_sid, r_sid,
                                       l_seq, r_seq)
        assert plan.n_chunks >= 2
        for b, ts in ((plan.l_bounds, l_ts), (plan.r_bounds, r_ts)):
            assert b.shape == (l_ts.shape[0], plan.n_chunks + 1)
            assert (np.diff(b, axis=1) >= 0).all()
            assert (b[:, 0] == 0).all()
            assert (b[:, -1] == (ts < TS_PAD).sum(axis=1)).all()
        assert ((np.diff(plan.l_bounds, axis=1)
                 + np.diff(plan.r_bounds, axis=1)) <= CHUNK // 2).all()
