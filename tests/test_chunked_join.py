"""Lane-chunked streaming AS-OF merge: chunked vs single-plan vs
host-bracket oracle across the full flag matrix.

The chunked engine (ops/pallas_merge.py:asof_merge_values_chunked) must
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
    got = pm.asof_merge_values_chunked(
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
    got = pm.asof_merge_values_chunked(
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
    got = pm.asof_merge_values_chunked(
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
    got = pm.asof_merge_values_chunked(
        lt2, rt2, rm2, rv2, lsid, rsid, chunk_lanes=CHUNK, interpret=True)
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
    """The three engines run the same network: real-lane outputs are
    bit-identical (fills select values, they never compute)."""
    rng = np.random.default_rng(21)
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, 4, 256, 256, 2,
                                                tie_heavy=True)
    a = pm.asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), interpret=True)
    b = pm.asof_merge_values_chunked(
        l_ts, r_ts, r_valids, r_values, chunk_lanes=CHUNK, interpret=True)
    c = pm.asof_merge_values_bitonic(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values))
    real = l_ts < TS_PAD
    for other, label in ((b, "chunked"), (c, "bitonic")):
        np.testing.assert_array_equal(
            np.asarray(a[0])[:, real].view(np.int32),
            np.asarray(other[0])[:, real].view(np.int32),
            err_msg=f"{label} not bitwise-identical")
        np.testing.assert_array_equal(
            np.asarray(a[2])[real], np.asarray(other[2])[real],
            err_msg=label)


def test_chunked_rejects_tracers():
    def f(a, b, c, d):
        return pm.asof_merge_values_chunked(a, b, c, d)[0]

    import jax

    l_ts, r_ts, r_valids, r_values = _rand_case(
        np.random.default_rng(0), 2, 128, 128, 1)
    with pytest.raises(TypeError, match="bitonic"):
        jax.jit(f)(jnp.asarray(l_ts), jnp.asarray(r_ts),
                   jnp.asarray(r_valids), jnp.asarray(r_values))


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


def _plane_chain(out, plan, l_layout, r_layout, bp, skip):
    """The former unpack, kept as the oracle: every channel gathered into
    a [K, Ll] plane (chunk_gather), -1 for no match, then read at each
    left row's (lane row, lane) plus its series' right start."""
    from tempo_tpu import packing

    coded = [np.where(np.isnan(p), -1, p).astype(np.int32)
             for p in (packing.chunk_gather(np.asarray(o), plan.l_out,
                                            np.nan, np.float32)
                       for o in out)]
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
    real_run, real_starts, real_rows = (
        pm._chunked_run, join._right_starts, join._right_rows)

    def run_spy(*a):
        seen["run"] = real_run(*a)
        return seen["run"]

    def starts_spy(*a):
        seen["layouts"] = a
        return real_starts(*a)

    def rows_spy(*a):
        seen["rows"].append(real_rows(*a))
        return seen["rows"][-1]

    monkeypatch.setattr(pm, "_chunked_run", run_spy)
    monkeypatch.setattr(join, "_right_starts", starts_spy)
    monkeypatch.setattr(join, "_right_rows", rows_spy)
    _force_chunked(monkeypatch, binpack)
    got = L.asofJoin(R, skipNulls=skip).df
    pd.testing.assert_frame_equal(got, want, check_exact=True)

    out, plan, _ = seen["run"]
    l_layout, _, bp = seen["layouts"]
    assert (bp is not None) == binpack
    assert plan.n_chunks >= 3                  # left runs cross chunk edges
    assert 0 in l_layout.lengths               # the empty left series
    oracle = _plane_chain(out, plan, *seen["layouts"], skip)
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
    base = pm.asof_merge_values_chunked(
        l_ts, r_ts, r_valids, r_values, chunk_lanes=512, interpret=True)
    for depth in (3, 4):
        monkeypatch.setenv("TEMPO_TPU_DMA_BUFFERS", str(depth))
        ring = pm.asof_merge_values_chunked(
            l_ts, r_ts, r_valids, r_values, chunk_lanes=512,
            interpret=True)
        for a, b, name in zip(base, ring, ("vals", "found", "idx")):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"depth={depth}:{name}")
