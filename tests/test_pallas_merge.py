"""Pallas merge-join kernel: interpret-mode correctness vs the XLA
sort-and-scan oracle (``sortmerge._asof_merge_explicit``) and numpy.

The compiled path is TPU-only: tests/test_tpu_compile.py compiles it
for a v5e, and the ``hhar.batch_chain`` cell runs it on the chip.  The
network logic (bitonic merge, ffill ladder, routing sort via roll +
iota masks) is identical in interpret mode.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tempo_tpu.ops import sortmerge as sm
from tempo_tpu.ops.pallas_merge import (
    asof_merge_values_pallas, merge_join_supported,
)
from tempo_tpu.packing import TS_PAD


def _rand_case(rng, K, Ll, Lr, C, tie_heavy=False):
    """Ragged TS_PAD-padded sides with ties, negative ts, nulls."""
    llen = rng.integers(0, Ll + 1, K)
    rlen = rng.integers(0, Lr + 1, K)
    llen[0], rlen[0] = Ll, 0        # no right rows at all
    if K > 1:
        llen[1], rlen[1] = 0, Lr    # no left rows at all
    span = 8 if tie_heavy else 50
    l_ts = np.full((K, Ll), TS_PAD, np.int64)
    r_ts = np.full((K, Lr), TS_PAD, np.int64)
    for k in range(K):
        base = rng.integers(-5, 5) * 10**9
        l_ts[k, : llen[k]] = np.sort(
            base + rng.integers(0, span, llen[k]) * 10**9
        )
        r_ts[k, : rlen[k]] = np.sort(
            base + rng.integers(0, span, rlen[k]) * 10**9
        )
    r_values = rng.standard_normal((C, K, Lr)).astype(np.float32)
    r_valids = rng.random((C, K, Lr)) > 0.3
    if C:
        r_valids[0, min(2, K - 1)] = False   # an all-null column/series
    for k in range(K):
        r_valids[:, k, rlen[k]:] = False
    return l_ts, r_ts, r_valids, r_values


@pytest.mark.parametrize(
    "K,Ll,Lr,C,ties",
    [
        (4, 128, 128, 2, False),
        (3, 256, 128, 1, False),
        (5, 128, 384, 3, False),
        (2, 128, 128, 0, False),
        (6, 256, 256, 2, True),   # dense timestamp ties
        (3, 200, 136, 2, False),  # non-128-multiple right side
    ],
)
def test_matches_xla_merge(K, Ll, Lr, C, ties):
    rng = np.random.default_rng(K * 1000 + Ll + Lr + C)
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, K, Ll, Lr, C, ties)
    want_v, want_f, want_i = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values),
    )
    got_v, got_f, got_i = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_allclose(
        np.asarray(got_v), np.asarray(want_v), equal_nan=True
    )
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_numpy_oracle_direct():
    """Independent oracle: per-row searchsorted + last-valid scan."""
    rng = np.random.default_rng(0)
    K, Ll, Lr, C = 5, 128, 128, 2
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, K, Ll, Lr, C)
    got_v, _, got_i = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), interpret=True,
    )
    gv, gi = np.asarray(got_v), np.asarray(got_i)
    for k in range(K):
        # real right rows only: pads carry TS_PAD and never match real ts
        pos = np.searchsorted(r_ts[k], l_ts[k], side="right") - 1
        real = l_ts[k] < TS_PAD
        for c in range(C):
            lv = np.where(r_valids[c, k], np.arange(Lr), -1)
            lv = np.maximum.accumulate(lv)
            idx = np.where(pos >= 0, lv[np.maximum(pos, 0)], -1)
            want = np.where(
                idx >= 0, r_values[c, k][np.maximum(idx, 0)], np.nan
            )
            np.testing.assert_allclose(
                gv[c, k][real[: Ll]], want[real[: Ll]], equal_nan=True,
                err_msg=f"k={k} c={c}",
            )


def test_right_ties_last_wins():
    """Equal-ts right rows: the later (by position) row is the as-of
    value, and tied-ts right rows are visible to tied left rows
    (rec_ind semantics, tsdf.py:119,546)."""
    T = 10**9
    l_ts = np.array([[2 * T, 3 * T]], np.int64)
    l_ts = np.pad(l_ts, ((0, 0), (0, 126)), constant_values=TS_PAD)
    r_ts = np.array([[2 * T, 2 * T]], np.int64)
    r_ts = np.pad(r_ts, ((0, 0), (0, 126)), constant_values=TS_PAD)
    r_vals = np.zeros((1, 1, 128), np.float32)
    r_vals[0, 0, :2] = [1.0, 2.0]
    r_valid = np.zeros((1, 1, 128), bool)
    r_valid[0, 0, :2] = True
    vals, found, idx = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valid),
        jnp.asarray(r_vals), interpret=True,
    )
    assert np.asarray(vals)[0, 0, :2].tolist() == [2.0, 2.0]
    assert np.asarray(idx)[0, :2].tolist() == [1, 1]


def _binpacked_case(seed=3, S=37, Lmax=96, C=2):
    """Skew-length series, bin-packed into shared lane rows, with the
    dense per-series layout kept as the oracle input."""
    from tempo_tpu import packing as pkg

    rng = np.random.default_rng(seed)
    llen = rng.integers(1, Lmax + 1, S)
    rlen = rng.integers(0, Lmax + 1, S)
    llen[0] = Lmax
    l_ts = np.full((S, Lmax), TS_PAD, np.int64)
    r_ts = np.full((S, Lmax), TS_PAD, np.int64)
    for s in range(S):
        base = rng.integers(-3, 3) * 10**9
        l_ts[s, : llen[s]] = np.sort(
            base + rng.integers(0, 40, llen[s]) * 10**9
        )
        r_ts[s, : rlen[s]] = np.sort(
            base + rng.integers(0, 40, rlen[s]) * 10**9
        )
    r_values = rng.standard_normal((C, S, Lmax)).astype(np.float32)
    r_valids = rng.random((C, S, Lmax)) > 0.3
    for s in range(S):
        r_valids[:, s, rlen[s]:] = False

    W = 256
    bp = pkg.bin_pack_series(llen, rlen, W, W)
    K2 = bp.n_rows
    lt2 = pkg.binpack_rows(l_ts, llen, bp.row, bp.l_off, K2, W, TS_PAD)
    rt2 = pkg.binpack_rows(r_ts, rlen, bp.row, bp.r_off, K2, W, TS_PAD)
    lsid = pkg.binpack_sid(llen, bp.row, bp.l_off, K2, W)
    rsid = pkg.binpack_sid(rlen, bp.row, bp.r_off, K2, W)
    rv2 = np.stack([
        pkg.binpack_rows(r_values[c], rlen, bp.row, bp.r_off, K2, W, 0.0)
        for c in range(C)
    ])
    rm2 = np.stack([
        pkg.binpack_rows(r_valids[c], rlen, bp.row, bp.r_off, K2, W,
                         False)
        for c in range(C)
    ])
    return (l_ts, r_ts, r_valids, r_values, llen, rlen, bp,
            lt2, rt2, lsid, rsid, rv2, rm2)


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_binpacked_matches_per_series_oracle(engine):
    case = _binpacked_case()
    (l_ts, r_ts, r_valids, r_values, llen, rlen, bp,
     lt2, rt2, lsid, rsid, rv2, rm2) = case
    C, S, _ = r_values.shape

    want_v, want_f, want_i = (np.asarray(a) for a in sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values),
    ))
    if engine == "pallas":
        got = asof_merge_values_pallas(
            jnp.asarray(lt2), jnp.asarray(rt2), jnp.asarray(rm2),
            jnp.asarray(rv2), jnp.asarray(lsid), jnp.asarray(rsid),
            interpret=True,
        )
    else:
        got = sm.asof_merge_values_binpacked(
            jnp.asarray(lt2), jnp.asarray(rt2), jnp.asarray(rm2),
            jnp.asarray(rv2), jnp.asarray(lsid), jnp.asarray(rsid),
        )
    gv, gf, gi = (np.asarray(a) for a in got)
    for s in range(S):
        r0, o0 = bp.row[s], bp.l_off[s]
        sl = slice(o0, o0 + llen[s])
        np.testing.assert_array_equal(
            gf[:, r0, sl], want_f[:, s, : llen[s]], err_msg=f"s={s} found"
        )
        np.testing.assert_allclose(
            gv[:, r0, sl], want_v[:, s, : llen[s]], equal_nan=True,
            err_msg=f"s={s} vals",
        )
        # last_row_idx is a within-lane-row position: convert back to
        # the per-series index with the packed right offset
        gidx = gi[r0, sl]
        w = want_i[s, : llen[s]]
        conv = np.where(gidx >= 0, gidx - bp.r_off[s], -1)
        np.testing.assert_array_equal(conv, w, err_msg=f"s={s} idx")


def test_bin_pack_layout_properties():
    from tempo_tpu import packing as pkg

    rng = np.random.default_rng(0)
    S = 200
    llen = np.maximum((512 / np.arange(1, S + 1) ** 0.6).astype(int), 3)
    rlen = rng.permutation(llen)
    bp = pkg.bin_pack_series(llen, rlen, 512, 512)
    # every series fits its row, no overlap, ascending-sid layout
    for side, lens, offs in (("l", llen, bp.l_off), ("r", rlen, bp.r_off)):
        for b in range(bp.n_rows):
            segs = sorted(
                (offs[s], offs[s] + lens[s])
                for s in range(S) if bp.row[s] == b
            )
            ids = sorted(
                (offs[s], s) for s in range(S) if bp.row[s] == b
            )
            assert segs[-1][1] <= 512
            for (a0, a1), (b0, _) in zip(segs, segs[1:]):
                assert a1 <= b0, side
            assert [x[1] for x in ids] == sorted(x[1] for x in ids)
    assert bp.occupancy(llen, rlen) > 0.8


@pytest.mark.parametrize("K,Ll,Lr,C", [(4, 128, 128, 2), (3, 200, 136, 1)])
def test_indices_kernel_matches_xla(K, Ll, Lr, C):
    from tempo_tpu.ops.pallas_merge import asof_merge_indices_pallas

    rng = np.random.default_rng(K + Lr)
    l_ts, r_ts, r_valids, _ = _rand_case(rng, K, Ll, Lr, C)
    want_last, want_col = sm._asof_merge_indices_xla(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids)
    )
    got_last, got_col = asof_merge_indices_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        interpret=True,
    )
    # per-col indices agree everywhere; the unconditional last-row
    # channel agrees at real left rows (at TS_PAD left slots both
    # engines report arbitrary-but-found pad matches: the XLA form
    # reports the pad's index, the NaN-encoded kernel the same — but
    # their tie order among equal-TS_PAD keys may differ)
    np.testing.assert_array_equal(np.asarray(got_col),
                                  np.asarray(want_col))
    real = l_ts < TS_PAD
    np.testing.assert_array_equal(
        np.asarray(got_last)[real], np.asarray(want_last)[real]
    )


@pytest.mark.parametrize(
    "K,Lk,Lq,dt",
    [
        (4, 128, 128, np.int32),
        (3, 200, 136, np.int64),
        (5, 384, 128, np.int32),
        (2, 128, 300, np.int64),
    ],
)
def test_merge_rank_kernel_matches_searchsorted(K, Lk, Lq, dt):
    from tempo_tpu.ops.pallas_merge import merge_rank_pallas

    rng = np.random.default_rng(K * 7 + Lk)
    keys = np.sort(rng.integers(0, 300, (K, Lk)), -1).astype(dt)
    qs = np.sort(rng.integers(-5, 310, (K, Lq)), -1).astype(dt)
    if dt == np.int64:
        keys, qs = keys * 10**9, qs * 10**9
    # clamped pads like real callers (rebased i32 / TS-pad headroom)
    big = np.iinfo(dt).max if dt == np.int32 else np.int64(2**62)
    keys[0, Lk // 2:] = big
    qs[0, Lq // 2:] = big
    for side in ("left", "right"):
        got = np.asarray(merge_rank_pallas(
            jnp.asarray(keys), jnp.asarray(qs), side=side, interpret=True
        ))
        want = np.stack([
            np.searchsorted(keys[k], qs[k], side=side) for k in range(K)
        ])
        np.testing.assert_array_equal(got, want, err_msg=side)


def test_supported_gate():
    l_ts = jnp.zeros((4, 128), jnp.int64)
    r_ts = jnp.zeros((4, 128), jnp.int64)
    vals32 = jnp.zeros((2, 4, 128), jnp.float32)
    vals64 = jnp.zeros((2, 4, 128), jnp.float64)
    seq = jnp.zeros((4, 128), jnp.float32)
    # CPU backend in tests: never engages compiled path (seq and
    # skipNulls=False included since round 4 — same answer here)
    assert not merge_join_supported(l_ts, r_ts, vals32, None, None, True)
    assert not merge_join_supported(l_ts, r_ts, vals32, None, seq, True)
    assert not merge_join_supported(l_ts, r_ts, vals32, None, None, False)
    # independent of backend: these shapes must always be rejected
    assert not merge_join_supported(l_ts, r_ts, vals64, None, None, True)
    assert not merge_join_supported(l_ts, r_ts, vals32, None, seq, True,
                                    segmented=True)


def test_gate_on_forced_tpu_backend(monkeypatch):
    """The gate's shape logic with the backend check forced open: the
    round-4 extensions admit seq and skipNulls=False, and the plane
    budget counts the extra seq key planes."""
    import tempo_tpu.ops.pallas_merge as pm

    monkeypatch.setattr(pm, "_pallas_enabled", lambda: True)
    l_ts = jnp.zeros((4, 128), jnp.int64)
    r_ts = jnp.zeros((4, 128), jnp.int64)
    vals32 = jnp.zeros((2, 4, 128), jnp.float32)
    seq32 = jnp.zeros((4, 128), jnp.float32)
    seq64 = jnp.zeros((4, 128), jnp.float64)
    seqi64 = jnp.zeros((4, 128), jnp.int64)
    assert merge_join_supported(l_ts, r_ts, vals32, None, None, True)
    assert merge_join_supported(l_ts, r_ts, vals32, None, seq32, True)
    assert merge_join_supported(l_ts, r_ts, vals32, seqi64, seqi64, True)
    assert merge_join_supported(l_ts, r_ts, vals32, None, None, False)
    assert merge_join_supported(l_ts, r_ts, vals32, None, seqi64, False)
    # f64 has no device key mapping (the TPU X64 rewriter cannot
    # bitcast 64-bit) — dispatchers re-encode via seq_kernel_form first
    assert not merge_join_supported(l_ts, r_ts, vals32, None, seq64,
                                    True)
    # round 6: segmented combines with seq (bin-pack layouts sort
    # (ts, seq) per series when a seq plane is packed — join.py)
    assert merge_join_supported(l_ts, r_ts, vals32, None, seq32,
                                True, segmented=True)
    assert merge_join_supported(l_ts, r_ts, vals32, None, None, False,
                                segmented=True)


def test_seq_kernel_form():
    """f64 sequence planes re-encode for the kernel: f32 when exact,
    int64 for big integral values, None (XLA fallback) otherwise."""
    from tempo_tpu.ops.pallas_merge import seq_kernel_form

    small = jnp.asarray(np.array([[1.0, 2.5, -np.inf, np.inf]]))
    out = seq_kernel_form(small)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(out), np.float32([[1.0, 2.5, -np.inf, np.inf]])
    )
    bigint = jnp.asarray(np.array([[2.0**40, 2.0**40 + 1, -np.inf,
                                    np.inf]]))
    out = seq_kernel_form(bigint)
    assert out.dtype == jnp.int64
    got = np.asarray(out)
    assert got[0, 0] == 2**40 and got[0, 1] == 2**40 + 1
    assert got[0, 2] == np.iinfo(np.int64).min
    assert got[0, 3] == np.iinfo(np.int64).max
    # non-integral and f32-inexact: no device form
    assert seq_kernel_form(
        jnp.asarray(np.array([[0.1 + 2.0**40]]))) is None
    # pass-throughs
    f32 = jnp.zeros((1, 4), jnp.float32)
    assert seq_kernel_form(f32) is f32
    assert seq_kernel_form(None) is None


def _seq_case(rng, K, Ll, Lr, C, sdt=np.float64, tie_heavy=True):
    """Tie-heavy case with sequence planes: right nulls ride -inf
    (join.py / dist.py NULLS FIRST encoding), pads +inf."""
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, K, Ll, Lr, C,
                                                tie_heavy)
    # per-row (ts, seq)-ascending right seq — the packed-layout
    # invariant (layouts sort by (key, ts, seq), packing.py:228-245)
    r_seq = np.full((K, Lr), np.inf, sdt)
    for k in range(K):
        n = int((r_ts[k] < TS_PAD).sum())
        s = rng.integers(-3, 3, n).astype(np.float64)
        s[rng.random(n) < 0.3] = -np.inf     # null seq -> NULLS FIRST
        order = np.lexsort((s, r_ts[k, :n]))
        r_seq[k, :n] = s[order].astype(sdt)
    return l_ts, r_ts, r_valids, r_values, r_seq


@pytest.mark.parametrize("sdt", [np.float64, np.float32])
@pytest.mark.parametrize("K,Ll,Lr,C", [(4, 128, 128, 2), (3, 200, 136, 1)])
def test_seq_tiebreak_matches_xla(K, Ll, Lr, C, sdt):
    from tempo_tpu.ops.pallas_merge import seq_kernel_form

    rng = np.random.default_rng(K * 31 + Lr + (0 if sdt == np.float64
                                               else 7))
    l_ts, r_ts, r_valids, r_values, r_seq = _seq_case(rng, K, Ll, Lr, C,
                                                      sdt)
    want_v, want_f, want_i = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), r_seq=jnp.asarray(r_seq),
    )
    # f64 planes ride the dispatchers' re-encoding (seq_kernel_form)
    sq = seq_kernel_form(jnp.asarray(r_seq))
    assert sq is not None
    got_v, got_f, got_i = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), r_seq=sq, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_allclose(
        np.asarray(got_v), np.asarray(want_v), equal_nan=True
    )
    real = l_ts < TS_PAD
    np.testing.assert_array_equal(
        np.asarray(got_i)[real], np.asarray(want_i)[real]
    )


def test_seq_tiebreak_semantics_direct():
    """Spark order on a full ts tie: right-null-seq < left < right-non-
    null-seq (tsdf.py:117-121) — the null-seq right row is visible to
    the tied left row, the non-null one is not."""
    T = 10**9
    l_ts = np.pad(np.array([[2 * T]], np.int64), ((0, 0), (0, 127)),
                  constant_values=TS_PAD)
    r_ts = np.pad(np.array([[2 * T, 2 * T]], np.int64),
                  ((0, 0), (0, 126)), constant_values=TS_PAD)
    r_seq = np.full((1, 128), np.inf)
    r_seq[0, :2] = [-np.inf, 5.0]            # null first, then seq=5
    r_vals = np.zeros((1, 1, 128), np.float32)
    r_vals[0, 0, :2] = [10.0, 20.0]
    r_valid = np.zeros((1, 1, 128), bool)
    r_valid[0, 0, :2] = True
    vals, found, idx = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valid),
        jnp.asarray(r_vals), r_seq=jnp.asarray(r_seq, jnp.float32),
        interpret=True,
    )
    assert np.asarray(vals)[0, 0, 0] == 10.0   # null-seq row wins
    assert np.asarray(idx)[0, 0] == 0


def test_seq_tiebreak_int64_planes():
    """The two-plane (hi, lo) seq path: integral seqs beyond f32
    exactness re-encode as int64 (seq_kernel_form) and must order
    correctly across the 2^31 lo-plane boundary."""
    from tempo_tpu.ops.pallas_merge import seq_kernel_form

    rng = np.random.default_rng(5)
    K, Ll, Lr, C = 3, 128, 128, 2
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, K, Ll, Lr, C,
                                                tie_heavy=True)
    base = 2.0**33
    r_seq = np.full((K, Lr), np.inf)
    for k in range(K):
        n = int((r_ts[k] < TS_PAD).sum())
        s = base + rng.integers(-(2**32), 2**32, n).astype(np.float64)
        s[rng.random(n) < 0.3] = -np.inf
        order = np.lexsort((s, r_ts[k, :n]))
        r_seq[k, :n] = s[order]
    sq = seq_kernel_form(jnp.asarray(r_seq))
    assert sq is not None and sq.dtype == jnp.int64
    want = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), r_seq=jnp.asarray(r_seq),
    )
    got = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), r_seq=sq, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), equal_nan=True
    )


@pytest.mark.parametrize("K,Ll,Lr,C,ties",
                         [(4, 128, 128, 2, False), (6, 256, 256, 2, True),
                          (3, 200, 136, 1, False)])
def test_skipnulls_false_matches_xla(K, Ll, Lr, C, ties):
    rng = np.random.default_rng(K * 77 + Lr + C)
    l_ts, r_ts, r_valids, r_values = _rand_case(rng, K, Ll, Lr, C, ties)
    want_v, want_f, want_i = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), skip_nulls=False,
    )
    got_v, got_f, got_i = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), skip_nulls=False, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_allclose(
        np.asarray(got_v), np.asarray(want_v), equal_nan=True
    )
    real = l_ts < TS_PAD
    np.testing.assert_array_equal(
        np.asarray(got_i)[real], np.asarray(want_i)[real]
    )


def test_skipnulls_false_seq_combined():
    """All round-4 kernel extensions at once: seq tie-break + lockstep
    skipNulls=False fill."""
    from tempo_tpu.ops.pallas_merge import seq_kernel_form

    rng = np.random.default_rng(11)
    l_ts, r_ts, r_valids, r_values, r_seq = _seq_case(rng, 5, 128, 128, 2)
    want = sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), r_seq=jnp.asarray(r_seq),
        skip_nulls=False,
    )
    got = asof_merge_values_pallas(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), r_seq=seq_kernel_form(jnp.asarray(r_seq)),
        skip_nulls=False, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(
        np.asarray(got[0]), np.asarray(want[0]), equal_nan=True
    )


def test_binpacked_skipnulls_false_matches_per_series_oracle():
    """Bin-packed layout + skipNulls=False through the segmented keyed
    fill (kernel) and the segmented pair fill (XLA), both vs the dense
    per-series oracle."""
    case = _binpacked_case(seed=9)
    (l_ts, r_ts, r_valids, r_values, llen, rlen, bp,
     lt2, rt2, lsid, rsid, rv2, rm2) = case
    C, S, _ = r_values.shape

    want_v, want_f, _ = (np.asarray(a) for a in sm._asof_merge_explicit(
        jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
        jnp.asarray(r_values), skip_nulls=False,
    ))
    for engine in ("pallas", "xla"):
        if engine == "pallas":
            got = asof_merge_values_pallas(
                jnp.asarray(lt2), jnp.asarray(rt2), jnp.asarray(rm2),
                jnp.asarray(rv2), jnp.asarray(lsid), jnp.asarray(rsid),
                skip_nulls=False, interpret=True,
            )
        else:
            got = sm._asof_merge_explicit(
                jnp.asarray(lt2), jnp.asarray(rt2), jnp.asarray(rm2),
                jnp.asarray(rv2), l_sid=jnp.asarray(lsid),
                r_sid=jnp.asarray(rsid), skip_nulls=False,
            )
        gv, gf = np.asarray(got[0]), np.asarray(got[1])
        for s in range(S):
            r0, o0 = bp.row[s], bp.l_off[s]
            sl = slice(o0, o0 + llen[s])
            np.testing.assert_array_equal(
                gf[:, r0, sl], want_f[:, s, : llen[s]],
                err_msg=f"{engine} s={s} found",
            )
            np.testing.assert_allclose(
                gv[:, r0, sl], want_v[:, s, : llen[s]], equal_nan=True,
                err_msg=f"{engine} s={s} vals",
            )


def test_binpacked_maxlookback_fenced():
    """maxLookback over bin-packed rows counts each series' own merged
    stream only (the sid fence): parity vs the dense per-series
    windowed form for several caps."""
    case = _binpacked_case(seed=21, S=17, Lmax=48)
    (l_ts, r_ts, r_valids, r_values, llen, rlen, bp,
     lt2, rt2, lsid, rsid, rv2, rm2) = case
    C, S, _ = r_values.shape
    for ml in (1, 3, 8):
        want_v, want_f, _ = (np.asarray(a) for a in
                             sm._asof_merge_explicit(
            jnp.asarray(l_ts), jnp.asarray(r_ts), jnp.asarray(r_valids),
            jnp.asarray(r_values), max_lookback=ml,
        ))
        got = sm._asof_merge_explicit(
            jnp.asarray(lt2), jnp.asarray(rt2), jnp.asarray(rm2),
            jnp.asarray(rv2), l_sid=jnp.asarray(lsid),
            r_sid=jnp.asarray(rsid), max_lookback=ml,
        )
        gv, gf = np.asarray(got[0]), np.asarray(got[1])
        for s in range(S):
            r0, o0 = bp.row[s], bp.l_off[s]
            sl = slice(o0, o0 + llen[s])
            np.testing.assert_array_equal(
                gf[:, r0, sl], want_f[:, s, : llen[s]],
                err_msg=f"ml={ml} s={s} found",
            )
            np.testing.assert_allclose(
                gv[:, r0, sl], want_v[:, s, : llen[s]], equal_nan=True,
                err_msg=f"ml={ml} s={s} vals",
            )
