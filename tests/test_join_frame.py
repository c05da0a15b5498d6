"""The AS-OF join assembles its output frame from the left columns as
Series and the gathered right columns, with ``copy=False``.

Oracle: the same join with the former assembly, one ``to_numpy()``
array a left column handed to ``pd.DataFrame`` (re-inferred and
consolidated).  The frames are equal bitwise, with the same column
order, dtypes, string storage and index, on every path of the join.
Where the former assembly re-inferred a left column's dtype (object,
categorical, nullable and Python-storage string columns), the joined
frame now keeps the input's dtype, pinned here."""

import numpy as np
import pandas as pd
import pytest

from tempo_tpu import TSDF
from tempo_tpu import join as join_mod
from tempo_tpu.ops import pallas_merge as pm

CHUNK = 256  # merged lanes per chunk: the chunked case crosses chunk edges


class _FormerAssembly:
    """pandas, except that the join's output frame is built as it was:
    ``pd.DataFrame`` of one numpy array a left column."""

    def __getattr__(self, name):
        return getattr(pd, name)

    @staticmethod
    def DataFrame(data=None, *args, copy=None, **kw):
        if copy is False:
            data = {k: v.to_numpy() if isinstance(v, pd.Series) else v
                    for k, v in data.items()}
            return pd.DataFrame(data)
        return pd.DataFrame(data, *args, copy=copy, **kw)


def _side(rng, counts, ts_range_s, sort=True):
    users = np.repeat(np.array(list(counts)), list(counts.values()))
    ts = pd.Timestamp("2024-01-01") + pd.to_timedelta(
        rng.integers(0, ts_range_s * 1000, len(users)), unit="ms")
    df = pd.DataFrame({"User": users, "event_ts": ts})
    if sort:
        return df.sort_values(["User", "event_ts"], kind="stable",
                              ignore_index=True)
    return df.sample(frac=1, random_state=3).reset_index(drop=True)


def _frames(seed, sort_left=True, n_right=None, user_dtype=None):
    rng = np.random.default_rng(seed)
    left = _side(rng, {"a": 300, "b": 37, "c": 410, "f": 25}, 400,
                 sort=sort_left)
    left["x"] = rng.standard_normal(len(left))
    left["tag"] = np.where(rng.random(len(left)) > 0.2, "on", None)
    counts = {"a": 200, "b": 90, "c": 333, "e": 40}
    right = _side(rng, counts, 400)
    if n_right is not None:
        right = right.iloc[:n_right].reset_index(drop=True)
    right["seq"] = rng.integers(0, 5, len(right)).astype(np.float64)
    right["v"] = np.where(rng.random(len(right)) > 0.3,
                          rng.standard_normal(len(right)), np.nan)
    right["w"] = rng.standard_normal(len(right))
    if user_dtype is not None:
        left["User"] = left["User"].astype(user_dtype)
        right["User"] = right["User"].astype(user_dtype)
    return left, right


# (id, frames kwargs, env, join kwargs, right sequence col)
CASES = [
    ("dense", {}, {"TEMPO_TPU_BINPACK": "0"}, {}, None),
    ("chunked", {}, {"TEMPO_TPU_BINPACK": "0",
                     "TEMPO_TPU_JOIN_ENGINE": "chunked",
                     "TEMPO_TPU_JOIN_CHUNK_LANES": str(CHUNK)}, {}, None),
    ("binpacked", {}, {"TEMPO_TPU_BINPACK": "1"}, {}, None),
    ("broadcast", {}, {}, {"sql_join_opt": True}, None),
    ("skew", {}, {}, {"tsPartitionVal": 60, "fraction": 0.5}, None),
    ("keep_nulls", {}, {}, {"skipNulls": False}, None),
    ("sequence", {}, {}, {}, "seq"),
    ("empty_right", {"n_right": 0}, {}, {}, None),
    ("shuffled_left", {"sort_left": False}, {}, {}, None),
    ("object_keys", {"user_dtype": object}, {}, {}, None),
    ("categorical_keys", {"user_dtype": "category"}, {}, {}, None),
]

def _join(left, right, env_kw, seq, monkeypatch):
    for k, v in env_kw[0].items():
        monkeypatch.setenv(k, v)
    return TSDF(left, "event_ts", ["User"]).asofJoin(
        TSDF(right, "event_ts", ["User"], sequence_col=seq),
        right_prefix="watch", **env_kw[1]).df


@pytest.mark.parametrize("case,frames_kw,env,kw,seq", CASES,
                         ids=[c[0] for c in CASES])
def test_frame_equals_former_assembly(monkeypatch, case, frames_kw, env,
                                      kw, seq):
    left, right = _frames(11, **frames_kw)
    monkeypatch.delenv("TEMPO_TPU_JOIN_ENGINE", raising=False)
    ran = {"chunked": 0}
    real_chunked = pm.asof_merge_indices_chunked

    def chunked_spy(*a, **k):
        ran["chunked"] += 1
        return real_chunked(*a, **k)

    monkeypatch.setattr(pm, "asof_merge_indices_chunked", chunked_spy)
    got = _join(left, right, (env, kw), seq, monkeypatch)
    monkeypatch.setattr(join_mod, "pd", _FormerAssembly())
    want = _join(left, right, (env, kw), seq, monkeypatch)

    assert ran["chunked"] == (2 if case == "chunked" else 0)
    assert list(got.columns) == list(want.columns)
    assert isinstance(got.index, pd.RangeIndex)
    if "user_dtype" in frames_kw:   # the former assembly made them str
        assert got["User"].dtype == left["User"].dtype
        assert want["User"].dtype == "str"
        want = want.astype({"User": left["User"].dtype})
    for c in got.columns:
        assert type(got[c].array) is type(want[c].array), c
    pd.testing.assert_frame_equal(got, want, check_exact=True)


def test_left_dtypes_are_kept(monkeypatch):
    """Columns the former assembly re-inferred through numpy keep the
    input's dtype; its results are pinned beside them."""
    left, right = _frames(12)
    n = len(left)
    left["obj"] = pd.Series(np.where(np.arange(n) % 2, "p", "q"),
                            dtype=object)
    left["cat"] = pd.Categorical(np.where(np.arange(n) % 3, "u", "w"))
    left["i64"] = pd.array(np.where(np.arange(n) % 2, 1, None),
                           dtype="Int64")
    left["sp"] = pd.array(np.where(np.arange(n) % 2, "s", None),
                          dtype="string[python]")
    left["tz"] = left["event_ts"].dt.tz_localize("UTC")
    args = (left, right, ({}, {}), None, monkeypatch)
    got = _join(*args)
    monkeypatch.setattr(join_mod, "pd", _FormerAssembly())
    want = _join(*args)
    former = {"obj": "str", "cat": "str", "i64": "float64", "sp": "str"}
    for c, dt in former.items():
        assert got[c].dtype == left[c].dtype, c
        assert want[c].dtype == dt, c
    assert got["tz"].dtype == want["tz"].dtype == left["tz"].dtype
    pd.testing.assert_frame_equal(got.astype(want.dtypes), want,
                                  check_exact=True)


@pytest.mark.parametrize("sort_left", [True, False],
                         ids=["presorted", "shuffled"])
def test_result_and_input_stay_isolated(sort_left):
    """Copy-on-write keeps the caller's frame and the joined frame apart,
    also where the left side is only wrapped: a write to either side's
    partition or value column does not show through in the other."""
    left, right = _frames(13, sort_left=sort_left)
    before = left.copy(deep=True)
    out = TSDF(left, "event_ts", ["User"]).asofJoin(
        TSDF(right, "event_ts", ["User"]), right_prefix="watch").df
    joined = out.copy(deep=True)

    row = int(np.flatnonzero(out["User"] == left.loc[0, "User"])[0])
    left.loc[0, "User"] = "zz"
    left.loc[0, "x"] = 1e9
    left.loc[:, "tag"] = "off"
    pd.testing.assert_frame_equal(out, joined, check_exact=True)

    out.loc[row, "User"] = "yy"
    out.loc[:, "x"] = -1.0
    out.loc[row, "event_ts"] = pd.Timestamp("2000-01-01")
    before.loc[0, "User"] = "zz"
    before.loc[0, "x"] = 1e9
    before.loc[:, "tag"] = "off"
    pd.testing.assert_frame_equal(left, before, check_exact=True)
