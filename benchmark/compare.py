"""The comparison that decides ``correct``: readings against limits.

A cell's driver compares what its timed path produced with the float64
reference and turns the comparison into named readings.  Each reading
has its own limit, in ``limits/<cell>.json``, set from the readings of
sound runs and of the control (see PERF.md).  A run is correct when
every reading is a number at or under its limit.
"""

import json
import os

import numpy as np
import pandas as pd

HERE = os.path.dirname(os.path.abspath(__file__))


def _same(a: pd.Series, b: pd.Series) -> np.ndarray:
    """Element-wise equality with null equal to null."""
    x, y = a.to_numpy(), b.to_numpy()
    na, nb = pd.isna(a).to_numpy(), pd.isna(b).to_numpy()
    eq = np.zeros(len(x), dtype=bool)
    both = ~na & ~nb
    eq[both] = x[both] == y[both]
    return eq | (na & nb)


def frame_readings(got: pd.DataFrame, ref: pd.DataFrame, key_cols,
                   sort_cols, exact: dict, within: dict) -> dict:
    """Readings of one answer against its reference.

    ``rows_missing`` counts the rows either side has that the other
    lacks, by the values of ``key_cols`` (series and time); where it is
    not 0 the other readings are None.  ``sort_cols`` (the keys first)
    orders both frames the same way.  ``exact`` maps each column that
    must be equal to the reading that counts its differing rows;
    ``within`` maps each column held to a limit to the reading that
    takes its largest absolute difference.  ``null_mismatch`` counts
    rows of the ``within`` columns that are null on one side only."""
    names = sorted(set(exact.values()) | set(within.values()))
    keys = list(key_cols)
    count = lambda f: f.groupby(keys, dropna=False).size()
    diff = count(got).sub(count(ref), fill_value=0).abs()
    out = {"rows_missing": int(diff.sum())}
    if out["rows_missing"]:
        out.update({n: None for n in names + ["null_mismatch"]})
        return out
    order = list(sort_cols)
    a = got.sort_values(order, kind="mergesort").reset_index(drop=True)
    b = ref.sort_values(order, kind="mergesort").reset_index(drop=True)
    out.update({n: 0 for n in exact.values()})
    out.update({n: 0.0 for n in within.values()})
    out["null_mismatch"] = 0
    for col, name in exact.items():
        if col not in a.columns:
            out[name] = None
            continue
        out[name] += int((~_same(a[col], b[col])).sum())
    for col, name in within.items():
        if col not in a.columns:
            out[name] = None
            continue
        x = a[col].to_numpy(np.float64)
        y = b[col].to_numpy(np.float64)
        out["null_mismatch"] += int((np.isnan(x) != np.isnan(y)).sum())
        ok = ~np.isnan(x) & ~np.isnan(y)
        err = float(np.max(np.abs(x[ok] - y[ok]), initial=0.0))
        out[name] = max(out[name], err)
    return out


def worst(readings: list) -> dict:
    """The largest of each reading over several answers (None wins)."""
    out = {}
    for r in readings:
        for k, v in r.items():
            if k not in out:
                out[k] = v
            elif out[k] is not None:
                out[k] = None if v is None else max(out[k], v)
    return out


def load_limits(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def judge(readings: dict, limits: dict):
    """(correct, checks): ``checks`` maps each reading to its value and
    limit.  A reading with no limit, or a limit with no reading, makes
    the run not correct."""
    checks, ok = {}, True
    for name in sorted(set(readings) | set(limits)):
        value = readings.get(name)
        limit = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not value <= limit:
            ok = False
    return ok, checks
