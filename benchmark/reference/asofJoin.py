"""AS-OF join in pandas: ``merge_asof`` per partition key.

Each left row takes, in its own series, the right timestamp of the last
right row at or before its time, and for every other right column the
last non-null value at or before it (skipNulls).  Ties in time resolve
as a stable sort of the right side leaves them: the later row in the
right table's order wins.  The left timestamp keeps its name; every
other right column is prefixed.  Float values are rounded to the
``joined_values`` precision the reference is given (``precision.py``).
"""

import pandas as pd

from precision import round_to


def apply(left: pd.DataFrame, right: pd.DataFrame, args: dict,
          spec: dict) -> pd.DataFrame:
    ts, part = spec["ts"], spec["partition"]
    prefix = args.get("right_prefix", "right")
    lf = left.sort_values([ts], kind="mergesort")
    rf = right.sort_values([ts], kind="mergesort")
    out = pd.merge_asof(
        lf, rf[part + [ts]].assign(**{f"{prefix}_{ts}": rf[ts]}),
        on=ts, by=part, direction="backward")
    for col in [c for c in right.columns if c not in part + [ts]]:
        has = rf.dropna(subset=[col])[part + [ts, col]]
        out = pd.merge_asof(out, has.rename(columns={col: f"{prefix}_{col}"}),
                            on=ts, by=part, direction="backward")
    out = out.sort_values(part + [ts], kind="mergesort")
    out = out.reset_index(drop=True)
    want = spec["precision"].get("joined_values")
    if want:
        for col in out.columns:
            if col not in part and out[col].dtype.kind == "f":
                out[col] = round_to(out[col].to_numpy(), want)
    return out
