"""Closed loop: one client runs the mix's pipeline back to back.

Each pipeline is what a user of the frame API runs: ``TSDF`` built from
the configuration's two pandas tables, the mix's ``pipeline`` of frame
methods in order (a step with ``"right": true`` takes the right table's
frame as its first argument), ending in ``.df``.  The harness records a
span around each of those calls.  The window launches pipelines until
``seconds`` have passed, at least one, and lets the last one finish.

The check compares the answers of the first pipeline, of one drawn from
the seed among the next two, and of the last, on a sample of series
drawn from the seed (the busiest series always among them), with the
float64 reference of the same pipeline.  Every op of a mix keeps one
row per left row, so each answer's row count is checked whole too.
"""

import gc
import time
import traceback

import compare
import harness
import precision
import work as work_model


def _tables(cell):
    t = cell.config["tables"]
    return t["left"], t["right"]


def setup(cell, spans, log):
    from tempo_tpu import TSDF

    gen = harness.plugin("data", cell.config["generator"])
    with spans("bench.data"):
        tables = gen.make(cell.config, cell.rng("data"))
    left_t, right_t = _tables(cell)
    state = {"cell": cell, "TSDF": TSDF, "left": tables[left_t["name"]],
             "right": tables[right_t["name"]], "kept": {}}
    log(f"data: {len(state['left'])} left rows, {len(state['right'])} "
        f"right rows")
    with spans("bench.warm"):
        _pipeline(state, lambda name: _null())
    return state


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _pipeline(state, spans):
    cell = state["cell"]
    left_t, right_t = _tables(cell)
    TSDF = state["TSDF"]
    with spans("bench.frame"):
        cur = TSDF(state["left"], left_t["ts"], left_t["partition"])
        right = TSDF(state["right"], right_t["ts"], right_t["partition"])
    for step in cell.traffic["pipeline"]:
        with spans(f"bench.{step['op']}"):
            call = getattr(cur, step["op"])
            args = step.get("args", {})
            cur = call(right, **args) if step.get("right") else call(**args)
    with spans("bench.df"):
        return cur.df


def window(state, seconds, spans, log):
    cell = state["cell"]
    also = 1 + int(cell.rng("sampled pipeline").integers(0, 2))
    records, kept, last = [], state["kept"], None
    t0 = time.perf_counter()
    while not records or time.perf_counter() - t0 < seconds:
        i = len(records)
        start = time.perf_counter()
        try:
            out = _pipeline(state, spans)
        except Exception:  # noqa: BLE001 - a failed pipeline is counted
            log(f"pipeline {i} failed:\n{traceback.format_exc()}")
            records.append({"start": start, "end": time.perf_counter(),
                            "rows": 0, "ok": False})
            continue
        records.append({"start": start, "end": time.perf_counter(),
                        "rows": len(state["left"]), "ok": True})
        if i in (0, also):
            kept[i] = out
        last = (i, out)
        del out
    if last is not None:
        kept[last[0]] = last[1]
    state["unanswered"] = sum(1 for r in records if not r["ok"])
    log(f"window: {len(records)} pipelines in "
        f"{time.perf_counter() - t0} s; checking pipelines {sorted(kept)}")
    return records


def work(state) -> dict:
    """The join's work for one pipeline (the first step of the mix is
    the join in every closed-loop mix)."""
    _, right_t = _tables(state["cell"])
    right_cols = [c for c in state["right"].columns
                  if c not in right_t["partition"]]
    return {"asof_join": work_model.asof_join(
        len(state["left"]), len(state["right"]), len(right_cols))}


def release(state) -> dict:
    """Drop the program's frames; keep the answers and the input tables
    the reference needs."""
    answers = {"outputs": list(state["kept"].values()),
               "left": state["left"], "right": state["right"],
               "unanswered": state["unanswered"]}
    state.clear()
    gc.collect()
    return answers


def sample_keys(cell, keys):
    """Sampled series: the busiest (the data's first key) and the rest
    drawn from the seed."""
    import numpy as np

    n = int(cell.traffic["check"]["sample_series"])
    rest = cell.rng("sample series").choice(
        np.arange(1, len(keys)), size=min(n, len(keys)) - 1, replace=False)
    return keys[np.concatenate([[0], np.sort(rest)])]


def reference(cell, left, right, keys, prec: dict):
    """The mix's pipeline in float64 pandas over the sampled series,
    with the given precisions; returns (output, the join's columns)."""
    left_t, right_t = _tables(cell)
    part = left_t["partition"]
    lf = left[left[part[0]].isin(keys)]
    rf = right[right[part[0]].isin(keys)]
    spec = {"ts": left_t["ts"], "partition": part, "precision": prec}
    out, join_cols = lf, None
    for step in cell.traffic["pipeline"]:
        ref = harness.plugin("reference", step["op"])
        out = ref.apply(out, rf if step.get("right") else None,
                        step.get("args", {}), spec)
        if join_cols is None:
            join_cols = list(out.columns)
    return out, join_cols


def readings(cell, outputs, ref, join_cols, keys, n_rows: int) -> dict:
    left_t, _ = _tables(cell)
    part = left_t["partition"]
    sort_cols = part + [left_t["ts"]] + [
        c for c in join_cols if c not in part + [left_t["ts"]]]
    exact = {c: "joined_mismatch" for c in join_cols}
    within = {c: "err_" + c.split("_")[0].lower()
              for c in ref.columns if c not in join_cols}
    found = []
    for o in outputs:
        r = compare.frame_readings(o[o[part[0]].isin(keys)], ref,
                                   part + [left_t["ts"]], sort_cols, exact,
                                   within)
        r["rows_missing"] += abs(len(o) - n_rows)
        found.append(r)
    return compare.worst(found)


def check(cell, answers, log) -> dict:
    if not answers["outputs"]:
        return {"rows_missing": None, "unanswered": answers["unanswered"]}
    gen = harness.plugin("data", cell.config["generator"])
    keys = sample_keys(cell, gen.series_keys(cell.config))
    stated = cell.config["guarantees"]["precision"]
    ref, join_cols = reference(cell, answers["left"], answers["right"], keys,
                               precision.reference(stated))
    found = readings(cell, answers["outputs"], ref, join_cols, keys,
                     len(answers["left"]))
    return {**found, "unanswered": answers["unanswered"]}


def control(cell, answers) -> dict:
    """The control's readings: the reference computed one precision
    below the stated ones, put in the program's place."""
    gen = harness.plugin("data", cell.config["generator"])
    keys = sample_keys(cell, gen.series_keys(cell.config))
    stated = cell.config["guarantees"]["precision"]
    ref, join_cols = reference(cell, answers["left"], answers["right"], keys,
                               precision.reference(stated))
    low, _ = reference(cell, answers["left"], answers["right"], keys,
                       precision.control(stated))
    return readings(cell, [low], ref, join_cols, keys, len(low))
