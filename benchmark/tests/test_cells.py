"""Each cell driven end to end at a small size on the CPU.

The harness's look for a chip is skipped (a stand-in ``require``
reports a v5e); everything else is a run: data from the seed, set-up,
the window, the check against the float64 reference, the metric
readers.  A sound run is correct.  With the timed path broken
underneath it, the run is not correct, once for each fault a cell can
have: half of the answer left out, and an answer altered where it is
produced.  (A state left unchanged and the exchange between chips do
not exist in these one-chip cells.)  The control, the reference one
precision below the stated one in the program's place, is not correct
either.
"""

import contextlib
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import compare
import harness

ROOT = harness.ROOT
#: each configuration at a size the CPU runs in seconds: a minute of
#: recording a user, so the 10 s windows slide
SMALL_HHAR = {"phone_rows": 9 * 3000 + 4, "watch_rows": 9 * 800 + 7,
              "phone_rate_hz": 50}
SMALL = {"hhar_quickstart": SMALL_HHAR, "hhar_short": SMALL_HHAR}
CELLS = ["hhar.batch_join", "hhar.batch_chain"]
SEED = 2**31 + 12345


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _fake_v5e(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


def _cell(name, seconds=1.5):
    bench = _bench()
    config_name = {w["name"]: w["config"] for w in bench["workloads"]}[name]
    return harness.Cell(name, SEED, seconds, bench=bench,
                        config=SMALL[config_name])


def _run(name):
    return harness.run(_cell(name), time.perf_counter(), require=_fake_v5e,
                       log=lambda msg: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = _run(name)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]


def _half(df):
    return df.iloc[: len(df) // 2].reset_index(drop=True)


def _altered(df, cols):
    df = df.copy()
    col = next(c for c in cols if c in df.columns)
    i = int(np.flatnonzero(df[col].notna().to_numpy())[0])
    df.loc[i, col] = df.loc[i, col] + 1e-3
    return df


@contextlib.contextmanager
def _broken(name, fault):
    """Break the path the cell times, where its answer is produced."""
    from tempo_tpu import TSDF, join

    def fix(df):
        if fault == "half":
            return _half(df)
        return _altered(df, ["watch_accel_x", "x"])

    orig = join.asof_join

    def asof_join(*a, **k):
        out = orig(*a, **k)
        return TSDF(fix(out.df), out.ts_col, out.partitionCols)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(join, "asof_join", asof_join)
        yield


@pytest.mark.parametrize("fault", ["half", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(name, fault):
    with _broken(name, fault):
        line = _run(name)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = _cell(name)
    driver = harness.plugin("drivers", cell.traffic["driver"])
    gen = harness.plugin("data", cell.config["generator"])
    tables = gen.make(cell.config, cell.rng("data"))
    t = cell.config["tables"]
    readings = driver.control(cell, {"left": tables[t["left"]["name"]],
                                     "right": tables[t["right"]["name"]]})
    readings["unanswered"] = 0
    correct, checks = compare.judge(readings,
                                    compare.load_limits(cell.name))
    assert not correct, checks


def test_every_name_has_its_files():
    """The harness finds everything by name: each configuration, mix,
    driver, generator, reference op, metric reader and limits file that
    BENCHMARK.json implies is there."""
    bench = _bench()
    here = os.path.join(ROOT, "benchmark")
    for c in bench["configs"]:
        config = harness.load_json(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(here, "data",
                                           config["generator"] + ".py"))
        for key in c["reduced"]:
            assert key in config and key in config["reduced"]
    for w in bench["workloads"]:
        mix = harness.load_json(os.path.join(here, "traffic",
                                             w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(here, "drivers",
                                           mix["driver"] + ".py"))
        for step in mix["pipeline"]:
            assert os.path.exists(os.path.join(here, "reference",
                                               step["op"] + ".py"))
        assert os.path.exists(os.path.join(here, "limits",
                                           w["name"] + ".json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(here, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_no_tpu_exits_non_zero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    import device

    with pytest.raises(KeyError):
        device.peaks("TPU v99")
