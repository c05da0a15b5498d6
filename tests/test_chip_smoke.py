"""chip_smoke.py on the CPU at a tiny size: every phase and its checks
run (the float64 reference, planned == eager bitwise through the query
service, the engine report), and ``main()`` refuses to run anywhere but
on a TPU."""

import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def probe(monkeypatch):
    fresh = chip_smoke.Probe().install(monkeypatch.setattr)
    monkeypatch.setattr(chip_smoke, "PROBE", fresh)
    return fresh


def test_one_chip_phases_check_out_at_tiny_size(probe, capsys):
    chip_smoke.one_chip(seed=3, rows=4096, series=8)
    out = capsys.readouterr().out
    for label in ("eager chain", "mesh chain", "interpolate",
                  "resampleEMA"):
        assert f"[{label}] reference check passed" in out, label
    assert "[service] answered {'chain': 8, 'sql_join': 1, " \
           "'sql_filter': 1} bitwise equal to eager" in out
    # the engine report: what the join and range-stats dispatchers took
    picks = {what for what, _ in probe.picks}
    assert {"join", "range engine (host frame)"} <= picks, picks
    assert "warm pass" in out and probe.compiles > 0


def test_reference_check_catches_a_wrong_join():
    left, right = chip_smoke.make_data(1, 2048, 4)
    keys = chip_smoke.sample_keys(4, 1, k=2)
    out = chip_smoke.eager_phase(left, right)["chain"]
    ref = chip_smoke.reference(left.df, right.df, keys)
    chip_smoke.check_against_reference(out, ref, keys, "ok")
    bad = out.copy()
    bad["right_wx"] = bad["right_wx"] + 1.0
    with pytest.raises(AssertionError, match="right_wx"):
        chip_smoke.check_against_reference(bad, ref, keys, "bad")


def _live_device_bytes():
    """Bytes of the live arrays' shards on each device: what
    ``memory_stats()`` reports on a TPU, which the CPU does not."""
    held = {d: 0 for d in jax.devices()}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] for d in jax.devices()]


def test_four_chip_phase_checks_out_on_virtual_devices(probe, monkeypatch,
                                                       capsys):
    monkeypatch.setattr(chip_smoke, "device_bytes", _live_device_bytes)
    chip_smoke.four_chips(seed=5, rows=2048, series=8)
    out = capsys.readouterr().out
    for label in ("1 chip", "series=4", "series=2,time=2"):
        assert f"[mesh {label}] planned == eager bitwise" in out, label
        assert f"[mesh {label}] reference check passed" in out, label
    assert "[mesh series=4] vs one chip: bitwise equal but []" in out


def _one_chip_frame():
    left, right = chip_smoke.make_data(2, 1024, 4)
    df = chip_smoke.eager_phase(left, right)["chain"]
    return df.sort_values(["user", "event_ts"],
                          kind="mergesort").reset_index(drop=True)


@pytest.mark.parametrize("column,time_axis,allowed", [
    ("mean_x", None, False),
    ("EMA_x", None, False),
    ("right_wx", "time", False),
    ("stddev_x", "time", False),
    ("EMA_x", "time", True),
])
def test_one_chip_comparison_allows_only_time_split_columns(
        column, time_axis, allowed):
    one = _one_chip_frame()
    shifted = one.copy()
    col = shifted[column].to_numpy(copy=True)
    i = int(np.flatnonzero(~np.isnan(col))[0])
    col[i] = np.nextafter(col[i], np.inf)
    shifted[column] = col
    chip_smoke.compare_with_one_chip(one, one, "same", time_axis)
    if allowed:
        chip_smoke.compare_with_one_chip(shifted, one, "shifted", time_axis)
    else:
        with pytest.raises(AssertionError, match=column):
            chip_smoke.compare_with_one_chip(shifted, one, "shifted",
                                             time_axis)


def test_main_refuses_a_cpu_and_names_it(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
