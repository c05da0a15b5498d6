"""``hhar.full_chain`` driven end to end at ``test_cells``' small size on
the CPU, with the lane-chunked range stats and the carry-passing EMA
forced (small chunks; the Pallas kernel in interpret mode): a sound run
is correct, and a run with half of an answer dropped, or one answer
altered, is not.  Also the work counts of ``work_stats.py`` and the
three readers it feeds, on synthetic contexts.
"""

import types

import pytest

import harness
import test_cells
import work_stats

CELL = "hhar.full_chain"


@pytest.fixture(autouse=True)
def small_config(monkeypatch):
    """The cell's configuration at ``test_cells``' small size."""
    monkeypatch.setitem(test_cells.SMALL, "hhar_full_chain",
                        test_cells.SMALL_HHAR)


@pytest.fixture
def chunked(monkeypatch):
    """Force the chunked forms at the small size; count their calls."""
    from tempo_tpu import rolling
    from tempo_tpu.ops import pallas_kernels as pk
    from tempo_tpu.ops import rolling as rk
    from tempo_tpu.ops import sortmerge as sm

    monkeypatch.setattr(sm, "use_sort_kernels", lambda: True)
    monkeypatch.setattr(rk, "SHIFTED_MAX_ROWS", 0)
    monkeypatch.setattr(rk, "RANGE_BLOCK_LANES", 256)
    monkeypatch.setattr(rk, "RANGE_BLOCK_HALOS", 1.5)
    monkeypatch.setattr(pk, "EMA_CHUNK_LANES", 256)
    monkeypatch.setattr(pk, "EMA_CALL_CHUNKS", 2)
    monkeypatch.setattr(pk, "ema_chunked_ok", lambda x: True)
    calls = {"range": 0, "ema": 0}

    def counted(key, fn):
        def run(*a, **k):
            calls[key] += 1
            return fn(*a, **k)
        return run
    monkeypatch.setattr(rolling, "_range_stats_chunked",
                        counted("range", rolling._range_stats_chunked))
    monkeypatch.setattr(pk, "ema_chunked", counted("ema", pk.ema_chunked))
    return calls


def test_sound_run_is_correct(chunked):
    line = test_cells._run(CELL)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert chunked["range"] > 0 and chunked["ema"] > 0


@pytest.mark.parametrize("fault", ["half", "altered"])
def test_broken_path_is_not_correct(chunked, fault):
    with test_cells._broken(CELL, fault):
        line = test_cells._run(CELL)
    assert not line["correct"], line["checks"]


def test_work_counts_by_hand():
    # 10 rows, 2 columns: seconds 4 B a row; per column value 4 B and
    # validity 1 B read, 7 stats of 4 B written
    assert work_stats.range_stats(10, 2) == {
        "bytes": 10 * 4 + 10 * 2 * (4 + 1) + 10 * 2 * 7 * 4,
        "ops": 10 * 2 * 7}
    assert work_stats.ema(10) == {"bytes": 10 * (4 + 1 + 4), "ops": 20}
    traffic = harness.load_json(
        f"{harness.HERE}/traffic/batch_chain.json")
    assert work_stats.stats_columns(traffic) == 1
    assert work_stats.exact_emas(traffic) == 1
    join_only = harness.load_json(
        f"{harness.HERE}/traffic/batch_join.json")
    assert work_stats.stats_columns(join_only) is None
    assert work_stats.exact_emas(join_only) == 0


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


class _Trace:
    devices = 1

    def __init__(self, module_s):
        self.module_s = module_s

    def time_matching(self, patterns):
        return sum(s for n, s in self.module_s.items()
                   if any(p in n for p in patterns))


def _ctx(trace=None, traffic="batch_chain"):
    cell = types.SimpleNamespace(traffic=harness.load_json(
        f"{harness.HERE}/traffic/{traffic}.json"))
    records = [{"ok": True, "rows": 1000}, {"ok": True, "rows": 1000},
               {"ok": False, "rows": 0}]
    window = [(harness.WINDOW, 0.0, 10.0)]
    return types.SimpleNamespace(
        cell=cell, peaks=PEAKS, records=records, trace=trace,
        spans=types.SimpleNamespace(items=window))


def _roofline(name):
    return harness.plugin("metrics", name)


@pytest.mark.parametrize("name,program,work", [
    ("range_stats_roofline.batch", "jit_range_stats_chunk",
     lambda: work_stats.range_stats(1000, 1)),
    ("range_stats_roofline.batch", "jit_windowed_stats",
     lambda: work_stats.range_stats(1000, 1)),
    ("ema_roofline.batch", "jit__ema_chunk_call",
     lambda: work_stats.ema(1000)),
    ("ema_roofline.batch", "jit_ema_exact", lambda: work_stats.ema(1000)),
])
def test_roofline_readers(name, program, work):
    import work as work_model

    read = _roofline(name).read
    assert read(_ctx()) is None                         # no trace
    assert read(_ctx(_Trace({"jit_other": 1.0}))) is None
    assert read(_ctx(_Trace({program: 1.0}),
                     traffic="batch_join")) is None      # no such op
    value = read(_ctx(_Trace({program: 0.5, "jit_other": 9.0})))
    least, bound = work_model.least_seconds(work(), PEAKS)
    assert bound == "memory"
    assert value == pytest.approx(100.0 * 2 * least / 0.5)


def _span(i, parent, root, name, rows, start=1.0):
    from tempo_tpu.profiling import SpanRecord

    return SpanRecord(i, parent, root, name, int(start * 1e9),
                      int(start * 1e9) + 1000, rows)


def test_lanes_per_row_reader(monkeypatch):
    from tempo_tpu import profiling

    read = harness.plugin("metrics", "stats_lanes_per_row.batch").read
    held = []
    monkeypatch.setattr(profiling, "recent_spans", lambda: (held, 0))
    assert read(_ctx()) is None                         # no spans
    held[:] = [
        # a parent without the nested spans: nothing to read
        _span(1, None, 1, "tempo.withRangeStats", 1000),
        _span(2, 1, 1, "tempo.dispatch", 7000),
        _span(3, None, 3, "tempo.asofJoin", 1000),
        _span(4, 3, 3, "tempo.dispatch", 5000),
    ]
    assert read(_ctx()) is None
    held += [
        _span(5, 2, 1, "tempo.dispatch", 1300),
        _span(6, None, 6, "tempo.EMA", 1000),
        _span(7, 6, 6, "tempo.dispatch", 1000),
        _span(8, 7, 6, "tempo.dispatch", 1100),
        # a span that starts before the window is not counted
        _span(9, 7, 6, "tempo.dispatch", 10 ** 6, start=-1.0),
    ]
    assert read(_ctx()) == pytest.approx((1300 + 1100) / 2000)


WATCHED_SETUP = """
import sys
sys.path[:0] = [{bench!r}, {tests!r}]
import harness, test_cells
from spans import Spans
test_cells.SMALL["hhar_full_chain"] = test_cells.SMALL_HHAR
cell = test_cells._cell("hhar.full_chain")
cell.traffic["setup_bounds"] = {{"deadline_s": {deadline},
                                "rss_ceiling_bytes": {ceiling}}}
driver = harness.plugin("drivers", cell.traffic["driver"])
driver.setup(cell, Spans(), lambda msg: print(msg, file=sys.stderr))
print("set-up finished")
"""


@pytest.mark.parametrize("deadline,ceiling,said", [
    (0.5, 3e10, "passed its deadline"),
    (600, 1e8, "passed its host-memory ceiling"),
    (600, 3e10, None),
])
def test_bounded_setup_fails_soon_and_says_why(deadline, ceiling, said):
    import os
    import subprocess
    import sys

    code = WATCHED_SETUP.format(
        bench=harness.HERE, tests=os.path.dirname(__file__),
        deadline=deadline, ceiling=ceiling)
    proc = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=600)
    if said is None:
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "set-up finished" in proc.stdout
        assert "set-up peak host RSS" in proc.stderr
    else:
        assert proc.returncode == 1
        assert said in proc.stderr
        assert "set-up finished" not in proc.stdout
