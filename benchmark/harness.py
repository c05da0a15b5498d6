"""Runs one cell once.  Everything a cell needs is found by its name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and
lists the metrics.  From those names the harness loads:

* ``configs/<config>.json``: the deployment's sizes and guarantees
  (the file the configuration's entry names);
* ``traffic/<mix>.json``: the mix's parameters; its ``driver`` names
  the general generator that runs it, ``drivers/<driver>.py``;
* ``data/<generator>.py``: the configuration's ``generator``, which
  makes the tables from the seed;
* ``reference/<op>.py``: the float64 reference of each op a mix names;
* ``metrics/<metric>.py``: one reader per metric, end-to-end or
  per-layer, which returns a number or None (nothing to read);
* ``limits/<cell>.json``: the limit of each reading the comparison makes.

A new cell, mix, configuration or metric is therefore new files and new
entries, and no edit of a file that is here.
"""

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
import zlib

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WINDOW = "bench.window"

for _p in (ROOT, HERE, os.path.join(HERE, "reference")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """The module ``<kind>/<name>.py`` under the benchmark directory."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"benchmark: no {kind} named {name!r} "
                                f"({path})")
    key = f"bench_{kind}_{name}".replace(".", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One cell of ``BENCHMARK.json`` with its configuration, mix and
    run settings.  ``config`` replaces the configuration file's sizes
    (the tests run cells at small sizes this way)."""

    def __init__(self, name: str, seed: int, seconds: float,
                 traced: bool = False, bench: dict = None,
                 config: dict = None):
        self.bench = bench or load_json(os.path.join(ROOT,
                                                     "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"benchmark: no cell named {name!r} (cells: "
                           f"{sorted(cells)})")
        self.name = name
        self.workload = cells[name]
        entry = {c["name"]: c for c in self.bench["configs"]}[
            self.workload["config"]]
        self.config = dict(load_json(os.path.join(ROOT, entry["file"])))
        self.config.update(config or {})
        self.traffic = load_json(os.path.join(
            HERE, "traffic", f"{self.workload['traffic']}.json"))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.traced = bool(traced)

    def rng(self, label: str) -> np.random.Generator:
        """A generator drawn from the seed and ``label``: the same seed
        gives the same draws, whatever else the run does."""
        return np.random.default_rng(
            [self.seed % (1 << 63), zlib.crc32(label.encode())])

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


@dataclasses.dataclass
class Context:
    """What a metric's reader reads."""
    cell: Cell
    peaks: dict
    setup_s: float
    records: list          # one dict per pipeline
    spans: object          # spans.Spans
    window_built: int      # programs compiled or loaded in the window
    work: dict             # per-kernel work of one call (work.py)
    trace: object = None   # trace_reduce.TraceSummary, traced runs only


def _profile(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def run(cell: Cell, t_start: float, require=None, log=None) -> dict:
    """Set up, measure, check and read the metrics of one run; returns
    the result line's object (``checks`` last)."""
    import jax

    import compare
    import device
    import trace_reduce
    from probe import Probe
    from spans import Spans

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    dev = (require or device.require)(int(cell.workload["chips"]))
    peaks = device.peaks(dev["kind"])
    driver = plugin("drivers", cell.traffic["driver"])
    spans = Spans(traced=cell.traced)
    summary = None
    with Probe() as probe:
        state = driver.setup(cell, spans, log)
        setup_s = time.perf_counter() - t_start
        log(f"set-up: {setup_s} s, {probe.compiles} backend compiles "
            f"({probe.compile_s} s), {probe.cache_loads} cache loads")
        built = probe.built()
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            if cell.traced:
                _profile(tdir)
            try:
                with spans(WINDOW):
                    records = driver.window(state, cell.seconds, spans, log)
            finally:
                if cell.traced:
                    jax.profiler.stop_trace()
            window_built = probe.built() - built
            if cell.traced:
                summary = trace_reduce.reduce_file(
                    trace_reduce.find_xplane(tdir))
                top = sorted(summary.module_s.items(), key=lambda kv: -kv[1])
                log(f"trace: {summary.devices} device(s), busy "
                    f"{summary.busy_s} s of {summary.window_s} s; programs "
                    f"by device time: {top[:25]}")
    dev["memory_peak_bytes"] = device.memory_peak_bytes(
        int(cell.workload["chips"]))
    work = driver.work(state)
    answers = driver.release(state)
    del state
    t_check = time.perf_counter()
    readings = driver.check(cell, answers, log)
    correct, checks = compare.judge(readings, compare.load_limits(cell.name))
    log(f"check: {time.perf_counter() - t_check} s")
    ctx = Context(cell=cell, peaks=peaks, setup_s=setup_s,
                  records=records, spans=spans, window_built=window_built,
                  work=work, trace=summary)
    metrics = {}
    for m in (cell.per_layer() if cell.traced else cell.end_to_end()):
        value = plugin("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": len(records),
            "failed": sum(1 for r in records if not r["ok"]),
            "metrics": metrics, "device": dev}
    if summary is not None:
        if summary.devices:
            dev["busy_s"] = summary.busy_s
            dev["window_s"] = summary.window_s
        line["breakdown"] = trace_reduce.breakdown(summary)
    line["checks"] = checks
    return line
