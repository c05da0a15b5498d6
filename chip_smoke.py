"""Smoke run of tempo-tpu on a TPU at the HHAR quickstart scale.

    python chip_smoke.py              # one chip: every one-chip phase
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

One process, no subprocesses.  It drives the main path once through the
entry points a user calls, on seeded HHAR-shaped data (13,062,144 rows a
side: the reference quickstart's 13,062,475 rounded down to 1024 equal
series):

* an eager ``asofJoin -> withRangeStats -> EMA`` chain, a floor
  ``resample -> interpolate`` and a fused ``resampleEMA``, then the same
  join chain through ``TSDF.on_mesh`` and ``collect()``;
* a ``QueryService`` with two tenants answering planned chains and SQL,
  each result bitwise equal to the eager one;
* a float64 pandas/numpy reference on a seeded sample of series.

Every phase runs twice, cold and warm.  The wall times it prints are
smoke timings, not benchmark metrics.  It prints the engine each op
took, whether each lowered program holds a Mosaic kernel
(``tpu_custom_call``), and the compile count of each pass.  It fails if
JAX finds no TPU, if any Pallas kernel ran in interpret mode, or if any
phase fails.  The last line of its output is one JSON object naming the
device.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import sys
import tempfile
import time
from collections import Counter

import numpy as np
import pandas as pd

import tempo_tpu  # noqa: F401  (x64 mode and the compile cache first)
import jax
from jax.experimental import pallas as pl

from tempo_tpu import TSDF, dist, join, native, packing, profiling
from tempo_tpu import resilience
from tempo_tpu import rolling as frame_rolling
from tempo_tpu.parallel import make_mesh
from tempo_tpu.service import QueryService, lazy_frame
from tempo_tpu.testing.hhar import HHAR_ROWS, HHAR_SERIES, make_frames
#: absolute f32-vs-f64 error bounds per result, the ones
#: tests/test_f32_numerics.py holds the TPU compute policy to
from tempo_tpu.testing.numerics import F32_BOUNDS

WINDOW_SECS = 10
SAMPLE_SERIES = 32
TENANTS = ("tenant-a", "tenant-b")
CHAIN_REQUESTS = 8
SQL_JOIN = "SELECT * FROM phone ASOF JOIN watch PREFIX 'right'"
SQL_FILTER = SQL_JOIN + " WHERE x > 0"


def log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------------
# device check
# ----------------------------------------------------------------------

def require_tpu(chips: int) -> dict:
    """The device JAX reports, or SystemExit when it is not a TPU with
    at least ``chips`` devices.  JAX falls back to the CPU by itself when
    its TPU backend cannot start, so this check is the only guard."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: JAX found platform {platform!r} "
            f"({len(devices)} device(s)); this smoke never runs elsewhere")
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPU devices, JAX "
            f"found {len(devices)}")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"device: {device['kind']} x{device['count']} ({platform})")
    return device


# ----------------------------------------------------------------------
# instrumentation: compiles, kernels, engine picks, lowered programs
# ----------------------------------------------------------------------

class Probe:
    """Counts what the program does while a phase runs: backend
    compiles and persistent-cache hits (JAX monitoring events), every
    ``pallas_call`` with its interpret flag, and the engine picks of the
    join and range-stats dispatchers.  Install once per process."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    HIT_EVENT = "/jax/compilation_cache/cache_hits"
    MISS_EVENT = "/jax/compilation_cache/cache_misses"
    SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
    TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
    LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.kernels = []      # (name, interpret)
        self.picks = []        # (what, detail)
        #: (program, backend compile s, compile s a cache hit saved)
        self.compile_log = []
        self.trace_log = []    # (function, trace or lowering s)
        self._saved = None
        self._installed = False

    def install(self, setattr=setattr) -> "Probe":
        """Wrap the recorded entry points (``setattr`` lets a test
        restore them afterwards)."""
        if self._installed:
            return self
        self._installed = True

        def on_duration(event, duration, **kwargs):
            if event == self.SAVED_EVENT:
                self._saved = duration
            elif event == self.COMPILE_EVENT:
                self.compiles += 1
                self.compile_log.append((kwargs.get("fun_name", "?"),
                                         duration, self._saved))
                self._saved = None
            elif event in (self.TRACE_EVENT, self.LOWER_EVENT):
                self.trace_log.append((kwargs.get("fun_name", "?"),
                                       duration))

        def on_event(event, **kwargs):
            if event == self.HIT_EVENT:
                self.cache_hits += 1
            elif event == self.MISS_EVENT:
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

        pallas_call = pl.pallas_call

        def recording_pallas_call(kernel, *args, **kwargs):
            name = kwargs.get("name") or getattr(kernel, "__name__",
                                                 type(kernel).__name__)
            self.kernels.append((name, bool(kwargs.get("interpret", False))))
            return pallas_call(kernel, *args, **kwargs)

        setattr(pl, "pallas_call", recording_pallas_call)

        pick_join = profiling.pick_join_engine
        estimate = join._estimate_merged_lanes
        host_range = frame_rolling.plan_range_engine
        mesh_range = dist._pick_range_engine_for_shard

        def recording_pick_join(est_lanes, limit, chunked_ok):
            engine = pick_join(est_lanes, limit, chunked_ok)
            self.picks.append(("join engine (pick_join_engine)", engine))
            return engine

        def recording_estimate(*args, **kwargs):
            est = estimate(*args, **kwargs)
            limit = resilience.max_merged_lanes()
            self.picks.append((
                "join", f"{est} merged lanes, single-program limit {limit}"
                + (": single-program merge" if est <= limit else "")))
            return est

        def recording_host_range(*args, **kwargs):
            out = host_range(*args, **kwargs)
            self.picks.append(("range engine (host frame)",
                               f"{out[0]} (row bounds {out[1]})"))
            return out

        def recording_mesh_range(*args, **kwargs):
            out = mesh_range(*args, **kwargs)
            self.picks.append(("range engine (mesh shard)",
                               f"{out[0]} (row bounds {out[1]})"))
            return out

        setattr(profiling, "pick_join_engine", recording_pick_join)
        setattr(join, "_estimate_merged_lanes", recording_estimate)
        setattr(frame_rolling, "plan_range_engine", recording_host_range)
        setattr(dist, "_pick_range_engine_for_shard", recording_mesh_range)
        return self

    def counts(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "kernels": len(self.kernels), "picks": len(self.picks),
                "compile_log": len(self.compile_log),
                "trace_log": len(self.trace_log)}


PROBE = Probe()


@contextlib.contextmanager
def dump_ir(directory: str):
    """Write every program lowered inside the block to ``directory``."""
    before = jax.config.values["jax_dump_ir_to"]
    jax.config.update("jax_dump_ir_to", directory)
    try:
        yield
    finally:
        jax.config.update("jax_dump_ir_to", before)


def lowered_programs(directory: str) -> dict:
    """{program name: holds a tpu_custom_call} over the dumped modules."""
    out = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.mlir"))):
        name = os.path.basename(path).split(".")[0]
        with open(path) as f:
            has = "tpu_custom_call" in f.read()
        out[name] = out.get(name, False) or has
    return out


def run_phase(name: str, fn):
    """Run ``fn`` twice, cold and warm, and return the warm result.
    Prints the wall time of each pass (it ends when every device array
    the phase returns is ready; the phases return host frames, which
    the device has finished), its compile count, persistent-cache
    traffic, and, for the first pass, the lowered programs with and
    without a Mosaic kernel.  A warm pass that compiles fails, as does
    a Pallas kernel traced in interpret mode."""
    result = None
    for i, pass_name in enumerate(("cold", "warm")):
        before = PROBE.counts()
        with tempfile.TemporaryDirectory() as ir_dir:
            ctx = dump_ir(ir_dir) if i == 0 else contextlib.nullcontext()
            t0 = time.perf_counter()
            with ctx:
                result = jax.block_until_ready(fn())
            wall = time.perf_counter() - t0
            programs = lowered_programs(ir_dir) if i == 0 else {}
        after = PROBE.counts()
        delta = {k: after[k] - before[k] for k in after}
        kernels = PROBE.kernels[before["kernels"]:after["kernels"]]
        compiled = PROBE.compile_log[before["compile_log"]:
                                     after["compile_log"]]
        traced = PROBE.trace_log[before["trace_log"]:after["trace_log"]]
        picks = PROBE.picks[before["picks"]:after["picks"]]
        log(f"[{name}] {pass_name} pass: {wall} s wall (smoke timing, not "
            f"a benchmark metric); backend compiles {delta['compiles']}, "
            f"persistent-cache hits {delta['cache_hits']}, misses "
            f"{delta['cache_misses']}")
        if compiled:
            total = sum(d for _, d, _ in compiled)
            slow = sorted(compiled, key=lambda c: -max(c[1], c[2] or 0))
            log(f"[{name}]   backend compile {total} s in all; slowest "
                f"(program, s, s a cache hit saved): {slow[:8]}")
        if traced:
            log(f"[{name}]   tracing + lowering "
                f"{sum(d for _, d in traced)} s in all; slowest: "
                f"{sorted(traced, key=lambda t: -t[1])[:5]}")
        for (what, detail), times in sorted(Counter(picks).items()):
            log(f"[{name}]   {what}: {detail} (x{times})")
        interpreted = sorted({k for k, interp in kernels if interp})
        if kernels:
            log(f"[{name}]   Pallas kernels traced: "
                f"{sorted({k for k, _ in kernels})}")
        if i > 0 and delta["compiles"]:
            raise AssertionError(
                f"[{name}] the {pass_name} pass compiled "
                f"{delta['compiles']} programs: {compiled}")
        if interpreted:
            raise AssertionError(
                f"[{name}] Pallas kernels ran in interpret mode: "
                f"{interpreted}")
        if programs:
            mosaic = sorted(p for p, has in programs.items() if has)
            log(f"[{name}]   lowered programs: {len(programs)}; with a "
                f"tpu_custom_call: {len(mosaic)} {mosaic}")
    return result


# ----------------------------------------------------------------------
# the phases
# ----------------------------------------------------------------------

def make_data(seed: int, n_rows: int, n_series: int):
    left, right, n = make_frames(n_rows, n_series, seed=seed)
    log(f"data: {n} rows a side over {n_series} series (seed {seed})")
    return (TSDF(left, "event_ts", ["user"]),
            TSDF(right, "event_ts", ["user"]))


def chain(frame, other):
    """The quickstart chain: any frame-like with the TSDF method names."""
    return (frame.asofJoin(other)
            .withRangeStats(colsToSummarize=["x"],
                            rangeBackWindowSecs=WINDOW_SECS)
            .EMA("x", exact=True))


def eager_phase(left, right) -> dict:
    """Eager frame chain, resample -> interpolate, fused resampleEMA,
    and the join chain on a one-device mesh, all as host frames."""
    joined = left.asofJoin(right)
    out = (joined.withRangeStats(colsToSummarize=["x"],
                                 rangeBackWindowSecs=WINDOW_SECS)
           .EMA("x", exact=True))
    resampled = left.resample(freq="sec", func="floor")
    interp = resampled.interpolate(method="linear")
    rema = left.resampleEMA("sec", "x")
    mesh = make_mesh({"series": 1})
    on_mesh = chain(left.on_mesh(mesh), right.on_mesh(mesh)).collect()
    return {"join": joined.df, "chain": out.df, "interp": interp.df,
            "resample_ema": rema.df, "mesh_chain": on_mesh.df}


def service_phase(left, right, eager: dict) -> dict:
    """A two-tenant QueryService answers CHAIN_REQUESTS planned chains
    and two SQL statements; every result must be bitwise equal to the
    eager frame of the same query."""
    tables = {"phone": left, "watch": right}
    want = {"chain": eager["chain"], "sql_join": eager["join"],
            "sql_filter": eager["join_filtered"]}
    with QueryService(workers=len(TENANTS)) as svc:
        tickets = [("chain", svc.submit(TENANTS[i % len(TENANTS)],
                                        chain(lazy_frame(left), right)))
                   for i in range(CHAIN_REQUESTS)]
        tickets.append(("sql_join", svc.submit_sql(TENANTS[0], SQL_JOIN,
                                                   tables)))
        tickets.append(("sql_filter", svc.submit_sql(TENANTS[1],
                                                     SQL_FILTER, tables)))
        answered = {}
        while tickets:
            kind, ticket = tickets.pop(0)
            got = ticket.result(timeout=1200).df
            pd.testing.assert_frame_equal(want[kind], got, check_exact=True)
            answered[kind] = answered.get(kind, 0) + 1
        stats = svc.stats()
    return {"answered": answered, "plan_cache": stats["plan_cache"]}


# ----------------------------------------------------------------------
# float64 reference on sampled series
# ----------------------------------------------------------------------

def sample_keys(n_series: int, seed: int, k: int = SAMPLE_SERIES):
    rng = np.random.default_rng(seed + 1)
    return np.sort(rng.choice(n_series, size=min(k, n_series),
                              replace=False))


def reference(left_df, right_df, keys) -> pd.DataFrame:
    """The quickstart chain in float64 pandas/numpy on ``keys``:
    ``merge_asof`` for the join (the right timestamp from the last right
    row, the value from the last non-null one), ``rolling`` over the
    inclusive 10 s range for the stats, and an exact recursive EMA."""
    lf = left_df[left_df["user"].isin(keys)]
    lf = lf.sort_values(["event_ts"], kind="mergesort")
    rf = right_df[right_df["user"].isin(keys)]
    rf = rf.sort_values(["event_ts"], kind="mergesort")
    rts = rf[["user", "event_ts"]].assign(right_event_ts=rf["event_ts"])
    out = pd.merge_asof(lf, rts, on="event_ts", by="user",
                        direction="backward")
    rwx = rf.dropna(subset=["wx"])[["user", "event_ts", "wx"]]
    out = pd.merge_asof(out, rwx.rename(columns={"wx": "right_wx"}),
                        on="event_ts", by="user", direction="backward")
    out = out.sort_values(["user", "event_ts"], kind="mergesort")
    out = out.reset_index(drop=True)

    roll = (out.set_index("event_ts").groupby("user")["x"]
            .rolling(f"{WINDOW_SECS}s", closed="both"))
    for stat, series in (("mean", roll.mean()), ("count", roll.count()),
                         ("min", roll.min()), ("max", roll.max()),
                         ("sum", roll.sum()), ("stddev", roll.std())):
        out[f"{stat}_x"] = series.to_numpy()
    out["zscore_x"] = (out["x"] - out["mean_x"]) / out["stddev_x"]

    alpha = 0.2
    ema = np.empty(len(out))
    x = out["x"].to_numpy()
    for _, idx in out.groupby("user").indices.items():
        acc = 0.0
        for i in idx:
            acc = alpha * x[i] + (1 - alpha) * acc
            ema[i] = acc
    out["EMA_x"] = ema
    return out


def check_against_reference(got: pd.DataFrame, ref: pd.DataFrame,
                            keys, label: str, value_dtype=None) -> None:
    """Joined columns exactly, stats and EMA within F32_BOUNDS, the
    null pattern of every column exactly.  ``value_dtype``: the dtype a
    mesh frame holds its value columns in on the device, so the joined
    values it returns are the reference's rounded to it."""
    got = got[got["user"].isin(keys)]
    got = got.sort_values(["user", "event_ts"], kind="mergesort")
    got = got.reset_index(drop=True)
    if len(got) != len(ref):
        raise AssertionError(f"[{label}] {len(got)} rows, reference "
                             f"{len(ref)}")
    for col in ("user", "event_ts", "x", "right_event_ts", "right_wx"):
        a, b = got[col].to_numpy(), ref[col].to_numpy()
        if value_dtype is not None and col in ("x", "right_wx"):
            b = b.astype(value_dtype).astype(a.dtype)
        same = (a == b) | (pd.isna(a) & pd.isna(b))
        if not same.all():
            raise AssertionError(f"[{label}] joined column {col} differs "
                                 f"in {int((~same).sum())} rows")
    errors = {}
    for stat in ("mean", "count", "min", "max", "sum", "stddev", "zscore",
                 "EMA"):
        col = f"{stat}_x"
        a = got[col].to_numpy(np.float64)
        b = ref[col].to_numpy(np.float64)
        if not (np.isnan(a) == np.isnan(b)).all():
            raise AssertionError(f"[{label}] {col}: null pattern differs")
        ok = ~np.isnan(a)
        err = float(np.max(np.abs(a[ok] - b[ok]), initial=0.0))
        bound = F32_BOUNDS["ema" if stat == "EMA" else stat]
        if not err <= bound:
            raise AssertionError(f"[{label}] {col}: max |err| {err} > "
                                 f"{bound}")
        errors[col] = err
    log(f"[{label}] reference check passed on {len(keys)} series: "
        f"joined columns exact; max |err| {errors}")


def check_interpolation(interp: pd.DataFrame, left_df, keys) -> None:
    """Linear interpolation on the 1 s grid against numpy.interp."""
    for key in keys:
        src = left_df[left_df["user"] == key]
        got = interp[interp["user"] == key].sort_values("event_ts")
        t = src["event_ts"].to_numpy().astype(np.int64)
        grid = got["event_ts"].to_numpy().astype(np.int64)
        want = np.interp(grid, t, src["x"].to_numpy())
        if len(grid) != (t.max() - t.min()) // 1_000_000_000 + 1:
            raise AssertionError(f"[interpolate] series {key}: {len(grid)} "
                                 f"grid rows")
        err = np.max(np.abs(got["x"].to_numpy(np.float64) - want))
        if not err <= F32_BOUNDS["linear"]:
            raise AssertionError(f"[interpolate] series {key}: max |err| "
                                 f"{err}")
    log(f"[interpolate] reference check passed on {len(keys)} series")


def check_resample_ema(rema: pd.DataFrame, left_df, keys) -> None:
    """Floor resample to 1 s then exact EMA, against numpy."""
    for key in keys:
        src = left_df[left_df["user"] == key].sort_values("event_ts")
        got = rema[rema["user"] == key].sort_values("event_ts")
        x = src["x"].to_numpy()
        acc, want = 0.0, np.empty(len(x))
        for i, v in enumerate(x):
            acc = 0.2 * v + 0.8 * acc
            want[i] = acc
        col = [c for c in got.columns if c.startswith("EMA")][0]
        err = np.max(np.abs(got[col].to_numpy(np.float64) - want))
        if not err <= F32_BOUNDS["ema"]:
            raise AssertionError(f"[resampleEMA] series {key}: max |err| "
                                 f"{err}")
    log(f"[resampleEMA] reference check passed on {len(keys)} series")


# ----------------------------------------------------------------------
# entry
# ----------------------------------------------------------------------

def report_environment() -> None:
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    log(f"native packer loaded: {native.available()}")


def one_chip(seed: int, rows: int, series: int = HHAR_SERIES) -> None:
    left, right = make_data(seed, rows, series)
    keys = sample_keys(series, seed)
    eager = run_phase("eager", lambda: eager_phase(left, right))
    ref = reference(left.df, right.df, keys)
    check_against_reference(eager["chain"], ref, keys, "eager chain")
    check_against_reference(eager["mesh_chain"], ref, keys, "mesh chain",
                            value_dtype=packing.compute_dtype())
    check_interpolation(eager["interp"], left.df, keys)
    check_resample_ema(eager["resample_ema"], left.df, keys)
    eager["join_filtered"] = (
        eager["join"][eager["join"]["x"] > 0].reset_index(drop=True))
    served = run_phase("service", lambda: service_phase(left, right, eager))
    log(f"[service] answered {served['answered']} bitwise equal to eager; "
        f"plan cache {served['plan_cache']}")


MESHES = (
    # label, mesh axes, time axis
    ("1 chip", {"series": 1}, None),
    ("series=4", {"series": 4}, None),
    ("series=2,time=2", {"series": 2, "time": 2}, "time"),
)
#: columns a mesh with a time axis may compute differently from one
#: chip: the EMA scan carries its state across the time shards, which
#: changes the association order of its f32 sums.  A series split
#: changes no per-series arithmetic, so there every column is bitwise.
TIME_SPLIT_MAY_DIFFER = ("EMA_x",)


def device_bytes() -> list:
    """Bytes in use on each device (0 where the backend reports none,
    as the CPU does)."""
    return [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in jax.devices()]


def mesh_phase(left, right, axes: dict, time_axis) -> dict:
    """The join chain on one mesh, eager and planned; the planned result
    must be bitwise equal to the eager one.  Reports the device bytes
    each device gained while the chain's frames were alive."""
    mesh = make_mesh(axes)
    before = device_bytes()
    dl = left.on_mesh(mesh, time_axis=time_axis)
    dr = right.on_mesh(mesh, time_axis=time_axis)
    out = chain(dl, dr)
    held = [b - a for a, b in zip(before, device_bytes())]
    eager = out.collect().df
    del out
    planned = chain(lazy_frame(dl), dr).collect().df
    pd.testing.assert_frame_equal(eager, planned, check_exact=True)
    return {"df": eager, "held": held}


def compare_with_one_chip(df: pd.DataFrame, one: pd.DataFrame, label: str,
                          time_axis) -> None:
    """Every column of a mesh's result bitwise equal to the one-chip
    run's, but those in TIME_SPLIT_MAY_DIFFER on a mesh with a time axis
    (they are held to the float64 reference instead).  Both frames
    sorted by series and time."""
    if list(df.columns) != list(one.columns) or len(df) != len(one):
        raise AssertionError(f"[{label}] result shape differs from the "
                             f"one-chip run")
    may_differ = TIME_SPLIT_MAY_DIFFER if time_axis else ()
    differ = sorted(c for c in one.columns if not np.array_equal(
        df[c].to_numpy(), one[c].to_numpy(),
        equal_nan=df[c].dtype.kind == "f"))
    unexpected = [c for c in differ if c not in may_differ]
    if unexpected:
        raise AssertionError(f"[{label}] columns differ from the one-chip "
                             f"run: {unexpected}")
    log(f"[{label}] vs one chip: bitwise equal but {differ} (allowed to "
        f"differ: {list(may_differ)})")


def four_chips(seed: int, rows: int, series: int = HHAR_SERIES) -> None:
    """The join chain on devices[0], on a 4-way series mesh and on a
    2x2 series x time mesh: planned == eager bitwise on each mesh, every
    column against the float64 reference, every column bitwise equal to
    the one-chip run but those a time split may change, and the data
    spread over the four devices."""
    left, right = make_data(seed, rows, series)
    keys = sample_keys(series, seed)
    ref = reference(left.df, right.df, keys)
    one = None
    for label, axes, time_axis in MESHES:
        got = run_phase(f"mesh {label}", lambda: mesh_phase(
            left, right, axes, time_axis))
        n_dev = int(np.prod(list(axes.values())))
        held = got["held"]
        log(f"[mesh {label}] device bytes held by the chain: {held}")
        if n_dev > 1 and (max(held[:n_dev]) <= 0
                          or min(held[:n_dev]) < max(held[:n_dev]) / 4):
            raise AssertionError(f"[mesh {label}] data is not spread over "
                                 f"{n_dev} devices: {held}")
        log(f"[mesh {label}] planned == eager bitwise")
        check_against_reference(got["df"], ref, keys, f"mesh {label}",
                                value_dtype=packing.compute_dtype())
        df = got["df"].sort_values(["user", "event_ts"], kind="mergesort")
        df = df.reset_index(drop=True)
        if one is None:
            one = df
        else:
            compare_with_one_chip(df, one, f"mesh {label}", time_axis)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rows", type=int, default=HHAR_ROWS,
                        help="rows a side before rounding to equal series "
                             "(default: the HHAR quickstart's)")
    args = parser.parse_args(argv)

    device = require_tpu(args.chips)
    report_environment()
    PROBE.install()
    if args.chips == 4:
        four_chips(args.seed, args.rows)
    else:
        one_chip(args.seed, args.rows)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
