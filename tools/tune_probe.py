"""The autotuner's measurement child: ``python tools/tune_probe.py
<class>`` measures one sweep point of ``tempo_tpu/tune`` and prints ONE
JSON line — the class, the knobs it ran with, the device fingerprint,
a rate and a CRC-32 digest of the full kernel outputs on deterministic
data (or ``hardware_gated`` with the reason).  Exits 1 on an unknown
class.

``tempo_tpu/tune/harness.py`` starts it once per candidate point with
the candidate knobs in the child environment, so a Mosaic OOM or a
compiler hang kills the child, never the tuner.  ``TEMPO_BENCH_SMOKE``
shrinks every shape for the CI smoke sweep.

The probe bodies came over unchanged from the repo's former root
benchmark script (``bench.py``).  This stays a script outside the
package because the ``fused_chain`` class runs ``_forward_step`` from
the root ``__graft_entry__.py``.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempo_tpu  # noqa: F401,E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from __graft_entry__ import N_RIGHT_COLS, _forward_step  # noqa: E402
from tempo_tpu.ops import rolling as rk  # noqa: E402

K = 1024          # series (partition keys)
L = 8192          # rows per series
SUB_K = 8         # series subsample the chained loop carries per output

if os.environ.get("TEMPO_BENCH_SMOKE"):
    # CI smoke sweep: full code path, tiny scale
    K, L, SUB_K = 64, 512, 4


def make_data(seed=0, k=None, l=None):
    k = K if k is None else k
    l = L if l is None else l
    rng = np.random.default_rng(seed)
    # ~1 event/sec with jitter, like the accelerometer quickstart data
    gaps = rng.integers(1, 3, size=(k, l)).astype(np.int64)
    l_secs = np.cumsum(gaps, axis=-1)
    l_ts = l_secs * np.int64(1_000_000_000)
    r_secs = np.cumsum(rng.integers(1, 3, size=(k, l)).astype(np.int64), axis=-1)
    r_ts = r_secs * np.int64(1_000_000_000)
    x = rng.standard_normal((k, l)).astype(np.float32)
    valid = np.ones((k, l), dtype=bool)
    r_values = rng.standard_normal((N_RIGHT_COLS, k, l)).astype(np.float32)
    r_valids = rng.random((N_RIGHT_COLS, k, l)) > 0.1
    return l_ts, l_secs, x, valid, r_ts, r_valids, r_values


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _probe(out):
    """A scalar consuming EVERY element of every output array (full
    reductions — a single-element sample would let XLA slice-propagate
    and narrow the per-iteration work), folded into the next
    iteration's input.  NaN-safe: unmatched join slots are legitimately
    NaN and must not poison the carry (a NaN scale makes the int jitter
    UB — measured: it faults the TPU worker)."""
    leaves = jax.tree.leaves(out)
    acc = jnp.float32(0.0)
    for leaf in leaves:
        acc = acc + jnp.nan_to_num(leaf.astype(jnp.float32)).sum() * 1e-9
    return acc


def _jitter_secs(scale):
    """Small integer second-offset derived from the loop carry: shifting
    BOTH sides' timestamps by it preserves every op's semantics while
    making all inputs iteration-dependent, so no sub-computation
    (searchsorted, sparse tables, ...) is loop-invariant-hoistable."""
    return (jnp.abs(scale) * 1e6).astype(jnp.int64) % 16


def _make_run(body):
    """Build the jitted chained-loop runner for a body.  Callers that
    share a body function object (and argument shapes) share ONE
    compile: a large merge program costs minutes of compile time."""

    def small(out):
        def sl(k, v):
            if k in ("stats_clipped", "clipped") \
                    or k.startswith("clipped_"):
                # the truncation audit must be GLOBAL (a strided
                # sample could miss clipped series) — the
                # plane is [K, 1], cheap to carry whole
                return v.astype(jnp.float32)
            stride = max(v.shape[-2] // SUB_K, 1)
            return v[..., ::stride, :][..., :SUB_K, :].astype(jnp.float32)

        return {k: sl(k, v) for k, v in out.items()}

    @jax.jit
    def run(n, scale0, *args):
        def step(i, carry):
            scale, acc, _ = carry
            out = body(scale, *args)
            p = _probe(out)
            return (1.0 + 1e-6 * jnp.tanh(p + acc * 1e-12), acc + p,
                    small(out))

        init_small = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda s, *a: small(body(s, *a)), scale0, *args),
        )
        return jax.lax.fori_loop(
            0, n, step, (scale0, jnp.float32(0.0), init_small)
        )

    return run


def _measured_rowbounds(secs, w):
    """Host-side (behind, ahead) row extents of a rangeBetween(-w, 0)
    frame over ``secs`` — one searchsorted sweep per series.
    The jitter offset shifts every timestamp uniformly, so the extents
    are jitter-invariant and need no headroom; the kernels' on-device
    ``clipped`` audit still proves the bounds covered every frame."""
    Kr, Lr = secs.shape
    behind = max(
        int((np.arange(Lr) - np.searchsorted(  # lint-ok: dynamic-gather: host
            secs[k], secs[k] - w, side="left")).max())
        for k in range(Kr)
    )
    ahead = max(
        int((np.searchsorted(  # lint-ok: dynamic-gather: host
            secs[k], secs[k], side="right") - 1 - np.arange(Lr)).max())
        for k in range(Kr)
    )
    return behind, ahead


# per-row plane traffic of the windowed-stats probes: reads (i64 ms +
# f32 x + bool valid), the i32 jitter-cast re-stream (write + kernel
# re-read), 8 written stat planes
_STATS_BYTES_ROW = 8 + 4 + 1 + 8 + 8 * 4


def _dense_stats_data(mean_gap_ms, seed=2, k=None, l=None):
    """~1000/mean_gap_ms Hz ticks: a 10s window spans ~10000/gap rows.
    Gap jitter is ±25% so the densest stretch bounds the row extent at
    ~4/3 of the mean — this keeps the medium config's XLA shifted form
    inside the HBM budget (it materialises ~2.4 shifted copies per
    pass; W≈266 at a ±2x jitter would not fit the 15.75G, measured via
    the W=512 OOM).  The ~140-row extent is far above the Pallas
    kernel's 64-row ceiling either way, so the shifted measurement IS
    the XLA form — exactly what the auto-pick would run here."""
    k = K if k is None else k
    l = L if l is None else l
    rng = np.random.default_rng(seed)
    gaps = rng.integers(max(3 * mean_gap_ms // 4, 1),
                        max(5 * mean_gap_ms // 4, 2),
                        size=(k, l)).astype(np.int64)
    ms = np.cumsum(gaps, axis=-1)
    x = rng.standard_normal((k, l)).astype(np.float32)
    valid = np.ones((k, l), dtype=bool)
    return ms, x, valid


def _chunked_case(Kc, Ls, seed=7):
    """Two-sided sorted join data at the oversize merged-lane shapes."""
    rng = np.random.default_rng(seed)
    l_ts = np.cumsum(rng.integers(1, 3, size=(Kc, Ls)).astype(np.int64),
                     axis=-1) * np.int64(1_000_000)
    r_ts = np.cumsum(rng.integers(1, 3, size=(Kc, Ls)).astype(np.int64),
                     axis=-1) * np.int64(1_000_000)
    r_values = rng.standard_normal(
        (N_RIGHT_COLS, Kc, Ls)).astype(np.float32)
    r_valids = rng.random((N_RIGHT_COLS, Kc, Ls)) > 0.1
    return l_ts, r_ts, r_valids, r_values


def _tune_rate(body, args, n_rows, label, run=None):
    """Compact probe timing for the autotuner: the body chained inside
    one fori_loop dispatch (:func:`_make_run`), per-iteration time from
    differencing two trip counts, with a small wall target (the sweep
    runs dozens of child probes).  Returns (rows_per_sec, t_iter)."""
    if run is None:
        run = _make_run(body)
    print(f"[{label}] compiling...", file=sys.stderr, flush=True)
    float(run(jnp.int32(1), jnp.float32(1.0), *args)[1])
    target = 0.5 if os.environ.get("TEMPO_BENCH_SMOKE") else 3.0

    def timed(n, salt):
        ts = []
        for i in range(2):
            t0 = time.perf_counter()
            float(run(jnp.int32(n), jnp.float32(1.0 + salt + i * 1e-6),
                      *args)[1])
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    t_pilot = timed(2, 1e-4)
    est = max(t_pilot / 2, 1e-6)
    n_long = int(np.clip(target / est, 4, 2048))
    n_short = max(n_long // 8, 1)
    t_short, t_long = timed(n_short, 2e-4), timed(n_long, 3e-4)
    t_iter = max(t_long - t_short, 1e-9) / (n_long - n_short)
    print(f"[{label}] {n_rows / t_iter:,.0f} rows/s", file=sys.stderr,
          flush=True)
    return n_rows / t_iter, t_iter


def _out_digest(body, args):
    """CRC-32 of the FULL outputs of one deterministic body call
    (scale=1.0, zero jitter): the autotuner's bitwise value-audit gate
    — a candidate knob setting must reproduce the default-knob output
    bytes exactly or it is rejected, not just slow."""
    import zlib

    out = jax.jit(body)(jnp.float32(1.0), *args)
    h = 0
    for key in sorted(out):
        h = zlib.crc32(np.asarray(out[key]).tobytes(), h)
    return h


def _stream_saxpy_rate(k, l):
    """Measured read+write stream rate (GB/s) of an elementwise saxpy
    at [k, l], compact enough to run inside the tune probes: the
    profile's measured ``hbm_stream_rate`` (the cost model then argmins
    over what THIS image can move, not a spec sheet)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((k, l)).astype(np.float32)

    def stream(scale, a):
        return {"y": a * scale + 1.0}

    _, t_iter = _tune_rate(stream, (jax.device_put(x),), x.size,
                           label="tune_stream_saxpy")
    return 2 * x.size * 4 / t_iter / 1e9


def tune_probe(probe):
    """One autotuner measurement point (child of
    ``tempo_tpu/tune/harness.py``): a compact rate measurement plus a
    CRC-32 digest of the full kernel outputs on deterministic data —
    the harness compares every candidate's digest against the
    default-knob baseline and rejects any mismatch.  The candidate
    knobs arrive via the child environment (the harness clears every
    other tunable knob and forces ``TEMPO_TPU_TUNE_PROFILE=off`` so the
    sweep measures raw knob values); shapes are probe-sized and
    ``TEMPO_BENCH_SMOKE`` shrinks them further for the CI smoke
    sweep."""
    from tempo_tpu import tune as tune_mod
    from tempo_tpu.tune import profile as tune_profile

    Kp, Lp = min(K, 256), min(L, 4096)
    out = {"class": probe,
           "knobs": {name: os.environ[name]
                     for name in tune_mod.TUNABLE_KNOBS
                     if name in os.environ},
           "fingerprint": tune_profile.runtime_fingerprint()}

    if probe in ("stream_dense", "stream_medium"):
        gap = 20 if probe == "stream_dense" else 100
        ms, x, valid = _dense_stats_data(gap, k=Kp, l=Lp)
        behind, ahead = _measured_rowbounds(ms, 10_000)
        w_ms = jnp.asarray(10_000, jnp.int32)

        def body(scale, ms, x, valid, mb, ma):
            ms32 = (ms + _jitter_secs(scale) * 1000).astype(jnp.int32)
            return dict(rk.range_stats_streaming(ms32, x, valid, w_ms,
                                                 mb, ma, scale=scale))

        args = [jax.device_put(a) for a in
                (ms, x, valid, np.int32(behind), np.int32(ahead))]
        rate, t_iter = _tune_rate(body, args, Kp * Lp,
                                  label=f"tune_{probe}")
        out.update(
            rows_per_sec=rate, t_iter=t_iter,
            bytes_per_iter=Kp * Lp * _STATS_BYTES_ROW,
            digest=_out_digest(body, args))
        if not out["knobs"] and not os.environ.get(
                "TEMPO_BENCH_TUNE_NO_SAXPY"):
            # the saxpy stream rate feeds the profile's measured cost
            # inputs, and the harness reads it off the FIRST baseline
            # probe only — candidate children (non-empty knobs) and
            # the incumbent-bias baseline re-probe (which sets the
            # marker) skip the measurement
            out["stream_gbps"] = round(_stream_saxpy_rate(Kp, 4 * Lp),
                                       2)
    elif probe == "packed_stream":
        C = 4
        rng = np.random.default_rng(21)
        ms, x, valid = _dense_stats_data(20, k=Kp, l=Lp)
        xs = np.stack([x * np.float32(1.0 + 0.25 * c)
                       for c in range(C)])
        vs = np.stack([valid if c == 0 else (rng.random(x.shape) > 0.1)
                       for c in range(C)])
        behind, ahead = _measured_rowbounds(ms, 10_000)
        w_ms = jnp.asarray(10_000, jnp.int32)

        def body(scale, ms, xs, vs, mb, ma):
            ms32 = (ms + _jitter_secs(scale) * 1000).astype(jnp.int32)
            return dict(rk.range_stats_streaming_packed(
                ms32, xs, vs, w_ms, mb, ma, scales=scale))

        args = [jax.device_put(a) for a in
                (ms, xs, vs, np.int32(behind), np.int32(ahead))]
        rate, t_iter = _tune_rate(body, args, C * Kp * Lp,
                                  label="tune_packed_stream")
        out.update(
            rows_per_sec=rate, t_iter=t_iter,
            bytes_per_iter=Kp * Lp * (8 + 8 + C * (4 + 1 + 8 * 4)),
            digest=_out_digest(body, args))
    elif probe == "fused_chain":
        data = make_data(k=Kp, l=Lp)

        def body(scale, l_ts, l_secs, x, valid, r_ts, r_valids,
                 r_values):
            js = _jitter_secs(scale)
            ns = js * 1_000_000_000
            return _forward_step(l_ts + ns, l_secs + js, x * scale,
                                 valid, r_ts + ns, r_valids, r_values)

        args = [jax.device_put(a) for a in data]
        rate, t_iter = _tune_rate(body, args, Kp * Lp,
                                  label="tune_fused_chain")
        out.update(rows_per_sec=rate, t_iter=t_iter,
                   bytes_per_iter=_tree_bytes(args),
                   digest=_out_digest(body, args))
    elif probe == "join_chunk":
        if jax.default_backend() != "tpu":
            out["hardware_gated"] = (
                "join_chunk probe requires the TPU backend (Mosaic "
                "chunked merge kernel); the class is hardware-gated, "
                "not faked")
            print(json.dumps(out))
            return out
        from tempo_tpu.ops import pallas_merge as pm

        Kc, Ls = min(K, 64), min(L * 2, 16384)
        l_ts, r_ts, r_valids, r_values = _chunked_case(Kc, Ls)
        keys, planes, plan, meta = pm.build_chunked_planes(
            l_ts, r_ts, r_valids, r_values)

        def body(scale, *args, _meta=meta, _plan=plan):
            ks = args[:_meta["n_keys"]]
            ps = tuple(p * scale for p in args[_meta["n_keys"]:])
            outs = pm._chunked_call(
                ks, ps, n_payload=_meta["n_payload"],
                n_out=_meta["n_out"], Cm=_plan.merged_lanes,
                segmented=False, keyed_fill=False,
                chunk_rows=_plan.chunk_rows)
            return {f"o{i}": o for i, o in enumerate(outs)}

        args = [jax.device_put(jnp.asarray(a)) for a in (*keys, *planes)]
        rate, t_iter = _tune_rate(body, args, Kc * Ls,
                                  label="tune_join_chunk")
        read_b = (meta["n_keys"] + meta["n_payload"]) \
            * Kc * plan.n_chunks * plan.merged_lanes * 4
        out.update(rows_per_sec=rate, t_iter=t_iter,
                   bytes_per_iter=read_b,
                   chunk_lanes=plan.merged_lanes,
                   digest=_out_digest(body, args))
    elif probe == "serve_batch":
        from tempo_tpu.serve import MicroBatchExecutor, StreamingTSDF

        rng = np.random.default_rng(5)
        Ks, C = 8, 2
        cols = ("bid", "ask")
        n = 400 if os.environ.get("TEMPO_BENCH_SMOKE") else 2500
        stream = StreamingTSDF(
            [f"s{i}" for i in range(Ks)], list(cols), window_secs=10.0,
            window_rows_bound=32, ema_alpha=0.2, max_lookback=64)
        # batch_rows=None: the executor reads the knob under test
        ex = MicroBatchExecutor(stream)
        stream.warmup(16)
        gaps = rng.exponential(scale=4e7, size=n).astype(np.int64) + 1
        ts = np.cumsum(gaps) + np.int64(10**9)
        series = rng.integers(0, Ks, n)
        is_left = rng.random(n) < 0.25
        vals = rng.standard_normal((n, C)).astype(np.float32)

        def feed(i0, i1):
            tickets = []
            for i in range(i0, i1):
                sym = f"s{series[i]}"
                if is_left[i]:
                    tickets.append(ex.submit("left", sym, ts[i]))
                else:
                    tickets.append(ex.submit(
                        "right", sym, ts[i],
                        {c: vals[i, j] for j, c in enumerate(cols)}))
            return tickets

        n_warm = n // 8
        for t in feed(0, n_warm):
            t.result(timeout=120)
        print("[tune_serve_batch] timing...", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        results = [t.result(timeout=300) for t in feed(n_warm, n)]
        wall = time.perf_counter() - t0
        ex.close()
        # digest in submission order: per-tick results are bitwise
        # invariant to the micro-batch split (the streamed ==
        # batch contract), so every admissible batch_rows value must
        # reproduce these bytes exactly
        import zlib

        h = 0
        for res in results:
            for key in sorted(res):
                h = zlib.crc32(
                    np.asarray(res[key], np.float64).tobytes(), h)
        out.update(rows_per_sec=(n - n_warm) / wall,
                   t_iter=wall / (n - n_warm),
                   batch_rows=ex.batch_rows, digest=h)
    elif probe == "ingest_sweep":
        import zlib

        from tempo_tpu.io import ingest as tpu_ingest

        smoke = bool(os.environ.get("TEMPO_BENCH_SMOKE"))
        n_slabs = 4 if smoke else 10
        slab_rows = (1 << 13) if smoke else (1 << 19)

        def load(i):
            rng = np.random.default_rng(100 + i)
            return np.sort(rng.standard_normal(slab_rows)
                           .astype(np.float32), kind="stable")

        step = jax.jit(lambda x: jnp.cumsum(x) * jnp.float32(0.5))
        jax.block_until_ready(step(jnp.zeros(slab_rows, jnp.float32)))

        def compute(i, x):
            return jax.block_until_ready(step(jnp.asarray(x)))

        def drain(i, y):
            return zlib.crc32(np.asarray(y).tobytes())

        # ring=None: sweep_slabs reads the knob under test from env
        tpu_ingest.sweep_slabs(2, load, compute, drain)   # warm
        t0 = time.perf_counter()
        res = tpu_ingest.sweep_slabs(n_slabs, load, compute, drain)
        wall = time.perf_counter() - t0
        h = 0
        for c in res:
            h = zlib.crc32(int(c).to_bytes(8, "little"), h)
        out.update(rows_per_sec=n_slabs * slab_rows / wall,
                   t_iter=wall / n_slabs, bytes_per_iter=slab_rows * 4,
                   digest=h)
    elif probe == "stitched_chain":
        import zlib

        import pandas as pd

        from tempo_tpu import TSDF
        from tempo_tpu.parallel import make_mesh
        from tempo_tpu.plan import cache as plan_cache

        smoke = bool(os.environ.get("TEMPO_BENCH_SMOKE"))
        Ks, Ls = (16, 512) if smoke else (64, 4096)
        rng = np.random.default_rng(7)
        secs = np.cumsum(rng.integers(1, 3, size=(Ks, Ls))
                         .astype(np.int64), axis=-1)
        df = pd.DataFrame({"sym": np.repeat(np.arange(Ks), Ls),
                           "event_ts": secs.ravel(),
                           "x": rng.standard_normal(Ks * Ls)})
        frame = TSDF(df, "event_ts", ["sym"]).on_mesh(
            make_mesh({"series": 1}))

        def chain():
            return (frame.resample("5 seconds", "mean")
                    .EMA("x", window=6)
                    .withRangeStats(colsToSummarize=["x"],
                                    rangeBackWindowSecs=20)
                    .collect().df)

        os.environ["TEMPO_TPU_PLAN"] = "1"
        try:
            plan_cache.CACHE.clear()
            ref = chain()                       # plan + compile
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                res = chain()
                ts.append(time.perf_counter() - t0)
                del res
            t_iter = float(np.median(ts))
        finally:
            os.environ.pop("TEMPO_TPU_PLAN", None)
            plan_cache.CACHE.clear()
        h = 0
        for c in sorted(ref.select_dtypes(include=[np.number])):
            h = zlib.crc32(np.ascontiguousarray(
                ref[c].to_numpy()).tobytes(), h)
        out.update(rows_per_sec=Ks * Ls / t_iter, t_iter=t_iter,
                   bytes_per_iter=Ks * Ls * 12, digest=h)
    elif probe == "serve_cohort":
        import zlib

        from tempo_tpu.serve import CohortExecutor, StreamCohort

        smoke = bool(os.environ.get("TEMPO_BENCH_SMOKE"))
        Sc = 32
        n = 600 if smoke else 4000
        rng = np.random.default_rng(9)
        cohort = StreamCohort(("px",), window_secs=10.0,
                              window_rows_bound=8, ema_alpha=0.2,
                              max_lookback=16, slots=Sc)
        members = [cohort.add_stream(f"u{i}", ["ticks"])
                   for i in range(Sc)]
        # coalesce_s=None: the executor reads the knob under test
        ex = CohortExecutor(cohort, batch_rows=16, queue_depth=64)
        cohort.warmup(16)
        gaps = rng.exponential(scale=4e7, size=n).astype(np.int64) + 1
        ts_arr = np.cumsum(gaps) + np.int64(10**9)
        stream_of = np.concatenate([
            rng.permutation(Sc),
            rng.integers(0, Sc, max(0, n - Sc))])[:n]
        is_left = rng.random(n) < 0.25
        is_left[:Sc] = False
        vals = rng.standard_normal(n).astype(np.float32)

        def feed(i0, i1):
            return ex.submit_many([
                ("left", members[stream_of[q]], "ticks",
                 int(ts_arr[q]), None, None)
                if is_left[q] else
                ("right", members[stream_of[q]], "ticks",
                 int(ts_arr[q]), {"px": vals[q]}, None)
                for q in range(i0, i1)])

        n_warm = n // 8
        for t in feed(0, n_warm):
            t.result(timeout=120)
        print("[tune_serve_cohort] timing...", file=sys.stderr,
              flush=True)
        t0 = time.perf_counter()
        results = [t.result(timeout=300) for t in feed(n_warm, n)]
        wall = time.perf_counter() - t0
        ex.close()
        # digest in submission order: per-tick results are bitwise
        # invariant to the coalescing window (the batch split never
        # changes per-(slot,row) state math), so every admissible
        # coalesce value must reproduce these bytes exactly
        h = 0
        for res in results:
            for key in sorted(res):
                h = zlib.crc32(
                    np.asarray(res[key], np.float64).tobytes(), h)
        out.update(rows_per_sec=(n - n_warm) / wall,
                   t_iter=wall / (n - n_warm),
                   coalesce_s=ex.coalesce_s, digest=h)
    else:
        out["error"] = f"unknown tune probe {probe!r}"
    print(json.dumps(out))
    return out


def main():
    if len(sys.argv) != 2:
        raise SystemExit("usage: python tools/tune_probe.py <class>")
    res = tune_probe(sys.argv[1])   # prints its own JSON line
    raise SystemExit(1 if "error" in res else 0)


if __name__ == "__main__":
    main()
