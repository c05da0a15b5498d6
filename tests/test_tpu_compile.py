"""The main path's Pallas kernels compile for a TPU v5e.

Each test lowers one raw kernel call (never a backend-gated wrapper,
which takes its CPU branch here) with shapes placed on one chip of a
described ``v5e:2x2`` topology, compiles it with the TPU compiler, and
checks that the program holds the Mosaic kernel (``tpu_custom_call``).
Interpret-mode tests cannot see what this sees: slices the tiling
refuses and kernels that overrun the fast memory.  Nothing runs.

The tests use 2048 lanes, where a compile takes a few seconds; Mosaic
compile time grows with the lane width, not with the series count.  Run
the file as a script to compile every kernel once at the full HHAR lane
width instead and print each compile time:

    JAX_PLATFORMS=cpu PYTHONPATH=. python tests/test_tpu_compile.py
"""

import os
import sys
import time

import numpy as np
import pytest

import tempo_tpu  # noqa: F401  (x64 mode, as the library runs)
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from tempo_tpu.ops import pallas_bucket as pb
from tempo_tpu.ops import pallas_kernels as pk
from tempo_tpu.ops import pallas_merge as pm
from tempo_tpu.ops import pallas_stream as psr
from tempo_tpu.ops import pallas_window as pw

#: series of the HHAR quickstart frames (tempo_tpu.testing.hhar)
SERIES = 1024
#: lane width of the tier-1 compiles
LANES = 2048
#: padded rows per series of the full HHAR frames (13,062,144 / 1024)
HHAR_LANES = 12800
#: ring depth that engages the explicit DMA ring (2 is the BlockSpec
#: pipeline, the default)
RING_DEPTH = 4
#: series of the ring compiles: the ring unrolls its slab loop in
#: Python, so its compile time grows with the slab count (series over
#: block rows) — 1024 series take ~3 min for the stream window; 64 make
#: two slabs, the fewest a ring overlaps
RING_SERIES = 64


def _describe_one_chip():
    """One chip of a described v5e:2x2 host, with the persistent
    compile cache off: an entry written for a described chip cannot be
    read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def one_chip():
    """The described chip for this module; afterwards the compile cache
    and the environment are as they were, so the tests that share this
    worker keep their persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.values["jax_enable_compilation_cache"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            try:
                sharding = _describe_one_chip()
            except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield sharding
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


def _s(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _planes(sharding, L, *dtypes, K=SERIES):
    return [_s(sharding, (K, L), dt) for dt in dtypes]


# ----------------------------------------------------------------------
# one function per kernel: (sharding, lanes) -> lowered program
# ----------------------------------------------------------------------

def _ema(sh, L):
    x, v = _planes(sh, L, jnp.float32, jnp.bool_)
    return pk._ema_call.lower(x, v, _s(sh, (), jnp.float32))


def _ema_chunk(sh, L):
    """The carry-passing EMA over the quickstart's 9 series, one chunk
    of its plan's width (whatever ``L``): one grid step, the whole
    kernel body."""
    _, _, Lc = pk.ema_chunk_plan(9)
    x, v = _planes(sh, Lc, jnp.float32, jnp.bool_, K=9)
    return pk._ema_chunk_call.lower(x, v, _s(sh, (9, pk.LANE), jnp.float32),
                                    _s(sh, (1,), jnp.float32))


def _cumsum3(sh, L):
    x, v = _planes(sh, L, jnp.float32, jnp.bool_)
    return pk._cumsum3_call.lower(x, v)


def _last_valid(sh, L):
    x, v = _planes(sh, L, jnp.float32, jnp.bool_)
    return pk._last_valid_call.lower(x, v)


def _index_scan(sh, L):
    (v,) = _planes(sh, L, jnp.bool_)
    return pk._index_scan_call.lower(v, kernel=pk._last_valid_index_kernel)


def _window_args(sh, L, depth):
    K = SERIES if depth == 2 else RING_SERIES
    secs, x, v = _planes(sh, L, jnp.int32, jnp.float32, jnp.bool_, K=K)
    return secs, x, v, _s(sh, (4,), jnp.int32), _s(sh, (1,), jnp.float32)


def _stream(depth):
    def build(sh, L):
        return pw._stream_call.lower(*_window_args(sh, L, depth),
                                     depth=depth)
    return build


def _unrolled(depth):
    def build(sh, L):
        return pw._unrolled_call.lower(*_window_args(sh, L, depth),
                                       max_behind=10, max_ahead=0,
                                       depth=depth)
    return build


def _merge(sh, L):
    """The single-plan merge: the quickstart join, one right column."""
    l_ts, r_ts = _planes(sh, L, jnp.int64, jnp.int64)
    r_valid = _s(sh, (1, SERIES, L), jnp.bool_)
    r_val = _s(sh, (1, SERIES, L), jnp.float32)
    keys, payload = jax.eval_shape(
        lambda a, b, c, d: pm._build_join_planes(a, b, c, d, None, None,
                                                 None, None)[:2],
        l_ts, r_ts, r_valid, r_val)
    _, Lc2, Llp = pm._pad_plan(L, L)
    place = lambda t: tuple(_s(sh, a.shape, a.dtype) for a in t)
    return pm._merge_call.lower(place(keys), place(payload),
                                n_payload=len(payload), Lc2=Lc2, Llp=Llp)


def _chunked(depth):
    """The lane-chunked merge over a host-built chunk plan (8 series;
    the chunk width, not the series count, shapes the kernel)."""
    def build(sh, L):
        rng = np.random.default_rng(0)
        K = 8
        l_ts = np.cumsum(rng.integers(1, 3, (K, L)), axis=1) * 10**9
        r_ts = np.cumsum(rng.integers(1, 3, (K, L)), axis=1) * 10**9
        r_valid = rng.random((1, K, L)) > 0.05
        r_val = rng.standard_normal((1, K, L)).astype(np.float32)
        keys, planes, plan, meta = pm.build_chunked_planes(
            l_ts, r_ts, r_valid, r_val,
            chunk_lanes=1 << ((L // 2).bit_length() - 1))
        place = lambda t: tuple(_s(sh, np.shape(a), np.asarray(a).dtype)
                                for a in t)
        return pm._chunked_call.lower(
            place(keys), place(planes), n_payload=meta["n_payload"],
            n_out=meta["n_out"], Cm=plan.merged_lanes, segmented=False,
            keyed_fill=False, chunk_rows=plan.chunk_rows, depth=depth)
    return build


def _bucket_stats(sh, L):
    bid, x, v = _planes(sh, L, jnp.int32, jnp.float32, jnp.bool_)
    return pb._bucket_stats_call.lower(bid, x, v)


def _resample_ema(sh, L):
    secs, x, v = _planes(sh, L, jnp.int32, jnp.float32, jnp.bool_)
    return pb._resample_ema_call.lower(
        secs, x, v, _s(sh, (), jnp.int32), _s(sh, (), jnp.float32),
        _s(sh, (), jnp.float32))


def _ring(sh, L):
    """pallas_stream.ring_call on its own: a masked doubling over row
    slabs (the bool plane rides the ring as int32)."""
    def run(x, valid):
        return psr.ring_call(
            lambda scalars, slabs: [jnp.where(slabs[1], slabs[0] * 2.0,
                                              0.0)],
            [], [x, valid], n_out=1, out_like=0, bk=32, depth=RING_DEPTH)
    return jax.jit(run).lower(*_planes(sh, L, jnp.float32, jnp.bool_,
                                       K=RING_SERIES))


KERNELS = {
    "pallas_kernels._ema_call": _ema,
    "pallas_kernels._ema_chunk_call": _ema_chunk,
    "pallas_kernels._cumsum3_call": _cumsum3,
    "pallas_kernels._last_valid_call": _last_valid,
    "pallas_kernels._index_scan_call": _index_scan,
    "pallas_window._stream_call[depth=2]": _stream(2),
    f"pallas_window._stream_call[depth={RING_DEPTH}]": _stream(RING_DEPTH),
    "pallas_window._unrolled_call[depth=2]": _unrolled(2),
    f"pallas_window._unrolled_call[depth={RING_DEPTH}]":
        _unrolled(RING_DEPTH),
    "pallas_merge._merge_call": _merge,
    "pallas_merge._chunked_call[depth=2]": _chunked(2),
    f"pallas_merge._chunked_call[depth={RING_DEPTH}]": _chunked(RING_DEPTH),
    "pallas_bucket._bucket_stats_call": _bucket_stats,
    "pallas_bucket._resample_ema_call": _resample_ema,
    "pallas_stream.ring_call": _ring,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    compiled = KERNELS[name](one_chip, LANES).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_range_chunk_program_compiles_for_v5e(one_chip):
    """The lane-chunked range-stats program (plain XLA, no kernel) at
    the plan of the full-size quickstart chain: 10 s windows of ~12k
    rows back and ~1k tie rows ahead."""
    from tempo_tpu.ops import rolling as rk

    block, halo, nlev = rk.range_chunk_plan(12000, 1100)
    G, core = rk.RANGE_CHUNK_ROWS, block - halo
    rk.range_stats_chunk.lower(
        _s(one_chip, (G, block), jnp.float32),
        _s(one_chip, (G, block), jnp.bool_),
        _s(one_chip, (G, core), jnp.int32), _s(one_chip, (G, core), jnp.int32),
        _s(one_chip, (G, 1), jnp.float32), _s(one_chip, (), jnp.int32),
        nlev=nlev).compile()


def main() -> int:
    """Compile every kernel once at the full HHAR lane width."""
    sharding = _describe_one_chip()
    for name in sorted(KERNELS):
        t0 = time.perf_counter()
        compiled = KERNELS[name](sharding, HHAR_LANES).compile()
        seconds = time.perf_counter() - t0
        assert "tpu_custom_call" in compiled.as_text(), name
        print(f"{name}: compiled for v5e at {HHAR_LANES} lanes in "
              f"{seconds} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
