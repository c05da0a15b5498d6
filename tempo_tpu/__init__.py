"""tempo-tpu: a TPU-native time-series analytics framework.

From-scratch rebuild of the capabilities of dbl-tempo
(/root/reference, the Databricks Labs TSDF library) on JAX/XLA:
series are packed, time-sorted columnar arrays sharded over a device
mesh; ops are jitted/vmapped kernels (searchsorted AS-OF merges,
prefix-scan rolling stats, segment-reduce resampling, associative-scan
EMA, batched FFT) instead of Spark Window expressions.

Public surface mirrors the reference: ``TSDF`` plus ``display``
(python/tempo/__init__.py:1-2).
"""

import os as _os

import jax

# int64-nanosecond timestamps and float64 golden-parity accumulations
# require 64-bit mode; TPU fast paths opt into f32/bf16 explicitly.
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: TSDF kernels are compiled per packed
# shape, and a Mosaic kernel at full lane width takes tens of seconds.
# Where JAX_COMPILATION_CACHE_DIR (or jax_compilation_cache_dir) is set,
# JAX uses it and nothing else is set here.  Otherwise the cache lives
# at a fixed path inside the checkout: the path is part of the cache
# key, so a cache that moves never hits.
if jax.config.jax_compilation_cache_dir is None:
    jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))

from tempo_tpu.frame import TSDF  # noqa: E402
from tempo_tpu.utils import display  # noqa: E402

__version__ = "0.1.0"
__all__ = ["TSDF", "DistributedTSDF", "display"]


def __getattr__(name):  # PEP 562: keep the mesh/shard_map stack lazy —
    # host-only users never pay for it (frame.on_mesh imports it lazily
    # for the same reason)
    if name == "DistributedTSDF":
        from tempo_tpu.dist import DistributedTSDF

        return DistributedTSDF
    raise AttributeError(f"module 'tempo_tpu' has no attribute {name!r}")
