"""The device the run is on, its published peaks, and its memory peak."""

import json
import os

import jax

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


class NoDevice(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require(chips: int) -> dict:
    """The device as JAX reports it, or ``NoDevice`` when it is not a
    TPU with at least ``chips`` devices.  JAX falls back to the CPU by
    itself when its TPU backend cannot start, so this is the guard."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoDevice(f"benchmark: no TPU: JAX found platform {platform!r} "
                       f"({len(devices)} device(s)); the benchmark runs on "
                       f"a TPU only")
    if len(devices) < chips:
        raise NoDevice(f"benchmark: the cell needs {chips} TPU devices, JAX "
                       f"found {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peaks(kind: str) -> dict:
    """The published peaks of ``kind``; a kind not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"benchmark: device kind {kind!r} is not in "
                       f"{os.path.basename(PEAKS)} (known: {sorted(table)})")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices
    (0 where the backend reports none, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
