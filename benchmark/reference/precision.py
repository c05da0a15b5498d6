"""Rounding float64 values to a stated precision.

A configuration states the precision of each part of its result
(``guarantees.precision``).  A key that ends in ``_compute`` names the
precision the program computes in: the reference computes that part in
float64 and the comparison's limit takes up the difference.  Any other
key names a precision the values are held in (``joined_values``,
``resident_values``): that is part of what the result is, so the
reference rounds to it too.  The control of a cell is the reference
with every stated precision, of both kinds, stepped down once
(``LOWER``).
"""

import ml_dtypes
import numpy as np

#: the next precision below each stated one: the control's
LOWER = {"float64": "float32", "float32": "bfloat16"}

_DTYPES = {"float64": np.float64, "float32": np.float32,
           "bfloat16": ml_dtypes.bfloat16}


def round_to(values, name: str) -> np.ndarray:
    """``values`` rounded to ``name`` and returned as float64 (NaN kept)."""
    a = np.asarray(values, dtype=np.float64)
    return a.astype(_DTYPES[name]).astype(np.float64)


def reference(stated: dict) -> dict:
    """The reference's precisions: held ones as stated, computed ones
    in float64."""
    return {k: ("float64" if k.endswith("_compute") else v)
            for k, v in stated.items()}


def control(stated: dict) -> dict:
    """The control's precisions: each stated one stepped down once."""
    return {k: LOWER[v] for k, v in stated.items()}
