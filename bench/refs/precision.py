"""The precision a reference computes in.

``float32`` is what every configuration states; ``bfloat16`` is the control:
the same reference, one precision lower, where every float input and every
intermediate result is rounded to bfloat16 (``ml_dtypes``, which ships with
JAX, computes each NumPy ufunc on bfloat16 arrays and rounds its result).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


def dtype(name: str):
    return DTYPES[name]
