"""Arithmetic of the plain references: float64, or bfloat16 for the control.

Every reference routine takes a ``q`` that rounds each intermediate array
to the precision it is computed in.  ``F64`` leaves float64 alone; ``BF16``
rounds to bfloat16 after every step, reductions accumulating in float64
first (as an accelerator accumulates a bfloat16 reduction in a wider
register).  The control of a cell is its reference run with ``BF16``.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


def F64(x):
    return np.asarray(x, np.float64)


def BF16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


PRECISIONS = {"float64": F64, "bfloat16": BF16}
