"""The controls' precision: float32 rounded to bfloat16, the step below the
float32 that every configuration here states."""

from __future__ import annotations

import numpy as np

__all__ = ["as_bfloat16"]


def as_bfloat16(values) -> np.ndarray:
    """Round float32 values to bfloat16 (to nearest, ties to even, on the
    upper 16 bits) and widen them back to float32."""
    u = np.ascontiguousarray(values, np.float32).view(np.uint32)
    with np.errstate(over="ignore"):
        bias = np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
        rounded = (u + bias) & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)
