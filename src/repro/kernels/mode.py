"""Whether Pallas kernels run compiled or in the interpreter.

The one place that decides it: kernels compile for the TPU and run in
Pallas interpret mode on every other backend (the CPU test runs).  The
answer comes from the backend JAX runs on, never from a caller's option.
"""

from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"
