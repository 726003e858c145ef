"""Pure-jnp oracle for rolling-window aggregation.

``out[i] = agg(values[starts[i] : i+1])`` — the contiguous row span from
``starts[i]`` through row ``i``, the kernels' contract.  Rows are sorted by
(entity, timestamp) upstream, and the DSL computes ``starts`` so that no span
crosses an entity boundary; it reads row ``i``'s feature at the last row of
its (entity, timestamp) tie, so rows that tie with ``i`` and sort after it are
in its window there, not in this span.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["rolling_sum_ref", "rolling_agg_ref"]


def rolling_sum_ref(values: jnp.ndarray, starts: jnp.ndarray) -> jnp.ndarray:
    """O(N^2) but trivially correct: masked sum per row.

    values: (N, F) float; starts: (N,) int32.  Returns (N, F) float32.
    """
    n = values.shape[0]
    idx = jnp.arange(n)
    # mask[i, j] = starts[i] <= j <= i
    mask = (idx[None, :] >= starts[:, None]) & (idx[None, :] <= idx[:, None])
    return mask.astype(jnp.float32) @ values.astype(jnp.float32)


def rolling_agg_ref(values: jnp.ndarray, starts: jnp.ndarray, agg: str) -> jnp.ndarray:
    """Oracle for every agg the DSL exposes (sum/mean/count/min/max)."""
    n, _ = values.shape
    idx = jnp.arange(n)
    mask = (idx[None, :] >= starts[:, None]) & (idx[None, :] <= idx[:, None])
    v32 = values.astype(jnp.float32)
    if agg == "sum":
        return mask.astype(jnp.float32) @ v32
    if agg == "count":
        cnt = (idx + 1 - starts).astype(jnp.float32)
        return jnp.broadcast_to(cnt[:, None], values.shape).astype(jnp.float32)
    if agg == "mean":
        s = mask.astype(jnp.float32) @ v32
        cnt = (idx + 1 - starts).astype(jnp.float32)[:, None]
        return s / jnp.maximum(cnt, 1.0)
    if agg == "min":
        big = jnp.where(mask[:, :, None], v32[None, :, :], jnp.inf)
        return jnp.min(big, axis=1)
    if agg == "max":
        small = jnp.where(mask[:, :, None], v32[None, :, :], -jnp.inf)
        return jnp.max(small, axis=1)
    raise ValueError(f"unknown agg {agg!r}")
