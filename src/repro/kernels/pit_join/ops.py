"""jit'd wrapper for the point-in-time search kernel.

Responsibilities: pad the table to (rows, 128) tiles and the query batch to
the block multiple, run the counting-search kernel, and convert counts to
(row index, valid).  Timestamp dtype policy: the kernel compares int32; the
caller (core/pit.py) rebases int64 epoch-ms timestamps to a per-call int32
offset domain host-side and falls back to the jnp oracle when the span does
not fit — TPU int64 vector compare is emulated and not worth claiming.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["pit_search"]

from repro.kernels.pit_join.kernel import pit_search_kernel_call

_LANE = 128
_INT32_MAX = 2**31 - 1


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("q_block", "table_rows_per_block"))
def pit_search(
    table_ts: jnp.ndarray,
    q_ts: jnp.ndarray,
    q_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    *,
    q_block: int = 512,
    table_rows_per_block: int = 8,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """table_ts (M,) int32 sorted within [lo,hi) segments; q_* (B,) int32.

    Returns (idx (B,) int32, valid (B,) bool): the greatest r in [lo, hi)
    with table_ts[r] <= q_ts, or valid=False when the segment has no past
    record (the §4.3 distinction between "not materialized" and "no data" is
    made by the caller, which knows the materialization interval state).
    """
    m = table_ts.shape[0]
    b = q_ts.shape[0]
    tile = table_rows_per_block * _LANE
    m_pad = _round_up(max(m, 1), tile)
    b_pad = _round_up(max(b, 1), q_block)

    tab = jnp.full((m_pad,), _INT32_MAX, jnp.int32).at[:m].set(table_ts)
    tab2d = tab.reshape(m_pad // _LANE, _LANE)

    def pad_q(x, fill):
        return jnp.full((b_pad, 1), fill, jnp.int32).at[:b, 0].set(x.astype(jnp.int32))

    counts = pit_search_kernel_call(
        tab2d,
        pad_q(q_ts, 0),
        pad_q(q_lo, 0),
        pad_q(q_hi, 0),  # padded queries have hi=0 => empty range => count 0
        q_block=q_block,
        table_rows_per_block=table_rows_per_block,
    )[:b, 0]
    idx = (q_lo + counts - 1).astype(jnp.int32)
    return idx, counts > 0
