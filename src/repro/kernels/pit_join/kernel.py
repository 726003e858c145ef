"""Pallas TPU kernel: point-in-time search as a counting searchsorted.

The paper's §4.4 query subsystem must find, per observation, the *nearest
past* feature record.  A GPU/CPU implementation binary-searches — O(log M)
random accesses per query.  Random access is the wrong primitive for TPU
vector memory; the TPU-native restatement is:

    idx[q] = lo[q] + |{ r in [lo,hi) : table_ts[r] <= q_ts[q] }| - 1

i.e. a *count* — computable as a streaming broadcast-compare-reduce over
table tiles resident in VMEM, with zero gathers and full VPU utilization.
We trade O(log M) latency-bound probes for O(M/lanes) bandwidth-bound
compares, the right trade on a machine with 128-wide lanes and sequential
grids (same reasoning that makes flash-attention stream K/V tiles).

Grid: (num_query_blocks, num_table_blocks), table minor (sequential).  Each
table block is ``rows`` lane-dense rows of 128 timestamps; the kernel compares
one (1, 128) row at a time against the (Bq, 1) query columns and adds the
hits into a (Bq, 128) int32 accumulator in VMEM scratch, which is reduced
across lanes once, after the last table block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

__all__ = ["pit_search_kernel_call"]

_LANE = 128


def _pit_kernel(qts_ref, qlo_ref, qhi_ref, tab_ref, out_ref, acc_ref):
    tb = pl.program_id(1)
    n_tb = pl.num_programs(1)
    rows = tab_ref.shape[0]

    @pl.when(tb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qts = qts_ref[...]  # (Bq, 1)
    qlo = qlo_ref[...]
    qhi = qhi_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    acc = acc_ref[...]  # (Bq, 128)
    for r in range(rows):
        gidx = (tb * rows + r) * _LANE + lane  # (1, 128) global row index
        pred = (gidx >= qlo) & (gidx < qhi) & (tab_ref[pl.ds(r, 1), :] <= qts)
        acc = acc + pred.astype(jnp.int32)
    acc_ref[...] = acc

    @pl.when(tb == n_tb - 1)
    def _write():
        out_ref[...] = acc.sum(axis=1, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("q_block", "table_rows_per_block", "interpret")
)
def pit_search_kernel_call(
    table_ts2d: jnp.ndarray,
    q_ts: jnp.ndarray,
    q_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    *,
    q_block: int = 512,
    table_rows_per_block: int = 8,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Counting search.  table_ts2d: (Mr, 128) int32, row-major flattening of
    the padded table (padding rows carry ts = INT32_MAX and are excluded by
    q_hi anyway).  q_*: (B, 1) int32 with B % q_block == 0.  Returns (B, 1)
    int32 counts; caller derives idx = lo + count - 1, valid = count > 0.
    """
    if interpret is None:
        interpret = interpret_mode()
    mr, lane = table_ts2d.shape
    if lane != _LANE:
        raise ValueError(f"table must be (rows, {_LANE})")
    b = q_ts.shape[0]
    if b % q_block or mr % table_rows_per_block:
        raise ValueError("shapes must be pre-padded by ops.py")
    grid = (b // q_block, mr // table_rows_per_block)
    return pl.pallas_call(
        _pit_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_block, 1), lambda qb, tb: (qb, 0)),
            pl.BlockSpec((q_block, 1), lambda qb, tb: (qb, 0)),
            pl.BlockSpec((q_block, 1), lambda qb, tb: (qb, 0)),
            pl.BlockSpec((table_rows_per_block, _LANE), lambda qb, tb: (tb, 0)),
        ],
        out_specs=pl.BlockSpec((q_block, 1), lambda qb, tb: (qb, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((q_block, _LANE), jnp.int32)],
        interpret=interpret,
    )(q_ts, q_lo, q_hi, table_ts2d)
