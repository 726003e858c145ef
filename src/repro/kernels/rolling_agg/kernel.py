"""Pallas TPU kernel: rolling-window sum as one masked MXU matmul per block.

TPU adaptation of the paper's §3.1.6 DSL-optimized rolling aggregation.  A
Spark implementation shuffles rows into windows; on TPU we exploit two
hardware facts instead:

  1. The Pallas grid is *sequential*, so a VMEM scratch buffer can carry the
     trailing ``hist`` rows across row-blocks (flash-attention-style carry).
  2. A windowed sum is a matmul against a 0/1 window mask, which the MXU
     does at full rate.

Layout: rows on lanes, features on sublanes.  A block holds B rows of the
(F, N) transposed value matrix (F padded to 8 sublanes by ops.py, so a
narrow feature set costs 8 rows of padding, not 128 lanes) and the matching
(1, B) slice of window starts.  With ``ext`` = the carried H history columns
followed by the block's B columns, row j of the block sums

    out[:, j] = sum(ext[:, k] for k in [rel[j], H + j]),
    rel[j] = starts[j] - (b*B - H)        (its window start in ext coords)

i.e. ``out = ext @ W`` with W[k, j] = rel[j] <= k <= H + j — one (F, H+B) @
(H+B, B) matmul.  Every output is a direct sum of its window (no prefix
difference, so no cancellation across long columns).

Grid: 1-D over row blocks.  VMEM working set per step is dominated by the
(H+B, B) mask and its iota/compare temporaries; ``ops.max_hist`` derives the
deepest history that fits ``VMEM_LIMIT_BYTES`` from that working set.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

__all__ = ["VMEM_LIMIT_BYTES", "rolling_sum_kernel_call"]

#: scoped VMEM the kernel asks the compiler for (v5e has 128 MiB per core;
#: the compiler's default scope is 16 MiB)
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _rolling_sum_kernel(starts_ref, vals_ref, out_ref, hist_ref):
    b = pl.program_id(0)
    blk = vals_ref.shape[1]
    hist = hist_ref.shape[1]

    @pl.when(b == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    ext = jnp.concatenate([hist_ref[...], vals_ref[...]], axis=1)  # (F, H+B)
    rel = starts_ref[...] - (b * blk - hist)  # (1, B)
    k = jax.lax.broadcasted_iota(jnp.int32, (hist + blk, blk), 0)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1) + hist
    window = ((k >= rel) & (k <= last)).astype(jnp.float32)  # (H+B, B)
    out_ref[...] = jax.lax.dot(ext, window, precision=jax.lax.Precision.HIGHEST)

    # carry the trailing H columns of raw values into the next block
    hist_ref[...] = ext[:, blk:]


@functools.partial(jax.jit, static_argnames=("block_rows", "hist", "interpret"))
def rolling_sum_kernel_call(
    values_t: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    block_rows: int = 256,
    hist: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """values_t: (F, N) float32, starts: (1, N) int32, window spans <= hist.

    F % 8 == 0, N % block_rows == 0 and 128-aligned block_rows/hist are
    ops.py's responsibility; this is the raw pallas_call wrapper.
    """
    if interpret is None:
        interpret = interpret_mode()
    feat, n = values_t.shape
    if n % block_rows or block_rows % 128 or hist % 128:
        raise ValueError(
            f"N={n}, block_rows={block_rows}, hist={hist} must be 128-aligned "
            "with N a multiple of block_rows"
        )
    return pl.pallas_call(
        _rolling_sum_kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda b: (0, b)),  # starts
            pl.BlockSpec((feat, block_rows), lambda b: (0, b)),  # values
        ],
        out_specs=pl.BlockSpec((feat, block_rows), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((feat, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((feat, hist), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(starts, values_t)
