"""Pallas TPU kernel: causal GQA flash attention (forward).

The §Roofline tables show attention score materialization dominating the
memory term on every *_4k/32k train/prefill cell — (B, H, S, S) fp32 blocks
bounced through HBM dozens of times by unfused elementwise chains.  The
flash formulation keeps each (block_q, block_k) score tile in VMEM with
running (max, sum, acc) carries; HBM traffic falls from O(S²) to O(S·D).

TPU mapping:
  grid = (batch·kv_heads·q_groups, num_q_blocks, num_k_blocks), k minor —
  the sequential minor axis lets VMEM scratch (m, l, acc) carry across
  k-blocks of one q-block (same accumulator pattern as our rolling_agg
  kernel's history carry).
  Blocks are (block_q, head_dim) x (block_k, head_dim) — MXU-shaped tiles;
  head_dim is the lane dim (128-friendly for every assigned arch except
  gemma's 256, which tiles as 2x128 lanes transparently).
  Causality: k-blocks strictly above the diagonal are skipped via
  ``pl.when`` (they produce no useful work; the index map still visits
  them — Pallas grids are dense — but the body cost is one predicate).

The backward pass uses the same tiling with recomputed probabilities
(standard flash-bwd); this repo ships the forward kernel + XLA backward
(see ops.py) — the §Perf adjusted-memory analysis only claims the forward
savings.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

__all__ = ["flash_attention_kernel_call"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, causal: bool, scale: float):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qb * block_q
    k_start = kb * block_k

    # causal: skip blocks entirely above the diagonal
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (bq, bk)
        if causal:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(ki <= qi, s, NEG_INF)
        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p, v, precision=jax.lax.Precision.DEFAULT
        )
        m_ref[...] = m_new

    @pl.when(kb == n_kb - 1)
    def _write():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "interpret"),
)
def flash_attention_kernel_call(
    q: jnp.ndarray,   # (N, S, D)  N = batch*heads (flattened by ops.py)
    k: jnp.ndarray,   # (N, T, D)  already GQA-expanded to N by ops.py
    v: jnp.ndarray,
    *,
    block_q: int = 512,
    block_k: int = 512,
    causal: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = interpret_mode()
    n, s, d = q.shape
    t = k.shape[1]
    if s % block_q or t % block_k:
        raise ValueError("ops.py must pad S/T to block multiples")
    scale = 1.0 / (d ** 0.5)
    grid = (n, s // block_q, t // block_k)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda n_, qb, kb: (n_, qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda n_, qb, kb: (n_, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda n_, qb, kb: (n_, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda n_, qb, kb: (n_, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
