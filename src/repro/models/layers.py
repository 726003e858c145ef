"""Shared neural-net layers: norms, rotary embeddings, MLP variants, inits.

Pure-function JAX (param pytrees of jnp arrays) — no framework dependency,
which keeps pjit sharding rules a simple path->PartitionSpec map.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "layer_norm",
    "rope",
    "apply_rope",
    "mlp_apply",
    "mlp_init",
    "dense_init",
    "reduce_boundary",
    "Param",
]


def reduce_boundary(x: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Pin the operand of a row-parallel (TP) matmul to a compact dtype.

    XLA folds ``convert(f32->bf16)`` into downstream dots, silently running
    the dot — and therefore the partial-sum all-reduce over ``model`` — in
    f32: 2x wire bytes (measured: 47 GiB of f32 all-reduce on a 5-layer ds3
    probe, §Perf iter-4).  An optimization barrier on the bf16 value keeps
    the reduction bf16.  AD passes cotangents through the barrier, so the
    backward dot's all-reduce is bf16 too (the gradient-compression lever)."""
    return jax.lax.optimization_barrier(x.astype(dtype))


def dense_init(key, shape, fan_in: Optional[int] = None, dtype=jnp.bfloat16):
    fan = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / math.sqrt(max(fan, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(
    x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray, eps: float = 1e-5
) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    out = out * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return out.astype(x.dtype)


# -- rotary position embeddings ------------------------------------------------
def rope(
    positions: jnp.ndarray, dim: int, theta: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """positions (...,) -> (cos, sin) of shape (..., dim//2), float32."""
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x (..., S, H, D) with cos/sin (..., S, D//2) — rotate-half convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x32_1 * c - x32_2 * s, x32_2 * c + x32_1 * s], axis=-1
    ).astype(x.dtype)


# -- MLPs ---------------------------------------------------------------------
def mlp_init(key, d_model: int, d_ff: int, variant: str, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 3)
    if variant in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
            "w_up": dense_init(ks[1], (d_model, d_ff), dtype=dtype),
            "w_down": dense_init(ks[2], (d_ff, d_model), dtype=dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "w_down": dense_init(ks[1], (d_ff, d_model), dtype=dtype),
    }


def mlp_apply(params: dict, x: jnp.ndarray, variant: str) -> jnp.ndarray:
    from repro.models.pspec import BATCH, constrain  # local: avoid cycle

    if variant in ("swiglu", "geglu"):
        act = jax.nn.silu if variant == "swiglu" else functools.partial(
            jax.nn.gelu, approximate=True
        )
        g = act(x @ params["w_gate"])
        h = g * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"], approximate=True)
    # Pin the hidden's F dim to the TP axis: without this anchor GSPMD may
    # materialize the full-width hidden per device (observed on the gemma
    # train cell: f32[B/dp, S, 16384] instead of [.., 1024]).
    h = constrain(h, *((BATCH,) + (None,) * (h.ndim - 2) + ("model",)))
    return reduce_boundary(h, x.dtype) @ params["w_down"]


Param = jnp.ndarray
