"""MoE dispatch equivalence: sort-based (production) vs GShard einsum
(oracle), single-device GSPMD path vs shard_map EP path (subprocess with 8
fake devices), drop policies, gradients."""

import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import moe
from repro.models.config import ModelConfig


def _cfg(e=8, k=2, shared=1):
    return ModelConfig(
        name="t", family="moe", num_layers=2, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, moe=True, num_experts=e,
        top_k=k, moe_d_ff=16, num_shared_experts=shared, d_ff=16,
        param_dtype="float32", compute_dtype="float32",
    )


def _params(cfg, seed=0):
    return moe.moe_init(jax.random.PRNGKey(seed), cfg, dtype=jnp.float32)


@pytest.mark.parametrize("e,k", [(4, 1), (8, 2), (16, 4)])
def test_sort_matches_einsum_no_drop(e, k):
    cfg = _cfg(e, k)
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    y1, a1 = moe.moe_apply(p, x, cfg, group_size=32, capacity_factor=float(e))
    y2, a2 = moe.moe_apply_einsum(p, x, cfg, group_size=32, capacity_factor=float(e))
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(a1, a2, rtol=1e-6)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), cf=st.floats(1.0, 2.0))
def test_sort_matches_einsum_drop_policy(seed, cf):
    """When capacity binds, both paths must drop the SAME assignments
    (GShard priority: earlier tokens, then lower expert-choice rank)."""
    cfg = _cfg(8, 2)
    p = _params(cfg, seed % 7)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 64, 32))
    y1, _ = moe.moe_apply(p, x, cfg, group_size=64, capacity_factor=cf)
    y2, _ = moe.moe_apply_einsum(p, x, cfg, group_size=64, capacity_factor=cf)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_gradients_match_oracle():
    cfg = _cfg()
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))

    def loss_sort(p):
        return moe.moe_apply(p, x, cfg, group_size=16, capacity_factor=8.0)[0].sum()

    def loss_ein(p):
        return moe.moe_apply_einsum(p, x, cfg, group_size=16, capacity_factor=8.0)[0].sum()

    g1, g2 = jax.grad(loss_sort)(p), jax.grad(loss_ein)(p)
    worst = max(
        jax.tree.leaves(jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), g1, g2))
    )
    assert worst < 1e-4


def test_aux_loss_balanced_vs_skewed():
    """The switch aux loss must penalize a skewed router more than a uniform
    one (sanity of the load-balance objective)."""
    cfg = _cfg(8, 2, shared=0)
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 32))
    _, aux_learned = moe.moe_apply(p, x, cfg, group_size=128)
    # force skew: router always picks expert 0 by biasing its column
    p_skew = dict(p)
    p_skew["router"] = p["router"].at[:, 0].add(100.0)
    _, aux_skew = moe.moe_apply(p_skew, x, cfg, group_size=128)
    assert float(aux_skew) > float(aux_learned)


_EP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models import moe
    from repro.models.config import ModelConfig
    from repro.models.pspec import activation_mesh

    cfg = ModelConfig(
        name="t", family="moe", num_layers=2, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, moe=True, num_experts=8,
        top_k=2, moe_d_ff=16, num_shared_experts=1, d_ff=16,
        param_dtype="float32", compute_dtype="float32",
    )
    p = moe.moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 32))

    y_ref, a_ref = moe.moe_apply_einsum(p, x, cfg, group_size=64,
                                        capacity_factor=8.0)
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh, activation_mesh(mesh):
        y_ep, a_ep = jax.jit(
            lambda p, x: moe.moe_apply(p, x, cfg, group_size=64,
                                       capacity_factor=8.0)
        )(p, x)
        # gradient through the EP block
        g = jax.jit(jax.grad(lambda p: moe.moe_apply(
            p, x, cfg, group_size=64, capacity_factor=8.0)[0].sum()))(p)
    g_ref = jax.grad(lambda p: moe.moe_apply_einsum(
        p, x, cfg, group_size=64, capacity_factor=8.0)[0].sum())(p)
    gd = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), g, g_ref)))
    print("EP_RESULT " + json.dumps({
        "y_diff": float(jnp.abs(y_ep - y_ref).max()),
        "aux_diff": float(abs(a_ep - a_ref)),
        "grad_diff": gd,
    }))
    """
)


@pytest.mark.proc
def test_ep_shard_map_matches_oracle_subprocess():
    proc = subprocess.run(
        [sys.executable, "-c", _EP_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("EP_RESULT")]
    res = json.loads(line[0].split(" ", 1)[1])
    assert res["y_diff"] < 1e-4, res
    assert res["aux_diff"] < 1e-4, res
    assert res["grad_diff"] < 5e-3, res
