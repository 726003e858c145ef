"""Sharding rules: param/batch/cache pytrees -> PartitionSpecs.

Strategy (1000+-chip posture):
  * params — TP over ``model`` (attention heads / FFN hidden / vocab /
    experts) + FSDP over ``data`` on the complementary dim; replicated over
    ``pod`` (gradients cross pods once per step — the hierarchical-DCN
    pattern).  Scan-stacked leading dims are never sharded.
  * batch — over every non-model axis; falls back to replication when the
    global batch does not divide the shard count (long_500k's batch=1).
  * caches/states — batch-sharded; the KV/state "width" dim shards over
    ``model`` when divisible (heads for GQA, SSM heads for mamba); otherwise
    the SEQUENCE dim shards over ``model`` (sequence-parallel attention —
    MQA and long-context cells), so no cell ever leaves the model axis idle.

Rules are name-based over tree paths, rank-generalized: a leaf's base spec
is right-aligned and leading (scan) dims get None.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import batch_axes
from repro.models.config import ModelConfig

__all__ = [
    "param_specs",
    "param_shardings",
    "batch_specs",
    "cache_specs",
    "tree_shardings",
    "opt_state_specs",
]

FSDP = "data"
TP = "model"

# leaf name -> base spec (right-aligned over the trailing dims)
_BASE_RULES: dict[str, tuple] = {
    # embeddings / heads
    "embed": (TP, FSDP),          # (V, D): vocab over model => sharded xent
    "lm_head": (FSDP, TP),        # (D, V)
    "pos_dec": (None, None),
    "vision_proj": (None, FSDP),
    # attention
    "wq": (FSDP, TP),
    "wk": (FSDP, TP),
    "wv": (FSDP, TP),
    "wo": (TP, FSDP),
    "bq": (TP,),
    "bk": (TP,),
    "bv": (TP,),
    # MLA
    "wq_a": (FSDP, None),
    "wq_b": (None, TP),
    "wkv_a": (FSDP, None),
    "wkv_b": (None, TP),
    # dense MLP
    "w_gate": (FSDP, TP),
    "w_up": (FSDP, TP),
    "w_down": (TP, FSDP),
    # MoE (expert-stacked leaves are rank-3; E is the leading dim => EP)
    "router": (FSDP, None),
    "moe.w_gate": (TP, FSDP, None),
    "moe.w_up": (TP, FSDP, None),
    "moe.w_down": (TP, None, FSDP),
    # mamba
    "w_in": (FSDP, TP),
    "w_out": (TP, FSDP),
    "conv_w": (None, TP),
    "conv_b": (TP,),
    "gate_norm": (TP,),
    # mtp
    "proj": (FSDP, TP),
}

_MOE_PARENT = "ffn"  # MoE leaves live under layers' "ffn" subtree


def _leaf_rule(path: tuple, leaf) -> tuple:
    names = [p.key for p in path if hasattr(p, "key")]
    name = names[-1] if names else ""
    # expert-stacked MoE weights: under ffn with rank >= 3 base
    if name in ("w_gate", "w_up", "w_down") and _MOE_PARENT in names:
        # distinguish MoE expert stacks from the (dense) "shared" experts
        if "shared" not in names:
            return _BASE_RULES[f"moe.{name}"]
    return _BASE_RULES.get(name, ())


def _right_align(base: tuple, ndim: int) -> P:
    if not base:
        return P()
    if ndim < len(base):
        # scalar-ish leaf (reduced configs can shrink ranks); replicate
        return P()
    return P(*((None,) * (ndim - len(base)) + tuple(base)))


def _drop_missing_axes(spec: P, mesh) -> P:
    """Replace axis names absent from the mesh with None (elasticity)."""
    cleaned = []
    for s in spec:
        if s is None:
            cleaned.append(None)
        elif isinstance(s, (tuple, list)):
            kept = tuple(a for a in s if a in mesh.axis_names)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(s if s in mesh.axis_names else None)
    return P(*cleaned)


def _divisible(spec: P, shape: tuple, mesh) -> P:
    """Drop shardings that do not divide the dim (GSPMD would pad; for
    tiny dims — MQA's single KV head — padding 15/16 of the axis is worse
    than replicating)."""
    out = []
    for dim, s in zip(shape, spec):
        if s is None:
            out.append(None)
            continue
        axes = (s,) if isinstance(s, str) else tuple(s)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(s if dim % size == 0 and dim >= size else None)
    return P(*out)


def param_specs(params_shape: Any, cfg: ModelConfig, mesh) -> Any:
    """PartitionSpec pytree matching a params pytree (arrays or
    ShapeDtypeStructs)."""

    def one(path, leaf):
        base = _leaf_rule(path, leaf)
        spec = _right_align(base, leaf.ndim)
        spec = _drop_missing_axes(spec, mesh)
        # pad spec to rank
        spec = P(*(tuple(spec) + (None,) * (leaf.ndim - len(spec))))
        return _divisible(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def tree_shardings(spec_tree: Any, mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def param_shardings(params_shape: Any, cfg: ModelConfig, mesh) -> Any:
    return tree_shardings(param_specs(params_shape, cfg, mesh), mesh)


def _batch_spec_first_dim(global_batch: int, mesh) -> Optional[tuple]:
    ba = batch_axes(mesh)
    size = int(np.prod([mesh.shape[a] for a in ba]))
    if global_batch % size == 0 and global_batch >= size:
        return ba
    # try data-only
    if "data" in mesh.axis_names and global_batch % mesh.shape["data"] == 0:
        return ("data",)
    return None


def batch_specs(batch_shape: Any, mesh) -> Any:
    """Sharding specs for a training/prefill batch pytree (tokens, frames,
    patch_embeds...): first dim over the batch axes, rest replicated."""

    def one(leaf):
        first = _batch_spec_first_dim(leaf.shape[0], mesh)
        return P(*((first,) + (None,) * (leaf.ndim - 1)))

    return jax.tree.map(one, batch_shape)


def cache_specs(cache_shape: Any, cfg: ModelConfig, mesh) -> Any:
    """Decode-state sharding.  Name-aware: see module docstring."""
    tp_size = mesh.shape[TP] if TP in mesh.axis_names else 1

    def one(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        name = names[-1] if names else ""
        if leaf.ndim == 0:
            return P()
        if name == "t":
            return P()
        if name in ("k", "v"):          # (.., B, S, KV, hd)
            base = ["__batch__", None, None, None]
        elif name == "pos":              # (.., B, S)
            base = ["__batch__", None]
        elif name in ("c_kv", "k_pe"):   # (.., B, S, R/pe) — MLA latent
            base = ["__batch__", TP if leaf.shape[-2] % tp_size == 0 else None, None]
        elif name == "ssm":              # (.., B, H, P, N)
            base = [
                "__batch__",
                TP if leaf.shape[-3] % tp_size == 0 else None,
                None,
                None,
            ]
        elif name == "conv":             # (.., B, W-1, C)
            base = ["__batch__", None, TP if leaf.shape[-1] % tp_size == 0 else None]
        elif name in ("self_k", "self_v", "mem_k", "mem_v"):  # (L,B,S,H,hd)
            heads_ok = leaf.shape[-2] % tp_size == 0
            base = [
                None, "__batch__",
                None if heads_ok else TP,
                TP if heads_ok else None,
                None,
            ]
        else:
            return P(*([None] * leaf.ndim))
        if name in ("k", "v"):
            heads_ok = leaf.shape[-2] % tp_size == 0
            if heads_ok:
                base[-2] = TP          # shard KV heads
            elif leaf.shape[-3] % tp_size == 0:
                base[-3] = TP          # MQA: sequence-parallel cache
        # batch placement: the '__batch__' slot may not be base[0] (enc-dec
        # caches carry a leading layer-stack dim)
        b_slot = base.index("__batch__")
        batch_size = leaf.shape[leaf.ndim - len(base) + b_slot]
        base[b_slot] = _batch_spec_first_dim(batch_size, mesh)
        spec = P(*((None,) * (leaf.ndim - len(base)) + tuple(base)))
        return _divisible(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def opt_state_specs(opt_shape, param_specs_tree, mesh=None):
    """Optimizer-state specs mirroring the param specs (quantized moments:
    q inherits the param spec, per-block scales drop the last-dim shard).

    ZeRO-across-pod: params replicate over ``pod`` (gradients cross pods
    once per step), but optimizer MOMENTS need not — each pod owns a slice
    (first spec-free dim divisible by the pod count; for scanned stacks
    that's the layer dim).  GSPMD turns the update into reduce-scatter(grad
    over pod) + update + all-gather(params) — exactly ZeRO-1.  Halves the
    biggest per-device state term on the 671B multi-pod cell."""

    def _pod_shard(ps, shape) -> P:
        if (
            mesh is None
            or "pod" not in getattr(mesh, "axis_names", ())
            or mesh.shape["pod"] == 1
        ):
            return ps
        npod = mesh.shape["pod"]
        entries = list(ps) + [None] * (len(shape) - len(tuple(ps)))
        for i, (e, dim) in enumerate(zip(entries, shape)):
            if e is None and dim % npod == 0 and dim >= npod:
                entries[i] = "pod"
                return P(*entries)
        return ps

    def mirror_moment(ps, leaf):
        if isinstance(leaf, dict):  # {"q": ..., "scale": ...}
            qs = _pod_shard(ps, leaf["q"].shape)
            scale_spec = (
                P(*(tuple(qs)[:-1] + (None,))) if len(tuple(qs)) else P()
            )
            return {"q": qs, "scale": scale_spec}
        return _pod_shard(ps, leaf.shape)

    def mirror(moment_tree):
        # walk the param-spec tree (specs are leaves) against the moment
        # tree, whose leaves are arrays or {"q","scale"} dicts per param.
        flat_specs, treedef = jax.tree_util.tree_flatten(
            param_specs_tree, is_leaf=lambda x: isinstance(x, P)
        )
        flat_moments = treedef.flatten_up_to(moment_tree)
        out = [mirror_moment(s, m) for s, m in zip(flat_specs, flat_moments)]
        return treedef.unflatten(out)

    return {"count": P(), "m": mirror(opt_shape["m"]), "v": mirror(opt_shape["v"])}
