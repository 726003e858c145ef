"""Mixture-of-Experts FFN: sort-based (dropping) dispatch + shard_map EP.

Dispatch never materializes the GShard (G, S, E, C) one-hot products — for
deepseek-v3's train_4k cell those are ~21 TB each in fp32 and the dispatch
einsum alone costs 2·T·E·C·D ≈ 3e17 FLOPs, ~400x the useful expert FLOPs.
Instead:

  1. argsort the (token, k)-assignments by expert id (stable: earlier
     tokens keep priority, matching GShard's cumsum drop policy),
  2. rank-within-expert via a vmapped searchsorted; rank >= capacity drops,
  3. scatter tokens into the (G, E, C, D) expert buffer (k static scatters
     of (G, S, D), indices unique by construction),
  4. batched expert FFN einsum,
  5. combine: k static gathers weighted by the (renormalized) router gates.

DISTRIBUTION — measured lesson (§Perf iter-1): expressing step 3/5 as
gather/scatter in pure GSPMD is catastrophic.  The SPMD partitioner cannot
shard a scatter/gather whose indexed dim is distributed, so it all-gathers
the (G, E, C, D) expert buffers over ``model`` every layer (~150 GB/layer
for ds3: measured 1.19 TB/dev peak, 36 s collective term).  The production
formulation is explicit: a ``shard_map`` expert-parallel block —

    tokens sharded over (pod, data, model)   [each device routes its own]
    local sort-dispatch into (G_loc, E, C, D)
    lax.all_to_all over 'model' on the E dim        -> owners compute FFN
    lax.all_to_all back, local combine

which moves exactly the true EP payload (tokens·k·cf·D / devices, ~0.55
GB/dev/layer each way on ds3) and nothing else.  Expert weights enter the
block P('model', None, None): the boundary resharding is the standard
FSDP weight all-gather.  The pure-GSPMD path remains for meshes without a
model axis (single-device tests) and for tiny token counts (decode cells,
where the gather's all-gather is bytes-trivial).

Router: softmax -> top-k -> renormalize among the chosen (deepseek V2
convention), with the switch-style load-balance auxiliary loss.

``moe_apply_einsum`` keeps the textbook GShard einsum formulation as the
test oracle: tests assert both production paths match it bit-for-bit in
fp32 at capacity factors where nothing drops, and match its drop policy
when capacity binds.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import dense_init
from repro.models.pspec import BATCH, constrain, current_mesh

__all__ = ["moe_init", "moe_apply", "moe_apply_einsum"]


def moe_init(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), fan_in=d, dtype=dtype),
        "w_up": dense_init(ks[2], (e, d, f), fan_in=d, dtype=dtype),
        "w_down": dense_init(ks[3], (e, f, d), fan_in=f, dtype=dtype),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, fs), dtype=dtype),
            "w_up": dense_init(ks[5], (d, fs), dtype=dtype),
            "w_down": dense_init(ks[6], (fs, d), fan_in=fs, dtype=dtype),
        }
    return p


def _capacity(cfg: ModelConfig, group_size: int, capacity_factor: float) -> int:
    c = int(group_size * cfg.top_k / cfg.num_experts * capacity_factor)
    return max(8, (c + 7) // 8 * 8)  # 8-aligned for TPU sublanes


def _group(x: jnp.ndarray, group_size: int) -> jnp.ndarray:
    b, s, d = x.shape
    tokens = b * s
    gs = min(group_size, tokens)
    while tokens % gs:  # snap to the largest divisor (e.g. MTP's B*(S-1))
        gs -= 1
    return x.reshape(tokens // gs, gs, d)


def _route(params, xg, cfg: ModelConfig):
    """Router probs -> (gate_k, idx_k, aux_loss).  fp32 for stability."""
    g, gs, _ = xg.shape
    e, k = cfg.num_experts, cfg.top_k
    logits = xg.astype(jnp.float32) @ params["router"]           # (G,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_k, idx_k = jax.lax.top_k(probs, k)                      # (G,S,k)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (switch): E * sum_e f_e * p_e.  f_e via bincount —
    # no (G,S,E) one-hot; f is an indicator (no grad path, as standard).
    me = probs.mean(axis=(0, 1))                                 # (E,)
    ce = jnp.bincount(idx_k[..., 0].reshape(-1), length=e) / float(g * gs)
    aux = (
        cfg.router_aux_coef
        * e
        * jnp.sum(me * jax.lax.stop_gradient(ce.astype(jnp.float32)))
    )
    return gate_k, idx_k, aux


def _dispatch_indices(idx_k: jnp.ndarray, e: int, cap: int):
    """(G,S,k) expert ids -> (dst (G,S,k) slot in [0, E*cap], keep (G,S,k)).

    dst == E*cap is the overflow sentinel (dropped assignment); all kept
    dst values are unique within a group by construction.
    """
    g, gs, k = idx_k.shape
    flat = idx_k.reshape(g, gs * k)
    order = jnp.argsort(flat, axis=1, stable=True)               # (G,S*k)
    e_sorted = jnp.take_along_axis(flat, order, axis=1)
    # first sorted position of each expert -> rank within expert
    starts = jax.vmap(lambda es: jnp.searchsorted(es, jnp.arange(e)))(e_sorted)
    rank = jnp.arange(gs * k)[None, :] - jnp.take_along_axis(
        starts, e_sorted, axis=1
    )                                                            # (G,S*k)
    keep_sorted = rank < cap
    dst_sorted = jnp.where(keep_sorted, e_sorted * cap + rank, e * cap)
    # unsort back to (s, k) layout
    garange = jnp.arange(g)[:, None]
    dst = jnp.zeros((g, gs * k), jnp.int32).at[garange, order].set(
        dst_sorted.astype(jnp.int32)
    )
    keep = jnp.zeros((g, gs * k), bool).at[garange, order].set(keep_sorted)
    return dst.reshape(g, gs, k), keep.reshape(g, gs, k)


def _expert_ffn(xe, params):
    """xe (..., E_loc, C, D) x expert-stacked weights -> (..., E_loc, C, D)."""
    hgate = jax.nn.silu(jnp.einsum("...ecd,edf->...ecf", xe, params["w_gate"]))
    hup = jnp.einsum("...ecd,edf->...ecf", xe, params["w_up"])
    return jnp.einsum("...ecf,efd->...ecd", hgate * hup, params["w_down"])


def _dispatch_ffn_combine_local(routed_params, xg, gate_k, idx_k, cfg, cap):
    """Steps 3-5 on local (already-sharded or unsharded) groups."""
    g, gs, d = xg.shape
    e, k = cfg.num_experts, cfg.top_k
    cdt = jnp.dtype(cfg.compute_dtype)

    dst, keep = _dispatch_indices(idx_k, e, cap)
    gate_k = gate_k * keep.astype(gate_k.dtype)                  # drop overflow

    garange = jnp.arange(g)[:, None]
    xe_flat = jnp.zeros((g, e * cap + 1, d), cdt)
    xgc = xg.astype(cdt)
    for j in range(k):
        xe_flat = xe_flat.at[garange, dst[:, :, j]].set(
            xgc, mode="drop", unique_indices=True
        )
    xe = xe_flat[:, : e * cap].reshape(g, e, cap, d)

    he = _expert_ffn(xe, routed_params)

    he_flat = jnp.concatenate(
        [he.reshape(g, e * cap, d), jnp.zeros((g, 1, d), he.dtype)], axis=1
    )
    y = jnp.zeros((g, gs, d), cdt)
    for j in range(k):
        yj = he_flat[garange, dst[:, :, j]]                      # (G,S,D)
        y = y + yj * gate_k[:, :, j, None].astype(cdt)
    return y


def _moe_gspmd(params, x, cfg, group_size, capacity_factor):
    """Pure-GSPMD path: single device / no model axis / tiny token counts."""
    b, s, d = x.shape
    xg = constrain(_group(x, group_size), BATCH, None, None)
    cap = _capacity(cfg, xg.shape[1], capacity_factor)
    gate_k, idx_k, aux = _route(params, xg, cfg)
    routed = {n: params[n] for n in ("w_gate", "w_up", "w_down")}
    y = _dispatch_ffn_combine_local(routed, xg, gate_k, idx_k, cfg, cap)
    return constrain(y, BATCH, None, None).reshape(b, s, d), aux


def _moe_ep(params, x, cfg, mesh, group_size, capacity_factor):
    """shard_map expert parallelism: tokens sharded over every mesh axis,
    experts owned by 'model' ranks, dispatch/return as explicit all-to-alls."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    ep = mesh.shape["model"]
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    tok_axes = (*dp_axes, "model")
    n_dev = mesh.size
    # Explicit reshard staging (measured, §Perf ds3 iter-3): without these
    # constraints the partitioner faces [tokens-sharded] -> [residual-layout]
    # cotangent reshards it cannot express and falls back to "involuntary
    # full rematerialization" — fully-replicated fp32 (B,S,D) buffers and
    # full-tensor all-reduces every MoE layer.
    x = constrain(x, BATCH, None, None)
    toks = constrain(x.reshape(b * s, d), (*dp_axes, "model"), None)
    t_loc = toks.shape[0] // n_dev
    gs = min(group_size, t_loc)
    while t_loc % gs:  # snap to the largest local divisor (odd token counts)
        gs -= 1
    cap = _capacity(cfg, gs, capacity_factor)

    def block(router, w_gate, w_up, w_down, toks_loc):
        xg = toks_loc.reshape(-1, gs, d)                         # (G_loc,S,D)
        gate_k, idx_k, aux = _route({"router": router}, xg, cfg)
        dst, keep = _dispatch_indices(idx_k, e, cap)
        gate_k = gate_k * keep.astype(gate_k.dtype)

        g = xg.shape[0]
        garange = jnp.arange(g)[:, None]
        cdt = jnp.dtype(cfg.compute_dtype)
        xgc = xg.astype(cdt)
        xe_flat = jnp.zeros((g, e * cap + 1, d), cdt)
        for j in range(k):
            xe_flat = xe_flat.at[garange, dst[:, :, j]].set(
                xgc, mode="drop", unique_indices=True
            )
        xe = xe_flat[:, : e * cap].reshape(g, e, cap, d)

        # -> expert owners: (G_loc, E, C, D) -> (G_loc*ep, E/ep, C, D)
        xe = jax.lax.all_to_all(xe, "model", split_axis=1, concat_axis=0,
                                tiled=True)
        he = _expert_ffn(xe, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down})
        # <- back to token owners
        he = jax.lax.all_to_all(he, "model", split_axis=0, concat_axis=1,
                                tiled=True)

        he_flat = jnp.concatenate(
            [he.reshape(g, e * cap, d), jnp.zeros((g, 1, d), he.dtype)], axis=1
        )
        y = jnp.zeros((g, gs, d), cdt)
        for j in range(k):
            yj = he_flat[garange, dst[:, :, j]]
            y = y + yj * gate_k[:, :, j, None].astype(cdt)
        aux = jax.lax.pmean(aux, dp_axes + ("model",))
        return y.reshape(-1, d), aux

    y, aux = shard_map(
        block,
        mesh=mesh,
        in_specs=(
            P(),                      # router: replicated (D x E is small)
            P("model", None, None),   # expert stacks: E owned by model ranks
            P("model", None, None),
            P("model", None, None),
            P(tok_axes, None),        # tokens: fully sharded
        ),
        out_specs=(P(tok_axes, None), P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"], toks)
    y = constrain(y, tok_axes, None)
    y = constrain(y.reshape(b, s, d), BATCH, None, None)
    return y, aux


def moe_apply(
    params: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    group_size: int = 2048,
    capacity_factor: float | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (y (B, S, D), aux_loss scalar)."""
    if capacity_factor is None:
        capacity_factor = cfg.capacity_factor
    b, s, d = x.shape
    tokens = b * s
    mesh = current_mesh()
    use_ep = (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and cfg.num_experts % mesh.shape["model"] == 0
        and tokens % mesh.size == 0
        and tokens // mesh.size >= 64   # decode cells: payload too small for EP
    )
    if use_ep:
        y, aux = _moe_ep(params, x, cfg, mesh, group_size, capacity_factor)
    else:
        y, aux = _moe_gspmd(params, x, cfg, group_size, capacity_factor)

    # -- shared experts (dense on all tokens; TP via GSPMD like any MLP) ------
    if "shared" in params:
        cdt = jnp.dtype(cfg.compute_dtype)
        sp = params["shared"]
        xc = x.astype(cdt)
        hs = jax.nn.silu(xc @ sp["w_gate"]) * (xc @ sp["w_up"])
        y = y + hs @ sp["w_down"]

    return y.astype(x.dtype), aux


# =============================================================================
# reference: textbook GShard einsum dispatch (test oracle; O(S^2·E·C) memory —
# never use on large cells)
# =============================================================================
def moe_apply_einsum(
    params: dict,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    group_size: int = 2048,
    capacity_factor: float = 1.25,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    xg = _group(x, group_size)
    g, gs, _ = xg.shape
    cap = _capacity(cfg, gs, capacity_factor)

    gate_k, idx_k, aux = _route(params, xg, cfg)

    # capacity positions: cumulative count of each expert along (s, k) order
    oh = jax.nn.one_hot(idx_k, e, dtype=jnp.float32)              # (G,S,k,E)
    flat = oh.reshape(g, gs * k, e)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(g, gs, k, e)
    pos = jnp.einsum("gske,gske->gsk", pos, oh)                   # (G,S,k)
    keep = pos < cap
    gate_k = gate_k * keep.astype(gate_k.dtype)

    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    pos_oh = pos_oh * keep[..., None]
    dispatch = jnp.einsum("gske,gskc->gsec", oh, pos_oh)          # 0/1
    combine = jnp.einsum("gsk,gske,gskc->gsec", gate_k, oh, pos_oh)

    cdt = jnp.dtype(cfg.compute_dtype)
    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(cdt), xg.astype(cdt))
    hgate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, params["w_gate"]))
    hup = jnp.einsum("gecd,edf->gecf", xe, params["w_up"])
    he = jnp.einsum("gecf,efd->gecd", hgate * hup, params["w_down"])
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(cdt), he)

    if "shared" in params:
        sp = params["shared"]
        xgc = xg.astype(cdt)
        hs = jax.nn.silu(xgc @ sp["w_gate"]) * (xgc @ sp["w_up"])
        y = y + hs @ sp["w_down"]

    return y.reshape(b, s, d).astype(x.dtype), aux
