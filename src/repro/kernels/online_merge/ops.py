"""jit'd wrappers + host routing for the online-merge write path.

Two device-side merge variants share the partitioned plane layout:

  * ``merge_at_slots`` — the DEVICE-RESIDENT hot path.  The store's sorted
    key index already resolved each winner record to its (partition, slot),
    so the compare-and-update is an O(batch) gather/lex-compare/scatter over
    donated planes (``donate_argnums``): the table buffers are rewritten in
    place, nothing table-sized crosses host<->device, and only the routed
    batch (coords + winner planes + feature rows) is uploaded.  The
    latest-wins decision itself still happens ON DEVICE — host tallies come
    from the merge plan and agree by construction — which is what makes the
    device planes a self-contained Algorithm-2 state machine (safe to replay
    for geo-replication).
  * ``merge`` / ``route_and_merge`` — the index-free streaming variant:
    route a flat per-id-winner batch to hash partitions, pad to lane shapes,
    split int64 ids/timestamps into int32 planes, and let the Pallas kernel
    broadcast-match every slot block (O(C·Q) scan).  Retained as the parity
    reference and for callers without a host-side slot index.

``gather_slot_ts`` is the read half of the resident protocol: fetch the
current (event_ts, creation_ts) planes at resolved coords so the host merge
plan can compute exact insert/override/no-op tallies against device truth
without pulling whole planes back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.online_lookup.ops import (
    combine_i64,
    route_flat,
    split_i64,
)
from repro.kernels.online_merge.kernel import i64_gt, merge_kernel_call

__all__ = [
    "gather_slot_ts",
    "merge",
    "merge_at_slots",
    "route_and_merge",
    "route_flat",
]

_LANE = 128
_SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
def merge_at_slots(
    keys_lo: jnp.ndarray,
    keys_hi: jnp.ndarray,
    ev_lo: jnp.ndarray,
    ev_hi: jnp.ndarray,
    cr_lo: jnp.ndarray,
    cr_hi: jnp.ndarray,
    values: jnp.ndarray,
    part: jnp.ndarray,
    slot: jnp.ndarray,
    q_klo: jnp.ndarray,
    q_khi: jnp.ndarray,
    is_new: jnp.ndarray,
    q_ev_lo: jnp.ndarray,
    q_ev_hi: jnp.ndarray,
    cr_planes: jnp.ndarray,
    q_values: jnp.ndarray,
) -> tuple[jnp.ndarray, ...]:
    """Donated-buffer compare-and-update at index-resolved slots.

    All seven table planes are DONATED — the update happens in the planes'
    existing device buffers; callers must drop their references and adopt
    the returned arrays.  Batch arrays are per-unique-id winner records in
    any order: ``part``/``slot`` (G,) int32 target coords, ``q_klo/q_khi``
    the key planes to stamp where ``is_new`` (fresh inserts, possibly into
    recycled slots), ``q_ev_lo/q_ev_hi`` winner event_ts planes,
    ``cr_planes`` (2,) int32 [lo, hi] of the shared batch creation_ts, and
    ``q_values`` (G, D) feature rows.  Coords must be distinct (the merge
    plan guarantees one winner per id, the index one slot per id).

    Algorithm 2, online branch, per coord: new slots always take the
    record; live slots take it iff (ev, cr) >lex (old_ev, old_cr).  The
    compare runs on device against device truth, so host mirrors can be
    arbitrarily stale.
    """
    old_elo = ev_lo[part, slot]
    old_ehi = ev_hi[part, slot]
    old_clo = cr_lo[part, slot]
    old_chi = cr_hi[part, slot]
    crlo = jnp.broadcast_to(cr_planes[0], part.shape)
    crhi = jnp.broadcast_to(cr_planes[1], part.shape)

    ev_gt = i64_gt(q_ev_hi, q_ev_lo, old_ehi, old_elo)
    ev_eq = (q_ev_hi == old_ehi) & (q_ev_lo == old_elo)
    cr_gt = i64_gt(crhi, crlo, old_chi, old_clo)
    win = is_new | ev_gt | (ev_eq & cr_gt)

    keys_lo = keys_lo.at[part, slot].set(
        jnp.where(is_new, q_klo, keys_lo[part, slot])
    )
    keys_hi = keys_hi.at[part, slot].set(
        jnp.where(is_new, q_khi, keys_hi[part, slot])
    )
    ev_lo = ev_lo.at[part, slot].set(jnp.where(win, q_ev_lo, old_elo))
    ev_hi = ev_hi.at[part, slot].set(jnp.where(win, q_ev_hi, old_ehi))
    cr_lo = cr_lo.at[part, slot].set(jnp.where(win, crlo, old_clo))
    cr_hi = cr_hi.at[part, slot].set(jnp.where(win, crhi, old_chi))
    values = values.at[part, slot].set(
        jnp.where(win[:, None], q_values, values[part, slot])
    )
    return keys_lo, keys_hi, ev_lo, ev_hi, cr_lo, cr_hi, values


@jax.jit
def gather_slot_ts(
    ev_lo: jnp.ndarray,
    ev_hi: jnp.ndarray,
    cr_lo: jnp.ndarray,
    cr_hi: jnp.ndarray,
    part: jnp.ndarray,
    slot: jnp.ndarray,
) -> tuple[jnp.ndarray, ...]:
    """(part, slot) (G,) int32 -> the four int32 timestamp planes at those
    coords — the O(batch) read that lets the host merge plan see device
    truth without syncing whole planes."""
    return (
        ev_lo[part, slot],
        ev_hi[part, slot],
        cr_lo[part, slot],
        cr_hi[part, slot],
    )


@functools.partial(jax.jit, static_argnames=("slot_block",))
def merge(
    keys_lo: jnp.ndarray,
    keys_hi: jnp.ndarray,
    ev_lo: jnp.ndarray,
    ev_hi: jnp.ndarray,
    cr_lo: jnp.ndarray,
    cr_hi: jnp.ndarray,
    values: jnp.ndarray,
    q_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    q_ev_lo: jnp.ndarray,
    q_ev_hi: jnp.ndarray,
    q_values: jnp.ndarray,
    creation_planes: jnp.ndarray,
    *,
    slot_block: int = 512,
) -> tuple[jnp.ndarray, ...]:
    """Pre-routed merge.  Table planes (P, C) (+ values (P, C, D)), routed
    queries (P, Q) (+ values (P, Q, D)) -> updated ev/cr planes + values.
    Handles slot-block/lane padding and the kernel's transposed value
    layout; at most one query per key."""
    p, c = keys_lo.shape
    d = values.shape[-1]
    c_pad = _round_up(c, min(slot_block, _round_up(c, _LANE)))
    sb = min(slot_block, c_pad)
    c_pad = _round_up(c_pad, sb)
    q = q_lo.shape[1]
    q_pad = _round_up(q, _LANE)
    d_pad = _round_up(d, _SUBLANE)

    def pad2(x, n, fill):
        return jnp.pad(x, ((0, 0), (0, n - x.shape[1])), constant_values=fill)

    def values_t(x, n):  # (P, N, D) -> (P, D_pad, N_pad), zero-padded
        x = jnp.swapaxes(x, 1, 2)
        return jnp.pad(x, ((0, 0), (0, d_pad - d), (0, n - x.shape[2])))

    # table pads are empty slots (keys -1); query pads carry (-2, -2), which
    # matches neither live keys nor the empty sentinel
    out = merge_kernel_call(
        pad2(keys_lo, c_pad, -1), pad2(keys_hi, c_pad, -1),
        pad2(ev_lo, c_pad, 0), pad2(ev_hi, c_pad, 0),
        pad2(cr_lo, c_pad, 0), pad2(cr_hi, c_pad, 0),
        values_t(values, c_pad),
        pad2(q_lo, q_pad, -2)[..., None], pad2(q_hi, q_pad, -2)[..., None],
        pad2(q_ev_lo, q_pad, 0)[..., None], pad2(q_ev_hi, q_pad, 0)[..., None],
        values_t(q_values, q_pad),
        creation_planes,
        slot_block=sb,
    )
    ev_lo_u, ev_hi_u, cr_lo_u, cr_hi_u, vals_t = out
    return (
        ev_lo_u[:, :c],
        ev_hi_u[:, :c],
        cr_lo_u[:, :c],
        cr_hi_u[:, :c],
        jnp.swapaxes(vals_t[:, :d, :c], 1, 2),
    )


def route_and_merge(
    keys_lo: np.ndarray,
    keys_hi: np.ndarray,
    event_ts: np.ndarray,
    creation_ts: np.ndarray,
    values: np.ndarray,
    ids: np.ndarray,
    ev: np.ndarray,
    vals: np.ndarray,
    batch_creation_ts: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flat merge path: winner records ids (B,) int64 (UNIQUE), ev (B,) int64,
    vals (B, D) f32 against table planes (P, C) + int64 ts + values (P, C, D).

    Returns updated host-side (event_ts, creation_ts, values) as int64/f32.
    """
    num_p, _ = keys_lo.shape
    ids = np.asarray(ids, np.int64)
    if len(ids) == 0:
        return event_ts.copy(), creation_ts.copy(), values.copy()
    q_ids, _, _, q_ev, q_vals = route_flat(
        num_p, ids, np.asarray(ev, np.int64), np.asarray(vals, np.float32)
    )
    q_lo, q_hi = split_i64(q_ids)
    # padding slots carry ids == -2 on BOTH planes (split of -2 is
    # (-2, -1)); overwrite the planes where the id is the pad sentinel so
    # they can never alias a live key's planes.
    pad = q_ids == -2
    q_lo[pad] = -2
    q_hi[pad] = -2
    q_ev_lo, q_ev_hi = split_i64(q_ev)
    ev_lo, ev_hi = split_i64(event_ts)
    cr_lo, cr_hi = split_i64(creation_ts)
    cr_planes = np.asarray(
        np.concatenate(split_i64(np.asarray([batch_creation_ts]))), np.int32
    )
    out = merge(
        jnp.asarray(keys_lo), jnp.asarray(keys_hi),
        jnp.asarray(ev_lo), jnp.asarray(ev_hi),
        jnp.asarray(cr_lo), jnp.asarray(cr_hi),
        jnp.asarray(values),
        jnp.asarray(q_lo), jnp.asarray(q_hi),
        jnp.asarray(q_ev_lo), jnp.asarray(q_ev_hi),
        jnp.asarray(q_vals), jnp.asarray(cr_planes),
    )
    ev_lo_u, ev_hi_u, cr_lo_u, cr_hi_u, vals_u = (np.asarray(o) for o in out)
    return (
        combine_i64(ev_lo_u, ev_hi_u),
        combine_i64(cr_lo_u, cr_hi_u),
        vals_u,
    )
