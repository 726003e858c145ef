"""jit'd programs for the online store's resident write path.

  * ``merge_at_slots`` — the compare-and-update.  The store's sorted key
    index already resolved each winner record to its (partition, slot), so
    the update is an O(batch) gather/lex-compare/scatter over donated
    planes (``donate_argnums``): the table buffers are rewritten in place,
    nothing table-sized crosses host<->device, and only the batch (coords +
    winner planes + feature rows) is uploaded.  The latest-wins decision
    itself happens ON DEVICE — host tallies come from the merge plan and
    agree by construction — which is what makes the device planes a
    self-contained Algorithm-2 state machine (safe to replay for
    geo-replication).
  * ``gather_slot_ts`` — the read half: fetch the current (event_ts,
    creation_ts) planes at resolved coords so the host merge plan can
    compute exact insert/override/no-op tallies against device truth
    without pulling whole planes back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.online_lookup.ops import i64_gt

__all__ = ["gather_slot_ts", "merge_at_slots"]


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def merge_at_slots(
    ev_lo: jnp.ndarray,
    ev_hi: jnp.ndarray,
    cr_lo: jnp.ndarray,
    cr_hi: jnp.ndarray,
    values: jnp.ndarray,
    part: jnp.ndarray,
    slot: jnp.ndarray,
    is_new: jnp.ndarray,
    q_ev_lo: jnp.ndarray,
    q_ev_hi: jnp.ndarray,
    cr_planes: jnp.ndarray,
    q_values: jnp.ndarray,
) -> tuple[jnp.ndarray, ...]:
    """Donated-buffer compare-and-update at index-resolved slots.

    All five table planes are DONATED — the update happens in the planes'
    existing device buffers; callers must drop their references and adopt
    the returned arrays.  Batch arrays are per-unique-id winner records in
    any order: ``part``/``slot`` (G,) int32 target coords, ``is_new`` where
    the slot was just assigned to the id (fresh inserts, possibly into
    recycled slots), ``q_ev_lo/q_ev_hi`` winner event_ts planes,
    ``cr_planes`` (2,) int32 [lo, hi] of the shared batch creation_ts, and
    ``q_values`` (G, D) feature rows.  Coords must be distinct (the merge
    plan guarantees one winner per id, the index one slot per id); coords
    out of bounds are dropped, which is how callers pad a batch.

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

    ev_gt = i64_gt(q_ev_lo, q_ev_hi, old_elo, old_ehi)
    ev_eq = (q_ev_hi == old_ehi) & (q_ev_lo == old_elo)
    cr_gt = i64_gt(crlo, crhi, old_clo, old_chi)
    win = is_new | ev_gt | (ev_eq & cr_gt)

    ev_lo = ev_lo.at[part, slot].set(jnp.where(win, q_ev_lo, old_elo))
    ev_hi = ev_hi.at[part, slot].set(jnp.where(win, q_ev_hi, old_ehi))
    cr_lo = cr_lo.at[part, slot].set(jnp.where(win, crlo, old_clo))
    cr_hi = cr_hi.at[part, slot].set(jnp.where(win, crhi, old_chi))
    values = values.at[part, slot].set(
        jnp.where(win[:, None], q_values, values[part, slot])
    )
    return ev_lo, ev_hi, cr_lo, cr_hi, values


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
