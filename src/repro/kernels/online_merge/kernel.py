"""Pallas TPU kernel: online-store latest-wins MERGE (Algorithm 2, §4.5).

Sibling of kernels/online_lookup: same hash-partitioned (P, C) slot layout,
same int64-as-two-int32-plane key codec, so the write path and the read path
share one device-resident table.  Where the lookup kernel answers "which slot
holds this key", the merge kernel answers "which slots must this batch
rewrite" — a broadcast compare-match followed by a masked compare-and-update:

  win[q, c] = key_match(q, c) AND (q.event_ts, q.creation_ts) >lex (slot c)

Each partition's routed batch is pre-reduced to ONE winner record per id
(ops/store responsibility), so at most one query wins any slot and the
update is a one-hot gather: timestamps via an integer masked sum, feature
rows via a 0/1 matmul against the routed values (MXU-friendly, exact
because each output row has exactly one contributing term).

Timestamps are int64 split into (lo, hi) int32 planes like keys; lexicographic
compare is signed on the hi plane, unsigned (sign-bit-flipped) on the lo
plane.  Callers routing fresh inserts through this scan must pre-stamp those
slots with INT64_MIN timestamps so any real record wins them (the resident
store path instead applies inserts via ops.merge_at_slots' ``is_new`` mask).

Layout (what the TPU compiler accepts without an in-kernel transpose):
table timestamp planes stay (P, C) with slots on lanes, a block holding
``part_block(P)`` partitions; queries arrive as (P, Q, 1) — queries on
sublanes — so every compare is a (Qb, 1) column against a (1, Cb) row.
Feature values travel transposed, (P, D, C) for the table and (P, D, Q) for
the queries, with D on sublanes: the update is then the plain matmul
(D, Qb) @ (Qb, Cb), and a narrow D pads to 8 sublanes rather than 128 lanes.
The batch creation_ts planes are two scalars and ride in SMEM.

Grid: (partition-block, slot-block, query-block), queries minor.  A slot
block stays resident in the output buffers while every query block passes
over it; because at most one query carries any key, applying query blocks
one after another equals applying the whole batch at once.

The table planes are ALIASED input->output (``input_output_aliases``), so a
caller that donates them has them rewritten in place; callers that retain
references to the inputs still get value semantics (XLA falls back to a
defensive copy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode
from repro.kernels.online_lookup.kernel import part_block

__all__ = ["i64_gt", "merge_kernel_call"]


def _u32_gt(a, b):
    """Unsigned > on int32 bit patterns (flip sign bit, compare signed)."""
    sign = jnp.asarray(-(2**31), dtype=jnp.int32)
    return (a ^ sign) > (b ^ sign)


def i64_gt(ahi, alo, bhi, blo):
    """(ahi, alo) > (bhi, blo) as int64: signed hi, unsigned lo.

    Public: the split-plane lexicographic compare is a cross-module contract
    — the Pallas scan kernel below and ops.merge_at_slots (the resident
    scatter path) must agree bit-for-bit on it."""
    return (ahi > bhi) | ((ahi == bhi) & _u32_gt(alo, blo))


def _merge_kernel(
    cr_ref, qlo_ref, qhi_ref, qelo_ref, qehi_ref, qv_ref,
    klo_ref, khi_ref, elo_ref, ehi_ref, clo_ref, chi_ref, v_ref,
    out_elo, out_ehi, out_clo, out_chi, out_v,
):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_elo[...] = elo_ref[...]
        out_ehi[...] = ehi_ref[...]
        out_clo[...] = clo_ref[...]
        out_chi[...] = chi_ref[...]
        out_v[...] = v_ref[...]

    crlo = cr_ref[0]  # scalars: batch creation_ts planes
    crhi = cr_ref[1]
    for p in range(klo_ref.shape[0]):
        row = pl.ds(p, 1)
        elo = out_elo[row, :]  # (1, Cb) current slot state
        ehi = out_ehi[row, :]
        clo = out_clo[row, :]
        chi = out_chi[row, :]
        qelo = qelo_ref[p]  # (Qb, 1)
        qehi = qehi_ref[p]

        match = (klo_ref[row, :] == qlo_ref[p]) & (khi_ref[row, :] == qhi_ref[p])
        ev_gt = i64_gt(qehi, qelo, ehi, elo)  # (Qb, Cb)
        ev_eq = (qehi == ehi) & (qelo == elo)
        cr_gt = i64_gt(crhi, crlo, chi, clo)  # (1, Cb)
        win = (match & (ev_gt | (ev_eq & cr_gt))).astype(jnp.int32)

        any_win = win.max(axis=0, keepdims=True) > 0  # (1, Cb)
        # one-hot gather: at most one query wins a slot
        sel = lambda q: (win * q).sum(axis=0, keepdims=True)
        out_elo[row, :] = jnp.where(any_win, sel(qelo), elo)
        out_ehi[row, :] = jnp.where(any_win, sel(qehi), ehi)
        out_clo[row, :] = jnp.where(any_win, crlo, clo)
        out_chi[row, :] = jnp.where(any_win, crhi, chi)

        upd = jax.lax.dot(
            qv_ref[p], win.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )  # (D, Cb), exact: each column has at most one contributing term
        out_v[p] = jnp.where(any_win, upd, out_v[p])


@functools.partial(jax.jit, static_argnames=("slot_block", "q_block", "interpret"))
def merge_kernel_call(
    keys_lo: jnp.ndarray,
    keys_hi: jnp.ndarray,
    ev_lo: jnp.ndarray,
    ev_hi: jnp.ndarray,
    cr_lo: jnp.ndarray,
    cr_hi: jnp.ndarray,
    values_t: jnp.ndarray,
    q_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    q_ev_lo: jnp.ndarray,
    q_ev_hi: jnp.ndarray,
    q_values_t: jnp.ndarray,
    creation_planes: jnp.ndarray,
    *,
    slot_block: int = 512,
    q_block: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, ...]:
    """Table planes (P, C) int32 + values_t (P, D, C) f32, routed winner
    queries (P, Q, 1) int32 + q_values_t (P, D, Q), creation_planes (2,)
    int32 [lo, hi] -> updated (ev_lo, ev_hi, cr_lo, cr_hi, values_t).

    C % slot_block == 0, Q % q_block == 0 and D % 8 == 0 are ops.py's
    responsibility; at most one query per partition may carry any key.
    """
    if interpret is None:
        interpret = interpret_mode()
    p, c = keys_lo.shape
    q = q_lo.shape[1]
    d = values_t.shape[1]
    if c % slot_block or q % q_block:
        raise ValueError("C and Q must be multiples of slot_block and q_block")
    pb = part_block(p)
    tab = pl.BlockSpec((pb, slot_block), lambda i, j, k: (i, j))
    vtab = pl.BlockSpec((pb, d, slot_block), lambda i, j, k: (i, 0, j))
    qspec = pl.BlockSpec((pb, q_block, 1), lambda i, j, k: (i, k, 0))
    out_shapes = [jax.ShapeDtypeStruct((p, c), jnp.int32)] * 4 + [
        jax.ShapeDtypeStruct((p, d, c), jnp.float32)
    ]
    return pl.pallas_call(
        _merge_kernel,
        grid=(p // pb, c // slot_block, q // q_block),
        # ev_lo/ev_hi/cr_lo/cr_hi/values update in place when donated
        # (positions 8..12 of the operand list below -> outputs 0..4)
        input_output_aliases={8: 0, 9: 1, 10: 2, 11: 3, 12: 4},
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            qspec, qspec, qspec, qspec,
            pl.BlockSpec((pb, d, q_block), lambda i, j, k: (i, 0, k)),
            tab, tab, tab, tab, tab, tab,
            vtab,
        ],
        out_specs=[tab, tab, tab, tab, vtab],
        out_shape=out_shapes,
        interpret=interpret,
    )(
        creation_planes, q_lo, q_hi, q_ev_lo, q_ev_hi, q_values_t,
        keys_lo, keys_hi, ev_lo, ev_hi, cr_lo, cr_hi, values_t,
    )
