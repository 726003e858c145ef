"""Pallas TPU kernel: online-store GET over a hash-partitioned key table.

The paper's online store is Redis; its GET is a pointer-chasing hash probe —
a latency primitive with no TPU analogue (no fine-grained random access from
vector units).  The TPU-native design applies the paper's own storage-
partitioning idea (§4.5) to the device: the key space is hash-partitioned
into P shards; a batch of queries is routed (host/XLA side) to its shard;
the kernel then resolves each shard's queries against the shard's slots with
a broadcast compare-match — an O(C/P) streaming scan per query batch at full
lane width instead of O(1) serial probes.

Keys are int64 IDs split into two int32 planes (TPU vector compare is 32-bit
native); a match requires both planes to agree.

Layout (what the TPU compiler accepts without relayout of the table):
  * key planes stay (P, C) — slots on lanes; one block holds ``part_block``
    partitions (8, or all P when P is not a multiple of 8) by ``slot_block``
    slots, and the kernel reads one partition's row at a time;
  * queries arrive as (P, Q, 1) — queries on sublanes — so the (Qb, Cb)
    compare is a plain broadcast of a (Qb, 1) column against a (1, Cb) row,
    with no in-kernel transpose.

Grid: (partition-block, query-block, slot-block), slot minor/sequential;
scratch keeps the best (1-based) slot per query, 0 = not found.

Device-resident contract (core/online_store.py): the key planes live on
device across calls — the store passes the same jax arrays every GET, so the
only per-call traffic is the routed queries up and the (P, Q) slot indices
down.  Value/timestamp rows are then fetched at those slots by
``ops.gather_rows``; the kernel itself never touches the value planes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mode import interpret_mode

__all__ = ["lookup_kernel_call", "part_block"]


def part_block(num_partitions: int) -> int:
    """Partitions per block: the sublane tile (8) when it divides P, else
    the whole partition axis (a block dim may equal the array dim)."""
    return 8 if num_partitions % 8 == 0 else num_partitions


def _lookup_kernel(qlo_ref, qhi_ref, klo_ref, khi_ref, out_ref, best_ref):
    cb = pl.program_id(2)
    n_cb = pl.num_programs(2)
    pb, cblk = klo_ref.shape

    @pl.when(cb == 0)
    def _init():
        best_ref[...] = jnp.zeros_like(best_ref)

    slot = cb * cblk + jax.lax.broadcasted_iota(jnp.int32, (1, cblk), 1)
    for p in range(pb):
        klo = klo_ref[pl.ds(p, 1), :]  # (1, Cb)
        khi = khi_ref[pl.ds(p, 1), :]
        match = (klo == qlo_ref[p]) & (khi == qhi_ref[p])  # (Qb, Cb)
        scored = jnp.where(match, slot + 1, 0)  # 1-based, 0 = miss
        best_ref[p] = jnp.maximum(best_ref[p], scored.max(axis=1, keepdims=True))

    @pl.when(cb == n_cb - 1)
    def _write():
        out_ref[...] = best_ref[...] - 1  # back to 0-based/-1


@functools.partial(jax.jit, static_argnames=("slot_block", "q_block", "interpret"))
def lookup_kernel_call(
    keys_lo: jnp.ndarray,
    keys_hi: jnp.ndarray,
    q_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    *,
    slot_block: int = 1024,
    q_block: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """keys_* (P, C) int32, q_* (P, Q, 1) int32 -> slot idx (P, Q, 1) int32
    (-1 miss).

    C % slot_block == 0 and Q % q_block == 0 are ops.py's responsibility.
    """
    if interpret is None:
        interpret = interpret_mode()
    p, c = keys_lo.shape
    q = q_lo.shape[1]
    if c % slot_block or q % q_block:
        raise ValueError("C and Q must be multiples of slot_block and q_block")
    pb = part_block(p)
    qspec = pl.BlockSpec((pb, q_block, 1), lambda i, j, k: (i, j, 0))
    kspec = pl.BlockSpec((pb, slot_block), lambda i, j, k: (i, k))
    return pl.pallas_call(
        _lookup_kernel,
        grid=(p // pb, q // q_block, c // slot_block),
        in_specs=[qspec, qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((p, q, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((pb, q_block, 1), jnp.int32)],
        interpret=interpret,
    )(q_lo, q_hi, keys_lo, keys_hi)
