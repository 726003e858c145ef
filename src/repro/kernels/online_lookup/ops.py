"""jit'd programs + host helpers for the online store's GET path.

The online store (core/online_store.py) keeps its device table in the
partitioned (P, C) slot layout, plus a device copy of its sorted key
index.  This module provides:

  * ``split_i64`` / ``combine_i64`` / ``partition_of`` — the shared
    hashing/key-splitting helpers (numpy, host-side) so the store, the
    offline store and the benchmark's generators agree bit-for-bit;
  * ``pow2_bucket`` — the one shape-bucketing rule of the device programs;
  * ``i64_gt`` — the one int64 compare over (lo, hi) int32 planes, shared
    by the key search here and the merge's latest-wins decision
    (kernels/online_merge);
  * ``lookup`` — the GET's slot resolution: a fixed-step binary search of
    the device-resident sorted key index for a flat (B,) query batch,
    returning device (part, slot, found).  Its work is O(B · log(P·C)),
    never O(P·C);
  * ``merge_index`` — brings keys inserted on the host into the device
    index in one O(P·C) program, with O(inserted) bytes uploaded;
  * ``gather_rows`` — the GET's second half: fetch feature rows and
    creation_ts planes at resolved (part, slot) coords on device, so a
    lookup returns (B, D) + (B,) arrays without the host ever holding the
    value planes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "split_i64",
    "combine_i64",
    "partition_of",
    "gather_rows",
    "i64_gt",
    "lookup",
    "merge_index",
    "pow2_bucket",
]

_LANE = 128
_MIX = np.uint64(0x9E3779B97F4A7C15)


def pow2_bucket(n: int, floor: int = _LANE) -> int:
    """Round a host-side length up to a power of two (>= ``floor``) — the ONE
    shape-bucketing rule every jitted device op on the GET/merge path uses, so
    a stream of varying batch sizes maps to a small fixed set of compiled
    entries instead of re-tracing per size (log2 buckets, not one per
    routing high-water mark)."""
    b = floor
    while b < n:
        b *= 2
    return b


def split_i64(ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 -> (lo, hi) int32 planes (two's-complement faithful)."""
    u = np.asarray(ids, dtype=np.int64).view(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    hi = (u >> np.uint64(32)).astype(np.uint32).view(np.int32)
    return lo, hi


def combine_i64(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """(lo, hi) int32 planes -> int64 (inverse of ``split_i64``)."""
    u = np.asarray(lo).view(np.uint32).astype(np.uint64) | (
        np.asarray(hi).view(np.uint32).astype(np.uint64) << np.uint64(32)
    )
    return u.view(np.int64)


def partition_of(ids: np.ndarray, num_partitions: int) -> np.ndarray:
    """Fibonacci-hash partition routing (identical for store + queries)."""
    u = np.asarray(ids, dtype=np.int64).view(np.uint64)
    mixed = (u * _MIX) >> np.uint64(33)
    if num_partitions & (num_partitions - 1) == 0:
        # power-of-two partition counts (the default) take the cheap mask;
        # uint64 modulo costs ~2.5ms per 100k keys on its own
        return (mixed & np.uint64(num_partitions - 1)).view(np.int64)
    return (mixed % np.uint64(num_partitions)).astype(np.int64)


_SIGN = np.int32(np.iinfo(np.int32).min)


def i64_gt(a_lo, a_hi, b_lo, b_hi):
    """(a_lo, a_hi) > (b_lo, b_hi) for int32 pairs holding int64 values in
    ``split_i64`` form: the hi words compare signed, the lo words unsigned."""
    return (a_hi > b_hi) | ((a_hi == b_hi) & ((a_lo ^ _SIGN) > (b_lo ^ _SIGN)))


def _count_below(keys_lo, keys_hi, q_lo, q_hi):
    """Keys of the sorted (N,) planes below each query: a branchless binary
    search of ``N.bit_length()`` steps, each one gather per plane of the
    queries' current probes.  The step count is static, so every batch of a
    bucket runs one compiled program, and nothing (B, N)-sized is built."""
    n = keys_lo.shape[0]
    pos = jnp.zeros(q_lo.shape, jnp.int32)
    for s in reversed(range(n.bit_length())):
        cand = pos + (1 << s)
        at = jnp.minimum(cand, n) - 1
        take = (cand <= n) & i64_gt(q_lo, q_hi, keys_lo[at], keys_hi[at])
        pos = jnp.where(take, cand, pos)
    return pos


@jax.jit
def lookup(
    idx_lo: jnp.ndarray,
    idx_hi: jnp.ndarray,
    idx_part: jnp.ndarray,
    idx_slot: jnp.ndarray,
    queries: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Resolve keys against the device's sorted key index.

    The index is four (N,) int32 planes in ascending int64 key order, its
    unused tail marked by ``idx_part`` -1; ``queries`` is (2, B) int32, the
    keys' lo and hi words.  Returns (part, slot, found), each (B,): the
    key's coordinates where found and (0, 0) elsewhere, so they feed
    ``gather_rows`` directly and the caller masks misses after."""
    q_lo, q_hi = queries[0], queries[1]
    at = jnp.minimum(_count_below(idx_lo, idx_hi, q_lo, q_hi), idx_lo.shape[0] - 1)
    part = idx_part[at]
    found = (idx_lo[at] == q_lo) & (idx_hi[at] == q_hi) & (part >= 0)
    return jnp.where(found, part, 0), jnp.where(found, idx_slot[at], 0), found


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def merge_index(
    idx_lo: jnp.ndarray,
    idx_hi: jnp.ndarray,
    idx_part: jnp.ndarray,
    idx_slot: jnp.ndarray,
    new: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Place new entries into the donated sorted key index.

    ``new`` is (4, M) int32: lo, hi, part, slot of keys the index does not
    hold, ascending, then padding with part -1.  Each new key lands at its
    rank plus the old keys below it; each old entry moves up by the new
    keys below it, a prefix count.  The live keys of both fit in N, since
    each holds a slot, and the old tail fills what is left."""
    n, m = idx_lo.shape[0], new.shape[1]
    live = new[2] >= 0
    rank = _count_below(idx_lo, idx_hi, new[0], new[1])
    new_at = jnp.where(live, jnp.arange(m, dtype=jnp.int32) + rank, n)
    shift = jnp.cumsum(
        jnp.zeros(n, jnp.int32).at[rank].add(live.astype(jnp.int32), mode="drop")
    )
    old_at = jnp.arange(n, dtype=jnp.int32) + shift

    def place(old, fresh):
        out = jnp.zeros(n, jnp.int32).at[old_at].set(old, mode="drop")
        return out.at[new_at].set(fresh, mode="drop")

    return tuple(
        place(old, new[i])
        for i, old in enumerate((idx_lo, idx_hi, idx_part, idx_slot))
    )


@jax.jit
def gather_rows(
    values: jnp.ndarray,
    cr_lo: jnp.ndarray,
    cr_hi: jnp.ndarray,
    part: jnp.ndarray,
    slot: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Resident gather: (part, slot) (B,) int32 coords -> feature rows
    (B, D) f32 + creation_ts planes (B,) int32.  Misses should be clamped
    to slot 0 by the caller and masked after; the creation planes feed the
    TTL check so expiry never needs the host timestamp mirror."""
    return values[part, slot], cr_lo[part, slot], cr_hi[part, slot]
