"""jit'd wrappers + host routing for the online store's GET path.

The online store (core/online_store.py) keeps its device mirror in the
partitioned (P, C) slot layout, plus a device copy of its sorted key
index.  This module provides:

  * ``split_i64`` / ``partition_of`` — the shared hashing/key-splitting
    helpers (numpy, host-side) so the store and the kernels agree
    bit-for-bit.
  * ``lookup`` — the GET's slot resolution: a fixed-step binary search of
    the device-resident sorted key index for a flat (B,) query batch,
    returning device (part, slot, found).  Its work is O(B · log(P·C)),
    never O(P·C).
  * ``merge_index`` — brings keys inserted on the host into the device
    index in one O(P·C) program, with O(inserted) bytes uploaded.
  * ``gather_rows`` — the resident GET's second half: fetch feature rows and
    creation_ts planes at resolved (part, slot) coords on device, so a
    lookup returns (B, D) + (B,) arrays without the host ever holding the
    value planes.
  * ``scan_slots`` — the Pallas scan of every slot of pre-routed (P, Q)
    queries; ``route_and_lookup`` — host-side convenience that routes a flat
    id batch to partitions, scans, gathers values and un-permutes.  The
    store's GET no longer uses them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.online_lookup.kernel import lookup_kernel_call

__all__ = [
    "split_i64",
    "combine_i64",
    "partition_of",
    "gather_rows",
    "lookup",
    "merge_index",
    "pow2_bucket",
    "route_and_lookup",
    "route_flat",
    "route_queries",
    "scan_slots",
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


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def route_flat(
    num_partitions: int, ids: np.ndarray, *payloads: np.ndarray
) -> tuple[np.ndarray, ...]:
    """Vectorized flat->routed scatter shared by the lookup and merge paths.

    ids (B,) -> (routed_ids (P, Qmax) int64 with -2 padding, part (B,),
    pos (B,) [each row's slot within its partition], *routed payloads
    (P, Qmax, ...) zero-padded).
    """
    b = len(ids)
    part = partition_of(ids, num_partitions)
    counts = np.bincount(part, minlength=num_partitions)
    q_max = max(int(counts.max()) if b else 0, 1)
    order = np.argsort(part, kind="stable")
    ps = part[order]
    # rank of each row within its partition's contiguous block
    pos_sorted = np.arange(b) - np.searchsorted(ps, ps)
    pos = np.empty(b, np.int64)
    pos[order] = pos_sorted
    routed_ids = np.full((num_partitions, q_max), -2, np.int64)
    routed_ids[part, pos] = ids
    out = [routed_ids, part, pos]
    for payload in payloads:
        shape = (num_partitions, q_max) + payload.shape[1:]
        r = np.zeros(shape, payload.dtype)
        r[part, pos] = payload
        out.append(r)
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("slot_block",))
def scan_slots(
    keys_lo: jnp.ndarray,
    keys_hi: jnp.ndarray,
    q_lo: jnp.ndarray,
    q_hi: jnp.ndarray,
    *,
    slot_block: int = 1024,
) -> jnp.ndarray:
    """Pre-routed lookup.  keys (P, C), queries (P, Q) -> slots (P, Q)."""
    p, c = keys_lo.shape
    c_pad = _round_up(c, min(slot_block, _round_up(c, _LANE)))
    sb = min(slot_block, c_pad)
    c_pad = _round_up(c_pad, sb)
    if c_pad != c:
        pad = jnp.full((p, c_pad - c), -1, jnp.int32)
        keys_lo = jnp.concatenate([keys_lo, pad], axis=1)
        keys_hi = jnp.concatenate([keys_hi, pad], axis=1)
    q = q_lo.shape[1]
    q_pad = _round_up(q, _LANE)
    if q_pad != q:
        # pad with (-2, -2): matches neither live keys (>=0 planes possible)
        # nor the empty sentinel (-1, -1).
        padq = jnp.full((p, q_pad - q), -2, jnp.int32)
        q_lo = jnp.concatenate([q_lo, padq], axis=1)
        q_hi = jnp.concatenate([q_hi, padq], axis=1)
    qb = 256 if q_pad % 256 == 0 else _LANE
    out = lookup_kernel_call(
        keys_lo, keys_hi, q_lo[..., None], q_hi[..., None],
        slot_block=sb, q_block=qb,
    )
    return out[:, :q, 0]


def route_queries(
    num_partitions: int, ids: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Route a flat id batch into kernel-ready (P, Q) query planes.

    Returns (q_lo, q_hi, part, pos): int32 planes padded host-side to a
    power-of-two lane bucket (``pow2_bucket``) with every pad entry stamped
    to the (-2, -2) sentinel — the ONE place that invariant lives: pads must
    match neither live keys (split planes can be anything >= 0) nor the
    empty-slot sentinel (-1, -1).  Power-of-two (not next-multiple-of-128)
    padding matters for the serving path: the routing high-water mark
    jitters run-to-run with key imbalance, and at large coalesced batches a
    128-granular pad would straddle bucket boundaries and re-trace the
    jitted kernel per batch; log2 buckets make repeated same-scale GETs hit
    the same compiled entry.  ``part``/``pos`` un-permute kernel results
    back to batch order."""
    routed_ids, part, pos = route_flat(num_partitions, ids)[:3]
    qmax = routed_ids.shape[1]
    qpad = pow2_bucket(qmax)
    if qpad != qmax:
        routed_ids = np.concatenate(
            [routed_ids, np.full((num_partitions, qpad - qmax), -2, np.int64)],
            axis=1,
        )
    q_lo, q_hi = split_i64(routed_ids)
    pad = routed_ids == -2
    q_lo[pad] = -2
    q_hi[pad] = -2
    return q_lo, q_hi, part, pos


_SIGN = np.int32(np.iinfo(np.int32).min)


def _below(a_lo, a_hi, b_lo, b_hi):
    """(lo, hi) int32 pairs compared in int64 order: hi signed, lo unsigned."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & ((a_lo ^ _SIGN) < (b_lo ^ _SIGN)))


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
        take = (cand <= n) & _below(keys_lo[at], keys_hi[at], q_lo, q_hi)
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


def route_and_lookup(
    keys_lo: np.ndarray,
    keys_hi: np.ndarray,
    values: np.ndarray,
    ids: np.ndarray,
) -> tuple[np.ndarray, np.ndarray]:
    """Flat query path: ids (B,) int64 against table (P, C) + values (P, C, D).

    Returns (values (B, D) float32 — zeros where missing, found (B,) bool).
    """
    num_p, cap = keys_lo.shape
    ids = np.asarray(ids, dtype=np.int64)
    b = len(ids)
    if b == 0:
        return np.zeros((0, values.shape[-1]), np.float32), np.zeros((0,), bool)
    q_lo, q_hi, part, slot_in_part = route_queries(num_p, ids)

    slots = np.asarray(
        scan_slots(
            jnp.asarray(keys_lo),
            jnp.asarray(keys_hi),
            jnp.asarray(q_lo),
            jnp.asarray(q_hi),
        )
    )
    got = slots[part, slot_in_part]
    found = got >= 0
    out = np.zeros((b, values.shape[-1]), np.float32)
    if found.any():
        out[found] = values[part[found], got[found]]
    return out, found
