"""online_lookup's Pallas slot scan vs oracle: routing, padding, sentinel handling."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.online_lookup.ops import (
    partition_of,
    route_and_lookup,
    scan_slots,
    split_i64,
)
from repro.kernels.online_lookup.ref import lookup_ref


def _build_store(rng, num_p, cap, n_live, dim=4):
    ids = rng.choice(np.arange(1, 10_000_000), size=n_live, replace=False).astype(
        np.int64
    )
    keys_lo = np.full((num_p, cap), -1, np.int32)
    keys_hi = np.full((num_p, cap), -1, np.int32)
    values = np.zeros((num_p, cap, dim), np.float32)
    part = partition_of(ids, num_p)
    lo, hi = split_i64(ids)
    fill = np.zeros(num_p, np.int64)
    kept = []
    for j in range(n_live):
        p = part[j]
        if fill[p] >= cap:
            continue
        keys_lo[p, fill[p]] = lo[j]
        keys_hi[p, fill[p]] = hi[j]
        values[p, fill[p]] = float(ids[j] % 97)
        fill[p] += 1
        kept.append(ids[j])
    return keys_lo, keys_hi, values, np.array(kept, np.int64)


def test_split_i64_roundtrip():
    ids = np.array([0, 1, 2**31, 2**40 + 17, -5, np.iinfo(np.int64).max], np.int64)
    lo, hi = split_i64(ids)
    rebuilt = (
        lo.view(np.uint32).astype(np.uint64)
        | (hi.view(np.uint32).astype(np.uint64) << np.uint64(32))
    ).view(np.int64)
    np.testing.assert_array_equal(rebuilt, ids)


def test_partition_routing_stable_and_in_range():
    ids = np.arange(1, 5000, dtype=np.int64)
    p1 = partition_of(ids, 16)
    p2 = partition_of(ids, 16)
    np.testing.assert_array_equal(p1, p2)
    assert p1.min() >= 0 and p1.max() < 16
    # reasonable balance for the Fibonacci mix: no partition > 3x the mean
    counts = np.bincount(p1, minlength=16)
    assert counts.max() < 3 * counts.mean()


@pytest.mark.parametrize("num_p,cap,q", [(1, 64, 16), (4, 1024, 100), (8, 100, 7)])
def test_lookup_vs_ref(num_p, cap, q):
    rng = np.random.default_rng(num_p * cap + q)
    keys_lo = rng.integers(0, 2**31 - 1, size=(num_p, cap)).astype(np.int32)
    keys_hi = rng.integers(0, 100, size=(num_p, cap)).astype(np.int32)
    # half the queries hit, half miss
    q_lo = np.full((num_p, q), -2, np.int32)
    q_hi = np.full((num_p, q), -2, np.int32)
    for p in range(num_p):
        for i in range(q):
            if rng.random() < 0.5:
                c = rng.integers(0, cap)
                q_lo[p, i] = keys_lo[p, c]
                q_hi[p, i] = keys_hi[p, c]
            else:
                q_lo[p, i] = rng.integers(0, 2**31 - 1)
                q_hi[p, i] = 101  # plane-2 value no live key uses
    got = scan_slots(
        jnp.asarray(keys_lo), jnp.asarray(keys_hi), jnp.asarray(q_lo), jnp.asarray(q_hi)
    )
    want = lookup_ref(
        jnp.asarray(keys_lo), jnp.asarray(keys_hi), jnp.asarray(q_lo), jnp.asarray(q_hi)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("slot_block", [128, 256, 1024])
def test_lookup_slot_block_sweep(slot_block):
    rng = np.random.default_rng(slot_block)
    num_p, cap, q = 2, 512, 64
    keys_lo = rng.integers(0, 1000, size=(num_p, cap)).astype(np.int32)
    keys_hi = np.zeros((num_p, cap), np.int32)
    q_lo = keys_lo[:, :q].copy()
    q_hi = np.zeros((num_p, q), np.int32)
    got = scan_slots(
        jnp.asarray(keys_lo), jnp.asarray(keys_hi),
        jnp.asarray(q_lo), jnp.asarray(q_hi), slot_block=slot_block,
    )
    want = lookup_ref(
        jnp.asarray(keys_lo), jnp.asarray(keys_hi), jnp.asarray(q_lo), jnp.asarray(q_hi)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_route_and_lookup_end_to_end():
    rng = np.random.default_rng(3)
    keys_lo, keys_hi, values, live = _build_store(rng, 8, 256, 900)
    hits = rng.choice(live, size=50, replace=False)
    misses = np.arange(20_000_000, 20_000_030, dtype=np.int64)
    ids = np.concatenate([hits, misses])
    rng.shuffle(ids)
    out, found = route_and_lookup(keys_lo, keys_hi, values, ids)
    for i, _id in enumerate(ids):
        if _id in set(live.tolist()):
            assert found[i], _id
            np.testing.assert_allclose(out[i], float(_id % 97))
        else:
            assert not found[i]
            np.testing.assert_allclose(out[i], 0.0)


def test_route_and_lookup_empty_batch():
    keys_lo = np.full((2, 8), -1, np.int32)
    out, found = route_and_lookup(
        keys_lo, keys_lo.copy(), np.zeros((2, 8, 3), np.float32), np.zeros(0, np.int64)
    )
    assert out.shape == (0, 3) and found.shape == (0,)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_q=st.integers(1, 120))
def test_route_and_lookup_property(seed, n_q):
    """Every id stored must be found with its value; ids never stored must
    miss.  (Exactly Algorithm-2 GET semantics over the partitioned mirror.)"""
    rng = np.random.default_rng(seed)
    keys_lo, keys_hi, values, live = _build_store(rng, 4, 128, 300)
    live_set = set(live.tolist())
    universe = np.concatenate([live, rng.integers(10**8, 10**9, size=50)])
    ids = rng.choice(universe, size=n_q)
    out, found = route_and_lookup(keys_lo, keys_hi, values, ids)
    for i, _id in enumerate(ids):
        assert found[i] == (_id in live_set)
        if found[i]:
            np.testing.assert_allclose(out[i], float(_id % 97))
