"""The GET's search of the device key index against the host's sorted index.

``lookup`` binary-searches four (N,) int32 planes (key lo, key hi, part,
slot) in int64 key order, padded past the live keys with the sentinel the
store writes (key int64 max, part and slot -1); ``merge_index`` places
newly inserted keys into them.  Both are checked against ``np.searchsorted``
over the int64 keys, the host index's own search (``_index_find``)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.online_lookup.ops import (
    lookup,
    merge_index,
    pow2_bucket,
    split_i64,
)

I64_MAX = np.iinfo(np.int64).max
I64_MIN = np.iinfo(np.int64).min
PAD = np.array([-1, np.iinfo(np.int32).max, -1, -1], np.int32)


def index_planes(keys, n, seed=0):
    """Host planes of a device index over sorted unique ``keys``, with
    distinct (part, slot) coordinates, padded to ``n`` entries."""
    k = len(keys)
    rng = np.random.default_rng(seed)
    planes = np.empty((4, n), np.int32)
    planes[0, :k], planes[1, :k] = split_i64(keys)
    planes[2, :k] = rng.integers(0, 16, k)
    planes[3, :k] = rng.permutation(n)[:k]
    planes[:, k:] = PAD[:, None]
    return planes


def queries(ids):
    q = np.zeros((2, pow2_bucket(len(ids))), np.int32)
    q[0, : len(ids)], q[1, : len(ids)] = split_i64(ids)
    return q


def search(planes, ids):
    part, slot, found = lookup(
        *(jnp.asarray(p) for p in planes), jnp.asarray(queries(ids))
    )
    b = len(ids)
    return np.asarray(part)[:b], np.asarray(slot)[:b], np.asarray(found)[:b]


def expect(planes, keys, ids):
    """``_index_find`` over the int64 keys: (part, slot) where found, else 0."""
    k = len(keys)
    pos = np.searchsorted(keys, ids)
    safe = np.minimum(pos, max(k - 1, 0))
    found = (pos < k) & (keys[safe] == ids) if k else np.zeros(len(ids), bool)
    return (
        np.where(found, planes[2][safe], 0),
        np.where(found, planes[3][safe], 0),
        found,
    )


def assert_search(keys, ids, n, seed=0):
    keys = np.unique(np.asarray(keys, np.int64))
    ids = np.asarray(ids, np.int64)
    planes = index_planes(keys, n, seed)
    for got, want in zip(search(planes, ids), expect(planes, keys, ids)):
        np.testing.assert_array_equal(got, want)


EDGE_KEYS = np.array(
    [
        I64_MIN, -(1 << 40), -1, 0, 1,
        0x7FFFFFFF, 0x80000000, 0xFFFFFFFF, 0x1_0000_0000,  # lo sign bit
        (5 << 32) | 0x80000001, (5 << 32) | 0x7FFFFFFF,
        I64_MAX - 1, I64_MAX,  # next to and equal to the sentinel
    ],
    np.int64,
)


@pytest.mark.parametrize(
    "case",
    ["edges", "edges_without_max", "empty", "full", "one_key"],
)
def test_lookup_matches_host_index(case):
    rng = np.random.default_rng(1)
    probes = np.concatenate(
        [EDGE_KEYS, EDGE_KEYS + 1, EDGE_KEYS - 1, rng.integers(I64_MIN, I64_MAX, 40)]
    )
    if case == "edges":
        assert_search(EDGE_KEYS, probes, 64)
    elif case == "edges_without_max":
        # a probe of int64 max must miss though the padding holds that key
        assert_search(EDGE_KEYS[:-1], probes, 64)
    elif case == "empty":
        assert_search([], probes, 16)
    elif case == "full":
        # no padding at all, at a length that is no power of two
        assert_search(EDGE_KEYS, probes, len(EDGE_KEYS))
    else:
        assert_search([42], probes, 1)


@pytest.mark.parametrize("batch", [5, 200, 700])
def test_lookup_hits_and_misses_across_buckets(batch):
    """Batches in the 128, 256 and 1024 buckets: every stored key is found
    at its coordinates, every other key misses."""
    rng = np.random.default_rng(batch)
    keys = np.unique(rng.integers(-(1 << 62), 1 << 62, 3_000))
    ids = np.concatenate(
        [rng.choice(keys, batch // 2), rng.integers(-(1 << 62), 1 << 62, batch - batch // 2)]
    )
    rng.shuffle(ids)
    assert_search(keys, ids, 3 * 1024 + 5, seed=batch)


@settings(max_examples=25, deadline=None)
@given(
    keys=st.lists(st.integers(I64_MIN, I64_MAX), max_size=300, unique=True),
    extra=st.lists(st.integers(I64_MIN, I64_MAX), min_size=1, max_size=150),
    spare=st.integers(0, 200),
    data=st.data(),
)
def test_lookup_property(keys, extra, spare, data):
    """Any keys, any probes (half of them stored keys), any padding."""
    stored = data.draw(st.lists(st.sampled_from(keys), max_size=150)) if keys else []
    assert_search(keys, extra + stored, len(keys) + spare + (0 if keys or spare else 1))


def merged(old_keys, new_keys, n):
    """``merge_index`` of ``new_keys`` into an index holding ``old_keys``,
    against the index built whole from both."""
    old_keys = np.unique(np.asarray(old_keys, np.int64))
    new_keys = np.unique(np.asarray(new_keys, np.int64))
    everything = np.concatenate([old_keys, new_keys])
    whole = index_planes(np.sort(everything), n)
    coord = {int(k): whole[2:, i] for i, k in enumerate(np.sort(everything))}
    old = np.empty((4, n), np.int32)
    old[:2], old[2:] = index_planes(old_keys, n)[:2], PAD[2:, None]
    for i, k in enumerate(old_keys):
        old[2:, i] = coord[int(k)]
    m = len(new_keys)
    new = np.empty((4, pow2_bucket(m)), np.int32)
    new[0, :m], new[1, :m] = split_i64(new_keys)
    for i, k in enumerate(new_keys):
        new[2:, i] = coord[int(k)]
    new[:, m:] = PAD[:, None]
    got = merge_index(*(jnp.asarray(p) for p in old), jnp.asarray(new))
    return np.stack([np.asarray(p) for p in got]), whole


@pytest.mark.parametrize(
    "old_keys,new_keys,n",
    [
        ([], [3, 1, 2], 8),
        ([10, 20, 30], [5, 15, 25, 35], 9),
        ([10, 20, 30], [5, 15, 25, 35], 7),  # fills the index exactly
        (EDGE_KEYS[::2], EDGE_KEYS[1::2], 40),
        (EDGE_KEYS[:-1], [I64_MAX], 16),  # a new key equal to the sentinel
        ([I64_MAX], [I64_MIN, 0], 5),
    ],
)
def test_merge_index_matches_whole_index(old_keys, new_keys, n):
    got, whole = merged(old_keys, new_keys, n)
    np.testing.assert_array_equal(got, whole)


@settings(max_examples=20, deadline=None)
@given(
    keys=st.lists(st.integers(I64_MIN, I64_MAX), min_size=1, max_size=300, unique=True),
    split=st.floats(0, 1),
    spare=st.integers(0, 50),
)
def test_merge_index_property(keys, split, spare):
    rng = np.random.default_rng(len(keys))
    keys = rng.permutation(np.asarray(keys, np.int64))
    cut = int(len(keys) * split)
    got, whole = merged(keys[:cut], keys[cut:], len(keys) + spare)
    np.testing.assert_array_equal(got, whole)
