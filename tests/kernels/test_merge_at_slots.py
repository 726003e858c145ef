"""The resident merge (``merge_at_slots``) and its timestamp gather
(``gather_slot_ts``) against the numpy oracle at (part, slot) coordinates.

The device program compares int64 timestamps as (lo, hi) int32 planes;
the oracle (``ref.merge_at_slots_ref``) compares the int64 values
themselves, so timestamps here span the whole int64 range, lo sign bit
included.  Batches are padded as the store pads them: to a
``pow2_bucket`` length, with coordinates out of bounds."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.online_store import device_width
from repro.kernels.online_lookup.ops import combine_i64, pow2_bucket, split_i64
from repro.kernels.online_merge.ops import gather_slot_ts, merge_at_slots
from repro.kernels.online_merge.ref import merge_at_slots_ref

I32_MAX = np.iinfo(np.int32).max
I64_MIN, I64_MAX = np.iinfo(np.int64).min, np.iinfo(np.int64).max
LO_SIGN = np.int64(1 << 31)


def table(rng, p, c, d):
    """Old (event_ts, creation_ts, values) planes; values zero past ``d``
    up to the device width, as the store keeps them."""
    # one step inside the int64 range, so that neighbours of any old
    # timestamp are int64 too
    ev = rng.integers(I64_MIN + 1, I64_MAX - 1, (p, c), dtype=np.int64)
    cr = rng.integers(I64_MIN + 1, I64_MAX - 1, (p, c), dtype=np.int64)
    vals = np.zeros((p, c, device_width(d)), np.float32)
    vals[..., :d] = rng.random((p, c, d), np.float32)
    return ev, cr, vals


def padded(part, slot, is_new, q_ev, q_vals):
    """Pad a batch to its bucket the way the store does: part 0, slot out
    of bounds, zero records."""
    g, gb = len(part), pow2_bucket(len(part))
    out_part = np.zeros(gb, np.int32)
    out_slot = np.full(gb, I32_MAX, np.int32)
    out_new = np.zeros(gb, bool)
    out_ev = np.zeros(gb, np.int64)
    out_vals = np.zeros((gb, q_vals.shape[1]), np.float32)
    out_part[:g], out_slot[:g], out_new[:g] = part, slot, is_new
    out_ev[:g], out_vals[:g] = q_ev, q_vals
    return out_part, out_slot, out_new, out_ev, out_vals


def run(ev, cr, vals, part, slot, is_new, q_ev, q_vals, batch_cr):
    elo, ehi = split_i64(ev)
    clo, chi = split_i64(cr)
    qlo, qhi = split_i64(q_ev)
    cr_planes = np.concatenate(split_i64(np.asarray([batch_cr], np.int64)))
    out = merge_at_slots(
        *(jnp.asarray(x) for x in (elo, ehi, clo, chi, vals)),
        jnp.asarray(part, jnp.int32), jnp.asarray(slot, jnp.int32),
        jnp.asarray(is_new), jnp.asarray(qlo), jnp.asarray(qhi),
        jnp.asarray(cr_planes), jnp.asarray(q_vals),
    )
    elo, ehi, clo, chi, vals = (np.asarray(x) for x in out)
    return combine_i64(elo, ehi), combine_i64(clo, chi), vals


def assert_merge(ev, cr, vals, part, slot, is_new, q_ev, q_vals, batch_cr):
    got = run(ev, cr, vals, part, slot, is_new, q_ev, q_vals, batch_cr)
    want = merge_at_slots_ref(
        ev, cr, vals, part, slot, is_new, q_ev, q_vals, batch_cr
    )
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    return got


def batch(rng, kind, ev, cr, d):
    """One record per distinct coordinate, of the given kind, and the
    batch's creation_ts."""
    p, c = ev.shape
    g = max(1, p * c // 3)
    flat = rng.choice(p * c, g, replace=False)
    part, slot = (flat // c).astype(np.int32), (flat % c).astype(np.int32)
    q_vals = np.zeros((g, device_width(d)), np.float32)
    q_vals[:, :d] = rng.random((g, d), np.float32) + 1.0
    batch_cr = int(rng.integers(I64_MIN + 1, I64_MAX - 1))
    old_ev = ev[part, slot]
    if kind == "inserts":
        # fresh slots and recycled ones whose stale timestamps are larger:
        # a new slot takes the record whatever it held
        is_new = np.ones(g, bool)
        q_ev = np.minimum(old_ev, rng.integers(I64_MIN, I64_MAX, g, dtype=np.int64))
        return part, slot, is_new, q_ev, q_vals, batch_cr
    is_new = np.zeros(g, bool)
    # later, earlier and equal event_ts, and ones that differ from the old
    # only in the lo word's sign bit, where a signed compare of the lo
    # words would answer wrong; where equal, the batch's creation_ts is
    # above some old ones and equal to or below others
    pick = rng.integers(0, 5, g)
    q_ev = np.choose(
        pick, [old_ev + 1, old_ev - 1, old_ev ^ LO_SIGN, old_ev, old_ev]
    )
    eq = q_ev == old_ev
    cr[part[eq], slot[eq]] = batch_cr + rng.integers(-1, 2, eq.sum())
    if kind == "padded":
        # a half-size batch, a mix of inserts and updates, padded out
        half = max(1, g // 2)
        is_new[: half // 2] = True
        return padded(part[:half], slot[:half], is_new[:half], q_ev[:half],
                      q_vals[:half]) + (batch_cr,)
    return part, slot, is_new, q_ev, q_vals, batch_cr


SHAPES = [(1, 64, 3), (4, 512, 8), (16, 256, 250)]


@pytest.mark.parametrize("kind", ["inserts", "overrides", "padded"])
@pytest.mark.parametrize(("p", "c", "d"), SHAPES)
def test_merge_at_slots_matches_ref(p, c, d, kind):
    rng = np.random.default_rng(p * c + d)
    ev, cr, vals = table(rng, p, c, d)
    part, slot, is_new, q_ev, q_vals, batch_cr = batch(rng, kind, ev, cr, d)
    new_ev, new_cr, new_vals = assert_merge(
        ev, cr, vals, part, slot, is_new, q_ev, q_vals, batch_cr
    )
    live = slot < c
    won = (new_ev[part[live], slot[live]] == q_ev[live]) & (
        new_cr[part[live], slot[live]] == batch_cr
    )
    if kind == "inserts":
        assert won.all()
    else:
        # the batch is a real mix: some records win, some are no-ops
        assert won.any() and not won.all()
    if kind == "padded":
        # the pads wrote nothing: only the live coordinates changed
        changed = (new_ev != ev) | (new_cr != cr)
        touched = np.zeros_like(changed)
        touched[part[live], slot[live]] = True
        assert not (changed & ~touched).any()
    assert not new_vals[..., d:].any(), "pad columns written"


@settings(max_examples=25, deadline=None)
@given(
    shape=st.sampled_from([(1, 8, 1), (2, 16, 3), (4, 8, 2)]),
    g=st.integers(1, 32),
    seed=st.integers(0, 2**32 - 1),
    batch_cr=st.integers(I64_MIN, I64_MAX),
)
def test_merge_at_slots_property(shape, g, seed, batch_cr):
    """Any distinct coordinates, any mix of new slots, any timestamps: a
    third equal to the old ones, a third across the lo word's sign bit
    from them."""
    p, c, d = shape
    rng = np.random.default_rng(seed)
    ev, cr, vals = table(rng, p, c, d)
    g = min(g, p * c)
    flat = rng.choice(p * c, g, replace=False)
    part, slot = flat // c, flat % c
    is_new = rng.random(g) < 0.3
    q_ev = np.choose(
        rng.integers(0, 3, g),
        [
            ev[part, slot],
            ev[part, slot] ^ LO_SIGN,
            rng.integers(I64_MIN, I64_MAX, g, dtype=np.int64),
        ],
    )
    q_vals = np.zeros((g, device_width(d)), np.float32)
    q_vals[:, :d] = rng.random((g, d), np.float32)
    assert_merge(
        ev, cr, vals, *padded(part, slot, is_new, q_ev, q_vals), batch_cr
    )


@pytest.mark.parametrize(("p", "c", "g"), [(1, 64, 5), (16, 256, 700)])
def test_gather_slot_ts_matches_indexing(p, c, g):
    rng = np.random.default_rng(g)
    planes = [rng.integers(-(2**31), 2**31, (p, c), dtype=np.int64).astype(np.int32)
              for _ in range(4)]
    part = rng.integers(0, p, pow2_bucket(g)).astype(np.int32)
    slot = rng.integers(0, c, pow2_bucket(g)).astype(np.int32)
    got = gather_slot_ts(
        *(jnp.asarray(x) for x in planes), jnp.asarray(part), jnp.asarray(slot)
    )
    for plane, out in zip(planes, got):
        np.testing.assert_array_equal(np.asarray(out), plane[part, slot])
