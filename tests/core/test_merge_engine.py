"""Merge-engine parity: vectorized write paths vs the retained loop reference.

The contract under test (ISSUE tentpole): for ANY merge sequence, the
``vector`` (and online ``kernel``) engines must leave the stores in
BYTE-IDENTICAL state to the sequential Algorithm-2 loop — same table planes,
same sorted indexes, same chunk contents — with identical
``inserts/overrides/noops`` / ``rows_merged/rows_deduped`` tallies.
Covers duplicate ids within one batch, equal-event_ts creation-ts tiebreaks,
TTL sweeps between merges, and growth/compaction boundaries.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import UDFTransform
from repro.core.merge_engine import (
    INT64_MIN,
    merge_sorted,
    plan_online_batch,
    segmented_exclusive_prefix_max,
)
from repro.core.offline_store import OfflineStore
from repro.core.online_store import OnlineStore
from repro.core.table import Table

_ONLINE_STATE = (
    "keys_lo", "keys_hi", "keys_full", "event_ts", "creation_ts",
    "values", "fill", "idx_keys", "idx_part", "idx_slot",
)


def make_spec(ttl=None, n_feats=1):
    return FeatureSetSpec(
        name="fs",
        version=1,
        entity=Entity("cust", ("entity_id",)),
        features=tuple(Feature(f"f{i}") for i in range(n_feats)),
        source_name="src",
        transform=UDFTransform(lambda df, ctx: df, name="id"),
        materialization=MaterializationSettings(True, True, online_ttl=ttl),
    )


def make_frame(rng, n, id_hi, ev_hi, n_feats=1):
    cols = {
        "entity_id": rng.integers(0, id_hi, n).astype(np.int64),
        "ts": rng.integers(0, ev_hi, n).astype(np.int64),
    }
    for i in range(n_feats):
        cols[f"f{i}"] = rng.random(n).astype(np.float32)
    return Table(cols)


def assert_online_identical(a: OnlineStore, b: OnlineStore, spec, label=""):
    # device-resident engines keep truth on device; pull the lazy host
    # mirrors up to date before comparing planes byte-for-byte
    a.sync_host_mirrors()
    b.sync_host_mirrors()
    ta, tb = a._tables[spec.key], b._tables[spec.key]
    for f in _ONLINE_STATE:
        np.testing.assert_array_equal(
            getattr(ta, f), getattr(tb, f), err_msg=f"{label}: plane {f}"
        )
    assert [list(f) for f in ta.free] == [list(f) for f in tb.free], (
        f"{label}: free lists"
    )
    assert (a.inserts, a.overrides, a.noops) == (b.inserts, b.overrides, b.noops), label


def assert_offline_identical(a: OfflineStore, b: OfflineStore, spec, label=""):
    assert a.read("fs", 1).equals(b.read("fs", 1)), label
    assert a.num_rows("fs", 1) == b.num_rows("fs", 1), label
    assert (a.rows_merged, a.rows_deduped) == (b.rows_merged, b.rows_deduped), label
    for i, (sa, sb) in enumerate(zip(a._shards[spec.key], b._shards[spec.key])):
        np.testing.assert_array_equal(
            sa.index, sb.index, err_msg=f"{label}: shard {i} index"
        )


# -- low-level engine pieces -------------------------------------------------


def test_segmented_prefix_max_vs_sequential():
    rng = np.random.default_rng(0)
    seg = np.sort(rng.integers(0, 40, 300))
    vals = rng.integers(-100, 100, 300)
    got = segmented_exclusive_prefix_max(seg, vals)
    run: dict = {}
    for i in range(300):
        want = run.get(seg[i], INT64_MIN)
        assert got[i] == want, i
        run[seg[i]] = max(want, vals[i])


def test_merge_sorted_matches_insert():
    rng = np.random.default_rng(1)
    a = np.unique(rng.integers(0, 1000, 80))
    b = np.unique(rng.integers(1000, 2000, 40))
    pa = rng.random(len(a))
    pb = rng.random(len(b))
    keys, payload = merge_sorted([a, pa], [b, pb])
    want_keys = np.insert(a, np.searchsorted(a, b), b)
    np.testing.assert_array_equal(keys, want_keys)
    np.testing.assert_array_equal(np.sort(payload), np.sort(np.r_[pa, pb]))
    assert (keys[np.argsort(keys, kind="stable")] == keys).all()


def test_plan_counters_match_sequential_loop():
    """plan_online_batch's tallies vs a literal Algorithm-2 interpreter."""
    rng = np.random.default_rng(2)
    for trial in range(30):
        n = int(rng.integers(1, 60))
        ids = rng.integers(0, 8, n).astype(np.int64)
        ev = rng.integers(0, 6, n).astype(np.int64)
        cr = int(rng.integers(10, 14))
        # simulated store: some ids present with random (ev, cr)
        state = {
            int(i): (int(rng.integers(0, 6)), int(rng.integers(8, 16)))
            for i in range(8)
            if rng.random() < 0.5
        }
        uids = np.unique(ids)
        old_ev = np.array([state.get(int(u), (0, 0))[0] for u in uids], np.int64)
        old_cr = np.array([state.get(int(u), (0, 0))[1] for u in uids], np.int64)
        found = np.array([int(u) in state for u in uids])
        plan = plan_online_batch(
            ids, ev, cr, lambda u: (old_ev, old_cr, found)
        )
        # sequential reference
        sim = dict(state)
        ins = ovr = nop = 0
        for i in range(n):
            k = int(ids[i])
            if k not in sim:
                sim[k] = (int(ev[i]), cr)
                ins += 1
            elif (int(ev[i]), cr) > sim[k]:
                sim[k] = (int(ev[i]), cr)
                ovr += 1
            else:
                nop += 1
        assert (plan.inserts, plan.overrides, plan.noops) == (ins, ovr, nop), trial
        # winners agree with the simulated end state for batch ids
        for g, u in enumerate(uids):
            want = sim[int(u)]
            if plan.beat[g]:
                assert (int(plan.winner_ev[g]), cr) == want, trial
            else:
                assert want == state[int(u)], trial


def test_encode_keys_string_width_independent():
    """A string id must hash identically regardless of the max width of the
    batch it arrives in — merge/lookup batches rarely share a width."""
    from repro.core.keys import encode_keys

    wide = encode_keys([np.array(["bob", "alexandria", "碧水"], dtype=object)])
    narrow = encode_keys([np.array(["bob"], dtype=object)])
    assert wide[0] == narrow[0]
    pair = encode_keys([np.array(["碧水", ""], dtype=object)])
    assert pair[0] == wide[2]
    # distinct values still disperse; empty string is stable
    assert len(np.unique(wide)) == 3
    assert pair[1] == encode_keys([np.array([""], dtype=object)])[0]


# -- online store: three engines, byte-identical ------------------------------


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    id_hi=st.integers(1, 40),
    ev_hi=st.integers(1, 8),
    n_batches=st.integers(1, 6),
)
def test_online_engines_byte_identical(seed, id_hi, ev_hi, n_batches):
    """Random merge sequences with heavy in-batch duplication and equal-ev
    ties: loop, vector, and kernel engines end byte-identical."""
    spec = make_spec()
    stores = {
        e: OnlineStore(num_partitions=4, initial_capacity=8, merge_engine=e)
        for e in ("loop", "vector", "kernel")
    }
    for b in range(n_batches):
        rng = np.random.default_rng(seed + b)
        frame = make_frame(rng, int(rng.integers(1, 120)), id_hi, ev_hi)
        cr = 10_000 + b * int(rng.integers(0, 2))  # repeated cr => cr ties
        for store in stores.values():
            store.merge(spec, frame, cr)
    assert_online_identical(stores["loop"], stores["vector"], spec, "vector")
    assert_online_identical(stores["loop"], stores["kernel"], spec, "kernel")


def test_online_growth_boundary_identical():
    """Inserts forcing repeated capacity doublings mid-batch land identically
    (same final capacity, same slot assignment) across engines."""
    spec = make_spec()
    rng = np.random.default_rng(3)
    ids = rng.permutation(np.arange(500, dtype=np.int64))
    frame = Table(
        {
            "entity_id": ids,
            "ts": np.full(500, 7, np.int64),
            "f0": rng.random(500).astype(np.float32),
        }
    )
    stores = {
        e: OnlineStore(num_partitions=2, initial_capacity=4, merge_engine=e)
        for e in ("loop", "vector", "kernel")
    }
    for store in stores.values():
        store.merge(spec, frame, 100)
    assert_online_identical(stores["loop"], stores["vector"], spec, "grow/vector")
    assert_online_identical(stores["loop"], stores["kernel"], spec, "grow/kernel")
    assert stores["loop"]._tables[spec.key].keys_full.shape[1] >= 256


def test_online_ttl_sweep_interleaved_identical():
    """TTL expiry + sweep between merges: freed ids re-insert identically."""
    spec = make_spec(ttl=50)
    stores = {
        e: OnlineStore(num_partitions=2, initial_capacity=8, merge_engine=e)
        for e in ("loop", "vector", "kernel")
    }
    rng = np.random.default_rng(4)
    for step, (cr, sweep_at) in enumerate([(100, None), (160, 130), (220, 215)]):
        frame = make_frame(rng, 40, 12, 5)
        for store in stores.values():
            if sweep_at is not None:
                store.sweep("fs", 1, now=sweep_at)
            store.merge(spec, frame, cr)
    assert_online_identical(stores["loop"], stores["vector"], spec, "ttl/vector")
    assert_online_identical(stores["loop"], stores["kernel"], spec, "ttl/kernel")
    # expired records invisible to both lookup paths
    for store in stores.values():
        _, found = store.lookup(
            "fs", 1, [np.arange(12)], now=10_000, use_kernel=False
        )
        assert not found.any()


def test_online_equal_event_ts_tiebreak_counters():
    """Same event_ts, later creation_ts overrides ONCE; in-batch equal-ev
    duplicates are no-ops.  Exact counters on a hand-checked sequence."""
    spec = make_spec()
    for engine in ("loop", "vector", "kernel"):
        s = OnlineStore(num_partitions=2, merge_engine=engine)
        f1 = Table(
            {
                "entity_id": np.array([5, 5, 5], np.int64),
                "ts": np.array([10, 10, 10], np.int64),
                "f0": np.array([1.0, 2.0, 3.0], np.float32),
            }
        )
        s.merge(spec, f1, 100)  # insert + 2 in-batch equal-ev no-ops
        s.merge(spec, f1, 200)  # cr tiebreak: 1 override + 2 no-ops
        s.merge(spec, f1, 150)  # stale cr: 3 no-ops
        assert (s.inserts, s.overrides, s.noops) == (1, 1, 7), engine
        rec = s.get_record("fs", 1, [np.array([5])])[0]
        # first row of the winning batch carries the value
        assert rec["features"][0] == 1.0 and rec["creation_ts"] == 200, engine


# -- offline store: loop vs vector --------------------------------------------


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    id_hi=st.integers(1, 30),
    ev_hi=st.integers(1, 10),
    n_batches=st.integers(1, 6),
)
def test_offline_engines_byte_identical(seed, id_hi, ev_hi, n_batches):
    """Random merges with replays (idempotence) + in-batch duplicate full
    keys: loop and vector end with identical chunks, counters, and index."""
    spec = make_spec()
    a = OfflineStore(num_shards=3, merge_engine="loop")
    b = OfflineStore(num_shards=3, merge_engine="vector")
    for i in range(n_batches):
        rng = np.random.default_rng(seed + i)
        frame = make_frame(rng, int(rng.integers(1, 100)), id_hi, ev_hi)
        cr = 1000 + i
        replay = rng.random() < 0.5  # retry replay: full dedup both paths
        for store in (a, b):
            store.merge(spec, frame, cr)
            if replay:
                store.merge(spec, frame, cr)
    assert_offline_identical(a, b, spec, f"seed={seed}")


def test_offline_compaction_boundary_identical():
    """Chunk-list compaction triggers at the same merge in both engines and
    never changes what ``read`` returns."""
    spec = make_spec()
    a = OfflineStore(num_shards=2, merge_engine="loop", compact_threshold=3)
    b = OfflineStore(num_shards=2, merge_engine="vector", compact_threshold=3)
    rng = np.random.default_rng(5)
    reads = []
    for i in range(8):
        frame = make_frame(rng, 20, 10, 5)
        for store in (a, b):
            store.merge(spec, frame, 1000 + i)
        reads.append(a.read("fs", 1).equals(b.read("fs", 1)))
    assert all(reads)
    assert_offline_identical(a, b, spec, "compaction")
    # compaction actually happened (chunk lists stayed bounded)
    assert all(
        len(s.chunks) <= 4 for s in a._shards[spec.key]
    ) and all(len(s.chunks) <= 4 for s in b._shards[spec.key])


def test_offline_latest_per_key_unchanged_by_engine():
    spec = make_spec()
    a = OfflineStore(num_shards=3, merge_engine="loop")
    b = OfflineStore(num_shards=3, merge_engine="vector")
    rng = np.random.default_rng(6)
    for cr in (1000, 2000, 3000):
        frame = make_frame(rng, 50, 15, 900)
        a.merge(spec, frame, cr)
        b.merge(spec, frame, cr)
    assert a.latest_per_key("fs", 1).equals(b.latest_per_key("fs", 1))
    assert a.time_partitions("fs", 1) == b.time_partitions("fs", 1)


# -- cross-store: the materialization path end-to-end -------------------------


def test_full_pipeline_engines_consistent():
    """Same frames through offline+online with each engine: every engine's
    store pair passes the §4.5.2 consistency check and agrees on state."""
    from repro.core.consistency import check_consistency

    spec = make_spec(n_feats=2)
    rng_seed = 9
    results = {}
    for engine in ("loop", "vector"):
        rng = np.random.default_rng(rng_seed)
        off = OfflineStore(num_shards=2, merge_engine=engine)
        on = OnlineStore(num_partitions=4, merge_engine=engine)
        for i in range(5):
            frame = make_frame(rng, 80, 25, 500, n_feats=2)
            off.merge(spec, frame, 10_000 + i)
            on.merge(spec, frame, 10_000 + i)
        assert check_consistency(spec, off, on).consistent, engine
        results[engine] = (off, on)
    assert_offline_identical(results["loop"][0], results["vector"][0], spec)
    assert_online_identical(results["loop"][1], results["vector"][1], spec)
