"""Sharded multi-home suite (ISSUE 9): routing properties + convergence.

The tentpole claims under test:

  * PARTITION — every encoded key maps to exactly one shard, that shard's
    ``[lo, hi)`` range contains the key's ``shard_coordinate``, and
    ``split_by_owner`` partitions a batch's row indices exactly (no row
    dropped, none duplicated, arrival order preserved per slice);
  * STABILITY — ``assign`` (the rebalance/failover cutover) rewrites only
    the moved range's owner: ownership of every key OUTSIDE the range is
    stable across any sequence of reassignments;
  * UNIFORMITY — routing happens in the ``keys.shard_coordinate`` space,
    so the small-id passthrough of ``encode_keys`` (ids returned unmixed)
    still spreads across all ranges instead of piling into shard 0;
  * AGREEMENT — the delta-bootstrap ``key_range`` filter masks on the
    SAME coordinate the router cuts on, so the rows a rebalance streams
    are exactly the rows the new owner will route to itself;
  * CONVERGENCE — concurrent writes entering at EVERY region converge the
    mesh byte-identical online / chunk-set-identical offline, including
    after per-shard failover, rejoin + rebalance, and graceful leave, and
    the steady state is echo-free (a drained mesh ships nothing more);
  * FACADE — ``FeatureStore``, ``GeoFeatureStore`` and
    ``MultiHomeGeoStore`` all satisfy the unified ``StoreFacade`` surface.

Routing properties run under ``hypothesis``.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.facade import StoreFacade
from repro.core.keys import KEY_SPACE_BITS, encode_keys, shard_coordinate
from repro.core.monitoring import HealthMonitor
from repro.core.multihome import MultiHomeGeoStore
from repro.core.regions import (
    GeoTopology,
    Region,
    RegionDownError,
    ShardMap,
)
from tests.core.test_replication import make_frame, make_spec

KEY_SPACE = 1 << KEY_SPACE_BITS
MH_REGIONS = ("r0", "r1", "r2")


def mh_topo():
    return GeoTopology(
        regions={r: Region(r) for r in MH_REGIONS},
        local_latency_ms=1.0,
        cross_region_latency_ms=60.0,
        link_latency_ms={
            ("r0", "r1"): 20.0,
            ("r1", "r2"): 30.0,
            ("r0", "r2"): 90.0,
        },
    )


def make_mh(**kw):
    kw.setdefault("topology", mh_topo())
    kw.setdefault("regions", list(MH_REGIONS))
    kw.setdefault("online_partitions", 4)
    mh = MultiHomeGeoStore("mh", **kw)
    mh.create_feature_set(make_spec())
    mh.advance_clock(10**9)
    return mh


def write_everywhere(mh, rng, *, rows=400, base_ts=10**7):
    """One concurrent ingest wave: a distinct batch enters at EVERY home."""
    return [
        mh.write_batch(
            "fs",
            1,
            make_frame(rng, rows, 5_000, 10**6),
            region=r,
            creation_ts=base_ts + i,
        )
        for i, r in enumerate(mh.regions())
    ]


def assert_mesh_identical(mh, ctx=""):
    """Drained-mesh invariant: every cell byte-identical online and
    chunk-set-identical offline (canonical_history sorts by full key)."""
    regions = mh.regions()
    ref_on = mh.online[regions[0]].dump_all("fs", 1)
    ref_off = mh.offline[regions[0]].canonical_history("fs", 1)
    for r in regions[1:]:
        d = mh.online[r].dump_all("fs", 1)
        for n in ref_on.names:
            np.testing.assert_array_equal(
                ref_on[n], d[n], err_msg=f"{ctx} [online {r}: {n}]"
            )
        h = mh.offline[r].canonical_history("fs", 1)
        assert len(ref_off) == len(h), f"{ctx} [offline {r}: row count]"
        for n in ref_off.names:
            np.testing.assert_array_equal(
                ref_off[n], h[n], err_msg=f"{ctx} [offline {r}: {n}]"
            )


# -- routing properties (hypothesis) ------------------------------------------


@settings(max_examples=60, deadline=None)
@given(
    st.integers(min_value=0, max_value=2**62),
    st.integers(min_value=2, max_value=5),
    st.integers(min_value=2, max_value=16),
)
def test_every_key_has_exactly_one_home(key, n_regions, n_shards):
    """Partition: one shard, whose coordinate range contains the key, and
    split_by_owner hands the key to exactly that shard's owner."""
    sm = ShardMap.even([f"h{i}" for i in range(n_regions)], n_shards)
    arr = np.array([key], np.int64)
    sid = int(sm.shard_of(arr)[0])
    assert 0 <= sid < sm.num_shards
    lo, hi = sm.shard_range(sid)
    coord = int(shard_coordinate(arr)[0])
    assert lo <= coord < hi
    split = sm.split_by_owner(arr)
    holders = [r for r, idx in split.items() if len(idx)]
    assert holders == [sm.owner_of(sid)]


@settings(max_examples=30, deadline=None)
@given(
    st.integers(min_value=0, max_value=2**32),
    st.integers(min_value=3, max_value=12),
    st.lists(st.integers(min_value=0, max_value=11), min_size=1, max_size=6),
)
def test_ownership_stable_outside_reassigned_ranges(seed, n_shards, moves):
    """Stability: an arbitrary sequence of assigns changes ownership ONLY
    for keys inside the reassigned ranges; shard ids never change at all
    (bounds are fixed at construction)."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2**62, 512).astype(np.int64)
    sm = ShardMap.even(list(MH_REGIONS), n_shards)
    sids = sm.shard_of(keys)
    owners_before = np.array([sm.owner_of(int(s)) for s in sids])
    touched = set()
    for i, mv in enumerate(moves):
        sid = mv % n_shards
        sm.assign(sid, MH_REGIONS[i % len(MH_REGIONS)])
        touched.add(sid)
    np.testing.assert_array_equal(sm.shard_of(keys), sids)
    owners_after = np.array([sm.owner_of(int(s)) for s in sids])
    moved = owners_before != owners_after
    assert set(np.unique(sids[moved]).tolist()) <= touched
    assert sm.version == len(moves)


def test_split_by_owner_partitions_rows_in_arrival_order():
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 2**62, 3_000).astype(np.int64)
    sm = ShardMap.even(list(MH_REGIONS), 9)  # several ranges per region
    split = sm.split_by_owner(keys)
    combined = np.sort(np.concatenate(list(split.values())))
    np.testing.assert_array_equal(combined, np.arange(len(keys)))
    sids = sm.shard_of(keys)
    for region, idx in split.items():
        assert np.all(np.diff(idx) > 0)  # arrival order, no duplicates
        assert all(sm.owner_of(int(s)) == region for s in sids[idx])


def test_small_passthrough_ids_spread_across_all_ranges():
    """The regression that motivated ``shard_coordinate``: encode_keys
    passes small single-column ids through unmixed, so routing on the raw
    encoded key piles every real-world id into shard 0."""
    ids = encode_keys([np.arange(3_000, dtype=np.int64)])
    sm = ShardMap.even(list(MH_REGIONS))
    counts = np.bincount(sm.shard_of(ids), minlength=3)
    assert counts.sum() == 3_000
    assert counts.min() > 700  # near-uniform thirds, not one hot range


def test_range_filter_agrees_with_routing():
    """The delta-bootstrap key_range mask and shard_of must carve the
    keyspace identically, or a rebalance streams the wrong rows."""
    rng = np.random.default_rng(11)
    keys = np.concatenate(
        [rng.integers(0, 2**62, 2_000), np.arange(200)]
    ).astype(np.int64)
    sm = ShardMap.even(list(MH_REGIONS), 5)
    coords = shard_coordinate(keys)
    sids = sm.shard_of(keys)
    for sid in range(sm.num_shards):
        lo, hi = sm.shard_range(sid)
        mask = (coords >= np.uint64(lo)) & (coords < np.uint64(hi))
        np.testing.assert_array_equal(mask, sids == sid, err_msg=f"shard {sid}")


def test_shard_ranges_tile_the_keyspace():
    sm = ShardMap.even(list(MH_REGIONS), 7)
    edges = [sm.shard_range(s) for s in range(sm.num_shards)]
    assert edges[0][0] == 0 and edges[-1][1] == KEY_SPACE
    for (_, hi), (lo, _) in zip(edges, edges[1:]):
        assert hi == lo


def test_negative_keys_rejected():
    sm = ShardMap.even(list(MH_REGIONS))
    with pytest.raises(ValueError, match="non-negative"):
        sm.shard_of(np.array([-1], np.int64))


# -- one facade over every store front ----------------------------------------


def test_store_fronts_satisfy_the_facade():
    from repro.core.featurestore import FeatureStore
    from repro.core.replication import GeoFeatureStore

    fs = FeatureStore("plain", region="r0", topology=mh_topo())
    geo = GeoFeatureStore("single-home", topology=mh_topo(), home_region="r0")
    mh = make_mh()
    for store in (fs, geo, mh):
        assert isinstance(store, StoreFacade), type(store).__name__


# -- gauge hygiene (the satellite bugfix) -------------------------------------


def test_clear_replica_gauges_is_shard_aware():
    """Per-shard lag gauges put the replica MID-PATH
    (``replication/shard_lag_batches/{replica}/{shard}``); eviction must
    clear those too, but only on full path segments — a replica named
    ``r1`` must not clear ``r11``'s gauges."""
    mon = HealthMonitor()
    mon.record_shard_lag("r1", 2, batches=5, rows=100)
    mon.record_shard_lag("r11", 2, batches=3, rows=60)
    mon.system.set_gauge("replication/lag_batches/r1", 5.0)
    mon.clear_replica_gauges("r1")
    gauges = mon.system.gauges
    assert not [
        k
        for k in gauges
        if k.startswith("replication/") and "r1" in k.split("/")
    ]
    assert gauges["replication/shard_lag_batches/r11/2"] == 3.0


# -- active-active convergence ------------------------------------------------


def test_concurrent_writes_at_every_home_converge():
    mh = make_mh()
    rng = np.random.default_rng(3)
    infos = write_everywhere(mh, rng)
    assert mh.pending_batches() > 0  # something actually replicated
    mh.converge()
    assert_mesh_identical(mh, "steady state")
    for info, region in zip(infos, mh.regions()):
        assert sum(info["slices"].values()) == info["rows"]
        assert info["forwarded_rows"] == info["rows"] - info["slices"].get(
            region, 0
        )
    wl = mh.write_log
    assert wl["rows"] == sum(i["rows"] for i in infos)
    assert wl["forwarded_rows"] == sum(i["forwarded_rows"] for i in infos)
    assert wl["local_rows"] == wl["rows"] - wl["forwarded_rows"]
    assert (
        mh.monitor.system.counters["multihome/forwarded_rows"]
        == wl["forwarded_rows"]
    )


def test_converged_mesh_is_echo_free():
    """After converge, further drains ship NOTHING: replica applies of
    foreign batches publish no echo into their own home's log."""
    mh = make_mh()
    rng = np.random.default_rng(4)
    write_everywhere(mh, rng)
    mh.converge()
    shipped = lambda: sum(
        ledger.batches
        for rep in mh.replicators.values()
        for ledger in rep.shipped.values()
    )
    before = shipped()
    for _ in range(3):
        mh.drain()
    assert mh.pending_batches() == 0
    assert shipped() == before
    assert mh.converge() == 0


def test_cross_shard_read_routes_in_sync_and_finds_all_rows():
    mh = make_mh()
    rng = np.random.default_rng(5)
    ids = np.arange(256, dtype=np.int64)
    frame = make_frame(rng, 256, 5_000, 10**6)
    frame.columns["entity_id"] = ids  # every queried id was written
    mh.write_batch("fs", 1, frame, region="r1", creation_ts=10**7)
    mh.converge()
    vals, found, route = mh.get_online_features(
        "fs", 1, [ids], consumer_region="r2"
    )
    assert found.all() and vals.shape == (256, 2)
    assert route["consumer"] == "r2"
    # every range serves from the in-sync consumer cell once converged
    assert {leg["region"] for leg in route["per_range"].values()} == {"r2"}
    assert route["modeled_ms"] == 1.0
    # a lagging consumer falls back to each range's HOME
    mh.write_batch("fs", 1, frame, region="r0", creation_ts=10**7 + 1)
    _, _, route = mh.get_online_features("fs", 1, [ids], consumer_region="r2")
    for sid, leg in route["per_range"].items():
        if sid not in mh.shard_map.owned_shards("r2"):
            assert leg["region"] == mh.shard_map.owner_of(sid)
    mh.converge()


def test_write_at_inactive_region_raises():
    mh = make_mh()
    rng = np.random.default_rng(6)
    with pytest.raises(RegionDownError, match="not an active home"):
        mh.write_batch(
            "fs", 1, make_frame(rng, 8, 100, 10**6), region="elsewhere"
        )


def test_failover_is_noop_while_everyone_is_healthy():
    assert make_mh().failover() is None


def test_per_shard_failover_moves_only_the_lost_range():
    mh = make_mh()
    rng = np.random.default_rng(8)
    write_everywhere(mh, rng)
    mh.converge()
    write_everywhere(mh, rng, base_ts=10**7 + 10)  # un-drained suffix
    owners_before = list(mh.shard_map.owners)
    victim = "r2"
    lost = mh.shard_map.owned_shards(victim)
    mh.mark_down(victim)
    info = mh.failover()
    assert info["shards"] == lost
    assert info["promoted"] in mh.regions()
    assert info["replayed_batches"] > 0  # the un-acked suffix replayed
    for sid, owner in enumerate(owners_before):
        expect = info["promoted"] if sid in lost else owner
        assert mh.shard_map.owner_of(sid) == expect
    assert victim not in mh.regions()
    mh.converge()
    assert_mesh_identical(mh, "post-failover")
    # the survivors still serve the WHOLE keyspace, writes keep flowing
    write_everywhere(mh, rng, base_ts=10**7 + 20)
    mh.converge()
    assert_mesh_identical(mh, "post-failover writes")
    ids = np.arange(64, dtype=np.int64)
    _, _, route = mh.get_online_features("fs", 1, [ids], consumer_region="r0")
    assert set(route["per_range"]) == set(range(mh.shard_map.num_shards))


def test_rejoin_comes_back_empty_then_rebalance_hands_a_range_back():
    mh = make_mh()
    rng = np.random.default_rng(9)
    write_everywhere(mh, rng)
    mh.converge()
    victim = "r2"
    lost = mh.shard_map.owned_shards(victim)
    mh.mark_down(victim)
    mh.failover()
    mh.converge()
    mh.mark_up(victim)
    back = mh.rejoin(victim)
    assert back["online_rows"] > 0 and back["offline_rows"] > 0
    assert mh.shard_map.owned_shards(victim) == []  # no ranges until handed
    mh.converge()
    assert_mesh_identical(mh, "post-rejoin")
    moved = mh.rebalance(lost[0], victim)
    assert moved["moved"] and mh.shard_map.owner_of(lost[0]) == victim
    write_everywhere(mh, rng, base_ts=10**7 + 30)  # incl. at the rejoined home
    mh.converge()
    assert_mesh_identical(mh, "post-rebalance writes")
    assert mh.monitor.system.counters["shards/rebalances"] == 1


def test_graceful_leave_rehomes_ranges_and_survivors_converge():
    mh = make_mh()
    rng = np.random.default_rng(10)
    write_everywhere(mh, rng)
    mh.converge()
    out = mh.leave_region("r2")
    assert out["left"] == "r2" and len(out["moves"]) == 1
    assert "r2" not in mh.shard_map.regions()
    assert mh.regions() == ["r0", "r1"]
    write_everywhere(mh, rng, base_ts=10**7 + 40)
    mh.converge()
    assert_mesh_identical(mh, "post-leave writes")
    with pytest.raises(ValueError, match="below two homes"):
        mh.leave_region("r1")


def test_rebalance_to_same_owner_is_a_noop():
    mh = make_mh()
    owner = mh.shard_map.owner_of(0)
    assert mh.rebalance(0, owner) == {
        "shard": 0,
        "from": owner,
        "to": owner,
        "moved": False,
    }
    assert mh.shard_map.version == 0
