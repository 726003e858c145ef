"""Device-resident online store: host-mirror/device-truth protocol.

The contract under test (ISSUE 2 tentpole): device memory is the source of
truth for the kernel engine's planes; host numpy mirrors are lazy, dirty-
tracked, synced on demand, and invalidated across ``_grow``/``sweep``/engine
switches.  Stale-mirror reads are the main new failure mode, so every
host-facing consumer (``dump_all``, ``get_record``, host-path lookups, the
``vector``/``loop`` engines) is exercised against fresh kernel merges; and a
steady-state merge+lookup cycle must move O(batch) bytes host<->device, not
O(P·C·D).  Sweep slot recycling (the TTL-churn capacity leak fix) is covered
here too, across all three engines.
"""

import numpy as np
import pytest

from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import UDFTransform
from repro.core.online_store import OnlineStore, o_batch_byte_budget
from repro.core.table import Table
from tests.core.test_merge_engine import assert_online_identical


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


# -- TTL expiry parity: kernel (device) vs host lookup path -------------------


def test_ttl_expiry_parity_kernel_vs_host_lookup():
    """Same store, both GET paths, across the expiry boundary: byte-identical
    (values AND found), with the kernel path reading creation_ts from device
    truth rather than the host mirror."""
    spec = make_spec(ttl=100)
    store = OnlineStore(num_partitions=4, merge_engine="kernel")
    rng = np.random.default_rng(0)
    store.merge(spec, make_frame(rng, 60, 20, 50), 1_000)
    store.merge(spec, make_frame(rng, 60, 20, 80), 1_050)  # half re-stamped
    ids = [np.arange(25, dtype=np.int64)]
    # now=None skips TTL; then just-inside, boundary (not expired: > is
    # strict), and past-expiry for the older creation_ts cohort
    for now in (None, 1_060, 1_100, 1_120, 1_200):
        vk, fk = store.lookup("fs", 1, ids, now=now, use_kernel=True)
        vh, fh = store.lookup("fs", 1, ids, now=now, use_kernel=False)
        np.testing.assert_array_equal(fk, fh, err_msg=f"found @ now={now}")
        np.testing.assert_array_equal(vk, vh, err_msg=f"values @ now={now}")
    # fully expired: both paths agree on nothing found
    _, fk = store.lookup("fs", 1, ids, now=10_000, use_kernel=True)
    _, fh = store.lookup("fs", 1, ids, now=10_000, use_kernel=False)
    assert not fk.any() and not fh.any()


def test_ttl_parity_after_sweep_and_reinsert():
    spec = make_spec(ttl=50)
    store = OnlineStore(num_partitions=2, initial_capacity=8, merge_engine="kernel")
    rng = np.random.default_rng(1)
    store.merge(spec, make_frame(rng, 30, 10, 5), 100)
    store.sweep("fs", 1, now=200)  # everything expired + freed
    store.merge(spec, make_frame(rng, 30, 10, 5), 300)  # recycled slots
    ids = [np.arange(10, dtype=np.int64)]
    for now in (310, 349, 350, 351, 400):
        vk, fk = store.lookup("fs", 1, ids, now=now, use_kernel=True)
        vh, fh = store.lookup("fs", 1, ids, now=now, use_kernel=False)
        np.testing.assert_array_equal(fk, fh, err_msg=f"now={now}")
        np.testing.assert_array_equal(vk, vh, err_msg=f"now={now}")


# -- mirror invalidation across engine switches / grow / sweep / dump ---------


def test_engine_switch_sequences_stay_identical():
    """kernel -> vector -> kernel -> loop on ONE store: every switch crosses
    the device/host truth boundary (sync + drop on the way down, re-upload
    on the way up).  End state must match a pure-loop store."""
    spec = make_spec()
    mixed = OnlineStore(num_partitions=4, initial_capacity=8)
    ref = OnlineStore(num_partitions=4, initial_capacity=8, merge_engine="loop")
    rng = np.random.default_rng(2)
    frames = [make_frame(rng, 50, 30, 6) for _ in range(4)]
    for i, (f, engine) in enumerate(
        zip(frames, ("kernel", "vector", "kernel", "loop"))
    ):
        mixed.merge(spec, f, 1_000 + i, engine=engine)
        ref.merge(spec, f, 1_000 + i, engine="loop")
    assert_online_identical(mixed, ref, spec, "engine switching")


def test_host_reads_see_kernel_merges():
    """dump_all / get_record / host lookup immediately after kernel merges:
    the lazy mirror must sync, not serve stale planes."""
    spec = make_spec()
    store = OnlineStore(num_partitions=4, merge_engine="kernel")
    rng = np.random.default_rng(3)
    store.merge(spec, make_frame(rng, 40, 15, 10), 500)
    t = store._tables[spec.key]
    assert t.host_stale  # kernel merge advanced device truth
    # an override the stale mirror doesn't know about
    f = Table({
        "entity_id": np.array([3], np.int64),
        "ts": np.array([99], np.int64),
        "f0": np.array([7.5], np.float32),
    })
    store.merge(spec, f, 600)
    rec = store.get_record("fs", 1, [np.array([3])])[0]
    assert rec["event_ts"] == 99 and rec["features"][0] == 7.5
    assert not t.host_stale  # get_record synced
    store.merge(spec, f, 700)  # noop (same ev, but cr 700 > 600 -> override)
    dump = store.dump_all("fs", 1)
    i = int(np.searchsorted(dump["__key__"], 3))
    assert dump["creation_ts"][i] == 700
    v, fd = store.lookup("fs", 1, [np.array([3])], use_kernel=False)
    assert fd[0] and v[0, 0] == 7.5


def test_grow_mid_kernel_stream_identical():
    """Capacity doublings during kernel merges force sync+drop+reupload;
    state stays byte-identical to the loop reference."""
    spec = make_spec()
    k = OnlineStore(num_partitions=2, initial_capacity=4, merge_engine="kernel")
    l = OnlineStore(num_partitions=2, initial_capacity=4, merge_engine="loop")
    rng = np.random.default_rng(4)
    ids = rng.permutation(np.arange(300, dtype=np.int64))
    for lo in range(0, 300, 60):  # growth interleaved with merges
        f = Table({
            "entity_id": ids[lo:lo + 60],
            "ts": np.full(60, 5, np.int64),
            "f0": rng.random(60).astype(np.float32),
        })
        k.merge(spec, f, 1_000 + lo)
        l.merge(spec, f, 1_000 + lo)
    assert_online_identical(k, l, spec, "grow under kernel engine")
    assert k._tables[spec.key].keys_full.shape[1] >= 256


def test_mirror_is_writable_after_kernel_merge():
    """Regression: the PR-1 kernel path left np views of device buffers as
    host planes — a later loop/vector merge on the same store would raise
    'assignment destination is read-only'.  The sync protocol must hand the
    host engines writable mirrors."""
    spec = make_spec()
    store = OnlineStore(num_partitions=2, merge_engine="kernel")
    rng = np.random.default_rng(5)
    store.merge(spec, make_frame(rng, 20, 8, 5), 100)
    store.merge(spec, make_frame(rng, 20, 8, 5), 200, engine="loop")  # must not raise
    store.merge(spec, make_frame(rng, 20, 8, 5), 300, engine="vector")
    for plane in ("event_ts", "creation_ts", "values"):
        assert getattr(store._tables[spec.key], plane).flags.writeable


# -- sweep slot recycling (TTL-churn capacity leak fix) -----------------------


@pytest.mark.parametrize("engine", ["loop", "vector", "kernel"])
def test_sweep_recycles_slots_capacity_bounded(engine):
    """Rolling TTL churn: every generation expires and is swept before the
    next insert wave.  With free-list recycling the partitions must never
    grow past their initial capacity (the pre-fix store doubled forever)."""
    spec = make_spec(ttl=10)
    store = OnlineStore(
        num_partitions=2, initial_capacity=64, merge_engine=engine
    )
    rng = np.random.default_rng(6)
    for gen in range(8):
        ids = (gen * 100 + np.arange(80)).astype(np.int64)  # fresh ids per gen
        f = Table({
            "entity_id": ids,
            "ts": np.full(80, gen, np.int64),
            "f0": rng.random(80).astype(np.float32),
        })
        now = gen * 100
        if gen:
            store.sweep("fs", 1, now=now)
        store.merge(spec, f, now + 1)
    t = store._tables[spec.key]
    assert t.keys_full.shape[1] == 64, "TTL churn leaked capacity"
    assert store.num_records("fs", 1) == 80
    # fill is bounded by live records + transient imbalance, never cumulative
    assert int(t.fill.sum()) <= 128


def test_sweep_recycling_parity_across_engines():
    """Sweep-heavy interleavings with partial expiry: all engines assign
    recycled slots identically (free lists are part of the compared state)."""
    spec = make_spec(ttl=40)
    stores = {
        e: OnlineStore(num_partitions=4, initial_capacity=8, merge_engine=e)
        for e in ("loop", "vector", "kernel")
    }
    rng = np.random.default_rng(7)
    for step in range(6):
        frame = make_frame(rng, 30, 25, 5)
        now = 100 + step * 30
        for store in stores.values():
            if step % 2:
                store.sweep("fs", 1, now=now)
            store.merge(spec, frame, now)
    assert_online_identical(stores["loop"], stores["vector"], spec, "sweep/vector")
    assert_online_identical(stores["loop"], stores["kernel"], spec, "sweep/kernel")


# -- transfer accounting: steady state is O(batch) ----------------------------


def test_steady_state_cycle_moves_o_batch_bytes():
    """After warmup, a kernel merge+lookup cycle must not re-upload or pull
    the (P, C, D) planes: zero device uploads, zero host syncs, and per-cycle
    bytes bounded by a small multiple of the batch footprint — far below the
    table footprint."""
    spec = make_spec(ttl=None, n_feats=4)
    store = OnlineStore(
        num_partitions=8, initial_capacity=256, merge_engine="kernel"
    )
    rng = np.random.default_rng(8)
    store.merge(spec, make_frame(rng, 20_000, 5_000, 100, n_feats=4), 10**6)
    batch = 512
    ids = [rng.integers(0, 5_000, batch).astype(np.int64)]
    # warm both jitted paths at the steady batch shapes
    store.merge(spec, make_frame(rng, batch, 5_000, 200, n_feats=4), 2 * 10**6)
    store.lookup("fs", 1, ids)
    store.reset_transfer_stats()

    cycles = 10
    for i in range(cycles):
        store.merge(
            spec, make_frame(rng, batch, 5_000, 300 + i, n_feats=4),
            3 * 10**6 + i,
        )
        store.lookup("fs", 1, ids)
    tx = store.transfer_stats()
    assert tx["device_uploads"] == 0, "steady-state merge re-uploaded the table"
    assert tx["host_syncs"] == 0, "steady-state cycle pulled the host mirror"

    table_bytes = store.device_state("fs", 1).nbytes()
    per_cycle = (tx["h2d_bytes"] + tx["d2h_bytes"]) / cycles
    record_bytes = 8 * 4 + 4 * 4  # id/ts planes + 4 f32 features
    assert per_cycle <= o_batch_byte_budget(batch, record_bytes), (
        f"per-cycle traffic {per_cycle} not O(batch)"
    )
    assert per_cycle < table_bytes / 4, (
        f"per-cycle traffic {per_cycle} is table-sized ({table_bytes})"
    )


def test_transfer_ledger_counts_uploads_and_syncs():
    spec = make_spec()
    store = OnlineStore(num_partitions=2, merge_engine="kernel")
    rng = np.random.default_rng(9)
    store.merge(spec, make_frame(rng, 50, 20, 5), 100)
    tx = store.transfer_stats()
    assert tx["device_uploads"] >= 1 and tx["h2d_bytes"] > 0
    assert tx["host_syncs"] == 0
    store.dump_all("fs", 1)  # forces one mirror sync
    assert store.transfer_stats()["host_syncs"] == 1
    store.dump_all("fs", 1)  # mirror clean: no second pull
    assert store.transfer_stats()["host_syncs"] == 1


# -- the lane-aligned device value plane --------------------------------------


@pytest.mark.parametrize(
    ("n_feats", "width"),
    [(1, 1), (3, 3), (4, 4), (5, 8), (8, 8), (9, 16), (40, 40), (56, 56),
     (57, 128), (128, 128), (129, 256), (250, 256), (300, 384)],
)
def test_device_width_rule(n_feats, width):
    """Widths the TPU compiler keeps row-major stay; the others pad to the
    next width that is copy-free (tests/kernels/test_tpu_compile.py)."""
    from repro.core.online_store import device_width

    assert device_width(n_feats) == width


@pytest.mark.parametrize("n_feats", [130, 250])
def test_padded_value_plane_matches_vector_engine(tmp_path, n_feats):
    """At a width that is not a multiple of 128 the device plane is
    (P, C, device_width(D)) with zero pad columns, and the kernel engine's
    GET triples stay byte-identical to the vector engine's through inserts,
    overrides, no-ops, a TTL expiry, a capacity doubling and a mirror sync."""
    import jax

    from repro.core.monitoring import SPANS
    from repro.core.online_store import device_width

    width = device_width(n_feats)
    assert width == 256
    spec = make_spec(ttl=100, n_feats=n_feats)
    k = OnlineStore(num_partitions=2, initial_capacity=8, merge_engine="kernel")
    v = OnlineStore(num_partitions=2, initial_capacity=8, merge_engine="vector")
    rng = np.random.default_rng(10)
    ids = np.arange(40, dtype=np.int64)

    def same_gets(now, label):
        got_k = k.lookup_encoded("fs", 1, ids, now=now)
        got_v = v.lookup_encoded("fs", 1, ids, now=now, use_kernel=False)
        for a, b in zip(got_k, got_v):
            assert a.dtype == b.dtype and a.shape == b.shape, label
            assert a.tobytes() == b.tobytes(), label

    def plane_ok(label):
        vals = np.asarray(k.device_state("fs", 1).values)
        c = k._tables[spec.key].keys_full.shape[1]
        assert vals.shape == (2, c, width), label
        assert not vals[..., n_feats:].any(), f"{label}: pad columns written"

    # inserts, then overrides and no-ops (the same ids at earlier and later ts)
    for i, creation in enumerate((1_000, 1_050, 1_060)):
        f = make_frame(rng, 30, 20, 10 + 40 * i, n_feats=n_feats)
        k.merge(spec, f, creation)
        v.merge(spec, f, creation)
        same_gets(creation + 5, f"merge {i}")
    assert k.noops > 0 and k.overrides > 0
    plane_ok("after merges")
    same_gets(1_125, "1,000 cohort expired")  # TTL: older rows invisible
    found = [k.lookup_encoded("fs", 1, ids, now=now)[1].sum() for now in (1_065, 1_125)]
    assert found[1] < found[0]
    # a capacity doubling: the kernel store syncs, drops and re-uploads
    f = make_frame(rng, 60, 400, 200, n_feats=n_feats)
    k.merge(spec, f, 1_100)
    v.merge(spec, f, 1_100)
    assert k._tables[spec.key].keys_full.shape[1] > 8
    plane_ok("after grow")
    ids = np.concatenate([ids, np.unique(f["entity_id"])])
    same_gets(1_110, "after grow")
    # the host mirror carries no pad
    k.sync_host_mirrors()
    assert k._tables[spec.key].values.shape[-1] == n_feats
    assert_online_identical(k, v, spec, "padded plane")
    with jax.profiler.trace(str(tmp_path / "trace")):
        k.lookup_encoded("fs", 1, ids[:5])
    (lookup,) = SPANS.spans("fs.store.lookup")
    assert lookup.attrs["pad_cols"] == width - n_feats


@pytest.mark.parametrize("n_feats", [1, 5, 9, 57])
def test_kernel_get_matches_host_get_through_kernel_merges(n_feats):
    """Kernel merges that insert, override and no-op, interleaved with
    kernel GETs: every GET triple is byte-identical to a host GET of a
    vector-engine store fed the same batches, at widths the device plane
    keeps (1), pads to 8 (5), to 16 (9) and to 128 (57)."""
    spec = make_spec(ttl=1_000, n_feats=n_feats)
    k = OnlineStore(num_partitions=4, initial_capacity=16, merge_engine="kernel")
    v = OnlineStore(num_partitions=4, initial_capacity=16, merge_engine="vector")
    rng = np.random.default_rng(13 + n_feats)
    ids = np.arange(-3, 90, dtype=np.int64)
    for i in range(6):
        # each batch reaches new ids (inserts) and revisits old ones
        # (overrides and no-ops); the table doubles on the way, and GETs
        # at ``now`` see the first cohort expire
        f = make_frame(rng, 40 if i != 3 else 120, 30 + 12 * i, 20 + 5 * i,
                       n_feats=n_feats)
        k.merge(spec, f, 1_000 + 10 * (i % 3))
        v.merge(spec, f, 1_000 + 10 * (i % 3))
        assert k._tables[spec.key].host_stale
        now = 1_000 + 10 * i + 995
        got_k = k.lookup_encoded("fs", 1, ids, now=now)
        got_v = v.lookup_encoded("fs", 1, ids, now=now, use_kernel=False)
        for a, b in zip(got_k, got_v):
            assert a.dtype == b.dtype and a.shape == b.shape, i
            assert a.tobytes() == b.tobytes(), i
    assert k._tables[spec.key].keys_full.shape[1] > 16
    assert k.inserts == v.inserts and k.overrides and k.noops
    assert (k.overrides, k.noops) == (v.overrides, v.noops)
    assert_online_identical(k, v, spec, f"width {n_feats}")


# -- the device key index the kernel GET searches -----------------------------


def test_device_index_exact_through_merges_sweep_and_grow():
    """Kernel merges that insert, override and no-op, a sweep and a capacity
    doubling, each followed by kernel GETs: the device index follows the
    host's, so kernel and host-mirror GETs agree byte for byte, TTL
    included, after every step."""
    spec = make_spec(ttl=500, n_feats=3)
    store = OnlineStore(num_partitions=4, initial_capacity=64, merge_engine="kernel")
    rng = np.random.default_rng(11)
    probes = np.arange(-5, 400, dtype=np.int64)

    def frame(ids, ts):
        return Table({
            "entity_id": np.asarray(ids, np.int64),
            "ts": np.full(len(ids), ts, np.int64),
            **{f"f{i}": rng.random(len(ids)).astype(np.float32) for i in range(3)},
        })

    def same_gets(now, label):
        got_k = store.lookup_encoded("fs", 1, probes, now=now)
        got_h = store.lookup_encoded("fs", 1, probes, now=now, use_kernel=False)
        for a, b in zip(got_k, got_h):
            assert a.dtype == b.dtype and a.shape == b.shape, label
            assert a.tobytes() == b.tobytes(), label

    steps = [
        ("load", lambda: store.merge(spec, frame(np.arange(0, 150), 10), 1_000)),
        ("insert", lambda: store.merge(spec, frame(np.arange(150, 170), 10), 1_100)),
        ("override", lambda: store.merge(spec, frame(np.arange(0, 40), 20), 1_200)),
        ("no-op", lambda: store.merge(spec, frame(np.arange(40, 60), 5), 1_300)),
        ("mixed", lambda: store.merge(
            spec, frame(np.r_[170:180, 60:70, 300:305], 15), 1_400)),
        ("sweep", lambda: store.sweep("fs", 1, now=1_550)),
        ("reinsert", lambda: store.merge(spec, frame(np.arange(100, 130), 30), 1_600)),
        ("grow", lambda: store.merge(spec, frame(np.arange(180, 400), 30), 1_700)),
        ("insert after grow", lambda: store.merge(spec, frame([-3, 399, 2], 40), 1_800)),
    ]
    updates = []
    for label, step in steps:
        step()
        for now in (None, 1_560, 2_050, 2_150):
            same_gets(now, f"{label} @ {now}")
        updates.append(store.transfers["index_updates"])
    assert store.num_records("fs", 1) > 0 and store.overrides and store.noops
    assert store._tables[spec.key].keys_full.shape[1] > 64
    # the inserts after the load were merged into the device index in place
    assert updates[1] == 1 and updates[-1] > updates[1]


def test_index_updates_move_o_inserted_bytes():
    """A kernel merge that inserts does no device work on the index; the
    next kernel GET uploads O(inserted) index bytes in one index update,
    and GETs with nothing pending upload only their queries."""
    spec = make_spec(n_feats=4)
    store = OnlineStore(num_partitions=8, initial_capacity=8192, merge_engine="kernel")
    rng = np.random.default_rng(12)
    store.merge(spec, make_frame(rng, 6_000, 6_000, 100, n_feats=4), 1_000)
    batch = 512
    ids = rng.integers(0, 6_000, batch).astype(np.int64)
    store.lookup_encoded("fs", 1, ids)  # brings the load's keys in, warms
    index = store.device_state("fs", 1).index
    assert not index.pending
    index_bytes = 16 * 8 * 8192

    store.reset_transfer_stats()
    for _ in range(10):
        store.lookup_encoded("fs", 1, ids)
    tx = store.transfer_stats()
    assert tx["index_updates"] == 0 and tx["device_uploads"] == 0
    record_bytes = 8 * 4 + 4 * 4
    assert (tx["h2d_bytes"] + tx["d2h_bytes"]) / 10 <= o_batch_byte_budget(
        batch, record_bytes
    )
    assert tx["h2d_bytes"] == 10 * 2 * 4 * batch  # the queries alone

    n = 37
    new_ids = np.arange(10_000, 10_000 + n, dtype=np.int64)
    store.merge(spec, Table({
        "entity_id": new_ids,
        "ts": np.full(n, 5, np.int64),
        **{f"f{i}": np.full(n, i, np.float32) for i in range(4)},
    }), 2_000)
    assert store.device_state("fs", 1).index is index  # untouched by the merge
    assert [len(p[0]) for p in index.pending] == [n]
    store.reset_transfer_stats()
    vals, found, _ = store.lookup_encoded("fs", 1, new_ids)
    tx = store.transfer_stats()
    assert tx["index_updates"] == 1 and tx["device_uploads"] == 0
    assert tx["h2d_bytes"] == 2 * 4 * 128 + 16 * 128  # queries + n entries' bucket
    assert tx["h2d_bytes"] < index_bytes / 100
    assert found.all() and (vals == np.arange(4, dtype=np.float32)).all()
    store.reset_transfer_stats()
    store.lookup_encoded("fs", 1, new_ids)
    assert store.transfer_stats()["index_updates"] == 0
