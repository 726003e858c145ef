"""Replica-daemon e2e over a real localhost socket (ISSUE 8 tentpole).

Every test here spawns ``repro.core.daemon`` as a genuine child process
(``python -m``, own interpreter, own stores) and talks to it through the
stream envelope — no in-process shortcuts.  The claims:

  * ROUND TRIP — frames transmitted through ``SocketChannel`` are applied
    by the child and acked with exactly the seqs shipped; the daemon's
    ledger accounts for every message;
  * IDEMPOTENCE — redelivering an already-applied frame over the socket
    is acked again (same seqs) and leaves the daemon's state bit-identical
    (at-least-once delivery, exactly-once effect — now across a process
    boundary);
  * CONVERGENCE — a ``GeoReplicator`` with a remote replica drains both
    planes to pending==0, and ``promote`` adopts the daemon's state into
    an in-process store byte-identically online / chunk-set-identically
    offline;
  * PIPELINING — the windowed in-flight drain produces the same replica
    state as the serialized (window=1) drain on the same workload;
  * FAULTS — the ``SocketChannel`` fault-proxy mode (seeded ``FaultPlan``)
    injects corruption and drops on the REAL wire; the delivery state
    machine retries through them and still converges.

Marked ``proc``: each test pays ~1 s of child-interpreter startup, and CI
runs this module in the parallel process-test lane.
"""

import os

import numpy as np
import pytest

from repro.core import wire
from repro.core.assets import (
    Entity,
    Feature,
    FeatureSetSpec,
    MaterializationSettings,
)
from repro.core.channel import FaultPlan
from repro.core.daemon import ReplicaDaemon, SocketChannel, spawn_replica_daemon
from repro.core.dsl import UDFTransform
from repro.core.offline_store import OfflineStore
from repro.core.online_store import OnlineStore
from repro.core.regions import GeoTopology, Region
from repro.core.replication import (
    DeliveryPolicy,
    GeoReplicator,
    ReplicationLog,
)
from repro.core.table import Table

pytestmark = pytest.mark.proc

HOUR = 3_600_000


def _spec(name="geo", online=True, offline=True):
    return FeatureSetSpec(
        name=name,
        version=1,
        entity=Entity("cust", ("entity_id",)),
        features=(Feature("f0"), Feature("f1")),
        source_name="src",
        transform=UDFTransform(lambda df, ctx: df, name="id"),
        materialization=MaterializationSettings(online, offline),
    )


def _frame(rng, n, entities, t0):
    return Table(
        {
            "entity_id": rng.integers(0, entities, n).astype(np.int64),
            "ts": (t0 + rng.integers(0, HOUR, n)).astype(np.int64),
            "f0": rng.random(n).astype(np.float32),
            "f1": rng.random(n).astype(np.float32),
        }
    )


def _topo():
    return GeoTopology(regions={r: Region(r) for r in ("westus2", "eastus")})


def _replicator(policy=None, offline=True):
    home = OnlineStore()
    home_off = OfflineStore() if offline else None
    rep = GeoReplicator(
        home,
        topology=_topo(),
        home_region="westus2",
        home_offline=home_off,
        log=ReplicationLog(capacity=1024),
        policy=policy or DeliveryPolicy(),
    )
    return rep, home, home_off


def _publish(home, home_off, spec, rng, n_merges, rows=400):
    for i in range(n_merges):
        f = _frame(rng, rows, 1000, (i + 1) * HOUR)
        home.merge(spec, f, 10**8 + i)
        if home_off is not None:
            home_off.merge(spec, f, 10**8 + i)


def _adopt_online(ch, spec):
    """Rebuild the daemon's online state locally from its dump stream."""
    store = OnlineStore()
    store.register(spec)
    for b in ch.fetch_dump(spec, "online"):
        store.merge_reduced(spec, b.keys, b.event_ts, b.values, b.creation_ts)
    return store


def _assert_online_identical(a: OnlineStore, b: OnlineStore, spec):
    da = a.dump_all(spec.name, spec.version)
    db = b.dump_all(spec.name, spec.version)
    assert da.names == db.names
    for name in da.names:
        np.testing.assert_array_equal(da[name], db[name], err_msg=name)


def _assert_offline_identical(a: OfflineStore, b: OfflineStore, spec):
    ha = a.canonical_history(spec.name, spec.version)
    hb = b.canonical_history(spec.name, spec.version)
    assert len(ha) == len(hb)
    for name in ha.names:
        np.testing.assert_array_equal(ha[name], hb[name], err_msg=name)


# -- round trip ---------------------------------------------------------------


def test_round_trip_acks_and_ledger():
    rep, home, home_off = _replicator()
    spec = _spec()
    rng = np.random.default_rng(0)
    with spawn_replica_daemon(region="eastus") as h:
        ch = SocketChannel(h.connect(), src="westus2", dst="eastus")
        rep.add_remote_replica("eastus", ch, offline=True)
        _publish(home, home_off, spec, rng, 4)
        out = rep.drain("eastus")
        assert out["eastus"]["applied_batches"] == 8  # 4 online + 4 offline
        assert rep.lag_batches("eastus") == 0
        st = rep.delivery["eastus"]
        assert st.status == "healthy"
        assert st.timeouts == 0 and st.corrupt_frames == 0
        ledger = ch.ledger()
        assert ledger["batches_applied"] == 8
        assert ledger["rows_applied"] > 0
        assert ledger["nacks"] == 0
        ch.close()


def test_redelivery_over_socket_is_idempotent():
    """Re-transmit every already-acked batch over the same pipe: the
    daemon acks each again and its state stays bit-identical to home."""
    rep, home, _ = _replicator(offline=False)
    spec = _spec(offline=False)
    rng = np.random.default_rng(1)
    with spawn_replica_daemon(region="eastus", offline=False) as h:
        ch = SocketChannel(h.connect(), src="westus2", dst="eastus")
        rep.add_remote_replica("eastus", ch)
        _publish(home, None, spec, rng, 3)
        # capture the pending batches BEFORE draining (the log truncates
        # its fully-acked prefix afterwards)
        redelivered = list(rep.log.pending("eastus"))
        assert redelivered
        rep.drain("eastus")
        assert rep.lag_batches("eastus") == 0
        before = ch.ledger()
        for b in redelivered:
            delivery = ch.transmit("westus2", "eastus", wire.encode_batch(b))
            ack = delivery.remote
            assert ack is not None and ack.ok
            assert ack.seqs == (b.seq,)
        after = ch.ledger()
        assert after["frames"] == before["frames"] + len(redelivered)
        _assert_online_identical(home, _adopt_online(ch, spec), spec)
        ch.close()


# -- convergence + promote ----------------------------------------------------


def test_replicator_converges_and_promote_adopts_both_planes():
    rep, home, home_off = _replicator(policy=DeliveryPolicy(inflight_window=8))
    spec = _spec()
    rng = np.random.default_rng(2)
    with spawn_replica_daemon(region="eastus") as h:
        ch = SocketChannel(
            h.connect(), src="westus2", dst="eastus", topology=rep.topology
        )
        rep.add_remote_replica("eastus", ch, offline=True)
        _publish(home, home_off, spec, rng, 6)
        rep.drain("eastus")
        assert rep.lag_batches("eastus") == 0
        # un-drained tail: promote must force-drain it before adopting
        _publish(home, home_off, spec, rng, 2)
        home_dump = home.dump_all(spec.name, spec.version)
        rep.promote("eastus")
        assert rep.home_region == "eastus"
        assert "eastus" not in rep.remote  # adopted into the store map
        db = rep.stores["eastus"].dump_all(spec.name, spec.version)
        for name in home_dump.names:
            np.testing.assert_array_equal(home_dump[name], db[name], err_msg=name)
        _assert_offline_identical(home_off, rep.offline_stores["eastus"], spec)
        # the link actually measured: the RTT gauge saw real acks
        assert rep.topology.measured_latency("westus2", "eastus") is not None
        ch.close()


def test_pipelined_drain_matches_serialized():
    """Same two-table workload into two daemons — one drained window=1,
    one window=8 (alternating tables keep the coalesced runs short, so
    the window genuinely holds multiple frames in flight) — must land
    byte-identical online state."""
    stores = []
    spec_a = _spec("geo_a", offline=False)
    spec_b = _spec("geo_b", offline=False)
    for window in (1, 8):
        rep, home, _ = _replicator(
            policy=DeliveryPolicy(inflight_window=window), offline=False
        )
        rng = np.random.default_rng(3)
        with spawn_replica_daemon(region="eastus", offline=False) as h:
            ch = SocketChannel(h.connect(), src="westus2", dst="eastus")
            rep.add_remote_replica("eastus", ch)
            for i in range(6):
                home.merge(spec_a, _frame(rng, 200, 500, (i + 1) * HOUR), 10**8 + i)
                home.merge(spec_b, _frame(rng, 200, 500, (i + 1) * HOUR), 10**8 + i)
            rep.drain("eastus")
            assert rep.lag_batches("eastus") == 0
            stores.append(
                (_adopt_online(ch, spec_a), _adopt_online(ch, spec_b))
            )
            ch.close()
    _assert_online_identical(stores[0][0], stores[1][0], spec_a)
    _assert_online_identical(stores[0][1], stores[1][1], spec_b)


# -- faults on the real wire --------------------------------------------------


def test_fault_proxy_corrupt_and_drop_still_converges():
    """Seeded drops + corruption on the actual socket: the daemon NACKs
    corrupt frames (intact envelope, damaged payload), drops surface as
    publisher timeouts, and repeated draining converges anyway."""
    policy = DeliveryPolicy(
        suspect_after=2,
        dead_after=6,
        backoff_base=1,
        backoff_cap=2,
        probe_interval=1,
        inflight_window=1,  # serialized so per-transmit faults are exact
    )
    rep, home, _ = _replicator(policy=policy, offline=False)
    spec_a = _spec("geo_a", offline=False)
    spec_b = _spec("geo_b", offline=False)
    rng = np.random.default_rng(4)
    plan = FaultPlan(seed=99, drop_rate=0.25, corrupt_rate=0.25)
    with spawn_replica_daemon(region="eastus", offline=False) as h:
        ch = SocketChannel(
            h.connect(), src="westus2", dst="eastus", fault_plan=plan
        )
        rep.add_remote_replica("eastus", ch)
        # alternating tables keep the coalesced runs short: many transmit
        # events, so the per-event fault draws actually strike
        for i in range(6):
            home.merge(spec_a, _frame(rng, 300, 1000, (i + 1) * HOUR), 10**8 + i)
            home.merge(spec_b, _frame(rng, 300, 1000, (i + 1) * HOUR), 10**8 + i)
        for _ in range(40):
            if rep.lag_batches("eastus") == 0:
                break
            rep.drain("eastus")
        assert rep.lag_batches("eastus") == 0
        assert ch.counts["dropped"] + ch.counts["corrupted"] > 0
        st = rep.delivery["eastus"]
        assert st.timeouts > 0  # the faults were really felt
        ledger = ch.ledger()
        assert ledger["nacks"] == ch.counts["corrupted"]
        _assert_online_identical(home, _adopt_online(ch, spec_a), spec_a)
        _assert_online_identical(home, _adopt_online(ch, spec_b), spec_b)
        ch.close()


def test_daemon_teardown_leaves_no_orphan():
    """DaemonHandle.close terminates the child; nothing survives it."""
    h = spawn_replica_daemon(region="eastus")
    pid = h.proc.pid
    h.close()
    assert h.proc.poll() is not None
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)


def test_daemon_refuses_the_device_engine():
    """A replica daemon is a host process: the kernel engine, which would
    take an accelerator the parent may hold, is refused before any child
    starts."""
    with pytest.raises(ValueError, match="host process"):
        spawn_replica_daemon(region="eastus", merge_engine="kernel")
    with pytest.raises(ValueError, match="host process"):
        ReplicaDaemon(region="eastus", merge_engine="kernel")
