"""Benchmark: §2.1/§3.1.4 online retrieval latency + §4.5 merge throughput.

  * GET: batched lookups/s and per-request latency percentiles for BOTH
    serving paths — host mirror (numpy compare-match) and the device-resident
    kernel path (Pallas scan over resident key planes + on-device row
    gather), steady-state post-warmup
  * MERGE (Algorithm 2): records/s merged into the online store, including
    the stale-update no-op path (idempotence under retries)
  * MERGE ENGINES: the per-row loop reference vs the vectorized engine vs
    the device-resident kernel path, same workload, rows/s each
  * RESIDENT CYCLE: host<->device bytes a steady merge+lookup cycle moves —
    GUARDED: raises if the serving path regresses to table-sized (O(P·C·D))
    traffic, so the tier-1 bench smoke fails instead of silently eroding
  * staleness metric: the §2.1 freshness SLA readout under a materialization
    cadence
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import DslTransform, RollingAgg, UDFTransform
from repro.core.featurestore import FeatureStore
from repro.core.online_store import OnlineStore, o_batch_byte_budget
from repro.core.table import Table
from repro.data.sources import SyntheticEventSource

HOUR = 3_600_000


def bench_merge_engines(rows: int = 50_000, batches: int = 5) -> dict:
    """Online-store Algorithm-2 merge rows/s per write engine (same data,
    byte-identical end states — parity is covered by tests/core)."""
    spec = FeatureSetSpec(
        name="m", version=1, entity=Entity("customer", ("entity_id",)),
        features=(Feature("f0", "float32"),), source_name="direct",
        transform=UDFTransform(lambda df, ctx: df, name="id"),
        timestamp_col="ts",
        materialization=MaterializationSettings(True, True),
    )
    per_batch = rows // batches
    out = {}
    for engine in ("loop", "vector", "kernel"):
        rng = np.random.default_rng(3)
        store = OnlineStore(merge_engine=engine)
        frames = [
            Table(
                {
                    "entity_id": rng.integers(0, 10_000, per_batch).astype(np.int64),
                    "ts": rng.integers(0, 10**6 * (i + 1), per_batch).astype(np.int64),
                    "f0": rng.random(per_batch).astype(np.float32),
                }
            )
            for i in range(batches)
        ]
        # steady-state warmup: insert EVERY id once so capacity growth, jit
        # traces, and the device upload all land off the clock — the timed
        # merges then exercise the resident override/no-op hot path
        warm = Table(
            {
                "entity_id": np.arange(10_000, dtype=np.int64),
                "ts": np.zeros(10_000, np.int64),
                "f0": np.zeros(10_000, np.float32),
            }
        )
        store.merge(spec, warm, 10**6)
        store.merge(spec, frames[0], 10**7)  # warm the per-batch jit shapes
        base = (store.inserts, store.overrides, store.noops)
        t0 = time.perf_counter()
        for i, f in enumerate(frames):
            store.merge(spec, f, 10**8 + i)
        wall = time.perf_counter() - t0
        out[engine] = {
            "rows_per_s": int(rows / wall),
            "wall_s": round(wall, 4),
            # timed-workload deltas only — warmup merges stay off the books
            "counters": {
                "inserts": store.inserts - base[0],
                "overrides": store.overrides - base[1],
                "noops": store.noops - base[2],
            },
        }
    return out


def _store(entities: int, hours: int = 8) -> FeatureStore:
    fs = FeatureStore("bench-online")
    src = SyntheticEventSource("tx", num_entities=entities, events_per_bucket=600)
    fs.register_source(src)
    fs.create_feature_set(
        FeatureSetSpec(
            name="act", version=1,
            entity=Entity("customer", ("entity_id",)),
            features=(Feature("s2", "float32"),),
            source_name="tx",
            transform=DslTransform("entity_id", "ts",
                                   [RollingAgg("s2", "amount", 2 * HOUR, "sum")]),
            timestamp_col="ts", source_lookback=2 * HOUR,
            materialization=MaterializationSettings(
                offline_enabled=True, online_enabled=True, schedule_interval=HOUR
            ),
        )
    )
    fs.tick(now=hours * HOUR)
    return fs


def _bench_get_path(fs, n_ent, batch, rounds, *, use_kernel) -> dict:
    """Steady-state GET: one warmup round (jit + device upload off the
    clock), then ``rounds`` timed batches."""
    rng = np.random.default_rng(1)
    fs.get_online_features(
        "act", 1, [rng.integers(0, n_ent, batch).astype(np.int64)],
        use_kernel=use_kernel,
    )
    lat = []
    hits = 0
    for _ in range(rounds):
        ids = rng.integers(0, n_ent, batch).astype(np.int64)
        t0 = time.perf_counter()
        _, found = fs.get_online_features("act", 1, [ids], use_kernel=use_kernel)
        lat.append((time.perf_counter() - t0) * 1e3)
        hits += int(found.sum())
    lat = np.array(lat)
    return {
        "lookups_per_s": int(batch / (lat.mean() / 1e3)),
        "batch_ms_p50": round(float(np.percentile(lat, 50)), 3),
        "batch_ms_p99": round(float(np.percentile(lat, 99)), 3),
        "hit_rate": round(hits / (batch * rounds), 3),
    }


def _resident_cycle(entities=20_000, batch=2_048, cycles=10) -> dict:
    """Steady-state merge+lookup cycle traffic on the device-resident path.

    Raises RuntimeError when the cycle re-uploads the table, pulls the host
    mirror, or moves more than an O(batch) byte budget — the transfer
    regression guard wired into tier-1 via ``benchmarks/run.py --fast``."""
    spec = FeatureSetSpec(
        name="m", version=1, entity=Entity("customer", ("entity_id",)),
        features=(Feature("f0", "float32"),), source_name="direct",
        transform=UDFTransform(lambda df, ctx: df, name="id"),
        timestamp_col="ts",
        materialization=MaterializationSettings(True, True),
    )
    rng = np.random.default_rng(5)
    store = OnlineStore(merge_engine="kernel")

    def frame(n, t0):
        return Table(
            {
                "entity_id": rng.integers(0, entities, n).astype(np.int64),
                "ts": (t0 + rng.integers(0, 10**6, n)).astype(np.int64),
                "f0": rng.random(n).astype(np.float32),
            }
        )

    store.merge(spec, frame(entities * 2, 0), 10**7)  # build + grow
    ids = [rng.integers(0, entities, 256).astype(np.int64)]
    store.merge(spec, frame(batch, 10**6), 10**7 + 1)  # warm merge shapes
    store.lookup("m", 1, ids)  # warm lookup shapes
    store.reset_transfer_stats()
    t0 = time.perf_counter()
    for i in range(cycles):
        store.merge(spec, frame(batch, 10**6 * (i + 2)), 10**8 + i)
        store.lookup("m", 1, ids)
    wall = time.perf_counter() - t0
    tx = store.transfer_stats()
    table_bytes = store.device_state("m", 1).nbytes()
    per_cycle = (tx["h2d_bytes"] + tx["d2h_bytes"]) / cycles
    budget = o_batch_byte_budget(batch, record_bytes=8 * 4 + 4)
    if tx["device_uploads"] or tx["host_syncs"]:
        raise RuntimeError(
            f"resident cycle re-moved the table: {tx} (transfer regression)"
        )
    if per_cycle > budget or per_cycle > table_bytes / 4:
        raise RuntimeError(
            f"resident cycle moves {per_cycle:.0f} B (budget {budget}, "
            f"table {table_bytes}) — serving path transfer regression"
        )
    return {
        "batch": batch,
        "cycles": cycles,
        "per_cycle_bytes": int(per_cycle),
        "table_bytes": int(table_bytes),
        "table_to_cycle_ratio_x": round(table_bytes / max(per_cycle, 1), 1),
        "cycle_ms": round(wall / cycles * 1e3, 3),
        "transfers": tx,
    }


def run(entity_counts=(1_000, 10_000), batch=256, rounds=20) -> dict:
    rows = []
    for n_ent in entity_counts:
        fs = _store(n_ent)
        row = {"entities": n_ent, "batch": batch}
        for path, use_kernel in (("host", False), ("kernel", True)):
            row[path] = _bench_get_path(
                fs, n_ent, batch, rounds, use_kernel=use_kernel
            )
        # steady-state GET traffic guard: resident kernel lookups must not
        # re-upload the table or sync the mirror
        fs.online.reset_transfer_stats()
        _bench_get_path(fs, n_ent, batch, 5, use_kernel=True)
        tx = fs.online.transfer_stats()
        if tx["device_uploads"] or tx["host_syncs"]:
            raise RuntimeError(f"kernel GET path re-moved the table: {tx}")
        row["kernel_get_bytes_per_batch"] = int(
            (tx["h2d_bytes"] + tx["d2h_bytes"]) / 6  # 5 rounds + warmup
        )
        rows.append(row)

    # -- merge throughput + idempotence (Algorithm 2) ---------------------------
    fs = _store(5_000, hours=4)
    online = fs.online
    spec = fs.registry.get_feature_set("act", 1)
    t0 = time.perf_counter()
    stats = fs.tick(now=8 * HOUR)  # four more hours of merges
    merge_s = time.perf_counter() - t0
    n_rows = len(fs.offline.read("act", 1))

    # staleness SLA metric
    snap = fs.monitor.system.snapshot()
    stale = snap["gauges"].get("staleness_ms/act:v1", None)

    return {
        "lookup_table": rows,
        "merge": {
            "rows_in_store": n_rows,
            "tick_wall_s": round(merge_s, 3),
            "jobs": stats,
        },
        "merge_engines": bench_merge_engines(),
        "resident_cycle": _resident_cycle(),
        "staleness_ms": stale,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run(), indent=1))
