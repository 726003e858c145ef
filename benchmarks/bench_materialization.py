"""Benchmark: §4.3/§4.5 materialization pipeline + fault tolerance.

  * merge-engine throughput: rows/s through offline+online Algorithm-2
    merges at a 100k-row window — old-style sequential loop vs the
    vectorized merge engine (the tentpole comparison)
  * scheduled-incremental throughput: source rows/s through Algorithm 1
    (read window -> transform -> filter) + Algorithm 2 merges
  * backfill: wall time for an on-demand window, and the §3.1.1 invariant
    (suspended schedules resume; no overlapping jobs) under load
  * fault injection: convergence under failure probability p — retries to
    eventual consistency (§4.5.4), reporting retry overhead factor
  * Fig.5 record-semantics check at benchmark scale (offline keeps all,
    online keeps latest)
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import DslTransform, RollingAgg
from repro.core.featurestore import FeatureStore
from repro.core.offline_store import OfflineStore
from repro.core.online_store import OnlineStore
from repro.core.table import Table
from repro.data.sources import SyntheticEventSource

HOUR = 3_600_000


def _make(entities=2_000, rate=800, fail_p=0.0, seed=0) -> FeatureStore:
    fs = FeatureStore("bench-mat")
    src = SyntheticEventSource(
        "tx", seed=seed, num_entities=entities, events_per_bucket=rate
    )
    fs.register_source(src)
    fs.create_feature_set(
        FeatureSetSpec(
            name="act", version=1,
            entity=Entity("customer", ("entity_id",)),
            features=(Feature("s2", "float32"), Feature("c2", "float32")),
            source_name="tx",
            transform=DslTransform("entity_id", "ts", [
                RollingAgg("s2", "amount", 2 * HOUR, "sum"),
                RollingAgg("c2", "amount", 2 * HOUR, "count"),
            ]),
            timestamp_col="ts", source_lookback=2 * HOUR,
            materialization=MaterializationSettings(
                offline_enabled=True, online_enabled=True, schedule_interval=HOUR
            ),
        )
    )
    if fail_p:
        fs.faults.set_failure_rate(fail_p, seed=seed)
    return fs


def _merge_spec() -> FeatureSetSpec:
    from repro.core.dsl import UDFTransform

    return FeatureSetSpec(
        name="merge-bench", version=1,
        entity=Entity("customer", ("entity_id",)),
        features=(Feature("f0", "float32"), Feature("f1", "float32")),
        source_name="direct",
        transform=UDFTransform(lambda df, ctx: df, name="id"),
        timestamp_col="ts",
        materialization=MaterializationSettings(True, True),
    )


def _merge_frame(rng, n: int, t0: int) -> Table:
    return Table(
        {
            "entity_id": rng.integers(0, 20_000, n).astype(np.int64),
            "ts": (t0 + rng.integers(0, 10**6, n)).astype(np.int64),
            "f0": rng.random(n).astype(np.float32),
            "f1": rng.random(n).astype(np.float32),
        }
    )


class _SeedStores:
    """Faithful replica of the SEED (pre-merge-engine) write path, pinned
    here so the benchmark baseline never drifts as the real stores improve:
    offline = per-row ``set[tuple]`` dedup + ``concat_tables`` on EVERY
    merge (O(history)); online = per-row dict-probe Algorithm-2 loop.
    Storage detail (monolithic table / slot planes) matches the seed."""

    def __init__(self, spec, num_shards=4, num_partitions=16, capacity=256):
        from repro.core.keys import encode_keys
        from repro.core.offline_store import _record_schema
        from repro.core.table import concat_tables
        from repro.kernels.online_lookup.ops import partition_of, split_i64

        self._encode = encode_keys
        self._partition_of = partition_of
        self._split = split_i64
        self._concat = concat_tables
        self.spec = spec
        self.num_shards = num_shards
        self.num_partitions = num_partitions
        self.off_tables = [Table.empty(_record_schema(spec)) for _ in range(num_shards)]
        self.off_keys = [set() for _ in range(num_shards)]
        p, d = num_partitions, len(spec.features)
        self.keys_full = np.full((p, capacity), -1, np.int64)
        self.event_ts = np.zeros((p, capacity), np.int64)
        self.creation_ts = np.zeros((p, capacity), np.int64)
        self.values = np.zeros((p, capacity, d), np.float32)
        self.fill = np.zeros(p, np.int64)
        self.slot_of: dict = {}

    def merge(self, frame: Table, creation_ts: int) -> None:
        spec = self.spec
        ids = self._encode([frame[c] for c in spec.index_columns])
        event_ts = frame[spec.timestamp_col].astype(np.int64)
        # -- offline branch (seed: set[tuple] + concat per merge)
        shard_of = self._partition_of(ids, self.num_shards)
        for s in range(self.num_shards):
            mask = shard_of == s
            if not mask.any():
                continue
            sub_ids, sub_ev = ids[mask], event_ts[mask]
            keep = np.zeros(mask.sum(), bool)
            for i, (k, ev) in enumerate(zip(sub_ids, sub_ev)):
                full = (int(k), int(ev), creation_ts)
                if full not in self.off_keys[s]:
                    self.off_keys[s].add(full)
                    keep[i] = True
            if not keep.any():
                continue
            sub = frame.filter(mask).filter(keep)
            cols = {"__key__": sub_ids[keep]}
            for c in spec.index_columns:
                cols[c] = sub[c].astype(np.int64)
            cols["event_ts"] = sub[spec.timestamp_col].astype(np.int64)
            cols["creation_ts"] = np.full(len(sub), creation_ts, np.int64)
            for f in spec.features:
                cols[f.name] = sub[f.name].astype(f.np_dtype())
            self.off_tables[s] = self._concat([self.off_tables[s], Table(cols)])
        # -- online branch (seed: per-row dict probe)
        feats = np.stack(
            [frame[f.name].astype(np.float32) for f in spec.features], axis=1
        )
        parts = self._partition_of(ids, self.num_partitions)
        for i in range(len(ids)):
            key_i, ev_i, p = int(ids[i]), int(event_ts[i]), int(parts[i])
            existing = self.slot_of.get(key_i)
            if existing is None:
                if self.fill[p] >= self.keys_full.shape[1]:
                    grow = lambda a, v: np.concatenate(
                        [a, np.full_like(a, v)], axis=1
                    )
                    self.keys_full = grow(self.keys_full, -1)
                    self.event_ts = grow(self.event_ts, 0)
                    self.creation_ts = grow(self.creation_ts, 0)
                    self.values = np.concatenate(
                        [self.values, np.zeros_like(self.values)], axis=1
                    )
                slot = int(self.fill[p])
                self.keys_full[p, slot] = key_i
                self.event_ts[p, slot] = ev_i
                self.creation_ts[p, slot] = creation_ts
                self.values[p, slot] = feats[i]
                self.slot_of[key_i] = (p, slot)
                self.fill[p] += 1
            else:
                pp, slot = existing
                old = (int(self.event_ts[pp, slot]), int(self.creation_ts[pp, slot]))
                if (ev_i, creation_ts) > old:
                    self.event_ts[pp, slot] = ev_i
                    self.creation_ts[pp, slot] = creation_ts
                    self.values[pp, slot] = feats[i]


def bench_merge_engines(
    window_rows: int = 100_000, batches: int = 1, trials: int = 5
) -> dict:
    """Rows/s through offline+online Algorithm-2 merges of a
    ``window_rows``-row window (after a same-size seeded history), per write
    path.  ``batches=1`` mirrors the Materializer: one job window produces
    ONE frame and each store gets one merge call.  ``seed`` is a faithful
    replica of the pre-engine implementation (the acceptance baseline,
    pinned so it can't drift); ``loop`` is the retained per-row reference
    inside the NEW storage layout; ``vector`` is the merge engine.  Median
    of ``trials`` each — medians beat best-of here because a lucky quiet
    trial flatters the noise-sensitive python-loop baselines far more than
    the vectorized path, skewing the ratio."""
    spec = _merge_spec()
    out: dict = {"window_rows": window_rows, "batches": batches}
    per_batch = window_rows // batches

    def _drive(make, merge):
        walls = []
        for _ in range(trials):
            rng = np.random.default_rng(1)
            state = make()
            merge(state, _merge_frame(rng, window_rows, 0), 10**7)
            frames = [
                _merge_frame(rng, per_batch, 10**6 * (i + 2))
                for i in range(batches)
            ]
            t0 = time.perf_counter()
            for i, f in enumerate(frames):
                merge(state, f, 10**8 + i)
            walls.append(time.perf_counter() - t0)
        med = float(np.median(walls))
        return {"rows_per_s": int(window_rows / med), "wall_s": round(med, 4)}

    out["seed"] = _drive(
        lambda: _SeedStores(spec), lambda st, f, cr: st.merge(f, cr)
    )
    for engine in ("loop", "vector", "kernel"):
        out[engine] = _drive(
            lambda: (
                OfflineStore(num_shards=4, merge_engine=engine),
                OnlineStore(merge_engine=engine),
            ),
            lambda st, f, cr: (st[0].merge(spec, f, cr), st[1].merge(spec, f, cr)),
        )
    out["speedup_vs_seed_x"] = round(
        out["vector"]["rows_per_s"] / max(out["seed"]["rows_per_s"], 1), 1
    )
    out["speedup_vs_loop_x"] = round(
        out["vector"]["rows_per_s"] / max(out["loop"]["rows_per_s"], 1), 1
    )
    return out


def run(hours=16, fail_ps=(0.0, 0.15, 0.3), merge_window=100_000) -> dict:
    # -- merge-engine comparison (tentpole: old-style loop vs engine) ----------
    merge_engines = bench_merge_engines(window_rows=merge_window)

    # -- throughput ------------------------------------------------------------
    fs = _make()
    t0 = time.perf_counter()
    stats = fs.tick(now=hours * HOUR)
    wall = time.perf_counter() - t0
    n_rows = len(fs.offline.read("act", 1))
    throughput = {
        "hours_materialized": hours,
        "jobs": stats,
        "feature_rows": n_rows,
        "rows_per_s": int(n_rows / max(wall, 1e-9)),
        "wall_s": round(wall, 3),
    }

    # -- backfill + scheduling invariant ------------------------------------------
    fs2 = _make(seed=1)
    fs2.tick(now=6 * HOUR)
    t0 = time.perf_counter()
    bstats = fs2.backfill("act", 1, start=0, end=3 * HOUR)
    t_backfill = time.perf_counter() - t0
    intervals = fs2.scheduler.materialized_intervals("act", 1)
    backfill = {
        "jobs": bstats,
        "wall_s": round(t_backfill, 3),
        "timeline_contiguous": intervals == [(0, 6 * HOUR)],
        "alerts": list(fs2.scheduler.alerts),
    }

    # -- fault-injected convergence (§4.5.4) ----------------------------------------
    fault_rows = []
    for p in fail_ps:
        fsf = _make(seed=2, fail_p=p)
        t0 = time.perf_counter()
        st = fsf.tick(now=8 * HOUR)
        repairs = 0
        while fsf.scheduler.materialized_intervals("act", 1) != [(0, 8 * HOUR)]:
            r = fsf.repair("act", 1)
            st = {k: st[k] + r[k] for k in st}
            repairs += 1
            if repairs > 20:
                break
        wall_f = time.perf_counter() - t0
        rep = fsf.check_consistency("act", 1)
        iv = fsf.scheduler.materialized_intervals("act", 1)
        fault_rows.append(
            {
                "failure_p": p,
                "jobs": st,
                "eventually_consistent": bool(rep.consistent),
                "timeline_complete": iv == [(0, 8 * HOUR)],
                "repair_rounds": repairs,
                "alerts": len(fsf.scheduler.alerts),
                "retry_overhead_x": round(
                    (st["succeeded"] + st["retried"]) / max(st["succeeded"], 1), 2
                ),
                "wall_s": round(wall_f, 3),
            }
        )

    # -- Fig.5 semantics at scale -----------------------------------------------------
    hist = fs.offline.read("act", 1)
    per_id_offline = len(hist)
    uniq = len(np.unique(hist["__key__"]))
    fig5 = {
        "offline_records": per_id_offline,
        "distinct_ids": uniq,
        "offline_keeps_history": per_id_offline > uniq,  # many records per id
        "online_keeps_latest_only": bool(fs.check_consistency("act", 1).consistent),
    }

    return {
        "merge_engines": merge_engines,
        "throughput": throughput,
        "backfill": backfill,
        "fault_tolerance": fault_rows,
        "fig5_semantics": fig5,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run(), indent=1))
