"""Benchmark: §4.4 point-in-time retrieval (offline training-frame builds).

Measures get_offline_features throughput (spine rows/s) as table/spine sizes
grow, on the XLA as-of path vs the naive per-row python join a hand-rolled
implementation would do (the paper's "complex and error prone" remark —
also slow).  The Pallas counting-search kernel is validated in tests; on CPU
it runs interpret-mode so its wall time is not meaningful — throughput here
is the XLA path that a kernel-less mesh would run.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import DslTransform, RollingAgg
from repro.core.featurestore import FeatureStore
from repro.core.offline_store import EVENT_TS
from repro.core.table import Table
from repro.data.sources import SyntheticEventSource

HOUR = 3_600_000


def _store(hours: int, entities: int) -> FeatureStore:
    fs = FeatureStore("bench")
    src = SyntheticEventSource(
        "tx", num_entities=entities, events_per_bucket=400
    )
    fs.register_source(src)
    fs.create_feature_set(
        FeatureSetSpec(
            name="act", version=1,
            entity=Entity("customer", ("entity_id",)),
            features=(Feature("s2", "float32"), Feature("m6", "float32")),
            source_name="tx",
            transform=DslTransform("entity_id", "ts", [
                RollingAgg("s2", "amount", 2 * HOUR, "sum"),
                RollingAgg("m6", "amount", 6 * HOUR, "mean"),
            ]),
            timestamp_col="ts", source_lookback=6 * HOUR,
            materialization=MaterializationSettings(
                offline_enabled=True, online_enabled=True,
                schedule_interval=HOUR,
            ),
        )
    )
    fs.tick(now=hours * HOUR)
    return fs


def _naive_pit(history: Table, spine: Table, feat_cols) -> np.ndarray:
    """Per-spine-row python binary-search join (the hand-rolled baseline)."""
    out = np.zeros((len(spine), len(feat_cols)), np.float32)
    ent = history["entity_id"]
    ts = history[EVENT_TS]
    for i in range(len(spine)):
        m = (ent == spine["entity_id"][i]) & (ts <= spine["ts"][i])
        idx = np.nonzero(m)[0]
        if len(idx):
            r = idx[np.argmax(ts[idx])]
            for j, c in enumerate(feat_cols):
                out[i, j] = history[c][r]
    return out


def run(spine_sizes=(1_000, 10_000), hours=24, entities=500) -> dict:
    fs = _store(hours, entities)
    hist = fs.offline.read("act", 1)
    rows = []
    rng = np.random.default_rng(0)
    for n in spine_sizes:
        spine = Table(
            {
                "entity_id": rng.integers(0, entities, n).astype(np.int64),
                "ts": rng.integers(2 * HOUR, hours * HOUR, n).astype(np.int64),
            }
        )
        t0 = time.perf_counter()
        frame = fs.get_offline_features(spine, [("act", 1)], use_kernel=False)
        t_sys = time.perf_counter() - t0
        t0 = time.perf_counter()
        frame = fs.get_offline_features(spine, [("act", 1)], use_kernel=False)
        t_sys_warm = time.perf_counter() - t0

        t_naive = None
        if n <= 1_000:  # naive is O(spine*history); cap it
            t0 = time.perf_counter()
            naive = _naive_pit(hist, spine, ["s2", "m6"])
            t_naive = time.perf_counter() - t0
            got = np.stack([frame["act:v1:s2"], frame["act:v1:m6"]], axis=1)
            found = frame["act:v1:__found__"].astype(bool)
            np.testing.assert_allclose(got[found], naive[found], rtol=1e-4, atol=1e-3)

        rows.append(
            {
                "history_rows": len(hist),
                "spine_rows": n,
                "pit_s": round(t_sys, 4),
                "pit_warm_s": round(t_sys_warm, 4),
                "spine_rows_per_s_warm": int(n / max(t_sys_warm, 1e-9)),
                "naive_python_s": round(t_naive, 4) if t_naive else None,
            }
        )
    return {"table": rows}


if __name__ == "__main__":
    import json

    print(json.dumps(run(), indent=1))
