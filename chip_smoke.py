#!/usr/bin/env python3
"""Drive the feature store's device path once on a TPU and check every answer.

    python chip_smoke.py [--seed N]

One process, one chip.  A ``FeatureStore(merge_engine="kernel")`` at a size
its users would call real runs its main path through the entry points a user
calls, and each phase is checked against a plain reference:

  materialize  SyntheticEventSource, 2^20 entities, 2^20 events per hourly
               bucket over 6 buckets; one DslTransform with 8 features (sum,
               mean, count, max over 2 h and 6 h windows); ``fs.tick`` at 2 h,
               4 h and 6 h — the Pallas rolling kernel, the offline merge and
               the device-resident online merge.  A twin
               ``FeatureStore(merge_engine="vector")`` takes the same ticks.
  rolling      the 6-hour source window through the DSL with the kernel and
               with ``backend="xla"``: sums and means must be allclose.
  serve        8 GETs of 4096 ids (about 10% unknown) through
               ``fs.get_online_features`` — serving front, key-index search
               and row gather — byte-identical to the twin's host GET and to
               the store's own ``lookup_encoded(use_kernel=False)``.
  retrieve     ``fs.get_offline_features`` on a 65,536-row spine through the
               Pallas PIT kernel: the kernel's indices must equal
               ``pit_search_ref`` and the returned rows must be the rows at
               those indices.
  consistency  ``fs.check_consistency`` clean; the store's monitor counts
               zero kernel-to-XLA or kernel-to-reference fallbacks.

Each phase prints one JSON line (rows, seconds, compile seconds).  The last
line is ``{"ok": true, "device": {...}}`` and appears only when every phase
passed.  Without a TPU, or without the repository's ``src/`` beside it, the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HOUR = 3_600_000
ENTITIES = 1 << 20
EVENTS_PER_BUCKET = 1 << 20
TICKS = (2 * HOUR, 4 * HOUR, 6 * HOUR)
GET_BATCHES = 8
GET_BATCH = 4096
UNKNOWN_SHARE = 0.1
SPINE_ROWS = 65_536
FEATURE_SET = ("txn_activity", 1)


class SmokeFailure(Exception):
    """A phase's answer disagreed with its reference."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _CompileClock:
    """Sums the seconds XLA spends compiling (tracing nests, so it is left
    out rather than counted twice)."""

    def __init__(self, monitoring) -> None:
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _feature_set(DslTransform, RollingAgg, *, use_kernel: bool = True):
    aggs = []
    for window, col in ((2 * HOUR, "amount"), (6 * HOUR, "quantity")):
        tag = f"{col}_{window // HOUR}h"
        for agg in ("sum", "mean", "max"):
            aggs.append(RollingAgg(f"{agg}_{tag}", col, window, agg))
        aggs.append(RollingAgg(f"count_{window // HOUR}h", col, window, "count"))
    return DslTransform("entity_id", "ts", aggs, use_kernel=use_kernel)


def run(
    *,
    seed: int = 0,
    entities: int = ENTITIES,
    events_per_bucket: int = EVENTS_PER_BUCKET,
    get_batch: int = GET_BATCH,
    spine_rows: int = SPINE_ROWS,
    emit=print,
) -> None:
    """Run every phase; raise ``SmokeFailure`` at the first wrong answer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.assets import (
        Entity,
        Feature,
        FeatureSetSpec,
        MaterializationSettings,
    )
    from repro.core.dsl import DslTransform, RollingAgg
    from repro.core.featurestore import FeatureStore
    from repro.core.keys import encode_keys
    from repro.core.offline_store import CREATION_TS, EVENT_TS
    from repro.core.table import Table
    from repro.data.sources import SyntheticEventSource
    from repro.kernels.pit_join import ops as pit_ops
    from repro.kernels.pit_join.ref import pit_search_ref

    clock = _CompileClock(jax.monitoring)
    name, version = FEATURE_SET
    source = SyntheticEventSource(
        "transactions",
        seed=seed,
        num_entities=entities,
        events_per_bucket=events_per_bucket,
    )
    transform = _feature_set(DslTransform, RollingAgg)

    def phase(label: str, fn):
        c0, t0 = clock.seconds, time.perf_counter()
        try:
            info = fn()
        except Exception as exc:
            emit(json.dumps({"phase": label, "ok": False, "error": repr(exc)}))
            raise
        emit(
            json.dumps(
                {
                    "phase": label,
                    "ok": True,
                    **info,
                    "seconds": time.perf_counter() - t0,
                    "compile_seconds": clock.seconds - c0,
                }
            )
        )

    def new_store(engine: str) -> FeatureStore:
        fs = FeatureStore(f"chip-smoke-{engine}", merge_engine=engine)
        fs.register_source(source)
        fs.create_feature_set(
            FeatureSetSpec(
                name=name,
                version=version,
                entity=Entity("customer", ("entity_id",)),
                features=tuple(Feature(a.output) for a in transform.aggs),
                source_name=source.name,
                transform=transform,
                timestamp_col="ts",
                source_lookback=transform.max_lookback,
                materialization=MaterializationSettings(
                    offline_enabled=True,
                    online_enabled=True,
                    schedule_interval=2 * HOUR,
                ),
            )
        )
        return fs

    fs = new_store("kernel")
    twin = new_store("vector")

    # -- materialize ------------------------------------------------------------
    def materialize():
        for now in TICKS:
            for store in (fs, twin):
                out = store.tick(now=now)
                _require(
                    out["failed"] == 0 and out["retried"] == 0,
                    f"tick {now} on {store.name}: {out}",
                )
        records = fs.online.num_records(name, version)
        _require(
            records == twin.online.num_records(name, version),
            "kernel and vector stores hold different entity counts",
        )
        plane_bytes = fs.online.device_state(name, version).nbytes()
        return {
            "rows": int(sum(o.rows for o in fs.materializer.outcomes)),
            "jobs": len(fs.materializer.outcomes),
            "online_records": int(records),
            "device_plane_bytes": int(plane_bytes),
        }

    phase("materialize", materialize)

    # -- rolling: the kernel's sums and means against the XLA formulation -------
    def rolling():
        window = source.read(0, TICKS[-1])
        got = transform(window, {"monitor": fs.monitor})
        want = _feature_set(DslTransform, RollingAgg, use_kernel=False)(window, {})
        checked = [a.output for a in transform.aggs if a.agg in ("sum", "mean")]
        for col in checked:
            np.testing.assert_allclose(
                got[col], want[col], rtol=1e-5, atol=1e-3, err_msg=col
            )
        return {"rows": len(window), "checked": checked}

    phase("rolling", rolling)

    # -- serve ----------------------------------------------------------------------
    def serve():
        rng = np.random.default_rng(seed + 1)
        hits = 0
        for _ in range(GET_BATCHES):
            ids = rng.integers(0, entities, get_batch)
            unknown = rng.random(get_batch) < UNKNOWN_SHARE
            ids[unknown] += entities  # never materialized
            vals, found = fs.get_online_features(name, version, [ids])
            ref_vals, ref_found = twin.get_online_features(
                name, version, [ids], use_kernel=False
            )
            _require(
                np.array_equal(found, ref_found)
                and vals.tobytes() == ref_vals.tobytes(),
                "kernel GET differs from the vector twin's host GET",
            )
            host_vals, host_found, _ = fs.online.lookup_encoded(
                name, version, encode_keys([ids]), use_kernel=False
            )
            _require(
                np.array_equal(found, host_found)
                and vals.tobytes() == host_vals.tobytes(),
                "kernel GET differs from lookup_encoded(use_kernel=False)",
            )
            _require(not found[unknown].any(), "an unknown id was found")
            hits += int(found.sum())
        total = GET_BATCHES * get_batch
        return {"rows": total, "batches": GET_BATCHES, "found": hits}

    phase("serve", serve)

    # -- retrieve: point-in-time training set ----------------------------------------
    def retrieve():
        rng = np.random.default_rng(seed + 2)
        spine_ids = rng.integers(0, entities, spine_rows)
        spine_ts = rng.integers(0, TICKS[-1] + HOUR, spine_rows)
        spine = Table({"entity_id": spine_ids, "ts": spine_ts})
        out = fs.get_offline_features(spine, [FEATURE_SET])

        # reference: the same search, by pit_search_ref, over the same history
        history = fs.offline.read(name, version)
        order = np.lexsort(
            (history[CREATION_TS], history[EVENT_TS], history["__key__"])
        )
        keys = history["__key__"][order]
        table_ts = history[EVENT_TS][order].astype(np.int64)
        uniq, first = np.unique(keys, return_index=True)
        offsets = np.concatenate([first, [len(keys)]])
        ids = encode_keys([spine_ids])
        seg = np.clip(np.searchsorted(uniq, ids), 0, len(uniq) - 1)
        known = uniq[seg] == ids
        q_lo = offsets[seg]
        q_hi = np.where(known, offsets[seg + 1], q_lo)
        _require(table_ts.max() < 2**31 and spine_ts.max() < 2**31, "int32 span")
        tab = table_ts.astype(np.int32)
        q_ts = spine_ts.astype(np.int32)

        idx_k, valid_k = pit_ops.pit_search(
            jnp.asarray(tab), jnp.asarray(q_ts),
            jnp.asarray(q_lo.astype(np.int32)), jnp.asarray(q_hi.astype(np.int32)),
        )
        idx_k, valid_k = np.asarray(idx_k), np.asarray(valid_k)

        # pit_search_ref over windows of the history: queries sorted by
        # segment, so each chunk of them reads one contiguous slice
        chunk = 2048
        by_lo = np.argsort(q_lo, kind="stable")
        starts = [int(q_lo[by_lo[i]]) for i in range(0, spine_rows, chunk)]
        ends = [
            int(q_hi[by_lo[i : i + chunk]].max()) for i in range(0, spine_rows, chunk)
        ]
        width = 1 << max(e - s for s, e in zip(starts, ends)).bit_length()
        padded = np.concatenate([tab, np.full(width, 2**31 - 1, np.int32)])
        ref = jax.jit(pit_search_ref)
        idx_r = np.empty(spine_rows, np.int64)
        valid_r = np.empty(spine_rows, bool)
        for i, s in zip(range(0, spine_rows, chunk), starts):
            sel = by_lo[i : i + chunk]
            ri, rv = ref(
                jnp.asarray(padded[s : s + width]),
                jnp.asarray(q_ts[sel]),
                jnp.asarray((q_lo[sel] - s).astype(np.int32)),
                jnp.asarray((q_hi[sel] - s).astype(np.int32)),
            )
            idx_r[sel] = np.asarray(ri) + s
            valid_r[sel] = np.asarray(rv)
        _require(np.array_equal(valid_k, valid_r), "PIT validity differs from ref")
        _require(
            np.array_equal(idx_k[valid_r], idx_r[valid_r]),
            "PIT indices differ from pit_search_ref",
        )
        prefix = f"{name}:v{version}"
        _require(
            np.array_equal(out[f"{prefix}:__found__"], valid_r),
            "get_offline_features found-flags differ from the reference",
        )
        safe = np.where(valid_r, idx_r, 0)
        for a in transform.aggs:
            want = np.where(valid_r, history[a.output][order][safe], 0)
            _require(
                out[f"{prefix}:{a.output}"].tobytes()
                == want.astype(np.float32).tobytes(),
                f"get_offline_features {a.output} differs from the reference rows",
            )
        return {
            "rows": spine_rows,
            "history_rows": len(history),
            "found": int(valid_r.sum()),
        }

    phase("retrieve", retrieve)

    # -- consistency and fallbacks ---------------------------------------------
    def consistency():
        report = fs.check_consistency(name, version)
        _require(report.consistent, f"offline/online: {report.summary()}")
        fallbacks = fs.monitor.kernel_fallbacks()
        _require(not any(fallbacks.values()), f"kernel fallbacks: {fallbacks}")
        return {"rows": int(report.checked_ids), "kernel_fallbacks": fallbacks}

    phase("consistency", consistency)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="data seed")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    platform = jax.default_backend()
    if platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
            file=sys.stderr,
        )
        return 1
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"phase": "setup", "compile_cache": cache_dir}), flush=True)
    try:
        run(seed=args.seed, emit=lambda line: print(line, flush=True))
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
