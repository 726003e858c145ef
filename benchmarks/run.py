"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run``.

One module per claim in the paper (§ refs in each module's docstring):

  rolling_dsl      §3.1.6  DSL-optimized aggregation vs black-box UDF
  pit_retrieval    §4.4    point-in-time offline retrieval throughput
  online_store     §2.1/§4.5  online GET latency + Algorithm-2 merge + staleness
  serving          §2.1/§3.1.4  serving front: coalesced GET amortization,
                   zipfian closed-loop latency + hit rate, overload shedding
  materialization  §4.3/§4.5.4  pipeline throughput, backfill, fault injection
  geo              §4.1.2  cross-region access vs geo-replication + stragglers
  geo_replication  §4.1.2  the replication data plane measured: ship/apply
                   throughput, local-read latency, failover replay
  roofline         (g)     §Roofline table from the dry-run artifacts

Writes results/benchmarks.json; ``--only <name>`` runs a subset; ``--fast``
shrinks workloads (CI).
"""

from __future__ import annotations

import argparse
import json
import time
import traceback
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset")
    ap.add_argument("--fast", action="store_true", help="small workloads (CI)")
    ap.add_argument("--out", default="results/benchmarks.json")
    args = ap.parse_args()

    from benchmarks import (  # noqa: PLC0415 — import after arg parsing
        bench_geo,
        bench_geo_replication,
        bench_materialization,
        bench_online_store,
        bench_pit_retrieval,
        bench_rolling_dsl,
        bench_serving,
        roofline_summary,
    )

    suites = {
        "rolling_dsl": lambda: bench_rolling_dsl.run(
            sizes=(2_000, 10_000) if args.fast else (2_000, 10_000, 50_000)
        ),
        "pit_retrieval": lambda: bench_pit_retrieval.run(
            spine_sizes=(1_000,) if args.fast else (1_000, 10_000)
        ),
        "online_store": lambda: bench_online_store.run(
            entity_counts=(1_000,) if args.fast else (1_000, 10_000)
        ),
        # fixed-shape even under --fast: the serving gates (hit rate,
        # coalesce sizes, overload counts) are exact, not calibrated
        "serving": lambda: bench_serving.run(fast=args.fast),
        "materialization": lambda: bench_materialization.run(
            hours=6 if args.fast else 16,
            merge_window=20_000 if args.fast else 100_000,
        ),
        "geo": bench_geo.run,
        "geo_replication": lambda: bench_geo_replication.run(fast=args.fast),
        "roofline": lambda: roofline_summary.summarize(),
    }
    only = {s for s in args.only.split(",") if s}
    results: dict = {}
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"=== bench: {name} ===", flush=True)
        t0 = time.time()
        try:
            results[name] = {"ok": True, "wall_s": None, "result": fn()}
            results[name]["wall_s"] = round(time.time() - t0, 2)
            print(json.dumps(results[name]["result"], indent=1, default=str)[:2000])
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            results[name] = {"ok": False, "error": f"{type(e).__name__}: {e}"}

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1, default=str))
    print(f"\nwrote {out}")

    # Standalone perf-trajectory artifacts, tracked PR-over-PR at the repo
    # root.  --fast runs use different workloads, so they must not overwrite
    # the tracked full-size numbers:
    #   BENCH_materialization.json — merge-path throughput trajectory
    #   BENCH_online_store.json    — serving-path latency (both GET paths) +
    #                                the resident-cycle transfer profile (the
    #                                O(batch) guarantee of the device-resident
    #                                online store)
    #   BENCH_serving.json         — serving-front trajectory: coalesced GET
    #                                amortization, closed-loop latency + cache
    #                                hit rate, overload degrade/shed counts
    def write_artifact(suite: str, filename: str, keys: tuple[str, ...]) -> None:
        res = results.get(suite)
        if not (res and res.get("ok")) or args.fast:
            return
        artifact = Path(__file__).resolve().parent.parent / filename
        artifact.write_text(
            json.dumps(
                {k: res["result"].get(k) for k in keys}, indent=1, default=str
            )
        )
        print(f"wrote {artifact}")

    write_artifact(
        "materialization", "BENCH_materialization.json",
        ("merge_engines", "throughput"),
    )
    write_artifact(
        "online_store", "BENCH_online_store.json",
        ("lookup_table", "merge_engines", "resident_cycle"),
    )
    write_artifact(
        "geo_replication", "BENCH_geo_replication.json",
        ("throughput", "read_latency", "failover", "chaos"),
    )
    write_artifact(
        "serving", "BENCH_serving.json",
        ("coalesced_lookup", "closed_loop", "overload"),
    )

    failed = [n for n, r in results.items() if not r.get("ok")]
    if failed:
        raise SystemExit(f"benchmark failures: {failed}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
