#!/usr/bin/env python3
"""Find the knee of an open-loop key-value cell: the highest offered rate it
sustains without a growing backlog.

    python3 bench/knee_sweep.py --workload ycsb_b_zipf --seed N \
        --seconds 6 --rates 1000,2000,4000,8000

One set-up serves every rate (the table is loaded once); each rate then
runs one window of fresh Poisson arrivals.  For each rate one JSON line
gives the offered and completed operations per second, the GET latency
quantiles, how late the generator ran, and ``growth``: the median GET
latency of the window's last quarter over its first quarter (a backlog that
grows all through the window reads well above 1).  The last line repeats
the set-up split and checks every GET of every window against the
reference.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True, help="comma-separated ops/s")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import numpy as np

    from bench import harness
    from bench.generators import kv_open_loop as kv

    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    harness.check_device(int(cell["cell"]["chips"]))
    rates = [float(r) for r in args.rates.split(",")]
    run = harness.Run(args.workload, cell, seed=args.seed, seconds=args.seconds,
                      trace=False)
    # one set-up for every rate: its schedule is replaced before each window
    run.traffic = dict(run.traffic, rate_per_s=rates[0])
    clock = harness.CompileClock()
    state = kv.setup(run)
    harness.settle_heap()
    print(json.dumps({"setup": dict(run.setup_parts),
                      "setup_s": time.perf_counter() - t_start,
                      "compile": clock.snapshot()}), flush=True)
    for i, rate in enumerate(rates):
        rng = np.random.default_rng([args.seed, i + 1])
        state.due, state.is_update, state.keys = kv.schedule(
            rng, run.config, run.traffic, rate, args.seconds)
        before = clock.snapshot()
        gc_clock = harness.GcClock()
        with gc_clock.watch():
            w = kv.window(state, run, args.seconds)
        lat = state.last_window["get_lat"]
        due = state.last_window["due"]
        q = len(lat) // 4
        first = np.median(lat[np.argsort(due)][:q]) if q else float("nan")
        last = np.median(lat[np.argsort(due)][-q:]) if q else float("nan")
        print(json.dumps({
            "rate": rate,
            "offered_ops_per_s": w.attempted / args.seconds,
            "completed_ops_per_s": w.lateness["ops_per_s"],
            "get_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "get_p99_ms": w.metrics["get_p99_ms"],
            "update_p99_ms": w.metrics.get("update_p99_ms"),
            "late_p99_ms": w.lateness["p99"] * 1e3,
            "growth": float(last / first),
            "keys_per_dispatch": run.counters["front.coalesced_keys"]
            / max(run.counters["front.dispatches"], 1),
            "cache_hit_share": run.counters["front.cache_hits"]
            / max(run.counters["front.cache_hits"] + run.counters["front.cache_misses"], 1),
            "window_lowered": clock.snapshot()["lowered"] - before["lowered"],
            "window_gc": gc_clock.summary(),
        }), flush=True)
    checks = kv.check(state, run)
    print(json.dumps({"checks": {c.name: [c.value, c.limit] for c in checks},
                      "correct": all(c.ok for c in checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
