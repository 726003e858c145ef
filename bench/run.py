#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout.  Set-up builds the cell's store and data from ``--seed`` and warms
every shape the window uses; the window then runs for ``--seconds``; last,
what the window produced is compared with the plain reference.  Earlier
lines of standard output give the set-up split, the programs compiled
inside the window (should be 0), the kernel fallbacks and, for open-loop
cells, how late the generator ran.  The last line is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``), then ``checks``: each compared number
beside its limit, which also close standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
repository's ``src/`` beside ``bench/``, the run exits non-zero and prints
no result.
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
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="data and traffic seed")
    ap.add_argument("--seconds", type=float, required=True, help="measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report per-layer metrics")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no repository sources at {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(src), str(ROOT)]

    from bench.harness import BenchError, check_lines, enable_cache, run_cell

    enable_cache()
    try:
        out = run_cell(
            args.workload,
            seed=args.seed,
            seconds=args.seconds,
            trace=bool(args.trace),
            emit=lambda line: print(line, flush=True),
            t_start=t_start,
        )
    except BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 1
    for line in check_lines(out):
        print(line, file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
