#!/usr/bin/env python3
"""Run a cell once and compare both the program and the control with the
reference: the numbers each gives, beside their limits.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

The control is the plain reference computed in bfloat16, the precision below
the float32 every configuration states, put in the program's place; it has
to come out not correct.  The benchmark's own runs never run the control.  Prints one JSON line.
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
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench import harness

    harness.enable_cache()
    name, cell = args.workload, harness.load_cell(args.workload)
    out = harness.run_cell(name, seed=args.seed, seconds=args.seconds, trace=False,
                           cell=cell, control=True, t_start=t_start,
                           emit=lambda line: print(line, flush=True))
    print(json.dumps({"seed": args.seed, **{k: out[k] for k in (
        "correct", "checks", "control_correct", "control_checks", "attempted",
        "failed", "device")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
