"""Cells at a size a CPU test run can hold: the committed configuration and
traffic files with their scale cut down, run on the CPU with the kernels in
Pallas interpret mode."""

from __future__ import annotations

from bench import harness

TINY = {
    "ycsb_1kb": {"recordcount": 4096, "features": 8},
}


def cell(config: str, traffic: str) -> dict:
    """A cell of ``config`` under ``traffic`` at its tiny size."""
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == config)
    cfg = harness.load_json(harness.ROOT / entry["file"])
    cfg.update(TINY[config])
    if "serving" in cfg:
        cfg["serving"] = dict(cfg["serving"], cache_capacity=256, max_batch_keys=64)
    mix = harness.load_json(harness.BENCH_DIR / "traffic" / f"{traffic}.json")
    if "rate_per_s" in mix:
        mix["rate_per_s"] = 150
    return {
        "cell": {"name": traffic, "config": config, "traffic": traffic, "chips": 1},
        "config": cfg,
        "traffic": mix,
        "end_to_end": [],
        "per_layer": [],
    }


def run(config: str, traffic: str, *, seed: int = 2**33 + 17, seconds: float = 0.5,
        control: bool = False) -> dict:
    """One run of the tiny cell on the CPU, set-up to comparison."""
    return harness.run_cell(
        traffic, seed=seed, seconds=seconds, trace=False, require_tpu=False,
        cell=cell(config, traffic), emit=lambda line: None, control=control,
    )
