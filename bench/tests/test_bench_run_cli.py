"""The command refuses to run where it cannot measure: without a TPU, and in
a directory that holds only the benchmark's own files."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ycsb_b_zipf",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def _no_result(proc) -> bool:
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            return False
    return True


def test_no_tpu_no_result():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert _no_result(proc)


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)


def test_benchmark_json_names_existing_files():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for c in spec["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        traffic = cell["traffic"]
        assert (harness.BENCH_DIR / "generators" / f"{traffic['generator']}.py").is_file()
        for m in cell["per_layer"]:
            assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
