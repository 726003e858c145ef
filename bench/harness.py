"""The benchmark's engine: one run of one cell, found by name in BENCHMARK.json.

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<traffic>.json``).  The mix names the generator that runs it
(``bench/generators/<generator>.py``), and each per-layer metric is read by a module
of its own (``bench/metrics/<metric>.py``).  Nothing here knows a cell, a
mix or a metric by name, so a later cell, mix or metric is new files and new
``BENCHMARK.json`` entries only.

A generator module provides three functions:

* ``setup(run) -> state``: build the store and its data from ``run.seed``
  and warm every shape the window uses, timing its parts with
  ``run.phase(name)``;
* ``window(state, run, seconds) -> Window``: the measured work; it returns
  the cell's end-to-end values and its attempted and failed counts;
* ``check(state, run, control=False) -> list[Check]``: compare what the
  window produced with the plain reference (``bench/reference/``); with
  ``control=True`` the reference's own answers, computed in the precision
  below the configuration's, stand in for the program's.

A metric module provides ``read(run) -> float | None``; ``None`` means it
found nothing to read, and the metric is left out of the result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# host spans written into the profiler's trace carry this prefix, so the
# trace reduction can tell them from the runtime's own host events
SPAN_PREFIX = "bench."


class BenchError(Exception):
    """The run cannot produce a result (no chip, unknown cell, bad data)."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit: the run is
    correct when every value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What the measured window did: end-to-end values by metric name, and
    the operations attempted and failed."""

    metrics: dict[str, float]
    attempted: int
    failed: int
    # open-loop cells: how late the generator issued requests (seconds)
    lateness: Optional[dict] = None
    # further readings of the window, printed on an earlier line
    detail: Optional[dict] = None


def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """Import a benchmark file by path (metric files carry dots in their
    names, so they cannot be imported by module name)."""
    name = "bench_" + "_".join(path.relative_to(BENCH_DIR).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str) -> dict:
    """Everything BENCHMARK.json says about one cell, with its files read."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")

    def in_cell(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    end_to_end = [m for m in spec["end_to_end"] if in_cell(m)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in spec["per_layer"] if in_cell(m) and m["moves"] in moved
    ]
    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


class CompileClock:
    """Counts the programs JAX lowers and compiles, and the seconds it
    spends compiling.  A program taken from the persistent cache is still
    lowered first, so ``lowered`` counts every new executable, cached or
    not: inside the measured window it should stay 0."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.compiled = 0
        self.lowered = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiled += 1
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {
            "lowered": self.lowered,
            "compiled": self.compiled,
            "cache_hits": self.cache_hits,
            "compile_s": self.seconds,
        }


class GcClock:
    """The host's garbage collections inside a block: how many of each
    generation and the longest pauses, so that a host stall in a window can
    be told from the system's own work."""

    def __init__(self) -> None:
        self.pauses: list[tuple[int, float]] = []
        self._t0 = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))

    @contextlib.contextmanager
    def watch(self):
        gc.callbacks.append(self._on_gc)
        try:
            yield self
        finally:
            gc.callbacks.remove(self._on_gc)

    def summary(self) -> dict:
        per_gen = defaultdict(list)
        for gen, s in self.pauses:
            per_gen[gen].append(s)
        return {
            f"gen{g}": {"n": len(v), "total_ms": sum(v) * 1e3, "max_ms": max(v) * 1e3}
            for g, v in sorted(per_gen.items())
        }


class Run:
    """One run's context: the cell's files, the seed, the spans and counters
    the benchmark records around its calls into the program, and (traced
    runs) the reduced device trace."""

    def __init__(
        self,
        workload: str,
        cell: dict,
        *,
        seed: int,
        seconds: float,
        trace: bool,
    ) -> None:
        self.workload = workload
        self.seconds = float(seconds)
        self.cell = cell["cell"]
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.end_to_end = cell["end_to_end"]
        self.per_layer = cell["per_layer"]
        self.seed = int(seed)
        self.trace = bool(trace)
        self.setup_parts: dict[str, float] = defaultdict(float)
        self.spans: dict[str, list[float]] = defaultdict(list)
        self.counters: dict[str, float] = defaultdict(float)
        self.device_trace: Optional[dict] = None
        self.peaks: dict = {}

    # -- set-up ---------------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one part of the set-up (data, load, warm)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.setup_parts[name] += time.perf_counter() - t0

    # -- spans and counters -----------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around a call into the program.  In a traced run it is
        also written into the profiler's trace, so idle gaps on the device
        can be charged to what the host was doing."""
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.spans[name].append(time.perf_counter() - t0)


def enable_cache() -> str:
    """The program's persistent compilation cache (in the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one), holding every program however
    quick its compile, so that a run after the first compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def settle_heap() -> None:
    """End set-up as a long-running server does: collect, then move what
    set-up left on the heap (the imports, the compiled programs' Python
    objects, the load's frames) out of the collector's reach, so that a
    full collection in the window walks only what the window allocated.
    ``gc.unfreeze()`` gives it back."""
    gc.collect()
    gc.freeze()


def check_device(chips: int) -> Any:
    """The chips JAX sees; a BenchError without a TPU or with too few."""
    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform {platform!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backends without memory stats
            stats = None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _peaks_for(kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table["devices"][kind]


@contextlib.contextmanager
def _profiled(enabled: bool):
    """Trace the block with the profiler when asked.  Yields a dict that,
    after the block, holds the written ``.xplane.pb`` under ``path`` (None
    when nothing was traced) and its temporary directory under ``dir``."""
    trace = {"path": None, "dir": None}
    if not enabled:
        yield trace
        return
    import jax

    trace["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python-call tracing would slow the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace["dir"], profiler_options=opts)
    try:
        yield trace
    finally:
        jax.profiler.stop_trace()
        found = sorted(Path(trace["dir"]).rglob("*.xplane.pb"))
        trace["path"] = found[-1] if found else None


def run_cell(
    workload: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    require_tpu: bool = True,
    cell: Optional[dict] = None,
    emit: Callable[[str], None] = print,
    t_start: Optional[float] = None,
    control: bool = False,
) -> dict:
    """One run: set-up, the measured window, the comparison with the
    reference and (traced runs) the per-layer metrics.  Returns the result
    object whose JSON is the run's last line.  With ``control`` the result
    also carries the control's comparison (``control_checks``,
    ``control_correct``): the reference in the precision below the
    configuration's, put in the program's place."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cell or load_cell(workload)
    chips = int(cell["cell"]["chips"])
    import jax

    devices = check_device(chips) if require_tpu else jax.devices()
    used = devices[:chips]
    kind = used[0].device_kind
    run = Run(workload, cell, seed=seed, seconds=seconds, trace=trace)
    if require_tpu:
        run.peaks = _peaks_for(kind)
    generator = load_module(BENCH_DIR / "generators" / f"{run.traffic['generator']}.py")
    clock = CompileClock()

    state = generator.setup(run)
    settle_heap()
    setup_s = time.perf_counter() - t_start
    at_window = clock.snapshot()
    emit(
        json.dumps(
            {
                "setup": {**run.setup_parts, "total_s": setup_s},
                "compile": at_window,
            }
        )
    )

    gc_clock = GcClock()
    with _profiled(trace) as profile, gc_clock.watch():
        with run.span("window"):
            result = generator.window(state, run, seconds)
    gc.unfreeze()
    after = clock.snapshot()
    in_window = {k: after[k] - at_window[k] for k in after}
    memory_peak = _memory_peak(used)
    fallbacks = state.fs.monitor.kernel_fallbacks()
    emit(json.dumps({"window_compiles": in_window, "kernel_fallbacks": fallbacks,
                     "window_gc": gc_clock.summary(),
                     "host_spans_s": {k: sum(v) for k, v in run.spans.items()}}))
    if result.lateness is not None:
        emit(json.dumps({"generator_late_s": result.lateness}))
    if result.detail is not None:
        emit(json.dumps({"window_detail": result.detail}))

    device = {
        "platform": used[0].platform,
        "kind": kind,
        "count": len(used),
        "memory_peak_bytes": memory_peak,
    }
    breakdown = None
    if trace:
        if profile["path"] is None:
            raise BenchError("the profiler wrote no trace")
        from bench import trace_reduce

        window_span = run.spans["window"][-1]
        reduced = trace_reduce.reduce_file(
            profile["path"], chips=len(used), window_span=SPAN_PREFIX + "window"
        )
        shutil.rmtree(profile["dir"], ignore_errors=True)
        run.device_trace = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {
            "device_ops": reduced["top_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
        emit(json.dumps({"host_window_s": window_span, "trace": {
            k: reduced[k] for k in ("busy_s", "window_s", "module_s", "longest")}}))

    checks = generator.check(state, run)
    correct = all(c.ok for c in checks) and result.failed == 0

    units = {m["name"]: m["unit"] for m in run.end_to_end + run.per_layer}
    if trace:
        values = {}
        for m in run.per_layer:
            reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py")
            v = reader.read(run)
            if v is None:
                print(f"bench: {m['name']} found nothing to read", file=sys.stderr)
            else:
                values[m["name"]] = float(v)
    else:
        values = dict(result.metrics)
        values["setup_s"] = setup_s
    out = {
        "correct": bool(correct),
        "attempted": int(result.attempted),
        "failed": int(result.failed),
        "metrics": {
            k: {"value": v, "unit": units[k]} for k, v in values.items() if k in units
        },
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        low = generator.check(state, run, control=True)
        out["control_correct"] = all(c.ok for c in low)
        out["control_checks"] = {
            c.name: {"value": c.value, "limit": c.limit} for c in low
        }
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return out


def check_lines(out: dict) -> list[str]:
    """The compared numbers as plain lines, each beside its limit."""
    return [
        f"check {name}: {c['value']!r} (limit {c['limit']!r})"
        + ("" if c["value"] <= c["limit"] else "  FAILED")
        for name, c in out["checks"].items()
    ]
