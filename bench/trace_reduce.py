"""Reduce a JAX profiler trace to the benchmark's device numbers.

The profiler writes one ``.xplane.pb`` per run.  Its device planes
(``/device:TPU:<n>``) carry two lines this reduction reads: ``XLA Modules``,
one event per executed program named ``jit_<function>(<fingerprint>)``, and
``XLA Ops``, one event per HLO operation named by its HLO text
(``%lookup_kernel_call.1 = s32[...] custom-call(...)``).  The host plane
(``/host:CPU``) carries the benchmark's own spans (``bench.<name>``), on the
same clock.  From these:

* ``busy_s``: the union of the device's op and program intervals inside the
  window, averaged over the chips used; ``window_s``: the window's length
  (the ``bench.window`` span);
* ``module_s``: device seconds per program, by its stable function name
  (``jit_gather_rows(123)`` -> ``gather_rows``);
* ``top_ops``: device seconds per operation, by HLO name without its
  numeric suffix (``%copy.2`` -> ``copy``), largest first;
* ``idle_gaps``: the device's idle time inside the window, charged to the
  innermost benchmark span open at each gap's midpoint, largest first;
* ``longest``: the five longest single programs and single idle gaps, which
  show a stall that the sums spread out.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Iterable

_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_OP = re.compile(r"^%?([^\s=]*?)(?:\.\d+)*(?:\s*=.*)?$")
DEVICE_PREFIX = "/device:TPU:"


def module_name(event_name: str) -> str:
    return _MODULE.match(event_name).group(1)


def op_name(event_name: str) -> str:
    return _OP.match(event_name.strip()).group(1)


def load(path) -> dict:
    """The events this reduction reads, as plain tuples (name, start_ns, end_ns):
    ``{"devices": {index: {"modules": [...], "ops": [...]}}, "host": [...]}``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    devices: dict[int, dict] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            dev = devices.setdefault(idx, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith("bench.")
                )
    return {"devices": devices, "host": host}


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _owners(points: list[float], spans: list[tuple]) -> list[str]:
    """For each point (ascending), the innermost span open at it: spans are
    sorted by start and nest (a span opened inside another closes first), so
    a stack of the open spans finds it in one sweep."""
    out = []
    stack: list[tuple] = []
    i = 0
    for p in points:
        while i < len(spans) and spans[i][1] <= p:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] <= p:
            stack.pop()
        out.append(stack[-1][0].removeprefix("bench.") if stack else "(no span)")
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    return max(s, lo), min(e, hi)


def reduce(events: dict, *, chips: int = 1, window_span: str = "bench.window") -> dict:
    """Busy and idle time, per-program and per-op device time, and idle gaps
    by host span, over the window span (or, without one, over the device
    events' extent)."""
    devices = [events["devices"][i] for i in sorted(events["devices"])[:chips]]
    if not devices:
        raise ValueError("the trace holds no device plane")
    host = events["host"]
    spans = [h for h in host if h[0] == window_span]
    if spans:
        lo, hi = spans[0][1], spans[0][2]
    else:
        ends = [t for d in devices for k in ("modules", "ops") for _, *t in d[k]]
        lo, hi = min(t[0] for t in ends), max(t[1] for t in ends)
    window_ns = hi - lo

    busy_total = 0.0
    module_s: dict[str, float] = defaultdict(float)
    op_s: dict[str, float] = defaultdict(float)
    gaps_by_span: dict[str, float] = defaultdict(float)
    long_modules: list = []
    long_gaps: list = []
    spans_by_start = sorted(
        (h for h in host if h[0] != window_span), key=lambda h: (h[1], -h[2])
    )
    for dev in devices:
        clipped = []
        for name, s, e in dev["modules"]:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                module_s[module_name(name)] += (e - s) / 1e9 / len(devices)
                long_modules.append([module_name(name), (e - s) / 1e9])
                clipped.append((s, e))
        for name, s, e in dev["ops"]:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                op_s[op_name(name)] += (e - s) / 1e9 / len(devices)
                clipped.append((s, e))
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
        for (gs, ge), owner in zip(gaps, _owners([(s + e) / 2 for s, e in gaps],
                                                 spans_by_start)):
            gaps_by_span[owner] += (ge - gs) / 1e9 / len(devices)
            long_gaps.append([owner, (ge - gs) / 1e9])

    def ranked(d: dict) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {
        "busy_s": busy_total / 1e9 / len(devices),
        "window_s": window_ns / 1e9,
        "module_s": dict(module_s),
        "top_ops": ranked(op_s),
        "idle_gaps": ranked(gaps_by_span),
        "longest": {
            "programs": sorted(long_modules, key=lambda kv: -kv[1])[:5],
            "idle_gaps": sorted(long_gaps, key=lambda kv: -kv[1])[:5],
        },
    }


def reduce_file(path, *, chips: int = 1, window_span: str = "bench.window") -> dict:
    return reduce(load(path), chips=chips, window_span=window_span)
