"""Plain rolling-window aggregates per entity: the reference for the
materialization cells.

For each query (entity, ts) and each aggregate, the window is every source
row of that entity with ``ts - window < ts_j <= ts``, ties included.  The
rows are sorted by (entity, ts) once; each query's window is then found by
two binary searches and summed element by element, in float64, or with every
input and partial result rounded to bfloat16 for the control.  Nothing here
uses the program's DSL or its window-start helper.
"""

from __future__ import annotations

import numpy as np

from bench.reference.precision import as_bfloat16

__all__ = ["window_features"]


def _round(x: np.ndarray, bf16: bool) -> np.ndarray:
    return as_bfloat16(x).astype(np.float64) if bf16 else x


def window_features(
    entity: np.ndarray,
    ts: np.ndarray,
    columns: dict,
    aggs: list[dict],
    q_entity: np.ndarray,
    q_ts: np.ndarray,
    *,
    bf16: bool = False,
) -> dict[str, np.ndarray]:
    """Each of ``aggs`` (dicts with ``output``, ``source``, ``window_ms`` and
    ``agg`` in sum, mean, count, max) at every query row, as float64.  With
    ``bf16`` the values, every running sum and every result are rounded to
    bfloat16, as a bfloat16 accumulator would leave them."""
    entity = np.asarray(entity, np.int64)
    ts = np.asarray(ts, np.int64)
    order = np.lexsort((ts, entity))
    e, t = entity[order], ts[order]
    e0, t0 = int(e.min()), int(t.min())
    span = int(t.max()) - t0 + 2
    if (int(e.max()) - e0 + 1) * span >= 2**62:
        raise ValueError("entity x timestamp range does not fit an int64 key")
    key = (e - e0) * span + (t - t0)
    qe = np.asarray(q_entity, np.int64) - e0
    qt = np.asarray(q_ts, np.int64) - t0
    # one past the query's last row: its ties included
    hi = np.searchsorted(key, qe * span + qt, side="right")
    out: dict[str, np.ndarray] = {}
    for a in aggs:
        w = int(a["window_ms"])
        lo = np.searchsorted(key, qe * span + np.maximum(qt - w, -1), side="right")
        count = (hi - lo).astype(np.float64)
        if a["agg"] == "count":
            out[a["output"]] = count
            continue
        vals = _round(np.asarray(columns[a["source"]], np.float64)[order], bf16)
        acc = np.zeros(len(qe)) if a["agg"] in ("sum", "mean") else np.full(
            len(qe), -np.inf)
        live = np.arange(len(qe))
        for k in range(int((hi - lo).max(initial=0))):
            live = live[lo[live] + k < hi[live]]  # the queries with a k-th row
            v = vals[lo[live] + k]
            if a["agg"] == "max":
                acc[live] = np.maximum(acc[live], v)
            else:
                acc[live] = _round(acc[live] + v, bf16)
        if a["agg"] == "mean":
            acc = _round(acc / np.maximum(count, 1.0), bf16)
        elif a["agg"] not in ("sum", "max"):
            raise ValueError(f"unknown agg {a['agg']!r}")
        out[a["output"]] = acc
    return out
