"""Feature-transformation DSL (paper §3.1.6).

When customers define features through a UDF, the platform must treat the
transformation as a black box.  When they use the DSL — "a common case is
rolling window aggregation" — the query engine can optimize execution.  Our
optimizer does exactly what the paper sketches ("optimize the aggregation
based on join results"):

  * given the ``feature_window`` Algorithm 1 keeps (``context``), the plan
    runs over the rows of the entities that have a row in it, and returns
    the window's rows: a row's window holds rows of its own entity alone,
    so the rest of the lookback cannot change a kept row's features;
  * the (entity, timestamp) sort and the end of each row's timestamp tie
    are computed ONCE for the plan, the per-row window-start index once per
    window length, and shared by every aggregation over it;
  * a row's window is every row of its entity with
    ``ts - window < ts_j <= ts``: rows tied with it on (entity, ts) are in
    it whatever their sorted order, so each aggregate of the contiguous
    span ``[start, i]`` is read at the tie's last row;
  * aggregations over the same source column share the loaded column;
  * sum-family aggregations lower to the Pallas rolling-sum kernel
    (kernels/rolling_agg) — one masked MXU matmul per row block;
  * count is closed-form from the shared window indices (zero data reads).

``UDFTransform`` is the black-box path: an arbitrary
``udf(source_df, context) -> feature_df`` per §4.2.
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
from typing import Any, Callable, Sequence

import numpy as np

from repro.core.assets import TransformProtocol
from repro.core.keys import encode_keys
from repro.core.table import Table
from repro.kernels.rolling_agg import ops as rolling_ops

__all__ = ["RollingAgg", "DslTransform", "UDFTransform", "SUPPORTED_AGGS"]

SUPPORTED_AGGS = ("sum", "mean", "count", "min", "max")


@dataclasses.dataclass(frozen=True)
class RollingAgg:
    """``<output> = <agg>(<source_col>) over trailing <window> ms per entity``."""

    output: str
    source_col: str
    window: int
    agg: str

    def __post_init__(self) -> None:
        if self.agg not in SUPPORTED_AGGS:
            raise ValueError(f"agg must be one of {SUPPORTED_AGGS}, got {self.agg!r}")
        if self.window <= 0:
            raise ValueError("window must be positive")


class DslTransform(TransformProtocol):
    """Declarative rolling-window aggregation plan, platform-optimizable."""

    is_dsl = True

    def __init__(
        self,
        entity_col: str | Sequence[str],
        timestamp_col: str,
        aggs: Sequence[RollingAgg],
        *,
        use_kernel: bool = True,
    ) -> None:
        if not aggs:
            raise ValueError("DslTransform needs at least one aggregation")
        self.entity_cols = (
            (entity_col,) if isinstance(entity_col, str) else tuple(entity_col)
        )
        self.timestamp_col = timestamp_col
        self.aggs = tuple(aggs)
        self.use_kernel = use_kernel
        outs = [a.output for a in self.aggs]
        if len(set(outs)) != len(outs):
            raise ValueError(f"duplicate DSL outputs: {outs}")

    # -- identity (immutable property of the feature set version) ----------
    def code_fingerprint(self) -> str:
        desc = repr(
            (self.entity_cols, self.timestamp_col,
             tuple((a.output, a.source_col, a.window, a.agg) for a in self.aggs))
        )
        return "dsl:" + hashlib.sha256(desc.encode()).hexdigest()[:16]

    @property
    def max_lookback(self) -> int:
        """What Algorithm 1 must use as ``source_lookback``."""
        return max(a.window for a in self.aggs)

    # -- optimized execution -------------------------------------------------
    def __call__(self, source_df: Table, context: dict[str, Any]) -> Table:
        kept = context.get("feature_window")
        if kept is not None:
            source_df = self._entities_in(source_df, kept)
        n = len(source_df)
        # Shared sort by (entity..., ts): done once for the whole plan.
        sort_cols = (*self.entity_cols, self.timestamp_col)
        order = np.lexsort(tuple(source_df[c] for c in reversed(sort_cols)))
        sorted_df = source_df.take(order)
        ts = sorted_df[self.timestamp_col].astype(np.int64)
        seg = self._segment_ids(sorted_df)
        # A row's window ends at the last row of its (entity, ts) tie, so
        # each aggregate is read there.
        tie_end = _tie_ends(seg, ts)

        # Shared window-start indices per distinct window length.
        starts_by_window: dict[int, np.ndarray] = {}
        for a in self.aggs:
            if a.window not in starts_by_window:
                starts_by_window[a.window] = (
                    rolling_ops.window_starts(seg, ts, a.window)
                    if n
                    else np.zeros((0,), np.int32)
                )
        counts = {w: tie_end + 1 - st for w, st in starts_by_window.items()}

        # Group sum/mean aggs that share a window so one kernel launch
        # covers all their source columns (columns stacked on the lane dim).
        out_cols: dict[str, np.ndarray] = {
            c: sorted_df[c] for c in (*self.entity_cols, self.timestamp_col)
        }
        kernel_groups: dict[int, list[RollingAgg]] = {}
        for a in self.aggs:
            if a.agg in ("sum", "mean") and n:
                kernel_groups.setdefault(a.window, []).append(a)

        for window, group in kernel_groups.items():
            cols = sorted(set(a.source_col for a in group))
            mat = np.stack([sorted_df[c].astype(np.float32) for c in cols], axis=1)
            sums = rolling_ops.rolling_agg(
                mat, starts_by_window[window], "sum",
                backend="pallas" if self.use_kernel else "xla",
                monitor=context.get("monitor"),
            )[tie_end]
            for a in group:
                col = sums[:, cols.index(a.source_col)]
                if a.agg == "mean":
                    col = col / np.maximum(counts[window], 1)
                out_cols[a.output] = col.astype(np.float32)

        for a in self.aggs:
            if a.output in out_cols:
                continue
            starts = starts_by_window[a.window]
            if a.agg == "count":
                out_cols[a.output] = counts[a.window].astype(np.float32)
            elif n == 0:
                out_cols[a.output] = np.zeros((0,), np.float32)
            else:
                vals = sorted_df[a.source_col].astype(np.float32)[:, None]
                out_cols[a.output] = rolling_ops.rolling_agg(
                    vals, starts, a.agg
                )[tie_end, 0].astype(np.float32)

        out = Table(out_cols)
        if kept is None:
            return out
        return out.filter_time_range(self.timestamp_col, kept.start, kept.end)

    def _entities_in(self, df: Table, window) -> Table:
        """The rows of ``df`` whose entity has a row in ``window``."""
        ts = df[self.timestamp_col]
        key = encode_keys([df[c] for c in self.entity_cols])
        in_window = key[(ts >= window.start) & (ts < window.end)]
        return df.filter(np.isin(key, in_window))

    def _segment_ids(self, sorted_df: Table) -> np.ndarray:
        n = len(sorted_df)
        if n == 0:
            return np.zeros((0,), np.int64)
        change = np.zeros(n, dtype=bool)
        for c in self.entity_cols:
            col = sorted_df[c]
            change[1:] |= col[1:] != col[:-1]
        return np.cumsum(change).astype(np.int64)


def _tie_ends(seg: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """For rows sorted by (segment, ts): the index of the last row of each
    row's (segment, ts) tie (its own index when nothing ties with it)."""
    n = len(ts)
    last = np.ones(n, bool)
    last[:-1] = (seg[1:] != seg[:-1]) | (ts[1:] != ts[:-1])
    # each row's tie is the number of tie ends before it
    return np.flatnonzero(last)[np.cumsum(last) - last]


class UDFTransform(TransformProtocol):
    """Black-box user code: ``udf(source_df, context) -> feature_df`` (§4.2)."""

    is_dsl = False

    def __init__(self, fn: Callable[[Table, dict[str, Any]], Table], name: str = ""):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "udf")

    def code_fingerprint(self) -> str:
        try:
            src = inspect.getsource(self.fn)
        except (OSError, TypeError):
            src = repr(self.fn)
        return "udf:" + hashlib.sha256(src.encode()).hexdigest()[:16]

    def __call__(self, source_df: Table, context: dict[str, Any]) -> Table:
        return self.fn(source_df, context)
