"""Point-in-time correct offline retrieval (paper §4.4).

Given an observation ("spine") table with entity keys and observation
timestamps ts0, join each requested feature set so that every row receives
the feature value from the NEAREST PAST of ts0 — never the future — while
honouring the feature set's expected source/feature delay:

    eligible records:  event_ts <= ts0 - expected_delay
    chosen record:     max event_ts among eligible (break ties by max
                       creation_ts, matching the §4.5 record ordering)

The search runs on the kernels/pit_join counting-search Pallas kernel over
the offline store's (entity-sorted, time-sorted) history.  Timestamps are
rebased host-side into the int32 domain the kernel compares natively; spans
that cannot be rebased fall back to the jnp oracle (see kernels/pit_join).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.assets import FeatureSetSpec
from repro.core.keys import encode_keys
from repro.core.offline_store import CREATION_TS, EVENT_TS, OfflineStore
from repro.core.table import Table
from repro.kernels.pit_join import ops as pit_ops
from repro.kernels.pit_join import ref as pit_ref

__all__ = ["pit_join_feature_set", "get_offline_features"]

_I32 = 2**31 - 1


@dataclasses.dataclass
class PitResult:
    values: dict[str, np.ndarray]  # feature name -> (B,) values
    found: np.ndarray  # (B,) bool
    event_ts: np.ndarray  # (B,) int64 (0 where not found)


def _prepare_history(history: Table) -> tuple[Table, np.ndarray, np.ndarray]:
    """Sort history by (key, event_ts, creation_ts); return per-row sorted
    table + unique keys + segment offsets (len = n_unique + 1)."""
    order = np.lexsort((history[CREATION_TS], history[EVENT_TS], history["__key__"]))
    h = history.take(order)
    keys = h["__key__"]
    uniq, first = np.unique(keys, return_index=True)
    offsets = np.concatenate([first, [len(keys)]])
    return h, uniq, offsets


def pit_join_feature_set(
    spine_keys: list[np.ndarray],
    spine_ts: np.ndarray,
    spec: FeatureSetSpec,
    history: Table,
    *,
    use_kernel: bool = True,
    monitor=None,
) -> PitResult:
    """Join one feature set's history onto the spine, point-in-time correct.

    A span too wide for the kernel's int32 domain takes the jnp oracle and,
    when ``monitor`` (a ``HealthMonitor``) is given, is counted there as a
    kernel fallback."""
    b = len(spine_ts)
    spine_ts = np.asarray(spine_ts, dtype=np.int64)
    ids = encode_keys(spine_keys)
    d = len(spec.features)
    empty = PitResult(
        {f.name: np.zeros(b, np.float32) for f in spec.features},
        np.zeros(b, bool),
        np.zeros(b, np.int64),
    )
    if len(history) == 0 or b == 0:
        return empty

    h, uniq, offsets = _prepare_history(history)
    table_ev = h[EVENT_TS].astype(np.int64)

    # Route each spine row to its entity segment.
    seg = np.searchsorted(uniq, ids)
    seg_clipped = np.clip(seg, 0, len(uniq) - 1)
    has_entity = (seg < len(uniq)) & (uniq[seg_clipped] == ids)
    q_lo = offsets[seg_clipped]
    q_hi = np.where(has_entity, offsets[seg_clipped + 1], q_lo)  # empty range

    # Leakage guard: only the past of ts0, minus the expected delay.
    q_ts = spine_ts - spec.expected_delay

    # Rebase int64 epoch-ms into the kernel's int32 domain.
    t0 = int(table_ev.min())
    lo_ts = min(t0, int(q_ts.min()))
    span_ok = int(table_ev.max()) - lo_ts < _I32 and int(q_ts.max()) - lo_ts < _I32
    if use_kernel and span_ok:
        idx, valid = pit_ops.pit_search(
            jnp.asarray((table_ev - lo_ts).astype(np.int32)),
            jnp.asarray(np.maximum(q_ts - lo_ts, -1).astype(np.int32)),
            jnp.asarray(q_lo.astype(np.int32)),
            jnp.asarray(q_hi.astype(np.int32)),
        )
        idx, valid = np.asarray(idx), np.asarray(valid)
    else:
        if use_kernel and monitor is not None:
            monitor.record_kernel_fallback("pit_join")
        # int64 epoch-ms compares: under jax's default 32-bit mode
        # jnp.asarray would wrap timestamps past 2**31
        with jax.enable_x64(True):
            idx, valid = pit_ref.pit_search_ref(
                jnp.asarray(table_ev),
                jnp.asarray(q_ts),
                jnp.asarray(q_lo),
                jnp.asarray(q_hi),
            )
            idx, valid = np.asarray(idx), np.asarray(valid)
    # Queries whose ts0 - delay predates the rebase floor can match nothing.
    valid = valid & has_entity

    safe_idx = np.where(valid, idx, 0)
    values = {
        f.name: np.where(valid, h[f.name][safe_idx], 0).astype(np.float32)
        for f in spec.features
    }
    event_out = np.where(valid, table_ev[safe_idx], 0)
    return PitResult(values, valid, event_out)


def get_offline_features(
    store: OfflineStore,
    spine: Table,
    specs: Sequence[FeatureSetSpec],
    *,
    spine_ts_col: str = "ts",
    use_kernel: bool = True,
    monitor=None,
) -> Table:
    """Spine join across many feature sets (the training-data path).

    Output columns: spine columns + ``<fs>:v<n>:<feature>`` per feature +
    ``<fs>:v<n>:__found__`` validity flags (the §4.3 "no data vs. not
    materialized" distinction is surfaced by the caller via the scheduler's
    interval state; here absence of any past record reads as not-found).
    """
    out = dict(spine.to_dict())
    for spec in specs:
        history = store.read(spec.name, spec.version)
        res = pit_join_feature_set(
            [spine[c] for c in spec.index_columns],
            spine[spine_ts_col],
            spec,
            history,
            use_kernel=use_kernel,
            monitor=monitor,
        )
        prefix = f"{spec.name}:v{spec.version}"
        for fname, vals in res.values.items():
            out[f"{prefix}:{fname}"] = vals
        out[f"{prefix}:__found__"] = res.found
    return Table(out)
