"""Pure-numpy oracle for ``ops.merge_at_slots``.

Works on the un-split int64 view of the table (event/creation ts as int64),
so the device program's two-plane arithmetic is checked against ordinary
integer comparisons.  The batch arrives at resolved (part, slot)
coordinates, one record per coordinate, with one creation_ts per batch.
"""

from __future__ import annotations

import numpy as np

__all__ = ["merge_at_slots_ref"]


def merge_at_slots_ref(
    event_ts: np.ndarray,     # (P, C) int64
    creation_ts: np.ndarray,  # (P, C) int64
    values: np.ndarray,       # (P, C, D) f32
    part: np.ndarray,         # (G,) int
    slot: np.ndarray,         # (G,) int, out of bounds = padding
    is_new: np.ndarray,       # (G,) bool
    q_ev: np.ndarray,         # (G,) int64
    q_values: np.ndarray,     # (G, D) f32
    batch_creation_ts: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Returns updated (event_ts, creation_ts, values); inputs untouched."""
    ev = event_ts.copy()
    cr = creation_ts.copy()
    vals = values.copy()
    p_n, c_n = ev.shape
    for p, s, new, e, row in zip(part, slot, is_new, q_ev, q_values):
        if not (0 <= p < p_n and 0 <= s < c_n):
            continue
        if new or (int(e), batch_creation_ts) > (int(ev[p, s]), int(cr[p, s])):
            ev[p, s] = e
            cr[p, s] = batch_creation_ts
            vals[p, s] = row
    return ev, cr, vals
