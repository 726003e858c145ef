"""The DSL's windows over rows that tie on (entity, timestamp).

A row's window is every row of its entity with ``ts - window < ts_j <= ts``.
Rows that share the entity and the timestamp (a ticket's line items) are all
in each other's windows, whatever order the sort leaves them in.  Every
aggregate, on both backends, is checked against a plain NumPy loop.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dsl import SUPPORTED_AGGS, DslTransform, RollingAgg
from repro.core.table import Table

WINDOWS = (250, 1000)  # ms; tickets every 50 ms


def _reference(ent, ts, val, window, agg):
    """Per row: ``agg`` over the rows of its entity in (ts - window, ts]."""
    out = np.empty(len(ts), np.float64)
    for i in range(len(ts)):
        m = (ent == ent[i]) & (ts > ts[i] - window) & (ts <= ts[i])
        v = val[m].astype(np.float64)
        out[i] = {"sum": v.sum(), "mean": v.mean(), "count": m.sum(),
                  "min": v.min(), "max": v.max()}[agg]
    return out


def _tickets():
    """Entity 0: 110 tickets of 8-16 items, one every 50 ms, so that a ticket
    straddles sorted rows 255/256 (the kernel's row block) and 1023/1024 (the
    XLA scan's block), and a ticket 250 ms back sits on the short window's
    edge.  Entities 1 and 2: a few tickets each around the same times.
    Rows arrive shuffled; values differ within each ticket."""
    rng = np.random.default_rng(5)
    ent, ts = [], []
    for k in range(110):
        size = 8 + k % 9
        ent += [0] * size
        ts += [k * 50] * size
    for e, times in ((1, (100, 100, 150, 400, 1100)), (2, (0, 950, 1000))):
        for t in times:
            size = int(rng.integers(8, 17))
            ent += [e] * size
            ts += [t] * size
    ent, ts = np.array(ent, np.int64), np.array(ts, np.int64)
    # in (entity, ts) order a tie crosses each block boundary
    order = np.lexsort((ts, ent))
    for b in (256, 1024):
        assert (ent[order][b - 1], ts[order][b - 1]) == (ent[order][b], ts[order][b])
    val = rng.normal(100.0, 30.0, len(ts)).astype(np.float32)
    perm = rng.permutation(len(ts))
    return ent[perm], ts[perm], val[perm]


def _run(ent, ts, val, agg, use_kernel):
    aggs = [RollingAgg(f"{agg}_{w}", "v", w, agg) for w in WINDOWS]
    dsl = DslTransform("e", "ts", aggs, use_kernel=use_kernel)
    return dsl(Table({"e": ent, "ts": ts, "v": val}), {})


@pytest.mark.parametrize("use_kernel", [True, False], ids=["pallas", "xla"])
@pytest.mark.parametrize("agg", SUPPORTED_AGGS)
def test_tied_rows_share_one_window(agg, use_kernel):
    ent, ts, val = _tickets()
    out = _run(ent, ts, val, agg, use_kernel)
    for w in WINDOWS:
        want = _reference(out["e"], out["ts"], _values_of(out, ent, ts, val), w, agg)
        np.testing.assert_allclose(out[f"{agg}_{w}"], want, rtol=2e-6, atol=1e-3)


def _values_of(out, ent, ts, val):
    """The source values, in the order of the DSL's output rows: the output
    is the source sorted by (entity, ts), stably."""
    return val[np.lexsort((ts, ent))]


@settings(max_examples=25, deadline=None)
@given(
    groups=st.lists(
        st.tuples(st.integers(0, 3), st.integers(0, 40), st.integers(1, 6)),
        min_size=1, max_size=40,
    ),
    window=st.integers(1, 25),
    agg=st.sampled_from(SUPPORTED_AGGS),
    use_kernel=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_random_tie_groups_match_reference(groups, window, agg, use_kernel, seed):
    """Random tie groups (entity, ts, size); groups may repeat a key."""
    ent = np.repeat([g[0] for g in groups], [g[2] for g in groups]).astype(np.int64)
    ts = np.repeat([g[1] for g in groups], [g[2] for g in groups]).astype(np.int64)
    rng = np.random.default_rng(seed)
    val = rng.normal(0.0, 10.0, len(ts)).astype(np.float32)
    perm = rng.permutation(len(ts))
    ent, ts, val = ent[perm], ts[perm], val[perm]
    dsl = DslTransform("e", "ts", [RollingAgg("f", "v", window, agg)],
                       use_kernel=use_kernel)
    out = dsl(Table({"e": ent, "ts": ts, "v": val}), {})
    want = _reference(out["e"], out["ts"], _values_of(out, ent, ts, val), window, agg)
    np.testing.assert_allclose(out["f"], want, rtol=1e-5, atol=1e-3)
