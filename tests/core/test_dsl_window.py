"""The DSL given the feature window Algorithm 1 keeps: it aggregates only
the rows of the entities with a row in the window, and returns the window's
rows with the features the whole lookback gives them."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dsl import SUPPORTED_AGGS, DslTransform, RollingAgg
from repro.core.table import Table
from repro.core.transform import FeatureWindow
from repro.kernels.rolling_agg import ops as rolling_ops


def _frame(rng, n, entities, horizon):
    """Two entity columns, tied timestamps, integer-valued values (so every
    float32 sum is exact whatever rows share a program's blocks)."""
    return Table({
        "a": rng.integers(0, entities, n).astype(np.int64),
        "b": rng.integers(0, 2, n).astype(np.int64),
        "ts": rng.integers(0, horizon, n).astype(np.int64) // 5 * 5,
        "v": rng.integers(-50, 50, n).astype(np.float32),
    })


def _dsl(use_kernel=True):
    aggs = [RollingAgg(f"{agg}_{w}", "v", w, agg)
            for agg in SUPPORTED_AGGS for w in (40, 300)]
    return DslTransform(("a", "b"), "ts", aggs, use_kernel=use_kernel)


def _clip(table, window):
    return table.filter_time_range("ts", window.start, window.end)


@pytest.mark.parametrize("use_kernel", [True, False], ids=["pallas", "xla"])
def test_window_rows_have_the_whole_lookbacks_features(use_kernel):
    rng = np.random.default_rng(3)
    df = _frame(rng, 3000, 60, 2000)
    dsl = _dsl(use_kernel)
    window = FeatureWindow(1500, 1700)
    got = dsl(df, {"feature_window": window})
    want = _clip(dsl(df, {}), window)
    assert len(got) == len(want) > 0
    assert ((got["ts"] >= 1500) & (got["ts"] < 1700)).all()
    for c in want.columns:
        np.testing.assert_array_equal(got[c], want[c], err_msg=c)


def test_only_the_windows_entities_reach_the_rolling_programs(monkeypatch):
    rng = np.random.default_rng(4)
    df = _frame(rng, 2000, 400, 2000)
    window = FeatureWindow(1900, 1950)
    ts = df["ts"]
    inside = {(a, b) for a, b, t in zip(df["a"], df["b"], ts) if 1900 <= t < 1950}
    kept = sum((a, b) in inside for a, b in zip(df["a"], df["b"]))
    assert 0 < kept < len(df) // 4
    rows = []
    real = rolling_ops.rolling_agg

    def counting(values, starts, agg, **kw):
        rows.append(len(starts))
        return real(values, starts, agg, **kw)

    monkeypatch.setattr(rolling_ops, "rolling_agg", counting)
    _dsl()(df, {"feature_window": window})
    assert rows and set(rows) == {kept}


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(1, 400),
    entities=st.integers(1, 30),
    start=st.integers(0, 600),
    length=st.integers(1, 300),
)
def test_random_windows_match_the_whole_pass(seed, n, entities, start, length):
    rng = np.random.default_rng(seed)
    df = _frame(rng, n, entities, 800)
    dsl = _dsl(use_kernel=False)
    window = FeatureWindow(start, start + length)
    got = dsl(df, {"feature_window": window})
    want = _clip(dsl(df, {}), window)
    assert len(got) == len(want)
    for c in want.columns:
        np.testing.assert_array_equal(got[c], want[c], err_msg=c)
