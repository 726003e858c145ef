"""rolling_agg kernel vs pure-jnp oracle: shape/dtype sweeps + properties.

Pallas runs in interpret mode here: the mode follows the backend (CPU here;
the kernels compile on a TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.monitoring import HealthMonitor
from repro.kernels.rolling_agg import ref as R
from repro.kernels.rolling_agg.ops import (
    max_hist,
    rolling_agg,
    rolling_sum,
    rolling_sum_xla,
    row_bucket,
    window_starts,
)


def _random_case(rng, n, feat, n_seg, window, dtype=np.float32):
    seg = np.sort(rng.integers(0, n_seg, size=n))
    ts_jitter = np.sort(rng.integers(0, 50, size=n))
    # per-segment sorted timestamps
    ts = np.empty(n, np.int64)
    for s in np.unique(seg):
        m = seg == s
        ts[m] = np.sort(rng.integers(0, 1000, size=m.sum()))
    vals = rng.standard_normal((n, feat)).astype(dtype)
    starts = window_starts(seg, ts, window)
    return vals, starts, seg, ts


# ---------------------------------------------------------------------------
# window_starts (host-side span computation)
# ---------------------------------------------------------------------------
def test_window_starts_matches_bruteforce():
    rng = np.random.default_rng(0)
    for _ in range(10):
        n = int(rng.integers(1, 200))
        _, starts, seg, ts = _random_case(rng, n, 1, 5, int(rng.integers(1, 100)))
        window = None
    # recompute explicitly with a fixed window
    n = 150
    window = 30
    vals, starts, seg, ts = _random_case(np.random.default_rng(1), n, 1, 4, window)
    for i in range(n):
        in_win = [
            j
            for j in range(i + 1)
            if seg[j] == seg[i] and ts[i] - window < ts[j] <= ts[i]
        ]
        assert starts[i] == min(in_win), (i, starts[i], min(in_win))


def test_window_starts_rejects_unsorted():
    with pytest.raises(ValueError):
        window_starts(np.array([1, 0]), np.array([0, 0]), 10)


# ---------------------------------------------------------------------------
# kernel vs oracle: sweeps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 7, 255, 256, 257, 1024])
@pytest.mark.parametrize("feat", [1, 3, 128, 130])
def test_rolling_sum_shapes(n, feat):
    rng = np.random.default_rng(n * 1000 + feat)
    vals, starts, _, _ = _random_case(rng, n, feat, 3, 40)
    got = rolling_sum(jnp.asarray(vals), jnp.asarray(starts), hist=256)
    want = R.rolling_sum_ref(jnp.asarray(vals), jnp.asarray(starts))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32])
def test_rolling_sum_dtypes(dtype):
    rng = np.random.default_rng(42)
    n, feat = 300, 5
    vals, starts, _, _ = _random_case(rng, n, feat, 4, 25)
    if np.issubdtype(dtype, np.integer):
        vals = (vals * 10).astype(dtype)
    else:
        vals = vals.astype(dtype)
    got = rolling_sum(jnp.asarray(vals), jnp.asarray(starts), hist=128)
    want = R.rolling_sum_ref(jnp.asarray(vals), jnp.asarray(starts))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("agg", ["sum", "mean", "count", "min", "max"])
def test_rolling_agg_all_aggs(agg):
    rng = np.random.default_rng(7)
    vals, starts, _, _ = _random_case(rng, 200, 4, 3, 60)
    got = rolling_agg(jnp.asarray(vals), starts, agg)
    want = R.rolling_agg_ref(jnp.asarray(vals), jnp.asarray(starts), agg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("block_rows,hist", [(64, 64), (64, 256), (256, 64), (128, 512)])
def test_rolling_sum_block_hist_sweep(block_rows, hist):
    """Spans bounded by hist; every (block, hist) tiling must agree."""
    rng = np.random.default_rng(block_rows + hist)
    n = 500
    vals = rng.standard_normal((n, 130)).astype(np.float32)
    max_span = hist
    starts = np.maximum(0, np.arange(n) - rng.integers(0, max_span, size=n)).astype(
        np.int32
    )
    got = rolling_sum(
        jnp.asarray(vals), jnp.asarray(starts), block_rows=block_rows, hist=hist
    )
    want = R.rolling_sum_ref(jnp.asarray(vals), jnp.asarray(starts))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_rolling_agg_deep_span_falls_back():
    """Spans deeper than the VMEM history bucket use the XLA path but stay
    correct, and the monitor counts the fallback."""
    n = max_hist(2) + 1
    vals = np.ones((n, 2), np.float32)
    starts = np.zeros(n, np.int32)  # every window reaches row 0: span = n
    monitor = HealthMonitor()
    got = rolling_agg(jnp.asarray(vals), starts, "sum", monitor=monitor)
    want = (np.arange(n) + 1).astype(np.float32)
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-6)
    assert monitor.kernel_fallbacks() == {"rolling_agg": 1.0}
    # a span the kernel holds is no fallback
    rolling_agg(jnp.asarray(vals[:600]), starts[:600], "sum", monitor=monitor)
    assert monitor.kernel_fallbacks() == {"rolling_agg": 1.0}


def test_rolling_sum_xla_long_column_is_exact():
    """Short windows deep in a long, large-valued column: a float32 prefix
    would be off by several units here; the blocked double-float prefix
    stays within float32 rounding of each window's own sum, also for
    windows that straddle a scan block."""
    rng = np.random.default_rng(3)
    n = 3 * 1024 + 5
    vals = (1e4 + rng.gamma(2.0, 50.0, (n, 2))).astype(np.float32)
    starts = np.maximum(0, np.arange(n) - rng.integers(0, 50, n)).astype(np.int32)
    got = np.asarray(rolling_sum_xla(jnp.asarray(vals), jnp.asarray(starts)))
    prefix = np.concatenate([np.zeros((1, 2)), np.cumsum(vals, 0, np.float64)])
    want = prefix[1:] - prefix[starts]
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("n", [1, 16_384, 16_385, 32_768, 32_769, 100_000, 1_110_612])
def test_row_bucket_pads_under_an_eighth(n):
    b = row_bucket(n)
    assert b >= max(n, 16_384) and b % 128 == 0
    if n > 16_384:
        assert (b - n) / n < 0.125


def test_rolling_agg_lowers_nothing_new_within_a_row_bucket():
    """Calls whose row counts differ but share a bucket run the programs
    the first call compiled: no jaxpr is lowered again."""
    lowered = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)

    rng = np.random.default_rng(11)
    cases = []
    for n in (2100, 9000, 16_000):  # all in the smallest bucket
        vals, starts, _, _ = _random_case(rng, n, 2, 40, 60)
        cases.append((vals, starts))
    aggs = ("sum", "mean", "max", "min")
    for agg in aggs:
        for backend in ("pallas", "xla"):
            rolling_agg(cases[0][0], cases[0][1], agg, backend=backend)
    want = {(i, agg): R.rolling_agg_ref(jnp.asarray(v), jnp.asarray(st), agg)
            for i, (v, st) in enumerate(cases) for agg in aggs}
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        got = {(i, agg, backend): rolling_agg(v, st, agg, backend=backend)
               for i, (v, st) in enumerate(cases) if i
               for agg in aggs for backend in ("pallas", "xla")}
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert lowered == []
    for (i, agg, _), out in got.items():
        np.testing.assert_allclose(out, want[i, agg], rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# properties
# ---------------------------------------------------------------------------
@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 300),
    feat=st.integers(1, 9),
    window=st.integers(1, 80),
    seed=st.integers(0, 2**31 - 1),
)
def test_rolling_sum_property(n, feat, window, seed):
    rng = np.random.default_rng(seed)
    vals, starts, _, _ = _random_case(rng, n, feat, 4, window)
    got = rolling_sum(jnp.asarray(vals), jnp.asarray(starts), hist=256)
    want = R.rolling_sum_ref(jnp.asarray(vals), jnp.asarray(starts))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_rolling_mean_bounded_by_extremes(seed):
    """mean(window) must lie within [min(window), max(window)]."""
    rng = np.random.default_rng(seed)
    vals, starts, _, _ = _random_case(rng, 128, 3, 3, 30)
    mean = np.asarray(rolling_agg(jnp.asarray(vals), starts, "mean"))
    lo = np.asarray(rolling_agg(jnp.asarray(vals), starts, "min"))
    hi = np.asarray(rolling_agg(jnp.asarray(vals), starts, "max"))
    assert (mean >= lo - 1e-4).all() and (mean <= hi + 1e-4).all()


def test_window_never_crosses_entity_boundary():
    """Rows of entity A must never contribute to entity B's windows."""
    seg = np.array([0] * 50 + [1] * 50)
    ts = np.concatenate([np.arange(50), np.arange(50)]).astype(np.int64)
    vals = np.where(seg[:, None] == 0, 1000.0, 1.0).astype(np.float32)
    starts = window_starts(seg, ts, window=100)
    out = np.asarray(rolling_agg(jnp.asarray(vals), starts, "sum"))
    # entity 1 rows: sums of ones only
    assert (out[50:, 0] <= 50.0).all()
    assert out[50, 0] == 1.0
