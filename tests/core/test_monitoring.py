"""§2.1/§3.1.2 health monitoring: metrics, alerts, staleness SLA — and the
bounded-histogram sketch the serving front's per-stage latencies ride on."""

import math

import numpy as np
import pytest

from repro.core.monitoring import BoundedHistogram, HealthMonitor, Metrics

# BoundedHistogram guarantees relative accuracy ~``resolution`` (5% default);
# the assertions below allow a little slack over one bucket width
RTOL = 0.06


def test_counters_gauges_histograms():
    m = Metrics()
    m.inc("jobs")
    m.inc("jobs", 2)
    m.set_gauge("depth", 7)
    for v in range(100):
        m.observe("lat", float(v))
    snap = m.snapshot()
    assert snap["counters"]["jobs"] == 3
    assert snap["gauges"]["depth"] == 7
    # histogram quantiles are sketched (bounded memory), not exact
    assert snap["histograms"]["lat"]["p50"] == pytest.approx(50.0, rel=RTOL)
    assert snap["histograms"]["lat"]["max"] == 99.0
    assert snap["histograms"]["lat"]["n"] == 100


# -- BoundedHistogram: quantile accuracy vs numpy on known distributions ------


def _assert_quantiles_close(h: BoundedHistogram, samples: np.ndarray) -> None:
    for q in (0.10, 0.50, 0.90, 0.99, 0.999):
        exact = float(np.quantile(samples, q, method="inverted_cdf"))
        got = h.quantile(q)
        assert got == pytest.approx(exact, rel=RTOL), (q, got, exact)


def test_bounded_histogram_uniform_vs_numpy():
    rng = np.random.default_rng(7)
    samples = rng.uniform(1.0, 1e4, 50_000)
    h = BoundedHistogram()
    for v in samples:
        h.observe(v)
    _assert_quantiles_close(h, samples)
    assert h.n == len(samples)
    assert h.mean == pytest.approx(samples.mean(), rel=1e-9)
    assert h.vmin == samples.min() and h.vmax == samples.max()


def test_bounded_histogram_lognormal_vs_numpy():
    # heavy tail over ~6 decades — the realistic latency shape
    rng = np.random.default_rng(11)
    samples = np.exp(rng.normal(3.0, 2.0, 50_000))
    h = BoundedHistogram()
    h.observe_batch(samples)  # vectorized path must match scalar indexing
    _assert_quantiles_close(h, samples)


def test_bounded_histogram_batch_matches_scalar():
    rng = np.random.default_rng(3)
    samples = rng.exponential(250.0, 10_000) + 0.5
    a, b = BoundedHistogram(), BoundedHistogram()
    for v in samples:
        a.observe(v)
    b.observe_batch(samples)
    np.testing.assert_array_equal(a.counts, b.counts)
    assert a.n == b.n and a.vmin == b.vmin and a.vmax == b.vmax
    assert a.total == pytest.approx(b.total, rel=1e-9)


def test_bounded_histogram_memory_is_bounded():
    h = BoundedHistogram()
    nbuckets = len(h.counts)
    h.observe_batch(np.random.default_rng(0).uniform(0.1, 1e6, 200_000))
    assert len(h.counts) == nbuckets  # storage never grows with samples


def test_bounded_histogram_edges():
    h = BoundedHistogram(lo=1.0, hi=1e3)
    assert math.isnan(h.quantile(0.5))  # empty
    h.observe(0.0)  # below lo clamps into the first bucket
    h.observe(1e9)  # above hi clamps into the last
    assert h.quantile(0.0) == 0.0  # reported values clamp to observed range
    # an above-hi outlier lands in the overflow bucket: reported near hi,
    # never beyond the observed max (accuracy only guaranteed inside [lo, hi))
    assert h.quantile(1.0) == pytest.approx(1e3, rel=RTOL)
    assert h.quantile(1.0) <= h.vmax
    single = BoundedHistogram()
    single.observe(42.0)
    for q in (0.01, 0.5, 0.999):
        assert single.quantile(q) == 42.0


def test_alert_hook_fires():
    got = []
    hm = HealthMonitor(alert_hook=got.append)
    hm.alert("region down")
    assert got == ["region down"] and hm.alerts == ["region down"]


def test_health_judgement():
    hm = HealthMonitor()
    for _ in range(99):
        hm.record_job(success=True)
    assert hm.healthy()
    hm2 = HealthMonitor()
    for _ in range(5):
        hm2.record_job(success=False)
    assert not hm2.healthy()
    # retries are counted separately (visibility into §4.5.4 convergence)
    hm3 = HealthMonitor()
    hm3.record_job(success=False, retried=True)
    assert hm3.system.counters["jobs_retried"] == 1


def test_staleness_gauge_per_feature_set():
    hm = HealthMonitor()
    hm.record_staleness("act", 1, 120_000)
    hm.record_staleness("act", 2, None)  # unknown: no gauge
    snap = hm.system.snapshot()
    assert snap["gauges"]["staleness_ms/act:v1"] == 120_000
    assert "staleness_ms/act:v2" not in snap["gauges"]


def test_staleness_reflects_schedule_lag():
    """End-to-end: staleness == now - materialized high-water mark."""
    from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
    from repro.core.dsl import DslTransform, RollingAgg
    from repro.core.featurestore import FeatureStore
    from repro.data.sources import SyntheticEventSource

    HOUR = 3_600_000
    fs = FeatureStore("stale")
    fs.register_source(SyntheticEventSource("tx", num_entities=4,
                                            events_per_bucket=10))
    fs.create_feature_set(FeatureSetSpec(
        name="act", version=1,
        entity=Entity("customer", ("entity_id",)),
        features=(Feature("s1", "float32"),),
        source_name="tx",
        transform=DslTransform("entity_id", "ts",
                               [RollingAgg("s1", "amount", HOUR, "sum")]),
        timestamp_col="ts", source_lookback=HOUR,
        materialization=MaterializationSettings(
            offline_enabled=True, online_enabled=False,
            schedule_interval=HOUR,
        ),
    ))
    fs.tick(now=3 * HOUR)
    # clock at 3h30 without a new tick-able hour: staleness = 30min... the
    # cadence materializes up to 3h, so at now=3h staleness is 0
    snap = fs.monitor.system.snapshot()
    assert snap["gauges"]["staleness_ms/act:v1"] == 0
    fs.advance_clock(3 * HOUR + 30 * 60_000)
    fs.tick()
    snap = fs.monitor.system.snapshot()
    assert snap["gauges"]["staleness_ms/act:v1"] == 30 * 60_000
