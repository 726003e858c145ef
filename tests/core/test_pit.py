"""Point-in-time retrieval (paper §4.4): leakage freedom as a property."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.assets import Entity, Feature, FeatureSetSpec
from repro.core.dsl import UDFTransform
from repro.core.offline_store import OfflineStore
from repro.core.pit import get_offline_features, pit_join_feature_set
from repro.core.table import Table


def make_spec(delay=0):
    return FeatureSetSpec(
        name="fs",
        version=1,
        entity=Entity("cust", ("entity_id",)),
        features=(Feature("val"),),
        source_name="src",
        transform=UDFTransform(lambda df, ctx: df, name="id"),
        expected_delay=delay,
    )


def history_table(ids, ev, cr, vals):
    return Table(
        {
            "__key__": np.asarray(ids, np.int64),
            "entity_id": np.asarray(ids, np.int64),
            "event_ts": np.asarray(ev, np.int64),
            "creation_ts": np.asarray(cr, np.int64),
            "val": np.asarray(vals, np.float32),
        }
    )


records = st.lists(
    st.tuples(st.integers(0, 5), st.integers(0, 1000)),
    min_size=1,
    max_size=60,
)
queries = st.lists(
    st.tuples(st.integers(0, 7), st.integers(0, 1100)),
    min_size=1,
    max_size=40,
)


@pytest.mark.slow
@settings(max_examples=60, deadline=None)
@given(records, queries, st.sampled_from([0, 7, 50]), st.booleans())
def test_property_no_leakage_and_nearest_past(recs, qs, delay, use_kernel):
    """For every query: (a) the joined record's event_ts <= ts0 - delay —
    NEVER the future; (b) it is the NEAREST past (max event_ts among
    eligible); (c) found=False iff no eligible record exists."""
    spec = make_spec(delay)
    ids = [r[0] for r in recs]
    evs = [r[1] for r in recs]
    hist = history_table(ids, evs, [e + 1 for e in evs], evs)

    q_ids = np.asarray([q[0] for q in qs], np.int64)
    q_ts = np.asarray([q[1] for q in qs], np.int64)
    res = pit_join_feature_set([q_ids], q_ts, spec, hist, use_kernel=use_kernel)

    for i in range(len(qs)):
        eligible = [
            e for (k, e) in zip(ids, evs) if k == q_ids[i] and e <= q_ts[i] - delay
        ]
        if eligible:
            assert res.found[i]
            assert res.event_ts[i] == max(eligible)          # nearest past
            assert res.event_ts[i] <= q_ts[i] - delay        # no leakage
            assert res.values["val"][i] == float(max(eligible))
        else:
            assert not res.found[i]


def test_tie_break_prefers_latest_creation():
    """Same event_ts twice (re-materialized): the later creation wins,
    matching the §4.5 record ordering."""
    spec = make_spec()
    hist = history_table([1, 1], [100, 100], [200, 300], [1.0, 2.0])
    res = pit_join_feature_set(
        [np.array([1])], np.array([150]), spec, hist, use_kernel=False
    )
    assert res.found[0] and res.values["val"][0] == 2.0


def test_span_beyond_int32_falls_back_and_is_counted():
    """Timestamps the kernel's int32 domain cannot rebase take the oracle,
    answer the same, and the monitor counts the fallback; a span that fits
    is no fallback."""
    from repro.core.monitoring import HealthMonitor

    spec = make_spec()
    far = 2**33
    hist = history_table([1, 1, 2], [0, far, 5], [1, 1, 1], [1.0, 2.0, 3.0])
    monitor = HealthMonitor()
    res = pit_join_feature_set(
        [np.array([1, 1, 2])], np.array([10, far + 1, 4]), spec, hist,
        monitor=monitor,
    )
    np.testing.assert_array_equal(res.found, [True, True, False])
    np.testing.assert_array_equal(res.values["val"][:2], [1.0, 2.0])
    assert monitor.kernel_fallbacks() == {"pit_join": 1.0}
    near = history_table([1], [0], [1], [1.0])
    pit_join_feature_set(
        [np.array([1])], np.array([10]), spec, near, monitor=monitor
    )
    assert monitor.kernel_fallbacks() == {"pit_join": 1.0}


def test_multi_feature_set_spine_join():
    store = OfflineStore(num_shards=2)
    spec_a, spec_b = make_spec(), None
    import dataclasses

    spec_b = dataclasses.replace(make_spec(), name="fs_b")
    for spec, base in ((spec_a, 0.0), (spec_b, 100.0)):
        store.register(spec)
        store.merge(
            spec,
            Table(
                {
                    "entity_id": np.arange(4, dtype=np.int64),
                    "ts": np.full(4, 10, np.int64),
                    "val": np.arange(4, dtype=np.float32) + base,
                }
            ),
            creation_ts=50,
        )
    spine = Table(
        {
            "entity_id": np.arange(4, dtype=np.int64),
            "ts": np.full(4, 100, np.int64),
        }
    )
    out = get_offline_features(store, spine, [spec_a, spec_b], use_kernel=False)
    assert np.allclose(out["fs:v1:val"], [0, 1, 2, 3])
    assert np.allclose(out["fs_b:v1:val"], [100, 101, 102, 103])
    assert out["fs:v1:__found__"].all() and out["fs_b:v1:__found__"].all()


def test_kernel_vs_oracle_agree_large():
    rng = np.random.default_rng(3)
    n, q = 500, 300
    spec = make_spec(delay=5)
    ids = rng.integers(0, 40, n)
    evs = rng.integers(0, 100_000, n)
    hist = history_table(ids, evs, evs + 1, evs.astype(np.float32))
    q_ids = rng.integers(0, 45, q).astype(np.int64)
    q_ts = rng.integers(0, 110_000, q).astype(np.int64)
    a = pit_join_feature_set([q_ids], q_ts, spec, hist, use_kernel=True)
    b = pit_join_feature_set([q_ids], q_ts, spec, hist, use_kernel=False)
    assert np.array_equal(a.found, b.found)
    assert np.array_equal(a.event_ts, b.event_ts)
    assert np.allclose(a.values["val"], b.values["val"])
