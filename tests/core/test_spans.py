"""The in-program span recorder (``monitoring.SPANS``): it records only
inside a JAX profiler session, keeps parents, roots and self time, bounds
its buffer, and starts afresh with each session; and the spans the serving
front, the online store, the materializer and the offline store put at
their layer boundaries."""

import contextlib
import itertools
import time

import jax
import numpy as np
import pytest

from repro.core import monitoring
from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import DslTransform, RollingAgg
from repro.core.featurestore import FeatureStore
from repro.core.monitoring import SPANS, HealthMonitor, SpanRecorder
from repro.core.online_store import OnlineStore
from repro.core.serving import ServingConfig, ServingFront
from repro.core.table import Table
from repro.data.sources import SyntheticEventSource
from tests.core.test_serving import make_frame, make_spec

HOUR = 3_600_000


@contextlib.contextmanager
def profiled(tmp_path, tag="s"):
    """A CPU profiler session, as an operator or the benchmark opens one."""
    with jax.profiler.trace(str(tmp_path / tag)):
        yield


def test_records_inside_a_session_and_none_outside(tmp_path):
    rec = SpanRecorder()
    with rec.span("fs.before") as sp:
        assert not sp
    with profiled(tmp_path):
        with rec.span("fs.inside", n=3) as sp:
            assert sp
            sp.set(m=4)
    with rec.span("fs.after"):
        pass
    (s,) = rec.spans()
    assert s.name == "fs.inside"
    assert s.attrs == {"n": 3, "m": 4}
    assert s.parent is None and s.root == s.id
    assert s.end_ns >= s.start_ns
    assert rec.summary()["fs.inside"]["count"] == 1


def test_parent_root_and_self_time(tmp_path):
    rec = SpanRecorder()
    with profiled(tmp_path):
        for _ in range(2):
            with rec.span("fs.outer"):
                with rec.span("fs.mid"):
                    with rec.span("fs.leaf"):
                        sum(range(20_000))
                with rec.span("fs.leaf"):
                    sum(range(20_000))
    spans = rec.spans()
    assert len(spans) == 8
    by_id = {s.id: s for s in spans}
    outers = rec.spans("fs.outer")
    assert [s.parent for s in outers] == [None, None]
    assert outers[0].root != outers[1].root
    for s in spans:
        if s.name != "fs.outer":
            parent = by_id[s.parent]
            assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
            assert s.root == parent.root
    mids = rec.spans("fs.mid")
    assert all(by_id[m.parent].name == "fs.outer" for m in mids)
    leaves = rec.spans("fs.leaf")
    assert sorted(by_id[x.parent].name for x in leaves) == [
        "fs.mid", "fs.mid", "fs.outer", "fs.outer"
    ]

    def ns(s):
        return s.end_ns - s.start_ns

    summary = rec.summary()
    children = {s.id: 0 for s in spans}
    for s in spans:
        if s.parent is not None:
            children[s.parent] += ns(s)
    for name in ("fs.outer", "fs.mid", "fs.leaf"):
        mine = rec.spans(name)
        assert summary[name]["count"] == len(mine)
        assert summary[name]["total_ms"] == pytest.approx(
            sum(ns(s) for s in mine) / 1e6
        )
        assert summary[name]["self_ms"] == pytest.approx(
            sum(ns(s) - children[s.id] for s in mine) / 1e6
        )
    # a leaf's self time is its whole time; an outer span's excludes both
    # its children
    assert summary["fs.leaf"]["self_ms"] == pytest.approx(
        summary["fs.leaf"]["total_ms"]
    )
    assert summary["fs.outer"]["self_ms"] < summary["fs.outer"]["total_ms"]


def test_buffer_cap_counts_drops(tmp_path):
    rec = SpanRecorder(capacity=3)
    with profiled(tmp_path):
        for i in range(5):
            with rec.span("fs.x", i=i):
                pass
    assert [s.attrs["i"] for s in rec.spans()] == [0, 1, 2]
    assert rec.dropped == 2


def test_new_session_starts_a_fresh_buffer(tmp_path):
    rec = SpanRecorder(capacity=2)
    with profiled(tmp_path, "first"):
        for _ in range(3):
            with rec.span("fs.first"):
                pass
    assert len(rec.spans()) == 2 and rec.dropped == 1
    # the buffer outlives its session, for the readers after it
    assert [s.name for s in rec.spans()] == ["fs.first", "fs.first"]
    with profiled(tmp_path, "second"):
        with rec.span("fs.second"):
            pass
    assert [s.name for s in rec.spans()] == ["fs.second"]
    assert rec.dropped == 0


# -- the program's spans ------------------------------------------------------


def _kernel_store():
    spec = make_spec()
    store = OnlineStore(num_partitions=4, merge_engine="kernel")
    rng = np.random.default_rng(0)
    store.merge(spec, make_frame(rng, 80, 40, 50), 1_000)
    return store, spec, rng


def test_front_dispatch_spans_match_counters(tmp_path):
    store, _, _ = _kernel_store()
    mon = HealthMonitor()
    ticks = itertools.count()
    front = ServingFront(
        store,
        config=ServingConfig(cache_capacity=16),
        monitor=mon,
        request_clock=lambda: float(next(ticks)),
    )
    front.get("fs", 1, ids=np.arange(4, dtype=np.int64), now=1_100)  # cached
    before = dict(front.counters)
    mon.system.histograms.pop("serving/queue_wait_us", None)
    with profiled(tmp_path):
        for ids in ([1, 2, 30], [2, 31, 32, 33], [3, 39, 50]):
            front.submit("fs", 1, ids=np.asarray(ids, np.int64), now=1_100)
        assert front.flush("fs", 1, now=1_100) == 1
    delta = {k: front.counters[k] - before.get(k, 0) for k in front.counters}

    (dispatch,) = SPANS.spans("fs.serving.dispatch")
    (lookup,) = SPANS.spans("fs.store.lookup")
    (scan,) = SPANS.spans("fs.store.lookup.scan")
    (gather,) = SPANS.spans("fs.store.lookup.gather")
    assert dispatch.parent is None
    assert lookup.parent == dispatch.id
    assert scan.parent == lookup.id and gather.parent == lookup.id
    assert {s.root for s in (dispatch, lookup, scan, gather)} == {dispatch.id}
    assert scan.end_ns <= gather.start_ns

    a = dispatch.attrs
    assert a["tickets"] == 3
    assert a["keys"] == delta["coalesced_keys"]
    assert a["unique"] == delta["unique_keys"]
    assert a["store_keys"] == delta["store_keys"] == lookup.attrs["keys"]
    assert delta["dispatches"] == 1
    assert lookup.attrs["rows"] >= lookup.attrs["keys"]
    hist = mon.system.histograms["serving/queue_wait_us"]
    assert hist.n == a["tickets"]
    assert a["wait_ms_sum"] / a["tickets"] == pytest.approx(hist.mean / 1e3)


def test_host_engine_lookup_has_no_kernel_children(tmp_path):
    store, _, _ = _kernel_store()
    with profiled(tmp_path):
        store.lookup_encoded("fs", 1, np.arange(5, dtype=np.int64), use_kernel=False)
    (lookup,) = SPANS.spans("fs.store.lookup")
    assert lookup.attrs == {"keys": 5, "rows": 5}
    assert not SPANS.spans("fs.store.lookup.scan")
    assert not SPANS.spans("fs.store.lookup.gather")


def test_kernel_lookup_scan_records_index_merged(tmp_path):
    """``fs.store.lookup.scan`` says how many inserted keys the GET first
    merged into the device key index: the inserts of the merge before it,
    then none."""
    store, spec, rng = _kernel_store()
    store.lookup_encoded("fs", 1, np.arange(4, dtype=np.int64))
    frame = make_frame(rng, 10, 10, 50)
    cols = {c: frame[c] for c in ("ts", "f0", "f1")}
    cols["entity_id"] = np.arange(100, 110, dtype=np.int64)
    store.merge(spec, Table(cols), 2_000)
    with profiled(tmp_path):
        for _ in range(2):
            store.lookup_encoded("fs", 1, np.arange(95, 105, dtype=np.int64))
    assert [s.attrs for s in SPANS.spans("fs.store.lookup.scan")] == [
        {"index_merged": 10}, {"index_merged": 0},
    ]


def test_kernel_merge_on_stale_mirror_records_resolve(tmp_path):
    store, spec, rng = _kernel_store()
    assert store._tables[spec.key].host_stale  # the first kernel merge
    seen = []
    store.merge_listeners.append(
        lambda spec, stats: seen.append((stats, time.perf_counter_ns()))
    )
    with profiled(tmp_path):
        stats = store.merge(spec, make_frame(rng, 60, 50, 80), 1_050)
    (merge,) = SPANS.spans("fs.store.merge")
    (resolve,) = SPANS.spans("fs.store.merge.resolve")
    assert resolve.parent == merge.id and resolve.root == merge.id
    assert resolve.attrs == {"stale": True}
    assert merge.attrs == {
        "rows": 60,
        "inserts": stats.inserts,
        "overrides": stats.overrides,
        "noops": stats.noops,
        "pad_cols": 0,
    }
    # the listeners ran inside the span
    ((heard, at),) = seen
    assert heard is stats and merge.start_ns <= at <= merge.end_ns


def test_off_path_records_nothing_and_touches_only_is_enabled(monkeypatch):
    class Off:
        calls = 0

        @classmethod
        def is_enabled(cls):
            cls.calls += 1
            return False

        def __init__(self, *a, **k):
            raise AssertionError("TraceAnnotation opened with tracing off")

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read with tracing off")

    class NoSession:
        def __getattr__(self, name):
            raise AssertionError("profiler session looked up with tracing off")

    store, spec, rng = _kernel_store()
    front = ServingFront(store, config=ServingConfig(cache_capacity=8))
    fs = _materializing_store()
    kept, dropped = SPANS.spans(), SPANS.dropped
    monkeypatch.setattr(monitoring, "TraceAnnotation", Off)
    monkeypatch.setattr(monitoring, "time", NoClock())
    monkeypatch.setattr(monitoring, "_PROFILE_STATE", NoSession())
    front.get("fs", 1, ids=np.arange(6, dtype=np.int64), now=1_100)
    store.merge(spec, make_frame(rng, 10, 50, 90), 1_060)
    store.merge_reduced(
        spec, np.arange(3, dtype=np.int64), np.full(3, 95, np.int64),
        np.zeros((3, 2), np.float32), 1_070,
    )
    calls = Off.calls
    assert fs.tick(2 * HOUR)["succeeded"] == 2
    assert Off.calls - calls == 2 * 6  # job, read, transform, both merges, resolve
    assert SPANS.span("fs.x") is SPANS.span("fs.y")
    assert Off.calls >= 6  # dispatch, lookup, scan, gather, merges, resolves
    assert SPANS.spans() == kept and SPANS.dropped == dropped


def _materializing_store():
    """A kernel-engine store with one DSL feature set over two window
    lengths, written to both stores every hour."""
    fs = FeatureStore("spans", online_partitions=4, merge_engine="kernel")
    fs.register_source(SyntheticEventSource("tx", num_entities=20,
                                            events_per_bucket=60))
    aggs = [RollingAgg("s1", "amount", HOUR, "sum"),
            RollingAgg("m3", "quantity", 3 * HOUR, "max")]
    fs.create_feature_set(FeatureSetSpec(
        name="act", version=1, entity=Entity("customer", ("entity_id",)),
        features=(Feature("s1"), Feature("m3")), source_name="tx",
        transform=DslTransform("entity_id", "ts", aggs),
        timestamp_col="ts", source_lookback=3 * HOUR,
        materialization=MaterializationSettings(
            offline_enabled=True, online_enabled=True, schedule_interval=HOUR
        ),
    ))
    return fs


def test_materialize_spans_once_per_job(tmp_path):
    fs = _materializing_store()
    kept = SPANS.spans()
    assert fs.tick(4 * HOUR)["succeeded"] == 4  # outside a session
    assert SPANS.spans() == kept
    with profiled(tmp_path):
        assert fs.tick(6 * HOUR)["succeeded"] == 2
    jobs = SPANS.spans("fs.materialize.job")
    assert [j.attrs["job_id"] for j in jobs] == [5, 6]
    by_id = {s.id: s for s in SPANS.spans()}
    for name in ("fs.materialize.read", "fs.materialize.transform",
                 "fs.offline.merge", "fs.store.merge"):
        found = SPANS.spans(name)
        assert [by_id[s.parent].name for s in found] == ["fs.materialize.job"] * 2
        assert [s.root for s in found] == [j.id for j in jobs]
    outcomes = fs.materializer.outcomes[-2:]
    for job, out in zip(jobs, outcomes):
        (read,) = [s for s in SPANS.spans("fs.materialize.read") if s.parent == job.id]
        (dsl,) = [s for s in SPANS.spans("fs.materialize.transform")
                  if s.parent == job.id]
        (off,) = [s for s in SPANS.spans("fs.offline.merge") if s.parent == job.id]
        (onl,) = [s for s in SPANS.spans("fs.store.merge") if s.parent == job.id]
        assert set(job.attrs) == {"job_id", "rows_out"}
        # four hours of 60 events: the lookback and the job's own hour
        assert read.attrs == dsl.attrs == {"rows": 240}
        assert job.attrs["rows_out"] == out.rows == onl.attrs["rows"] == 60
        assert off.attrs == {
            "rows": 60,
            "inserted": out.offline_stats["inserted"],
            "deduped": out.offline_stats["deduped"],
        }
        assert off.attrs["inserted"] + off.attrs["deduped"] == 60
