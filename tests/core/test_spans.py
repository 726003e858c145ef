"""The in-program span recorder (``monitoring.SPANS``): it records only
inside a JAX profiler session, keeps parents, roots and self time, bounds
its buffer, and starts afresh with each session; and the spans the serving
front and the online store put at their layer boundaries."""

import contextlib
import itertools
import time

import jax
import numpy as np
import pytest

from repro.core import monitoring
from repro.core.monitoring import SPANS, HealthMonitor, SpanRecorder
from repro.core.online_store import OnlineStore
from repro.core.serving import ServingConfig, ServingFront
from tests.core.test_serving import make_frame, make_spec


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
    assert SPANS.span("fs.x") is SPANS.span("fs.y")
    assert Off.calls >= 6  # dispatch, lookup, scan, gather, merges, resolves
    assert SPANS.spans() == kept and SPANS.dropped == dropped
