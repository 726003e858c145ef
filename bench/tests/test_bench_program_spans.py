"""The six readers of the program's own spans: each on a recorded span set,
each ``None`` without its span and without the recorder, and all six on a
tiny window recorded under a CPU profiler session."""

from __future__ import annotations

import sys
import types

import jax
import pytest

from bench import harness
from bench.tests import tiny

READERS = {
    "serving.queue_wait_ms": "fs.serving.dispatch",
    "serving.dispatch_host_ms": "fs.serving.dispatch",
    "store.scan_ms": "fs.store.lookup.scan",
    "store.gather_ms": "fs.store.lookup.gather",
    "store.merge_ms": "fs.store.merge",
    "store.merge_resolve_ms": "fs.store.merge.resolve",
}


def reader(name: str):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")


def _record(tmp_path):
    """Two dispatches (one lookup each, with its scan and gather) and two
    merges (one resolve each), recorded by the program's recorder."""
    from repro.core.monitoring import SPANS

    with jax.profiler.trace(str(tmp_path / "spans")):
        for tickets, wait in ((2, 3.0), (3, 9.0)):
            with SPANS.span("fs.serving.dispatch", tickets=tickets,
                            wait_ms_sum=wait):
                sum(range(5_000))
                with SPANS.span("fs.store.lookup"):
                    with SPANS.span("fs.store.lookup.scan"):
                        sum(range(5_000))
                    with SPANS.span("fs.store.lookup.gather"):
                        sum(range(5_000))
        for _ in range(2):
            with SPANS.span("fs.store.merge"):
                with SPANS.span("fs.store.merge.resolve", stale=True):
                    sum(range(5_000))
    return SPANS


def _mean_ms(spans, name, *, own=False):
    mine = spans.spans(name)
    total = 0
    for s in mine:
        total += s.end_ns - s.start_ns
        if own:
            total -= sum(c.end_ns - c.start_ns for c in spans.spans()
                         if c.parent == s.id)
    return total / len(mine) / 1e6


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_on_a_recorded_span_set(tmp_path, metric):
    spans = _record(tmp_path)
    got = reader(metric).read(None)
    if metric == "serving.queue_wait_ms":
        assert got == pytest.approx((3.0 + 9.0) / (2 + 3))
    elif metric == "serving.dispatch_host_ms":
        assert got == pytest.approx(_mean_ms(spans, "fs.serving.dispatch", own=True))
        assert got < _mean_ms(spans, "fs.serving.dispatch")
    else:
        assert got == pytest.approx(_mean_ms(spans, READERS[metric]))
    assert got > 0


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_without_its_span(tmp_path, metric):
    from repro.core.monitoring import SPANS

    with jax.profiler.trace(str(tmp_path / "other")):
        with SPANS.span("fs.other"):
            pass
    assert reader(metric).read(None) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_without_the_recorder(monkeypatch, metric):
    """A program that records no spans (the parent of the recorder)."""
    monkeypatch.setitem(
        sys.modules, "repro.core.monitoring", types.ModuleType("repro.core.monitoring")
    )
    assert reader(metric).read(None) is None


def test_all_six_read_a_tiny_window(tmp_path):
    cell = tiny.cell("ycsb_1kb", "ycsb_b_zipf")
    run = harness.Run("ycsb_b_zipf", cell, seed=2**33 + 29, seconds=0.8,
                      trace=False)
    gen = harness.load_module(harness.BENCH_DIR / "generators" / "kv_open_loop.py")
    state = gen.setup(run)
    with jax.profiler.trace(str(tmp_path / "window")):
        with run.span("window"):
            gen.window(state, run, 0.8)
    values = {m: reader(m).read(run) for m in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    update_ms = reader("store.update_ms").read(run)
    assert values["store.merge_ms"] <= update_ms
    assert values["store.merge_resolve_ms"] <= values["store.merge_ms"]
    # the readers ran after the session: the check's reads add no spans
    from repro.core.monitoring import SPANS

    n = len(SPANS.spans())
    assert all(c.ok for c in gen.check(state, run))
    assert len(SPANS.spans()) == n
