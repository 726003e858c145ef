"""The TPC-DS hourly materialization cell at a tiny size on the CPU: the
program agrees with the plain rolling-window reference, the bfloat16 control
does not, the window lowers no program, each fault the cell can have turns
``correct`` false, and the cell's four metric readers read a tiny window."""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench import harness

CONFIG, TRAFFIC = "tpcds_sf100_sales", "tpcds_materialize"
# a few thousand customers, the 7-day lookback and the warm-up day, then four
# days the window can reach (a CPU job takes ~30 ms); every width, window and
# ratio as configured
TINY = {"customers": 3000, "sales_per_day": 1500, "sale_days": 12}
SEED = 2**33 + 41


def cell() -> dict:
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    cfg = dict(harness.load_json(harness.ROOT / entry["file"]), **TINY)
    mix = harness.load_json(harness.BENCH_DIR / "traffic" / f"{TRAFFIC}.json")
    return {
        "cell": {"name": TRAFFIC, "config": CONFIG, "traffic": TRAFFIC, "chips": 1},
        "config": cfg,
        "traffic": mix,
        "end_to_end": [],
        "per_layer": [],
    }


def run(*, seconds: float = 0.5, control: bool = False, lines=None) -> dict:
    return harness.run_cell(
        TRAFFIC, seed=SEED, seconds=seconds, trace=False, require_tpu=False,
        cell=cell(), emit=(lines.append if lines is not None else lambda _: None),
        control=control,
    )


def generator():
    return harness.load_module(harness.BENCH_DIR / "generators" / "tpcds_hourly.py")


def test_program_correct_control_fails_and_window_compiles_nothing():
    lines: list = []
    out = run(control=True, lines=lines)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert not out["control_correct"], out["control_checks"]
    assert out["control_checks"]["feature_max_rel_err"]["value"] > 1e-3
    assert out["control_checks"]["online_rows_wrong"]["value"] > 0
    window = next(json.loads(x) for x in lines if "window_compiles" in x)
    # jobs of different row counts after the warm-up lower no program
    assert window["window_compiles"]["lowered"] == 0, window
    assert window["kernel_fallbacks"] == {}
    detail = next(json.loads(x) for x in lines if "window_detail" in x)
    assert detail["window_detail"]["jobs"] == out["attempted"]


def test_sales_are_tickets_sorted_by_time():
    cfg = cell()["config"]
    sales = generator().generate_sales(SEED, cfg)
    ts, cust = sales["ts"], sales["ss_customer_sk"]
    assert len(ts) == cfg["sale_days"] * cfg["sales_per_day"]
    assert (np.diff(ts) >= 0).all() and ts.min() >= 86_400_000
    # a ticket's items share customer and second: runs of 8-16 rows (a day's
    # last ticket is cut to fit)
    change = np.flatnonzero((np.diff(ts) != 0) | (np.diff(cust) != 0)) + 1
    runs = np.diff(np.concatenate([[0], change, [len(ts)]]))
    assert runs.max() <= 2 * 16 and np.median(runs) >= 8
    assert sales["ss_quantity"].min() >= 1 and sales["ss_quantity"].max() <= 100
    assert sales["ss_net_paid"].max() <= 2_000_000
    assert (generator().generate_sales(SEED, cfg)["ts"] == ts).all()


def _untied(monkeypatch):
    """The DSL reading every row's window at the row itself: the later rows
    of a (customer, second) tie left out."""
    from repro.core import dsl

    monkeypatch.setattr(dsl, "_tie_ends", lambda seg, ts: np.arange(len(ts)))


def _skip_online(monkeypatch):
    """Jobs that acknowledge without writing the online store."""
    from repro.core.online_store import OnlineStore

    merge = OnlineStore.merge

    def offline_only(self, spec, frame, creation_ts, **kw):
        if frame["ts"].max() > 0:  # a job's hour, not the bootstrap load
            return self._empty_stats("kernel", len(spec.features), creation_ts)
        return merge(self, spec, frame, creation_ts, **kw)

    monkeypatch.setattr(OnlineStore, "merge", offline_only)


def _drop_a_row(monkeypatch):
    """The offline merge of each job losing the rows of its first customer."""
    from repro.core.offline_store import OfflineStore

    merge = OfflineStore._merge_stats

    def dropped(self, spec, frame, creation_ts, engine):
        key = frame["ss_customer_sk"]
        return merge(self, spec, frame.filter(key != key[0]), creation_ts, engine)

    monkeypatch.setattr(OfflineStore, "_merge_stats", dropped)


@pytest.mark.parametrize(
    "fault, check",
    [(_untied, "feature_max_rel_err"), (_skip_online, "online_rows_wrong"),
     (_drop_a_row, "offline_rows_missing")],
    ids=["tie_defect", "online_skipped", "offline_row_lost"],
)
def test_fault_turns_correct_false(monkeypatch, fault, check):
    fault(monkeypatch)
    out = run(seconds=0.3)
    assert not out["correct"], out["checks"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]


def test_window_past_the_history_is_an_error():
    """A window longer than the generated days stops with a BenchError, not
    a job on missing source rows."""
    c = cell()
    c["config"]["sale_days"] = 9  # the window's first day only
    r = harness.Run(TRAFFIC, c, seed=SEED, seconds=600.0, trace=False)
    gen = generator()
    state = gen.setup(r)
    with pytest.raises(harness.BenchError, match="past the 9 generated days"):
        gen.window(state, r, 600.0)
    assert state.next_hour < (1 + 9) * 24


# -- the cell's metric readers --------------------------------------------


def reader(name: str):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny window recorded under a CPU profiler session."""
    r = harness.Run(TRAFFIC, cell(), seed=SEED, seconds=0.5, trace=False)
    gen = generator()
    state = gen.setup(r)
    with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
        with r.span("window"):
            gen.window(state, r, 0.5)
    r.peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")["devices"][
        "TPU v5 lite"]
    return r


def test_span_readers_read_a_tiny_window(traced):
    from repro.core.monitoring import SPANS

    jobs = SPANS.spans("fs.materialize.job")
    assert len(jobs) == traced.counters["materialize.jobs"] >= 1
    for metric, span in (("materialize.transform_ms", "fs.materialize.transform"),
                         ("materialize.offline_merge_ms", "fs.offline.merge")):
        found = SPANS.spans(span)
        assert len(found) == len(jobs)
        want = sum(s.end_ns - s.start_ns for s in found) / len(found) / 1e6
        assert reader(metric).read(traced) == pytest.approx(want)


def test_roofline_reads_the_jobs_bytes_over_the_rolling_programs(traced):
    from repro.core.monitoring import SPANS

    jobs = SPANS.spans("fs.materialize.job")
    rows_in = traced.counters["materialize.rows_needed"]
    rows_out = sum(j.attrs["rows_out"] for j in jobs)
    # a job's own rows and its customers' earlier rows, of the lookback read
    read = sum(r.attrs["rows"] for r in SPANS.spans("fs.materialize.read"))
    assert read > rows_in > rows_out > 0
    traced.device_trace = {"module_s": {"rolling_sum": 0.002, "rolling_extreme": 0.003,
                                        "lookup": 5.0}}
    got = reader("rolling_kernels_roofline").read(traced)
    need = rows_in * (8 + 8 + 4 * 2) + rows_out * 4 * 8
    assert got == pytest.approx(100 * need / 819e9 / 0.005)
    # a program that ran but is missing from the trace: nothing to read
    traced.device_trace = {"module_s": {"rolling_sum": 0.002}}
    assert reader("rolling_kernels_roofline").read(traced) is None
    traced.counters["materialize.rolling_fallbacks"] = 1.0
    traced.device_trace = {"module_s": {"rolling_sum": 0.002, "rolling_extreme": 0.003}}
    assert reader("rolling_kernels_roofline").read(traced) is None
    traced.counters["materialize.rolling_fallbacks"] = 0.0


def test_device_idle_reads_the_trace(traced):
    traced.device_trace = {"busy_s": 1.5, "window_s": 6.0}
    assert reader("device_idle.materialize").read(traced) == pytest.approx(75.0)
    traced.device_trace = None
    assert reader("device_idle.materialize").read(traced) is None


@pytest.mark.parametrize("metric", ["materialize.transform_ms",
                                    "materialize.offline_merge_ms",
                                    "rolling_kernels_roofline"])
def test_span_readers_without_their_spans(tmp_path, metric):
    """A program without these spans (the parent of this cell) gives
    nothing to read."""
    from repro.core.monitoring import SPANS

    with jax.profiler.trace(str(tmp_path / "other")):
        with SPANS.span("fs.other"):
            pass
    r = harness.Run(TRAFFIC, cell(), seed=SEED, seconds=1.0, trace=False)
    r.device_trace = {"module_s": {"rolling_sum": 1.0, "rolling_extreme": 1.0}}
    r.peaks = {"hbm_bytes_per_s": 819e9}
    assert reader(metric).read(r) is None


def test_rows_needed_counts_the_rows_the_dsl_aggregates():
    """The roofline's count of the source rows the jobs need is the rows of
    each job's lookback whose customer has a sale in its hour: what the DSL
    keeps of the lookback."""
    from repro.core.dsl import DslTransform, RollingAgg
    from repro.core.transform import FeatureWindow

    gen = generator()
    cfg = cell()["config"]
    sales = gen.generate_sales(SEED, cfg)
    dsl = DslTransform(gen.KEY_COL, gen.TS_COL,
                       [RollingAgg("n", "ss_quantity", gen.DAY_MS, "count")])
    source = gen.SalesSource(sales)
    first = (gen.DAY0 + 8) * 24
    back = cfg["source_lookback_days"] * gen.DAY_MS
    kept = 0
    for h in range(first, first + 6):
        start, end = h * gen.HOUR_MS, (h + 1) * gen.HOUR_MS
        kept += len(dsl._entities_in(source.read(start - back, end),
                                     FeatureWindow(start, end)))
    assert kept == gen.rows_needed(sales, cfg, first, first + 6) > 0
