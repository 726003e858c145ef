"""Scheduled hourly materialization of TPC-DS store sales into both stores.

The configuration gives the deployment: ``customers``, ``sales_per_day``
drawn as tickets of ``ticket_items`` line items (each ticket one customer and
one second of the day), the value ranges, the feature set's rolling
aggregates, the source lookback and the schedule; ``sale_days`` is how many
days of sales are generated.  Day 0 starts one day after time 0, so the
bootstrap rows at time 0 lie before the history.

Set-up generates the sales from the seed into host columns sorted by time
(the source's ``read`` only slices them), writes one zero row per customer
to both stores through ``write_batch`` in chunks of every power-of-two size
a job's online merge can take (so those programs compile here), and runs the
traffic's ``warm_jobs`` hourly jobs at the end of day 7 (so the rolling
programs compile here).  The window then calls ``tick`` for one hour after
another from day 8, hour 0, each job due when the previous one returns: an
update is one hour's rows, materialized and acknowledged in both stores.
"""

from __future__ import annotations

import dataclasses
import resource
import time

import numpy as np

from bench.harness import BenchError, Check, Window
from bench.reference.rolling_window import window_features

FEATURE_SET = ("customer_spend", 1)
KEY_COL = "ss_customer_sk"
TS_COL = "ts"
HOUR_MS = 3_600_000
DAY_MS = 24 * HOUR_MS
DAY0 = 1  # the history's first day, in days after time 0
BOOTSTRAP_TS = 0
# a feature is right within this relative error (of max(|want|, 1)): float32
# sums of up to ~50 positive values err ~3e-6 relatively, bfloat16 ~4e-3
FEATURE_LIMIT = 1e-4


@dataclasses.dataclass
class State:
    fs: object
    sales: dict  # host columns sorted by time
    history_end_ms: int
    next_hour: int  # the window's next job covers [next_hour, next_hour + 1) h
    window_hours: tuple = (0, 0)  # [first, end) hours the window's jobs covered
    fallbacks_before: float = 0.0


class SalesSource:
    """``store_sales`` as the feature set's source: a read slices the
    time-sorted columns."""

    name = "store_sales"

    def __init__(self, columns: dict) -> None:
        self.columns = columns

    def read(self, start_ts: int, end_ts: int):
        from repro.core.table import Table

        i, j = np.searchsorted(self.columns[TS_COL], [start_ts, end_ts])
        return Table({k: v[i:j] for k, v in self.columns.items()})


def generate_sales(seed: int, cfg: dict) -> dict:
    """``sale_days`` days of sales: per day, tickets of uniform 8-16 items
    until the day's quota (the last ticket cut to fit), each ticket one
    uniform customer and one uniform second; per item a uniform quantity
    and net paid.  Columns sorted by time, a ticket's items together."""
    rng = np.random.default_rng(seed)
    days, per_day = int(cfg["sale_days"]), int(cfg["sales_per_day"])
    lo, hi = cfg["ticket_items"]
    sizes = rng.integers(lo, hi + 1, size=(days, per_day // lo + 1), dtype=np.int32)
    start = np.cumsum(sizes, axis=1) - sizes
    live = start < per_day
    sizes = np.minimum(sizes, per_day - start)[live]
    day = np.repeat(np.arange(days), live.sum(axis=1))
    second = rng.integers(0, 86_400, size=len(sizes))
    ticket_ts = (DAY0 + day) * DAY_MS + second * 1000
    customer = rng.integers(1, int(cfg["customers"]) + 1, size=len(sizes))
    # tickets in time order, each ticket's items repeated in place
    order = np.argsort(ticket_ts, kind="stable")
    sizes = sizes[order]
    n = int(sizes.sum())
    q_lo, q_hi = cfg["ss_quantity"]
    p_lo, p_hi = cfg["ss_net_paid_cents"]
    return {
        KEY_COL: np.repeat(customer[order], sizes),
        TS_COL: np.repeat(ticket_ts[order], sizes),
        "ss_quantity": rng.integers(q_lo, q_hi + 1, size=n, dtype=np.int32).astype(np.float32),
        "ss_net_paid": rng.integers(p_lo, p_hi + 1, size=n, dtype=np.int32).astype(np.float32),
    }


def aggregates(cfg: dict) -> list[dict]:
    """The feature set's aggregates, with their windows in ms."""
    return [dict(f, window_ms=int(f["window_days"]) * DAY_MS) for f in cfg["features"]]


def build_store(run, sales: dict):
    """The feature store with the online table sized for every customer and
    the feature set registered, its schedule starting at the first warm-up
    hour."""
    from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
    from repro.core.dsl import DslTransform, RollingAgg
    from repro.core.featurestore import FeatureStore
    from repro.core.keys import encode_keys
    from repro.kernels.online_lookup.ops import partition_of

    cfg = run.config
    parts = int(cfg["online_partitions"])
    fs = FeatureStore(
        f"bench-{run.workload}",
        merge_engine="kernel",
        online_partitions=parts,
        offline_shards=int(cfg["offline_shards"]),
    )
    # the capacity the store's doubling would reach for every customer, so
    # the load uploads the planes once and nothing grows in the window
    keys = encode_keys([np.arange(1, int(cfg["customers"]) + 1, dtype=np.int64)])
    most = int(np.bincount(partition_of(keys, parts), minlength=parts).max())
    while fs.online.initial_capacity < most:
        fs.online.initial_capacity *= 2

    aggs = aggregates(cfg)
    transform = DslTransform(
        KEY_COL, TS_COL,
        [RollingAgg(a["output"], a["source"], a["window_ms"], a["agg"]) for a in aggs],
    )
    fs.register_source(SalesSource(sales))
    interval = int(cfg["schedule_interval_hours"]) * HOUR_MS
    # the schedule starts at the first warm-up hour: the history before it
    # is the lookback, not hours to materialize
    fs.scheduler.register_feature_set(
        *FEATURE_SET, schedule_interval=interval, partition_window=None,
        timeline_origin=(DAY0 + 8) * DAY_MS - int(run.traffic["warm_jobs"]) * HOUR_MS,
    )
    fs.create_feature_set(
        FeatureSetSpec(
            name=FEATURE_SET[0],
            version=FEATURE_SET[1],
            entity=Entity("customer", (KEY_COL,)),
            features=tuple(Feature(a["output"]) for a in aggs),
            source_name=SalesSource.name,
            transform=transform,
            timestamp_col=TS_COL,
            source_lookback=int(cfg["source_lookback_days"]) * DAY_MS,
            materialization=MaterializationSettings(
                offline_enabled=bool(cfg["offline_enabled"]),
                online_enabled=bool(cfg["online_enabled"]),
                schedule_interval=interval,
            ),
        )
    )
    return fs


def bootstrap(fs, cfg: dict) -> None:
    """One zero row per customer at ``BOOTSTRAP_TS``, written to both stores
    in chunks of 128, 256, ... 65,536 rows and then 65,536 at a time: every
    batch bucket an hourly job's online merge can fall in compiles here."""
    from repro.core.table import Table

    n = int(cfg["customers"])
    sizes = [1 << k for k in range(7, 17)]
    done = 0
    while done < n:
        size = min(sizes.pop(0) if sizes else 1 << 16, n - done)
        cols = {KEY_COL: np.arange(done + 1, done + size + 1, dtype=np.int64),
                TS_COL: np.full(size, BOOTSTRAP_TS, np.int64)}
        cols.update({f["output"]: np.zeros(size, np.float32) for f in cfg["features"]})
        fs.write_batch(*FEATURE_SET, Table(cols), creation_ts=BOOTSTRAP_TS + 1)
        done += size


def setup(run) -> State:
    cfg = run.config
    if int(cfg["sale_days"]) < 9:
        raise BenchError("sale_days must cover the 7 lookback days, day 7 and day 8")
    with run.phase("data"):
        sales = generate_sales(run.seed, cfg)
    fs = build_store(run, sales)
    with run.phase("load"):
        bootstrap(fs, cfg)
    with run.phase("warm"):
        warm = int(run.traffic["warm_jobs"])
        first = (DAY0 + 8) * 24
        for hour in range(first - warm, first):
            stats = fs.tick((hour + 1) * HOUR_MS)
            if stats != {"succeeded": 1, "retried": 0, "failed": 0}:
                raise BenchError(f"warm-up job for hour {hour} did not succeed: {stats}")
    return State(
        fs, sales, history_end_ms=(DAY0 + int(cfg["sale_days"])) * DAY_MS,
        next_hour=first,
        fallbacks_before=fs.monitor.kernel_fallbacks().get("rolling_agg", 0.0),
    )


def window(state: State, run, seconds: float) -> Window:
    fs = state.fs
    span = run.span
    first = hour = state.next_hour
    lat = []
    host = []  # per job: main thread's CPU s, page faults, switches
    failed = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        end = (hour + 1) * HOUR_MS
        if end > state.history_end_ms:
            raise BenchError(
                f"the window reached hour {hour}, past the {run.config['sale_days']} "
                "generated days of sales"
            )
        with span("materialize.tick"):
            r0, c0 = resource.getrusage(resource.RUSAGE_SELF), time.thread_time()
            t = time.perf_counter()
            stats = fs.tick(end)
            lat.append(time.perf_counter() - t)
            r1, c1 = resource.getrusage(resource.RUSAGE_SELF), time.thread_time()
        host.append((c1 - c0, r1.ru_minflt - r0.ru_minflt,
                     r1.ru_nvcsw - r0.ru_nvcsw, r1.ru_nivcsw - r0.ru_nivcsw))
        if stats != {"succeeded": 1, "retried": 0, "failed": 0}:
            failed += 1
        hour += 1
    elapsed = time.perf_counter() - t0
    state.next_hour = hour
    state.window_hours = (first, hour)
    run.counters["window_s"] = elapsed
    run.counters["materialize.jobs"] = len(lat)
    run.counters["materialize.rolling_fallbacks"] = (
        fs.monitor.kernel_fallbacks().get("rolling_agg", 0.0) - state.fallbacks_before
    )
    run.counters["window.update_p99_ms"] = float(np.percentile(lat, 99)) * 1e3
    run.counters["materialize.rows_needed"] = rows_needed(state.sales, run.config,
                                                          first, hour)
    q = (50, 90, 99, 100)
    cpu, faults, vol, invol = np.asarray(host, float).T
    return Window(
        metrics={"update_p50_ms": float(np.percentile(lat, 50)) * 1e3},
        attempted=len(lat),
        failed=failed,
        detail={
            "jobs": len(lat),
            "hours": [first, hour],
            "job_ms": dict(zip(map(str, q), np.percentile(lat, q) * 1e3)),
            "jobs_per_s": len(lat) / elapsed,
            # medians per job: the main thread's CPU time (the rest of a
            # job's wall time is waiting: on the device, or for a core),
            # minor page faults, voluntary and involuntary context switches
            "job_host": {
                "thread_cpu_ms": float(np.median(cpu)) * 1e3,
                "minflt": float(np.median(faults)),
                "nvcsw": float(np.median(vol)),
                "nivcsw": float(np.median(invol)),
            },
        },
    )


def rows_needed(sales: dict, cfg: dict, first: int, end: int) -> int:
    """The source rows the aggregates of the jobs of hours [first, end)
    need: for each job, every row of its lookback read whose customer has a
    sale in the job's hour (a row's windows hold its own customer's rows
    alone)."""
    ts, cust = sales[TS_COL], sales[KEY_COL]
    back = int(cfg["source_lookback_days"]) * DAY_MS
    i0, i, j = np.searchsorted(ts, [first * HOUR_MS - back, first * HOUR_MS,
                                    end * HOUR_MS])
    # each (customer, hour) with a sale, as customer * 2^32 + hour
    pairs = np.unique(cust[i:j].astype(np.int64) << 32 | ts[i:j] // HOUR_MS)
    c, h = pairs >> 32, pairs & 0xFFFFFFFF
    # the history in (customer, ts) order, one int64 key a row
    t0 = int(ts[i0])
    span = end * HOUR_MS - t0 + 1
    order = np.argsort(cust[i0:j], kind="stable")  # time order within a customer
    key = cust[i0:j][order].astype(np.int64) * span + (ts[i0:j][order] - t0)
    lo = c * span + np.maximum(h * HOUR_MS - back - t0, 0)
    hi = c * span + ((h + 1) * HOUR_MS - t0)
    return int((np.searchsorted(key, hi) - np.searchsorted(key, lo)).sum())


def _row_keys(entity: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """One int64 per (customer, second) of the history."""
    return np.asarray(entity, np.int64) * (1 << 40) + np.asarray(ts, np.int64) // 1000


def _rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    return np.abs(got - want) / np.maximum(np.abs(want), 1.0)


def check(state: State, run, control: bool = False) -> list[Check]:
    """Every offline row the window's jobs wrote, and every customer they
    touched read back online, against the plain rolling-window reference
    (``bench/reference/rolling_window.py``).  With ``control`` the
    reference computed in bfloat16 stands in for the program's rows."""
    cfg = run.config
    aggs = aggregates(cfg)
    names = [a["output"] for a in aggs]
    sales = state.sales
    lo, hi = (h * HOUR_MS for h in state.window_hours)
    ts = sales[TS_COL]
    i, j = np.searchsorted(ts, [lo, hi])
    # the rows the window's jobs had to write: one per (customer, second)
    want_keys, first = np.unique(_row_keys(sales[KEY_COL][i:j], ts[i:j]),
                                 return_index=True)
    q_ent, q_ts = sales[KEY_COL][i:j][first], ts[i:j][first]
    # the rows any window of the window's jobs can reach
    h0 = np.searchsorted(ts, lo - max(a["window_ms"] for a in aggs))
    history = {k: v[h0:j] for k, v in sales.items()}
    want = window_features(history[KEY_COL], history[TS_COL], history, aggs,
                           q_ent, q_ts)
    if control:
        low = window_features(history[KEY_COL], history[TS_COL], history, aggs,
                              q_ent, q_ts, bf16=True)
        got_keys, got = want_keys, np.stack([low[n] for n in names], axis=1)
    else:
        table = state.fs.offline.read(*FEATURE_SET, window=(lo, hi))
        got_keys = _row_keys(table[KEY_COL], table["event_ts"])
        got = np.stack([table[n].astype(np.float64) for n in names], axis=1)
    pos = np.searchsorted(want_keys, got_keys)
    hit = (pos < len(want_keys)) & (want_keys[np.minimum(pos, len(want_keys) - 1)]
                                    == got_keys)
    matched, at = np.unique(pos[hit], return_index=True)
    missing = len(want_keys) - len(matched)
    extra = len(got_keys) - len(matched)
    want_m = np.stack([want[n] for n in names], axis=1)[matched]
    err = _rel_err(got[np.flatnonzero(hit)[at]], want_m)
    max_err = float(err.max()) if err.size else 0.0

    # online: each touched customer at its latest sale of the window
    last = np.unique(q_ent[::-1], return_index=True)[1]
    cust = q_ent[::-1][last]
    online_want = np.stack([want[n][::-1][last] for n in names], axis=1)
    if control:
        low_rows = np.stack([low[n][::-1][last] for n in names], axis=1)
        values, found = low_rows, np.ones(len(cust), bool)
    else:
        parts = [state.fs.get_online_features(*FEATURE_SET, [cust[s: s + 8192]])
                 for s in range(0, len(cust), 8192)]
        values = np.concatenate([p[0] for p in parts]).astype(np.float64)
        found = np.concatenate([p[1] for p in parts])
    bad = ~found | (_rel_err(values, online_want) > FEATURE_LIMIT).any(axis=1)
    return [
        Check("feature_max_rel_err", max_err, FEATURE_LIMIT),
        Check("offline_rows_missing", float(missing), 0.0),
        Check("offline_rows_extra", float(extra), 0.0),
        Check("online_rows_wrong", float(bad.sum()), 0.0),
    ]
