"""Open-loop key-value traffic: YCSB core workloads against the serving front.

The configuration gives the table (``recordcount`` records of ``features``
float32 values, hash-partitioned over ``online_partitions``) and the serving
front's settings; the traffic file gives the mix:

  ``rate_per_s``          offered operations per second (Poisson arrivals)
  ``update_proportion``   share of single-key updates (the rest are reads)
  ``request_distribution`` ``zipfian`` (scrambled, YCSB's constant from the
                          configuration) or ``uniform``

Reads go through ``fs.serving.submit`` and ``pump`` (one coalesced store
dispatch per pump); updates go through ``fs.write_batch`` in arrival order.
Each operation is timed from its due time in the schedule, so a stall is
charged to every request that waited behind it.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.harness import BenchError, Check, Window
from bench.reference.kv_latest_wins import LatestWins, record_values, seed_words
from bench.reference.precision import as_bfloat16

FEATURE_SET = ("usertable", 1)
KEY_COL = "user_id"
TS_COL = "ts"
LOAD_CHUNK = 1 << 20
_SCRAMBLE = np.uint64(0x9E3779B97F4A7C15)


@dataclasses.dataclass
class State:
    fs: object
    keys: np.ndarray  # per arrival: record key
    is_update: np.ndarray  # per arrival
    due: np.ndarray  # per arrival: seconds after the window opens
    reference: LatestWins
    seed: int
    features: int
    # every window's GETs, as answered (one Gets per window)
    gets: list = dataclasses.field(default_factory=list)
    counters_before: dict = dataclasses.field(default_factory=dict)
    # the last window's GETs: due times and latencies (seconds)
    last_window: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Gets:
    """One window's GETs in arrays, filled in as each completes, so that the
    window holds no ticket past its completion.  A GET may answer with any
    version acknowledged between update ``lo`` (before its submit) and
    update ``hi`` (once it was seen complete)."""

    key: np.ndarray
    due: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    done_ms: np.ndarray  # NaN: never answered
    found: np.ndarray
    values: np.ndarray

    @classmethod
    def empty(cls, key: np.ndarray, due: np.ndarray, d: int) -> "Gets":
        n = len(key)
        return cls(key, due, np.zeros(n, np.int64), np.zeros(n, np.int64),
                   np.full(n, np.nan), np.zeros(n, bool), np.zeros((n, d), np.float32))

    def harvest(self, open_gets: list, applied: int) -> list:
        """Record every ticket of ``open_gets`` that is no longer pending;
        returns those still pending."""
        pending = []
        for g, t in open_gets:
            if t.status == "pending":
                pending.append((g, t))
                continue
            self.hi[g] = applied
            if t.status == "done":
                self.done_ms[g] = t.done_ms
                self.found[g] = t.found[0]
                self.values[g] = t.values[0]
        return pending


def _field_names(d: int) -> list[str]:
    return [f"field{j}" for j in range(d)]


def _device_values(seed: int, d: int):
    """The jitted twin of ``record_values`` for version 0: a (d, n) block of
    loaded rows for keys ``key0 .. key0 + n``, made on the device."""
    import jax
    import jax.numpy as jnp

    lo, hi = seed_words(seed)
    u32 = jnp.uint32

    def fmix(h):
        h = h ^ (h >> u32(16))
        h = h * u32(0x85EBCA6B)
        h = h ^ (h >> u32(13))
        h = h * u32(0xC2B2AE35)
        return h ^ (h >> u32(16))

    @jax.jit
    def block(key0, seed_lo, seed_hi):
        s = fmix(seed_lo ^ fmix(seed_hi + u32(0x68E31DA4)))
        h = fmix(s ^ (u32(0) * u32(0x27D4EB2F)))
        k = (key0 + jnp.arange(LOAD_CHUNK, dtype=jnp.uint32))[None, :]
        f = jnp.arange(d, dtype=jnp.uint32)[:, None]
        h = fmix(h ^ (k * u32(0x9E3779B1)))
        h = fmix(h ^ (f * u32(0x85EBCA77)))
        return (h >> u32(8)).astype(jnp.float32) * jnp.float32(2.0**-24)

    return lambda key0: block(u32(key0), u32(lo), u32(hi))


def _zipf_cdf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return np.cumsum(w) / w.sum()


def draw_keys(rng, dist: str, n_records: int, n: int, theta: float) -> np.ndarray:
    """``n`` YCSB request keys, uniform or scrambled Zipfian (a Zipf rank
    hashed over the key space, so the hot keys are spread over partitions),
    in the seed's order.  The draw is stratified: the i-th of ``n`` equal
    slices of the distribution gives one key, so every seed asks for the
    same hot keys as often and the seed moves only the cold tail and the
    order."""
    u = (np.arange(n) + rng.random(n)) / n
    if dist == "uniform":
        keys = np.minimum((u * n_records).astype(np.int64), n_records - 1)
        return rng.permutation(keys)
    if dist != "zipfian":
        raise BenchError(f"unknown request_distribution {dist!r}")
    rank = np.searchsorted(_zipf_cdf(n_records, theta), u)
    with np.errstate(over="ignore"):
        h = rank.astype(np.uint64) * _SCRAMBLE
        h ^= h >> np.uint64(29)
    return rng.permutation((h % np.uint64(n_records)).astype(np.int64))


def arrivals(rng, rate: float, seconds: float) -> np.ndarray:
    """``round(rate * seconds)`` Poisson arrival times in [0, seconds): the
    gaps are the exponential distribution's quantiles at the midpoints of
    equal slices, scaled to fill the window and put in the seed's order, so
    every seed offers the same number of requests with the same gaps."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps) - gaps


def schedule(rng, cfg: dict, mix: dict, rate: float, seconds: float):
    """(due times, is-update flags, keys) of one window's arrivals.  Reads
    and updates draw their keys apart, and the updates are a fixed share of
    the arrivals at positions the seed picks: a seed changes the order of
    the work, not its amount."""
    due = arrivals(rng, rate, seconds)
    n = len(due)
    n_upd = int(round(n * float(mix["update_proportion"])))
    is_update = np.zeros(n, bool)
    is_update[rng.choice(n, n_upd, replace=False)] = True
    draw = lambda m: draw_keys(  # noqa: E731
        rng, mix["request_distribution"], int(cfg["recordcount"]), m,
        float(cfg["zipfian_constant"]),
    )
    keys = np.empty(n, np.int64)
    keys[~is_update] = draw(n - n_upd)
    keys[is_update] = draw(n_upd)
    return due, is_update, keys


def _update(fs, state: State, key: int, version: int, ts: int) -> None:
    vals = record_values(state.seed, [key], [version], state.features)[0]
    cols = {KEY_COL: np.array([key], np.int64), TS_COL: np.array([ts], np.int64)}
    for j, name in enumerate(_field_names(state.features)):
        cols[name] = vals[j : j + 1]
    from repro.core.table import Table

    fs.write_batch(*FEATURE_SET, Table(cols))


def table_capacity(n_records: int, parts: int, initial: int) -> int:
    """Slots a partition holds once keys ``0 .. n_records`` are loaded: the
    store's initial capacity, doubled until the fullest partition fits."""
    from repro.kernels.online_lookup.ops import partition_of

    every = np.arange(n_records, dtype=np.int64)
    most = int(np.bincount(partition_of(every, parts), minlength=parts).max())
    cap = initial
    while cap < most:
        cap *= 2
    return cap


def build_store(run):
    """The feature store with the table registered, and nothing loaded."""
    from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
    from repro.core.dsl import UDFTransform
    from repro.core.featurestore import FeatureStore
    from repro.core.serving import ServingConfig

    cfg = run.config
    serving = cfg["serving"]
    fs = FeatureStore(
        f"bench-{run.workload}",
        merge_engine="kernel",
        online_partitions=int(cfg["online_partitions"]),
        serving=ServingConfig(
            cache_capacity=int(serving["cache_capacity"]),
            max_batch_keys=int(serving["max_batch_keys"]),
            deadline_ms=serving["deadline_ms"],
        ),
    )
    if "recordcount" in cfg:
        # the table sized for the whole load at once, at the capacity the
        # store's doubling would reach for it: the load then uploads the
        # planes once, not again at every doubling
        fs.online.initial_capacity = table_capacity(
            int(cfg["recordcount"]), int(cfg["online_partitions"]),
            fs.online.initial_capacity,
        )

    class _Direct:
        """The table is written directly (YCSB load and updates); no job reads
        a source."""

        name = "usertable_writes"

        def read(self, start_ts, end_ts):
            raise RuntimeError("the usertable is written directly, never read")

    fs.register_source(_Direct())
    fs.create_feature_set(
        FeatureSetSpec(
            name=FEATURE_SET[0],
            version=FEATURE_SET[1],
            entity=Entity("user", (KEY_COL,)),
            features=tuple(Feature(n) for n in _field_names(int(cfg["features"]))),
            source_name=_Direct.name,
            transform=UDFTransform(lambda df, ctx: df, "direct_write"),
            timestamp_col=TS_COL,
            materialization=MaterializationSettings(
                offline_enabled=False, online_enabled=True
            ),
        )
    )
    return fs


def setup(run) -> State:
    import jax

    from repro.core.table import Table
    from repro.kernels.online_lookup.ops import partition_of

    cfg, mix = run.config, run.traffic
    n_records = int(cfg["recordcount"])
    d = int(cfg["features"])
    fs = build_store(run)

    with run.phase("data"):
        due, is_update, keys = schedule(
            np.random.default_rng(run.seed), cfg, mix, float(mix["rate_per_s"]),
            run.seconds,
        )
    block = _device_values(run.seed, d)
    names = _field_names(d)
    for key0 in range(0, n_records, LOAD_CHUNK):
        n = min(LOAD_CHUNK, n_records - key0)
        with run.phase("data"):
            vals = np.asarray(jax.block_until_ready(block(key0)))[:, :n]
            cols = {
                KEY_COL: np.arange(key0, key0 + n, dtype=np.int64),
                TS_COL: np.zeros(n, np.int64),
            }
            cols.update(zip(names, vals))
            frame = Table(cols)
        with run.phase("load"):
            fs.write_batch(*FEATURE_SET, frame, creation_ts=1)
        del cols, frame, vals

    state = State(fs, keys, is_update, due, LatestWins(), run.seed, d)
    with run.phase("warm"):
        online = fs.online
        parts = int(cfg["online_partitions"])
        every = np.arange(n_records, dtype=np.int64)
        part = partition_of(every, parts)
        # gather buckets 128 .. max_batch_keys, keys spread over partitions
        b = 1
        while b <= int(cfg["serving"]["max_batch_keys"]):
            online.lookup_encoded(*FEATURE_SET, every[:: max(n_records // b, 1)][:b])
            b *= 2
        # routed query buckets: up to 1024 keys of one partition
        for q in (128, 256, 512, 1024):
            online.lookup_encoded(*FEATURE_SET, every[part == 0][:q])
        # the update path; version-0 values, so the table's answers are unchanged
        for k in (0, 1):
            _update(fs, state, k, 0, 1)
        fs.serving.get(*FEATURE_SET, ids=np.array([2], np.int64))
        fs.serving.cache.clear()
    return state


def window(state: State, run, seconds: float) -> Window:
    fs = state.fs
    front = fs.serving
    name, version = FEATURE_SET
    due, keys, is_update = state.due, state.keys, state.is_update
    n = len(due)
    submit = front.submit
    pump = front.pump
    ref = state.reference
    get_of = np.cumsum(~is_update) - 1  # arrival -> index among the GETs
    rec = Gets.empty(keys[~is_update], due[~is_update], state.features)
    state.gets.append(rec)
    lat_upd = []
    late = np.zeros(n)
    for stage in ("kernel", "assembly", "decode", "queue_wait", "request"):
        fs.monitor.system.histograms.pop(f"serving/{stage}_us", None)
    state.counters_before = dict(front.counters)
    span = run.span

    t0 = time.perf_counter()
    i = 0
    # submitted GETs not yet seen complete: (index among the GETs, ticket)
    open_gets = []
    while i < n:
        now = time.perf_counter() - t0
        j = int(np.searchsorted(due, now, side="right"))
        if j == i:
            wait = due[i] - now
            if wait > 2e-4:
                with span("kv.wait_for_arrival"):
                    time.sleep(wait - 1e-4)
            continue
        with span("kv.submit"):
            for k in range(i, j):
                late[k] = time.perf_counter() - t0 - due[k]
                key = int(keys[k])
                if is_update[k]:
                    open_gets = rec.harvest(open_gets, ref.applied)
                    with span("store.update"):
                        _update(fs, state, key, ref.applied + 1, 2 + ref.applied)
                    ref.apply(key)
                    lat_upd.append(time.perf_counter() - t0 - due[k])
                else:
                    g = int(get_of[k])
                    rec.lo[g] = ref.applied
                    open_gets.append((g, submit(name, version, ids=keys[k : k + 1])))
        i = j
        with span("kv.pump"):
            pump()
        with span("kv.harvest"):
            open_gets = rec.harvest(open_gets, ref.applied)
    with span("kv.pump"):
        pump(force=True)
    elapsed = time.perf_counter() - t0
    rec.harvest(open_gets, ref.applied)

    get_lat = (rec.done_ms - t0 * 1e3) / 1e3 - rec.due
    get_lat[np.isnan(get_lat)] = np.inf
    state.last_window = {"due": rec.due, "get_lat": get_lat}
    after = front.counters
    run.counters.update(
        {
            f"front.{c}": after[c] - state.counters_before.get(c, 0)
            for c in ("cache_hits", "cache_misses", "dispatches",
                      "coalesced_keys", "store_keys")
        }
    )
    hist = fs.monitor.system.histograms.get("serving/kernel_us")
    if hist is not None and hist.n:
        run.counters["front.kernel_us_mean"] = hist.mean
    run.counters["window_s"] = elapsed
    # every GET and update of the window; the tails are also the per-layer
    # readers' (a traced run reports those alone)
    metrics = {
        "get_p50_ms": float(np.percentile(get_lat, 50)) * 1e3,
        "get_p99_ms": float(np.percentile(get_lat, 99)) * 1e3,
    }
    if lat_upd:
        metrics["update_p50_ms"] = float(np.percentile(lat_upd, 50)) * 1e3
        metrics["update_p99_ms"] = float(np.percentile(lat_upd, 99)) * 1e3
    run.counters.update({f"window.{k}": v for k, v in metrics.items()})
    failed = int(np.sum(~np.isfinite(get_lat)))
    q = (50, 90, 99, 100)
    detail = {"gets": len(get_lat), "updates": len(lat_upd),
              "get_ms": dict(zip(map(str, q), np.percentile(get_lat, q) * 1e3))}
    if lat_upd:
        detail["update_ms"] = dict(zip(map(str, q), np.percentile(lat_upd, q) * 1e3))
    return Window(
        metrics=metrics,
        attempted=n,
        failed=failed,
        detail=detail,
        lateness={
            "p50": float(np.percentile(late, 50)),
            "p99": float(np.percentile(late, 99)),
            "max": float(late.max()) if n else 0.0,
            "ops_per_s": n / elapsed,
        },
    )


def check(state: State, run, control: bool = False) -> list[Check]:
    """Every GET of the window against the latest-wins reference, and a read
    back of every updated key's last acknowledged value."""
    d, seed, ref = state.features, state.seed, state.reference
    gets = state.gets
    keys = np.concatenate([g.key for g in gets])
    lo = np.concatenate([g.lo for g in gets])
    hi = np.concatenate([g.hi for g in gets])
    answered = np.concatenate([np.isfinite(g.done_ms) for g in gets])
    found_all = np.concatenate([g.found for g in gets])
    values = np.concatenate([g.values for g in gets])
    expect_v = ref.version_at(keys, lo)
    wrong = 0
    chunk = 65_536
    for s in range(0, len(keys), chunk):
        sl = slice(s, s + chunk)
        want = record_values(seed, keys[sl], expect_v[sl], d)
        if control:
            got = as_bfloat16(want)
            found = np.ones(len(want), bool)
            done = np.ones(len(want), bool)
        else:
            got, found, done = values[sl], found_all[sl], answered[sl]
        bad = ~(got == want).all(axis=1) | ~found | ~done
        for r in np.flatnonzero(bad & done & found):
            i = s + r
            later = ref.versions_between(keys[i], lo[i], hi[i])
            if any(
                (record_values(seed, [keys[i]], [v], d)[0] == got[r]).all()
                for v in later
            ):
                bad[r] = False
        wrong += int(bad.sum())

    upd_keys, upd_ver = ref.last_versions()
    readback_wrong = 0
    for s in range(0, len(upd_keys), 2048):
        ks, vs = upd_keys[s : s + 2048], upd_ver[s : s + 2048]
        want = record_values(seed, ks, vs, d)
        if control:
            got, found = as_bfloat16(want), np.ones(len(ks), bool)
        else:
            got, found = state.fs.get_online_features(*FEATURE_SET, [ks])
        readback_wrong += int((~(got == want).all(axis=1) | ~found).sum())
    return [
        Check("gets_wrong", float(wrong), 0.0),
        Check("updates_not_read_back", float(readback_wrong), 0.0),
    ]
