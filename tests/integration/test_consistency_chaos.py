"""Eventual consistency under failure injection (§4.5.4) + bootstrap
equivalence (§4.5.5) + Fig.5 record semantics — property-based.

The central §4.5 argument: merges are idempotent (offline full-key dedup,
online latest-wins), therefore ANY failure at ANY seam followed by retries
converges both stores to the same state as a failure-free run.
"""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import DslTransform, RollingAgg
from repro.core.featurestore import FeatureStore
from repro.core.offline_store import CREATION_TS, EVENT_TS
from repro.data.sources import SyntheticEventSource

HOUR = 3_600_000
SEAMS = ("before_compute", "after_compute", "between_merges", "after_merges")


def _store(seed=0, online=True, offline=True) -> FeatureStore:
    fs = FeatureStore("chaos")
    src = SyntheticEventSource("tx", seed=seed, num_entities=12,
                               events_per_bucket=40)
    fs.register_source(src)
    fs.create_feature_set(
        FeatureSetSpec(
            name="act", version=1,
            entity=Entity("customer", ("entity_id",)),
            features=(Feature("s2", "float32"),),
            source_name="tx",
            transform=DslTransform("entity_id", "ts",
                                   [RollingAgg("s2", "amount", 2 * HOUR, "sum")]),
            timestamp_col="ts", source_lookback=2 * HOUR,
            materialization=MaterializationSettings(
                offline_enabled=offline, online_enabled=online,
                schedule_interval=HOUR,
            ),
        )
    )
    return fs


def _offline_fingerprint(fs) -> bytes:
    h = fs.offline.read("act", 1)
    order = np.lexsort((h[CREATION_TS], h[EVENT_TS], h["__key__"]))
    return h["s2"][order].tobytes() + h[EVENT_TS][order].tobytes()


@settings(max_examples=10, deadline=None)
@given(
    faults=st.lists(
        st.tuples(st.sampled_from(SEAMS), st.integers(1, 3)),
        min_size=0, max_size=6,
    ),
    hours=st.integers(3, 10),
)
def test_chaos_converges_to_failure_free_state(faults, hours):
    """Arm arbitrary fault patterns; after retries the stores must equal the
    failure-free run's stores exactly (same source is deterministic)."""
    clean = _store()
    clean.tick(now=hours * HOUR)

    chaotic = _store()
    for seam, times in faults:
        chaotic.faults.arm(seam, times)
    chaotic.tick(now=hours * HOUR)
    # jobs that exhausted their automatic retries leave timeline gaps; the
    # §4.5.2 'manual retry' path (repair) re-drives them to convergence
    for _ in range(4):
        if chaotic.scheduler.materialized_intervals("act", 1) == [
            (0, hours * HOUR)
        ]:
            break
        chaotic.repair("act", 1)

    assert _offline_fingerprint(chaotic) == _offline_fingerprint(clean)
    rep = chaotic.check_consistency("act", 1)
    assert rep.consistent, rep.summary()
    assert chaotic.scheduler.materialized_intervals("act", 1) == [
        (0, hours * HOUR)
    ]


def test_failure_between_merges_reaches_eventual_consistency():
    """The paper's exact §4.5.4 scenario: offline merge lands, online merge
    fails -> stores diverge -> retry converges them."""
    fs = _store()
    fs.faults.arm("between_merges", 1)
    fs.tick(now=2 * HOUR)
    fs.tick(now=2 * HOUR)  # retries the failed job
    rep = fs.check_consistency("act", 1)
    assert rep.consistent, rep.summary()


def test_bootstrap_offline_to_online_matches_always_on():
    """§4.5.5: enabling online late + bootstrap == online enabled all along."""
    always = _store(online=True)
    always.tick(now=6 * HOUR)

    late = _store(online=False)
    late.tick(now=6 * HOUR)
    n = late.enable_online("act", 1)
    assert n > 0

    ids = np.arange(12, dtype=np.int64)
    va, fa = always.get_online_features("act", 1, [ids])
    vl, fl = late.get_online_features("act", 1, [ids])
    np.testing.assert_array_equal(fa, fl)
    np.testing.assert_allclose(va[fa], vl[fl], rtol=1e-6)


def test_bootstrap_online_to_offline():
    """§4.5.5 reverse direction: offline enabled late gets online's records
    (latest-only — the documented asymmetry)."""
    fs = _store(online=True, offline=False)
    fs.tick(now=4 * HOUR)
    assert len(fs.offline.read("act", 1)) == 0
    n = fs.enable_offline("act", 1)
    assert n > 0
    h = fs.offline.read("act", 1)
    # exactly one record per live online id
    assert len(h) == len(np.unique(h["__key__"]))
    rep = fs.check_consistency("act", 1)
    assert rep.consistent


def test_fig5_semantics_exact():
    """The worked Fig.5 example: R0(t0), R1(t1), R2(t2), then R3 rewrites t1
    with a later creation_ts.  Offline keeps 4 records; online still serves
    R2 (greater event_ts wins over creation_ts)."""
    fs = _store()
    fs.tick(now=3 * HOUR)  # materialize t0..t2 equivalents
    spec = fs.registry.get_feature_set("act", 1)
    # backfill re-materializes an old window -> new creation_ts for same
    # event window (the R3 pattern)
    before = len(fs.offline.read("act", 1))
    fs.backfill("act", 1, start=0, end=1 * HOUR)
    h = fs.offline.read("act", 1)
    # offline: every (id, event_ts, creation_ts) kept — backfill adds records
    # only if creation differs; dedup guarantees no duplicates
    assert len(h) >= before
    keys = np.stack([h["__key__"], h[EVENT_TS], h[CREATION_TS]], axis=1)
    assert len(np.unique(keys, axis=0)) == len(h)
    # online: still the latest event_ts per id
    rep = fs.check_consistency("act", 1)
    assert rep.consistent
