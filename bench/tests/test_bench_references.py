"""The plain references against the program's own paths at tiny sizes, and
the bfloat16 rounding that every control uses."""

from __future__ import annotations

import numpy as np

from bench.reference.kv_latest_wins import LatestWins, record_values
from bench.reference.precision import as_bfloat16


def test_bfloat16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, -2.5], np.float32)
    got = as_bfloat16(x)
    assert got.tolist() == [1.0, 1.0, 1.0078125, -2.5]
    vals = record_values(7, np.arange(100), np.zeros(100), 16)
    assert (as_bfloat16(vals) != vals).mean() > 0.99


def test_record_values_device_twin():
    """The bulk loader's jitted values equal the reference's NumPy values."""
    from bench.generators import kv_open_loop

    seed = 2**33 + 3
    block = np.asarray(kv_open_loop._device_values(seed, 5)(1000))
    ref = record_values(seed, np.arange(1000, 1010), np.zeros(10), 5)
    assert block[:, :10].T.tobytes() == ref.tobytes()


def test_latest_wins_versions():
    lw = LatestWins()
    assert lw.apply(5) == 1
    assert lw.apply(6) == 2
    assert lw.apply(5) == 3
    assert lw.version_at([5, 5, 5, 6, 7], [0, 1, 3, 3, 3]).tolist() == [0, 1, 3, 2, 0]
    assert lw.versions_between(5, 1, 3) == [3]
    keys, vers = lw.last_versions()
    assert keys.tolist() == [5, 6] and vers.tolist() == [3, 2]


def test_kv_reference_matches_online_store():
    """Rows merged through the online store's kernel engine read back as the
    reference regenerates them, latest write winning."""
    from bench.generators.kv_open_loop import FEATURE_SET, build_store
    from repro.core.table import Table

    class _Run:
        workload = "t"
        config = {"online_partitions": 16, "features": 4,
                  "serving": {"cache_capacity": 16, "max_batch_keys": 64,
                              "deadline_ms": None}}

    fs = build_store(_Run)
    keys = np.arange(300, dtype=np.int64)
    vals = record_values(1, keys, np.zeros(300), 4)
    cols = {"user_id": keys, "ts": np.zeros(300, np.int64)}
    cols.update({f"field{j}": vals[:, j] for j in range(4)})
    fs.write_batch(*FEATURE_SET, Table(cols), creation_ts=1)
    upd = record_values(1, [7], [1], 4)
    cols = {"user_id": np.array([7]), "ts": np.array([2])}
    cols.update({f"field{j}": upd[:, j] for j in range(4)})
    fs.write_batch(*FEATURE_SET, Table(cols))
    got, found = fs.get_online_features(*FEATURE_SET, [keys])
    lw = LatestWins()
    lw.apply(7)
    want = record_values(1, keys, lw.version_at(keys, [1] * 300), 4)
    assert found.all() and got.tobytes() == want.tobytes()


def test_table_capacity_is_what_doubling_reaches():
    """The loader's pre-sized table has the shape the store's own doubling
    reaches for the same load."""
    from bench.generators.kv_open_loop import FEATURE_SET, build_store, table_capacity
    from repro.core.table import Table

    class _Run:
        workload = "t"
        config = {"online_partitions": 4, "features": 2,
                  "serving": {"cache_capacity": 16, "max_batch_keys": 64,
                              "deadline_ms": None}}

    n = 3000
    fs = build_store(_Run)
    keys = np.arange(n, dtype=np.int64)
    cols = {"user_id": keys, "ts": np.zeros(n, np.int64),
            "field0": np.zeros(n, np.float32), "field1": np.ones(n, np.float32)}
    fs.write_batch(*FEATURE_SET, Table(cols), creation_ts=1)
    grown = fs.online._tables[FEATURE_SET].keys_lo.shape[1]
    assert grown > 256
    assert table_capacity(n, 4, 256) == grown
