"""Compile the store's device path for a described TPU v5e chip.

No chip is attached: ``topologies.get_topology_desc`` describes one and the
TPU compiler, which is installed, compiles for it.  This is what interpret
mode cannot show — the (8, 128) block rule, layouts and VMEM limits — so
each kernel of the main path compiles here compiled (``interpret=False``),
at the sizes ``chip_smoke.py`` runs: a 2^20-entity online table (16
partitions x 65,536 slots, 8 features), 2^21-row merge batches, a
6 x 2^20-row rolling window and offline history, a 65,536-row spine and
4096-id GETs.  The resident value plane's programs also compile at the
benchmark's table (16 x 2^18 slots) over the feature widths the store's
``device_width`` rule gives, and must carry no plane-sized temporary: at
a width the compiler stores feature-major, every call would first
relayout the whole plane.  The GET's search of the key index compiles at
that table too, and must do O(B log N) work, not O(N).

The topology is described inside a module-scoped fixture, never while a
module is imported, so every xdist worker collects the same tests and only
the worker that runs this file loads the TPU library.  Keep these tests in
this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.online_store import device_width
from repro.kernels.online_lookup.ops import gather_rows, lookup, merge_index
from repro.kernels.online_merge.ops import merge_at_slots
from repro.kernels.pit_join.kernel import pit_search_kernel_call
from repro.kernels.rolling_agg.kernel import rolling_sum_kernel_call
from repro.kernels.rolling_agg.ops import max_hist, rolling_sum_xla

PARTS, SLOTS, FEATS = 16, 1 << 16, 8  # online table after growth to ~2^20 ids
MERGE_BATCH = 1 << 21  # one 2-hour tick of 2^20 events per hour
ROLL_ROWS = 6 << 20  # the 6-hour transform window
HISTORY_ROWS = 6 << 20
SPINE = 65_536
HBM_BYTES = 16 * 2**30  # one v5e chip
BENCH_SLOTS = 1 << 18  # the benchmark's 3.9M records, 16 partitions
SERVE_BATCH, UPDATE_BATCH = 4096, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip; keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled, *, kernel: bool, plane_bytes: int = 0):
    """Fits one chip, has a Pallas kernel iff ``kernel``; given the value
    plane's bytes, its temporaries stay under 1% of them (no relayout)."""
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert used < HBM_BYTES, used
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    if plane_bytes:
        assert mem.temp_size_in_bytes < plane_bytes / 100, (
            mem.temp_size_in_bytes, plane_bytes
        )


def test_pit_join_compiles(one_chip):
    query = _spec(one_chip, (SPINE, 1))
    compiled = pit_search_kernel_call.lower(
        _spec(one_chip, (HISTORY_ROWS // 128, 128)), query, query, query,
        q_block=512, table_rows_per_block=8, interpret=False,
    ).compile()
    _check(compiled, kernel=True)


@pytest.mark.parametrize("depth", ["shallowest", "deepest"])
def test_rolling_agg_compiles(one_chip, depth):
    """The shallowest history bucket and the deepest one ``ops.max_hist``
    lets a one-column window group take before it switches to XLA."""
    hist = 128 if depth == "shallowest" else max_hist(1)
    compiled = rolling_sum_kernel_call.lower(
        _spec(one_chip, (8, ROLL_ROWS), jnp.float32),
        _spec(one_chip, (1, ROLL_ROWS)),
        block_rows=256, hist=hist, interpret=False,
    ).compile()
    _check(compiled, kernel=True)


def test_rolling_sum_xla_compiles(one_chip):
    """The XLA rolling sum (the kernel's reference and its deep-span path)
    at the full window: its scans are blocked, so this takes seconds."""
    compiled = rolling_sum_xla.lower(
        _spec(one_chip, (ROLL_ROWS, 1), jnp.float32), _spec(one_chip, (ROLL_ROWS,))
    ).compile()
    _check(compiled, kernel=False)


def test_merge_at_slots_compiles(one_chip):
    """A whole materialization tick: the 2^21-row batch is larger than the
    table, so its temporaries are batch-sized; the plane check is made at
    serving batches in ``test_value_plane_programs_copy_free``."""
    plane = _spec(one_chip, (PARTS, SLOTS))
    batch = _spec(one_chip, (MERGE_BATCH,))
    compiled = merge_at_slots.lower(
        plane, plane, plane, plane,
        _spec(one_chip, (PARTS, SLOTS, FEATS), jnp.float32),
        batch, batch,
        _spec(one_chip, (MERGE_BATCH,), jnp.bool_),
        batch, batch,
        _spec(one_chip, (2,)),
        _spec(one_chip, (MERGE_BATCH, FEATS), jnp.float32),
    ).compile()
    _check(compiled, kernel=False)
    # the five table planes are donated, and rewritten in place
    plane_bytes = 4 * PARTS * SLOTS * 4 + PARTS * SLOTS * FEATS * 4
    assert compiled.memory_analysis().alias_size_in_bytes == plane_bytes


def test_gather_rows_compiles(one_chip):
    plane = _spec(one_chip, (PARTS, SLOTS))
    coords = _spec(one_chip, (4096,))
    compiled = gather_rows.lower(
        _spec(one_chip, (PARTS, SLOTS, FEATS), jnp.float32),
        plane, plane, coords, coords,
    ).compile()
    _check(compiled, kernel=False, plane_bytes=PARTS * SLOTS * FEATS * 4)


# 3 and 8 (chip_smoke) keep their width, as do 40 and 128; 6 widens to 8,
# 60 and 100 to 128, 250 (the benchmark's) to 256 and 300 to 384.  From 9
# to 56 the merge still copies the plane once (see ``device_width``), so 40
# is checked for the gather alone; so is 3, whose 50 MB plane is too small
# for 1% of it to hold the merge's 1.2 MB of batch temporaries.
_PLANE_CASES = [
    (program, features)
    for features in (3, 6, 8, 40, 60, 100, 128, 250, 300)
    for program in ("gather_rows", "merge_at_slots")
    if (program, features) not in {("merge_at_slots", 40), ("merge_at_slots", 3)}
]


@pytest.mark.parametrize(("program", "features"), _PLANE_CASES)
def test_value_plane_programs_copy_free(one_chip, program, features):
    width = device_width(features)
    values = _spec(one_chip, (PARTS, BENCH_SLOTS, width), jnp.float32)
    plane = _spec(one_chip, (PARTS, BENCH_SLOTS))
    if program == "gather_rows":
        coords = _spec(one_chip, (SERVE_BATCH,))
        lowered = gather_rows.lower(values, plane, plane, coords, coords)
    else:
        batch = _spec(one_chip, (UPDATE_BATCH,))
        lowered = merge_at_slots.lower(
            plane, plane, plane, plane, values,
            batch, batch,
            _spec(one_chip, (UPDATE_BATCH,), jnp.bool_),
            batch, batch,
            _spec(one_chip, (2,)),
            _spec(one_chip, (UPDATE_BATCH, width), jnp.float32),
        )
    _check(
        lowered.compile(), kernel=False, plane_bytes=PARTS * BENCH_SLOTS * width * 4
    )


@pytest.mark.parametrize("batch", [128, 256, SERVE_BATCH])
def test_index_search_compiles_without_table_work(one_chip, batch):
    """The GET's key search over the benchmark's index (16 x 2^18 entries)
    and the gather it feeds.  The search does O(B log N) operations, at
    most 64 a query and step, and holds no temporary: one that compared
    every entry (``searchsorted(method="compare_all")``) does B x N, and
    any pass over the index at least N, more than the bound at the small
    batches.  The TPU cost model charges each gather its
    whole operand as bytes accessed, so bytes cannot tell these apart."""
    n = PARTS * BENCH_SLOTS
    index = _spec(one_chip, (n,))
    compiled = lookup.lower(
        index, index, index, index, _spec(one_chip, (2, batch))
    ).compile()
    _check(compiled, kernel=False)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["flops"] < 64 * batch * n.bit_length(), (cost["flops"], n)
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    values = _spec(one_chip, (PARTS, BENCH_SLOTS, device_width(250)), jnp.float32)
    plane = _spec(one_chip, (PARTS, BENCH_SLOTS))
    coords = _spec(one_chip, (batch,))
    _check(
        gather_rows.lower(values, plane, plane, coords, coords).compile(),
        kernel=False, plane_bytes=PARTS * BENCH_SLOTS * device_width(250) * 4,
    )


def test_index_merge_compiles_in_place(one_chip):
    """Merging a bucket of inserted keys into the benchmark's index reuses
    the donated planes: no second index is allocated beside them."""
    n = PARTS * BENCH_SLOTS
    index = _spec(one_chip, (n,))
    compiled = merge_index.lower(
        index, index, index, index, _spec(one_chip, (4, 128))
    ).compile()
    _check(compiled, kernel=False)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * n * 4
    assert mem.temp_size_in_bytes < n * 4, mem.temp_size_in_bytes
