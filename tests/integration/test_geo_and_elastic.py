"""Geo fail-over (§3.1.2, §4.1.2) + elastic mesh resharding — integration.

The elastic test runs in a subprocess because the 8-device host platform
flag must be set before jax initializes (the test process runs 1-device).
"""

import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.assets import Entity, Feature, FeatureSetSpec, MaterializationSettings
from repro.core.dsl import DslTransform, RollingAgg
from repro.core.featurestore import FeatureStore
from repro.core.regions import (
    ComplianceError,
    GeoTopology,
    Region,
    RegionDownError,
    ReplicationPolicy,
)
from repro.data.sources import SyntheticEventSource

HOUR = 3_600_000


def _geo_store(policy, fenced=False):
    topo = GeoTopology(
        regions={
            "westus2": Region("westus2", geo_fenced=fenced),
            "eastus": Region("eastus"),
        },
        local_latency_ms=1.0, cross_region_latency_ms=60.0,
    )
    fs = FeatureStore("geo", region="westus2", topology=topo, replication=policy)
    src = SyntheticEventSource("tx", num_entities=8, events_per_bucket=30)
    fs.register_source(src)
    fs.create_feature_set(
        FeatureSetSpec(
            name="act", version=1,
            entity=Entity("customer", ("entity_id",)),
            features=(Feature("s2", "float32"),),
            source_name="tx",
            transform=DslTransform("entity_id", "ts",
                                   [RollingAgg("s2", "amount", HOUR, "sum")]),
            timestamp_col="ts", source_lookback=HOUR,
            materialization=MaterializationSettings(
                offline_enabled=True, online_enabled=True, schedule_interval=HOUR
            ),
        )
    )
    return fs


def test_failover_resumes_without_data_loss():
    fs = _geo_store(ReplicationPolicy.GEO_REPLICATED)
    fs.tick(now=4 * HOUR)
    fs.geo.add_replica("eastus")
    state = fs.scheduler_state()

    fs.geo.mark_down("westus2")
    assert fs.geo.failover() == "eastus"
    # reads keep working (served by the replica)
    serving, _ = fs.geo.route_read("westus2")
    assert serving == "eastus"

    # the promoted region restores control-plane state and resumes the
    # timeline exactly where it stopped — no holes, no re-materialization
    fs.restore_scheduler(state)
    fs.tick(now=7 * HOUR)
    assert fs.scheduler.materialized_intervals("act", 1) == [(0, 7 * HOUR)]
    assert fs.check_consistency("act", 1).consistent


def test_cross_region_access_no_replica_down_raises():
    fs = _geo_store(ReplicationPolicy.CROSS_REGION_ACCESS)
    fs.geo.mark_down("westus2")
    with pytest.raises(RegionDownError):
        fs.geo.route_read("eastus")


def test_geo_fencing_blocks_replication():
    fs = _geo_store(ReplicationPolicy.GEO_REPLICATED, fenced=True)
    with pytest.raises(ComplianceError):
        fs.geo.add_replica("eastus")


def test_hub_and_spoke_cross_subscription_sharing():
    """§4.1.1/§4.1.2: spokes in other subscriptions/regions resolve assets
    through the hub; cross-region reads require an explicit grant."""
    fs = _geo_store(ReplicationPolicy.CROSS_REGION_ACCESS)
    from repro.core.registry import RegistryError, Workspace

    spoke = Workspace("ml-team-b", subscription="sub-B", region="eastus")
    fs.registry.attach_workspace(spoke)
    # no grant yet -> cross-region access denied
    with pytest.raises(RegistryError):
        fs.registry.resolve_for_workspace("ml-team-b", "act", 1)
    fs.registry.grant_access("ml-team-b", "act")
    spec, mode = fs.registry.resolve_for_workspace("ml-team-b", "act", 1)
    assert spec.name == "act" and mode == "cross-region"
    # local spoke resolves without a grant
    local = Workspace("ml-team-a", subscription="sub-A", region="westus2")
    fs.registry.attach_workspace(local)
    _, mode = fs.registry.resolve_for_workspace("ml-team-a", "act", 1)
    assert mode == "local"


_ELASTIC_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys, tempfile
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.manager import restore_checkpoint, save_checkpoint
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import TrainState, make_train_step
    from repro.models import api
    from repro.models import sharding as shd
    from repro.models.pspec import activation_mesh
    from repro.optim.adamw import adamw
    import dataclasses

    cfg = get_config("qwen1.5-4b", reduced=True)
    cfg = dataclasses.replace(cfg, param_dtype="float32", compute_dtype="float32")
    opt = adamw(lr=1e-3)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    state = TrainState.create(params, opt)
    batch = api.make_dummy_batch(cfg, 4, 16)
    step = make_train_step(cfg, opt)

    def place(state, mesh):
        pspec = shd.param_specs(state.params, cfg, mesh)
        sspec = TrainState(pspec, shd.opt_state_specs(state.opt, pspec), P())
        shards = jax.tree.map(lambda s: NamedSharding(mesh, s), sspec,
                              is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(state, shards), shards

    # run 2 steps on a 4x2 mesh, checkpoint
    mesh_a = make_mesh((4, 2), ("data", "model"))
    state_a, shards_a = place(state, mesh_a)
    with mesh_a, activation_mesh(mesh_a):
        jit_a = jax.jit(step)
        state_a, _ = jit_a(state_a, batch)
        state_a, _ = jit_a(state_a, batch)
    d = tempfile.mkdtemp()
    save_checkpoint(d, 2, state_a)

    # restore onto a DIFFERENT (2x4) mesh and continue
    mesh_b = make_mesh((2, 4), ("data", "model"))
    template = jax.eval_shape(lambda: TrainState.create(
        api.init_params(jax.random.PRNGKey(0), cfg), opt))
    _, shards_b = place(jax.tree.map(np.zeros_like,
                                     jax.device_get(state_a)), mesh_b)
    state_b, _ = restore_checkpoint(d, 2, template, shardings=shards_b)
    with mesh_b, activation_mesh(mesh_b):
        state_b, metrics_b = jax.jit(step)(state_b, batch)

    # reference: continue on the original mesh
    with mesh_a, activation_mesh(mesh_a):
        state_ref, metrics_ref = jit_a(state_a, batch)

    out = {
        "loss_resharded": float(metrics_b["total_loss"]),
        "loss_reference": float(metrics_ref["total_loss"]),
    }
    print("ELASTIC_RESULT " + json.dumps(out))
    """
)


@pytest.mark.proc
def test_elastic_reshard_subprocess():
    """Checkpoint saved from a (4,2) mesh restores onto a (2,4) mesh and the
    next step's loss matches the non-resharded continuation."""
    proc = subprocess.run(
        [sys.executable, "-c", _ELASTIC_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("ELASTIC_RESULT")]
    assert line, proc.stdout
    res = json.loads(line[0].split(" ", 1)[1])
    np.testing.assert_allclose(
        res["loss_resharded"], res["loss_reference"], rtol=1e-5
    )
