"""Production mesh construction.

A FUNCTION (never a module-level constant) so importing this module never
touches jax device state — the 512-device dry-run and the 1-device test
processes both import it safely.

Axis semantics:
  pod   — one TPU v5e pod per index; the feature store's "region" axis
          (geo-replication = replicate over pod; cross-region access =
          collectives over pod).  DCN-connected.
  data  — data parallel + FSDP parameter sharding within a pod (ICI).
  model — tensor/expert parallel (ICI).

Elastic scaling: any (pod, data, model) factorization is accepted; sharding
rules reference axis NAMES only, and checkpoints reshard on load.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "batch_axes", "axis_size"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Elastic variant for tests (e.g. (2,2,2) on 8 host devices).

    Axes are Auto: the models place activations with
    ``with_sharding_constraint`` and let GSPMD propagate the rest, which
    ``jax.make_mesh``'s default Explicit axes reject."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the global batch (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def axis_size(mesh, *names: str) -> int:
    out = 1
    for n in names:
        if n in mesh.axis_names:
            out *= mesh.shape[n]
    return out
