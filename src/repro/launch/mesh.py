"""Production mesh construction.

Single pod: (data=16, model=16) == 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) == 512 chips; the `pod` axis is an
outer data-parallel axis whose gradient reduction crosses the inter-pod
links (DCN/ICI), which is exactly what the multi-pod dry-run must prove
shards.

`make_production_mesh` is a function (not a module constant) so importing
this module never touches jax device state; only launch/dryrun.py sets
--xla_force_host_platform_device_count before calling it.

In the paper's vocabulary the `model` axis is the NODE GROUP of hybrid
parallelism: model parallelism inside a group of 16, data parallelism across
the 16 (or 2x16) groups.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """Every axis Auto (jax.make_mesh defaults to Explicit): the partitioner
    places what the step leaves open, and shard_map regions pick their
    manual axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (possibly fake) devices exist locally."""
    return _auto_mesh((data, model), ("data", "model"))


def make_hier_mesh(node: int = 2, local: int = 4,
                   model: int = 1) -> jax.sharding.Mesh:
    """Factored data-parallel mesh for hierarchical collectives.

    ``node`` is the inter-node (fabric) axis, ``local`` the intra-node
    (high-bandwidth) axis; gradient reduction runs two-level over
    ("node", "local"). ``model=1`` keeps a model axis for hybrid plans.
    """
    if model > 1:
        return _auto_mesh((node, local, model), ("node", "local", "model"))
    return _auto_mesh((node, local), ("node", "local"))


def n_chips(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
