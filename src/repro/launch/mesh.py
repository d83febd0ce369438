"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Every mesh here has Auto axes: the training and serving code shards by
``NamedSharding`` placement plus ``with_sharding_constraint`` and lets the
partitioner resolve the rest.  ``jax.make_mesh`` defaults to Explicit axes,
under which sharding-in-types rejects ops whose output sharding it cannot
infer (e.g. the embedding gather of a vocab-sharded table).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary Auto-axis mesh (tests use small host-device meshes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading 2-pod axis."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_local_mesh():
    """Whatever devices exist, as a (data, model) mesh with model = 1."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
