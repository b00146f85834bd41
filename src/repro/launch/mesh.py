"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before first jax use.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Mesh of ``shape`` over ``axes``, every axis Auto-sharded."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serving_mesh(num_shards: int = 0, axis: str = "model"):
    """1-D mesh for the mesh-backed ServingEngine: ``num_shards`` devices on
    the channel ('model') axis, one HashMem shard each.  0 -> all devices.
    """
    n = num_shards or len(jax.devices())
    assert n <= len(jax.devices()), \
        f"serving mesh wants {n} devices, have {len(jax.devices())}"
    return make_mesh((n,), (axis,))
