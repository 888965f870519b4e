"""The repo's one home for mesh construction and ``shard_map``.

Every mesh and every ``shard_map`` in the repo routes through this module, so
the axis types and the replication-check setting are chosen in one place.
Targets jax 0.9.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_names)),
    )


def get_mesh():
    """The ambient mesh set by ``jax.set_mesh``, or None when unset/empty."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
