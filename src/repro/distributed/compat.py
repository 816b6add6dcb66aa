"""Mesh and shard_map helpers shared by the distributed runtime.

Everything that builds a mesh or enters one goes through this module, so
the choice of axis types lives in exactly one place.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

shard_map = jax.shard_map
axis_size = jax.lax.axis_size
use_mesh = jax.set_mesh


def shard_map_nocheck(f, mesh, in_specs, out_specs):
    """`shard_map` with replication checking off."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """`jax.make_mesh` with every axis Auto: the sharding code places
    arrays with `NamedSharding` and lets the compiler propagate, which
    Explicit axes (the default `jax.make_mesh` picks) would reject."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_shapes))
