"""The one route to the jax sharding APIs the engines use.

Everything that builds a mesh, enters one, or maps a function over one
goes through here, so the settings the engines rely on (replication
checking off, Auto axis types) live in one place.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax

__all__ = ["shard_map", "make_mesh", "use_mesh"]


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (per-node
    randomness makes outputs intentionally non-replicated)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices=None):
    """``jax.make_mesh`` with Auto axis types (jax defaults to Explicit
    sharding, under which the engine's untyped specs would be
    rejected).  ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), devices=devices)


def use_mesh(mesh) -> Any:
    """Context manager making ``mesh`` the ambient mesh."""
    return jax.set_mesh(mesh)
