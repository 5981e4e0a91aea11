"""Meshes.

Defined as FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — required because the dry-run
and the ``--smoke`` CLIs must set XLA_FLAGS before any jax
initialization.

The launch CLIs build :func:`make_device_mesh` from the devices
present (one DASHA-PP node per device); the 16x16 pod layout is built
only where it is asked for by name (``launch.train --mesh pod``, the
dry-run).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod (v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_device_mesh(devices=None) -> Mesh:
    """``(n, 1)`` over ``("data", "model")`` from the ``n`` devices
    present: ``(1, 1)`` on one chip, ``(4, 1)`` on a four-chip host."""
    devs = list(jax.devices() if devices is None else devices)
    return make_mesh((len(devs), 1), ("data", "model"), devices=devs)


def data_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def num_nodes(mesh: Mesh) -> int:
    n = 1
    for a in data_axes_of(mesh):
        n *= mesh.shape[a]
    return n


def make_host_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Small mesh for CPU tests/examples (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=data*model)."""
    return make_mesh((data, model), ("data", "model"))
