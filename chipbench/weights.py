"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights, hands them to the program and to the
plain reference alike, so the reference takes nothing the program made.
The layout and each leaf's init are the architecture's
(``chipbench/archs/<model_type>.py``, the ``arch`` argument; where
:func:`make` or :func:`leaf_sizes` is given none, this checkout's module
for the model's ``model_type``); :func:`check_layout` holds the
program's own parameter shapes against the layout.
"""
from __future__ import annotations

import math
from types import ModuleType
from typing import Dict, Optional, Tuple

import jax

from chipbench import harness


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number, beyond the 32 bits ``jax.random.key``
    takes in one piece."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.key(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)


def stream(seed: int, name: str) -> jax.Array:
    """Independent key streams of one seed: ``weights``, ``data``,
    ``rounds``."""
    return jax.random.fold_in(seed_key(seed),
                              {"weights": 1, "data": 2, "rounds": 3}[name])


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _arch(model: Dict, arch: Optional[ModuleType]) -> ModuleType:
    return harness.load_arch(model) if arch is None else arch


def make(key: jax.Array, model: Dict, dtype,
         arch: Optional[ModuleType] = None) -> Dict:
    """Every leaf of the architecture's layout from its own standard
    normal draw (leaf ``i`` in tree order from ``fold_in(key, i)``),
    shaped by the architecture's ``init_leaf``.  Call under
    ``jax.jit``."""
    arch = _arch(model, arch)
    shapes = arch.layout(model)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes,
                                                  is_leaf=_is_shape)[0]]
    out = []
    for i, (shape, path) in enumerate(zip(leaves, paths)):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, dtype)
        w = arch.init_leaf(path, shape, z, model, dtype)
        out.append(w.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def check_layout(program_shapes, model: Dict, arch: ModuleType) -> None:
    """Raise unless the program's parameter tree has exactly the
    layout's keys and shapes."""
    ours = arch.layout(model)
    got = jax.tree.map(lambda s: tuple(s.shape), program_shapes)
    if jax.tree.structure(got, is_leaf=_is_shape) != \
            jax.tree.structure(ours, is_leaf=_is_shape) or \
            jax.tree.leaves(got, is_leaf=_is_shape) != \
            jax.tree.leaves(ours, is_leaf=_is_shape):
        raise ValueError(f"the program's parameters {got} differ from the "
                         f"configuration's layout {ours}")


def leaf_sizes(model: Dict, arch: Optional[ModuleType] = None
               ) -> Tuple[int, ...]:
    return tuple(math.prod(s) for s in
                 jax.tree.leaves(_arch(model, arch).layout(model),
                                 is_leaf=_is_shape))
