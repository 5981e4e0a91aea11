"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights, hands them to the program and to the
plain reference alike, so the reference takes nothing the program made.
The layout is the program's stacked-layer dict; :func:`check_layout`
holds the program's own parameter shapes against it.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


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


def embedding_rows(model: Dict) -> int:
    return int(model.get("embedding_rows", model["vocab_size"]))


def layout(model: Dict) -> Dict:
    """Shapes of every weight, in the program's stacked layout."""
    L, d = model["num_hidden_layers"], model["hidden_size"]
    H, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or d // H
    f = model["intermediate_size"]
    return {
        "embed": (embedding_rows(model), d),
        "final_norm": (d,),
        "layers": {
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, kv * hd),
                     "wv": (L, d, kv * hd), "wo": (L, H * hd, d)},
            "ln1": (L, d), "ln2": (L, d),
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        },
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make(key: jax.Array, model: Dict, dtype) -> Dict:
    """Normal weights: embedding at the configuration's
    ``embedding_std`` (0.02 where it states none), matrices at
    1/sqrt(fan-in), norm scales at 1 + 0.1 N(0, 1) (so a path that
    ignores a scale shows).  Call under ``jax.jit``."""
    shapes = layout(model)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes,
                                                  is_leaf=_is_shape)[0]]
    out = []
    for i, (shape, path) in enumerate(zip(leaves, paths)):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, dtype)
        if "embed" in path:
            w = z * jnp.asarray(model.get("embedding_std", 0.02), dtype)
        elif len(shape) <= 2 and ("ln" in path or "norm" in path):
            w = (1 + 0.1 * z.astype(jnp.float32)).astype(dtype)
        else:
            w = z * jnp.asarray(1.0 / math.sqrt(shape[-2]), dtype)
        out.append(w.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def check_layout(program_shapes, model: Dict) -> None:
    """Raise unless the program's parameter tree has exactly the
    layout's keys and shapes."""
    ours = layout(model)
    got = jax.tree.map(lambda s: tuple(s.shape), program_shapes)
    if jax.tree.structure(got, is_leaf=_is_shape) != \
            jax.tree.structure(ours, is_leaf=_is_shape) or \
            jax.tree.leaves(got, is_leaf=_is_shape) != \
            jax.tree.leaves(ours, is_leaf=_is_shape):
        raise ValueError(f"the program's parameters {got} differ from the "
                         f"configuration's layout {ours}")


def leaf_sizes(model: Dict) -> Tuple[int, ...]:
    return tuple(math.prod(s) for s in
                 jax.tree.leaves(layout(model), is_leaf=_is_shape))
