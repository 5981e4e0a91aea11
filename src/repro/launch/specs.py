"""ShapeDtypeStruct stand-ins for every model input — the dry-run's
shardable, zero-allocation input descriptions — plus spec builders for
params / decode caches.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import data_axes_of, num_nodes
from repro.models.common import ArchConfig
from repro.models.registry import InputShape

Array = jax.Array


def _lead(data_axes) -> Any:
    return data_axes[0] if len(data_axes) == 1 else tuple(data_axes)


def train_input_specs(cfg: ArchConfig, shape: InputShape, mesh: Mesh
                      ) -> Tuple[Dict[str, jax.ShapeDtypeStruct],
                                 Dict[str, P]]:
    """Node-major training batch {tokens/embeds/targets}: leaves
    (n_nodes, per_node, ...)."""
    data_axes = data_axes_of(mesh)
    n = num_nodes(mesh)
    if shape.global_batch % n:
        raise ValueError(f"batch {shape.global_batch} % nodes {n} != 0")
    b = shape.global_batch // n
    t = shape.seq_len
    dt = cfg.param_dtype
    lead = _lead(data_axes)
    sds, specs = {}, {}
    if cfg.frontend == "audio":
        sds["embeds"] = jax.ShapeDtypeStruct((n, b, t, cfg.d_model), dt)
        sds["targets"] = jax.ShapeDtypeStruct((n, b, t), jnp.int32)
        specs["embeds"] = P(lead, None, None, None)
        specs["targets"] = P(lead, None, None)
    elif cfg.frontend == "vision":
        sds["embeds"] = jax.ShapeDtypeStruct(
            (n, b, cfg.frontend_tokens, cfg.d_model), dt)
        sds["tokens"] = jax.ShapeDtypeStruct((n, b, t), jnp.int32)
        specs["embeds"] = P(lead, None, None, None)
        specs["tokens"] = P(lead, None, None)
    else:
        sds["tokens"] = jax.ShapeDtypeStruct((n, b, t), jnp.int32)
        specs["tokens"] = P(lead, None, None)
    return sds, specs


def prefill_input_specs(cfg: ArchConfig, shape: InputShape, mesh: Mesh
                        ) -> Tuple[Dict[str, jax.ShapeDtypeStruct],
                                   Dict[str, P]]:
    """Inference prefill batch: global (B, T) sharded over the data axes."""
    data_axes = data_axes_of(mesh)
    lead = _lead(data_axes)
    B, t = shape.global_batch, shape.seq_len
    n = num_nodes(mesh)
    blead = lead if B % n == 0 else None
    dt = cfg.param_dtype
    sds, specs = {}, {}
    if cfg.frontend == "audio":
        sds["embeds"] = jax.ShapeDtypeStruct((B, t, cfg.d_model), dt)
        sds["targets"] = jax.ShapeDtypeStruct((B, t), jnp.int32)
        specs["embeds"] = P(blead, None, None)
        specs["targets"] = P(blead, None)
    elif cfg.frontend == "vision":
        sds["embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.d_model), dt)
        sds["tokens"] = jax.ShapeDtypeStruct((B, t), jnp.int32)
        specs["embeds"] = P(blead, None, None)
        specs["tokens"] = P(blead, None)
    else:
        sds["tokens"] = jax.ShapeDtypeStruct((B, t), jnp.int32)
        specs["tokens"] = P(blead, None)
    return sds, specs


def decode_state_specs(state_shapes: Any, mesh: Mesh,
                       num_layers: Optional[int] = None) -> Any:
    """Heuristic sharding for DecodeState leaves.

    Leaves look like (L, B, S, kvH, hd) (scanned), (B, S, ...) (unrolled),
    or SSM states (B, di, N) / (B, H, hd, hd).  We shard the batch dim
    over 'data' when divisible and the largest remaining dim over 'model'
    when divisible; scalars replicate.

    ``num_layers`` guards the stacked-layer dim: a leading dim equal to
    the layer count is NEVER treated as batch.  (Perf iteration Q1,
    EXPERIMENTS.md §Perf: qwen's 80-layer cache had dim0 % 16 == 0 and
    was mis-sharded over 'data', forcing per-layer cache regathers —
    a 100x collective-term regression the roofline exposed.)
    """
    data_axes = data_axes_of(mesh)
    lead = _lead(data_axes)
    n_data = num_nodes(mesh)
    n_model = mesh.shape["model"]

    def spec_of(leaf):
        if not hasattr(leaf, "shape") or leaf.ndim == 0:
            return P()
        dims = [None] * leaf.ndim
        batch_dim = None
        for cand in (0, 1):
            if cand >= leaf.ndim:
                continue
            if cand == 0 and num_layers is not None \
                    and leaf.ndim >= 3 and leaf.shape[0] == num_layers:
                continue   # stacked-layer dim, not batch
            if leaf.shape[cand] % n_data == 0 and leaf.shape[cand] >= n_data:
                batch_dim = cand
                break
        if batch_dim is not None:
            dims[batch_dim] = lead
        rest = [i for i in range(leaf.ndim) if i != batch_dim]
        rest.sort(key=lambda i: -leaf.shape[i])
        for i in rest:
            if leaf.shape[i] % n_model == 0 and leaf.shape[i] >= n_model:
                dims[i] = "model"
                break
        return P(*dims)

    return jax.tree.map(spec_of, state_shapes)


def paged_state_specs(state_shapes: Any, mesh: Mesh,
                      num_layers: Optional[int] = None) -> Any:
    """Sharding for PagedDecodeState trees (DESIGN.md §11).  Walks by
    CACHE TYPE, not shape heuristics: pool leaves (KVCache (…, NP, P,
    kvH * hd), MLACache (…, NP, P, r)) replicate their page dims over
    'data' (any slot reads any page — sharding pages over data would
    all-gather the pool every step) and shard only the trailing
    feature dim over 'model'; everything else (recurrent SSM
    states, the (B, M) table, (B,) lens) takes the dense
    :func:`decode_state_specs` batch-over-'data' rule."""
    from repro.models.model import map_cache_tree
    n_model = mesh.shape["model"]

    def pool_spec(leaf):
        dims = [None] * leaf.ndim
        if leaf.shape[-1] % n_model == 0 and leaf.shape[-1] >= n_model:
            dims[-1] = "model"
        return P(*dims)

    def attn_spec(c):
        return type(c)(*(pool_spec(leaf) for leaf in c))

    return map_cache_tree(
        state_shapes, on_attention=attn_spec,
        on_leaf=lambda c: decode_state_specs(c, mesh,
                                             num_layers=num_layers))


def to_shardings(tree_of_specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda x: isinstance(x, P))
