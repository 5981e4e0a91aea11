"""BlockRandK compress as a Pallas TPU kernel: gather K random
(8x128-aligned) blocks out of a grad-sized vector, scaled for
unbiasedness.

Why a kernel (DESIGN.md §6): XLA lowers a gather of K blocks from an
(nb, bs) array on TPU as either a full-array dynamic-slice loop or a
one-hot matmul — both touch O(nb*bs) HBM.  With scalar-prefetch
(`PrefetchScalarGridSpec`), the block indices land in SMEM before the
body runs and the kernel's BlockSpec index_map *is* the gather: only the
K selected blocks are ever read from HBM — O(K*bs) traffic, the whole
point of RandK compression.

The companion scatter (server-side decompress/accumulate) has the same
structure with input/output roles swapped; implemented here as
``block_scatter_pallas`` with `input_output_aliasing` so the base buffer
is updated in place.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANES = 128


def block_view(bs: int) -> Tuple[int, int]:
    """The (rows, lanes) tile one ``bs``-sized block is viewed as, so a
    block's BlockSpec satisfies the TPU tiling rule: the last two block
    dims must be multiples of (8, 128) or equal the array's own dims.
    An ``(nb, bs)`` array with ``(1, bs)`` row blocks breaks it (a
    second-minor block dim of 1 over ``nb`` rows); viewed as
    ``(nb, rows, lanes)`` with ``(1, rows, lanes)`` blocks, the last two
    block dims are the array's own.  Lane-aligned blocks use full
    128-lane rows; any other size is one row of ``bs``."""
    if bs % LANES == 0:
        return bs // LANES, LANES
    return 1, bs


def block_spec(bs: int, index_map) -> "pl.BlockSpec":
    """BlockSpec of one block in the :func:`block_view` layout."""
    rows, lanes = block_view(bs)
    return pl.BlockSpec((1, rows, lanes), index_map)


def _gather_kernel(idx_ref, x_ref, out_ref, *, scale: float):
    # x_ref block is chosen by the index_map via idx_ref (scalar prefetch);
    # the body just scales and copies.
    out_ref[...] = x_ref[...] * scale


@functools.partial(jax.jit, static_argnames=("k_blocks", "scale",
                                             "interpret"))
def block_gather_pallas(x_blocks: Array, block_idx: Array, *, k_blocks: int,
                        scale: float, interpret: bool = True) -> Array:
    """x_blocks: (nb, bs) f32; block_idx: (k_blocks,) int32 ->
    (k_blocks, bs) = x_blocks[block_idx] * scale."""
    nb, bs = x_blocks.shape
    rows, lanes = block_view(bs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k_blocks,),
        in_specs=[block_spec(bs, lambda i, idx: (idx[i], 0, 0))],
        out_specs=block_spec(bs, lambda i, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k_blocks, rows, lanes),
                                       x_blocks.dtype),
        interpret=interpret,
    )(block_idx, x_blocks.reshape(nb, rows, lanes))
    return out.reshape(k_blocks, bs)


def _scatter_kernel(idx_ref, vals_ref, base_ref, out_ref):
    # grid step i accumulates vals[i] into the block idx[i] of the base;
    # out aliases base so untouched blocks pass through.
    out_ref[...] = base_ref[...] + vals_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_scatter_pallas(base_blocks: Array, vals: Array, block_idx: Array,
                         *, interpret: bool = True) -> Array:
    """base (nb, bs) += vals (kb, bs) at rows block_idx.  Assumes the
    selected rows are distinct (RandK samples without replacement)."""
    nb, bs = base_blocks.shape
    kb = vals.shape[0]
    rows, lanes = block_view(bs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kb,),
        in_specs=[
            block_spec(bs, lambda i, idx: (i, 0, 0)),        # vals
            block_spec(bs, lambda i, idx: (idx[i], 0, 0)),   # base row
        ],
        out_specs=block_spec(bs, lambda i, idx: (idx[i], 0, 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, rows, lanes), base_blocks.dtype),
        interpret=interpret,
        input_output_aliases={2: 0},   # alias base (input 2) -> out 0
    )(block_idx, vals.reshape(kb, rows, lanes),
      base_blocks.reshape(nb, rows, lanes))
    return out.reshape(nb, bs)
