"""pallas-contract near-misses: the dasha_update/paged_attention
idioms, dimensionally consistent and comfortably inside ~16 MB VMEM.

Never imported — the linter fixtures are parsed, not executed.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 512


def _specs(rows, block_rows=DEFAULT_BLOCK_ROWS):
    """Helper the checker's resolver must follow (dasha_update idiom)."""
    grid = (rows // block_rows,)
    tile = (block_rows, LANES)
    return grid, tile


def kernel(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] + y_ref[...]


def add(x, y, block_rows=DEFAULT_BLOCK_ROWS):
    grid, tile = _specs(4096, block_rows)
    spec = pl.BlockSpec(tile, lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((4096, LANES), jnp.float32),
    )(x, y)


def gather_kernel(idx_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...]


def page_lookup(i, idx_ref):
    """Named index_map (paged_attention idiom)."""
    return idx_ref[i], 0


def gather_rows(table, idx):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(8,),
        in_specs=[pl.BlockSpec((DEFAULT_BLOCK_ROWS, LANES),
                               page_lookup)],
        out_specs=pl.BlockSpec((DEFAULT_BLOCK_ROWS, LANES),
                               lambda i, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4096, LANES), jnp.float32),
    )(idx, table)


def block_view(bs):
    """Two returns: the resolver must not guess a branch."""
    if bs % LANES == 0:
        return bs // LANES, LANES
    return 1, bs


def head_major_pages(table, q, pages, bs):
    """The repaired idioms: a (1, 1, rows, hd) block of a head-major
    pool, scalar blocks (1, 1), and row blocks viewed as full planes."""
    rows, lanes = block_view(bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4, 8, 2),
        in_specs=[
            pl.BlockSpec((1, 1, 16, 64),
                         lambda b, h, j, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 16, 64),
                         lambda b, h, j, t: (t[b, j], h, 0, 0)),
            pl.BlockSpec((1, rows, lanes),
                         lambda b, h, j, t: (t[b, j], 0, 0)),
            pl.BlockSpec((1, 1), lambda b, h, j, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 16, 64),
                               lambda b, h, j, t: (b, h, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4, 8, 16, 64), jnp.float32),
    )(table, q, pages, pages, q)
