"""pallas-contract positives: arities, VMEM, blocks the TPU refuses.

Never imported — the linter fixtures are parsed, not executed.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
PAGE_VMEM_BUDGET = 4 << 20


def kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def bad_index_map_params(x):
    return pl.pallas_call(
        kernel,
        grid=(4, 2),
        # FIRE: 1 lambda parameter for a 2-axis grid
        in_specs=[pl.BlockSpec((256, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((256, LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((1024, 256), jnp.float32),
    )(x)


def bad_return_arity(x):
    return pl.pallas_call(
        kernel,
        grid=(4,),
        # FIRE: 1 coordinate returned for a 2-dim block
        in_specs=[pl.BlockSpec((256, LANES), lambda i: (i,))],
        out_specs=pl.BlockSpec((256, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((1024, LANES), jnp.float32),
    )(x)


def bad_operand_count(x, y):
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((256, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((256, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((1024, LANES), jnp.float32),
    )(x, y)                     # FIRE: 2 operands, 1 in_spec


def bad_out_arity(x):
    return pl.pallas_call(
        kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((256, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((256, LANES), lambda i: (i, 0))],
        # FIRE: 1 out_spec for 2 results
        out_shape=[jax.ShapeDtypeStruct((1024, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((1024, LANES), jnp.float32)],
    )(x)


def budget_blowout(x):
    tile = (8192, LANES)        # 4 MB per ref at fp32
    # FIRE: 2 tiles + scratch ~ 8.5 MB > PAGE_VMEM_BUDGET (4 MB)
    return pl.pallas_call(
        kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec(tile, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(tile, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16384, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1024, LANES), jnp.float32)],
    )(x)


def row_blocks(x_blocks, idx):
    # FIRE: (1, bs) row blocks over (nb, bs) — the BlockRandK gather
    row = pl.BlockSpec((1, 128), lambda i, idx_ref: (idx_ref[i], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(8,), in_specs=[row],
        out_specs=pl.BlockSpec((8, LANES), lambda i, idx_ref: (i, 0)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((64, LANES), jnp.float32),
    )(idx, x_blocks)


def one_kv_head_blocks(pages):
    return pl.pallas_call(
        kernel,
        grid=(4, 8),
        # FIRE: (1, rows, 1, hd) over an (NP, P, kvH, hd) pool
        in_specs=[pl.BlockSpec((1, 16, 1, 64), lambda p, h: (p, 0, h, 0))],
        out_specs=pl.BlockSpec((1, 16, 8, 64), lambda p, h: (p, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((4, 16, 8, 64), jnp.float32),
    )(pages)
