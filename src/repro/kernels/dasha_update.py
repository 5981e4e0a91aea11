"""Fused DASHA control-variate updates as Pallas TPU kernels.

Why kernels (DESIGN.md §6): the per-node update is a chain of five
elementwise passes over grad-sized vectors

    k       = gn - go - b (h - go)
    h_new   = h + part * k / pa
    payload = k / pa - (a/pa)(g_i - h)

with arithmetic intensity ~O(1) — pure HBM-bandwidth-bound.  Unfused,
XLA may materialize k and intermediate diffs; the fused kernel streams
the four inputs once and writes the three outputs once: 7 HBM transfers
of D instead of ~11+, a ~1.6x memory-roofline win on the optimizer phase
(validated against the HLO bytes in benchmarks/bench_kernels.py).

Kernel family (one per ``k_i`` rule of Algorithm 1, DESIGN.md §6):

* :func:`dasha_update_pallas`          — flat (D,) single-node form
  (Algs. 2/5; the sharded engine's per-leaf local vector).
* :func:`dasha_update_batched_pallas`  — node-major (n, D) form with a
  per-node participation mask; one launch updates every simulated node
  of the reference :class:`~repro.core.dasha_pp.DashaPP` engine.
* :func:`dasha_page_update_batched_pallas` — the Alg. 3 PAGE rule: both
  branches (full ``gn - go - (b/p_page)(h - go)`` and minibatch
  ``bn - bo``) fused with the shared Bernoulli coin select.
* :func:`dasha_tail_batched_pallas`    — lines 10-11 only, for variants
  whose ``k_i`` is produced elsewhere (Alg. 4 finite-MVR scatter).
* :func:`dasha_h_update_pallas` / :func:`dasha_payload_blocks_pallas` —
  the compressed-wire split: a dense h-tracker pass plus a
  scalar-prefetch block gather that computes the Alg. 1 line-11 payload
  *only at the BlockRandK-selected blocks*, so the dense payload never
  round-trips through HBM.
* :func:`buffered_commit_pallas` — the async server-step commit
  (DESIGN.md §9): ``g += (1/n) sum_k w_k m_k`` over the ``(K, D)``
  arrival buffer with per-contribution staleness weights, one pass —
  the buffer rows stream through VMEM once and the weighted reduction
  stays in-register instead of XLA materializing the ``(K, D)``
  scaled intermediate.

Tiling: inputs are reshaped to (rows, 128) lanes; the grid walks row
tiles of ``block_rows`` (default 512 rows = 256 KB/operand in VMEM ->
4 inputs + 3 outputs ~ 1.75 MB, comfortably inside ~16 MB VMEM).

``b, a, pa`` are compile-time constants (algorithm hyperparameters);
``participates`` (and the PAGE coin) are runtime scalars streamed via
(1, 1) / (n, 1) operands.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.randk import block_spec, block_view

Array = jax.Array

LANES = 128
DEFAULT_BLOCK_ROWS = 512


def _pad_rows(d: int, block_rows: int) -> Tuple[int, int]:
    """Rows after padding ``d`` lanes-wise up to a tile multiple, and the
    flat pad length."""
    rows = -(-d // LANES)
    rows_pad = -(-rows // block_rows) * block_rows
    return rows_pad, rows_pad * LANES - d


def _prep_flat(x: Array, rows_pad: int, pad: int) -> Array:
    return jnp.pad(x, (0, pad)).reshape(rows_pad, LANES)


def _unprep_flat(x: Array, d: int) -> Array:
    return x.reshape(-1)[:d]


def _unprep_batched(x: Array, n: int, d: int) -> Array:
    return x.reshape(n, -1)[:, :d]


def _kernel(part_ref, gn_ref, go_ref, h_ref, gi_ref,
            k_ref, h_new_ref, payload_ref, *, b: float, a: float,
            pa: float):
    part = part_ref[0, 0]
    gn = gn_ref[...]
    go = go_ref[...]
    h = h_ref[...]
    gi = gi_ref[...]
    k = gn - go - b * (h - go)
    inv_pa = 1.0 / pa
    k_ref[...] = k
    h_new_ref[...] = h + part * (k * inv_pa)
    payload_ref[...] = k * inv_pa - (a * inv_pa) * (gi - h)


@functools.partial(jax.jit, static_argnames=("b", "a", "pa", "block_rows",
                                             "interpret"))
def dasha_update_pallas(gn: Array, go: Array, h: Array, gi: Array,
                        participates: Array, *, b: float, a: float,
                        pa: float,
                        block_rows: int = DEFAULT_BLOCK_ROWS,
                        interpret: bool = True
                        ) -> Tuple[Array, Array, Array]:
    """Inputs are flat (D,) float32 vectors; returns (k, h_new, payload).

    ``interpret=True`` executes the kernel body in Python on CPU (this
    container); on TPU pass ``interpret=False``.
    """
    (d,) = gn.shape
    rows_pad, pad = _pad_rows(d, block_rows)
    gn2, go2, h2, gi2 = (_prep_flat(x, rows_pad, pad)
                         for x in (gn, go, h, gi))
    part = jnp.reshape(participates.astype(jnp.float32), (1, 1))
    grid = (rows_pad // block_rows,)

    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))

    k2, hn2, pay2 = pl.pallas_call(
        functools.partial(_kernel, b=b, a=a, pa=pa),
        grid=grid,
        in_specs=[scalar, tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows_pad, LANES), jnp.float32)] * 3,
        interpret=interpret,
    )(part, gn2, go2, h2, gi2)

    return (_unprep_flat(k2, d), _unprep_flat(hn2, d),
            _unprep_flat(pay2, d))


# ----------------------------------------------------------------------
# Node-major batched forms (the reference DashaPP engine's layout)
# ----------------------------------------------------------------------

def _prep_batched(x: Array, rows_pad: int, pad: int) -> Array:
    n = x.shape[0]
    return jnp.pad(x, ((0, 0), (0, pad))).reshape(n, rows_pad, LANES)


def _batched_specs(n: int, rows_pad: int, block_rows: int):
    grid = (n, rows_pad // block_rows)
    tile = pl.BlockSpec((1, block_rows, LANES), lambda i, j: (i, j, 0))
    per_node = pl.BlockSpec((1, 1), lambda i, j: (i, 0))
    return grid, tile, per_node


def _batched_kernel(mask_ref, gn_ref, go_ref, h_ref, gi_ref,
                    k_ref, h_new_ref, payload_ref, *, b: float, a: float,
                    pa: float):
    part = mask_ref[0, 0]
    gn = gn_ref[...]
    go = go_ref[...]
    h = h_ref[...]
    gi = gi_ref[...]
    k = gn - go - b * (h - go)
    inv_pa = 1.0 / pa
    k_ref[...] = k
    h_new_ref[...] = h + part * (k * inv_pa)
    payload_ref[...] = k * inv_pa - (a * inv_pa) * (gi - h)


@functools.partial(jax.jit, static_argnames=("b", "a", "pa", "block_rows",
                                             "interpret"))
def dasha_update_batched_pallas(gn: Array, go: Array, h: Array, gi: Array,
                                mask: Array, *, b: float, a: float,
                                pa: float,
                                block_rows: int = DEFAULT_BLOCK_ROWS,
                                interpret: bool = True
                                ) -> Tuple[Array, Array, Array]:
    """Node-major fused update: inputs (n, d) float32, ``mask`` (n,) —
    the per-node participation indicator.  Returns (k, h_new, payload),
    each (n, d).  One launch covers all ``n`` simulated nodes: the grid
    walks (node, row-tile) so the Alg. 2/5 chain never materializes
    per-node intermediates (DESIGN.md §6)."""
    n, d = gn.shape
    rows_pad, pad = _pad_rows(d, block_rows)
    gn2, go2, h2, gi2 = (_prep_batched(x, rows_pad, pad)
                         for x in (gn, go, h, gi))
    mask2 = jnp.reshape(mask.astype(jnp.float32), (n, 1))
    grid, tile, per_node = _batched_specs(n, rows_pad, block_rows)

    k2, hn2, pay2 = pl.pallas_call(
        functools.partial(_batched_kernel, b=b, a=a, pa=pa),
        grid=grid,
        in_specs=[per_node, tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((n, rows_pad, LANES),
                                        jnp.float32)] * 3,
        interpret=interpret,
    )(mask2, gn2, go2, h2, gi2)

    return (_unprep_batched(k2, n, d), _unprep_batched(hn2, n, d),
            _unprep_batched(pay2, n, d))


def _page_kernel(mask_ref, coin_ref, gn_ref, go_ref, bn_ref, bo_ref, h_ref,
                 gi_ref, k_ref, h_new_ref, payload_ref, *, b: float,
                 a: float, pa: float, p_page: float):
    part = mask_ref[0, 0]
    coin = coin_ref[0, 0]
    gn = gn_ref[...]
    go = go_ref[...]
    h = h_ref[...]
    gi = gi_ref[...]
    k_full = gn - go - (b / p_page) * (h - go)
    k_mini = bn_ref[...] - bo_ref[...]
    k = coin * k_full + (1.0 - coin) * k_mini
    inv_pa = 1.0 / pa
    k_ref[...] = k
    h_new_ref[...] = h + part * (k * inv_pa)
    payload_ref[...] = k * inv_pa - (a * inv_pa) * (gi - h)


@functools.partial(jax.jit, static_argnames=("b", "a", "pa", "p_page",
                                             "block_rows", "interpret"))
def dasha_page_update_batched_pallas(gn: Array, go: Array, bn: Array,
                                     bo: Array, h: Array, gi: Array,
                                     mask: Array, coin: Array, *, b: float,
                                     a: float, pa: float, p_page: float,
                                     block_rows: int = DEFAULT_BLOCK_ROWS,
                                     interpret: bool = True
                                     ) -> Tuple[Array, Array, Array]:
    """Alg. 3 (PAGE) rule fused with lines 10-11: the full-gradient branch
    ``gn - go - (b/p_page)(h - go)`` and the minibatch branch ``bn - bo``
    are both computed in-register and selected by the shared Bernoulli
    ``coin`` (a runtime (1,1) scalar — one compilation serves both
    branches).  Inputs (n, d); returns (k, h_new, payload)."""
    n, d = gn.shape
    rows_pad, pad = _pad_rows(d, block_rows)
    gn2, go2, bn2, bo2, h2, gi2 = (_prep_batched(x, rows_pad, pad)
                                   for x in (gn, go, bn, bo, h, gi))
    mask2 = jnp.reshape(mask.astype(jnp.float32), (n, 1))
    coin2 = jnp.reshape(coin.astype(jnp.float32), (1, 1))
    grid, tile, per_node = _batched_specs(n, rows_pad, block_rows)
    scalar = pl.BlockSpec((1, 1), lambda i, j: (0, 0))

    k2, hn2, pay2 = pl.pallas_call(
        functools.partial(_page_kernel, b=b, a=a, pa=pa, p_page=p_page),
        grid=grid,
        in_specs=[per_node, scalar, tile, tile, tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((n, rows_pad, LANES),
                                        jnp.float32)] * 3,
        interpret=interpret,
    )(mask2, coin2, gn2, go2, bn2, bo2, h2, gi2)

    return (_unprep_batched(k2, n, d), _unprep_batched(hn2, n, d),
            _unprep_batched(pay2, n, d))


def _tail_kernel(mask_ref, k_ref, h_ref, gi_ref, h_new_ref, payload_ref, *,
                 a: float, pa: float):
    part = mask_ref[0, 0]
    k = k_ref[...]
    h = h_ref[...]
    inv_pa = 1.0 / pa
    h_new_ref[...] = h + part * (k * inv_pa)
    payload_ref[...] = k * inv_pa - (a * inv_pa) * (gi_ref[...] - h)


@functools.partial(jax.jit, static_argnames=("a", "pa", "block_rows",
                                             "interpret"))
def dasha_tail_batched_pallas(k: Array, h: Array, gi: Array, mask: Array, *,
                              a: float, pa: float,
                              block_rows: int = DEFAULT_BLOCK_ROWS,
                              interpret: bool = True
                              ) -> Tuple[Array, Array]:
    """Lines 10-11 of Algorithm 1 given a precomputed ``k_i`` (n, d):
    the finite-MVR rule (Alg. 4) builds ``k_i`` by a component scatter
    that has no dense-elementwise shape, so only the tail fuses.
    Returns (h_new, payload)."""
    n, d = k.shape
    rows_pad, pad = _pad_rows(d, block_rows)
    k2, h2, gi2 = (_prep_batched(x, rows_pad, pad) for x in (k, h, gi))
    mask2 = jnp.reshape(mask.astype(jnp.float32), (n, 1))
    grid, tile, per_node = _batched_specs(n, rows_pad, block_rows)

    hn2, pay2 = pl.pallas_call(
        functools.partial(_tail_kernel, a=a, pa=pa),
        grid=grid,
        in_specs=[per_node, tile, tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((n, rows_pad, LANES),
                                        jnp.float32)] * 2,
        interpret=interpret,
    )(mask2, k2, h2, gi2)

    return _unprep_batched(hn2, n, d), _unprep_batched(pay2, n, d)


# ----------------------------------------------------------------------
# Compressed-wire split: dense h pass + payload-at-selected-blocks
# ----------------------------------------------------------------------

def _h_update_kernel(part_ref, gn_ref, go_ref, h_ref, h_new_ref, *,
                     b: float, pa: float):
    part = part_ref[0, 0]
    gn = gn_ref[...]
    go = go_ref[...]
    h = h_ref[...]
    k = gn - go - b * (h - go)
    h_new_ref[...] = h + part * (k * (1.0 / pa))


@functools.partial(jax.jit, static_argnames=("b", "pa", "block_rows",
                                             "interpret"))
def dasha_h_update_pallas(gn: Array, go: Array, h: Array,
                          participates: Array, *, b: float, pa: float,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = True) -> Array:
    """Line 10 only, flat (D,): ``h += part * k / pa`` with ``k``
    recomputed in-register (3 reads + 1 write of D — ``k`` itself never
    touches HBM).  Pairs with :func:`dasha_payload_blocks_pallas` for the
    sparse wire path (DESIGN.md §6)."""
    (d,) = gn.shape
    rows_pad, pad = _pad_rows(d, block_rows)
    gn2, go2, h2 = (_prep_flat(x, rows_pad, pad) for x in (gn, go, h))
    part = jnp.reshape(participates.astype(jnp.float32), (1, 1))
    grid = (rows_pad // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))

    hn2 = pl.pallas_call(
        functools.partial(_h_update_kernel, b=b, pa=pa),
        grid=grid,
        in_specs=[scalar, tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.float32),
        interpret=interpret,
    )(part, gn2, go2, h2)
    return _unprep_flat(hn2, d)


def _page_h_update_kernel(part_ref, coin_ref, gn_ref, go_ref, bn_ref,
                          bo_ref, h_ref, h_new_ref, *, b: float, pa: float,
                          p_page: float):
    part = part_ref[0, 0]
    coin = coin_ref[0, 0]
    gn = gn_ref[...]
    go = go_ref[...]
    h = h_ref[...]
    k_full = gn - go - (b / p_page) * (h - go)
    k_mini = bn_ref[...] - bo_ref[...]
    k = coin * k_full + (1.0 - coin) * k_mini
    h_new_ref[...] = h + part * (k * (1.0 / pa))


@functools.partial(jax.jit, static_argnames=("b", "pa", "p_page",
                                             "block_rows", "interpret"))
def dasha_page_h_update_pallas(gn: Array, go: Array, bn: Array, bo: Array,
                               h: Array, participates: Array, coin: Array,
                               *, b: float, pa: float, p_page: float,
                               block_rows: int = DEFAULT_BLOCK_ROWS,
                               interpret: bool = True) -> Array:
    """Line 10 with the Alg. 3 PAGE ``k`` recomputed in-register, flat
    (D,): both branches + the shared-coin select never touch HBM.
    Pairs with :func:`dasha_page_payload_blocks_pallas` for the PAGE
    sparse wire (DESIGN.md §8)."""
    (d,) = gn.shape
    rows_pad, pad = _pad_rows(d, block_rows)
    gn2, go2, bn2, bo2, h2 = (_prep_flat(x, rows_pad, pad)
                              for x in (gn, go, bn, bo, h))
    part = jnp.reshape(participates.astype(jnp.float32), (1, 1))
    coin2 = jnp.reshape(coin.astype(jnp.float32), (1, 1))
    grid = (rows_pad // block_rows,)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))

    hn2 = pl.pallas_call(
        functools.partial(_page_h_update_kernel, b=b, pa=pa,
                          p_page=p_page),
        grid=grid,
        in_specs=[scalar, scalar, tile, tile, tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.float32),
        interpret=interpret,
    )(part, coin2, gn2, go2, bn2, bo2, h2)
    return _unprep_flat(hn2, d)


# ----------------------------------------------------------------------
# Async buffered commit (DESIGN.md §9)
# ----------------------------------------------------------------------

def _buffered_commit_kernel(w_ref, g_ref, m_ref, out_ref, *, inv_n: float):
    # m tile: (K, block_rows, LANES); w: (K, 1) staleness weights.
    w = w_ref[...]                          # (K, 1)
    m = m_ref[...]
    acc = jnp.sum(m * w[:, :, None], axis=0)
    out_ref[...] = g_ref[...] + inv_n * acc


def _commit_block_rows(k: int, budget_bytes: int = 4 << 20) -> int:
    """Largest multiple-of-8 row tile such that the (K + 2) operands of
    one grid step fit the VMEM budget."""
    rows = budget_bytes // ((k + 2) * LANES * 4)
    return max(8, min(DEFAULT_BLOCK_ROWS, (rows // 8) * 8))


@functools.partial(jax.jit, static_argnames=("inv_n", "interpret"))
def buffered_commit_pallas(g: Array, m_buf: Array, weights: Array, *,
                           inv_n: float, interpret: bool = True) -> Array:
    """The async server step (DESIGN.md §9): commit a buffer of ``K``
    arrived messages into the server estimator in one fused pass,

        g_new = g + (1/n) * sum_k weights[k] * m_buf[k],

    with ``weights`` the per-contribution staleness weights ``w(s)``.
    ``g`` is flat (D,), ``m_buf`` (K, D), ``weights`` (K,) — all
    float32.  The grid walks row tiles; each step streams the K buffer
    rows of that tile through VMEM once and reduces in-register."""
    (d,) = g.shape
    kk = int(m_buf.shape[0])
    block_rows = _commit_block_rows(kk)
    rows_pad, pad = _pad_rows(d, block_rows)
    g2 = _prep_flat(g, rows_pad, pad)
    m2 = jnp.pad(m_buf, ((0, 0), (0, pad))).reshape(kk, rows_pad, LANES)
    w2 = jnp.reshape(weights.astype(jnp.float32), (kk, 1))
    grid = (rows_pad // block_rows,)

    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    buf_tile = pl.BlockSpec((kk, block_rows, LANES), lambda i: (0, i, 0))
    wspec = pl.BlockSpec((kk, 1), lambda i: (0, 0))

    out = pl.pallas_call(
        functools.partial(_buffered_commit_kernel, inv_n=inv_n),
        grid=grid,
        in_specs=[wspec, tile, buf_tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.float32),
        interpret=interpret,
    )(w2, g2, m2)
    return _unprep_flat(out, d)


def _payload_blocks_kernel(idx_ref, gn_ref, go_ref, h_ref, gi_ref, out_ref,
                           *, b: float, a: float, pa: float, scale: float):
    # The BlockSpec index_map (scalar prefetch) already routed block
    # idx[i] of every input here; the body is the full line-9..11 chain
    # plus the RandK unbiasedness scale, in-register.
    gn = gn_ref[...]
    go = go_ref[...]
    h = h_ref[...]
    k = gn - go - b * (h - go)
    inv_pa = 1.0 / pa
    payload = k * inv_pa - (a * inv_pa) * (gi_ref[...] - h)
    out_ref[...] = payload * scale


@functools.partial(jax.jit, static_argnames=("b", "a", "pa", "scale",
                                             "block_size", "interpret"))
def dasha_payload_blocks_pallas(gn: Array, go: Array, h: Array, gi: Array,
                                block_idx: Array, *, b: float, a: float,
                                pa: float, scale: float, block_size: int,
                                interpret: bool = True) -> Array:
    """Fused update+compress for the BlockRandK wire (DESIGN.md §6):
    computes the Alg. 1 line-11 payload **only at the selected blocks**
    and scales it for unbiasedness — the dense payload intermediate
    never exists in HBM.  Inputs are flat (D,) float32; ``block_idx``
    is (k_blocks,) int32 over the (ceil(D/bs), bs) block view.  Returns
    (k_blocks, block_size) wire values."""
    (d,) = gn.shape
    kb = int(block_idx.shape[0])
    bs = block_size
    nb = -(-d // bs)
    pad = nb * bs - d
    rows, lanes = block_view(bs)

    def prep(x):
        return jnp.pad(x, (0, pad)).reshape(nb, rows, lanes)

    gn2, go2, h2, gi2 = map(prep, (gn, go, h, gi))
    row = block_spec(bs, lambda i, idx: (idx[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kb,),
        in_specs=[row, row, row, row],
        out_specs=block_spec(bs, lambda i, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_payload_blocks_kernel, b=b, a=a, pa=pa,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kb, rows, lanes), jnp.float32),
        interpret=interpret,
    )(block_idx.astype(jnp.int32), gn2, go2, h2, gi2)
    return out.reshape(kb, bs)


def _page_payload_blocks_kernel(idx_ref, coin_ref, gn_ref, go_ref, bn_ref,
                                bo_ref, h_ref, gi_ref, out_ref, *,
                                b: float, a: float, pa: float,
                                p_page: float, scale: float):
    # Same scalar-prefetch gather as _payload_blocks_kernel, with the
    # Alg. 3 k-rule (both branches + shared coin) in-register.
    coin = coin_ref[0, 0]
    gn = gn_ref[...]
    go = go_ref[...]
    h = h_ref[...]
    k_full = gn - go - (b / p_page) * (h - go)
    k_mini = bn_ref[...] - bo_ref[...]
    k = coin * k_full + (1.0 - coin) * k_mini
    inv_pa = 1.0 / pa
    payload = k * inv_pa - (a * inv_pa) * (gi_ref[...] - h)
    out_ref[...] = payload * scale


@functools.partial(jax.jit, static_argnames=("b", "a", "pa", "p_page",
                                             "scale", "block_size",
                                             "interpret"))
def dasha_page_payload_blocks_pallas(gn: Array, go: Array, bn: Array,
                                     bo: Array, h: Array, gi: Array,
                                     block_idx: Array, coin: Array, *,
                                     b: float, a: float, pa: float,
                                     p_page: float, scale: float,
                                     block_size: int,
                                     interpret: bool = True) -> Array:
    """Fused PAGE update+compress for the BlockRandK wire: the Alg. 3
    line-11 payload evaluated **only at the selected blocks** (the
    dense payload never exists in HBM), pre-scaled for unbiasedness.
    Inputs are flat (D,) float32 plus the shared (scalar) coin; returns
    (k_blocks, block_size) wire values."""
    (d,) = gn.shape
    kb = int(block_idx.shape[0])
    bs = block_size
    nb = -(-d // bs)
    pad = nb * bs - d
    rows, lanes = block_view(bs)

    def prep(x):
        return jnp.pad(x, (0, pad)).reshape(nb, rows, lanes)

    gn2, go2, bn2, bo2, h2, gi2 = map(prep, (gn, go, bn, bo, h, gi))
    coin2 = jnp.reshape(coin.astype(jnp.float32), (1, 1))
    row = block_spec(bs, lambda i, idx: (idx[i], 0, 0))
    scalar = pl.BlockSpec((1, 1), lambda i, idx: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kb,),
        in_specs=[scalar, row, row, row, row, row, row],
        out_specs=block_spec(bs, lambda i, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_page_payload_blocks_kernel, b=b, a=a, pa=pa,
                          p_page=p_page, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kb, rows, lanes), jnp.float32),
        interpret=interpret,
    )(block_idx.astype(jnp.int32), coin2, gn2, go2, bn2, bo2, h2, gi2)
    return out.reshape(kb, bs)
