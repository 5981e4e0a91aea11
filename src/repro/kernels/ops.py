"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile for the chip; on any other backend they run
in interpret mode (the body executes in Python, numerics identical).
``REPRO_PALLAS_INTERPRET`` or ``interpret=`` overrides the choice.
"""
from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dasha_update import (buffered_commit_pallas,
                                        dasha_h_update_pallas,
                                        dasha_page_h_update_pallas,
                                        dasha_page_payload_blocks_pallas,
                                        dasha_page_update_batched_pallas,
                                        dasha_payload_blocks_pallas,
                                        dasha_tail_batched_pallas,
                                        dasha_update_batched_pallas,
                                        dasha_update_pallas)
from repro.kernels.paged_attention import (paged_attention_batched_pallas,
                                           paged_attention_pallas,
                                           paged_mla_attention_pallas)
from repro.kernels.randk import block_gather_pallas, block_scatter_pallas
from repro.obs.trace import kernel_scope

Array = jax.Array


def _scoped(name: str):
    """Wrap an op in :func:`repro.obs.trace.kernel_scope` so its Pallas
    launch is attributable (``repro.kernel.<name>``) in jax.profiler /
    Perfetto device traces.  named_scope costs only at trace time."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with kernel_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def interpret_default() -> bool:
    """Whether Pallas kernels run in interpret mode by default here:
    yes unless on TPU, overridable via ``REPRO_PALLAS_INTERPRET``."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


_interpret_default = interpret_default   # internal alias


def _f32(*xs: Array) -> tuple:
    return tuple(x.astype(jnp.float32) for x in xs)


@_scoped("dasha_update")
def dasha_update_op(gn: Array, go: Array, h: Array, gi: Array, *,
                    b: float, a: float, pa: float, participates: Array,
                    interpret: bool | None = None
                    ) -> Tuple[Array, Array, Array]:
    """Fused (k, h_new, payload); see kernels/dasha_update.py."""
    interp = _interpret_default() if interpret is None else interpret
    part = jnp.asarray(participates, jnp.float32)
    return dasha_update_pallas(
        *_f32(gn, go, h, gi), part,
        b=float(b), a=float(a), pa=float(pa), interpret=interp)


@_scoped("dasha_update_batched")
def dasha_update_batched_op(gn: Array, go: Array, h: Array, gi: Array,
                            mask: Array, *, b: float, a: float, pa: float,
                            interpret: bool | None = None
                            ) -> Tuple[Array, Array, Array]:
    """Node-major fused (k, h_new, payload), inputs (n, d), mask (n,).
    Covers the Alg. 2 (gradient) and Alg. 5 (MVR) k-rules — they share
    the ``gn - go - b (h - go)`` shape with ``gn/go`` = full vs minibatch
    gradients respectively."""
    interp = _interpret_default() if interpret is None else interpret
    return dasha_update_batched_pallas(
        *_f32(gn, go, h, gi), mask.astype(jnp.float32),
        b=float(b), a=float(a), pa=float(pa), interpret=interp)


@_scoped("dasha_page_update")
def dasha_page_update_op(gn: Array, go: Array, bn: Array, bo: Array,
                         h: Array, gi: Array, mask: Array, coin: Array, *,
                         b: float, a: float, pa: float, p_page: float,
                         interpret: bool | None = None
                         ) -> Tuple[Array, Array, Array]:
    """Fused Alg. 3 (PAGE) update: both branches + coin select + lines
    10-11 in one kernel launch.  Inputs (n, d); coin is a () scalar."""
    interp = _interpret_default() if interpret is None else interpret
    return dasha_page_update_batched_pallas(
        *_f32(gn, go, bn, bo, h, gi), mask.astype(jnp.float32),
        jnp.asarray(coin, jnp.float32),
        b=float(b), a=float(a), pa=float(pa), p_page=float(p_page),
        interpret=interp)


@_scoped("dasha_tail")
def dasha_tail_op(k: Array, h: Array, gi: Array, mask: Array, *,
                  a: float, pa: float, interpret: bool | None = None
                  ) -> Tuple[Array, Array]:
    """Fused lines 10-11 given precomputed k (finite-MVR, Alg. 4)."""
    interp = _interpret_default() if interpret is None else interpret
    return dasha_tail_batched_pallas(
        *_f32(k, h, gi), mask.astype(jnp.float32),
        a=float(a), pa=float(pa), interpret=interp)


@_scoped("dasha_h_update")
def dasha_h_update_op(gn: Array, go: Array, h: Array, *, b: float,
                      pa: float, participates: Array,
                      interpret: bool | None = None) -> Array:
    """Line-10 h-tracker pass only (flat (D,)); k stays in-register."""
    interp = _interpret_default() if interpret is None else interpret
    return dasha_h_update_pallas(
        *_f32(gn, go, h), jnp.asarray(participates, jnp.float32),
        b=float(b), pa=float(pa), interpret=interp)


@_scoped("dasha_payload_blocks")
def dasha_payload_blocks_op(gn: Array, go: Array, h: Array, gi: Array,
                            block_idx: Array, *, b: float, a: float,
                            pa: float, scale: float, block_size: int,
                            interpret: bool | None = None) -> Array:
    """Fused update+BlockRandK-compress: line-11 payload evaluated only
    at the selected blocks (never dense in HBM), pre-scaled for
    unbiasedness.  Returns (k_blocks, block_size) wire values."""
    interp = _interpret_default() if interpret is None else interpret
    return dasha_payload_blocks_pallas(
        *_f32(gn, go, h, gi), block_idx.astype(jnp.int32),
        b=float(b), a=float(a), pa=float(pa), scale=float(scale),
        block_size=int(block_size), interpret=interp)


@_scoped("dasha_page_h_update")
def dasha_page_h_update_op(gn: Array, go: Array, bn: Array, bo: Array,
                           h: Array, coin: Array, *, b: float, pa: float,
                           p_page: float, participates: Array,
                           interpret: bool | None = None) -> Array:
    """Line-10 h-tracker pass with the Alg. 3 PAGE k-rule in-register
    (flat (D,)); pairs with :func:`dasha_page_payload_blocks_op`."""
    interp = _interpret_default() if interpret is None else interpret
    return dasha_page_h_update_pallas(
        *_f32(gn, go, bn, bo, h), jnp.asarray(participates, jnp.float32),
        jnp.asarray(coin, jnp.float32),
        b=float(b), pa=float(pa), p_page=float(p_page), interpret=interp)


@_scoped("dasha_page_payload_blocks")
def dasha_page_payload_blocks_op(gn: Array, go: Array, bn: Array,
                                 bo: Array, h: Array, gi: Array,
                                 block_idx: Array, coin: Array, *,
                                 b: float, a: float, pa: float,
                                 p_page: float, scale: float,
                                 block_size: int,
                                 interpret: bool | None = None) -> Array:
    """Fused PAGE update+BlockRandK compress: the Alg. 3 payload
    evaluated only at the selected blocks (never dense in HBM)."""
    interp = _interpret_default() if interpret is None else interpret
    return dasha_page_payload_blocks_pallas(
        *_f32(gn, go, bn, bo, h, gi), block_idx.astype(jnp.int32),
        jnp.asarray(coin, jnp.float32),
        b=float(b), a=float(a), pa=float(pa), p_page=float(p_page),
        scale=float(scale), block_size=int(block_size), interpret=interp)


@_scoped("buffered_commit")
def buffered_commit_op(g: Array, m_buf: Array, weights: Array, *,
                       n_nodes: int, interpret: bool | None = None
                       ) -> Array:
    """Async server-step commit: ``g + (1/n_nodes) * (weights @ m_buf)``
    fused into one pass over the (K, D) arrival buffer (DESIGN.md §9)."""
    interp = _interpret_default() if interpret is None else interpret
    return buffered_commit_pallas(
        *_f32(g, m_buf, weights), inv_n=1.0 / float(n_nodes),
        interpret=interp)


@_scoped("paged_attention")
def paged_attention_op(q: Array, k_pages: Array, v_pages: Array,
                       page_table: Array, lens: Array, *,
                       window: int | None = None,
                       interpret: bool | None = None) -> Array:
    """Paged-attention decode read (DESIGN.md §11): online softmax over
    the pool pages selected by each slot's page-table row.  q (B, H,
    hd), pages (NP, P, kvH, hd), table (B, M), lens (B,) valid tokens
    per slot including the one just written.  Returns (B, H, hd) f32."""
    interp = _interpret_default() if interpret is None else interpret
    return paged_attention_pallas(
        q.astype(jnp.float32), k_pages.astype(jnp.float32),
        v_pages.astype(jnp.float32), page_table.astype(jnp.int32),
        lens.astype(jnp.int32),
        window=None if window is None else int(window), interpret=interp)


@_scoped("paged_attention_batched")
def paged_attention_batched_op(q: Array, k_pages: Array, v_pages: Array,
                               page_table: Array, start: Array,
                               q_lens: Array, *,
                               window: int | None = None,
                               interpret: bool | None = None) -> Array:
    """Fused multi-request paged-attention launch (DESIGN.md §11): one
    kernel invocation serves every active slot of a serve pass, each
    carrying up to C queries (chunked prefill folds prompt chunks into
    the same launch as single-token decode).  q (B, C, H, hd), start
    (B,) tokens per slot BEFORE this pass's writes, q_lens (B,) valid
    queries per slot.  Returns (B, C, H, hd) f32; rows ``c >= q_lens``
    are garbage by contract."""
    interp = _interpret_default() if interpret is None else interpret
    return paged_attention_batched_pallas(
        q.astype(jnp.float32), k_pages.astype(jnp.float32),
        v_pages.astype(jnp.float32), page_table.astype(jnp.int32),
        start.astype(jnp.int32), q_lens.astype(jnp.int32),
        window=None if window is None else int(window), interpret=interp)


@_scoped("paged_mla_attention")
def paged_mla_attention_op(q_abs: Array, q_rope: Array, ckv_pages: Array,
                           kr_pages: Array, page_table: Array,
                           start: Array, q_lens: Array, *, scale: float,
                           window: int | None = None,
                           interpret: bool | None = None) -> Array:
    """Paged MLA latent attention in the absorbed form (DESIGN.md §11):
    scores taken directly against the rank-r latent pages, output
    accumulated in latent space (caller applies W_uv).  q_abs (B, C, H,
    r) is ``q_nope · W_uk``; pages are (NP, P, r) / (NP, P, rope_hd)."""
    interp = _interpret_default() if interpret is None else interpret
    return paged_mla_attention_pallas(
        q_abs.astype(jnp.float32), q_rope.astype(jnp.float32),
        ckv_pages.astype(jnp.float32), kr_pages.astype(jnp.float32),
        page_table.astype(jnp.int32), start.astype(jnp.int32),
        q_lens.astype(jnp.int32), scale=float(scale),
        window=None if window is None else int(window), interpret=interp)


@_scoped("block_gather")
def block_gather_op(x_blocks: Array, block_idx: Array, *, scale: float,
                    interpret: bool | None = None) -> Array:
    interp = _interpret_default() if interpret is None else interpret
    return block_gather_pallas(
        x_blocks.astype(jnp.float32), block_idx.astype(jnp.int32),
        k_blocks=int(block_idx.shape[0]), scale=float(scale),
        interpret=interp)


@_scoped("block_scatter")
def block_scatter_op(base_blocks: Array, vals: Array, block_idx: Array,
                     interpret: bool | None = None) -> Array:
    interp = _interpret_default() if interpret is None else interpret
    return block_scatter_pallas(
        base_blocks.astype(jnp.float32), vals.astype(jnp.float32),
        block_idx.astype(jnp.int32), interpret=interp)
