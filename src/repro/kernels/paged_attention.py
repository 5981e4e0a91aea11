"""Paged-attention decode kernels (DESIGN.md §11).

The paged serving engine stores the KV cache as fixed-size token pages
in a shared pool: per layer ``k_pages``/``v_pages`` are
``(num_pages, page_size, kvH, hd)`` and each request owns an ordered
page table mapping its logical positions ``[i*P, (i+1)*P)`` to physical
page ids.  The decode read is therefore a *gather* attention: for each
batch slot, collect that slot's pages via its page-table row and run
online softmax over the valid token range.

Why a kernel: the jnp path materializes the gathered ``(B, M*P, kvH,
hd)`` K and V in HBM (2 extra round trips of the whole attended
context per layer per token) before the attention reduction reads them
again.  The kernel gathers each page HBM→VMEM exactly once via the
scalar-prefetched page table (the BlockSpec index_map routes physical
page ``table[b, j]`` to its grid step — the same idiom as
``dasha_payload_blocks_pallas``) and keeps the online-softmax
accumulators (``acc``, ``m``, ``l``) in VMEM scratch across the page
walk, so the gathered context never exists densely in HBM.

Two kernels share the page-table-walk machinery:

* :func:`paged_attention_batched_pallas` — the fused multi-request GQA
  launch.  ONE invocation serves every active sequence of a serve pass:
  the grid walks ``(slot, kv_head, page_tile)`` and each slot carries
  ``C >= 1`` queries (``q_lens`` per slot), so a chunked-prefill pass
  (several prompt tokens for some slots, one decode token for others)
  is the same launch as a pure decode pass with ``C == 1``.
* :func:`paged_mla_attention_pallas` — the rank-compressed latent
  cache (MLA).  Works in the *absorbed* form: scores are taken directly
  against the latent pages ``q_abs · c_kv + q_rope · k_rope`` (W_uk
  folded into the query by the caller) and the output is the latent-
  space accumulation ``p · c_kv`` (W_uv applied by the caller), so the
  per-token page traffic stays ``r + rope_hd`` floats — the up-projected
  K/V never exist, in HBM *or* VMEM.

VMEM budget (mirrors ``buffered_commit_pallas``): one grid step holds
one K tile + V tile (GQA: a single kv head's ``(rows, hd)`` plane of
the head-major pool view; MLA: the latent + rope rows), the query
block, and the accumulators.  Pages larger than the
row budget are walked in sub-page tiles of ``_page_tile_rows`` rows (a
multiple of 8 f32 sublanes) so the working set stays inside
``PAGE_VMEM_BUDGET`` regardless of ``page_size``.

Masking contract: the fed tokens' KV is written *before* the read (the
serving engine's write-then-attend step).  ``start`` is the tokens per
slot BEFORE this pass's writes, so query ``c`` of a slot sits at
absolute position ``start + c`` and attends every index
``i < start + c + 1`` — and, for sliding-window archs,
``start + c - i < window``.  Padded page-table entries point at page 0;
their positions are ``>= lens`` and masked.  Pool pages carry stale
bytes from previous occupants in their unwritten slots; those positions
are also ``>= lens`` for the owning slot, so the validity mask is the
single source of isolation.  Queries ``c >= q_lens[b]`` are padding;
their outputs are well-defined (position-0 attention) but garbage by
contract — callers must ignore them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

PAGE_VMEM_BUDGET = 4 << 20   # bytes per grid step, as buffered_commit


def _page_tile_rows(page_size: int, row_bytes: int,
                    budget: int = PAGE_VMEM_BUDGET) -> int:
    """Largest multiple-of-8 divisor of ``page_size`` whose K+V tiles fit
    the VMEM budget; falls back to the full page when ``page_size`` has
    no 8-aligned divisor (small smoke pages in interpret mode)."""
    max_rows = max(1, budget // max(row_bytes, 1))
    if page_size <= max_rows:
        return page_size
    best = page_size   # fallback: caller sized pages past the budget
    for rows in range(8, page_size, 8):
        if page_size % rows == 0 and rows <= max_rows:
            best = rows
    return best


def paged_attention_vmem_bytes(page_size: int, kvh: int, hd: int,
                               num_q_heads: int) -> int:
    """VMEM bytes of one decode grid step (f32): K/V tiles, the query
    and output blocks and the accumulators — the number the §11 budget
    table reports.  The fused grid walks one kv head per step, so the
    tile is ``rows * hd`` regardless of ``kvh``, and the query rows are
    that head's ``num_q_heads / kvh`` group (times C for a chunked
    pass)."""
    rows = _page_tile_rows(page_size, 2 * hd * 4)
    tile = 2 * rows * hd * 4
    g = num_q_heads // kvh
    return tile + 3 * g * hd * 4 + 2 * g * 4


# ----------------------------------------------------------------------
# jnp references (the oracles the kernels are tested against)
# ----------------------------------------------------------------------

def paged_attention_batched_ref(q: Array, k_pages: Array, v_pages: Array,
                                page_table: Array, start: Array,
                                q_lens: Array, *,
                                window: int | None = None) -> Array:
    """Batched gather-attention oracle.  q: (B, C, H, hd) — up to C
    queries per slot; k_pages/v_pages: (NP, P, kvH, hd); page_table:
    (B, M) int32; start: (B,) tokens per slot BEFORE this pass's writes;
    q_lens: (B,) valid queries per slot (query ``c`` sits at position
    ``start + c``; rows ``c >= q_lens`` are garbage by contract).
    Returns (B, C, H, hd) f32."""
    B, C, H, hd = q.shape
    _, P, kvh, _ = k_pages.shape
    M = page_table.shape[1]
    G = H // kvh
    k = k_pages[page_table].reshape(B, M * P, kvh, hd).astype(jnp.float32)
    v = v_pages[page_table].reshape(B, M * P, kvh, hd).astype(jnp.float32)
    idx = jnp.arange(M * P)[None, None, :]                  # (1, 1, S)
    q_pos = start[:, None] + jnp.arange(C)[None, :]         # (B, C)
    valid = idx < (q_pos + 1)[:, :, None]
    if window is not None:
        valid &= idx > (q_pos[:, :, None] - window)
    qg = q.reshape(B, C, kvh, G, hd).astype(jnp.float32)
    s = jnp.einsum("bckgh,bskh->bkgcs", qg, k) / math.sqrt(hd)
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskh->bckgh", p, v)
    return out.reshape(B, C, H, hd)


def paged_attention_ref(q: Array, k_pages: Array, v_pages: Array,
                        page_table: Array, lens: Array, *,
                        window: int | None = None) -> Array:
    """Single-query decode oracle (the legacy contract): q (B, H, hd),
    ``lens`` (B,) valid tokens per slot INCLUDING the one just written.
    A thin C=1 view of :func:`paged_attention_batched_ref`."""
    B = q.shape[0]
    out = paged_attention_batched_ref(
        q[:, None], k_pages, v_pages, page_table,
        jnp.maximum(lens - 1, 0), jnp.ones((B,), jnp.int32), window=window)
    return out[:, 0]


def paged_mla_attention_ref(q_abs: Array, q_rope: Array, ckv_pages: Array,
                            kr_pages: Array, page_table: Array,
                            start: Array, q_lens: Array, *,
                            scale: float,
                            window: int | None = None) -> Array:
    """Absorbed-form MLA latent attention oracle.  q_abs: (B, C, H, r)
    — the nope query with W_uk folded in (``q_nope · W_uk``); q_rope:
    (B, C, H, rope_hd); ckv_pages: (NP, P, r); kr_pages: (NP, P,
    rope_hd).  Returns the latent-space output (B, C, H, r) — the
    caller applies W_uv.  ``scale`` is 1/sqrt(qk_nope + qk_rope), the
    full-head softmax scale of the unabsorbed math."""
    B, C, H, r = q_abs.shape
    _, P, _ = ckv_pages.shape
    M = page_table.shape[1]
    ckv = ckv_pages[page_table].reshape(B, M * P, r).astype(jnp.float32)
    kr = kr_pages[page_table].reshape(B, M * P, -1).astype(jnp.float32)
    idx = jnp.arange(M * P)[None, None, :]
    q_pos = start[:, None] + jnp.arange(C)[None, :]
    valid = idx < (q_pos + 1)[:, :, None]
    if window is not None:
        valid &= idx > (q_pos[:, :, None] - window)
    s = (jnp.einsum("bchr,bsr->bhcs", q_abs.astype(jnp.float32), ckv)
         + jnp.einsum("bchx,bsx->bhcs", q_rope.astype(jnp.float32), kr))
    s = s * scale
    s = jnp.where(valid[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhcs,bsr->bchr", p, ckv)


# ----------------------------------------------------------------------
# fused multi-request GQA kernel
# ----------------------------------------------------------------------

def _paged_attention_batched_kernel(table_ref, start_ref, qlen_ref,
                                    q_ref, k_ref, v_ref, out_ref,
                                    acc_ref, m_ref, l_ref, *,
                                    page_size: int, tile_rows: int,
                                    group: int, window: int | None,
                                    scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    tiles_per_page = page_size // tile_rows
    del qlen_ref   # rows past q_lens are garbage by contract

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    # one kv head's query rows, query-major: row r is query r // group
    CG = q_ref.shape[2]
    start = start_ref[b]
    base = (j // tiles_per_page) * page_size \
        + (j % tiles_per_page) * tile_rows
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, tile_rows), 1)
    q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (CG, 1), 0) // group
    valid = pos < q_pos + 1                          # (CG, tile_rows)
    if window is not None:
        valid &= pos > q_pos - window

    q = q_ref[0, 0]                                  # (CG, hd)
    k = k_ref[0, 0]                                  # (tile_rows, hd)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, -1e30)                   # (CG, tile_rows)

    m_old = m_ref[...]                               # (CG, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(j == n_j - 1)
    def _():
        out_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_batched_pallas(q: Array, k_pages: Array,
                                   v_pages: Array, page_table: Array,
                                   start: Array, q_lens: Array, *,
                                   window: int | None = None,
                                   interpret: bool = True) -> Array:
    """Fused multi-request paged attention; same contract as
    :func:`paged_attention_batched_ref`.  ONE launch per serve pass:
    the grid walks ``(slot, kv_head, page_tile)``, the scalar-prefetched
    page table routes physical pages into VMEM, and the online-softmax
    state lives in scratch across each (slot, head) walk.  Walking one
    kv head per step keeps the tile at ``rows * hd`` bytes independent
    of ``kvH``, so big-GQA configs stay under the VMEM budget.

    The kernel reads the pool head-major, ``(NP, kvH, P, hd)``, so a
    K/V block ``(1, 1, rows, hd)`` ends in the ``(rows, hd)`` plane the
    TPU tiles: a ``(1, rows, 1, hd)`` block of the ``(NP, P, kvH, hd)``
    pool puts a 1 over ``kvH`` in the second-minor dim, which the TPU
    compiler refuses.  The relayout fuses with the f32 cast of the
    pool.  Each slot's queries of one kv head are a ``(C * G, hd)``
    matrix (query-major), so scores and the value product are plain
    2-D matmuls."""
    B, C, H, hd = q.shape
    NP, P, kvh, _ = k_pages.shape
    M = page_table.shape[1]
    G = H // kvh
    CG = C * G
    tile_rows = _page_tile_rows(P, 2 * hd * 4)
    tiles_per_page = P // tile_rows
    scale = 1.0 / math.sqrt(hd)

    qh = (q.astype(jnp.float32).reshape(B, C, kvh, G, hd)
          .transpose(0, 2, 1, 3, 4).reshape(B, kvh, CG, hd))
    kh = k_pages.astype(jnp.float32).transpose(0, 2, 1, 3)
    vh = v_pages.astype(jnp.float32).transpose(0, 2, 1, 3)

    def page_idx(b, h, j, table, start_, qlens_):
        return (table[b, j // tiles_per_page], h, j % tiles_per_page, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, kvh, M * tiles_per_page),
        in_specs=[
            pl.BlockSpec((1, 1, CG, hd),
                         lambda b, h, j, t, s, ql: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, tile_rows, hd), page_idx),
            pl.BlockSpec((1, 1, tile_rows, hd), page_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, CG, hd),
                               lambda b, h, j, t, s, ql: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((CG, hd), jnp.float32),
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attention_batched_kernel, page_size=P,
                          tile_rows=tile_rows, group=G, window=window,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, CG, hd), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      q_lens.astype(jnp.int32), qh, kh, vh)
    return (out.reshape(B, kvh, C, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, H, hd))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(q: Array, k_pages: Array, v_pages: Array,
                           page_table: Array, lens: Array, *,
                           window: int | None = None,
                           interpret: bool = True) -> Array:
    """Single-query decode view of the fused kernel (legacy contract of
    :func:`paged_attention_ref`): ``lens`` counts the token just
    written, so ``start = lens - 1`` and every slot carries one query."""
    B = q.shape[0]
    out = paged_attention_batched_pallas(
        q[:, None], k_pages, v_pages, page_table,
        jnp.maximum(lens - 1, 0), jnp.ones((B,), jnp.int32),
        window=window, interpret=interpret)
    return out[:, 0]


# ----------------------------------------------------------------------
# paged MLA latent-attention kernel (absorbed form)
# ----------------------------------------------------------------------

def _paged_mla_kernel(table_ref, start_ref, qlen_ref, qa_ref, qr_ref,
                      ckv_ref, kr_ref, out_ref, acc_ref, m_ref, l_ref, *,
                      page_size: int, tile_rows: int,
                      window: int | None, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_j = pl.num_programs(1)
    tiles_per_page = page_size // tile_rows
    del qlen_ref

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    C = qa_ref.shape[1]
    start = start_ref[b]
    base = (j // tiles_per_page) * page_size \
        + (j % tiles_per_page) * tile_rows
    pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, tile_rows), 1)
    q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    valid = pos < q_pos + 1                          # (C, tile_rows)
    if window is not None:
        valid &= pos > q_pos - window

    qa = qa_ref[0].astype(jnp.float32)               # (C, H, r)
    qr = qr_ref[0].astype(jnp.float32)               # (C, H, rope_hd)
    ckv = ckv_ref[0].astype(jnp.float32)             # (tile_rows, r)
    kr = kr_ref[0].astype(jnp.float32)               # (tile_rows, rope_hd)
    s = (jnp.einsum("chr,sr->chs", qa, ckv)
         + jnp.einsum("chx,sx->chs", qr, kr)) * scale
    s = jnp.where(valid[:, None], s, -1e30)

    m_old = m_ref[...]                               # (C, H)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[..., None])
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * corr[..., None]
                    + jnp.einsum("chs,sr->chr", p, ckv))

    @pl.when(j == n_j - 1)
    def _():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...],
                                                1e-30)[..., None]


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "interpret"))
def paged_mla_attention_pallas(q_abs: Array, q_rope: Array,
                               ckv_pages: Array, kr_pages: Array,
                               page_table: Array, start: Array,
                               q_lens: Array, *, scale: float,
                               window: int | None = None,
                               interpret: bool = True) -> Array:
    """Paged MLA decode in the absorbed form; same contract as
    :func:`paged_mla_attention_ref`.  Shares the page-table-walk idiom
    with the GQA kernel: grid ``(slot, page_tile)`` (every head reads
    the same rank-``r`` latent rows, so there is no head axis to walk),
    scores taken directly against the latent pages, output accumulated
    in latent space — the up-projected K/V never exist."""
    B, C, H, r = q_abs.shape
    NP, P, _ = ckv_pages.shape
    rr = kr_pages.shape[2]
    M = page_table.shape[1]
    tile_rows = _page_tile_rows(P, (r + rr) * 4)
    tiles_per_page = P // tile_rows

    def page_idx(b, j, table, start_, qlens_):
        return (table[b, (j * tile_rows) // P], (j % tiles_per_page), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, M * tiles_per_page),
        in_specs=[
            pl.BlockSpec((1, C, H, r), lambda b, j, t, s, ql: (b, 0, 0, 0)),
            pl.BlockSpec((1, C, H, rr), lambda b, j, t, s, ql: (b, 0, 0, 0)),
            pl.BlockSpec((1, tile_rows, r), page_idx),
            pl.BlockSpec((1, tile_rows, rr), page_idx),
        ],
        out_specs=pl.BlockSpec((1, C, H, r),
                               lambda b, j, t, s, ql: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C, H, r), jnp.float32),
            pltpu.VMEM((C, H), jnp.float32),
            pltpu.VMEM((C, H), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_mla_kernel, page_size=P,
                          tile_rows=tile_rows, window=window,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H, r), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      q_lens.astype(jnp.int32), q_abs.astype(jnp.float32),
      q_rope.astype(jnp.float32), ckv_pages.astype(jnp.float32),
      kr_pages.astype(jnp.float32))
