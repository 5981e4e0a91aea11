"""Paged-attention decode kernels (DESIGN.md §11).

The paged serving engine stores the KV cache as fixed-size token pages
in a shared pool: per layer ``k_pages``/``v_pages`` are
``(num_pages, page_size, kvH * hd)`` (the heads merged; the oracles
take ``(num_pages, page_size, kvH, hd)``, the same bytes) and each
request owns an ordered page table mapping its logical positions
``[i*P, (i+1)*P)`` to physical page ids.  The decode read is therefore
a *gather* attention: for each batch slot, collect that slot's pages
via its page-table row and run online softmax over the valid token
range.

Why a kernel: the jnp path materializes the gathered ``(B, M*P, kvH,
hd)`` K and V in HBM (2 extra round trips of the whole attended
context per layer per token) before the attention reduction reads them
again.  The kernels gather each page HBM→VMEM once, routed by the
scalar-prefetched page table, and keep the online-softmax accumulators
(``acc``, ``m``, ``l``) in VMEM scratch across the walk, so the
gathered context never exists densely in HBM.

* :func:`paged_attention_batched_pallas` — the fused multi-request GQA
  launch.  ONE invocation serves every active sequence of a serve pass,
  each slot carrying ``C >= 1`` queries (``q_lens`` per slot), so a
  chunked-prefill pass is the same launch as a pure decode pass with
  ``C == 1``.  One grid step is one slot × one block of pages × every
  kv head: the block's pages are copied from the pool as stored (its
  dtype, its ``(P, kvH * hd)`` planes) by manual async copies, the
  next live block fetched while this one computes, and each head's
  lanes are upcast to f32 in VMEM.  Idle slots and blocks past the
  last position any query row attends are skipped, DMA and compute.
* :func:`paged_mla_attention_pallas` — the rank-compressed latent
  cache (MLA).  Works in the *absorbed* form: scores are taken directly
  against the latent pages ``q_abs · c_kv + q_rope · k_rope`` (W_uk
  folded into the query by the caller) and the output is the latent-
  space accumulation ``p · c_kv`` (W_uv applied by the caller), so the
  per-token page traffic stays ``r + rope_hd`` floats — the up-projected
  K/V never exist, in HBM *or* VMEM.  Its grid is ``(slot, page_tile)``
  over f32 pages, routed by the BlockSpec index_map.

VMEM budget (mirrors ``buffered_commit_pallas``): ``PAGE_VMEM_BUDGET``
holds a GQA step's double-buffered K and V blocks (``_block_rows``:
whole pages making at least ``MIN_BLOCK_ROWS`` rows, capped at the
table width), or an MLA step's latent + rope tile.  Pages larger than
the budget are walked in sub-page tiles (``_page_tile_rows``, whole
sublane tiles), so the working set stays bounded regardless of
``page_size``.

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
their outputs are well-defined (position-``start + c`` attention) but
garbage by contract — callers must ignore them; a slot with
``q_lens == 0`` reads nothing and its rows are zero.
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
MIN_BLOCK_ROWS = 128         # token rows a GQA grid step reads at least


def _page_tile_rows(page_size: int, row_bytes: int,
                    budget: int = PAGE_VMEM_BUDGET, step: int = 8) -> int:
    """Largest multiple-of-``step`` divisor of ``page_size`` whose tiles
    of ``row_bytes`` a row fit the VMEM budget; falls back to the full
    page when ``page_size`` has no aligned divisor (small smoke pages
    in interpret mode)."""
    max_rows = max(1, budget // max(row_bytes, 1))
    if page_size <= max_rows:
        return page_size
    best = page_size   # fallback: caller sized pages past the budget
    for rows in range(step, page_size, step):
        if page_size % rows == 0 and rows <= max_rows:
            best = rows
    return best


def _block_rows(page_size: int, width: int, lanes: int, itemsize: int,
                budget: int = PAGE_VMEM_BUDGET) -> int:
    """Token rows one GQA grid step reads: the whole pages that make at
    least ``MIN_BLOCK_ROWS`` rows, capped at the table's ``width`` pages
    and at what the budget holds of double-buffered K and V blocks of
    ``lanes`` (``kvH * hd``) at the pool's ``itemsize``.  A page past
    the budget alone is cut into sub-page tiles of whole sublane
    tiles."""
    row_bytes = 2 * 2 * lanes * itemsize
    max_rows = max(1, budget // row_bytes)
    if page_size > max_rows:
        return _page_tile_rows(page_size, row_bytes, budget,
                               step=8 * max(1, 4 // itemsize))
    pages = min(-(-MIN_BLOCK_ROWS // page_size), width,
                max_rows // page_size)
    return pages * page_size


def paged_attention_vmem_bytes(page_size: int, kvh: int, hd: int,
                               num_q_heads: int) -> int:
    """VMEM bytes of one GQA decode grid step on a bf16 pool — the
    number the §11 budget table reports: the double-buffered K and V
    blocks of every kv head as stored, one head's f32 upcast K and V
    tiles, its f32 scores and probabilities, the pipelined query and
    output blocks of the slot's ``num_q_heads`` rows, and the
    accumulators."""
    lanes, itemsize = kvh * hd, 2
    rows = _block_rows(page_size, 1 << 30, lanes, itemsize)
    g = num_q_heads // kvh
    blocks = 2 * 2 * rows * lanes * itemsize
    upcast = 2 * rows * hd * 4 + 2 * g * rows * 4
    q_out = 2 * 2 * kvh * g * hd * 4
    acc = kvh * g * (hd + 2) * 4
    return blocks + upcast + q_out + acc


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

def _gqa_kernel(table_ref, start_ref, nblk_ref, next_ref, order_ref,
                q_ref, k_hbm, v_hbm, out_ref,
                k_buf, v_buf, sems, acc_ref, m_ref, l_ref, *,
                chunk: int, group: int, page_size: int, block_rows: int,
                window: int | None, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    batch = pl.num_programs(0)
    width = table_ref.shape[1]
    kvh, CG, hd = acc_ref.shape
    copies, copy_rows = k_buf.shape[1], k_buf.shape[2]
    # heads a loop step reads: the fewest whose lanes fill whole
    # 128-lane tiles (all of them where none do)
    tile_heads = next((n for n in range(1, kvh + 1)
                       if kvh % n == 0 and n * hd % 128 == 0), kvh)
    tiles = kvh // tile_heads
    nblk = nblk_ref[b]

    def live_pages(s):
        """Pages of slot ``s`` that any of its C query rows attends."""
        return jnp.minimum(pl.cdiv(start_ref[s] + chunk, page_size), width)

    def each_copy(s, blk, buf, act):
        """``act`` on the K and V copies of each live page (or sub-page
        tile) of block ``blk`` of slot ``s``, into VMEM buffer ``buf``.
        A loop, not unrolled: the kernel's trace stays small."""
        pages = live_pages(s)

        def body(i, carry):
            row = blk * block_rows + i * copy_rows
            page = table_ref[s, jnp.minimum(row // page_size, width - 1)]
            if copy_rows == page_size:
                src_k, src_v = k_hbm.at[page], v_hbm.at[page]
            else:
                rows = pl.ds(row % page_size, copy_rows)
                src_k, src_v = k_hbm.at[page, rows], v_hbm.at[page, rows]

            @pl.when(row // page_size < pages)
            def _():
                act(pltpu.make_async_copy(src_k, k_buf.at[buf, i],
                                          sems.at[buf, 0]))
                act(pltpu.make_async_copy(src_v, v_buf.at[buf, i],
                                          sems.at[buf, 1]))
            return carry
        jax.lax.fori_loop(0, copies, body, 0)

    def start_block(s, blk, buf):
        each_copy(s, blk, buf, lambda c: c.start())

    def wait_block(s, blk, buf):
        each_copy(s, blk, buf, lambda c: c.wait())

    @pl.when(nblk == 0)
    def _():   # empty slot: nothing read, rows garbage by contract
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j < nblk)
    def _():
        step = order_ref[b] + j          # rank among the launch's live blocks
        buf = step % 2

        @pl.when(step == 0)
        def _():
            start_block(b, j, buf)

        # prefetch the next live block, this slot's or the next live slot's
        more = j + 1 < nblk
        nb = jnp.where(more, b, next_ref[b + 1])
        nj = jnp.where(more, j + 1, 0)

        @pl.when(nb < batch)
        def _():
            start_block(nb, nj, 1 - buf)

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)

        wait_block(b, j, buf)

        base = j * block_rows
        start = start_ref[b]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_rows), 1)
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (CG, 1), 0) // group
        valid = pos < q_pos + 1                      # (CG, rows)
        if window is not None:
            valid &= pos > q_pos - window
        # rows of pages past the live bound were never copied: stale
        # VMEM, kept out of the value product (0 * NaN is NaN)
        held = (base + jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
                < live_pages(b) * page_size)

        def heads(t, carry):
            # the heads whose lanes make whole lane tiles, at an offset
            # the compiler can prove tile-aligned
            lo = 0 if tiles == 1 else pl.multiple_of(t * tile_heads * hd,
                                                     tile_heads * hd)
            kt = k_buf[buf, :, :, pl.ds(lo, tile_heads * hd)]
            vt = v_buf[buf, :, :, pl.ds(lo, tile_heads * hd)]
            for e in range(tile_heads):
                h = t * tile_heads + e
                lanes = slice(e * hd, (e + 1) * hd)
                k = kt[..., lanes].reshape(block_rows, hd).astype(jnp.float32)
                v = vt[..., lanes].reshape(block_rows, hd).astype(jnp.float32)
                v = jnp.where(held, v, 0.0)
                s = jax.lax.dot_general(
                    q_ref[0, h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.where(valid, s * scale, -1e30)   # (CG, rows)
                m_old = m_ref[h]                         # (CG, 1)
                m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
                corr = jnp.exp(m_old - m_new)
                p = jnp.exp(s - m_new)
                m_ref[h] = m_new
                l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                    p, v, preferred_element_type=jnp.float32)
            return carry
        jax.lax.fori_loop(0, tiles, heads, 0)

        @pl.when(j == nblk - 1)
        def _():
            out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_batched_pallas(q: Array, k_pages: Array,
                                   v_pages: Array, page_table: Array,
                                   start: Array, q_lens: Array, *,
                                   window: int | None = None,
                                   interpret: bool = True) -> Array:
    """Fused multi-request paged attention; same contract as
    :func:`paged_attention_batched_ref`.  ONE launch per serve pass:
    the grid walks ``(slot, block)``, a block being ``_block_rows``
    positions of the slot's page table (whole pages, or sub-page tiles
    of a page past the budget) and every kv head.

    The pool is read as stored, ``(NP, P, kvH * hd)`` (a ``(NP, P,
    kvH, hd)`` pool is viewed so: a contiguous merge), in its own
    dtype, from HBM by manual async copies routed through the scalar-prefetched
    page table, double-buffered across the launch's live blocks (the
    next one is fetched while this one computes).  Each K/V head's
    ``(rows, hd)`` lanes are upcast to f32 in VMEM; scores,
    probabilities and accumulators are f32, and each slot's queries of
    one kv head are a ``(C * G, hd)`` matrix (query-major), so scores
    and the value product are plain 2-D matmuls.

    Dead work is skipped, DMA and compute: slots with ``q_lens == 0``,
    and blocks wholly past ``ceil((start + C) / P)`` pages, the last
    position any of the slot's C query rows attends (padding rows
    included, so their output is the oracle's)."""
    B, C, H, hd = q.shape
    NP, P = k_pages.shape[:2]
    kf = k_pages.reshape(NP, P, -1)
    vf = v_pages.reshape(NP, P, -1)
    kvh = kf.shape[2] // hd
    W = page_table.shape[1]
    G = H // kvh
    CG = C * G
    rows = _block_rows(P, W, kvh * hd, kf.dtype.itemsize)
    copies, copy_rows = max(1, rows // P), min(rows, P)

    start = start.astype(jnp.int32)
    bound = jnp.minimum(start + C, W * P)
    nblk = jnp.where(q_lens > 0, (bound + rows - 1) // rows, 0)
    order = (jnp.cumsum(nblk) - nblk).astype(jnp.int32)
    # next_live[i]: the first slot >= i with a live block, else B
    first = jnp.where(nblk > 0, jnp.arange(B, dtype=jnp.int32), B)
    next_live = jnp.concatenate([
        jax.lax.cummin(first, reverse=True), jnp.full((1,), B, jnp.int32)])

    qh = (q.astype(jnp.float32).reshape(B, C, kvh, G, hd)
          .transpose(0, 2, 1, 3, 4).reshape(B, kvh, CG, hd))

    def slot_rows(b, j, table, start_, nblk_, next_, order_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, pl.cdiv(W * P, rows)),
        in_specs=[
            pl.BlockSpec((1, kvh, CG, hd), slot_rows),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvh, CG, hd), slot_rows),
        scratch_shapes=[
            pltpu.VMEM((2, copies, copy_rows, kvh * hd), kf.dtype),
            pltpu.VMEM((2, copies, copy_rows, kvh * hd), vf.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kvh, CG, hd), jnp.float32),
            pltpu.VMEM((kvh, CG, 1), jnp.float32),
            pltpu.VMEM((kvh, CG, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_gqa_kernel, chunk=C, group=G, page_size=P,
                          block_rows=rows, window=window,
                          scale=1.0 / math.sqrt(hd)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, CG, hd), jnp.float32),
        # the DMA chain runs across slots: both axes stay sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), start, nblk.astype(jnp.int32),
      next_live, order, qh, kf, vf)
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
