"""Chunked prefill, prompt-length bucketing, and the fused batched
kernels (DESIGN.md §11).

tests/test_paged_engine.py anchors the engine's DEFAULT configuration
against the dense DecodeServer; this file stresses the prefill paths
specifically:

* tiny explicit chunk budgets force every prompt through MULTIPLE fused
  passes (the chunk accounting, the drop-routed page writes past
  ``q_lens``, and the mid-prompt ``start`` offsets all get exercised),
  and the greedy outputs must still equal the dense server's;
* preemption landing on a slot that is still ingesting its prompt must
  requeue it with nothing registered and reproduce the uncontended run;
* bulk-mode prompt-length bucketing must compile once per BUCKET (not
  once per distinct length) while the padded prefill stays greedy-
  equivalent to the exact-length one;
* TTFT is stamped at the pass that EMITS the first logit — never at
  admission (the chunked-prefill regression this PR fixes);
* the fused batched GQA kernel and the absorbed MLA kernel match their
  jnp oracles on random page tables, chunk widths, and windows.
"""
import jax
import jax.numpy as jnp
import math
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.kernels.ops import paged_attention_batched_op, paged_mla_attention_op
from repro.kernels.paged_attention import (paged_attention_batched_ref,
                                           paged_mla_attention_ref)
from repro.models import Model, get_smoke_config
from repro.models.model import PagedDecodeState
from repro.serving import DecodeServer, PagedEngine, Request


def _model(arch="granite-3-2b"):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def _requests(cfg, n, seed=0, new=6, lo=2, hi=9):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(lo, hi))).tolist(),
                    max_new_tokens=new)
            for i in range(n)]


def _assert_token_parity(a, b):
    for ra, rb in zip(a, b):
        assert ra.generated == rb.generated, (ra.uid, ra.generated,
                                              rb.generated)


# ----------------------------------------------------------------------
# chunked prefill: multi-pass prompt ingestion keeps dense parity
# ----------------------------------------------------------------------

@pytest.mark.parametrize("arch,use_kernel",
                         [("granite-3-2b", False), ("granite-3-2b", True),
                          ("deepseek-v2-lite-16b", True)])
def test_small_chunk_parity(arch, use_kernel):
    """chunk=3 with prompts up to 8 tokens: every prompt needs several
    fused passes (mid-prompt ``start`` offsets, variable ``q_lens``
    per slot, pages crossed mid-chunk) and the greedy outputs still
    equal the dense server token-for-token."""
    cfg, model, params = _model(arch)
    dense = DecodeServer(model, params, batch_size=2, max_seq_len=32)
    d = dense.run(_requests(cfg, 5, lo=4, hi=9))
    paged = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                        page_size=4, use_kernel=use_kernel,
                        prefill_chunk_tokens=3)
    p = paged.run(_requests(cfg, 5, lo=4, hi=9))
    _assert_token_parity(d, p)
    # the chunk budget actually split prompts: at least one request took
    # more than one ingestion pass, and prompt tokens rode fused passes
    # that also advanced decodes
    assert any(s.prefill_calls > 1 for s in paged.stats.values())
    assert paged.mixed_passes >= 1


def test_chunked_matches_bulk_prefill():
    """Chunked and bulk ingestion are different schedules over the same
    math: identical greedy outputs, and the default chunk folds the
    whole workload into no more prompt-ingesting passes than bulk's
    one-forward-per-admission."""
    cfg, model, params = _model()
    bulk = PagedEngine(model, params, batch_size=3, max_seq_len=32,
                       page_size=4, prefill_chunk_tokens=0)
    b = bulk.run(_requests(cfg, 6, seed=3))
    chunked = PagedEngine(model, params, batch_size=3, max_seq_len=32,
                          page_size=4)
    c = chunked.run(_requests(cfg, 6, seed=3))
    _assert_token_parity(b, c)
    assert bulk.prefill_forwards == 6       # one per admission
    assert 0 < chunked.prefill_forwards <= bulk.prefill_forwards


def test_preemption_mid_chunked_prefill():
    """Pool exhaustion while a slot is still ingesting its prompt: the
    victim requeues with nothing registered (its partially-written
    pages just vanish) and the greedy outputs still equal an
    uncontended reference run."""
    cfg, model, params = _model()
    # chunk=1 + 10..12-token prompts: ingestion takes ~11 passes, so the
    # second admission is still feeding when the first crosses a page
    # boundary into a dry 7-page pool (3+3 prompt pages + 1 decode page)
    reqs = _requests(cfg, 6, seed=1, new=8, lo=10, hi=13)
    reference = PagedEngine(model, params, batch_size=3, max_seq_len=32,
                            page_size=4, prefill_chunk_tokens=1)
    ref = reference.run([Request(uid=r.uid, prompt=list(r.prompt),
                                 max_new_tokens=r.max_new_tokens)
                         for r in reqs])
    assert reference.mid_prefill_preemptions == 0

    tight = PagedEngine(model, params, batch_size=3, max_seq_len=32,
                        page_size=4, num_pages=7, prefill_chunk_tokens=1)
    out = tight.run(reqs)
    assert tight.mid_prefill_preemptions >= 1
    assert all(len(r.generated) == 8 for r in out)
    _assert_token_parity(ref, out)
    tight.pool.check_invariants()


def test_ctor_rejects_recurrent_archs():
    """Chunk tails and bucket padding hide behind the causal mask;
    recurrent scans have none, so explicit opt-in raises instead of
    silently corrupting state — and the auto defaults fall back to
    bulk exact-length prefill."""
    cfg, model, params = _model("xlstm-350m")
    with pytest.raises(ValueError):
        PagedEngine(model, params, batch_size=2, max_seq_len=16,
                    page_size=4, prefill_chunk_tokens=4)
    with pytest.raises(ValueError):
        PagedEngine(model, params, batch_size=2, max_seq_len=16,
                    page_size=4, bucket_sizes=[8, 16])
    eng = PagedEngine(model, params, batch_size=2, max_seq_len=16,
                      page_size=4)
    assert eng.chunk == 0 and eng.bucket_sizes == []
    # the fused step itself refuses a multi-query pass on recurrent state
    state = PagedDecodeState(caches=eng._caches,
                             page_table=jnp.asarray(eng._table),
                             seq_lens=jnp.asarray(eng._lens))
    with pytest.raises(ValueError):
        model.paged_fused_step(params, jnp.zeros((2, 2), jnp.int32),
                               state, jnp.ones((2,), jnp.int32))


# ----------------------------------------------------------------------
# prompt-length bucketing (bulk mode)
# ----------------------------------------------------------------------

def test_bucketed_prefill_compiles_once_per_bucket():
    """Distinct prompt lengths inside one bucket reuse the SAME jit
    program (the recompile tax this PR removes); only crossing into a
    new bucket adds a compile."""
    cfg, model, params = _model()
    eng = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                      page_size=4, prefill_chunk_tokens=0,
                      bucket_sizes=[8, 16])
    reqs = [Request(uid=i, prompt=[3 + i] * (3 + i), max_new_tokens=2)
            for i in range(5)]             # lengths 3..7: one bucket (8)
    eng.run(reqs)
    assert eng.prefill_cache_size() == 1
    eng.run([Request(uid=10, prompt=[7] * 10, max_new_tokens=2)])
    assert eng.prefill_cache_size() == 2   # length 10 -> bucket 16
    eng.run([Request(uid=11, prompt=[2] * 12, max_new_tokens=2)])
    assert eng.prefill_cache_size() == 2   # length 12: bucket 16 again


def test_padded_prefill_greedy_parity():
    """Bucket padding is drop-routed (``true_len`` gates the page
    writes, the head reads the hidden state at the true last token):
    padded and exact-length prefill produce the same greedy tokens and
    numerically-equal decode logits."""
    cfg, model, params = _model()
    exact = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                        page_size=4, prefill_chunk_tokens=0,
                        bucket_sizes=[], trace_logits=True)
    e = exact.run(_requests(cfg, 5, seed=2))
    padded = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                         page_size=4, prefill_chunk_tokens=0,
                         trace_logits=True)
    p = padded.run(_requests(cfg, 5, seed=2))
    _assert_token_parity(e, p)
    for uid in exact.logit_trace:
        # padded prefill reduces in a different shape than exact-length,
        # so allclose (not bitwise) is the contract here
        np.testing.assert_allclose(np.stack(exact.logit_trace[uid]),
                                   np.stack(padded.logit_trace[uid]),
                                   rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# TTFT accounting
# ----------------------------------------------------------------------

def test_ttft_stamped_at_first_logit_not_admission():
    """A length-7 prompt under chunk=2 needs 4 ingestion passes; the
    first logit exists only after the last of them.  Stamping at
    admission (the bug this PR fixes) would report ttft=0."""
    cfg, model, params = _model()
    eng = PagedEngine(model, params, batch_size=1, max_seq_len=32,
                      page_size=4, prefill_chunk_tokens=2)
    req = Request(uid=0, prompt=[5, 9, 3, 7, 2, 8, 4], max_new_tokens=3)
    eng.run([req])
    st_ = eng.stats[0]
    assert st_.admitted_at == 0
    assert st_.first_token_at == 4         # ceil(7 / 2) ingestion passes
    assert st_.ttft == 4

    # bulk mode: the single prefill forward emits the logit -> ttft 1
    bulk = PagedEngine(model, params, batch_size=1, max_seq_len=32,
                       page_size=4, prefill_chunk_tokens=0)
    bulk.run([Request(uid=0, prompt=[5, 9, 3, 7, 2, 8, 4],
                      max_new_tokens=3)])
    assert bulk.stats[0].ttft == 1


def test_serve_pass_span_counts_pages_walked():
    """Each ``serve.pass`` span carries ``pages_walked``: over the fed
    slots, the table pages up to ``ceil((start + C) / P)``, capped at
    the pass's width — the pages the paged kernel reads.  A 7-token
    prompt in chunks of 2 over pages of 4, then 3 decode passes (the
    last two on a 4-page table once the answer needs a third page)."""
    from repro.obs import trace as obs_trace

    cfg, model, params = _model()
    tracer = obs_trace.configure()
    try:
        eng = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                          page_size=4, prefill_chunk_tokens=2)
        eng.run([Request(uid=0, prompt=[5, 9, 3, 7, 2, 8, 4],
                         max_new_tokens=3)])
    finally:
        obs_trace.uninstall()
    passes = [(e["args"]["width"], e["args"]["pages_walked"])
              for e in tracer.events if e["name"] == "serve.pass"]
    # chunk passes start at 0, 2, 4, 6 (C = 2); decode at 7, 8, 9
    assert passes == [(2, 1), (2, 1), (2, 2), (2, 2), (2, 2), (4, 3),
                      (4, 3)]


def test_ttft_percentiles_reflect_queueing():
    """Requests beyond the batch wait in the queue; their TTFT includes
    the wait, so p95 > p50 on an oversubscribed workload and every
    chunked TTFT is at least the ingestion-pass lower bound."""
    cfg, model, params = _model()
    eng = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                      page_size=4, prefill_chunk_tokens=2)
    eng.run(_requests(cfg, 6, seed=4, new=6, lo=5, hi=9))
    for st_ in eng.stats.values():
        assert st_.first_token_at is not None
        assert st_.first_token_at > st_.admitted_at
    m = eng.metrics()
    assert m["ttft_p95"] >= m["ttft_p50"] > 0


# ----------------------------------------------------------------------
# fused batched kernels vs jnp oracles
# ----------------------------------------------------------------------

# Shapes the batched GQA kernel adapts to: the pool's dtype, idle slots
# (q_lens == 0), a table wider than every slot's live pages (dead
# blocks: 72 pages of 4 make three 32-page blocks, and starts stay in
# the first two), and kvH * hd lanes of 16, 128 and 256 (two 128-lane
# tiles of two heads each, walked by the kernel's head loop).
GQA_CASES = {
    "f32_pool": dict(),
    "bf16_pool": dict(dtype=jnp.bfloat16),
    "idle_slots": dict(B=4, NP=24, idle=True),
    "dead_blocks": dict(M=72, NP=152, start_hi=150),
    "lanes128": dict(hd=64),
    "lanes256": dict(H=8, kvh=4, hd=64),
}


def _gqa_inputs(seed, B=2, C=3, H=4, kvh=2, hd=8, P=4, NP=16, M=4,
                dtype=jnp.float32, idle=False, start_hi=None):
    key = jax.random.key(seed)
    mk = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s)
    q = mk(0, (B, C, H, hd))
    k = mk(1, (NP, P, kvh, hd)).astype(dtype)
    v = mk(2, (NP, P, kvh, hd)).astype(dtype)
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(NP)[:B * M].reshape(B, M), jnp.int32)
    start = rng.integers(0, (start_hi or M * P - C) + 1, B)
    q_lens = rng.integers(1, C + 1, B)
    if idle:
        q_lens[rng.integers(0, B)] = 0
        q_lens[rng.random(B) < 0.3] = 0
    return (q, k, v, table, jnp.asarray(start, jnp.int32),
            jnp.asarray(q_lens, jnp.int32))


@pytest.mark.parametrize("case", sorted(GQA_CASES))
@settings(max_examples=6, deadline=None)   # first example compiles
@given(seed=st.integers(0, 1000), windowed=st.booleans())
def test_batched_paged_attention_kernel_matches_ref(case, seed, windowed):
    """The multi-query GQA launch on random page tables, starts, and
    chunk widths.  Padding rows (c >= q_lens) compute the same
    position-(start+c) attention in kernel and oracle — the engine
    ignores them via drop-routed writes, so every row of a slot that
    is fed is compared; an idle slot's rows are garbage by contract."""
    q, k, v, table, start, q_lens = _gqa_inputs(seed, **GQA_CASES[case])
    window = 5 if windowed else None
    ref = paged_attention_batched_ref(q, k, v, table, start, q_lens,
                                      window=window)
    out = paged_attention_batched_op(q, k, v, table, start, q_lens,
                                     window=window, interpret=True)
    fed = np.asarray(q_lens) > 0
    np.testing.assert_allclose(np.asarray(out)[fed], np.asarray(ref)[fed],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("windowed", [False, True])
def test_batched_paged_attention_never_reads_dead_pages(windowed):
    """Pages past each fed slot's ``ceil((start + C) / P)``, the pages
    of an idle slot and pages in no table hold NaN: the kernel must
    skip them (DMA and compute), so every row of a fed slot is finite
    and equals the oracle's on the clean pool."""
    B, C, H, kvh, hd, P, M = 4, 3, 4, 2, 8, 4, 40   # two 32-page blocks
    NP = B * M + 8
    q, k, v, table, _, _ = _gqa_inputs(0, B=B, C=C, H=H, kvh=kvh, hd=hd,
                                       P=P, NP=NP, M=M,
                                       dtype=jnp.bfloat16)
    start = jnp.asarray([5, 100, 130, 0], jnp.int32)
    q_lens = jnp.asarray([3, 0, 1, 2], jnp.int32)
    live = np.minimum(-(-(np.asarray(start) + C) // P), M)
    read = np.zeros((NP,), bool)
    for b in np.flatnonzero(np.asarray(q_lens)):
        read[np.asarray(table)[b, :live[b]]] = True
    dead = jnp.asarray(~read)[:, None, None, None]
    window = 5 if windowed else None
    ref = paged_attention_batched_ref(q, k, v, table, start, q_lens,
                                      window=window)
    out = paged_attention_batched_op(
        q, jnp.where(dead, jnp.nan, k).astype(k.dtype),
        jnp.where(dead, jnp.nan, v).astype(v.dtype), table, start, q_lens,
        window=window, interpret=True)
    fed = np.asarray(q_lens) > 0
    assert np.isfinite(np.asarray(out)[fed]).all()
    np.testing.assert_allclose(np.asarray(out)[fed], np.asarray(ref)[fed],
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=6, deadline=None)   # first example compiles
@given(seed=st.integers(0, 1000), windowed=st.booleans())
def test_paged_mla_kernel_matches_ref(seed, windowed):
    """The absorbed-form latent kernel: scores against the rank-r pages
    plus the rope rows, output accumulated in latent space."""
    key = jax.random.key(seed)
    B, C, H, r, rr, P, NP, M = 2, 3, 4, 8, 4, 4, 16, 4
    mk = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s)
    q_abs = mk(0, (B, C, H, r))
    q_rope = mk(1, (B, C, H, rr))
    ckv = mk(2, (NP, P, r))
    kr = mk(3, (NP, P, rr))
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(NP)[:B * M].reshape(B, M), jnp.int32)
    start = jnp.asarray(rng.integers(0, M * P - C + 1, B), jnp.int32)
    q_lens = jnp.asarray(rng.integers(1, C + 1, B), jnp.int32)
    window = 6 if windowed else None
    scale = 1.0 / math.sqrt(12.0)
    ref = paged_mla_attention_ref(q_abs, q_rope, ckv, kr, table, start,
                                  q_lens, scale=scale, window=window)
    out = paged_mla_attention_op(q_abs, q_rope, ckv, kr, table, start,
                                 q_lens, scale=scale, window=window,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
