"""PagedEngine correctness (DESIGN.md §11).

The parity anchor: with ``page_size >= max_seq`` (one page per slot)
and greedy sampling, the paged engine must reproduce the dense
``DecodeServer.run`` token-for-token — attention and MLA archs, Pallas
kernel on and off.  Token ids ARE compared here (unlike
tests/test_serving.py's byte-level asserts) because both servers run in
the same process on the same params: the sequences are mathematically
identical greedy decodes and the seeds below produce decisive logit
gaps (bulk vs token-by-token prefill reduce in different shapes, so
bit-equality is not guaranteed, only argmax equality).

Beyond the anchor: shared-prefix pages produce BITWISE-identical decode
logits vs an unshared engine (same-length prompts compile to the same
prefill program, so the prefix KV bytes coincide exactly); pool
exhaustion preempts and re-admits without changing any greedy output;
and the paged-attention kernel matches its jnp oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.kernels.ops import paged_attention_op
from repro.kernels.paged_attention import (_block_rows, paged_attention_ref,
                                           paged_attention_vmem_bytes)
from repro.models import Model, get_smoke_config
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
# dense parity anchor
# ----------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["granite-3-2b", "deepseek-v2-lite-16b"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_dense_parity_anchor(arch, use_kernel):
    """page_size >= max_seq + one page per slot + greedy == the dense
    DecodeServer, token-for-token, with more requests than slots (the
    continuous-batching refill included)."""
    cfg, model, params = _model(arch)
    dense = DecodeServer(model, params, batch_size=2, max_seq_len=32)
    d = dense.run(_requests(cfg, 5))
    paged = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                        page_size=32, num_pages=2, use_kernel=use_kernel)
    p = paged.run(_requests(cfg, 5))
    _assert_token_parity(d, p)
    # prompt ingestion never costs one pass per token: bulk mode is one
    # forward per admission, and the default chunked mode folds several
    # admissions into shared fused passes (3 observed here vs 5 bulk)
    assert 0 < paged.prefill_forwards <= 5
    assert paged.pool.metrics.preemptions == 0


@pytest.mark.parametrize("arch", ["xlstm-350m", "hymba-1.5b"])
def test_recurrent_archs_keep_dense_state(arch):
    """SSM/hybrid: recurrent state stays dense in the engine (only
    attention caches page) and the greedy outputs still match."""
    cfg, model, params = _model(arch)
    d = DecodeServer(model, params, batch_size=2,
                     max_seq_len=32).run(_requests(cfg, 4, new=5))
    p = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                    page_size=8).run(_requests(cfg, 4, new=5))
    _assert_token_parity(d, p)


def test_scanned_layers_parity():
    """Production configs stack layers under lax.scan; the paged state,
    prefill scatter, and COW copy all address the extra leading layer
    dim — parity must hold there too (smoke configs are unscanned, so
    this flips the flag explicitly)."""
    cfg = get_smoke_config("granite-3-2b").with_overrides(scan_layers=True)
    model = Model(cfg)
    assert model.scan
    params = model.init_params(jax.random.key(0))
    d = DecodeServer(model, params, batch_size=2,
                     max_seq_len=24).run(_requests(cfg, 3, new=4))
    p = PagedEngine(model, params, batch_size=2, max_seq_len=24,
                    page_size=4).run(_requests(cfg, 3, new=4))
    _assert_token_parity(d, p)


def test_small_pages_parity_and_memory_accounting():
    """Multi-page sequences (page_size 4) keep token parity, and the
    in-use byte accounting matches the pool counters exactly."""
    cfg, model, params = _model()
    d = DecodeServer(model, params, batch_size=3,
                     max_seq_len=32).run(_requests(cfg, 7))
    eng = PagedEngine(model, params, batch_size=3, max_seq_len=32,
                      page_size=4)
    p = eng.run(_requests(cfg, 7))
    _assert_token_parity(d, p)
    m = eng.metrics()
    assert m["cache_in_use_bytes"] == \
        eng.pool.in_use * eng.cache_page_bytes()
    assert m["pool"]["peak_in_use"] <= eng.num_pages
    assert m["requests"] == 7 and m["latency_p95"] >= m["latency_p50"]
    eng.pool.check_invariants()


# ----------------------------------------------------------------------
# preemption
# ----------------------------------------------------------------------

def test_pool_exhaustion_preempts_and_completes():
    """A pool too small for the whole batch forces evictions; every
    request still finishes with its full token budget, and the greedy
    outputs equal an uncontended reference run (the re-queued prompt =
    prompt + generated reconstruction is exact under greedy)."""
    cfg, model, params = _model()
    # bulk mode: the prefill_forwards assert below counts one forward
    # per (re-)admission (chunked-mode preemption is covered in
    # tests/test_chunked_prefill.py)
    reference = PagedEngine(model, params, batch_size=3, max_seq_len=32,
                            page_size=4, prefill_chunk_tokens=0)
    ref = reference.run(_requests(cfg, 6, new=8))

    tight = PagedEngine(model, params, batch_size=3, max_seq_len=32,
                        page_size=4, num_pages=6, prefill_chunk_tokens=0)
    out = tight.run(_requests(cfg, 6, new=8))
    assert tight.pool.metrics.preemptions >= 1
    assert all(len(r.generated) == 8 for r in out)
    _assert_token_parity(ref, out)
    # preempted requests were re-prefilled: more prefill forwards than
    # admissions-from-queue alone
    assert tight.prefill_forwards > 6
    tight.pool.check_invariants()
    # finished requests returned their pages; only prefix-cache entries
    # still hold any, and spilling the cache drains the pool completely
    tight.prefix.drop_all()
    assert tight.pool.in_use == 0


def test_oversized_request_rejected():
    cfg, model, params = _model()
    eng = PagedEngine(model, params, batch_size=2, max_seq_len=16,
                      page_size=4)
    with pytest.raises(ValueError):
        eng.enqueue(Request(uid=0, prompt=[1] * 12, max_new_tokens=8))
    eng2 = PagedEngine(model, params, batch_size=1, max_seq_len=32,
                       page_size=4, num_pages=2)
    with pytest.raises(ValueError):
        eng2.enqueue(Request(uid=0, prompt=[1] * 10, max_new_tokens=8))


def test_empty_prompt_decodes_from_bos():
    cfg, model, params = _model()
    eng = PagedEngine(model, params, batch_size=2, max_seq_len=16,
                      page_size=4)
    req = Request(uid=0, prompt=[], max_new_tokens=3)
    eng.run([req])
    assert len(req.generated) == 3
    d = Request(uid=0, prompt=[], max_new_tokens=3)
    DecodeServer(model, params, batch_size=2, max_seq_len=16).run([d])
    assert req.generated == d.generated


# ----------------------------------------------------------------------
# shared-prefix copy-on-write
# ----------------------------------------------------------------------

def test_shared_prefix_bitwise_logits_and_cow():
    """Two same-length prompts with a common prefix share pages until
    the divergence point (full pages + one partial page COW'd on
    write); every decode logit is BITWISE equal to an engine with
    sharing disabled, and sharing strictly reduces page allocations."""
    cfg, model, params = _model()

    def reqs():
        # page_size 4: page0 fully shared, page1 holds one common token
        # (position 4) before the length-6 prompts diverge at position 5
        # — the second admission shares page1 partially and COWs it
        base = [5, 9, 3, 7, 2]
        return [Request(uid=0, prompt=base + [11], max_new_tokens=5),
                Request(uid=1, prompt=base + [12], max_new_tokens=5)]

    shared = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                         page_size=4, trace_logits=True)
    unshared = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                           page_size=4, share_prefixes=False,
                           trace_logits=True)
    a = shared.run(reqs())
    b = unshared.run(reqs())
    _assert_token_parity(a, b)
    for uid in (0, 1):
        np.testing.assert_array_equal(
            np.stack(shared.logit_trace[uid]),
            np.stack(unshared.logit_trace[uid]))
    assert shared.pool.metrics.prefix_hits >= 2     # page0 + partial page1
    assert shared.pool.metrics.cow_copies >= 1      # divergence mid-page
    assert shared.pool.metrics.allocs < unshared.pool.metrics.allocs


def test_identical_prompt_shares_all_pages_then_cows_on_decode():
    """Resubmitting an identical prompt shares every prompt page; the
    first decode write into the shared partial page goes through the
    COW gate, and both requests decode the same greedy continuation."""
    cfg, model, params = _model()
    prompt = [4, 8, 2, 6, 9, 1]
    eng = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                      page_size=4)
    out = eng.run([Request(uid=0, prompt=list(prompt), max_new_tokens=5),
                   Request(uid=1, prompt=list(prompt), max_new_tokens=5)])
    assert out[0].generated == out[1].generated
    assert eng.pool.metrics.prefix_hits >= 2
    assert eng.pool.metrics.cow_copies >= 1
    single = PagedEngine(model, params, batch_size=1, max_seq_len=32,
                         page_size=4, share_prefixes=False)
    solo = single.run([Request(uid=0, prompt=list(prompt),
                               max_new_tokens=5)])
    assert solo[0].generated == out[0].generated


# ----------------------------------------------------------------------
# paged-attention kernel vs jnp oracle
# ----------------------------------------------------------------------

@settings(max_examples=6, deadline=None)   # first example compiles
@given(seed=st.integers(0, 1000), page_size=st.sampled_from([4, 8, 16]),
       windowed=st.booleans())
def test_paged_attention_kernel_matches_ref(seed, page_size, windowed):
    key = jax.random.key(seed)
    B, H, kvh, hd, NP, M = 3, 4, 2, 8, 12, 3
    mk = lambda i, s: jax.random.normal(jax.random.fold_in(key, i), s)
    q = mk(0, (B, H, hd))
    k = mk(1, (NP, page_size, kvh, hd))
    v = mk(2, (NP, page_size, kvh, hd))
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(NP)[:B * M].reshape(B, M), jnp.int32)
    lens = jnp.asarray(rng.integers(1, M * page_size + 1, B), jnp.int32)
    window = 5 if windowed else None
    ref = paged_attention_ref(q, k, v, table, lens, window=window)
    out = paged_attention_op(q, k, v, table, lens, window=window,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("page_size,width,lanes,itemsize,rows", [
    (16, 16, 512, 2, 128),     # granite-3-2b.serve: 8 pages of 16 a step
    (16, 4, 512, 2, 64),       # capped at a narrow table
    (2, 128, 16, 4, 128),      # many small pages make the block
    (4096, 32, 1024, 2, 512),  # a page past the budget: sub-page tiles
])
def test_gqa_block_rows_follow_the_shapes(page_size, width, lanes,
                                          itemsize, rows):
    """The GQA kernel's step reads whole pages making >= 128 rows,
    capped at the table width and at the VMEM budget of double-buffered
    K and V blocks as stored; a page past the budget alone is cut into
    tiles of whole sublane tiles."""
    got = _block_rows(page_size, width, lanes, itemsize)
    assert got == rows
    assert 2 * 2 * got * lanes * itemsize <= (4 << 20)


def test_paged_state_specs_replicate_pages_shard_heads():
    """Production placement rule (launch/specs.paged_state_specs): pool
    page dims replicate over 'data' (any slot reads any page), only the
    trailing feature dims may shard over 'model'; recurrent and table
    leaves keep the dense batch-over-'data' rule."""
    from types import SimpleNamespace
    from jax.sharding import PartitionSpec as P
    from repro.launch.specs import paged_state_specs
    from repro.models.layers import KVCache
    from repro.models.mla import MLACache

    mesh = SimpleNamespace(shape={"data": 4, "model": 4},
                           axis_names=("data", "model"))
    kv = KVCache(k=jax.ShapeDtypeStruct((64, 16, 4 * 32), jnp.float32),
                 v=jax.ShapeDtypeStruct((64, 16, 4 * 32), jnp.float32))
    mla = MLACache(c_kv=jax.ShapeDtypeStruct((64, 16, 32), jnp.float32),
                   k_rope=jax.ShapeDtypeStruct((64, 16, 16), jnp.float32))
    recurrent = jax.ShapeDtypeStruct((8, 6, 24), jnp.float32)   # (B, ...)
    table = jax.ShapeDtypeStruct((8, 5), jnp.int32)
    specs = paged_state_specs(((kv, recurrent), mla, table), mesh)
    (kv_s, rec_s), mla_s, table_s = specs
    # the merged kvH * hd = 128 shards over 'model'; the (NP=64, P=16)
    # page dims never shard even though both divide the data axis
    assert kv_s.k == P(None, None, "model")
    assert mla_s.c_kv == P(None, None, "model")  # latent rank only
    assert rec_s == P("data", None, "model")     # dense batch rule
    assert table_s == P("data", None)
    # big pages are sub-tiled back under the budget
    big = paged_attention_vmem_bytes(page_size=4096, kvh=8, hd=128,
                                     num_q_heads=32)
    assert big < (5 << 20)
    small = paged_attention_vmem_bytes(page_size=16, kvh=2, hd=32,
                                       num_q_heads=4)
    assert small < (1 << 20)
