"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) never applies the TPU
compiler's rules, e.g. that the last two dims of a block must be
multiples of (8, 128) or equal the array's own dims.  These tests
compile each kernel of the trainer and serving paths at real widths
for a *described* v5e chip — the TPU compiler is installed even where
no chip is attached — and check that the program holds the kernel
(``tpu_custom_call``).  Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and test workers
that each import this file must all collect the same tests.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dasha_update as du
from repro.kernels import paged_attention as pa

# The fixture turns the persistent cache off; should an entry written
# for a described chip still be looked up, JAX warns that it cannot
# read it back and compiles again, which is harmless here.
pytestmark = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry:UserWarning")

F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16
D = 2048 * 8192          # one granite-3-2b MLP matrix, flattened
HP = dict(b=0.3, a=0.1, pa=0.5)
# serve-alpaca's paged pass: 32 slots, 32 query / 8 kv heads of 64, a
# pool of 1024 pages of 16 stored bf16 with the heads merged, tables
# of up to 32 pages
SERVE = dict(B=32, H=32, kvh=8, hd=64, P=16, NP=1024, M=32)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_cases():
    kb = (D // 128) // 64
    B, C, H, kvh, hd, P, M = 8, 16, 32, 8, 64, 16, 128
    NP = B * M

    def gqa(c):
        return (lambda q, k, v, t, s, ql: pa.paged_attention_batched_pallas(
                    q, k, v, t, s, ql, interpret=False),
                [((B, c, H, hd), F32), ((NP, P, kvh, hd), F32),
                 ((NP, P, kvh, hd), F32), ((B, M), I32), ((B,), I32),
                 ((B,), I32)])

    def merged_gqa(c, B, H, kvh, hd, P, NP, M):
        pool = ((NP, P, kvh * hd), BF16)
        return (lambda q, k, v, t, s, ql: pa.paged_attention_batched_pallas(
                    q, k, v, t, s, ql, interpret=False),
                [((B, c, H, hd), F32), pool, pool, ((B, M), I32),
                 ((B,), I32), ((B,), I32)])

    return {
        "paged_gqa_serve_decode": merged_gqa(1, **SERVE),
        "paged_gqa_serve_chunk64": merged_gqa(64, **SERVE),
        # other shapes the kernel adapts to: one 128-lane tile of two
        # heads, heads of 128 lanes each, a page past the VMEM budget
        # (walked in sub-page tiles)
        "paged_gqa_lanes128": merged_gqa(4, 8, 8, 2, 64, 16, 256, 32),
        "paged_gqa_hd128": merged_gqa(4, 8, 32, 8, 128, 16, 256, 32),
        "paged_gqa_big_pages": merged_gqa(4, 4, 32, 8, 128, 4096, 8, 2),
        "dasha_update_batched": (
            lambda gn, go, h, gi, m: du.dasha_update_batched_pallas(
                gn, go, h, gi, m, interpret=False, **HP),
            [((1, D), F32)] * 4 + [((1,), F32)]),
        "dasha_h_update": (
            lambda gn, go, h, p: du.dasha_h_update_pallas(
                gn, go, h, p, b=0.3, pa=0.5, interpret=False),
            [((D,), F32)] * 3 + [((), F32)]),
        "blockrandk_payload_bs128": (
            lambda gn, go, h, gi, i: du.dasha_payload_blocks_pallas(
                gn, go, h, gi, i, scale=64.0, block_size=128,
                interpret=False, **HP),
            [((D,), F32)] * 4 + [((kb,), I32)]),
        "paged_gqa_decode": gqa(1),
        "paged_gqa_chunked_prefill": gqa(C),
        "paged_mla_r512_rope64": (
            lambda qa, qr, c, kr, t, s, ql: pa.paged_mla_attention_pallas(
                qa, qr, c, kr, t, s, ql, scale=0.07, interpret=False),
            [((B, C, 16, 512), F32), ((B, C, 16, 64), F32),
             ((NP, P, 512), F32), ((NP, P, 64), F32), ((B, M), I32),
             ((B,), I32), ((B,), I32)]),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_training_attention_compiles_to_flash_kernels(one_chip, monkeypatch):
    """The routed self-attention at train-d8-seq4k's shapes (granite-3-2b:
    T 4095, 32 query / 8 KV heads of 64, bf16), differentiated through
    ``jax.checkpoint`` as the model's layers are, compiles to the flash
    kernel's forward and its fused backward (dq, dk and dv in the dkv
    launch) and to no f32 score scan."""
    from repro.models.layers import self_attention

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    B, T, H, kvh, hd = 1, 4095, 32, 8, 64

    def loss(q, k, v):
        out = self_attention(q, k, v, jnp.arange(T), causal=True,
                             window=None)
        return jnp.sum(out.astype(F32))

    grad = jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in [(B, T, H, hd), (B, T, kvh, hd), (B, T, kvh, hd)]]
    text = _compile(grad, *args).as_text()
    assert text.count("tpu_custom_call") >= 2
    for phase in ("fwd", "dkv"):
        assert f"splash_mqa_{phase}" in text
    assert " while(" not in text



def _assert_pool_as_stored(text: str) -> None:
    """Every value of the compiled text that holds a layer pool's
    elements is the stored bf16 pool, ``(NP, P, kvH * hd)`` or its
    ``(NP * P, kvH * hd)`` rows, in row-major order (memory-space moves
    and views aside), and none is a transpose or a convert: no f32 and
    no head-major copy of the pool exists."""
    g = SERVE
    lanes = g["kvh"] * g["hd"]
    views = {(g["NP"], g["P"], lanes), (g["NP"] * g["P"], lanes)}
    seen = 0
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]\{([\d,]*)[:}]\S*\s+"
                         r"([\w-]+)\(", text):
        dims = tuple(int(d) for d in m.group(2).split(","))
        if math.prod(dims) != g["NP"] * g["P"] * lanes:
            continue
        seen += 1
        dtype, order, op = m.group(1), m.group(3), m.group(4)
        assert dtype == "bf16" and dims in views, (op, dtype, dims)
        assert order == ",".join(map(str, reversed(range(len(dims))))), \
            (op, dims, order)
        assert op not in ("transpose", "convert"), (op, dims)
    assert seen >= 2   # the K and V pools themselves


@pytest.mark.parametrize("name", ["paged_gqa_serve_decode",
                                  "paged_gqa_serve_chunk64"])
def test_paged_gqa_kernel_reads_the_pool_as_stored(one_chip, name):
    """At serve-alpaca's shapes the kernel takes the bf16 pool as the
    engine stores it: nothing is made of it around the launch — no f32
    cast, no head-major transpose, no relayout copy."""
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in text
    _assert_pool_as_stored(text)


@pytest.mark.parametrize("chunk", [1, 64])
def test_paged_serve_layer_keeps_the_pool_as_stored(one_chip, monkeypatch,
                                                    chunk):
    """One paged attention layer of the serve pass at granite-3-2b's
    widths on the pool as the model stores it (the KV write into the
    donated pool, then the kernel's read), compiled for a described
    v5e, makes no f32 or head-major copy of the pool."""
    from repro.models import Model, get_config
    from repro.models.layers import KVCache, attention_block, init_attention

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    cfg = get_config("granite-3-2b")
    g = SERVE
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.hd) == (g["H"], g["kvh"],
                                                         g["hd"])
    model = Model(cfg)
    state = jax.eval_shape(lambda: model.init_paged_state(
        g["B"], g["NP"], g["P"], g["M"]))
    stored = jax.tree.leaves(state.caches)[0]   # one layer's K pool
    stored_shape = stored.shape[1:] if model.scan else stored.shape

    def layer(p, x, cache, table, start, q_lens):
        pos = start[:, None] + jnp.arange(chunk)[None]
        return attention_block(p, x, pos, cfg, cache=cache, cache_pos=start,
                               paged_table=table, paged_kernel=True,
                               q_lens=q_lens)

    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    pool = sds(stored_shape, stored.dtype)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: init_attention(jax.random.key(0), cfg)))
    args = (params, sds((g["B"], chunk, cfg.d_model), BF16),
            KVCache(k=pool, v=pool), sds((g["B"], g["M"]), I32),
            sds((g["B"],), I32), sds((g["B"],), I32))
    text = jax.jit(layer, donate_argnums=(2,)).lower(*args).compile() \
        .as_text()
    assert "paged_attention_batched" in text
    _assert_pool_as_stored(text)
