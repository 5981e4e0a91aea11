"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned family — 2 layers, d_model <= 512, <= 4 experts — one forward /
train step on CPU asserting output shapes and no NaNs; plus decode
consistency and attention-path equivalences."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ARCH_IDS, Model, count_params, get_smoke_config
from repro.kernels.ops import flash_attention_op
from repro.models.layers import (attention_weights_mask,
                                 blockwise_gqa_attention, gqa_attention,
                                 self_attention)

B, T = 2, 16


def _batch(cfg, key, t=T):
    if cfg.frontend == "audio":
        return {"embeds": jax.random.normal(key, (B, t, cfg.d_model),
                                            cfg.param_dtype),
                "targets": jax.random.randint(key, (B, t), 0,
                                              cfg.vocab_size)}
    if cfg.frontend == "vision":
        return {"embeds": jax.random.normal(
                    key, (B, cfg.frontend_tokens, cfg.d_model),
                    cfg.param_dtype),
                "tokens": jax.random.randint(key, (B, t), 0,
                                             cfg.vocab_size)}
    return {"tokens": jax.random.randint(key, (B, t), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    """One forward + one gradient step on the reduced config."""
    cfg = get_smoke_config(arch)
    assert cfg.num_layers == 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    model = Model(cfg)
    key = jax.random.key(0)
    params = model.init_params(key)
    batch = _batch(cfg, jax.random.key(1))

    logits, aux = jax.jit(model.forward)(params, batch)
    t_expect = (T + cfg.frontend_tokens if cfg.frontend == "vision" else
                T)
    assert logits.shape == (B, t_expect, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert jnp.isfinite(loss)
    gn = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gn) and float(gn) > 0
    # one SGD step still yields finite loss
    params2 = jax.tree.map(lambda p, g: p - 1e-2 * g.astype(p.dtype),
                           params, grads)
    loss2 = jax.jit(model.loss)(params2, batch)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if a != "hubert-xlarge"])
def test_smoke_decode_matches_forward(arch):
    """prefill -> one serve_step equals the (T+1)-token forward."""
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    last, state = jax.jit(
        lambda p, b: model.prefill(p, b, extra_capacity=4))(params, batch)
    assert last.shape == (B, cfg.vocab_size)
    tok = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
    logits, state2 = jax.jit(model.serve_step)(params, tok, state)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert int(state2.position) == int(state.position) + 1
    if cfg.frontend is None:
        batch2 = {"tokens": jnp.concatenate([batch["tokens"], tok], 1)}
        ref = model.forward(params, batch2)[0][:, -1, :cfg.vocab_size]
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                    - logits.astype(jnp.float32))))
        assert err < 5e-3, err


def test_encoder_has_no_decode():
    cfg = get_smoke_config("hubert-xlarge")
    assert cfg.is_encoder and not cfg.supports_decode


def test_long_context_variants():
    """for_long_context() enables SWA exactly for the full-attention
    archs and leaves SSM/hybrid untouched."""
    from repro.models import get_config
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        lc = cfg.for_long_context()
        if arch in ("xlstm-350m", "hymba-1.5b"):
            assert lc.attention_window == cfg.attention_window
        elif arch == "hubert-xlarge":
            pass
        else:
            assert lc.attention_window == 4096
            assert cfg.attention_window is None  # decode_32k keeps full KV


def test_blockwise_attention_matches_dense():
    key = jax.random.key(0)
    Bq, Tq, H, kvH, hd = 2, 200, 8, 2, 16
    q = jax.random.normal(key, (Bq, Tq, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (Bq, Tq, kvH, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (Bq, Tq, kvH, hd))
    pos = jnp.arange(Tq)
    for causal, window, prefix in [(True, None, 0), (True, 31, 0),
                                   (True, None, 13), (False, None, 0)]:
        mask = attention_weights_mask(pos, pos, causal, window,
                                      full_prefix=prefix)
        ref = gqa_attention(q, k, v, mask)
        out = blockwise_gqa_attention(q, k, v, pos, pos, causal=causal,
                                      window=window, full_prefix=prefix,
                                      q_block=48, k_block=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


# The flash kernel keeps bf16 operands: probabilities and outputs carry
# 8 significant bits, so an O(1) output is off by a few 2^-8 of the
# largest |v| (~4 here) and a gradient by about 2^-8 of its norm.  f32
# operands leave only the kernel's summation order.
FLASH_TOL = {jnp.bfloat16: dict(out=3e-2, grad=1e-2),
             jnp.float32: dict(out=1e-4, grad=1e-4)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_flash_attention_matches_dense(dtype):
    """The flash kernel in interpret mode against the dense f32 causal
    attention, forward and the gradients of q, k and v.  T 200 is no
    multiple of the block, so the end padding is exercised."""
    key = jax.random.key(0)
    Bq, Tq, H, kvH, hd = 2, 200, 8, 2, 64
    q, k, v, ct = (jax.random.normal(jax.random.fold_in(key, i), shape)
                   for i, shape in enumerate([(Bq, Tq, H, hd),
                                              (Bq, Tq, kvH, hd),
                                              (Bq, Tq, kvH, hd),
                                              (Bq, Tq, H, hd)]))
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    pos = jnp.arange(Tq)
    mask = attention_weights_mask(pos, pos, True, None)

    def dense(q, k, v):
        f32 = jnp.float32
        return gqa_attention(q.astype(f32), k.astype(f32), v.astype(f32),
                             mask)

    def flash(q, k, v):
        return flash_attention_op(q, k, v, interpret=True)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)

    tol = FLASH_TOL[dtype]
    out = jax.jit(flash)(q, k, v)
    assert out.shape == q.shape and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense(q, k, v)), rtol=0,
                               atol=tol["out"])
    grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    refs = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, refs):
        assert g.dtype == dtype
        err = jnp.linalg.norm(g.astype(jnp.float32) - r) / jnp.linalg.norm(r)
        assert float(err) < tol["grad"]


@pytest.mark.parametrize(
    "causal, window, prefix, on_tpu, devices, T, path",
    [(True, None, 0, True, 1, 1100, "flash"),
     (True, 64, 0, True, 1, 1100, "scan"),
     (True, None, 5, True, 1, 1100, "scan"),
     (False, None, 0, True, 1, 1100, "scan"),
     (True, None, 0, False, 1, 1100, "scan"),
     (True, None, 0, True, 4, 1100, "scan"),
     (True, None, 0, True, 1, 1024, "dense")])
def test_self_attention_routing(monkeypatch, causal, window, prefix,
                                on_tpu, devices, T, path):
    """Past 1024 tokens only a plain causal mask on one TPU chip takes
    the flash kernel; windowed, prefix-LM, bidirectional, off-TPU runs
    and programs over a mesh of several devices take the blockwise scan.
    Traced only, never run: the TPU is stood in for by compiled (not
    interpreted) kernels, the mesh by an abstract one."""
    from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0" if on_tpu else "1")
    on = NamedSharding(AbstractMesh((devices, 1), ("data", "model")),
                       PartitionSpec())
    q = jax.ShapeDtypeStruct((1, T, 4, 64), jnp.bfloat16, sharding=on)
    kv = jax.ShapeDtypeStruct((1, T, 2, 64), jnp.bfloat16, sharding=on)
    jaxpr = str(jax.make_jaxpr(
        lambda q, k, v: self_attention(q, k, v, jnp.arange(T),
                                       causal=causal, window=window,
                                       full_prefix=prefix))(q, kv, kv))
    taken = ("flash" if "pallas_call" in jaxpr else
             "scan" if "scan" in jaxpr else "dense")
    assert taken == path


def test_moe_capacity_drops_bounded():
    """With capacity_factor=1.0 the dispatch keeps <= C tokens per expert
    and the layer still runs/normalizes."""
    import dataclasses
    cfg = get_smoke_config("dbrx-132b")
    cfg = cfg.with_overrides(moe=dataclasses.replace(cfg.moe,
                                                     capacity_factor=1.0))
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    loss = jax.jit(model.loss)(params, batch)
    assert jnp.isfinite(loss)


def test_vocab_padding_multiple_of_256():
    from repro.models import get_config
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size
        assert cfg.padded_vocab - cfg.vocab_size < 256
