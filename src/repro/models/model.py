"""Model assembly: embeddings -> blocks (scan or unrolled) -> head.

One :class:`Model` class covers all six arch families via block
composition:

* ``dense``   — GQA attention + gated MLP (granite, yi, qwen, llama3)
* ``moe``     — GQA attention + MoE FFN (dbrx); ``mla`` sub-config swaps
  the attention for Multi-head Latent Attention (deepseek-v2-lite)
* ``ssm``     — xLSTM: mLSTM blocks with every k-th an sLSTM (xlstm-350m)
* ``hybrid``  — parallel attention + Mamba heads per layer (hymba)
* ``audio``   — bidirectional encoder over stub frame embeddings (hubert)
* ``vlm``     — stub patch embeddings prefixed to a gemma-style decoder
  with full attention over the prefix (paligemma)

Training entry: ``loss(params, batch)``; decode entry:
``serve_step(params, token, state)`` (one new token against the cache).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import ssm as ssm_lib
from repro.models.common import ArchConfig
from repro.models.layers import (KVCache, attention_block, embed_init,
                                 init_attention, init_mlp, mlp_block,
                                 rmsnorm)
from repro.models.mla import MLACache, init_mla, mla_block
from repro.models.moe import init_moe, moe_block

Array = jax.Array


class DecodeState(NamedTuple):
    """Per-layer decode state; leaves stacked over layers for scanned
    models, tuples for unrolled (xLSTM)."""
    caches: Any
    position: Array       # scalar int32 — next absolute position


class PagedDecodeState(NamedTuple):
    """Decode state over a shared page pool (DESIGN.md §11).  Attention
    cache leaves (KVCache/MLACache) hold POOL pages — (num_pages,
    page_size, ...) instead of (batch, seq, ...) — addressed through one
    page table shared by every layer (all layers allocate identically,
    so one logical page id maps to the same physical row per layer).
    Recurrent (SSM/mamba) leaves stay dense per-slot: they are O(1) per
    sequence and have nothing to page."""
    caches: Any
    page_table: Array     # (B, max_pages) int32 physical page ids
    seq_lens: Array       # (B,) int32 tokens written per slot


# ======================================================================
# Blocks
# ======================================================================

def _block_init(key: Array, cfg: ArchConfig, kind: str) -> dict:
    ks = jax.random.split(key, 8)
    dt = cfg.param_dtype
    D = cfg.d_model
    p = {"ln1": jnp.ones((D,), dt)}
    if kind in ("dense", "encoder", "vlm"):
        p["attn"] = init_attention(ks[0], cfg)
        p["ln2"] = jnp.ones((D,), dt)
        p["mlp"] = init_mlp(ks[1], D, cfg.d_ff, dt)
    elif kind == "moe":
        if cfg.mla is not None:
            p["attn"] = init_mla(ks[0], cfg)
        else:
            p["attn"] = init_attention(ks[0], cfg)
        p["ln2"] = jnp.ones((D,), dt)
        p["moe"] = init_moe(ks[1], cfg)
    elif kind == "hybrid":
        p["attn"] = init_attention(ks[0], cfg)
        p["mamba"] = ssm_lib.init_mamba(ks[1], cfg)
        p["ln_attn"] = jnp.ones((D,), dt)
        p["ln_mamba"] = jnp.ones((D,), dt)
        p["ln2"] = jnp.ones((D,), dt)
        p["mlp"] = init_mlp(ks[2], D, cfg.d_ff, dt)
    elif kind == "mlstm":
        p["mix"] = ssm_lib.init_mlstm(ks[0], cfg)
    elif kind == "slstm":
        p["mix"] = ssm_lib.init_slstm(ks[0], cfg)
    else:
        raise ValueError(kind)
    return p


def _block_apply(p: dict, x: Array, positions: Array, cfg: ArchConfig,
                 kind: str, cache=None, cache_pos=None, prefix_len: int = 0,
                 update=None, paged_table=None,
                 paged_kernel: bool = False,
                 q_lens=None) -> Tuple[Array, Any, Array]:
    """-> (x_out, new_cache, aux_loss).  ``update`` (decode only): (B,)
    mask of batch slots whose attention caches may be written; recurrent
    (SSM) states are masked by the caller (:meth:`Model.serve_step`).
    ``paged_table`` (paged decode only): the (B, max_pages) page table
    routed to the attention caches — recurrent states never page.
    ``q_lens`` (fused paged decode only): per-slot valid-token counts
    for the multi-query contract (layers.attention_block)."""
    aux = jnp.zeros((), jnp.float32)
    causal = not cfg.is_encoder
    if kind in ("dense", "encoder", "vlm"):
        h, new_cache = attention_block(p["attn"], rmsnorm(x, p["ln1"], cfg.rms_eps),
                                       positions, cfg, cache=cache,
                                       cache_pos=cache_pos, causal=causal,
                                       full_prefix=prefix_len,
                                       update=update,
                                       paged_table=paged_table,
                                       paged_kernel=paged_kernel,
                                       q_lens=q_lens)
        x = x + h
        x = x + mlp_block(p["mlp"], rmsnorm(x, p["ln2"], cfg.rms_eps),
                          activation="gelu" if kind == "vlm" else "silu")
    elif kind == "moe":
        xn = rmsnorm(x, p["ln1"], cfg.rms_eps)
        if cfg.mla is not None:
            h, new_cache = mla_block(p["attn"], xn, positions, cfg,
                                     cache=cache, cache_pos=cache_pos,
                                     update=update,
                                     paged_table=paged_table,
                                     paged_kernel=paged_kernel,
                                     q_lens=q_lens)
        else:
            h, new_cache = attention_block(p["attn"], xn, positions, cfg,
                                           cache=cache, cache_pos=cache_pos,
                                           causal=True, update=update,
                                           paged_table=paged_table,
                                           paged_kernel=paged_kernel,
                                           q_lens=q_lens)
        x = x + h
        mo, aux = moe_block(p["moe"], rmsnorm(x, p["ln2"], cfg.rms_eps), cfg)
        x = x + mo
    elif kind == "hybrid":
        xn = rmsnorm(x, p["ln1"], cfg.rms_eps)
        a_cache = m_state = None
        if cache is not None:
            a_cache, m_state = cache
        h_attn, a_new = attention_block(p["attn"], xn, positions, cfg,
                                        cache=a_cache, cache_pos=cache_pos,
                                        causal=True, update=update,
                                        paged_table=paged_table,
                                        paged_kernel=paged_kernel,
                                        q_lens=q_lens)
        h_mamba, m_new = ssm_lib.mamba_forward(p["mamba"], xn, cfg,
                                               state=m_state)
        # parallel-head fusion (arXiv:2411.13676): mean of normalized outputs
        fused = 0.5 * (rmsnorm(h_attn, p["ln_attn"], cfg.rms_eps)
                       + rmsnorm(h_mamba, p["ln_mamba"], cfg.rms_eps))
        x = x + fused
        x = x + mlp_block(p["mlp"], rmsnorm(x, p["ln2"], cfg.rms_eps))
        new_cache = (a_new, m_new)
    elif kind == "mlstm":
        xn = rmsnorm(x, p["ln1"], cfg.rms_eps)
        h, new_cache = ssm_lib.mlstm_forward(p["mix"], xn, cfg, state=cache)
        x = x + h
    elif kind == "slstm":
        xn = rmsnorm(x, p["ln1"], cfg.rms_eps)
        h, new_cache = ssm_lib.slstm_forward(p["mix"], xn, cfg, state=cache)
        x = x + h
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def _pad_cache_capacity(caches: Any, extra: int) -> Any:
    """Grow the sequence axis of attention caches by ``extra`` empty
    slots (SSM states are O(1) and untouched).  Works for stacked
    (L, B, S, ...) and unstacked (B, S, ...) layouts: the seq axis sits
    at -3 for KVCache and -2 for MLACache leaves."""

    def pad_axis(x, axis):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, extra)
        return jnp.pad(x, widths)

    def rec(c):
        if isinstance(c, KVCache):
            return KVCache(k=pad_axis(c.k, -3), v=pad_axis(c.v, -3))
        if isinstance(c, MLACache):
            return MLACache(c_kv=pad_axis(c.c_kv, -2),
                            k_rope=pad_axis(c.k_rope, -2))
        if isinstance(c, tuple) and not hasattr(c, "_fields"):
            return tuple(rec(e) for e in c)
        return c

    return rec(caches)


def map_cache_tree(tree: Any, on_attention, on_leaf, other: Any = None
                   ) -> Any:
    """THE decode-cache tree walk: ``on_attention`` handles whole
    KVCache/MLACache nodes, ``on_leaf`` every other array leaf
    (recurrent SSM state), tuples/NamedTuples recurse preserving type.
    With ``other`` given, walks two same-structure trees zipped and the
    callbacks take ``(leaf, other_leaf)``.  Every paged/dense cache
    transformation (masking, prefill scatter, COW copy, sharding specs,
    byte accounting) goes through here, so a new attention-cache
    NamedTuple is added in ONE place."""
    zipped = other is not None

    def rec(c, o):
        if isinstance(c, (KVCache, MLACache)):
            return on_attention(c, o) if zipped else on_attention(c)
        if isinstance(c, tuple):
            pairs = zip(c, o) if zipped else ((e, None) for e in c)
            merged = tuple(rec(e, oe) for e, oe in pairs)
            return type(c)(*merged) if hasattr(c, "_fields") else merged
        return on_leaf(c, o) if zipped else on_leaf(c)

    return rec(tree, other)


def _mask_recurrent_states(old: Any, new: Any, update: Array,
                           batch_axis: int) -> Any:
    """Merge decode states for a per-slot ``update`` mask: attention
    caches (KVCache/MLACache) already routed masked-out writes to a
    dropped row inside their blocks and pass through; every other array
    leaf is a recurrent (SSM) state updated wholesale, so masked-out
    slots get their OLD rows back along ``batch_axis`` (1 for stacked
    scan layouts, 0 for unstacked)."""

    def merge(o, n):
        shape = [1] * n.ndim
        shape[batch_axis] = n.shape[batch_axis]
        return jnp.where(update.reshape(shape), n, o)

    return map_cache_tree(old, on_attention=lambda o, n: n, on_leaf=merge,
                          other=new)


# ======================================================================
# Model
# ======================================================================

def _layer_kinds(cfg: ArchConfig) -> Tuple[str, ...]:
    if cfg.arch_type == "dense":
        return ("dense",) * cfg.num_layers
    if cfg.arch_type == "moe":
        return ("moe",) * cfg.num_layers
    if cfg.arch_type == "hybrid":
        return ("hybrid",) * cfg.num_layers
    if cfg.arch_type == "audio":
        return ("encoder",) * cfg.num_layers
    if cfg.arch_type == "vlm":
        return ("vlm",) * cfg.num_layers
    if cfg.arch_type == "ssm":  # xLSTM mix
        k = cfg.xlstm.slstm_every
        return tuple("slstm" if (i + 1) % k == 0 else "mlstm"
                     for i in range(cfg.num_layers))
    raise ValueError(cfg.arch_type)


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.kinds = _layer_kinds(cfg)
        self.uniform = len(set(self.kinds)) == 1
        self.scan = cfg.scan_layers and self.uniform

    @property
    def attention_only(self) -> bool:
        """True when every layer's decode state is attention cache only
        (no recurrent SSM/mamba leaves) — the archs eligible for padded-
        bucket prefill and chunked (multi-token) paged decode: tail
        padding sits behind the causal mask, whereas a recurrent scan
        would thread garbage tokens through its state."""
        return all(k in ("dense", "encoder", "vlm", "moe")
                   for k in self.kinds)

    # -- params ----------------------------------------------------------
    def init_params(self, key: Array) -> dict:
        cfg = self.cfg
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        params: dict = {"final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype)}
        # vocab padded to a multiple of 256 so embed/head shard over the
        # model axis (common.ArchConfig.padded_vocab)
        if cfg.frontend != "audio":
            params["embed"] = embed_init(k_embed, cfg.padded_vocab,
                                         cfg.d_model, cfg.param_dtype)
        else:
            params["embed_norm"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
        if not cfg.tie_embeddings or cfg.frontend == "audio":
            params["lm_head"] = embed_init(k_head, cfg.padded_vocab,
                                           cfg.d_model, cfg.param_dtype).T

        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        if self.uniform:
            kind = self.kinds[0]
            if self.scan:
                params["layers"] = jax.vmap(
                    lambda k: _block_init(k, cfg, kind))(layer_keys)
            else:
                stacked = [_block_init(k, cfg, kind) for k in layer_keys]
                params["layers"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *stacked)
        else:
            params["layers"] = tuple(
                _block_init(k, cfg, kind)
                for k, kind in zip(layer_keys, self.kinds))
        return params

    # -- embedding -------------------------------------------------------
    def _embed(self, params: dict, batch: dict) -> Tuple[Array, Array]:
        """-> (x (B, T, D), positions (T,))."""
        cfg = self.cfg
        if cfg.frontend == "audio":
            x = rmsnorm(batch["embeds"], params["embed_norm"], cfg.rms_eps)
        elif cfg.frontend == "vision":
            tok = params["embed"][batch["tokens"]]
            x = jnp.concatenate([batch["embeds"].astype(tok.dtype), tok], axis=1)
        else:
            x = params["embed"][batch["tokens"]]
        if cfg.frontend != "vlm":
            x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
        positions = jnp.arange(x.shape[1])
        return x, positions

    # -- forward ----------------------------------------------------------
    def forward(self, params: dict, batch: dict, *,
                collect_caches: bool = False, last_token_only: bool = False,
                last_index: Optional[Array] = None):
        """Training/prefill forward.  -> (logits (B, T, V_pad), aux_loss)
        [+ per-layer caches if ``collect_caches``].  ``last_index``
        (bucketed prefill): dynamic true prompt length — the head runs
        on the single hidden state at position ``last_index - 1``, so a
        prompt padded to its bucket emits the same logits as the
        unpadded run (causal masking keeps tail padding out of every
        earlier position)."""
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        prefix_len = (batch["embeds"].shape[1]
                      if cfg.frontend == "vision" else 0)

        if self.scan:
            kind = self.kinds[0]

            def body(carry, layer_p):
                h, aux = carry
                h, c, a = _block_apply(layer_p, h, positions, cfg, kind,
                                       prefix_len=prefix_len)
                return (h, aux + a), (c if collect_caches else None)

            body_fn = jax.checkpoint(body) if cfg.remat else body
            (x, aux), caches = jax.lax.scan(
                body_fn, (x, jnp.zeros((), jnp.float32)), params["layers"])
        else:
            aux = jnp.zeros((), jnp.float32)
            cache_list = []
            layers = params["layers"]
            for i, kind in enumerate(self.kinds):
                lp = (layers[i] if isinstance(layers, tuple)
                      else jax.tree.map(lambda t: t[i], layers))
                apply = functools.partial(_block_apply, kind=kind,
                                          prefix_len=prefix_len)
                if cfg.remat:
                    apply = jax.checkpoint(apply, static_argnums=(3,))
                x, c, a = apply(lp, x, positions, cfg)
                cache_list.append(c)
                aux = aux + a
            caches = tuple(cache_list) if collect_caches else None

        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        if last_index is not None:
            idx = jnp.maximum(jnp.asarray(last_index, jnp.int32) - 1, 0)
            x = jax.lax.dynamic_slice_in_dim(x, idx, 1, 1)
        elif last_token_only:
            x = x[:, -1:]
        head = (params["embed"].T if self.cfg.tie_embeddings
                and "lm_head" not in params else params["lm_head"])
        logits = x @ head
        if collect_caches:
            return logits, aux, caches
        return logits, aux

    def prefill(self, params: dict, batch: dict, extra_capacity: int = 0,
                true_len: Optional[Array] = None
                ) -> Tuple[Array, "DecodeState"]:
        """Inference prefill: run the full prompt once, return the
        last-position logits (B, vocab) and a DecodeState holding the
        per-layer KV caches / recurrent states for subsequent decode.
        Cache capacity is prompt length + ``extra_capacity`` (ring
        semantics evict the oldest tokens once exhausted).

        ``true_len`` (bucketed prefill, attention-only archs): the
        prompt is padded to a bucket length and ``true_len`` is its real
        length — the returned logits come from position ``true_len - 1``
        and the decode position starts there, so one jit compile serves
        every prompt length in the bucket."""
        cfg = self.cfg
        logits, _, caches = self.forward(
            params, batch, collect_caches=True,
            last_token_only=true_len is None, last_index=true_len)
        if extra_capacity:
            caches = _pad_cache_capacity(caches, extra_capacity)
        if cfg.frontend == "vision":
            T = batch["embeds"].shape[1] + batch["tokens"].shape[1]
        elif cfg.frontend == "audio":
            T = batch["embeds"].shape[1]
        else:
            T = batch["tokens"].shape[1]
        pos = (jnp.asarray(T, jnp.int32) if true_len is None
               else jnp.asarray(true_len, jnp.int32))
        return (logits[:, 0, :cfg.vocab_size],
                DecodeState(caches=caches, position=pos))

    # -- loss --------------------------------------------------------------
    def loss(self, params: dict, batch: dict) -> Array:
        """Next-token CE (decoder), frame CE (audio encoder), or text CE
        on the suffix (VLM)."""
        cfg = self.cfg
        logits, aux = self.forward(params, batch)
        if cfg.frontend == "audio":
            targets = batch["targets"]
            mask = jnp.ones_like(targets, jnp.float32)
        elif cfg.frontend == "vision":
            ptoks = batch["embeds"].shape[1]
            logits = logits[:, ptoks:-1]
            targets = batch["tokens"][:, 1:]
            mask = (targets >= 0).astype(jnp.float32)
        else:
            logits = logits[:, :-1]
            targets = batch["tokens"][:, 1:]
            mask = (targets >= 0).astype(jnp.float32)
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        ce = jnp.sum((lse - tgt) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return ce + aux

    # -- decode -------------------------------------------------------------
    def cache_capacity(self, seq_len: int) -> int:
        w = self.cfg.attention_window
        return min(seq_len, w) if w else seq_len

    def _layer_cache(self, kind: str, batch: int, seq_len: int,
                     dtype) -> Any:
        cfg = self.cfg
        S = self.cache_capacity(seq_len)
        if kind in ("dense", "vlm", "hybrid"):
            kv = KVCache(
                k=jnp.zeros((batch, S, cfg.num_kv_heads, cfg.hd), dtype),
                v=jnp.zeros((batch, S, cfg.num_kv_heads, cfg.hd), dtype))
            if kind == "hybrid":
                return (kv, ssm_lib.mamba_init_state(cfg, batch, dtype=dtype))
            return kv
        if kind == "moe":
            if cfg.mla is not None:
                a = cfg.mla
                return MLACache(
                    c_kv=jnp.zeros((batch, S, a.kv_lora_rank), dtype),
                    k_rope=jnp.zeros((batch, S, a.qk_rope_head_dim), dtype))
            return KVCache(
                k=jnp.zeros((batch, S, cfg.num_kv_heads, cfg.hd), dtype),
                v=jnp.zeros((batch, S, cfg.num_kv_heads, cfg.hd), dtype))
        if kind == "mlstm":
            return ssm_lib.mlstm_init_state(cfg, batch)
        if kind == "slstm":
            return ssm_lib.slstm_init_state(cfg, batch)
        raise ValueError(kind)

    def init_decode_state(self, batch: int, seq_len: int,
                          position: Optional[int] = None) -> DecodeState:
        """Empty caches sized for ``seq_len`` context.  ``position`` is the
        absolute next position (defaults to seq_len: the dry-run scenario
        'cache already holds seq_len tokens')."""
        cfg = self.cfg
        dtype = cfg.param_dtype
        pos = seq_len if position is None else position
        if self.scan:
            single = self._layer_cache(self.kinds[0], batch, seq_len, dtype)
            caches = jax.tree.map(
                lambda t: jnp.broadcast_to(
                    t[None], (cfg.num_layers,) + t.shape).copy(), single)
        else:
            caches = tuple(self._layer_cache(k, batch, seq_len, dtype)
                           for k in self.kinds)
        return DecodeState(caches=caches,
                           position=jnp.asarray(pos, jnp.int32))

    def serve_step(self, params: dict, tokens: Array, state: DecodeState,
                   update: Optional[Array] = None
                   ) -> Tuple[Array, DecodeState]:
        """One decode step.  tokens: (B, 1) int32 -> logits (B, V).

        ``state.position`` may be scalar (all slots advance in lockstep
        — the legacy/dry-run path, bit-identical to before) or a (B,)
        per-slot vector, in which case each slot writes its cache at
        ITS OWN ring position.  ``update`` (requires per-slot
        positions): (B,) bool — masked-out slots touch NOTHING (caches,
        recurrent states, and positions stay put; their returned logits
        are garbage and must be ignored).  This is what lets a serving
        loop prefill one slot while other slots hold live decodes
        (serving/decode.py)."""
        cfg = self.cfg
        x = params["embed"][tokens]
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
        pos = state.position
        per_slot = jnp.ndim(pos) == 1
        if update is not None and not per_slot:
            raise ValueError("serve_step(update=...) needs per-slot "
                             "positions: state.position must be (B,)")
        if per_slot:
            positions = pos[:, None].astype(jnp.int32)   # (B, 1)
        else:
            positions = pos[None].astype(jnp.int32)      # (1,)

        if self.scan:
            kind = self.kinds[0]

            def body(h, xs):
                layer_p, cache = xs
                h, new_cache, _ = _block_apply(layer_p, h, positions, cfg,
                                               kind, cache=cache,
                                               cache_pos=pos,
                                               update=update)
                return h, new_cache

            x, new_caches = jax.lax.scan(body, x,
                                         (params["layers"], state.caches))
        else:
            new_caches = []
            layers = params["layers"]
            for i, kind in enumerate(self.kinds):
                lp = (layers[i] if isinstance(layers, tuple)
                      else jax.tree.map(lambda t: t[i], layers))
                x, nc, _ = _block_apply(lp, x, positions, cfg, kind,
                                        cache=state.caches[i], cache_pos=pos,
                                        update=update)
                new_caches.append(nc)
            new_caches = tuple(new_caches)

        if update is not None:
            new_caches = _mask_recurrent_states(
                state.caches, new_caches, update,
                batch_axis=1 if self.scan else 0)

        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                and "lm_head" not in params else params["lm_head"])
        logits = (x @ head)[:, 0, :cfg.vocab_size]
        if update is None:
            new_pos = pos + 1
        else:
            new_pos = jnp.where(update, pos + 1, pos)
        return logits, DecodeState(caches=new_caches, position=new_pos)

    # -- paged decode (DESIGN.md §11) ---------------------------------------
    def _layer_paged_cache(self, kind: str, num_pages: int, page_size: int,
                           batch: int, dtype) -> Any:
        """One layer's cache with attention leaves laid out as POOL pages
        (num_pages, page_size, ...); recurrent leaves stay (B, ...)."""
        cfg = self.cfg
        # KV pages store the heads merged, (NP, P, kvH * hd): minor
        # dims a TPU tiles as they are, so the paged kernel reads the
        # pool as stored (a (…, kvH, hd) pool is laid out otherwise on
        # the chip, and its merged view would be a copy)
        kv_pages = (num_pages, page_size, cfg.num_kv_heads * cfg.hd)
        if kind in ("dense", "vlm", "hybrid"):
            kv = KVCache(k=jnp.zeros(kv_pages, dtype),
                         v=jnp.zeros(kv_pages, dtype))
            if kind == "hybrid":
                return (kv, ssm_lib.mamba_init_state(cfg, batch, dtype=dtype))
            return kv
        if kind == "moe":
            if cfg.mla is not None:
                a = cfg.mla
                return MLACache(
                    c_kv=jnp.zeros((num_pages, page_size, a.kv_lora_rank),
                                   dtype),
                    k_rope=jnp.zeros((num_pages, page_size,
                                      a.qk_rope_head_dim), dtype))
            return KVCache(k=jnp.zeros(kv_pages, dtype),
                           v=jnp.zeros(kv_pages, dtype))
        if kind == "mlstm":
            return ssm_lib.mlstm_init_state(cfg, batch)
        if kind == "slstm":
            return ssm_lib.slstm_init_state(cfg, batch)
        raise ValueError(kind)

    def init_paged_state(self, batch: int, num_pages: int, page_size: int,
                         max_pages: int) -> PagedDecodeState:
        """Empty paged state: a ``num_pages``-page pool per layer plus a
        zeroed (B, max_pages) page table and (B,) lengths.  The serving
        engine owns the allocator (serving/pages.py); the model only
        reads/writes through the table it is handed."""
        cfg = self.cfg
        dtype = cfg.param_dtype
        if self.scan:
            single = self._layer_paged_cache(self.kinds[0], num_pages,
                                             page_size, batch, dtype)
            caches = jax.tree.map(
                lambda t: jnp.broadcast_to(
                    t[None], (cfg.num_layers,) + t.shape).copy(), single)
        else:
            caches = tuple(
                self._layer_paged_cache(k, num_pages, page_size, batch,
                                        dtype)
                for k in self.kinds)
        return PagedDecodeState(
            caches=caches,
            page_table=jnp.zeros((batch, max_pages), jnp.int32),
            seq_lens=jnp.zeros((batch,), jnp.int32))

    def paged_fused_step(self, params: dict, tokens: Array,
                         state: PagedDecodeState, q_lens: Array,
                         use_kernel: bool = False
                         ) -> Tuple[Array, PagedDecodeState]:
        """THE paged forward (DESIGN.md §11): one launch over all active
        slots, each carrying up to C tokens.  tokens: (B, C) int32 —
        slot ``b``'s tokens land at absolute positions ``seq_lens[b] +
        c`` for ``c < q_lens[b]``; the rest are padding (writes
        drop-routed, outputs garbage).  A pure decode pass is C == 1
        with ``q_lens`` of ones; a chunked-prefill pass folds prompt
        chunks (q_lens up to C) into the same launch.  Returns the
        logits of each slot's LAST valid token (B, vocab) — garbage for
        slots with ``q_lens == 0`` — and the advanced state
        (``seq_lens += q_lens``).  C > 1 requires an attention-only arch
        (recurrent states cannot mask a mid-scan tail)."""
        cfg = self.cfg
        C = tokens.shape[1]
        if C > 1 and not self.attention_only:
            raise ValueError(
                f"fused multi-token paged decode (C={C}) needs an "
                f"attention-only arch; {cfg.arch_type} carries recurrent "
                "state — serve it with C=1 (bulk prefill + plain decode)")
        x = params["embed"][tokens]
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
        start = state.seq_lens
        q_lens = q_lens.astype(jnp.int32)
        positions = (start[:, None]
                     + jnp.arange(C, dtype=jnp.int32)[None])   # (B, C)
        table = state.page_table

        if self.scan:
            kind = self.kinds[0]

            def body(h, xs):
                layer_p, cache = xs
                h, new_cache, _ = _block_apply(layer_p, h, positions, cfg,
                                               kind, cache=cache,
                                               cache_pos=start,
                                               paged_table=table,
                                               paged_kernel=use_kernel,
                                               q_lens=q_lens)
                return h, new_cache

            x, new_caches = jax.lax.scan(body, x,
                                         (params["layers"], state.caches))
        else:
            new_caches = []
            layers = params["layers"]
            for i, kind in enumerate(self.kinds):
                lp = (layers[i] if isinstance(layers, tuple)
                      else jax.tree.map(lambda t: t[i], layers))
                x, nc, _ = _block_apply(lp, x, positions, cfg, kind,
                                        cache=state.caches[i],
                                        cache_pos=start, paged_table=table,
                                        paged_kernel=use_kernel,
                                        q_lens=q_lens)
                new_caches.append(nc)
            new_caches = tuple(new_caches)

        # recurrent leaves update wholesale — restore rows of inactive
        # slots (attention caches already drop-routed their writes)
        new_caches = _mask_recurrent_states(
            state.caches, new_caches, q_lens > 0,
            batch_axis=1 if self.scan else 0)

        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                and "lm_head" not in params else params["lm_head"])
        logits_all = x @ head                          # (B, C, V_pad)
        last = jnp.maximum(q_lens - 1, 0)[:, None, None]
        logits = jnp.take_along_axis(logits_all, last,
                                     axis=1)[:, 0, :cfg.vocab_size]
        return logits, PagedDecodeState(caches=new_caches, page_table=table,
                                        seq_lens=start + q_lens)

    def paged_serve_step(self, params: dict, tokens: Array,
                         state: PagedDecodeState,
                         update: Optional[Array] = None,
                         use_kernel: bool = False
                         ) -> Tuple[Array, PagedDecodeState]:
        """One decode step against the page pool — the C == 1 view of
        :meth:`paged_fused_step`.  Same ``update`` contract as
        :meth:`serve_step`: masked-out slots touch nothing and their
        logits are garbage."""
        B = tokens.shape[0]
        q_lens = (jnp.ones((B,), jnp.int32) if update is None
                  else jnp.where(update, 1, 0).astype(jnp.int32))
        return self.paged_fused_step(params, tokens, state, q_lens,
                                     use_kernel=use_kernel)

    def write_prefill_to_pages(self, caches: Any, prefill_caches: Any,
                               table_row: Array, shared_len: Array,
                               slot, *, page_size: int,
                               true_len: Optional[Array] = None) -> Any:
        """Scatter a bulk-prefill handoff (:meth:`prefill` on one (1, T)
        prompt) into the pool: attention KV of positions
        ``[shared_len, T)`` lands in the pages of ``table_row`` (the
        shared-prefix positions are already resident in shared pages and
        are drop-routed); recurrent leaves overwrite ``slot``'s row
        wholesale — the prefill state IS the recurrent state after the
        prompt, so nothing of a previous occupant survives.
        ``true_len`` (bucketed prefill): positions past the real prompt
        length are bucket padding and are drop-routed too."""
        scan = self.scan
        P = page_size

        def page_idx(T, n_pages):
            pos = jnp.arange(T)
            idx = jnp.minimum(pos // P, table_row.shape[0] - 1)
            pid = table_row[idx]
            pid = jnp.where(pos >= shared_len, pid, n_pages)   # drop shared
            if true_len is not None:
                pid = jnp.where(pos < true_len, pid, n_pages)  # drop pad
            return pid, pos % P

        def pages_write(pages, seq):
            # the prefill's (…, T, kvH, hd) rows merge into the pool's
            # trailing feature dim
            if scan:                       # (L, NP, P, ...) <- (L, 1, T, ...)
                T = seq.shape[2]
                pid, sl = page_idx(T, pages.shape[1])
                rows = seq[:, 0].reshape((seq.shape[0], T) + pages.shape[3:])
                return pages.at[:, pid, sl].set(rows.astype(pages.dtype),
                                                mode="drop")
            T = seq.shape[1]
            pid, sl = page_idx(T, pages.shape[0])
            rows = seq[0].reshape((T,) + pages.shape[2:])
            return pages.at[pid, sl].set(rows.astype(pages.dtype),
                                         mode="drop")

        def recurrent_write(cur, new):
            if scan:                       # (L, B, ...) <- (L, 1, ...)
                return cur.at[:, slot].set(new[:, 0].astype(cur.dtype))
            return cur.at[slot].set(new[0].astype(cur.dtype))

        def attn_write(c, pc):
            return type(c)(*(pages_write(a, b) for a, b in zip(c, pc)))

        return map_cache_tree(caches, on_attention=attn_write,
                              on_leaf=recurrent_write,
                              other=prefill_caches)

    def copy_cache_page(self, caches: Any, src, dst) -> Any:
        """Copy-on-write data move: duplicate physical page ``src`` into
        ``dst`` across every layer's attention leaves (unwritten slots
        carry stale bytes along; they stay behind the validity mask)."""
        axis = 1 if self.scan else 0

        def cp(x):
            idx_src = [slice(None)] * x.ndim
            idx_src[axis] = src
            idx_dst = list(idx_src)
            idx_dst[axis] = dst
            return x.at[tuple(idx_dst)].set(x[tuple(idx_src)])

        return map_cache_tree(caches,
                              on_attention=lambda c: type(c)(*map(cp, c)),
                              on_leaf=lambda c: c)
