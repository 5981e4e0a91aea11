"""Causal self-attention on the TPU's flash kernel.

The kernel is the splash-attention kernel that JAX ships
(``jax.experimental.pallas.ops.tpu.splash_attention``) in its MQA form:
one kernel per KV head serves that head's G query heads, vmapped over
batch and KV heads.  It takes the operands in their own dtype (bf16 in
the models), accumulates and keeps the softmax max and sum in f32,
skips every block above the causal diagonal, and brings its own
backward kernel (dq, dk and dv in one launch), so no score block
reaches HBM in either direction.

Contract: ``q`` (B, T, H, hd), ``k``/``v`` (B, T, kvH, hd), query and
key positions both ``arange(T)``, causal mask, no window, no prefix.
Query heads group as in :func:`repro.models.layers.gqa_attention`
(``q.reshape(B, T, kvH, G, hd)``).  T is padded at the end to a whole
number of blocks: under the causal mask a padded key comes after every
real query, so the real rows are exact, and the padded rows are sliced
off.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

Array = jax.Array

LANES = 128


def block_size(T: int) -> int:
    """The q and kv block of the forward and backward kernels at
    sequence length ``T``: 1024, the fastest timed at T 4096 on a v5e
    (PERF.md §6), unless it would pad T further than 512 does; T
    rounded up to the lane width where that is 512 or less."""
    t = -(-T // LANES) * LANES
    if t <= 512:
        return t
    return 512 if -(-t // 512) % 2 else 1024


@functools.lru_cache(maxsize=None)
def _kernel(t_pad: int, groups: int, block: int, interpret: bool):
    """The splash kernel for one KV head's ``groups`` query heads over
    ``t_pad`` positions, built once per shape and block.  The backward
    is one fused kernel that yields dk, dv and dq together."""
    mask = splash.MultiHeadMask([splash.CausalMask((t_pad, t_pad))]
                                * groups)
    blocks = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block,
        block_kv_dkv_compute=block, use_fused_bwd_kernel=True)
    # the mask's block tables are constants: keep them out of whatever
    # trace first asks for the kernel
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask, block_sizes=blocks, interpret=interpret)


def flash_attention_pallas(q: Array, k: Array, v: Array, *,
                           interpret: bool = False) -> Array:
    """Causal GQA self-attention; returns (B, T, H, hd) in v's dtype."""
    B, T, H, hd = q.shape
    kvH = k.shape[2]
    G = H // kvH
    blk = block_size(T)
    t_pad = -(-T // blk) * blk
    pad = ((0, 0), (0, t_pad - T), (0, 0), (0, 0))
    # splash takes no scale: fold 1/sqrt(hd) into q (exact for hd 64)
    q = (q.astype(jnp.float32) / math.sqrt(hd)).astype(q.dtype)
    q = jnp.pad(q, pad).reshape(B, t_pad, kvH, G, hd)
    q = q.transpose(0, 2, 3, 1, 4)                    # (B, kvH, G, Tp, hd)
    k = jnp.pad(k, pad).transpose(0, 2, 1, 3)          # (B, kvH, Tp, hd)
    v = jnp.pad(v, pad).transpose(0, 2, 1, 3)
    kernel = _kernel(t_pad, G, blk, bool(interpret))
    out = jax.vmap(jax.vmap(kernel))(q, k, v)         # (B, kvH, G, Tp, hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, t_pad, H, hd)
    return out[:, :T].astype(v.dtype)
