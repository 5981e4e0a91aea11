"""Plain reference of a Granite decoder (Hugging Face ``granite`` model
type: pre-norm RMSNorm, RoPE, grouped-query attention, SwiGLU MLP, tied
embeddings, and the four Granite multipliers where the configuration
states them; where it does not, embeddings are scaled by
sqrt(hidden_size), scores by 1/sqrt(head_dim), and residuals and logits
are left unscaled).

Written from the published equations and the configuration file alone;
it imports nothing of the program under test.  Weights are a dict of
stacked per-layer arrays (the layout :func:`chipbench.weights.make`
builds).  Every matrix product goes through ``mm``: :func:`f32_mm` is
float32 at the highest precision (a TPU otherwise multiplies float32 in
bfloat16), :func:`fp8_mm` the control's float8 products.  Layers run
under a scan with each layer recomputed in the backward pass, and
attention one block of queries at a time, so a whole-width model fits
on one chip beside nothing else.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
LOSS_ROWS = 512


def f32_mm(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def fp8_round(x):
    """Float8 (e4m3) round trip with one scale per tensor, the usual
    recipe: the largest magnitude maps to the format's largest value."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def fp8_mm(x, w):
    """Products of float8 operands accumulated in float32; the backward
    pass sees the rounded operands (straight-through)."""
    def st(a):
        a = a.astype(jnp.float32)
        return a + jax.lax.stop_gradient(fp8_round(a) - a)
    return jnp.matmul(st(x), st(w), precision=HIGHEST)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding on (T, heads, hd), halves rotated together (the
    ``rotate_half`` convention)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, scale, mm: Callable):
    """Causal grouped-query attention, one sequence: q (T, H, hd), k/v
    (T, kvH, hd).  Query blocks are recomputed in the backward pass."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    kk = jnp.repeat(k, G, axis=1).transpose(1, 2, 0)      # (H, hd, T)
    vv = jnp.repeat(v, G, axis=1).transpose(1, 0, 2)      # (H, T, hd)
    qb = min(Q_BLOCK, T)
    pad = (-T) % qb
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    nblk = qp.shape[0] // qb
    qp = qp.reshape(nblk, qb, H, hd).transpose(0, 2, 1, 3)  # (n, H, qb, hd)

    @jax.checkpoint
    def block(args):
        qblk, i = args
        s = mm(qblk, kk) * scale                          # (H, qb, T)
        qpos = i * qb + jnp.arange(qb)
        mask = qpos[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm(p, vv)                                  # (H, qb, hd)

    out = jax.lax.map(block, (qp, jnp.arange(nblk)))
    out = out.transpose(0, 2, 1, 3).reshape(nblk * qb, H * hd)
    return out[:T]


def _head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def multipliers(m: Dict) -> Dict[str, float]:
    """The four Granite multipliers, as stated or at their plain values."""
    return {"embedding_multiplier": m.get("embedding_multiplier",
                                          m["hidden_size"] ** 0.5),
            "attention_multiplier": m.get("attention_multiplier",
                                          _head_dim(m) ** -0.5),
            "residual_multiplier": m.get("residual_multiplier", 1.0),
            "logits_scaling": m.get("logits_scaling", 1.0)}


def layer(x, p: Dict, positions, m: Dict, mm: Callable):
    H, kvH = m["num_attention_heads"], m["num_key_value_heads"]
    hd = _head_dim(m)
    mult = multipliers(m)
    eps, res = m["rms_norm_eps"], mult["residual_multiplier"]
    a = p["attn"]
    h = rmsnorm(x, p["ln1"], eps)
    q = rope(mm(h, a["wq"]).reshape(-1, H, hd), positions, m["rope_theta"])
    k = rope(mm(h, a["wk"]).reshape(-1, kvH, hd), positions, m["rope_theta"])
    v = mm(h, a["wv"]).reshape(-1, kvH, hd)
    x = x + res * mm(attention(q, k, v, mult["attention_multiplier"], mm),
                     a["wo"])
    h = rmsnorm(x, p["ln2"], eps)
    f = p["mlp"]
    g = jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"])
    return x + res * mm(g, f["w_down"])


def hidden(params: Dict, tokens, m: Dict, mm: Callable = f32_mm):
    """Final normed hidden states (T, d) of one sequence of tokens."""
    x = params["embed"][tokens].astype(jnp.float32) \
        * multipliers(m)["embedding_multiplier"]
    positions = jnp.arange(tokens.shape[0])

    @jax.checkpoint
    def body(x, p):
        return layer(x, p, positions, m, mm), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"], m["rms_norm_eps"])


def logits(params: Dict, tokens, m: Dict, rows: int,
           mm: Callable = f32_mm):
    """(T, rows) next-token logits of one sequence over the first
    ``rows`` rows of the tied embedding."""
    h = hidden(params, tokens, m, mm)
    return mm(h, params["embed"][:rows].T) / multipliers(m)["logits_scaling"]


def loss(params: Dict, tokens, m: Dict, rows: int, mm: Callable = f32_mm):
    """Mean next-token cross-entropy of a (seqs, T) batch.  The logits
    are made ``LOSS_ROWS`` positions at a time and recomputed in the
    backward pass, so that the (T, rows) float32 matrix never exists."""
    head = params["embed"][:rows].T
    scaling = multipliers(m)["logits_scaling"]

    def one(t):
        h = hidden(params, t, m, mm)[:-1]
        tgt = t[1:]
        n = h.shape[0]
        blk = min(LOSS_ROWS, n)
        pad = (-n) % blk
        hs = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, blk, h.shape[1])
        ts = jnp.pad(tgt, (0, pad)).reshape(-1, blk)

        @jax.checkpoint
        def piece(args):
            hb, tb = args
            lg = mm(hb, head) / scaling
            lse = jax.nn.logsumexp(lg, axis=-1)
            return lse - jnp.take_along_axis(lg, tb[:, None], 1)[:, 0]

        nll = jax.lax.map(piece, (hs, ts)).reshape(-1)[:n]
        return jnp.mean(nll)
    return jnp.mean(jax.lax.map(one, tokens))
