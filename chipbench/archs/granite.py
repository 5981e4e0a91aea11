"""The program side of the Hugging Face ``granite`` model type: the
program's config, the weights' layout and init, and the operations a
round or a serve pass needs.

The program implements a Granite decoder without Granite's four
multipliers: it scales embeddings by sqrt(d_model), scores by
1/sqrt(head_dim), and adds residuals and logits unscaled, and its head
holds the vocabulary padded to a multiple of 256 rows.  A configuration
file leaves the multipliers out; :func:`program_arch` refuses one that
states them, or another padding, since the program could not run it as
stated.

Counts follow ``chipbench.counts``: what the work requires, operands at
their stored dtype, recomputation not counted, attention over the causal
part of each query's context.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax.numpy as jnp

from chipbench import counts

MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")


def program_arch(model: Dict, name: str):
    """The program's ``ArchConfig`` for a configuration's ``model``."""
    from repro.models import get_config

    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    hd = model.get("head_dim") or d // heads
    cfg = get_config(name).with_overrides(
        num_layers=model["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=model["num_key_value_heads"], head_dim=hd,
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        rms_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        dtype=model["torch_dtype"])
    stated = [k for k in MULTIPLIERS if k in model]
    if stated:
        raise ValueError(f"the program runs no Granite multipliers; the "
                         f"configuration states {stated}")
    if model["embedding_rows"] != cfg.padded_vocab:
        raise ValueError(f"the program pads the embedding to "
                         f"{cfg.padded_vocab} rows; the configuration "
                         f"states {model['embedding_rows']}")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's MLP is SwiGLU (silu)")
    return cfg


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------

def embedding_rows(model: Dict) -> int:
    return int(model.get("embedding_rows", model["vocab_size"]))


def layout(model: Dict) -> Dict:
    """Shapes of every weight, in the program's stacked layout."""
    L, d = model["num_hidden_layers"], model["hidden_size"]
    H, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or d // H
    f = model["intermediate_size"]
    return {
        "embed": (embedding_rows(model), d),
        "final_norm": (d,),
        "layers": {
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, kv * hd),
                     "wv": (L, d, kv * hd), "wo": (L, H * hd, d)},
            "ln1": (L, d), "ln2": (L, d),
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        },
    }


def init_leaf(path: str, shape: Tuple[int, ...], z, model: Dict, dtype):
    """The leaf at ``path`` from its standard normal draw ``z``:
    embedding at the configuration's ``embedding_std`` (0.02 where it
    states none), norm scales at 1 + 0.1 z (so a path that ignores a
    scale shows), matrices at 1/sqrt(fan-in)."""
    if "embed" in path:
        return z * jnp.asarray(model.get("embedding_std", 0.02), dtype)
    if len(shape) <= 2 and ("ln" in path or "norm" in path):
        return (1 + 0.1 * z.astype(jnp.float32)).astype(dtype)
    return z * jnp.asarray(1.0 / math.sqrt(shape[-2]), dtype)


# ----------------------------------------------------------------------
# counts
# ----------------------------------------------------------------------

def dims(model: Dict) -> Tuple[int, int, int, int, int, int, int]:
    """(layers, d_model, heads, kv_heads, head_dim, d_ff, vocab)."""
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    hd = model.get("head_dim") or d // heads
    return (model["num_hidden_layers"], d, heads,
            model["num_key_value_heads"], hd, model["intermediate_size"],
            model["vocab_size"])


def layer_matmul_params(model: Dict) -> int:
    """Weights one decoder layer multiplies by: q, k, v, o and the gated
    MLP's three matrices (norm scales do no matmul work)."""
    _, d, h, kv, hd, f, _ = dims(model)
    return d * h * hd * 2 + 2 * d * kv * hd + 3 * d * f


def matmul_params(model: Dict) -> int:
    """N of the 2N / 6N rules: every layer's matmul weights plus the
    output head over the model's vocabulary."""
    layers, d, *_, vocab = dims(model)
    return layers * layer_matmul_params(model) + vocab * d


def train_round_flops(model: Dict, seq_len: int, seqs: int) -> float:
    """Model FLOPs of one DASHA-PP-MVR round over ``seqs`` sequences of
    ``seq_len`` tokens (all nodes together): forward and backward
    (6·N·T plus three times the causal attention) for each of the two
    gradient evaluations of the MVR pair.  Remat is not counted."""
    layers, _, h, _, hd, _, _ = dims(model)
    tokens = seq_len * seqs
    attn = counts.attention_fwd_flops(layers, h, hd, [(seq_len, 1)] * seqs)
    return 2.0 * (6.0 * matmul_params(model) * tokens + 3.0 * attn)


def serve_pass_counts(model: Dict, page_size: int, kv_bytes: int,
                      act_bytes: int, starts: Sequence[int],
                      q_lens: Sequence[int]) -> Dict[str, float]:
    """One fused serve pass: slot ``b`` feeds ``q_lens[b]`` tokens after
    the ``starts[b]`` it already holds.

    * ``flops``: 2·N_layers per token fed, the head for the one token of
      each slot whose logits are read, and causal attention over each
      token's live context.
    * ``attn_flops``, ``attn_bytes``: the paged attention read over all
      layers: K and V of the live pages each slot's table covers, at the
      pool's dtype, plus q in and out at the activations' dtype.
    """
    layers, d, h, kv, hd, _, vocab = dims(model)
    tokens = sum(int(q) for q in q_lens)
    rows = sum(1 for q in q_lens if q > 0)
    attn = counts.attention_fwd_flops(
        layers, h, hd,
        [(int(q), int(s) + 1) for s, q in zip(starts, q_lens) if q])
    kv_read = 0
    for s, q in zip(starts, q_lens):
        if q > 0:
            pages = -(-(int(s) + int(q)) // page_size)
            kv_read += 2 * pages * page_size * kv * hd * kv_bytes
    qo = 2 * tokens * h * hd * act_bytes
    return {
        "tokens": float(tokens),
        "flops": (2.0 * layers * layer_matmul_params(model) * tokens
                  + 2.0 * vocab * d * rows + attn),
        "attn_flops": attn,
        "attn_bytes": float(layers * (kv_read + qo)),
    }
