"""The program under test, built from a configuration file.

The program implements a Granite decoder without Granite's four
multipliers: it scales embeddings by sqrt(d_model), scores by
1/sqrt(head_dim), and adds residuals and logits unscaled, and its head
holds the vocabulary padded to a multiple of 256 rows.  A configuration
file leaves the multipliers out; this module refuses one that states
them, or another padding, since the program could not run it as stated.
"""
from __future__ import annotations

from typing import Dict

MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")


def arch(model: Dict, name: str):
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
