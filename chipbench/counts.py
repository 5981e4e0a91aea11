"""Operations and bytes the algorithms need, from a cell's shapes.

Every count here is what the work requires, never what an
implementation happens to move: operands are counted once, at the dtype
they are stored in (bfloat16 parameters, gradients, optimizer state and
KV pages), recomputation is not counted, and attention counts only the
causal part of each query's context.  So a roofline share built from
these numbers cannot pass 100% unless the time leaves out work.

``model`` is the ``model`` section of a configuration file (Hugging
Face key names).
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Sequence, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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


def attention_fwd_flops(model: Dict, q_ctx: Iterable[Tuple[int, int]]
                        ) -> float:
    """Scores and weighted values, multiply-adds counted as two, over
    all layers.  ``q_ctx`` yields ``(queries, first_context)``: a run of
    consecutive queries whose first attends ``first_context`` keys
    (itself included) and each next one key more."""
    layers, _, h, _, hd, _, _ = dims(model)
    keys = 0
    for q, c0 in q_ctx:
        keys += q * c0 + q * (q - 1) // 2
    return 4.0 * h * hd * keys * layers


def train_round_flops(model: Dict, seq_len: int, seqs: int) -> float:
    """Model FLOPs of one DASHA-PP-MVR round over ``seqs`` sequences of
    ``seq_len`` tokens (all nodes together): forward and backward
    (6·N·T plus three times the causal attention) for each of the two
    gradient evaluations of the MVR pair.  Remat is not counted."""
    tokens = seq_len * seqs
    attn = attention_fwd_flops(model, [(seq_len, 1)] * seqs)
    return 2.0 * (6.0 * matmul_params(model) * tokens + 3.0 * attn)


def block_plan(d: int, block_size: int, ratio: float) -> Tuple[int, int, int]:
    """(block size, blocks, selected blocks) of BlockRandK on a
    ``d``-vector: ``ceil(ratio * blocks)`` of ``ceil(d / bs)`` blocks,
    at least one."""
    bs = min(block_size, d)
    nb = -(-d // bs)
    return bs, nb, max(1, math.ceil(ratio * nb))


def dasha_update_bytes(leaf_sizes: Sequence[int], ratio: float,
                       block_size: int, stored_bytes: int = 2,
                       wire_bytes: int = 4) -> float:
    """HBM bytes one node's DASHA-PP-MVR update and BlockRandK compress
    need per round: the tracker pass reads the gradient pair and h and
    writes h (line 10, every coordinate), and the payload (line 11) is
    read at the selected blocks only (the pair, h and g_i) and written
    as wire values.  State and gradients at ``stored_bytes`` a value,
    wire values at ``wire_bytes``."""
    total = 0.0
    for d in leaf_sizes:
        bs, _, kb = block_plan(d, block_size, ratio)
        sel = kb * bs
        total += 4 * d * stored_bytes          # gn, go, h in; h out
        total += 4 * sel * stored_bytes        # gn, go, h, g_i at blocks
        total += sel * wire_bytes              # wire values out
    return total


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
    attn = attention_fwd_flops(
        model, [(int(q), int(s) + 1) for s, q in zip(starts, q_lens) if q])
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
