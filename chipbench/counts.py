"""Operations and bytes the algorithms need, from a cell's shapes: what
every architecture shares.  What depends on the architecture (the
model's FLOPs a round or a serve pass) is its module's under
``chipbench/archs/``, built from these.

Every count here is what the work requires, never what an
implementation happens to move: operands are counted once, at the dtype
they are stored in (bfloat16 parameters, gradients, optimizer state and
KV pages), recomputation is not counted, and attention counts only the
causal part of each query's context.  So a roofline share built from
these numbers cannot pass 100% unless the time leaves out work.
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attention_fwd_flops(layers: int, heads: int, head_dim: int,
                        q_ctx: Iterable[Tuple[int, int]]) -> float:
    """Scores and weighted values, multiply-adds counted as two, over
    ``layers`` attention layers of ``heads`` query heads of
    ``head_dim``.  ``q_ctx`` yields ``(queries, first_context)``: a run
    of consecutive queries whose first attends ``first_context`` keys
    (itself included) and each next one key more."""
    keys = 0
    for q, c0 in q_ctx:
        keys += q * c0 + q * (q - 1) // 2
    return 4.0 * heads * head_dim * keys * layers


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
