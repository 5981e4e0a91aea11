"""Pallas TPU kernels for the DASHA-PP hot path (DESIGN.md §6).

Layout: one module per kernel family (``dasha_update``, ``randk``,
``paged_attention``, ``flash_attention``),
``ops`` for the jit'd public wrappers with interpret-mode auto-detect,
``ref`` for the pure-jnp oracles every kernel is tested against.
"""
from repro.kernels.ops import (block_gather_op, block_scatter_op,
                               dasha_h_update_op, dasha_page_update_op,
                               dasha_payload_blocks_op, dasha_tail_op,
                               dasha_update_batched_op, dasha_update_op,
                               flash_attention_op, interpret_default,
                               paged_attention_op)

__all__ = [
    "block_gather_op", "block_scatter_op", "dasha_h_update_op",
    "dasha_page_update_op", "dasha_payload_blocks_op", "dasha_tail_op",
    "dasha_update_batched_op", "dasha_update_op", "flash_attention_op",
    "interpret_default", "paged_attention_op",
]
