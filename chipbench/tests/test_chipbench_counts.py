"""Operations and bytes of ``chipbench.counts`` and of Granite's
``chipbench/archs/granite.py``, against sums worked out by hand from the
configurations' shapes."""
from __future__ import annotations

import json
import os

import pytest

from chipbench import counts, weights
from chipbench.archs import granite

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _model(name: str) -> dict:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["model"]


def test_matmul_params_of_granite():
    m = _model("granite-3-2b.train-d8")
    # q and o 2048x2048, k and v 2048x512, three MLP matrices 2048x8192
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert granite.layer_matmul_params(m) == per_layer
    assert granite.matmul_params(m) == 8 * per_layer + 49155 * 2048


@pytest.mark.parametrize("seq_len,tflop", [(4096, 32.16), (512, 3.659)])
def test_train_round_flops(seq_len, tflop):
    m = _model("granite-3-2b.train-d8")
    n = granite.matmul_params(m)
    keys = seq_len * (seq_len + 1) // 2          # causal: 1 + 2 + ... + T
    attn = 4 * 32 * 64 * keys * 8
    want = 2 * (6 * n * seq_len + 3 * attn)      # the MVR pair, fwd + bwd
    got = granite.train_round_flops(m, seq_len, 1)
    assert got == pytest.approx(want, rel=1e-12)
    assert got / 1e12 == pytest.approx(tflop, rel=2e-3)
    # nodes' sequences add up
    assert granite.train_round_flops(m, seq_len, 4) == pytest.approx(4 * got)


def test_dasha_bytes_at_stored_dtype():
    # d = 1000 in blocks of 128: 8 blocks, ceil(8 / 64) = 1 selected
    assert counts.block_plan(1000, 128, 1 / 64) == (128, 8, 1)
    bf16 = counts.dasha_update_bytes([1000], 1 / 64, 128)
    assert bf16 == 4 * 1000 * 2 + 4 * 128 * 2 + 128 * 4
    f32 = counts.dasha_update_bytes([1000], 1 / 64, 128, stored_bytes=4)
    assert f32 == 4 * 1000 * 4 + 4 * 128 * 4 + 128 * 4
    # the whole 8-layer stack: about 8.19 bytes a parameter in bf16
    m = _model("granite-3-2b.train-d8")
    sizes = weights.leaf_sizes(m)
    per_param = counts.dasha_update_bytes(sizes, 1 / 64, 128) / sum(sizes)
    assert per_param == pytest.approx(8 + 12 / 64, rel=1e-3)


def test_serve_pass_counts_read_live_pages_only():
    m = _model("granite-3-2b.serve")
    kv_page = 2 * 16 * 8 * 64 * 2               # K and V of one bf16 page
    # a prefill chunk of 20 tokens from 0 (2 pages) and one decode token
    # after 100 (101 tokens: 7 pages); slot 2 idle
    c = granite.serve_pass_counts(m, 16, 2, 2, [0, 100, 0], [20, 1, 0])
    qo = 2 * 21 * 32 * 64 * 2
    assert c["attn_bytes"] == 40 * ((2 + 7) * kv_page + qo)
    keys = (20 * 1 + 20 * 19 // 2) + 101
    assert c["attn_flops"] == 4 * 32 * 64 * keys * 40
    assert c["tokens"] == 21
    assert c["flops"] == (2 * 40 * granite.layer_matmul_params(m) * 21
                          + 2 * 49155 * 2048 * 2 + c["attn_flops"])
    # the pool's size does not enter: an idle slot adds nothing
    again = granite.serve_pass_counts(m, 16, 2, 2, [0, 100, 4000],
                                      [20, 1, 0])
    assert again == c
