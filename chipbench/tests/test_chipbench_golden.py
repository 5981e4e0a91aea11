"""Golden numbers of both configurations: the counts and the weights the
cells computed before their architecture moved into
``chipbench/archs/granite.py``, recorded on that tree and held equal,
digit for digit, through the module each cell now loads."""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, harness, weights
from chipbench.tests import tiny

TRAIN, SERVE = "train-d8-seq4k", "serve-alpaca"

TRAIN_ROUND_FLOPS = {4096: 32161822408704.0, 512: 3659450548224.0}

LEAF_SIZES = {
    TRAIN: (101187584, 2048, 8388608, 33554432, 33554432, 8388608, 16384,
            16384, 134217728, 134217728, 134217728),
    SERVE: (101187584, 2048, 41943040, 167772160, 167772160, 41943040,
            81920, 81920, 671088640, 671088640, 671088640),
}

# at the train cell's BlockRandK ratio 1/64 and block 128
DASHA_UPDATE_BYTES = {TRAIN: 4812299776.0, SERVE: 20747533824.0}

# a pass over 12 slots: prompt chunks, decode tokens and idle slots at
# contexts from 0 to 4000 tokens, pages of 16 at bf16
STARTS = [0, 100, 0, 4000, 17, 255, 511, 63, 1023, 2, 300, 48]
Q_LENS = [20, 1, 0, 1, 64, 1, 1, 64, 1, 33, 0, 16]
SERVE_PASS_COUNTS = {
    TRAIN: {"tokens": 202.0, "flops": 199683792896.0,
            "attn_flops": 1108541440.0, "attn_bytes": 116260864.0},
    SERVE: {"tokens": 202.0, "flops": 990365409280.0,
            "attn_flops": 5542707200.0, "attn_bytes": 581304320.0},
}

# sha256 of every leaf's bytes in tree order, bf16, seed 2**33 + 5, CPU
MAKE_SHA256 = {
    "train": "1274ccee33e52f76a9bee05958970d9c94a2decd2ebf715a30ca77b98b4219ef",
    "serve": "667a367895228179abdc4d689ce3be6dd5acf7228b8de2df7a8b148d02ae8873",
}


def _cell(name: str) -> harness.Cell:
    return harness.resolve(name, harness.ROOT)


@pytest.mark.parametrize("seq_len", sorted(TRAIN_ROUND_FLOPS))
def test_train_round_flops(seq_len):
    cell = _cell(TRAIN)
    got = cell.arch.train_round_flops(cell.config["model"], seq_len, 1)
    assert got == TRAIN_ROUND_FLOPS[seq_len]


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_leaf_sizes_and_dasha_update_bytes(name):
    cell = _cell(name)
    sizes = weights.leaf_sizes(cell.config["model"], cell.arch)
    assert sizes == LEAF_SIZES[name]
    assert counts.dasha_update_bytes(sizes, 1 / 64, 128) == \
        DASHA_UPDATE_BYTES[name]


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_serve_pass_counts(name):
    cell = _cell(name)
    got = cell.arch.serve_pass_counts(cell.config["model"], 16, 2, 2,
                                      STARTS, Q_LENS)
    assert got == SERVE_PASS_COUNTS[name]


@pytest.mark.parametrize("which", sorted(MAKE_SHA256))
def test_weights_of_the_tiny_models(which):
    cell = {"train": tiny.train_cell, "serve": tiny.serve_cell}[which]()
    params = jax.jit(functools.partial(
        weights.make, model=cell.config["model"], dtype=jnp.bfloat16,
        arch=cell.arch))(weights.stream(2 ** 33 + 5, "weights"))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == MAKE_SHA256[which]
