"""Compressor bit-accounting property tests (satellite of the async
PR): the ``wire_bits`` formulas of ``Composed``, ``TopK`` and
``RandomDithering`` must agree with the *measured* payload an actual
compression produces, and the sharded engine's ``NodeUpdateMetrics.
bits_sent`` must stay aggregation-aware for the new wire formats."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.core import variants
from repro.core.compressors import (Composed, NaturalCompression, RandK,
                                    RandomDithering, TopK, _index_bits)

_FLOAT = 32


# ----------------------------------------------------------------------
# wire_bits == measured payload
# ----------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(d=st.integers(4, 256), k=st.integers(1, 64), seed=st.integers(0, 99))
def test_topk_wire_bits_match_measured_payload(d, k, seed):
    """TopK sends exactly its sparse payload: keff float values plus
    keff coordinate indices at ceil(log2 d) bits."""
    comp = TopK(k=k)
    x = jax.random.normal(jax.random.key(seed), (d,))
    vals, idx = comp.compress_sparse(jax.random.key(seed + 1), x)
    keff = min(k, d)
    assert vals.shape == (keff,) and idx.shape == (keff,)
    measured = vals.size * _FLOAT + idx.size * _index_bits(d)
    assert comp.wire_bits(d) == measured


@settings(max_examples=25, deadline=None)
@given(d=st.integers(8, 256), k=st.integers(1, 64), seed=st.integers(0, 99))
def test_composed_wire_bits_match_measured_payload(d, k, seed):
    """Composed(RandK, Natural): keff indices + keff natural-compressed
    values at 9 bits each — the sparse payload it actually emits."""
    comp = Composed(inner=RandK(k=k), outer=NaturalCompression())
    x = jax.random.normal(jax.random.key(seed), (d,)) + 0.1
    vals, idx = comp.compress_sparse(jax.random.key(seed + 1), x)
    keff = min(k, d)
    assert vals.shape == (keff,) and idx.shape == (keff,)
    measured = idx.size * _index_bits(d) + vals.size * 9.0
    assert comp.wire_bits(d) == measured
    # and the values really are natural-compressed (powers of two times
    # sign — exponent+sign is all that crosses the wire)
    nz = np.asarray(vals)[np.asarray(vals) != 0]
    exps = np.log2(np.abs(nz))
    np.testing.assert_allclose(exps, np.round(exps), atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(d=st.integers(4, 256), s=st.integers(1, 15), seed=st.integers(0, 99))
def test_dithering_wire_bits_match_measured_payload(d, s, seed):
    """RandomDithering sends one norm float plus (sign + level) per
    coordinate; the output must decode from exactly that: at most s+1
    distinct levels of |x|/||x||, i.e. ceil(log2(s+1)) level bits."""
    comp = RandomDithering(s=s)
    x = jax.random.normal(jax.random.key(seed), (d,))
    out = np.asarray(comp.compress(jax.random.key(seed + 1), x))
    norm = float(jnp.linalg.norm(x))
    levels = np.unique(np.round(np.abs(out) / norm * s, 6))
    assert len(levels) <= s + 1
    level_bits = math.ceil(math.log2(s + 1))
    assert comp.wire_bits(d) == _FLOAT + d * (1 + level_bits)


# ----------------------------------------------------------------------
# rule-layer message_bits for the sharded wire formats
# ----------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
# ratio stops at 1/4, where every sparse wire is provably below dense
# for d >= 64: a top-k (value, index) pair costs 64 bits, so at 1/2 it
# ties dense exactly, and BlockRandK adds one index per block and rounds
# the block count up (kb <= ratio * (d/32 + 1) + 1 blocks of 33 words).
@given(d=st.integers(64, 4096), ratio=st.floats(0.01, 0.25))
def test_message_bits_wire_formats(d, ratio):
    kw = dict(aggregation="sparse_allgather", compression_ratio=ratio,
              block_size=32)
    dense = variants.message_bits(d, aggregation="dense_psum",
                                  compression_ratio=ratio, block_size=32)
    topk = variants.message_bits(d, wire_format="topk", **kw)
    blk = variants.message_bits(d, wire_format="block_randk", **kw)
    dith = variants.message_bits(d, wire_format="dithering",
                                 dithering_levels=4, **kw)
    assert dense == d * 32.0
    k = max(1, math.ceil(ratio * d))
    assert topk == k * (32.0 + 32.0)
    bs, _, kb = variants.block_plan(d, 32, ratio)
    assert blk == kb * (bs * 32.0 + 32.0)
    # dithering: ratio-independent, (1 + ceil(log2 5)) = 4 bits/coord
    assert dith == 32.0 + 4.0 * d
    assert dith == variants.message_bits(
        d, wire_format="dithering", aggregation="sparse_allgather",
        compression_ratio=0.9, block_size=32)
    for bits in (topk, blk, dith):
        assert bits < dense


def test_sharded_config_validates_wire_format():
    from repro.core.sharded import ShardedDashaConfig
    base = dict(gamma=0.1, a=0.1, b=0.1)
    with pytest.raises(ValueError):
        ShardedDashaConfig(wire_format="bogus", **base)
    with pytest.raises(ValueError):
        ShardedDashaConfig(wire_format="topk", aggregation="dense_psum",
                           **base)
    with pytest.raises(ValueError):
        # ratio None is the dense baseline — it would silently bypass
        # the requested wire format
        ShardedDashaConfig(wire_format="dithering",
                           compression_ratio=None, **base)
    ShardedDashaConfig(wire_format="dithering", **base)   # ok


# ----------------------------------------------------------------------
# NodeUpdateMetrics.bits_sent stays aggregation-aware per wire format
# (single-device mesh: runs in-process)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("wire,expect", [
    ("block_randk", None),      # expectation computed from block_plan
    ("topk", None),
    ("dithering", None),
])
def test_node_update_bits_sent_new_wire_formats(wire, expect):
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, use_mesh
    from repro.core.sharded import ShardedDasha, ShardedDashaConfig
    d, bs, ratio = 96, 8, 0.25
    mesh = make_mesh((1,), ("data",))
    cfg = ShardedDashaConfig(gamma=0.1, a=0.1, b=0.3, p_a=1.0,
                             sampler="full", compression_ratio=ratio,
                             block_size=bs, data_axes=("data",),
                             wire_format=wire, dithering_levels=4)
    eng = ShardedDasha(mesh, {"w": P()}, cfg)
    g0 = {"w": jnp.ones((1, d))}
    with use_mesh(mesh):
        st = eng.init(g0)
        st, met = eng.node_update(g0, g0, st, jax.random.key(0))
    per_node = variants.message_bits(
        d, aggregation="sparse_allgather", compression_ratio=ratio,
        block_size=bs, wire_format=wire, dithering_levels=4)
    assert float(met.participants) == 1.0
    assert float(met.bits_sent) == per_node
    assert eng.uplink_bits_per_round(d) == per_node   # p_a = 1
    # dense_psum with the default wire still reports dense bits
    if wire == "block_randk":
        dense_cfg = ShardedDashaConfig(
            gamma=0.1, a=0.1, b=0.3, p_a=1.0, sampler="full",
            compression_ratio=ratio, block_size=bs,
            aggregation="dense_psum", data_axes=("data",))
        dense_eng = ShardedDasha(mesh, {"w": P()}, dense_cfg)
        assert dense_eng.uplink_bits_per_round(d) == d * 32.0
