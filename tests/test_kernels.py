"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.kernels import ref
from repro.kernels.ops import (block_gather_op, block_scatter_op,
                               dasha_h_update_op, dasha_page_h_update_op,
                               dasha_page_payload_blocks_op,
                               dasha_page_update_op,
                               dasha_payload_blocks_op, dasha_tail_op,
                               dasha_update_batched_op, dasha_update_op)


def _node_arrays(n, d, count, seed=0):
    key = jax.random.key(seed)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (n, d))
                 for i in range(count))


def _assert_all_close(outs, refs, rtol=1e-5, atol=1e-6):
    for o, r in zip(outs, refs):
        assert o.shape == r.shape
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("d", [1, 7, 128, 1000, 128 * 512, 128 * 512 + 17,
                               1 << 18])
@pytest.mark.parametrize("part", [0.0, 1.0])
def test_dasha_update_shapes(d, part):
    key = jax.random.key(d)
    gn, go, h, gi = (jax.random.normal(jax.random.fold_in(key, i), (d,))
                     for i in range(4))
    args = dict(b=0.25, a=0.04, pa=0.5, participates=jnp.asarray(part))
    outs = dasha_update_op(gn, go, h, gi, **args)
    refs = ref.dasha_update_ref(gn, go, h, gi, **args)
    for o, r in zip(outs, refs):
        assert o.shape == (d,)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(b=st.floats(0.0, 1.0), a=st.floats(0.0, 1.0),
       pa=st.floats(0.05, 1.0), seed=st.integers(0, 50))
def test_dasha_update_hyperparam_sweep(b, a, pa, seed):
    d = 513
    key = jax.random.key(seed)
    gn, go, h, gi = (jax.random.normal(jax.random.fold_in(key, i), (d,))
                     for i in range(4))
    args = dict(b=b, a=a, pa=pa, participates=jnp.asarray(1.0))
    outs = dasha_update_op(gn, go, h, gi, **args)
    refs = ref.dasha_update_ref(gn, go, h, gi, **args)
    for o, r in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=1e-5)


def test_dasha_update_participation_freezes_h():
    d = 256
    key = jax.random.key(0)
    gn, go, h, gi = (jax.random.normal(jax.random.fold_in(key, i), (d,))
                     for i in range(4))
    _, h_new, _ = dasha_update_op(gn, go, h, gi, b=0.3, a=0.1, pa=0.25,
                                  participates=jnp.asarray(0.0))
    np.testing.assert_allclose(np.asarray(h_new), np.asarray(h))


# ---------------------------------------------------------------------
# Batched (node-major) kernel family vs the jnp oracles
# ---------------------------------------------------------------------

@pytest.mark.parametrize("d", [1, 7, 129, 1000])   # odd d -> padding path
@pytest.mark.parametrize("mask", [[1, 1, 1], [0, 0, 0], [1, 0, 1]])
def test_batched_update_parity(d, mask):
    """p_a < 1 participation masks and the lane-padding path."""
    gn, go, h, gi = _node_arrays(3, d, 4, seed=d)
    m = jnp.asarray(mask, jnp.float32)
    args = dict(b=0.25, a=0.04, pa=0.5)
    outs = dasha_update_batched_op(gn, go, h, gi, m, **args)
    refs = ref.dasha_update_batched_ref(gn, go, h, gi, m, **args)
    _assert_all_close(outs, refs)


def test_batched_update_interpret_explicit():
    """interpret=True must be forceable regardless of backend default."""
    gn, go, h, gi = _node_arrays(2, 300, 4, seed=9)
    m = jnp.asarray([1.0, 0.0])
    args = dict(b=0.1, a=0.3, pa=0.25)
    outs = dasha_update_batched_op(gn, go, h, gi, m, interpret=True, **args)
    refs = ref.dasha_update_batched_ref(gn, go, h, gi, m, **args)
    _assert_all_close(outs, refs)


@pytest.mark.parametrize("coin", [0.0, 1.0])
@pytest.mark.parametrize("d", [5, 384, 1000])
def test_page_update_parity(coin, d):
    """Both PAGE branches of the fused Alg. 3 kernel."""
    gn, go, bn, bo, h, gi = _node_arrays(4, d, 6, seed=d + 1)
    m = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    c = jnp.asarray(coin)
    args = dict(b=0.25, a=0.04, pa=0.5, p_page=0.125)
    outs = dasha_page_update_op(gn, go, bn, bo, h, gi, m, c, **args)
    refs = ref.dasha_page_update_ref(gn, go, bn, bo, h, gi, m, c, **args)
    _assert_all_close(outs, refs)


@pytest.mark.parametrize("d", [3, 256, 777])
def test_tail_parity(d):
    """Lines 10-11 with an externally supplied k (finite-MVR path)."""
    k, h, gi = _node_arrays(5, d, 3, seed=d + 2)
    m = jnp.asarray([0.0, 1.0, 1.0, 0.0, 1.0])
    outs = dasha_tail_op(k, h, gi, m, a=0.07, pa=0.25)
    refs = ref.dasha_tail_ref(k, h, gi, m, a=0.07, pa=0.25)
    _assert_all_close(outs, refs)


@pytest.mark.parametrize("d,bs,kb", [(1024, 128, 2), (1000, 128, 3),
                                     (64, 8, 4), (129, 128, 1)])
def test_payload_blocks_fused_compress(d, bs, kb):
    """The fused update+compress must equal dense payload -> block gather
    (unbiasedness scale included), incl. ragged last block."""
    gn, go, h, gi = (jax.random.normal(jax.random.fold_in(jax.random.key(d), i),
                                       (d,)) for i in range(4))
    nb = -(-d // bs)
    idx = jnp.asarray(
        np.random.default_rng(d).choice(nb, kb, replace=False), jnp.int32)
    args = dict(b=0.3, a=0.05, pa=0.5, scale=nb / kb, block_size=bs)
    out = dasha_payload_blocks_op(gn, go, h, gi, idx, **args)
    want = ref.dasha_payload_blocks_ref(gn, go, h, gi, idx, **args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("part", [0.0, 1.0])
def test_h_update_parity(part):
    d = 513
    gn, go, h, gi = (jax.random.normal(jax.random.fold_in(jax.random.key(1), i),
                                       (d,)) for i in range(4))
    out = dasha_h_update_op(gn, go, h, b=0.2, pa=0.5,
                            participates=jnp.asarray(part))
    _, want, _ = ref.dasha_update_ref(gn, go, h, gi, b=0.2, a=0.0, pa=0.5,
                                      participates=jnp.asarray(part))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("coin", [0.0, 1.0])
@pytest.mark.parametrize("part", [0.0, 1.0])
def test_page_h_update_parity(coin, part):
    """Line 10 with the PAGE k recomputed in-register (both branches,
    both participation states)."""
    d = 513
    gn, go, bn, bo, h = (
        jax.random.normal(jax.random.fold_in(jax.random.key(3), i), (d,))
        for i in range(5))
    args = dict(b=0.2, pa=0.5, p_page=0.25)
    out = dasha_page_h_update_op(gn, go, bn, bo, h, jnp.asarray(coin),
                                 participates=jnp.asarray(part), **args)
    want = ref.dasha_page_h_update_ref(gn, go, bn, bo, h,
                                       jnp.asarray(part),
                                       jnp.asarray(coin), **args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("coin", [0.0, 1.0])
@pytest.mark.parametrize("d,bs,kb", [(1024, 128, 2), (1000, 128, 3),
                                     (64, 8, 4)])
def test_page_payload_blocks_fused_compress(coin, d, bs, kb):
    """The fused PAGE update+compress must equal dense PAGE payload ->
    block gather, on both coin branches (incl. ragged last block)."""
    gn, go, bn, bo, h, gi = (
        jax.random.normal(jax.random.fold_in(jax.random.key(d), i), (d,))
        for i in range(6))
    nb = -(-d // bs)
    idx = jnp.asarray(
        np.random.default_rng(d).choice(nb, kb, replace=False), jnp.int32)
    args = dict(b=0.3, a=0.05, pa=0.5, p_page=0.25, scale=nb / kb,
                block_size=bs)
    c = jnp.asarray(coin)
    out = dasha_page_payload_blocks_op(gn, go, bn, bo, h, gi, idx, c,
                                       **args)
    want = ref.dasha_page_payload_blocks_ref(gn, go, bn, bo, h, gi, idx,
                                             c, **args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("nb,bs,kb", [(8, 128, 1), (64, 128, 7),
                                      (32, 8, 32), (100, 128, 50)])
def test_block_gather(nb, bs, kb):
    key = jax.random.key(nb * bs)
    x = jax.random.normal(key, (nb, bs))
    idx = jnp.asarray(
        np.random.default_rng(0).choice(nb, kb, replace=False), jnp.int32)
    scale = nb / kb
    out = block_gather_op(x, idx, scale=scale)
    want = ref.block_gather_ref(x, idx, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("nb,bs,kb", [(8, 128, 3), (64, 64, 17)])
def test_block_scatter(nb, bs, kb):
    rng = np.random.default_rng(1)
    base = jnp.asarray(rng.standard_normal((nb, bs)), jnp.float32)
    vals = jnp.asarray(rng.standard_normal((kb, bs)), jnp.float32)
    idx = jnp.asarray(rng.choice(nb, kb, replace=False), jnp.int32)
    out = block_scatter_op(base, vals, idx)
    want = ref.block_scatter_add_ref(base, vals, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


def test_gather_scatter_roundtrip_unbiased():
    """BlockRandK as used by the sharded engine: gather-then-scatter of a
    zero base reproduces the dense BlockRandK output, and averaging over
    many keys approaches the identity (unbiasedness at block level)."""
    from repro.core.sharded import block_randk_dense
    d = 1024
    x = jax.random.normal(jax.random.key(0), (d,))
    keys = jax.random.split(jax.random.key(1), 600)
    outs = jax.vmap(lambda k: block_randk_dense(k, x, 4, 128))(keys)
    mean = jnp.mean(outs, axis=0)
    rel = float(jnp.linalg.norm(mean - x) / jnp.linalg.norm(x))
    assert rel < 0.15, rel
