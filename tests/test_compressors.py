"""Property tests for the unbiased compressors (paper Definition 1):
E[C(x)] = x and E||C(x)-x||^2 <= omega ||x||^2, plus wire formats."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.core.compressors import (Composed, Identity, NaturalCompression,
                                    RandK, RandomDithering, TopK)

TRIALS = 512


def _mc_check_unbiased(comp, x, trials=TRIALS, tol=None):
    keys = jax.random.split(jax.random.key(42), trials)
    outs = jax.vmap(lambda k: comp.compress(k, x))(keys)
    mean = jnp.mean(outs, axis=0)
    err = float(jnp.linalg.norm(mean - x) / (jnp.linalg.norm(x) + 1e-12))
    if tol is None:
        # MC std of the mean is ~sqrt(omega/trials)*||x||; allow 3 sigma
        tol = 3.0 * (comp.omega(x.shape[-1]) / trials) ** 0.5 + 0.02
    assert err < tol, f"unbiasedness violated: rel err {err} (tol {tol})"
    # omega bound (Definition 1), with Monte-Carlo slack
    sq = jnp.mean(jnp.sum((outs - x) ** 2, axis=-1))
    bound = comp.omega(x.shape[-1]) * float(jnp.sum(x ** 2))
    assert float(sq) <= bound * 1.3 + 1e-9, (float(sq), bound)


@pytest.mark.parametrize("comp", [
    Identity(),
    RandK(k=3),
    RandK(k=10),
    NaturalCompression(),
    RandomDithering(s=4),
    Composed(inner=RandK(k=8), outer=NaturalCompression()),
])
def test_unbiased_and_omega(comp):
    x = jax.random.normal(jax.random.key(1), (32,))
    _mc_check_unbiased(comp, x)


@settings(max_examples=25, deadline=None)
@given(d=st.integers(4, 64), k=st.integers(1, 64), seed=st.integers(0, 99))
def test_randk_structure(d, k, seed):
    """RandK keeps exactly min(k, d) coords, scaled by d/min(k,d)."""
    comp = RandK(k=k)
    x = jax.random.normal(jax.random.key(seed), (d,)) + 0.1
    out = comp.compress(jax.random.key(seed + 1), x)
    nz = int(jnp.sum(out != 0))
    keff = min(k, d)
    assert nz == keff
    ratio = out[out != 0] / x[out != 0]
    np.testing.assert_allclose(np.asarray(ratio), d / keff, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(d=st.integers(8, 64), seed=st.integers(0, 99))
def test_natural_within_factor_two(d, seed):
    """Natural compression outputs sign(x) * 2^e with 2^e in [|x|/2, 2|x|]."""
    comp = NaturalCompression()
    x = jax.random.normal(jax.random.key(seed), (d,))
    out = comp.compress(jax.random.key(seed + 1), x)
    nz = x != 0
    r = np.abs(np.asarray(out)[nz] / np.asarray(x)[nz])
    assert np.all(r >= 0.49) and np.all(r <= 2.01)
    assert np.all(np.sign(np.asarray(out)[nz]) == np.sign(np.asarray(x)[nz]))


def test_topk_selects_largest():
    x = jnp.asarray([0.1, -5.0, 0.3, 2.0, -0.2])
    out = TopK(k=2).compress(jax.random.key(0), x)
    np.testing.assert_allclose(np.asarray(out),
                               [0.0, -5.0, 0.0, 2.0, 0.0])


def test_sparse_wire_format_roundtrip():
    comp = RandK(k=4)
    x = jax.random.normal(jax.random.key(3), (16,))
    vals, idx = comp.compress_sparse(jax.random.key(4), x)
    dense = comp.compress(jax.random.key(4), x)
    rebuilt = jnp.zeros_like(x).at[idx].set(vals)
    np.testing.assert_allclose(np.asarray(rebuilt), np.asarray(dense),
                               rtol=1e-6)


def test_wire_bits_ordering():
    d = 1000
    assert RandK(k=10).wire_bits(d) < Identity().wire_bits(d)
    assert (Composed(inner=RandK(k=10), outer=NaturalCompression())
            .wire_bits(d) < RandK(k=10).wire_bits(d))


def test_pp_wrapper_omega():
    """Footnote 3: C^{p_a} in U((w+1)/p_a - 1)."""
    from repro.core.compressors import PartialParticipationCompressor
    inner = RandK(k=8)
    d = 32
    w = inner.omega(d)
    wrapped = PartialParticipationCompressor(inner=inner, p_a=0.25)
    assert np.isclose(wrapped.omega(d), (w + 1) / 0.25 - 1)
    x = jax.random.normal(jax.random.key(5), (d,))
    _mc_check_unbiased(wrapped, x, trials=2048, tol=0.3)
