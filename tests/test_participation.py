"""Assumption-8 property tests for the participation samplers, and the
sampling Lemma 1 identity checked by Monte-Carlo."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo import given, settings, st

from repro.core.participation import FullParticipation, Independent, SNice


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 30), seed=st.integers(0, 10))
def test_snice_exact_count(n, seed):
    s = max(1, n // 3)
    samp = SNice(n=n, s=s)
    mask = samp.sample(jax.random.key(seed))
    assert int(jnp.sum(mask)) == s


@pytest.mark.parametrize("samp", [SNice(n=12, s=4), Independent(n=12, p=0.3),
                                  FullParticipation(n=12)])
def test_assumption8_probabilities(samp):
    trials = 4000
    keys = jax.random.split(jax.random.key(0), trials)
    masks = jax.vmap(samp.sample)(keys).astype(jnp.float32)
    p_hat = jnp.mean(masks, axis=0)
    np.testing.assert_allclose(np.asarray(p_hat), samp.p_a, atol=0.04)
    # pairwise
    pair = jnp.einsum("ti,tj->ij", masks, masks) / trials
    off = np.asarray(pair)[~np.eye(samp.n, dtype=bool)]
    np.testing.assert_allclose(off, samp.p_aa, atol=0.05)
    # eq. (5): p_aa <= p_a^2
    assert samp.p_aa <= samp.p_a ** 2 + 1e-12


def test_one_pa_definition():
    samp = SNice(n=10, s=5)
    expected = np.sqrt(1 - samp.p_aa / samp.p_a)
    assert np.isclose(samp.one_pa, expected)
    assert np.isclose(FullParticipation(n=7).one_pa, 0.0)


def test_sampling_lemma_variance():
    """Lemma 1 (the workhorse of every proof): for v_i = r_i + s_i/p_a on
    participation, Var(mean v) equals the three-term closed form."""
    n, d = 8, 5
    key = jax.random.key(0)
    r = jax.random.normal(key, (n, d))
    mu = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    sigma = 0.3
    samp = SNice(n=n, s=3)
    pa, paa = samp.p_a, samp.p_aa

    def one(k):
        k1, k2 = jax.random.split(k)
        s_i = mu + sigma * jax.random.normal(k1, (n, d))
        mask = samp.sample(k2)[:, None]
        v = r + jnp.where(mask, s_i / pa, 0.0)
        return jnp.mean(v, axis=0)

    trials = 20000
    outs = jax.vmap(one)(jax.random.split(key, trials))
    emp_var = float(jnp.mean(jnp.sum(
        (outs - jnp.mean(outs, axis=0)) ** 2, axis=-1)))
    # closed form (equality line of Lemma 1)
    term1 = (1 / (n ** 2 * pa)) * n * sigma ** 2 * d
    term2 = (pa - paa) / (n ** 2 * pa ** 2) * float(jnp.sum(mu ** 2))
    term3 = (paa - pa ** 2) / pa ** 2 * float(
        jnp.sum(jnp.mean(mu, axis=0) ** 2))
    closed = term1 + term2 + term3
    assert np.isclose(emp_var, closed, rtol=0.08), (emp_var, closed)
