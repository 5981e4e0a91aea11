"""End-to-end behaviour tests for the paper's system: baselines run,
degradation ordering holds, substrates (data/checkpoint/serving) work."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Frecon, FreconConfig, Marina, MarinaConfig, RandK,
                        SNice, dasha, dasha_pp, theory)


def _constants(prob):
    L, L_hat, L_max, L_sigma = prob.smoothness()
    return theory.ProblemConstants(L=L, L_hat=L_hat, L_max=L_max,
                                   L_sigma=L_sigma, n=prob.n, m=prob.m,
                                   d=prob.d)


def test_pp_degradation_bounded_by_inverse_pa(small_problem):
    """Paper Fig. 1 claim at test scale: rounds(PP)/rounds(full) <= ~1/p_a
    with theory parameters and a shared (tuned) stepsize."""
    prob = small_problem
    c = _constants(prob)
    comp = RandK(k=max(1, prob.d // 8))
    omega = comp.omega(prob.d)
    x0 = jnp.zeros(prob.d)
    gamma = theory.dasha_gradient(c, omega).gamma * 4

    runs = {}
    for s in (prob.n, 3):
        samp = SNice(n=prob.n, s=s)
        hp = theory.dasha_pp_gradient(c, omega, samp.p_a, samp.p_aa)
        alg = dasha_pp(prob, comp, samp, gamma=gamma, a=hp.a, b=hp.b)
        _, mets = jax.jit(lambda k, a=alg: a.run(k, x0, 2500))(
            jax.random.key(3))
        runs[s] = np.asarray(mets.grad_norm_sq)
    eps = runs[prob.n][300]
    r_full = int(np.argmax(runs[prob.n] <= eps))
    hit = np.nonzero(runs[3] <= eps)[0]
    assert hit.size, "PP run never reached the full-participation level"
    ratio = hit[0] / max(r_full, 1)
    inv_pa = prob.n / 3
    assert ratio <= 1.6 * inv_pa, (ratio, inv_pa)


def test_marina_and_frecon_run(small_problem):
    prob = small_problem
    comp = RandK(k=4)
    samp = SNice(n=prob.n, s=4)
    x0 = jnp.zeros(prob.d)
    m = Marina(prob, comp, samp, MarinaConfig(gamma=0.02, p_sync=0.2))
    _, mm = jax.jit(lambda k: m.run(k, x0, 300))(jax.random.key(0))
    assert np.isfinite(np.asarray(mm.grad_norm_sq)).all()
    assert mm.grad_norm_sq[-1] < mm.grad_norm_sq[0]
    f = Frecon(prob, comp, samp, FreconConfig(gamma=0.02, batch_size=2))
    _, mf = jax.jit(lambda k: f.run(k, x0, 300))(jax.random.key(1))
    assert np.isfinite(np.asarray(mf.loss)).all()


def test_data_pipeline_node_major_and_heterogeneous():
    from repro.data.synthetic import DataConfig, make_batch, token_batches
    from repro.models import get_smoke_config
    cfg = get_smoke_config("granite-3-2b")
    dc = DataConfig(seq_len=32, global_batch=8, num_nodes=4,
                    vocab_size=cfg.vocab_size)
    it = token_batches(dc)
    b1, b2 = next(it), next(it)
    assert b1["tokens"].shape == (4, 2, 32)
    assert not np.array_equal(b1["tokens"], b2["tokens"])
    # heterogeneity: node unigram histograms differ
    h = [np.bincount(b1["tokens"][i].ravel(), minlength=dc.vocab_size)
         for i in range(4)]
    assert not np.array_equal(h[0], h[1])
    # modality batches
    vb = make_batch(get_smoke_config("paligemma-3b"), dc, dtype="float32")
    assert "embeds" in vb and vb["embeds"].shape[2] == 8
    ab = make_batch(get_smoke_config("hubert-xlarge"), dc, dtype="float32")
    assert ab["embeds"].shape == (4, 2, 32, 128)


def test_checkpoint_roundtrip(tmp_path):
    from repro.training.checkpoints import (latest_step, restore_checkpoint,
                                            save_checkpoint)
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "nested": {"b": jnp.ones((4,), jnp.int32)},
             "t": (jnp.zeros(()), jnp.full((2,), 7.0))}
    save_checkpoint(str(tmp_path), state, step=3)
    assert latest_step(str(tmp_path)) == 3
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        state)
    back = restore_checkpoint(str(tmp_path), like)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_server_generates():
    from repro.models import Model, get_smoke_config
    from repro.serving.decode import DecodeServer, Request
    cfg = get_smoke_config("granite-3-2b")
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    srv = DecodeServer(model, params, batch_size=2, max_seq_len=32)
    reqs = [Request(uid=i, prompt=[1, 2, 3], max_new_tokens=4)
            for i in range(3)]
    done = srv.run(reqs)
    for r in done:
        assert len(r.generated) == 4
        assert all(0 <= t < cfg.padded_vocab for t in r.generated)


def test_registry_pairs():
    from repro.models import (ARCH_IDS, INPUT_SHAPES, get_config,
                              pair_supported)
    statuses = {}
    for a in ARCH_IDS:
        for s in INPUT_SHAPES.values():
            cfg = get_config(a)
            if s.name == "long_500k":
                cfg = cfg.for_long_context()
            ok, why = pair_supported(cfg, s)
            statuses[(a, s.name)] = ok
    # exactly the 2 documented encoder-decode skips
    skipped = [k for k, v in statuses.items() if not v]
    assert sorted(skipped) == [("hubert-xlarge", "decode_32k"),
                               ("hubert-xlarge", "long_500k")]
    assert len(statuses) == 40


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache uses $JAX_COMPILATION_CACHE_DIR when
    set, else one fixed, gitignored directory inside the checkout."""
    from repro.launch import compile_cache

    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    assert compile_cache.cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv(compile_cache.ENV)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.cache_dir() == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
