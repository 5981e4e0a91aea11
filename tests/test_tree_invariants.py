"""Property tests (hypothesis) for the hierarchical fleet (fl/tree.py):

(a) the wire-bits ledger balances — the cumulative ``bits_cum`` metric
    equals the sum of per-hop totals, which themselves equal the
    arrival-counted client uplinks and the per-tier message logs;
(b) staleness composes across hops — every commit record's staleness
    telescopes through its hop stamps to commit minus dispatch round;
(c) edge pre-reduction is associative — with a lossless schedule (zero
    jitter, barrier buffers) a tree commits the same contribution
    multiset as the flat topology and lands on the same estimator up
    to float64 summation order.
"""
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypo import given, settings, st
from repro.core import (LogisticSigmoidProblem, RandK, SNice,
                        make_synthetic_classification)
from repro.core.dasha_pp import DashaPPConfig
from repro.fl import (ConstantLatency, DenseProblemWorkload, FleetConfig,
                      HierarchicalFleet, LognormalLatency, TierConfig,
                      compose_hops)

N, M, D = 6, 5, 16


@pytest.fixture(scope="module")
def workload():
    feats, y = make_synthetic_classification(jax.random.key(0),
                                             n_nodes=N, m_per_node=M, d=D)
    problem = LogisticSigmoidProblem(feats, y)
    return DenseProblemWorkload(
        problem, RandK(k=4), SNice(n=N, s=3),
        DashaPPConfig("gradient", gamma=0.02, a=0.1, b=0.3,
                      batch_size=2))


def _fcfg(depth, edge_k, root_k, max_st=None):
    tiers = ()
    if depth >= 1:
        tiers += (TierConfig(aggregators=2, buffer_size=edge_k),)
    if depth >= 2:
        tiers += (TierConfig(aggregators=2, buffer_size=edge_k),)
    return FleetConfig(tiers=tiers, buffer_size=root_k,
                       max_staleness=max_st)


def _run(workload, fcfg, latency, seed, rounds=6):
    fleet = HierarchicalFleet(workload, fcfg, latency)
    return fleet.run(jax.random.key(seed), jnp.zeros(D), rounds)


# ----------------------------------------------------------------------
# (a) the wire-bits ledger balances
# ----------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(depth=st.integers(0, 2),
       edge_k=st.sampled_from([None, 1, 2]),
       root_k=st.sampled_from([None, 1, 2]),
       dropout=st.sampled_from([0.0, 0.3]),
       seed=st.integers(0, 5))
def test_wire_bits_ledger(workload, depth, edge_k, root_k, dropout, seed):
    fcfg = _fcfg(depth, edge_k, root_k, max_st=4)
    lat = LognormalLatency(compute_s=1.0, sigma=0.7, client_sigma=0.7,
                           dropout=dropout, seed=seed)
    _, res = _run(workload, fcfg, lat, seed)
    assert len(res.tier_bits) == depth + 1
    # the headline metric is exactly the sum of the per-hop ledgers
    assert res.bits_cum[-1] == pytest.approx(res.tier_bits.sum(),
                                             rel=1e-9)
    # hop 0: one client uplink per delivered ARRIVAL event
    n_arrivals = sum(1 for e in res.event_log if e[2] == "arrival")
    assert res.tier_bits[0] == pytest.approx(
        n_arrivals * workload.wire_bits, rel=1e-9)
    # hop k+1: the sum of tier-k flush messages, as logged on the wire
    for k in range(depth):
        logged = sum(m.bits for m in res.message_log if m.tier == k)
        assert res.tier_bits[k + 1] == pytest.approx(logged, rel=1e-9)
    # the root hop is what root_bits_cum tracks
    assert res.root_bits_cum[-1] == pytest.approx(res.tier_bits[-1],
                                                  rel=1e-9)


# ----------------------------------------------------------------------
# (b) staleness composes across hops
# ----------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(depth=st.integers(0, 2),
       edge_k=st.sampled_from([None, 1, 3]),
       root_k=st.sampled_from([None, 1, 2]),
       seed=st.integers(0, 5))
def test_staleness_composes_across_hops(workload, depth, edge_k, root_k,
                                        seed):
    fcfg = _fcfg(depth, edge_k, root_k)
    lat = LognormalLatency(compute_s=1.0, sigma=1.0, client_sigma=1.0,
                           seed=seed)
    _, res = _run(workload, fcfg, lat, seed)
    assert res.commit_log
    for rec in res.commit_log:
        assert len(rec.hops) == depth
        total, increments = compose_hops(
            rec.dispatch_round, [r for _, r in rec.hops],
            rec.commit_round)
        assert total == rec.staleness \
            == rec.commit_round - rec.dispatch_round
        assert sum(increments) == total
        assert all(i >= 0 for i in increments)
        assert [k for k, _ in rec.hops] == list(range(depth))
    assert Counter(r.staleness for r in res.commit_log) \
        == res.staleness_hist


def test_compose_hops_rejects_time_travel():
    with pytest.raises(ValueError):
        compose_hops(3, [2], 5)
    total, inc = compose_hops(1, [2, 4], 7)
    assert total == 6 and inc == (1, 2, 3)


# ----------------------------------------------------------------------
# (c) edge pre-reduction is associative
# ----------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(depth=st.integers(1, 2), seed=st.integers(0, 5))
def test_pre_reduction_is_associative(workload, depth, seed):
    """Zero jitter + barrier buffers: the tree and the flat topology
    dispatch identical cohorts, commit the identical (client, round)
    multiset, and agree on g to float64 summation order — pre-reduction
    reorders the sum, it never changes it."""
    lat = ConstantLatency(compute_s=1.0)
    fs_tree, r_tree = _run(workload, _fcfg(depth, None, None), lat, seed)
    fs_flat, r_flat = _run(workload, _fcfg(0, None, None), lat, seed)
    assert Counter((r.client, r.dispatch_round)
                   for r in r_tree.commit_log) \
        == Counter((r.client, r.dispatch_round)
                   for r in r_flat.commit_log)
    np.testing.assert_allclose(fs_tree.g, fs_flat.g,
                               rtol=1e-12, atol=1e-14)
    np.testing.assert_allclose(fs_tree.x, fs_flat.x, rtol=0, atol=0)
    np.testing.assert_array_equal(fs_tree.store.dense("g_i"),
                                  fs_flat.store.dense("g_i"))
