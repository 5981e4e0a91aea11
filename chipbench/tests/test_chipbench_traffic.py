"""The traffic generator and the seed streams: every seed gets the same
sizes and arrival times, in another order and on other tokens, and
seeds past 32 bits are taken whole."""
from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from chipbench import traffic, weights

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def _alpaca() -> dict:
    with open(os.path.join(TRAFFIC, "alpaca.json")) as f:
        return json.load(f)


def test_every_seed_gets_the_same_schedule():
    t = _alpaca()
    lead = t["lead_s"]
    a = traffic.requests(t, 51.0, 3000000019, 49155)
    b = traffic.requests(t, 51.0, 2 ** 40 + 7, 49155)
    assert len(a) == len(b) == round(t["rate_per_s"] * (lead + 51))
    assert [q.due_s for q in a] == [q.due_s for q in b]
    sizes = [(len(q.prompt), q.max_new) for q in a]
    assert sorted(sizes) == sorted((len(q.prompt), q.max_new) for q in b)
    assert sizes != [(len(q.prompt), q.max_new) for q in b]
    assert [q.prompt for q in a] != [q.prompt for q in b]
    for q in a:
        assert t["prompt"]["min"] <= len(q.prompt) <= t["prompt"]["max"]
        assert t["output"]["min"] <= q.max_new <= t["output"]["max"]
        assert len(q.prompt) + q.max_new <= t["max_total"]
        assert -lead <= q.due_s < 51.0
        assert min(q.prompt) >= 1 and max(q.prompt) < 49155
    assert sum(q.due_s < 0 for q in a) > 0
    # the clipped means stay near the source's (19.31 and 58.45 tokens)
    many = traffic.requests(dict(t, rate_per_s=100.0), 51.0, 1, 49155)
    assert np.mean([len(q.prompt) for q in many]) == pytest.approx(19.31,
                                                                   rel=0.05)
    assert np.mean([q.max_new for q in many]) == pytest.approx(58.45,
                                                               rel=0.05)
    # the same seed, the same requests
    again = traffic.requests(t, 51.0, 3000000019, 49155)
    assert [(q.prompt, q.max_new) for q in again] == \
        [(q.prompt, q.max_new) for q in a]


def test_check_sample_holds_the_longest_and_is_drawn_from_the_seed():
    t = _alpaca()
    finished = {u: 10 + (u * 37) % 90 for u in range(40)}
    s1 = traffic.check_sample(finished, t, 11)
    s2 = traffic.check_sample(finished, t, 12)
    longest = max(finished, key=lambda u: (finished[u], -u))
    assert s1[0] == s2[0] == longest
    assert s1 != s2
    assert len(s1) <= t["check_max_requests"]
    assert traffic.check_sample({}, t, 11) == []


def test_seed_streams_take_large_seeds_whole():
    k = [jax.random.key_data(weights.stream(s, "weights"))
         for s in (5, 5 + 2 ** 31, 5 + 2 ** 32)]
    assert not np.array_equal(k[0], k[1])
    assert not np.array_equal(k[1], k[2])
    assert np.array_equal(
        jax.random.key_data(weights.stream(2 ** 33 + 1, "data")),
        jax.random.key_data(weights.stream(2 ** 33 + 1, "data")))
    with pytest.raises(ValueError):
        weights.seed_key(-1)
