"""The one generator of serving traffic, driven by a traffic file.

Every seed gets the same set of sizes and the same arrival times, both
drawn from the file's ``shape_seed``; the run's seed draws which size
comes at which arrival, and the tokens.  So runs with different seeds
do the same work, in another order, on other inputs.

Traffic file keys (``chipbench/traffic/<name>.json``):

* ``rate_per_s``: mean arrivals per second of a Poisson process, open
  loop;
* ``lead_s``: seconds of arrivals before the window opens, served in
  set-up so that the window starts with the engine at its steady load;
* ``prompt``, ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length, clipped; ``max_total`` caps prompt + output.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Req:
    uid: int
    due_s: float
    prompt: List[int]
    max_new: int


def _lognormal(rng, spec: Dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def requests(traffic: Dict, seconds: float, seed: int, vocab: int
             ) -> List[Req]:
    """The requests due from ``lead_s`` before the window to its close,
    in due order; the window opens at 0."""
    lead = float(traffic.get("lead_s", 0.0))
    n = max(1, int(round(traffic["rate_per_s"] * (lead + seconds))))
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    prompts = _lognormal(shape, traffic["prompt"], n)
    outputs = _lognormal(shape, traffic["output"], n)
    outputs = np.minimum(outputs, int(traffic["max_total"]) - prompts)
    if np.any(outputs < 1):
        raise ValueError("max_total leaves a request no output token")
    # n arrivals of a Poisson process over the span, given n, are
    # uniform order statistics
    due = np.sort(shape.uniform(0.0, lead + seconds, n)) - lead

    rng = np.random.default_rng(int(seed))
    order = rng.permutation(n)
    return [Req(uid=slot, due_s=float(due[slot]),
                prompt=rng.integers(1, vocab, int(prompts[i])).tolist(),
                max_new=int(outputs[i]))
            for slot, i in enumerate(order)]


def check_sample(finished: Dict[int, int], traffic: Dict, seed: int
                 ) -> List[int]:
    """uids of the finished requests the correctness check reads: the one
    with the most served tokens, then others drawn from the seed until
    ``check_tokens`` served tokens or ``check_max_requests`` requests.
    ``finished`` maps uid -> served tokens."""
    if not finished:
        return []
    uids = sorted(finished)
    first = max(uids, key=lambda u: (finished[u], -u))
    rng = np.random.default_rng([int(seed), 7])
    rest = [u for u in rng.permutation(uids).tolist() if u != first]
    picked, total = [first], finished[first]
    for u in rest:
        if total >= traffic["check_tokens"] or \
                len(picked) >= traffic["check_max_requests"]:
            break
        picked.append(u)
        total += finished[u]
    return picked
