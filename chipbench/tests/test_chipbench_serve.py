"""The serve driver end to end at a tiny size on the CPU, with the paged
kernel in interpret mode: a sound run is correct under the tiny size's
limit, a run whose engine alters each token where it is produced is
not, and the float8 control fails the limit too."""
from __future__ import annotations

import functools
import io
import json
import os
import types
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp

from chipbench import harness, run, weights
from chipbench.reference.serve_check import ServedGaps
from chipbench.tests import tiny

SEED = 2 ** 33 + 5
LIMITS = tiny.SERVE_LIMITS


def _run_line(cell) -> dict:
    args = types.SimpleNamespace(seconds=2.0, seed=SEED, trace=0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run._run(cell, args, jax.devices()[:1], None) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_a_sound_run_is_correct():
    line = _run_line(tiny.serve_cell(LIMITS))
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 8
    assert list(line)[-1] == "checks"
    with open(os.path.join(harness.ROOT, "chipbench", "limits",
                           "serve-alpaca.json")) as f:
        assert set(line["checks"]) == set(json.load(f)["limits"])


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving import PagedEngine

    real = PagedEngine.step
    vocab = tiny.serve_cell().config["model"]["vocab_size"]

    def step(self):
        out = real(self)
        # the greedy token each slot feeds next, one id off
        self._next_tok[:, 0] = (self._next_tok[:, 0] + 1) % vocab
        return out

    monkeypatch.setattr(PagedEngine, "step", step)
    line = _run_line(tiny.serve_cell(LIMITS))
    assert not line["correct"], line["checks"]


def test_the_float8_control_fails_the_limit():
    m = tiny.serve_cell().config["model"]
    params = jax.jit(functools.partial(weights.make, model=m,
                                       dtype=jnp.bfloat16))(
        weights.stream(SEED, "weights"))
    key = jax.random.key(1)
    rows = []
    for i in range(3):
        prompt = jax.random.randint(jax.random.fold_in(key, i), (40,), 1,
                                    m["vocab_size"]).tolist()
        rows.append((prompt, [0] * 24))
    gap = ServedGaps(m).control(params, rows)
    assert gap > LIMITS["served_logit_gap"], gap
