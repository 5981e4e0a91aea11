"""The train driver end to end at a tiny size on the CPU, with the
kernels in interpret mode: a sound run is correct under the tiny size's
limits, and a run whose timed path is broken underneath (the state left
unchanged, half of each batch left out) is not; the float8 control, the
reference in the program's place, fails them too."""
from __future__ import annotations

import functools
import io
import json
import types
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest

from chipbench import compare, harness, run, weights
from chipbench.drivers import train
from chipbench.reference import dasha, granite
from chipbench.tests import tiny

SEED = 2 ** 33 + 5          # takes part in every checked round
LIMITS = tiny.TRAIN_LIMITS


def _run_line(cell) -> dict:
    args = types.SimpleNamespace(seconds=0.5, seed=SEED, trace=0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run._run(cell, args, jax.devices()[:1], None) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def test_a_sound_run_is_correct():
    line = _run_line(tiny.train_cell(LIMITS))
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    # the cell compares the same numbers
    assert set(harness.resolve("train-d8-seq4k", harness.ROOT).limits) == \
        set(line["checks"])
    assert line["device"]["platform"] == "cpu"


def _unchanged_state(monkeypatch):
    from repro.training.trainer import Trainer

    real = Trainer.jit_train_step

    def jit_train_step(self, batch):
        step = real(self, batch)

        class Lowered:
            def __init__(self, lowered):
                self.lowered = lowered

            def compile(self):
                inner = self.lowered.compile()
                # the step donates its state: run it on a copy, keep the
                # state as it was and report the step's own metrics
                return lambda state, b, k: (state, inner(
                    jax.tree.map(jnp.copy, state), b, k)[1])

        return types.SimpleNamespace(
            lower=lambda *a: Lowered(step.lower(*a)))

    monkeypatch.setattr(Trainer, "jit_train_step", jit_train_step)


def _half_batch(monkeypatch):
    import repro.data.sharding as sharding

    real = sharding.place_batch

    def place_batch(batch, mesh, axes):
        toks = batch["tokens"]
        return real({"tokens": toks[..., : toks.shape[-1] // 2]}, mesh, axes)

    monkeypatch.setattr(sharding, "place_batch", place_batch)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = _run_line(tiny.train_cell(LIMITS))
    assert not line["correct"], line["checks"]


def test_the_float8_control_fails_the_limits():
    cell = tiny.train_cell()
    m, tcfg = cell.config["model"], cell.config["trainer"]
    make_w = jax.jit(functools.partial(weights.make, model=m,
                                       dtype=jnp.bfloat16))

    def x0():
        return make_w(weights.stream(SEED, "weights"))

    data, rkeys = weights.stream(SEED, "data"), weights.stream(SEED, "rounds")
    batches = [train._batch(jax.random.fold_in(data, j), 1, 1, 32,
                            m["vocab_size"])["tokens"] for j in range(3)]
    keys = [jax.random.fold_in(rkeys, t) for t in range(3)]
    ref = dasha.run_reference(m, tcfg, x0, batches, keys, 1)
    ctl = dasha.run_reference(m, tcfg, x0, batches, keys, 1,
                              mm=granite.fp8_mm)
    checks = compare.judge(compare.train_gaps(ctl, ref), LIMITS)
    assert not compare.is_correct(checks), checks
