"""The phase readers on a hand-built trace whose every number is known:
ops nested in others, ops outside the window, ops under no phase, and a
program without phase scopes."""
from __future__ import annotations

import pytest

from chipbench import harness, phases
from chipbench.trace_reduce import Op, Trace, busy_s

MS = 1_000_000  # ns
STEP = "jit(train_step)"
PHASE_METRICS = ("train_grad_pair_ms", "train_dasha_dispatch_ms",
                 "train_dasha_commit_ms", "train_server_step_ms",
                 "train_unscoped_ms")


def _op(name, start_ms, dur_ms, scope, self_ms=None):
    return Op(name, start_ms * MS, (start_ms + dur_ms) * MS, scope,
              self_ns=int((dur_ms if self_ms is None else self_ms) * MS))


def _device(shift_ms=0):
    s = shift_ms
    return [
        # outside the window
        _op("fusion.0", s + 1, 2, f"{STEP}/repro.phase.grad_pair/dot"),
        _op("fusion.1", s + 10, 2, f"{STEP}/repro.phase.server_step/add"),
        # a loop whose body holds two ops: its self time is 4 ms
        _op("while.2", s + 12, 10, f"{STEP}/repro.phase.grad_pair/while",
            self_ms=4),
        _op("fusion.3", s + 13, 3,
            f"{STEP}/repro.phase.grad_pair/while/body/dot"),
        _op("fusion.4", s + 17, 3,
            f"{STEP}/repro.phase.grad_pair/transpose(jvp(dot))"),
        # the dispatch and a kernel nested in it
        _op("fusion.5", s + 22, 2,
            f"{STEP}/repro.phase.dasha_dispatch/shard_map/convert"),
        _op("dasha_h_update_pallas.6", s + 24, 3,
            f"{STEP}/repro.phase.dasha_dispatch/shard_map/"
            "repro.kernel.dasha_h_update/pallas_call"),
        _op("fusion.7", s + 27, 1, f"{STEP}/repro.phase.dasha_commit/add"),
        # no phase: a copy the compiler put in, the step's scalars
        _op("copy-done", s + 28, 1, ""),
        _op("fusion.8", s + 29, 1, f"{STEP}/reduce_sum"),
        # outside the window
        _op("fusion.9", s + 45, 3, f"{STEP}/repro.phase.dasha_commit/add"),
    ]


def _trace(devices=1):
    return Trace(devices={f"/device:TPU:{d}": _device()
                          for d in range(devices)},
                 host=[], window=(5 * MS, 40 * MS))


def _ctx(trace, units=2):
    return harness.LayerContext(
        trace=trace, units=units, counts={"model_flops": 197e12 * 0.01},
        peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}, chips=1)


def _read(name, ctx):
    return harness.reader(name, harness.ROOT)(ctx)


@pytest.mark.parametrize("devices", [1, 2])
def test_phase_seconds_by_self_time_inside_the_window(devices):
    t = _trace(devices)
    assert phases.phase_s(t, "grad_pair") == pytest.approx(0.010)
    assert phases.phase_s(t, "server_step") == pytest.approx(0.002)
    assert phases.phase_s(t, "dasha_dispatch") == pytest.approx(0.005)
    assert phases.phase_s(t, "dasha_commit") == pytest.approx(0.001)
    assert phases.unscoped_s(t) == pytest.approx(0.002)
    assert phases.phase_s(t, "no_such_phase") is None


def test_the_innermost_phase_names_an_op():
    ops = {o.name: o for o in _device()}
    assert phases.phase_of(ops["dasha_h_update_pallas.6"]) == \
        "dasha_dispatch"
    assert phases.phase_of(ops["copy-done"]) == ""


def test_readers_per_round_and_the_five_sum_to_busy_time():
    ctx = _ctx(_trace())
    got = {m: _read(m, ctx) for m in PHASE_METRICS}
    assert got == pytest.approx({
        "train_grad_pair_ms": 5.0, "train_dasha_dispatch_ms": 2.5,
        "train_dasha_commit_ms": 0.5, "train_server_step_ms": 1.0,
        "train_unscoped_ms": 1.0})
    # busy: [10, 30) inside the window, 20 ms over 2 rounds
    assert busy_s(ctx.trace) == pytest.approx(0.020)
    assert sum(got.values()) == pytest.approx(
        1000.0 * busy_s(ctx.trace) / ctx.units)
    # 1.97 TFLOP in 10 ms of the gradient pair at 197 TFLOP/s
    assert _read("train_grad_pair_mfu", ctx) == pytest.approx(100.0)


def test_a_missing_phase_and_a_program_without_phases_read_nothing():
    t = _trace()
    for ops in t.devices.values():
        ops[:] = [o for o in ops if "dasha_commit" not in o.scope]
    assert _read("train_dasha_commit_ms", _ctx(t)) is None
    assert _read("train_grad_pair_ms", _ctx(t)) == pytest.approx(5.0)

    bare = _trace()
    for ops in bare.devices.values():
        for o in ops:
            o.scope = o.scope.split("/repro.phase.")[0]
    for m in PHASE_METRICS + ("train_grad_pair_mfu",):
        assert _read(m, _ctx(bare)) is None, m
    assert _read("train_grad_pair_ms", _ctx(_trace(), units=0)) is None
