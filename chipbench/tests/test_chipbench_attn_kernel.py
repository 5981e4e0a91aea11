"""The training attention's kernel reader on the phase tests' hand-built
trace, with the flash kernel's launches added under the gradient pair."""
from __future__ import annotations

import pytest

from chipbench.tests.test_chipbench_phases import STEP, _ctx, _op, _read, \
    _trace


def test_attention_kernel_reader_reads_its_scope_alone():
    """``train_attn_kernel_ms`` reads the flash kernel's forward and its
    transposed backward under the gradient pair; the DASHA-PP kernel
    reader does not count them, and a program without the kernel
    reads nothing."""
    attn = f"{STEP}/repro.phase.grad_pair/repro.kernel.flash_attention"
    t = _trace()
    for ops in t.devices.values():
        ops += [
            _op("splash_mqa_fwd_residuals.10", 31, 2,
                f"{attn}/vmap(vmap(jit(_splash_attention)))/pallas_call"),
            _op("splash_mqa_dq_no_residuals.11", 34, 3,
                f"{STEP}/transpose(jvp(jvp()))/checkpoint/"
                "repro.phase.grad_pair/repro.kernel.flash_attention/"
                "pallas_call"),
            # outside the window
            _op("splash_mqa_dkv_no_residuals.12", 41, 3, f"{attn}/x")]
    ctx = _ctx(t)
    assert _read("train_attn_kernel_ms", ctx) == pytest.approx(2.5)
    assert _read("train_dasha_kernel_ms", ctx) == pytest.approx(1.5)
    assert _read("train_grad_pair_ms", ctx) == pytest.approx(7.5)
    assert _read("train_attn_kernel_ms", _ctx(_trace())) is None
    assert _read("train_attn_kernel_ms", _ctx(t, units=0)) is None
