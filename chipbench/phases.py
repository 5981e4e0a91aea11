"""Device time of the DASHA-PP step by phase.

The program wraps each phase of its jitted step in
``jax.named_scope("repro.phase.<name>")`` (``server_step``,
``grad_pair``, ``dasha_dispatch``, ``dasha_commit``); the scope path is
each op's ``tf_op`` in the device trace, as for the
``repro.kernel.<name>`` scopes that nest inside ``dasha_dispatch``.
The conventions are :func:`trace_reduce.scope_s`'s: self seconds of the
ops that lie wholly inside the window, averaged over the devices
traced.  Phases do not nest, so the phases' times and
:func:`unscoped_s` partition the ops' self time, which is the window's
busy time.
"""
from __future__ import annotations

import re
from typing import Optional

_PHASE_RE = re.compile(r"repro\.phase\.([A-Za-z0-9_]+)")


def phase_of(op) -> str:
    """The innermost ``repro.phase.<name>`` of ``op``'s scope, or ""."""
    found = _PHASE_RE.findall(op.scope)
    return found[-1] if found else ""


def _window_ops(trace):
    lo, hi = trace.window
    for ops in trace.devices.values():
        for op in ops:
            if lo <= op.start and op.end <= hi:
                yield op


def _per_device_s(trace, ns: int) -> float:
    return ns * 1e-9 / len(trace.devices)


def phase_s(trace, name: str) -> Optional[float]:
    """Self seconds of the window's ops under ``repro.phase.<name>``,
    averaged over devices; None where no such op ran."""
    tot, hit = 0, False
    for op in _window_ops(trace):
        if phase_of(op) == name:
            tot += op.self_ns
            hit = True
    return _per_device_s(trace, tot) if hit else None


def unscoped_s(trace) -> Optional[float]:
    """Self seconds of the window's ops under no ``repro.phase.`` scope,
    averaged over devices; None where no op of the window has a phase
    (a program without phase scopes: there is nothing to tell apart)."""
    tot, phased = 0, False
    for op in _window_ops(trace):
        if phase_of(op):
            phased = True
        else:
            tot += op.self_ns
    return _per_device_s(trace, tot) if phased else None
