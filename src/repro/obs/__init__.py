"""Unified observability layer (DESIGN.md §13).

Three pillars, all stdlib-only (jax imported lazily where needed):

- ``obs.trace``    — nestable spans, dual wall/virtual clocks, Chrome
  trace-event export (loadable in Perfetto).
- ``obs.metrics``  — typed counter/gauge/histogram registry, jsonl
  sink and JSON snapshot; the engines publish their ledgers into it.
- ``obs.monitors`` — live invariant checks (wire-bits reconciliation,
  pool refcount conservation, staleness-hop monotonicity) firing as
  structured warnings in traced runs.

:func:`start_run` is the one-call entrypoint the launch CLIs and
benches use to honor ``--trace-out`` / ``--metrics-out``.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional

from repro.obs import metrics, monitors, provenance, trace
from repro.obs.metrics import (Counter, Gauge, Histogram, JsonlSink,
                               Registry, get_registry)
from repro.obs.monitors import MonitorResult, ObsWarning
from repro.obs.trace import (Tracer, active, counter, instant,
                             kernel_scope, phase_scope, set_virtual_time,
                             span, traced)

__all__ = [
    "metrics", "monitors", "provenance", "trace",
    "Counter", "Gauge", "Histogram", "JsonlSink", "Registry",
    "get_registry", "MonitorResult", "ObsWarning", "Tracer", "active",
    "counter", "instant", "kernel_scope", "phase_scope",
    "set_virtual_time", "span",
    "traced", "ObsRun", "start_run", "add_cli_flags", "profiler_trace",
]


class ObsRun:
    """Handle for one observed run; ``finish()`` writes the artifacts."""

    def __init__(self, trace_out: Optional[str] = None,
                 metrics_out: Optional[str] = None,
                 meta: Optional[Dict[str, Any]] = None):
        self.trace_out = trace_out
        self.metrics_out = metrics_out
        self._finished = False
        self.provenance = provenance.collect()
        if meta:
            self.provenance.update(meta)
        self.tracer: Optional[Tracer] = None
        if trace_out:
            self.tracer = trace.configure(meta=self.provenance)

    def finish(self, registry: Optional[Registry] = None,
               quiet: bool = False) -> "ObsRun":
        """Export trace + metrics snapshot; idempotent."""
        if self._finished:
            return self
        self._finished = True
        if self.tracer is not None:
            if trace.get_tracer() is self.tracer:
                trace.uninstall()
            self.tracer.export_chrome(self.trace_out)
            if not quiet:
                print(f"[obs] trace -> {self.trace_out} "
                      f"({len(self.tracer.events)} events)")
        if self.metrics_out:
            reg = registry or get_registry()
            reg.write_snapshot(self.metrics_out,
                               extra={"provenance": self.provenance})
            if not quiet:
                print(f"[obs] metrics -> {self.metrics_out} "
                      f"({len(reg.names())} metrics)")
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.finish()
        return False


def start_run(trace_out: Optional[str] = None,
              metrics_out: Optional[str] = None,
              meta: Optional[Dict[str, Any]] = None) -> ObsRun:
    """Begin an observed run (no-op handle when both outputs are None)."""
    return ObsRun(trace_out=trace_out, metrics_out=metrics_out, meta=meta)


def add_cli_flags(ap) -> None:
    """Attach the standard ``--trace-out`` / ``--metrics-out`` /
    ``--profile-dir`` flags."""
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON (open in "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics registry snapshot JSON")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture the hot section with jax.profiler "
                         "(TensorBoard/Perfetto-loadable; the "
                         "repro.kernel.* and repro.phase.* named scopes "
                         "appear in the device trace, the program's "
                         "spans in its host plane)")


@contextlib.contextmanager
def profiler_trace(profile_dir: Optional[str]):
    """``jax.profiler.trace(profile_dir)`` as a context manager, or a
    no-op context when ``profile_dir`` is None (jax is then never
    imported — the obs core stays stdlib-only).  The launch CLIs wrap
    their hot section in this so ``--profile-dir`` captures the
    ``kernel_scope`` and ``phase_scope`` names in a real device
    profile; while it is open every :func:`span` is also a profiler
    ``TraceAnnotation`` (:func:`trace.profiler_spans`), so the
    program's spans sit in the profile's host plane on the device
    trace's clock."""
    if not profile_dir:
        yield
        return
    import jax
    with jax.profiler.trace(profile_dir), trace.profiler_spans():
        yield
