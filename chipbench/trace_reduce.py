"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Events come from :class:`jax.profiler.ProfileData`.  It does not expose
an event's metadata, where the TPU trace keeps each op's ``tf_op`` (the
``jax.named_scope`` path, e.g. ``.../repro.kernel.dasha_h_update/...``)
and ``hlo_category``; :func:`read_op_metadata` reads those from the
plane's metadata table with a minimal protobuf walk that skips the
event lines.

Conventions:

* Device ops are the events of each TPU plane's ``XLA Ops`` line.  An op
  can contain others (a ``while`` loop's event spans its body), so each
  op gets a *self* time: its duration less what the ops nested in it
  cover.  Sums of op time are sums of self time.
* Busy time is the union of the ``XLA Ops`` intervals inside the window.
  Asynchronous copies (``Async XLA Ops``) overlap compute and are not
  busy time.
* The window is the host span :data:`WINDOW_SPAN` that the benchmark
  wraps around its traced loop; host spans are the events of the host
  plane's lines, one line per thread (``jax.profiler.TraceAnnotation``
  names among them).
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
_KERNEL_RE = re.compile(r"repro\.kernel\.([A-Za-z0-9_]+)")


@dataclasses.dataclass
class Op:
    name: str            # HLO instruction name, e.g. "fusion.65"
    start: int           # ns
    end: int             # ns
    scope: str = ""      # tf_op metadata (named-scope path)
    category: str = ""   # hlo_category metadata
    self_ns: int = 0

    @property
    def kernel(self) -> str:
        """``repro.kernel.<name>`` of the innermost kernel scope, or ""."""
        found = _KERNEL_RE.findall(self.scope)
        return f"repro.kernel.{found[-1]}" if found else ""


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]                 # plane name -> ops
    host: List[Tuple[str, int, int]]             # (name, start, end)
    window: Tuple[int, int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


# ----------------------------------------------------------------------
# protobuf walk for the per-op metadata
# ----------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None):
    """Yield (field number, wire type, value or (start, end)) of one
    message; length-delimited values are returned as slices, unread."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield num, wt, v
        elif wt == 1:
            yield num, wt, buf[i:i + 8]
            i += 8
        elif wt == 2:
            n, i = _varint(buf, i)
            yield num, wt, (i, i + n)
            i += n
        elif wt == 5:
            yield num, wt, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")


def read_op_metadata(raw: bytes, keys: Sequence[str] = ("tf_op",
                                                        "hlo_category")
                     ) -> Dict[str, Dict[str, Dict[str, str]]]:
    """{plane name: {event metadata name: {stat: value}}} for the string
    stats ``keys`` of every device plane's event metadata."""
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for num, wt, span in _fields(raw):
        if num != 1 or wt != 2:                           # XSpace.planes
            continue
        name, entries, stat_names = "", [], {}
        for pn, pwt, pv in _fields(raw, *span):
            if pn == 2:                                     # XPlane.name
                name = raw[pv[0]:pv[1]].decode()
            elif pn == 4:                                   # event_metadata
                entries.append(pv)
            elif pn == 5:                                   # stat_metadata
                sid, sname = None, ""
                for en, _, ev in _fields(raw, *pv):
                    if en == 2:
                        for mn, _, mv in _fields(raw, *ev):
                            if mn == 1:
                                sid = mv
                            elif mn == 2:
                                sname = raw[mv[0]:mv[1]].decode()
                if sid is not None:
                    stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        want = {sid for sid, s in stat_names.items() if s in keys}
        table: Dict[str, Dict[str, str]] = {}
        for entry in entries:
            for en, _, ev in _fields(raw, *entry):
                if en != 2:
                    continue
                md_name, stats = "", {}
                for mn, _, mv in _fields(raw, *ev):
                    if mn == 2:
                        md_name = raw[mv[0]:mv[1]].decode(errors="replace")
                    elif mn == 5:
                        sid, sval = None, None
                        for sn, _, sv in _fields(raw, *mv):
                            if sn == 1:
                                sid = sv
                            elif sn == 5:
                                sval = raw[sv[0]:sv[1]].decode(
                                    errors="replace")
                        if sid in want and sval is not None:
                            stats[stat_names[sid]] = sval
                if stats:
                    table[md_name] = stats
        out[name] = table
    return out


# ----------------------------------------------------------------------
# recording and loading
# ----------------------------------------------------------------------

def start_trace(trace_dir: str) -> None:
    """Start the profiler with the device trace and the host's
    ``TraceAnnotation`` spans, and without the Python function tracer:
    it records every Python call of the serving loop, slows the host it
    measures, and can crowd the window's own span out of the trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def _short(event_name: str) -> str:
    """``%fusion.65 = bf16[...] fusion(...)`` -> ``fusion.65``."""
    head = event_name.split(" ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _self_times(ops: List[Op]) -> None:
    """Self time of nested ops: a stack walk over ops sorted by start."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for op in ops:
        op.self_ns = op.end - op.start
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack and op.end <= stack[-1].end:
            stack[-1].self_ns -= op.end - op.start
        stack.append(op)


def from_profile(pd, metadata: Dict[str, Dict[str, Dict[str, str]]],
                 window_span: str = WINDOW_SPAN) -> Trace:
    """Build a :class:`Trace` from a ``ProfileData`` and the metadata
    table of :func:`read_op_metadata`."""
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, int, int]] = []
    with warnings.catch_warnings():
        # the bindings' stat types lack a __module__ on some versions
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            pname = plane.name
            if pname.startswith("/device:TPU:") or (
                    pname.startswith("/device:") and pname in metadata):
                meta = metadata.get(pname, {})
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    ops = devices.setdefault(pname, [])
                    for e in line.events:
                        md = meta.get(e.name, {})
                        ops.append(Op(_short(e.name), int(e.start_ns),
                                      int(e.end_ns), md.get("tf_op", ""),
                                      md.get("hlo_category", "")))
            elif pname.startswith("/host:"):
                # one line per host thread, named after the thread
                for line in plane.lines:
                    for e in line.events:
                        host.append((e.name, int(e.start_ns),
                                     int(e.end_ns)))
    for ops in devices.values():
        _self_times(ops)
    spans = [(s, e) for n, s, e in host if n == window_span]
    if not spans:
        raise ValueError(f"trace has no host span {window_span!r}")
    window = (min(s for s, _ in spans), max(e for _, e in spans))
    return Trace(devices=devices, host=host, window=window)


def load(path: str, window_span: str = WINDOW_SPAN) -> Trace:
    """Read the newest ``.xplane.pb`` at or under ``path`` (or the file
    ``path``, gzipped where its name ends in ``.gz``)."""
    from jax.profiler import ProfileData

    f = find_xplane(path)
    with (gzip.open if f.endswith(".gz") else open)(f, "rb") as fh:
        raw = fh.read()
    return from_profile(ProfileData.from_serialized_xspace(raw),
                        read_op_metadata(raw), window_span)


# ----------------------------------------------------------------------
# reductions
# ----------------------------------------------------------------------

def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def busy_intervals(trace: Trace, device: str) -> List[Tuple[int, int]]:
    lo, hi = trace.window
    return _union(_clip(((o.start, o.end) for o in trace.devices[device]),
                        lo, hi))


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the devices traced."""
    if not trace.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(trace, d))
              for d in trace.devices)
    return tot * 1e-9 / len(trace.devices)


def _in_window(trace: Trace, op: Op) -> bool:
    return trace.window[0] <= op.start and op.end <= trace.window[1]


def scope_s(trace: Trace, kernels: Sequence[str]) -> Optional[float]:
    """Self seconds of the window's ops under any ``repro.kernel.<k>``
    scope whose name starts with one of ``kernels``, averaged over
    devices; None where no such op ran."""
    tot, hit = 0, False
    for ops in trace.devices.values():
        for op in ops:
            k = op.kernel
            if k and any(k.startswith("repro.kernel." + p)
                         for p in kernels) and _in_window(trace, op):
                tot += op.self_ns
                hit = True
    if not hit:
        return None
    return tot * 1e-9 / len(trace.devices)


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """[[name, seconds]]: the ops with the most self time in the window,
    named ``<repro.kernel scope>/<instruction>`` where a kernel scope
    holds them, averaged over devices."""
    acc: Dict[str, int] = {}
    for ops in trace.devices.values():
        for op in ops:
            if not _in_window(trace, op):
                continue
            key = f"{op.kernel}/{op.name}" if op.kernel else op.name
            acc[key] = acc.get(key, 0) + op.self_ns
    nd = max(len(trace.devices), 1)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9 / nd] for k, v in best]


def idle_gaps(trace: Trace, labels: Sequence[str], n: int = 10
              ) -> List[List]:
    """[[host span, seconds]]: idle time of the device in the window,
    summed by the innermost of the benchmark's host spans (``labels``)
    covering the middle of each gap ("host.other" where none does);
    first device only."""
    if not trace.devices:
        return []
    dev = sorted(trace.devices)[0]
    busy = busy_intervals(trace, dev)
    lo, hi = trace.window
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = [(s, e, name) for name, s, e in trace.host if name in labels]
    acc: Dict[str, int] = {}
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        mid = (s + e) // 2
        inner = [(se - ss, name) for ss, se, name in spans
                 if ss <= mid < se]
        label = min(inner)[1] if inner else "host.other"
        acc[label] = acc.get(label, 0) + (e - s)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]
