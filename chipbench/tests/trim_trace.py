#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a slice of time, for the trace
reduction's test fixtures.

    python3 chipbench/tests/trim_trace.py <xplane.pb> <out.xplane.pb.gz> \\
        <from_s> <to_s>

``from_s`` and ``to_s`` are seconds after the start of the window span
(``bench.window``).  Events that start in the slice are kept, host
spans that cover part of it are clipped to it (the window span among
them, so the slice is a window of its own), and each plane keeps the
event metadata its kept events use, each name cut at the `` = `` that
starts the HLO text.  Everything else is copied as it was recorded.
"""
from __future__ import annotations

import gzip
import sys
from typing import List, Tuple

from chipbench.trace_reduce import WINDOW_SPAN, _varint


def _enc_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num: int, wt: int, payload) -> bytes:
    key = _enc_varint((num << 3) | wt)
    if wt == 0:
        return key + _enc_varint(payload)
    if wt == 2:
        return key + _enc_varint(len(payload)) + payload
    return key + payload


def _raw(buf: bytes, i: int = 0, end=None) -> List[Tuple[int, int, object,
                                                         bytes]]:
    """(num, wire type, value, the field's bytes as recorded)."""
    end = len(buf) if end is None else end
    out = []
    while i < end:
        start = i
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        out.append((num, wt, v, buf[start:i]))
    return out


def _event(ev: bytes):
    mid = off = dur = 0
    for num, wt, v, _ in _raw(ev):
        if num == 1 and wt == 0:
            mid = v
        elif num == 2 and wt == 0:
            off = v
        elif num == 3 and wt == 0:
            dur = v
    return mid, off, dur


def _window_ps(planes: List[bytes], names_of) -> Tuple[int, int]:
    for plane in planes:
        names = names_of(plane)
        for num, _, line, _ in _raw(plane):
            if num != 3:
                continue
            fields = _raw(line)
            ts = next((v for n, w, v, _ in fields if n == 3 and w == 0), 0)
            for n, _, ev, _ in fields:
                if n == 4:
                    mid, off, dur = _event(ev)
                    if names.get(mid) == WINDOW_SPAN:
                        return ts * 1000 + off, dur
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def _metadata_names(plane: bytes):
    names = {}
    for num, _, entry, _ in _raw(plane):
        if num != 4:
            continue
        mid, name = None, ""
        for n, _, v, _ in _raw(entry):
            if n == 1:
                mid = v
            elif n == 2:
                for mn, _, mv, _ in _raw(v):
                    if mn == 2:
                        name = mv.decode(errors="replace")
        names[mid] = name
    return names


def _trim_line(line: bytes, lo: int, hi: int, used: set) -> bytes:
    fields = _raw(line)
    ts = next((v for n, w, v, _ in fields if n == 3 and w == 0), 0)
    out = bytearray()
    for num, wt, v, raw in fields:
        if num != 4:
            out += raw
            continue
        mid, off, dur = _event(v)
        s, e = ts * 1000 + off, ts * 1000 + off + dur
        if lo <= s < hi:
            pass
        elif s < lo < e:
            s = lo                      # a span that covers the slice
        else:
            continue
        e = min(e, hi)
        ev = bytearray()
        for n, w, x, r in _raw(v):
            if n == 2 and w == 0:
                ev += _field(2, 0, s - ts * 1000)
            elif n == 3 and w == 0:
                ev += _field(3, 0, e - s)
            else:
                ev += r
        used.add(mid)
        out += _field(4, 2, bytes(ev))
    return bytes(out)


def _short_metadata(entry: bytes) -> bytes:
    out = bytearray()
    for n, w, v, r in _raw(entry):
        if n != 2:
            out += r
            continue
        md = bytearray()
        for mn, mw, mv, mr in _raw(v):
            if mn == 2:
                md += _field(2, 2, mv.split(b" = ", 1)[0])
            elif mn == 4:
                continue                # display name
            else:
                md += mr
        out += _field(2, 2, bytes(md))
    return bytes(out)


def trim(raw: bytes, from_s: float, to_s: float) -> bytes:
    planes = [v for n, w, v, _ in _raw(raw) if n == 1]
    start, _ = _window_ps(planes, _metadata_names)
    lo, hi = start + int(from_s * 1e12), start + int(to_s * 1e12)
    out = bytearray()
    for num, wt, v, r in _raw(raw):
        if num != 1:
            out += r
            continue
        used: set = set()
        lines = bytearray()
        rest = []
        for n, w, x, rr in _raw(v):
            if n == 3:
                lines += _field(3, 2, _trim_line(x, lo, hi, used))
            else:
                rest.append((n, x, rr))
        plane = bytearray()
        for n, x, rr in rest:
            if n == 4:
                mid = next((vv for nn, _, vv, _ in _raw(x) if nn == 1), None)
                if mid in used:
                    plane += _field(4, 2, _short_metadata(x))
            else:
                plane += rr
            if n == 2:
                plane += lines          # lines follow the plane's name
        out += _field(1, 2, bytes(plane))
    return bytes(out)


def main() -> int:
    src, dst, a, b = sys.argv[1:5]
    with open(src, "rb") as f:
        raw = f.read()
    data = trim(raw, float(a), float(b))
    with open(dst, "wb") as f:
        f.write(gzip.compress(data, mtime=0))
    print(f"{len(raw)} bytes -> {len(data)} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
