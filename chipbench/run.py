#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The run exits
nonzero and prints no result where it finds no TPU, fewer chips than
the cell asks for, or kernels forced into interpret mode.  The last
line of standard output is one JSON object; the compared numbers and
their limits end standard error and the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import harness

    cell = harness.resolve(args.workload, ROOT)
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr, flush=True)
        return 2
    harness.enable_compile_cache(ROOT)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        return _run(cell, args, devices, trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def _run(cell, args, devices, trace_dir) -> int:
    from chipbench import compare, harness, trace_reduce
    from chipbench.drivers import serve, train
    from chipbench.peaks import peaks

    driver = {"train": train.run, "serve": serve.run}[cell.driver]
    out = driver(cell, args.seconds, args.seed, devices, T_START,
                 trace_dir=trace_dir)
    print(f"chipbench: programs compiled in set-up {out['compiles'][0]}, "
          f"in the window {out['compiles'][1]}", file=sys.stderr,
          flush=True)
    checks = compare.judge(out["gaps"], cell.limits)
    correct = compare.is_correct(checks)
    device = harness.device_info(devices, out["peak_bytes"])
    breakdown = None
    if trace_dir:
        tr = trace_reduce.load(trace_dir)
        busy = trace_reduce.busy_s(tr)
        if busy <= 0:
            print("chipbench: no operation ran on the device in the "
                  "traced window; no result", file=sys.stderr, flush=True)
            return 3
        device["busy_s"] = busy
        device["window_s"] = tr.window_s
        ctx = harness.LayerContext(
            trace=tr, units=out["units"], counts=out["layer_counts"],
            peaks=peaks(devices[0].device_kind), chips=len(devices))
        metrics = harness.read_layers(cell, ctx)
        breakdown = {"device_ops": trace_reduce.top_ops(tr),
                     "idle_gaps": trace_reduce.idle_gaps(
                         tr, out["host_spans"])}
    else:
        values = dict(out["e2e"], setup_s=out["setup_s"])
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    harness.emit(correct, out["attempted"], out["failed"], metrics, device,
                 checks, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
