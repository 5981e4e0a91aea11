"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One benchmark per paper table/figure (see DESIGN.md §7):
  bench_pa_sweep      Fig. 1   (1/p_a degradation, finite-sum + stochastic)
  bench_methods       Figs.2-5 (DASHA-PP vs MARINA vs FRECON)
  bench_comm          Tab.1-2  (communication complexity, CC column)
  bench_batch_effect  §C       (mean-estimation batch-size effect)
  bench_kernels       kernels  (fused update HBM traffic)
  roofline            §Roofline (from dry-run artifacts, if present)

Prints ``name,...,derived`` CSV lines per benchmark.  ``--full`` runs
paper-scale round counts (slow on 1 CPU core); the default quick mode
keeps every benchmark's qualitative claim intact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="results/bench")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from benchmarks import (bench_async, bench_batch_effect, bench_comm,
                            bench_fleet, bench_kernels, bench_methods,
                            bench_obs, bench_pa_sweep, bench_serving,
                            roofline)
    suites = {
        "pa_sweep": bench_pa_sweep.main,
        "methods": bench_methods.main,
        "comm": bench_comm.main,
        "batch_effect": bench_batch_effect.main,
        "kernels": bench_kernels.main,
        "async": bench_async.main,
        "serving": bench_serving.main,
        "fleet": bench_fleet.main,
        "obs": bench_obs.main,
        "roofline": roofline.main,
    }
    # suites that append to their own trajectory file under results/;
    # the generic per-suite dump below must not clobber it
    self_managed = {"kernels"}
    if args.only:
        if args.only not in suites:
            sys.exit(f"unknown benchmark {args.only!r}; choose from "
                     f"{sorted(suites)}")
        suites = {args.only: suites[args.only]}
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for name, fn in suites.items():
        print(f"\n===== benchmark: {name} =====", flush=True)
        t0 = time.time()
        try:
            results = list(fn(quick=quick))
            if name not in self_managed:
                with open(os.path.join(args.out, f"{name}.json"), "w") as f:
                    json.dump(results, f, indent=1, default=str)
            print(f"===== {name} done in {time.time()-t0:.1f}s =====",
                  flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"FAILED benchmarks: {failures}")
        sys.exit(1)
    print("\nall benchmarks passed")


if __name__ == "__main__":
    main()
