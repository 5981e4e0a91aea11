#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, and the serving
knee; run on the chip, never by the benchmark's own runs.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--seconds S] [--sound-only] [--out FILE]
    python3 chipbench/calibrate.py --workload <cell> --sweep 1,2,4 \\
        --seeds 7 --seconds 30 [--out FILE]

For each seed, in one process: the program's run as the benchmark makes
it (its compared numbers are the lower readings), then, unless
``--sound-only``, the control, the reference computed with float8
products in the program's place, and for training cells the planted
fault that leaves half of each batch out (the mean taken over the
rest), each held against the float32 reference.  ``--sweep`` instead
serves the cell's traffic at each rate and reports the requests waiting
in each quarter of the window: a backlog that keeps growing is over the
knee.  One JSON line per reading; ``--out`` collects them.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(cell, seed, seconds, devices, sound_only=False):
    import jax
    import jax.numpy as jnp

    from chipbench import compare, weights
    from chipbench.drivers import train
    from chipbench.reference import dasha

    out = train.run(cell, seconds, seed, devices, time.perf_counter())
    m, tcfg = cell.config["model"], cell.config["trainer"]
    ref = out["reference"]
    if sound_only:
        return {"sound": out["gaps"], "participants": ref["participants"],
                "setup_s": out["setup_s"], "e2e": out["e2e"]}
    make_w = jax.jit(functools.partial(weights.make, model=m,
                                       dtype=jnp.bfloat16, arch=cell.arch))

    def x0():
        return make_w(weights.stream(seed, "weights"))

    n = cell.chips
    tr = cell.traffic
    data_key = weights.stream(seed, "data")
    rkeys = weights.stream(seed, "rounds")
    batches = [train._batch(jax.random.fold_in(data_key, j), n,
                            int(tr["seqs_per_node"]), int(tr["seq_len"]),
                            m["vocab_size"])["tokens"]
               for j in range(train.CHECKED_ROUNDS)]
    keys = [jax.random.fold_in(rkeys, t)
            for t in range(train.CHECKED_ROUNDS)]
    plain = cell.reference
    control = dasha.run_reference(m, tcfg, x0, batches, keys, n,
                                  mm=plain.fp8_mm, ref=plain)
    half = dasha.run_reference(
        m, tcfg, x0, batches, keys, n,
        tokens_view=lambda t: t[..., : t.shape[-1] // 2], ref=plain)
    return {"sound": out["gaps"],
            "control": compare.train_gaps(control, ref),
            "half_batch": compare.train_gaps(half, ref),
            "participants": ref["participants"],
            "loss": ref["loss"], "setup_s": out["setup_s"],
            "e2e": out["e2e"]}


def _serve(cell, seed, seconds, devices, sound_only=False):
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.drivers import serve
    from chipbench.reference.serve_check import ServedGaps

    out = serve.run(cell, seconds, seed, devices, time.perf_counter())
    if sound_only:
        return {"sound": out["gaps"], "setup_s": out["setup_s"],
                "e2e": out["e2e"], "failed": out["failed"],
                "attempted": out["attempted"]}
    m = cell.config["model"]
    params = jax.jit(functools.partial(weights.make, model=m,
                                       dtype=jnp.bfloat16, arch=cell.arch))(
        weights.stream(seed, "weights"))
    control = ServedGaps(m, cell.reference).control(params, out["rows"])
    return {"sound": out["gaps"],
            "control": {"served_logit_gap": control},
            "served_tokens": sum(len(o) for _, o in out["rows"]),
            "setup_s": out["setup_s"], "e2e": out["e2e"],
            "failed": out["failed"], "attempted": out["attempted"]}


def _sweep(cell, rate, seed, seconds, devices):
    from chipbench.drivers import serve

    cell.traffic = dict(cell.traffic, rate_per_s=rate)
    out = serve.run(cell, seconds, seed, devices, time.perf_counter())
    # requests queued or in a slot, averaged over each quarter of the
    # window: a backlog that grows from quarter to quarter is over the
    # knee
    quarters = [[w for t, w in out["waiting"]
                 if q * seconds / 4 <= t < (q + 1) * seconds / 4]
                for q in range(4)]
    return {"rate_per_s": rate, "e2e": out["e2e"],
            "waiting_by_quarter": [sum(x) / len(x) if x else None
                                   for x in quarters],
            "attempted": out["attempted"], "failed": out["failed"],
            "queued_at_close": out["queued_at_close"],
            "unfinished_at_close": out["unfinished_at_close"],
            "sound": out["gaps"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--sound-only", action="store_true",
                    help="the program's readings alone, without the "
                    "control and the planted fault")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import harness

    cell = harness.resolve(args.workload, ROOT)
    devices = harness.tpu_devices(cell.chips)
    harness.enable_compile_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = []
    if args.sweep:
        jobs = [(_sweep, float(r), s) for r in args.sweep.split(",")
                for s in seeds]
    else:
        fn = {"train": _train, "serve": _serve}[cell.driver]
        jobs = [(fn, None, s) for s in seeds]
    for fn, rate, seed in jobs:
        if rate is None:
            rec = fn(cell, seed, args.seconds, devices, args.sound_only)
        else:
            rec = fn(cell, rate, seed, args.seconds, devices)
        rec = dict(rec, seed=seed, workload=cell.name)
        line = json.dumps(rec)
        print(line, flush=True)
        lines.append(line)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
