"""Async-runtime benchmark: virtual wall-clock of buffered first-K
aggregation vs the full-barrier round, swept over latency heterogeneity
x buffer size x variant (DESIGN.md §9).

The sync engines price a round at the cohort MAX latency; the async
server prices it at the K-th order statistic.  Under lognormal
heterogeneity the gap is the paper's partial-participation story told
in wall-clock: the server never needed everyone, so it should not pay
for everyone.  Reported per row:

* ``t_virtual``   — total virtual seconds for the same dispatch budget,
* ``speedup``     — barrier time / this time (barrier row = 1.0),
* ``gnorm``       — median final ||∇f(x)||² (solution quality),
* ``s_mean``      — mean commit staleness (the price of not waiting),
* ``util``        — mean client busy-fraction.

    PYTHONPATH=src python benchmarks/bench_async.py [--smoke] [--sharded]

``--sharded`` additionally compares barrier vs gang-scheduled cohorts
on the sharded LM TRAINER (DESIGN.md §10) by driving
``repro.launch.async_sharded_train --smoke`` in subprocesses (its
8-device host mesh needs XLA_FLAGS set before jax imports, which this
process has already done) and asserts the flight-buffered scheduler
beats the barrier in virtual wall-clock on a heterogeneous fleet.  The
cell measures virtual time, not the device, so the children are pinned
to the CPU: none of them ever needs an accelerator this process may
hold.
"""
from __future__ import annotations

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import make_paper_problem  # noqa: E402
from repro.core import RandK, SNice
from repro.core.dasha_pp import DashaPPConfig
from repro.fl import (AsyncConfig, AsyncDashaServer, ConstantLatency,
                      LognormalLatency)


def run_cell(prob, variant: str, sigma: float, buffer_frac, rounds: int,
             s_cohort: int, seed: int = 1):
    samp = SNice(n=prob.n, s=s_cohort)
    comp = RandK(k=max(1, prob.d // 20))
    cfg = DashaPPConfig(variant, gamma=0.05, a=0.1, b=0.3, p_page=0.25,
                        batch_size=2)
    if sigma == 0.0:
        lat = ConstantLatency(compute_s=1.0, bandwidth_bps=1e5)
    else:
        lat = LognormalLatency(compute_s=1.0, sigma=sigma,
                               client_sigma=sigma, bandwidth_bps=1e5,
                               bandwidth_sigma=sigma / 2)
    K = (None if buffer_frac is None
         else max(1, int(round(buffer_frac * s_cohort))))
    srv = AsyncDashaServer(prob, comp, samp, cfg, AsyncConfig(
        buffer_size=K, staleness_exponent=0.5), lat)
    _, res = srv.run(jax.random.key(seed), jnp.zeros(prob.d), rounds)
    return dict(
        t_virtual=res.total_time,
        gnorm=float(np.median(res.grad_norm_sq[-max(1, rounds // 10):])),
        s_mean=float(np.mean(res.staleness_mean)),
        util=float(np.mean(res.utilization)),
        bits=float(res.bits_cum[-1]))


def main(quick: bool = True):
    if quick:
        n, m, d, rounds = 8, 6, 24, 25
        variants_ = ("mvr", "page")
        sigmas = (0.0, 1.0)
        buffers = (None, 0.5)
    else:
        n, m, d, rounds = 32, 12, 120, 400
        variants_ = ("gradient", "mvr", "page", "finite_mvr")
        sigmas = (0.0, 0.5, 1.0)
        buffers = (None, 0.5, 0.25)
    prob = make_paper_problem(setting="finite_sum", n=n, m=m, d=d)
    s_cohort = max(2, n // 4)

    print("# async runtime: buffered first-K vs full barrier "
          "(virtual wall-clock)")
    rows, ok = [], True
    for variant in variants_:
        for sigma in sigmas:
            base = None
            for frac in buffers:
                cell = run_cell(prob, variant, sigma, frac, rounds,
                                s_cohort)
                if frac is None:
                    base = cell["t_virtual"]
                speed = base / cell["t_virtual"]
                tag = "barrier" if frac is None else f"K={frac:.2f}s"
                cell.update(variant=variant, sigma=sigma, buffer=tag,
                            speedup=speed)
                rows.append(cell)
                print(f"  async,{variant},sigma={sigma},{tag},"
                      f"t_virtual={cell['t_virtual']:.1f},"
                      f"speedup={speed:.2f},gnorm={cell['gnorm']:.3e},"
                      f"s_mean={cell['s_mean']:.2f},"
                      f"util={cell['util']:.2f}")
                # acceptance: under heterogeneity, not waiting for the
                # stragglers must be faster than waiting for them
                if sigma > 0 and frac is not None:
                    ok &= speed > 1.0
    # AssertionError (not SystemExit) so benchmarks/run.py's failure
    # handling records this suite and still runs the rest
    assert ok, ("buffered first-K failed to beat the barrier under "
                "latency heterogeneity")
    print("OK: buffered-first-K beats the full barrier under "
          "heterogeneity")
    yield rows


def _run_sharded_cell(buffer: int, rounds: int, sigma: float) -> dict:
    """One barrier-vs-gang cell on the LM trainer, via the CLI in a
    subprocess (a fresh process so --smoke can set the host-mesh
    XLA_FLAGS before jax initializes), pinned to the CPU."""
    import re
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
        + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""))
    cmd = [sys.executable, "-m", "repro.launch.async_sharded_train",
           "--smoke", "--rounds", str(rounds), "--buffer", str(buffer),
           "--latency", "lognormal", "--sigma", str(sigma),
           "--variant", "mvr", "--seed", "3"]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    m = re.search(r"^RESULT (.*)$", out.stdout, re.M)
    assert m, out.stdout
    return {k: float(v) for k, v in
            (kv.split("=") for kv in m.group(1).split())}


def main_sharded(quick: bool = True):
    """Gang-scheduled cohorts vs barrier on the sharded LM trainer."""
    rounds = 10 if quick else 40
    sigma = 1.2
    print("# sharded trainer: gang-scheduled cohorts vs barrier "
          "(virtual wall-clock, lognormal sigma=%.1f)" % sigma)
    base = None
    rows = []
    for buffer in (0, 3):   # 0 = barrier
        cell = _run_sharded_cell(buffer, rounds, sigma)
        if base is None:
            base = cell["t_virtual"]
        speed = base / max(cell["t_virtual"], 1e-9)
        tag = "barrier" if buffer == 0 else f"K={buffer}"
        cell.update(buffer=tag, speedup=speed)
        rows.append(cell)
        print(f"  async-sharded,mvr,{tag},"
              f"t_virtual={cell['t_virtual']:.1f},speedup={speed:.2f},"
              f"loss={cell['loss']:.4f},s_mean={cell['s_mean']:.2f}")
    assert rows[-1]["speedup"] > 1.0, (
        "gang-scheduled cohorts failed to beat the barrier in virtual "
        "wall-clock on the heterogeneous fleet")
    print("OK: gang-scheduled cohorts beat the trainer barrier under "
          "heterogeneity")
    yield rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / fewer cells — the CI row")
    ap.add_argument("--sharded", action="store_true",
                    help="also run the LM-trainer cohort comparison")
    args = ap.parse_args()
    list(main(quick=args.smoke))
    if args.sharded:
        list(main_sharded(quick=args.smoke))
