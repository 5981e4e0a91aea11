#!/usr/bin/env python3
"""Run the DASHA-PP trainer and the paged server on a TPU chip.

    python chip_smoke.py               # one chip: trainer, then server
    python chip_smoke.py --four-chips  # four chips: the trainer only,
                                       # one DASHA-PP node per chip

Both phases run granite-3-2b (hf:ibm-granite/granite-3.0-2b-base) at
its published widths with random weights made from ``--seed``, through
the library's own entry points, with the Pallas kernels compiled for
the chip:

* trainer — a few DASHA-PP-MVR rounds of ``Trainer`` + ``ShardedDasha``
  with ``use_pallas=True`` (fused update + BlockRandK compress), then the
  same rounds with ``use_pallas=False`` under the same keys.  Depth is
  the only cut (``TRAIN_DEPTH``: 8 of 40 layers on one chip, 4 on
  four).  Loss, grad-norm and final params
  must agree to the stated tolerances; ``bits_sent`` and
  ``participants`` must agree exactly.
* server — ``PagedEngine`` at full depth (40 layers, bf16) with the
  paged-attention kernel on a batch of 8 and ``max_seq`` 2048, serving
  a few requests with chunked prefill; then the same requests through
  the jnp gather path.  Decode logits must agree to the stated
  tolerance for as long as both runs fed the same tokens.

The program of each phase must hold a Pallas kernel (``tpu_custom_call``).
The script exits nonzero, and prints no result line, when the devices
are not TPUs, when ``REPRO_PALLAS_INTERPRET`` would put the kernels in
interpret mode, or when any check fails.  Its last line is one JSON
object naming the device.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "granite-3-2b"
# Deepest granite-3-2b stack whose compiled train step (params, g, g_i,
# h_i and both gradient evaluations of a 4096-token round per node)
# leaves at least HEADROOM of each v5e chip's HBM free, by chip count.
# From memory_analysis of the step compiled for a described v5e (GiB
# per device, use_pallas on): one chip 11.43 at 8 layers, 14.22 at 12;
# four chips (FSDP params, one node per chip) 10.09 at 4, 14.40 at 6.
# Re-checked on the chip against the device's own bytes_limit.
TRAIN_DEPTH = {1: 8, 4: 4}
HEADROOM = 0.25
SEQ = 4096
ROUNDS = 4
P_A, RATIO, GAMMA = 0.5, 1 / 64, 1e-3

SERVE_BATCH, SERVE_MAX_SEQ, PAGE_SIZE, CHUNK = 8, 2048, 16, 64
N_REQUESTS, NEW_TOKENS = 8, 12

# Tolerances, pallas vs jnp (trainer) and kernel vs jnp (server).  The
# two trainer paths do the same float32 update math in a different
# order, and the state is bfloat16: a last-bit difference rounds an
# element of g one bf16 step apart, which moves that parameter by
# gamma * step(g) — many of its own bf16 steps when it is near zero.
# So params are compared at the scale of their leaf.
TOL_LOSS = 1e-3            # |delta loss|, absolute
TOL_GNORM = 1e-2           # |delta grad_norm| / grad_norm
TOL_PARAM = 2.0 ** -6      # max |delta p| / max |p| per leaf: 2 bf16 steps
# The kernel reads the bf16 pool in float32 throughout; the jnp path
# rounds scores and probabilities to bf16.  Over 40 layers the logits
# then differ at the bf16 level of the residual stream.
TOL_LOGITS = 5e-2          # max |delta logit| / max |logit|


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def tpu_devices(count: int):
    """The first ``count`` TPU devices; fails on any other platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"platform is {devs[0].platform!r}, not 'tpu' — this script "
             "measures the chip and never falls back")
    if len(devs) < count:
        fail(f"need {count} TPU devices, found {len(devs)}")
    from repro.kernels.ops import interpret_default
    if interpret_default():
        fail("REPRO_PALLAS_INTERPRET forces interpret mode; unset it")
    return devs[:count]


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def hbm_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("bytes_limit", 16 << 30))


def program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def require_kernel(compiled, what: str) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        fail(f"{what}: no tpu_custom_call in the compiled program")


# ----------------------------------------------------------------------
# trainer
# ----------------------------------------------------------------------

def train_run(cfg, mesh, batches, seed: int, use_pallas: bool):
    """ROUNDS synchronous DASHA-PP-MVR rounds -> (per-round metrics,
    final params on host, node-state leaves, timings)."""
    import jax

    from repro.core.sharded import ShardedDashaConfig
    from repro.data.sharding import place_batch
    from repro.launch.mesh import data_axes_of
    from repro.models import Model
    from repro.training.loop import round_train_key
    from repro.training.optim import paper_server
    from repro.training.trainer import Trainer, TrainerConfig

    axes = data_axes_of(mesh)
    omega = 1.0 / RATIO - 1.0
    dcfg = ShardedDashaConfig(
        gamma=GAMMA, a=P_A / (2 * omega + 1), b=P_A / (2 - P_A), p_a=P_A,
        sampler="independent", compression_ratio=RATIO, data_axes=axes,
        variant="mvr", use_pallas=use_pallas)
    trainer = Trainer(Model(cfg), mesh, TrainerConfig(
        dasha=dcfg, server=paper_server(GAMMA)))
    state = trainer.init(jax.random.key(seed))
    placed = [place_batch(b, mesh, axes) for b in batches]
    t0 = time.perf_counter()
    step = trainer.jit_train_step(batches[0]).lower(
        state, placed[0], round_train_key(seed, 0)).compile()
    compile_s = time.perf_counter() - t0
    if use_pallas:
        require_kernel(step, "trainer step")
    metrics, step_s = [], []
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        state, m = step(state, placed[r], round_train_key(seed, r))
        jax.block_until_ready((state, m))
        step_s.append(time.perf_counter() - t0)
        metrics.append({k: float(v) for k, v in m._asdict().items()})
    params = jax.device_get(state.params)
    return dict(metrics=metrics, params=params, compile_s=compile_s,
                step_s=step_s, program_bytes=program_bytes(step),
                state=state)


def check_node_spread(state, mesh) -> str:
    """Every node-state leaf (h_i, g_i) has one shard on each mesh
    device holding one node's rows, not all nodes parked on one device.
    Returns where the first leaf's shards live."""
    import jax

    devices = set(mesh.devices.flat)
    n = mesh.devices.size
    leaves = jax.tree.leaves((state.dasha.h_i, state.dasha.g_i))
    for leaf in leaves:
        shards = leaf.addressable_shards
        on = {s.device for s in shards}
        if on != devices or any(s.data.shape[0] * n != leaf.shape[0]
                                for s in shards):
            fail(f"node state {leaf.shape} is not spread one node per "
                 f"device: shards on {sorted(map(str, on))}")
    first = leaves[0]
    return ", ".join(f"{s.device}:{tuple(s.data.shape)}"
                     for s in first.addressable_shards)


def params_max_rel(pa, pb) -> float:
    """Largest per-leaf max |a - b| / max(|a|, |b|) over all params."""
    import jax
    import numpy as np

    worst = 0.0
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(float(np.max(np.abs(a))), float(np.max(np.abs(b))),
                    1e-30)
        worst = max(worst, float(np.max(np.abs(a - b))) / scale)
    return worst


def trainer_phase(devices, seed: int) -> dict:
    import jax

    from repro.data.synthetic import DataConfig, make_batch
    from repro.launch.mesh import make_device_mesh, num_nodes
    from repro.models import get_config

    full = get_config(ARCH)
    depth = TRAIN_DEPTH[len(devices)]
    cfg = full.with_overrides(num_layers=depth)
    mesh = make_device_mesh(devices)
    n = num_nodes(mesh)
    log(f"[trainer] {ARCH} widths d_model={cfg.d_model} heads={cfg.num_heads}"
        f"/{cfg.num_kv_heads} hd={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} seq={SEQ}; mesh {dict(mesh.shape)}, "
        f"{n} node(s)")
    log(f"[trainer] depth {depth} of {full.num_layers}: the deepest "
        f"stack whose step leaves >= {HEADROOM:.0%} of HBM free; the full "
        "stack needs params, g, g_i, h_i and two gradient evaluations, "
        ">= 6 buffers of ~2.5e9 params (~30 GB in bf16)")
    data = DataConfig(seq_len=SEQ, global_batch=n, num_nodes=n,
                      vocab_size=cfg.vocab_size, seed=seed)
    batches = [make_batch(cfg, data, r) for r in range(ROUNDS)]

    runs = {}
    for use_pallas in (True, False):
        tag = "pallas" if use_pallas else "jnp"
        run = train_run(cfg, mesh, batches, seed, use_pallas)
        limit = (1.0 - HEADROOM) * hbm_bytes(devices[0])
        log(f"[trainer] {tag}: compile {run['compile_s']:.3f} s, step "
            f"program {run['program_bytes']} bytes (limit {limit:.0f})")
        if run["program_bytes"] > limit:
            fail(f"trainer step needs {run['program_bytes']} bytes, more "
                 f"than {1 - HEADROOM:.0%} of HBM")
        if n > 1:
            where = check_node_spread(run["state"], mesh)
            log(f"[trainer] {tag}: node state one node per device: "
                f"{where}")
        for r, (m, s) in enumerate(zip(run["metrics"], run["step_s"])):
            log(f"[trainer] {tag} round {r}: {s:.6f} s  loss={m['loss']:.6f}"
                f" grad_norm={m['grad_norm']:.6e} bits={m['bits_sent']:.0f}"
                f" participants={m['participants']:.0f}")
        del run["state"]
        runs[tag] = run
        jax.clear_caches()

    a, b = runs["pallas"]["metrics"], runs["jnp"]["metrics"]
    d_loss = max(abs(x["loss"] - y["loss"]) for x, y in zip(a, b))
    d_gn = max(abs(x["grad_norm"] - y["grad_norm"])
               / max(abs(y["grad_norm"]), 1e-30) for x, y in zip(a, b))
    d_p = params_max_rel(runs["pallas"]["params"], runs["jnp"]["params"])
    log(f"[trainer] pallas vs jnp: max |d loss| = {d_loss:.3e} "
        f"(tol {TOL_LOSS}), max rel d grad_norm = {d_gn:.3e} (tol "
        f"{TOL_GNORM}), params max |d p| / max |p| per leaf = {d_p:.3e} "
        f"(tol {TOL_PARAM:.3e})")
    for x, y in zip(a, b):
        if (x["bits_sent"], x["participants"]) != (y["bits_sent"],
                                                   y["participants"]):
            fail(f"wire accounting differs: {x} vs {y}")
    if not any(x["participants"] > 0 for x in a):
        fail("no round had a participant")
    if d_loss > TOL_LOSS or d_gn > TOL_GNORM or d_p > TOL_PARAM:
        fail("pallas and jnp trainer paths disagree beyond tolerance")
    peak = peak_bytes(devices[0])
    log(f"[trainer] peak_bytes_in_use {peak}")
    return dict(depth=depth, d_loss=d_loss, d_grad_norm=d_gn,
                d_params=d_p, peak_bytes=peak,
                compile_s=runs["pallas"]["compile_s"],
                step_s=runs["pallas"]["step_s"])


# ----------------------------------------------------------------------
# server
# ----------------------------------------------------------------------

def make_requests(cfg, seed: int):
    """Prompts of 96-112 tokens + NEW_TOKENS fit 8 pages of 16, so every
    pass uses one page-table width (one compile per pass shape)."""
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        int(rng.integers(96, 113))).tolist(),
                    max_new_tokens=NEW_TOKENS)
            for i in range(N_REQUESTS)]


def serve_run(model, params, cfg, seed: int, use_kernel: bool):
    from repro.serving import PagedEngine

    eng = PagedEngine(model, params, batch_size=SERVE_BATCH,
                      max_seq_len=SERVE_MAX_SEQ, page_size=PAGE_SIZE,
                      use_kernel=use_kernel, trace_logits=True,
                      prefill_chunk_tokens=CHUNK)
    width = -(-(112 + NEW_TOKENS) // PAGE_SIZE)
    t0 = time.perf_counter()
    for chunk in (CHUNK, 1):
        compiled = eng.lower_fused_pass(chunk, width).compile()
        if use_kernel:
            require_kernel(compiled, f"fused serve pass (C={chunk})")
    compile_s = time.perf_counter() - t0
    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    run_s = time.perf_counter() - t0
    m = eng.metrics()
    return dict(done={r.uid: list(r.generated) for r in done},
                trace={u: [x.copy() for x in t]
                       for u, t in eng.logit_trace.items()},
                compile_s=compile_s, run_s=run_s, passes=m["clock"],
                decode_steps=m["decode_steps"],
                decode_s=m["decode_seconds"],
                decode_tokens=m["decode_tokens"])


def serve_phase(device, seed: int) -> dict:
    import jax
    import numpy as np

    from repro.models import Model, get_config

    cfg = get_config(ARCH)
    model = Model(cfg)
    log(f"[server] {ARCH} full depth {cfg.num_layers} layers {cfg.dtype}; "
        f"batch {SERVE_BATCH}, max_seq {SERVE_MAX_SEQ}, page {PAGE_SIZE}, "
        f"chunked prefill {CHUNK} tokens/pass, {N_REQUESTS} requests")
    params = jax.jit(model.init_params)(jax.random.key(seed))
    runs = {}
    for use_kernel in (True, False):
        tag = "kernel" if use_kernel else "jnp"
        run = serve_run(model, params, cfg, seed, use_kernel)
        per_pass = run["decode_s"] / max(run["decode_steps"], 1)
        log(f"[server] {tag}: compile {run['compile_s']:.3f} s; "
            f"{run['passes']} passes in {run['run_s']:.6f} s; "
            f"{run['decode_steps']} pure decode passes, "
            f"{per_pass:.6f} s each")
        runs[tag] = run

    worst, compared, agree = 0.0, 0, 0
    for uid, toks in runs["jnp"]["done"].items():
        ktoks = runs["kernel"]["done"][uid]
        same = 0
        while same < len(toks) and toks[same] == ktoks[same]:
            same += 1
        agree += same
        # decode trace entry i fed generated[i]: comparable while the
        # tokens fed so far agree
        for i, (x, y) in enumerate(zip(runs["kernel"]["trace"][uid],
                                       runs["jnp"]["trace"][uid])):
            if i >= same:
                break
            scale = max(float(np.max(np.abs(y))), 1e-30)
            worst = max(worst, float(np.max(np.abs(x - y))) / scale)
            compared += 1
    total = sum(len(t) for t in runs["jnp"]["done"].values())
    log(f"[server] kernel vs jnp: max |d logit| / max |logit| = "
        f"{worst:.3e} (tol {TOL_LOGITS}) over {compared} decode passes; "
        f"greedy tokens agree on {agree} of {total}")
    if compared == 0:
        fail("no decode pass could be compared")
    if not np.isfinite(worst) or worst > TOL_LOGITS:
        fail("kernel and jnp serving paths disagree beyond tolerance")
    peak = peak_bytes(device)
    log(f"[server] peak_bytes_in_use {peak}")
    return dict(logit_rel_diff=worst, passes_compared=compared,
                peak_bytes=peak, compile_s=runs["kernel"]["compile_s"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="trainer only, one DASHA-PP node per chip on a "
                         "(4, 1) mesh, with its jnp comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    count = 4 if args.four_chips else 1
    devices = tpu_devices(count)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"devices: {count} x {devices[0].device_kind}; compile cache "
        f"{enable_compile_cache()}")

    trainer_phase(devices, args.seed)
    if not args.four_chips:
        serve_phase(devices[0], args.seed)

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
