"""Train driver: synchronous DASHA-PP-MVR rounds of the program's
``Trainer`` on the cell's mesh, one node per chip.

Set-up builds one object, the compiled step with its state, on weights
and data made from the seed, and drives it through the first
``CHECKED_ROUNDS`` rounds with the window's own call and feed on rows
that all differ; the program's numbers of those rounds are what the
reference is compared with.  The window then runs further rounds of the
same step on the same state until ``seconds`` have passed, each round
sent before the host waits on the one before it, and ends when the last
round sent is done; a traced run profiles its first ``TRACED_ROUNDS``
rounds, and its per-layer counts are of those.
"""
from __future__ import annotations

import functools
import gc
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, counts, harness, trace_reduce, weights
from chipbench.reference import dasha as ref_dasha

CHECKED_ROUNDS = 3
# a round's trace holds some 40,000 device ops: a few rounds are enough
TRACED_ROUNDS = 4
HOST_SPANS = ("train.dispatch", "train.wait")


class _Tracer:
    """The profiler and the window span around the traced rounds (off
    where ``trace_dir`` is None)."""

    def __init__(self, trace_dir: Optional[str]):
        self.on = bool(trace_dir)
        if self.on:
            trace_reduce.start_trace(trace_dir)
            self.span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self.span.__enter__()

    def stop(self) -> None:
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False


def _round_key(base, r: int):
    return jax.random.fold_in(base, r)


def _batch(key, n: int, seqs: int, seq_len: int, vocab: int):
    return {"tokens": jax.random.randint(key, (n, seqs, seq_len), 0, vocab,
                                         jnp.int32)}


def run(cell, seconds: float, seed: int, devices, t_start: float,
        trace_dir: Optional[str] = None) -> Dict:
    m, tcfg = cell.config["model"], cell.config["trainer"]
    out, make_w, batches, n = _program(cell, seconds, seed, devices,
                                       t_start, trace_dir)
    # the program's state is gone with _program's frame
    gc.collect()
    in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                 for d in devices)
    print(f"chipbench: {in_use} device bytes in use before the reference",
          file=sys.stderr, flush=True)

    # the reference, on the same weights and rows
    rkeys = weights.stream(seed, "rounds")
    ref = ref_dasha.run_reference(
        m, tcfg, lambda: make_w(weights.stream(seed, "weights")),
        [b["tokens"] for b in batches[:CHECKED_ROUNDS]],
        [_round_key(rkeys, t) for t in range(CHECKED_ROUNDS)], n,
        ref=cell.reference)
    out["gaps"] = compare.train_gaps(out["program"], ref)
    out["reference"] = ref
    return out


def _program(cell, seconds: float, seed: int, devices, t_start: float,
             trace_dir: Optional[str]):
    """Set-up, the checked rounds and the window; returns the result
    without the comparison, the weights' maker, the rows and the node
    count."""
    from repro.core.sharded import ShardedDashaConfig
    from repro.data.sharding import place_batch
    from repro.launch.mesh import data_axes_of, make_device_mesh, num_nodes
    from repro.models import Model
    from repro.training.optim import paper_server
    from repro.training.trainer import Trainer, TrainerConfig

    conf, traffic = cell.config, cell.traffic
    m, tcfg = conf["model"], conf["trainer"]
    arch = cell.arch.program_arch(m, conf["arch"])
    mesh = make_device_mesh(devices)
    axes = data_axes_of(mesh)
    n = num_nodes(mesh)
    seq_len, seqs = int(traffic["seq_len"]), int(traffic["seqs_per_node"])
    distinct = max(int(traffic["distinct_batches"]), CHECKED_ROUNDS)
    dcfg = ShardedDashaConfig(
        gamma=float(tcfg["gamma"]), a=float(tcfg["a"]), b=float(tcfg["b"]),
        p_a=float(tcfg["p_a"]), sampler=tcfg["sampler"],
        compression_ratio=float(tcfg["compression_ratio"]),
        block_size=int(tcfg["block_size"]), data_axes=axes,
        variant=tcfg["variant"], use_pallas=bool(tcfg["use_pallas"]))
    model = Model(arch)
    trainer = Trainer(model, mesh, TrainerConfig(
        dasha=dcfg, server=paper_server(float(tcfg["gamma"]))))
    weights.check_layout(jax.eval_shape(model.init_params,
                                        jax.random.key(0)), m, cell.arch)

    # one state, on the benchmark's weights
    state = trainer.init(jax.random.key(0))
    shardings = jax.tree.map(lambda x: x.sharding, state.params)
    make_w = jax.jit(functools.partial(weights.make, model=m,
                                       dtype=arch.param_dtype,
                                       arch=cell.arch),
                     out_shardings=shardings)
    state = state._replace(params=make_w(weights.stream(seed, "weights")))
    data_key = weights.stream(seed, "data")
    make_b = jax.jit(functools.partial(_batch, n=n, seqs=seqs,
                                       seq_len=seq_len,
                                       vocab=m["vocab_size"]))
    batches = [make_b(jax.random.fold_in(data_key, j))
               for j in range(distinct)]
    placed = [place_batch(b, mesh, axes) for b in batches]
    rkeys = weights.stream(seed, "rounds")
    step = trainer.jit_train_step(batches[0]).lower(
        state, placed[0], _round_key(rkeys, 0)).compile()

    norms = jax.jit(ref_dasha.leaf_norms)
    server = trainer.cfg.server

    def change(params, g, opt, x0):
        delta, _ = server.update(g, opt, params)
        nxt = jax.tree.map(lambda p, d: (p.astype(jnp.float32) + d
                                         ).astype(p.dtype), params, delta)
        return ref_dasha.leaf_norms(jax.tree.map(
            lambda p, q: p.astype(jnp.float32) - q.astype(jnp.float32),
            nxt, x0))

    prog: Dict = {"loss": [], "participants": [], "bits": []}
    for r in range(CHECKED_ROUNDS):
        state, met = step(state, placed[r], _round_key(rkeys, r))
        prog["loss"].append(float(met.loss))
        prog["participants"].append(float(met.participants))
        prog["bits"].append(float(met.bits_sent))
        if r == 0:
            prog["g1"] = np.asarray(norms(state.dasha.g))
    x0 = make_w(weights.stream(seed, "weights"))
    prog["change"] = np.asarray(jax.jit(change)(
        state.params, state.dasha.g, state.opt, x0))
    del x0
    setup_s = time.perf_counter() - t_start
    compiled = harness.compiles()

    # the window; a traced run traces its first TRACED_ROUNDS rounds.
    # Round r + 1 is sent before the host waits on round r, as the
    # program's own loop does, so the device never waits on the host.
    tracer = _Tracer(trace_dir)
    rounds = traced = 0
    sent = CHECKED_ROUNDS

    def send():
        nonlocal state, sent
        with jax.profiler.TraceAnnotation("train.dispatch"):
            state, met = step(state, placed[sent % distinct],
                              _round_key(rkeys, sent))
        sent += 1
        return met

    t0 = time.perf_counter()
    try:
        in_flight = send()
        closing = False
        while True:
            nxt = None if closing else send()
            with jax.profiler.TraceAnnotation("train.wait"):
                jax.block_until_ready(in_flight)
            rounds += 1
            if tracer.on and rounds == TRACED_ROUNDS:
                tracer.stop()
                traced = rounds
            if nxt is None:
                break
            in_flight = nxt
            closing = time.perf_counter() - t0 >= seconds
        elapsed = time.perf_counter() - t0
        compiled = (compiled, harness.compiles() - compiled)
    finally:
        if tracer.on:
            tracer.stop()
            traced = rounds
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    tokens = rounds * n * seqs * seq_len
    units = traced if trace_dir else rounds
    per_round = {
        "model_flops": cell.arch.train_round_flops(m, seq_len, n * seqs),
        "dasha_bytes": counts.dasha_update_bytes(
            weights.leaf_sizes(m, cell.arch),
            float(tcfg["compression_ratio"]), int(tcfg["block_size"])),
    }
    hook = getattr(cell.arch, "train_layer_counts", None)
    if hook is not None:
        per_round.update(hook(m, seq_len, n * seqs))
    layer_counts = {k: units * v for k, v in per_round.items()}
    out = {"setup_s": setup_s,
           "e2e": {"train_tokens_per_s": tokens / elapsed},
           "attempted": rounds, "failed": 0, "peak_bytes": peak,
           "units": units, "layer_counts": layer_counts,
           "host_spans": HOST_SPANS, "program": prog,
           "compiles": compiled}
    return out, make_w, batches, n
