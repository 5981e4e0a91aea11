"""Serve driver: open-loop traffic through the program's ``PagedEngine``.

Set-up makes the weights from the seed, builds the engine as the
configuration states it, runs one fused pass of every shape the cell's
traffic can ask for (each chunk width against each page-table width its
lengths reach) and one copy-on-write of a page, and then serves the
traffic's first ``lead_s`` seconds, so that the window opens on an
engine at its steady load.  The window
offers the traffic file's requests at their due times, between passes,
and serves them greedily; after it closes, the engine drains what was
due in it until the file's drain limit.  Times are on the host clock:
each served token is stamped when the pass that produced it returns.
Latency is read over every request due in the window: time to first
token from when the request was due, at its median and at the highest
percentile with ten requests beyond it, and the gaps between the
tokens of those requests, all pooled.
"""
from __future__ import annotations

import functools
import gc
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, harness, trace_reduce, traffic as gen, weights
from chipbench.reference.serve_check import ServedGaps

HOST_SPANS = ("engine.step", "serve.admit", "serve.enqueue", "serve.idle")


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def shapes(engine_cfg: Dict, traffic: Dict):
    """(chunk widths, page-table widths) the traffic can reach."""
    page = int(engine_cfg["page_size"])
    max_pages = -(-int(engine_cfg["max_seq_len"]) // page)
    lo = _pow2(-(-int(traffic["prompt"]["min"]) // page))
    hi = min(_pow2(-(-int(traffic["max_total"]) // page)), max_pages)
    widths = []
    w = lo
    while w <= hi:
        widths.append(w)
        w *= 2
    if widths[-1] != hi:
        widths.append(hi)
    return (1, int(engine_cfg["prefill_chunk_tokens"])), widths


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


TAIL_PERCENTILES = (99, 95, 90)
TPOT_PERCENTILE = 95


def tail_percentile(n: int) -> Optional[int]:
    """The highest of :data:`TAIL_PERCENTILES` with at least ten of
    ``n`` samples beyond it; None where none has."""
    for q in TAIL_PERCENTILES:
        if n * (100 - q) >= 10 * 100:
            return q
    return None


def latency(due: Dict[int, float], stamps: Dict[int, List[float]],
            finished: Set[int], gave_up: float
            ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """End-to-end latency over the requests ``due`` (uid -> due time).

    ``stamps[uid]`` are the times at which the request's served tokens
    were produced, the first token first.  A request with no first token
    reads ``gave_up`` as its first token's time.  Time to first token is
    read at its median and at :func:`tail_percentile` of the requests
    due, named by that percentile (``serve_ttft_p90_s``); the gaps
    between successive tokens of every request due are pooled, and read
    at their median and ``serve_tpot_p95_s``.  A request that did not
    finish, refused ones among them, counts in ``failed``.  Returns the
    metrics and the sample counts."""
    ttft = [(stamps[u][0] if stamps.get(u) else gave_up) - d
            for u, d in due.items()]
    gaps = [b - a for u in due for a, b in zip(stamps.get(u, ()),
                                                stamps.get(u, ())[1:])]
    e2e = {"serve_ttft_p50_s": percentile(ttft, 50) if ttft else gave_up}
    q = tail_percentile(len(ttft))
    if q is not None:
        e2e[f"serve_ttft_p{q}_s"] = percentile(ttft, q)
    e2e["serve_tpot_p50_s"] = percentile(gaps, 50) if gaps else gave_up
    e2e[f"serve_tpot_p{TPOT_PERCENTILE}_s"] = \
        percentile(gaps, TPOT_PERCENTILE) if gaps else gave_up
    n = {"requests": len(ttft), "gaps": len(gaps),
         "failed": sum(1 for u in due if u not in finished)}
    return e2e, n


def run(cell, seconds: float, seed: int, devices, t_start: float,
        trace_dir: Optional[str] = None) -> Dict:
    from repro.models import Model
    from repro.models.model import PagedDecodeState
    from repro.serving import PagedEngine, Request

    conf, tr = cell.config, cell.traffic
    m, ecfg = conf["model"], conf["engine"]
    arch = cell.arch.program_arch(m, conf["arch"])
    model = Model(arch)
    weights.check_layout(jax.eval_shape(model.init_params,
                                        jax.random.key(0)), m, cell.arch)
    make_w = jax.jit(functools.partial(weights.make, model=m,
                                       dtype=arch.param_dtype,
                                       arch=cell.arch))
    params = make_w(weights.stream(seed, "weights"))
    eng = PagedEngine(model, params, batch_size=int(ecfg["slots"]),
                      max_seq_len=int(ecfg["max_seq_len"]),
                      page_size=int(ecfg["page_size"]),
                      num_pages=int(ecfg["num_pages"]),
                      use_kernel=bool(ecfg["use_kernel"]),
                      prefill_chunk_tokens=int(ecfg["prefill_chunk_tokens"]))
    B = eng.batch
    chunks, widths = shapes(ecfg, tr)
    for c in chunks:
        for w in widths:
            eng.lower_fused_pass(c, w).compile()
            # one real pass of that shape (no valid token: nothing is
            # written) so the call path, not only the compile, is warm
            logits, st = eng._fused_fn(
                eng.params, jnp.zeros((B, c), jnp.int32),
                PagedDecodeState(caches=eng._caches,
                                 page_table=jnp.zeros((B, w), jnp.int32),
                                 seq_lens=jnp.zeros((B,), jnp.int32)),
                jnp.zeros((B,), jnp.int32))
            eng._caches = st.caches
            np.asarray(jnp.argmax(logits, axis=-1))
    # the copy-on-write of a page: the prefix cache shares a prompt's
    # first tokens whenever two prompts begin alike, which random prompts
    # sometimes do (page 0 onto itself: nothing changes)
    eng._caches = eng._copy_fn(eng._caches, 0, 0)
    jax.block_until_ready(eng._caches)
    reqs = gen.requests(tr, seconds, seed, m["vocab_size"])
    lead = float(tr.get("lead_s", 0.0))

    pending = deque(reqs)
    live: Dict[int, Request] = {}
    stamps: Dict[int, List[float]] = {}  # each served token's time
    last: Dict[int, float] = {}
    refused = set()
    late = []                          # how late each request was offered
    passes = []                        # (start, end, starts, q_lens)
    waiting = []                       # (time, requests queued or live)
    window_tokens = 0
    backlog = (0, 0)
    drain_end = seconds + float(tr["drain_s"])
    window = None                      # the window's span, once open
    phase = 0                          # 0 lead, 1 window, 2 drain
    marks = (0.0, float(seconds))      # the window's open and close
    opened_at = closed_at = None
    compiled = setup_s = None
    t0 = time.perf_counter() + lead    # the clock reads -lead here

    def advance(now):
        nonlocal phase, opened_at, closed_at, compiled, backlog, setup_s
        nonlocal window
        if phase == 0 and now >= marks[0]:
            setup_s = time.perf_counter() - t_start
            compiled = harness.compiles()
            if trace_dir:
                trace_reduce.start_trace(trace_dir)
            # made once the profiler runs: a span made before it started
            # is never recorded
            window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            window.__enter__()
            phase, opened_at = 1, now
        if phase == 1 and now >= marks[1]:
            window.__exit__(None, None, None)
            phase, closed_at = 2, now
            backlog = (len(eng.queue), sum(1 for u in live if u not in last))
            compiled = (compiled, harness.compiles() - compiled)
            if trace_dir:
                jax.profiler.stop_trace()

    try:
        while True:
            now = time.perf_counter() - t0
            advance(now)
            if now >= drain_end:
                break
            with jax.profiler.TraceAnnotation("serve.enqueue"):
                while pending and pending[0].due_s <= now:
                    q = pending.popleft()
                    late.append(now - q.due_s)
                    r = Request(uid=q.uid, prompt=q.prompt,
                                max_new_tokens=q.max_new)
                    try:
                        eng.enqueue(r)
                        live[q.uid] = r
                    except ValueError:
                        refused.add(q.uid)
            with jax.profiler.TraceAnnotation("serve.admit"):
                eng._admit_pending()
                busy = any(r is not None for r in eng.slots)
                if not busy and eng.queue:
                    if eng.prefix is not None and len(eng.prefix):
                        eng.prefix.drop_all()
                        continue
                    raise RuntimeError("admission stuck with an empty pool")
            if not busy:
                if not pending and phase == 2:
                    break
                with jax.profiler.TraceAnnotation("serve.idle"):
                    nxt = [pending[0].due_s] if pending else []
                    if phase < 2:
                        nxt.append(marks[phase])
                    wait = min(nxt) - (time.perf_counter() - t0)
                    if wait > 0:
                        time.sleep(wait)
                continue
            before = [(i, r, len(r.generated)) for i, r in
                      enumerate(eng.slots) if r is not None]
            lens0 = eng._lens.copy()
            ts = time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
            te = time.perf_counter() - t0
            lens1 = eng._lens
            starts = np.zeros(B, np.int64)
            qs = np.zeros(B, np.int64)
            made = 0
            for i, r, g0 in before:
                grown = len(r.generated) - g0
                made += grown
                starts[i] = lens0[i]
                qs[i] = (lens1[i] - lens0[i]) if eng.slots[i] is r \
                    else grown
                st = eng.stats[r.uid]
                # the pass that ends a prompt produces the first token; a
                # decode pass appends the token fed to it and produces the
                # next, unless the request is done with the one appended
                if st.first_token_at is not None and r.uid not in stamps:
                    stamps[r.uid] = [te]
                elif grown and st.finished_at is None:
                    stamps[r.uid].append(te)
                if st.finished_at is not None and r.uid not in last:
                    last[r.uid] = te
            passes.append((ts, te, starts, qs))
            if marks[0] < te <= marks[1]:
                window_tokens += made
                waiting.append((te, len(eng.queue) + sum(
                    r is not None for r in eng.slots)))
    finally:
        if phase == 1:
            window.__exit__(None, None, None)
            compiled = (compiled, harness.compiles() - compiled)
            if trace_dir:
                jax.profiler.stop_trace()
    if closed_at is None:
        raise RuntimeError("the serve loop ended before the window closed")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # end-to-end numbers over every request due in the window
    gave_up = time.perf_counter() - t0
    due = {q.uid: q.due_s for q in reqs if q.due_s >= marks[0]}
    e2e, n = latency(due, stamps, set(last), gave_up)
    e2e["serve_tokens_per_s"] = window_tokens / seconds

    finished = {u: len(live[u].generated) for u in last}
    sample = gen.check_sample(finished, tr, seed)
    rows = [(live[u].prompt, list(live[u].generated)) for u in sample]
    del eng, params
    gc.collect()

    params = make_w(weights.stream(seed, "weights"))
    gap = ServedGaps(m, cell.reference).gaps(params, rows) if rows \
        else float("inf")
    del params

    in_window = [p for p in passes
                 if p[0] >= opened_at and p[1] <= closed_at]
    tot = {"flops": 0.0, "attn_flops": 0.0, "attn_bytes": 0.0,
           "tokens": 0.0}
    kv_bytes = counts.DTYPE_BYTES[m["torch_dtype"]]
    hook = getattr(cell.arch, "serve_layer_counts", None)
    for _, _, starts, qs in in_window:
        args = (m, int(ecfg["page_size"]), kv_bytes, kv_bytes, starts, qs)
        c = cell.arch.serve_pass_counts(*args)
        if hook is not None:
            c.update(hook(*args))
        for k, v in c.items():
            tot[k] = tot.get(k, 0.0) + v
    print(f"chipbench: {len(due)} requests due in the window "
          f"({len(reqs) - len(due)} before it), "
          f"{len(due) - n['failed']} finished, "
          f"{sum(1 for u in due if u in refused)} refused; "
          f"{len(passes)} passes ({len(in_window)} in the window); "
          f"requests offered late by {percentile(late or [0.0], 50):.6f} s "
          f"at the median, {max(late, default=0.0):.6f} s at most; "
          f"checked {len(rows)} requests, "
          f"{sum(len(o) for _, o in rows)} served tokens",
          flush=True)
    tail = tail_percentile(n["requests"])
    beyond = (f"p{tail} with {n['requests'] * (100 - tail) // 100} beyond "
              f"it" if tail else "too few for a tail")
    print(f"chipbench: time to first token over {n['requests']} requests "
          f"({beyond}), gaps between tokens over {n['gaps']} gaps; "
          + ", ".join(f"{k} {v!r}" for k, v in e2e.items()), flush=True)
    return {"setup_s": setup_s, "e2e": e2e, "attempted": len(due),
            "failed": n["failed"], "gaps": {"served_logit_gap": gap},
            "peak_bytes": peak, "units": len(in_window),
            "layer_counts": tot, "host_spans": HOST_SPANS,
            "rows": rows, "queued_at_close": backlog[0],
            "unfinished_at_close": backlog[1], "waiting": waiting,
            "compiles": compiled}
