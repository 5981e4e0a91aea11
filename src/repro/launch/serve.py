"""Serving entrypoint:

    python -m repro.launch.serve --arch granite-3-2b \
        [--engine {dense,paged}] [--smoke/--no-smoke] [--batch 8] \
        [--max-seq 256] [--requests 16] [--page-size 16] [--pages N]

``--smoke`` (the default; disable with ``--no-smoke``) uses the reduced
config on a forced host platform.  ``--no-smoke`` routes through the
production path: an ``(n, 1)`` mesh of the devices present, params
initialized directly into their param_specs_like shardings, and decode
state placed via launch/specs (``decode_state_specs`` for the dense
engine, ``paged_state_specs`` for the page pool — pages replicate over
'data', heads shard over 'model').

``--engine paged`` serves through the PagedEngine (chunked/bucketed
prefill + continuous batching + preemption, DESIGN.md §11); ``dense``
keeps the ring-cache DecodeServer parity anchor.
``--prefill-chunk-tokens`` and ``--bucket-sizes`` expose the chunked-
prefill budget and the bulk-prefill prompt-length buckets.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config on the host platform (default); "
                         "--no-smoke uses the production mesh + full config")
    ap.add_argument("--engine", choices=("dense", "paged"), default="dense")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged engine: tokens per page")
    ap.add_argument("--pages", type=int, default=0,
                    help="paged engine: pool pages (0 = dense-equivalent)")
    ap.add_argument("--no-kernel", action="store_true",
                    help="paged engine: the jnp gather read instead of "
                         "the Pallas kernel (interpret mode off-TPU)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None,
                    help="paged engine: fold prompt prefill into the fused "
                         "decode pass, this many prompt tokens per pass "
                         "(0 = bulk prefill; default: auto, 16 for "
                         "attention-only archs)")
    ap.add_argument("--bucket-sizes", type=str, default=None,
                    help="paged engine: comma-separated prompt-length "
                         "buckets for bulk prefill, e.g. 8,16,32 "
                         "('' = exact-length, one compile per length; "
                         "default: auto powers of two)")
    from repro.obs import add_cli_flags
    add_cli_flags(ap)
    args = ap.parse_args()

    if args.smoke and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                                   + os.environ.get("XLA_FLAGS", ""))

    import jax
    import numpy as np
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import Model, get_config, get_smoke_config
    from repro.obs import start_run
    from repro.serving import DecodeServer, PagedEngine, Request

    enable_compile_cache()
    obsrun = start_run(trace_out=args.trace_out,
                       metrics_out=args.metrics_out,
                       meta={"cli": "serve", "engine": args.engine,
                             "arch": args.arch})

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if not cfg.supports_decode:
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    model = Model(cfg)

    if args.smoke:
        params = model.init_params(jax.random.key(0))
    else:
        # production path: params born sharded on the mesh
        from repro.launch.mesh import make_device_mesh
        from repro.launch.specs import (decode_state_specs,
                                        paged_state_specs, to_shardings)
        from repro.models import param_specs_like
        mesh = make_device_mesh()
        shapes = jax.eval_shape(model.init_params, jax.random.key(0))
        shardings = to_shardings(param_specs_like(shapes, mesh), mesh)
        params = jax.jit(model.init_params,
                         out_shardings=shardings)(jax.random.key(0))

    if args.engine == "dense":
        server = DecodeServer(model, params, batch_size=args.batch,
                              max_seq_len=args.max_seq)
    else:
        buckets = None
        if args.bucket_sizes is not None:
            buckets = [int(b) for b in args.bucket_sizes.split(",") if b]
        server = PagedEngine(model, params, batch_size=args.batch,
                             max_seq_len=args.max_seq,
                             page_size=args.page_size,
                             num_pages=args.pages or None,
                             use_kernel=not args.no_kernel,
                             prefill_chunk_tokens=args.prefill_chunk_tokens,
                             bucket_sizes=buckets)

    if not args.smoke:
        # place the decode state on the mesh; the jitted serve steps
        # keep the placement through every subsequent step
        if args.engine == "dense":
            server.place_state(to_shardings(decode_state_specs(
                server.state, mesh, num_layers=cfg.num_layers), mesh))
        else:
            server.place_caches(to_shardings(paged_state_specs(
                server._caches, mesh, num_layers=cfg.num_layers), mesh))

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size, 4).tolist(),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]
    import time

    from repro.obs import profiler_trace
    t0 = time.time()
    with profiler_trace(args.profile_dir):
        done = server.run(reqs)
    dt = time.time() - t0
    tot = sum(len(r.generated) for r in done)
    print(f"served {len(done)} requests, {tot} tokens, "
          f"{tot/dt:.1f} tok/s (engine={args.engine}, batch={args.batch}"
          + (f", kernel={not args.no_kernel})" if args.engine == "paged"
             else ")"))
    if args.engine == "paged":
        m = server.metrics()
        print(f"  prefill_forwards={m['prefill_forwards']} "
              f"decode_steps={m['decode_steps']} "
              f"pool_util={m['pool_utilization']:.2f} "
              f"cache_hbm_bytes={m['cache_hbm_bytes']}")
        if m["latency_p50"] is not None:
            print(f"  latency p50={m['latency_p50']:.0f} "
                  f"p95={m['latency_p95']:.0f} serve-passes; "
                  f"ttft p50={m['ttft_p50']:.0f} p95={m['ttft_p95']:.0f}")
    obsrun.finish()


if __name__ == "__main__":
    main()
