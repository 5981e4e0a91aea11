"""Production training entrypoint:

    python -m repro.launch.train --arch granite-3-2b --shape train_4k \
        [--mesh {devices,pod,multi-pod}] [--steps N] [--smoke]

Without ``--smoke`` this runs the full config on a mesh of the devices
present (``(n, 1)`` over ``data, model``: one DASHA-PP node per
device); ``--mesh pod`` / ``multi-pod`` ask for the 16x16 / 2x16x16
production layouts by name.  ``--smoke`` (the CPU path) shrinks to the
reduced config on an 8-device host mesh — same code path end to end.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="devices",
                    choices=["devices", "pod", "multi-pod"],
                    help="devices: (n, 1) over the devices present; "
                         "pod / multi-pod: the 16x16 / 2x16x16 layouts")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--p-a", type=float, default=0.5)
    ap.add_argument("--ratio", type=float, default=1 / 64)
    ap.add_argument("--aggregation", default="sparse_allgather")
    ap.add_argument("--variant", default="mvr",
                    choices=["mvr", "gradient", "page", "finite_mvr"],
                    help="k_i rule (core/variants.py); gradient and "
                         "finite_mvr are fixed-batch finite-sum settings")
    ap.add_argument("--p-page", type=float, default=1 / 8,
                    help="page variant: full-pass probability")
    ap.add_argument("--page-mini-batch", type=int, default=1,
                    help="page variant: per-node minibatch examples")
    ap.add_argument("--component-batch", type=int, default=1,
                    help="finite_mvr variant: components (examples) "
                         "sampled per node per round")
    ap.add_argument("--use-pallas", action="store_true",
                    help="fused Pallas update path (DESIGN.md §6)")
    ap.add_argument("--server", choices=["paper", "adamw"], default="paper")
    ap.add_argument("--gamma", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log", default=None)
    from repro.obs import add_cli_flags
    add_cli_flags(ap)
    args = ap.parse_args()

    if args.smoke and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                                   + os.environ.get("XLA_FLAGS", ""))

    import jax
    from repro.compat import use_mesh
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import start_run
    from repro.core.sharded import ShardedDashaConfig
    from repro.data.synthetic import DataConfig, make_batch
    from repro.launch.mesh import (data_axes_of, make_device_mesh,
                                   make_host_mesh, make_production_mesh,
                                   num_nodes)
    from repro.models import Model, get_config, get_smoke_config
    from repro.models.registry import INPUT_SHAPES
    from repro.training.loop import train
    from repro.training.metrics import MetricsLogger
    from repro.training.optim import adamw_server, paper_server
    from repro.training.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    if args.smoke:
        mesh = make_host_mesh(data=4, model=2)
        cfg = get_smoke_config(args.arch).with_overrides(dtype="float32")
        seq, gbatch = 64, 8
    else:
        mesh = (make_device_mesh() if args.mesh == "devices" else
                make_production_mesh(multi_pod=args.mesh == "multi-pod"))
        cfg = get_config(args.arch)
        shp = INPUT_SHAPES[args.shape]
        seq, gbatch = shp.seq_len, shp.global_batch

    model = Model(cfg)
    axes = data_axes_of(mesh)
    n = num_nodes(mesh)
    omega = 1.0 / args.ratio - 1.0
    dcfg = ShardedDashaConfig(
        gamma=args.gamma,
        a=args.p_a / (2 * omega + 1),
        b=args.p_a / (2 - args.p_a),
        p_a=args.p_a, sampler="independent",
        compression_ratio=args.ratio,
        aggregation=args.aggregation, data_axes=axes,
        variant=args.variant, p_page=args.p_page,
        use_pallas=args.use_pallas)
    server = (paper_server(args.gamma) if args.server == "paper"
              else adamw_server(lr=3e-4))
    trainer = Trainer(model, mesh, TrainerConfig(
        dasha=dcfg, server=server,
        page_mini_batch=args.page_mini_batch,
        num_components=(gbatch // n if args.variant == "finite_mvr"
                        else None),
        component_batch=args.component_batch))
    state = trainer.init(jax.random.key(0))

    data = DataConfig(seq_len=seq, global_batch=gbatch, num_nodes=n,
                      vocab_size=cfg.vocab_size)

    def batches():
        # The gradient and finite_mvr variants (Algs. 2/4) are finite-
        # sum settings: each node's dataset is FIXED across rounds
        # (this is also what makes the gradient old-grad cache exact,
        # and what makes the finite_mvr h_ij trackers track anything).
        # Streaming fresh batches would break the correlated gn/go pair;
        # use mvr/page for stochastic data.
        if args.variant in ("gradient", "finite_mvr"):
            fixed = make_batch(cfg, data, 0, dtype=cfg.dtype)
            while True:
                yield fixed
        i = 0
        while True:
            yield make_batch(cfg, data, i, dtype=cfg.dtype)
            i += 1

    obsrun = start_run(trace_out=args.trace_out,
                       metrics_out=args.metrics_out,
                       meta={"cli": "train", "arch": args.arch,
                             "variant": args.variant})
    from repro.obs import profiler_trace
    with use_mesh(mesh), profiler_trace(args.profile_dir):
        train(trainer, state, batches(), num_steps=args.steps,
              logger=MetricsLogger(args.log, print_every=10),
              checkpoint_dir=args.ckpt,
              checkpoint_every=50 if args.ckpt else 0)
    obsrun.finish()


if __name__ == "__main__":
    main()
