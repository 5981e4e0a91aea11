"""Gang-scheduled async training entrypoint for the sharded LM trainer
(DESIGN.md §10):

    PYTHONPATH=src python -m repro.launch.async_sharded_train \
        --smoke --arch granite-3-2b --variant mvr --rounds 30 \
        --latency lognormal --sigma 1.0 --buffer 2 \
        [--staleness-policy power|adaptive] [--availability-rate 0.02]

Runs :class:`repro.fl.CohortScheduler` over the production
``Trainer``/``ShardedDasha`` stack: each round gang-schedules one SPMD
cohort, buffers it by virtual arrival time, and commits the first-K
cohorts with staleness weights.  ``--buffer 0`` waits for every
outstanding cohort — the barrier baseline the bench compares against.
``--smoke`` shrinks to the reduced config on an 8-device host mesh
(same code path end to end); without it the full config runs on an
``(n, 1)`` mesh of the devices present.  The final line is
machine-readable:

    RESULT t_virtual=<s> loss=<f> grad_norm=<f> commits=<n> ...
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--variant", default="mvr",
                    choices=["mvr", "gradient", "page", "finite_mvr"])
    ap.add_argument("--p-a", type=float, default=0.5)
    ap.add_argument("--ratio", type=float, default=1 / 16)
    ap.add_argument("--gamma", type=float, default=1e-3)
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--buffer", type=int, default=2,
                    help="cohort flight capacity: up to K cohorts ride "
                         "concurrently, the earliest arrival beyond that "
                         "commits; 0 (or 1) = barrier")
    ap.add_argument("--staleness-policy", default="power",
                    choices=["power", "adaptive"])
    ap.add_argument("--staleness-exponent", type=float, default=0.5)
    ap.add_argument("--max-staleness", type=int, default=None)
    ap.add_argument("--latency", default="lognormal",
                    choices=["constant", "lognormal"])
    ap.add_argument("--sigma", type=float, default=1.0,
                    help="lognormal jitter + persistent fleet spread")
    ap.add_argument("--bandwidth", type=float, default=1e6,
                    help="uplink bits/s (0 = instant network)")
    ap.add_argument("--availability-rate", type=float, default=0.0,
                    help="Poisson outage rate per client per virtual "
                         "second (0 = always available)")
    ap.add_argument("--availability-off-mean", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log", default=None)
    from repro.obs import add_cli_flags
    add_cli_flags(ap)
    args = ap.parse_args()

    if args.smoke and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                                   + os.environ.get("XLA_FLAGS", ""))

    import jax
    import numpy as np
    from repro.compat import use_mesh
    from repro.core.sharded import ShardedDashaConfig
    from repro.data.synthetic import DataConfig, make_batch
    from repro.fl import (CohortConfig, PoissonAvailability, make_latency,
                          train_async)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import (data_axes_of, make_host_mesh,
                                   make_device_mesh, num_nodes)
    from repro.models import Model, get_config, get_smoke_config
    from repro.models.registry import INPUT_SHAPES
    from repro.obs import start_run
    from repro.training.metrics import MetricsLogger
    from repro.training.optim import paper_server
    from repro.training.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    if args.smoke:
        mesh = make_host_mesh(data=4, model=2)
        cfg = get_smoke_config(args.arch).with_overrides(dtype="float32")
        seq, gbatch = 64, 8
    else:
        mesh = make_device_mesh()
        cfg = get_config(args.arch)
        shp = INPUT_SHAPES["train_4k"]
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
        compression_ratio=args.ratio, data_axes=axes,
        variant=args.variant, use_pallas=args.use_pallas)
    trainer = Trainer(model, mesh, TrainerConfig(
        dasha=dcfg, server=paper_server(args.gamma),
        num_components=(gbatch // n if args.variant == "finite_mvr"
                        else None)))
    state = trainer.init(jax.random.key(0))

    data = DataConfig(seq_len=seq, global_batch=gbatch, num_nodes=n,
                      vocab_size=cfg.vocab_size)

    def batches():
        if args.variant in ("gradient", "finite_mvr"):
            fixed = make_batch(cfg, data, 0, dtype=cfg.dtype)
            while True:
                yield fixed
        i = 0
        while True:
            yield make_batch(cfg, data, i, dtype=cfg.dtype)
            i += 1

    lat_kw = dict(bandwidth_bps=args.bandwidth or None, seed=args.seed)
    if args.latency == "lognormal":
        lat_kw.update(sigma=args.sigma, client_sigma=args.sigma)
    latency = make_latency(args.latency, **lat_kw)
    avail = None
    if args.availability_rate > 0:
        avail = PoissonAvailability(rate=args.availability_rate,
                                    off_mean=args.availability_off_mean,
                                    seed=args.seed)
    ccfg = CohortConfig(buffer_cohorts=args.buffer or None,
                        staleness_policy=args.staleness_policy,
                        staleness_exponent=args.staleness_exponent,
                        max_staleness=args.max_staleness,
                        seed=args.seed)

    obsrun = start_run(trace_out=args.trace_out,
                       metrics_out=args.metrics_out,
                       meta={"cli": "async_sharded_train",
                             "arch": args.arch})
    logger = MetricsLogger(args.log, name="async_sharded_train",
                           print_every=max(1, args.rounds // 10))
    from repro.obs import profiler_trace
    with use_mesh(mesh), profiler_trace(args.profile_dir):
        state, res = train_async(trainer, state, batches(), args.rounds,
                                 latency, config=ccfg, availability=avail,
                                 logger=logger,
                                 log_every=max(1, args.rounds // 10))
    logger.close()
    print(f"\nstaleness hist = {res.staleness_hist}  "
          f"skipped busy/offline = {int(res.skipped_busy.sum())}/"
          f"{int(res.skipped_offline.sum())}  "
          f"discarded = {res.discarded_stale}")
    print(f"RESULT t_virtual={res.total_time:.3f} "
          f"loss={res.loss[-1]:.6f} "
          f"grad_norm={res.grad_norm[-1]:.6f} "
          f"commits={int(res.committed.sum())} "
          f"clients={int(res.committed_clients.sum())} "
          f"mbits={res.bits_cum[-1] / 1e6:.3f} "
          f"s_mean={float(np.sum(res.staleness_mean * res.committed) / max(1, res.committed.sum())):.3f}")
    obsrun.finish()


if __name__ == "__main__":
    main()
