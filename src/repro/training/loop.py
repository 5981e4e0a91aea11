"""Training-loop driver: batches -> jit step -> metrics/checkpoints.

Resume contract: both the per-round randomness and the checkpoint
numbering derive from the GLOBAL step carried in ``state.step``, not
the loop-local iteration index — a run resumed from a restored
``TrainState`` continues the key stream where it left off instead of
replaying round 0's randomness, and its checkpoints never overwrite
the earlier run's files (tests/test_training_resume.py).
"""
from __future__ import annotations

from typing import Callable, Iterator, Optional

import jax
import numpy as np

from repro.data.sharding import place_batch
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.training.checkpoints import save_checkpoint
from repro.training.metrics import MetricsLogger
from repro.training.trainer import Trainer, TrainState


def round_train_key(seed: int, global_step: int) -> jax.Array:
    """The canonical per-round key of the LM training loops — shared by
    the sync loop below and the gang-scheduled cohort scheduler
    (repro/fl/cohorts.py), so the two runtimes consume identical
    randomness for a given global step (the trainer-scale sync-limit
    parity contract, DESIGN.md §10)."""
    return jax.random.key(seed + global_step)


def train(trainer: Trainer, state: TrainState,
          batches: Iterator[dict], num_steps: int,
          logger: Optional[MetricsLogger] = None,
          checkpoint_dir: Optional[str] = None,
          checkpoint_every: int = 0,
          log_every: int = 10,
          seed: int = 0) -> TrainState:
    logger = logger or MetricsLogger(print_every=log_every)
    first = next(batches)
    step_fn = trainer.jit_train_step(first)
    mesh = trainer.mesh
    data_axes = trainer.cfg.dasha.data_axes
    start = int(jax.device_get(state.step))

    batch = first
    # per-step device scalars, summed once at the end: publishing the
    # wire ledger must not force a host sync every step
    bits_seen = []
    parts_seen = []
    metrics = None
    for i in range(num_steps):
        gstep = start + i
        placed = place_batch(batch, mesh, data_axes)
        key = round_train_key(seed, gstep)
        # times the step's (asynchronous) dispatch, not its device work
        with obs_trace.span("train.dispatch", track="train", step=gstep):
            state, metrics = step_fn(state, placed, key)
        bits_seen.append(metrics.bits_sent)
        parts_seen.append(metrics.participants)
        if i % log_every == 0 or i == num_steps - 1:
            logger.log(gstep, loss=metrics.loss, grad_norm=metrics.grad_norm,
                       bits_sent=metrics.bits_sent,
                       participants=metrics.participants)
        if checkpoint_dir and checkpoint_every \
                and (gstep + 1) % checkpoint_every == 0:
            save_checkpoint(checkpoint_dir, state, gstep + 1)
        if i < num_steps - 1:
            batch = next(batches)
    if metrics is not None:
        reg = obs_metrics.get_registry()
        reg.gauge("train.bits_sent").set(
            float(np.sum(jax.device_get(bits_seen), dtype=np.float64)))
        # one oracle call per participating node per round
        reg.gauge("train.oracle_calls").set(
            float(np.sum(jax.device_get(parts_seen), dtype=np.float64)))
        reg.gauge("train.steps").set(float(num_steps))
        reg.gauge("train.loss").set(float(jax.device_get(metrics.loss)))
    return state
