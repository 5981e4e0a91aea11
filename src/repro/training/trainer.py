"""The production train step: Model x ShardedDasha x ServerOptimizer.

Step order is Algorithm 1, faithfully:

    1. x^{t+1} = x^t + server_update(g^t)        (paper: -gamma g^t)
    2. per-node stochastic grads at x^{t+1} AND x^t — what is evaluated
       depends on the variant (core/variants.py):
         * ``mvr``      — the same minibatch at both points (Alg. 5 pair)
         * ``gradient`` — the (fixed-batch) local gradient pair; the
           old-point gradient is deterministic, so it is CACHED from the
           previous round instead of re-evaluated (one vjp per step
           instead of two — exactness requires node batches fixed
           across rounds, the Alg. 2 full-gradient setting)
         * ``page``     — the shared Alg. 3 coin picks EITHER a full
           pass over the whole node batch OR a minibatch pass over the
           first ``page_mini_batch`` examples (two batch-shape paths in
           one step; ``lax.cond`` executes only the taken branch, so
           full-pass compute is paid only with probability p_page)
         * ``finite_mvr`` — each node's FIXED batch examples are the m
           finite-sum components: per round, ``component_batch`` of
           them are sampled without replacement (the engine's canonical
           ``k_oracle``), per-example gradients (n, B, *param) are
           evaluated at both points, and the engine carries the
           (n, m, *param) component trackers ``h_ij`` in its state
           (``TrainerConfig.num_components`` sizes them)
    3. node update: h_i, g_i, compressed messages m_i, aggregation -> g^{t+1}

The whole step is one jit-able function; the dry-run lowers it with
ShapeDtypeStructs for every (arch x input-shape x mesh) combination.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import variants
from repro.core.problems import sample_batch_indices
from repro.core.sharded import (ShardedDasha, ShardedDashaConfig,
                                ShardedDashaState, ShardedDispatch,
                                component_spec, estimator_spec, node_spec,
                                per_node_value_and_grads)
from repro.data.sharding import batch_specs
from repro.models.common import param_specs_like
from repro.models.model import Model
from repro.obs.trace import phase_scope
from repro.training.optim import ServerOptimizer

Array = jax.Array
PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    dasha: ShardedDashaState
    opt: Any
    step: Array
    # gradient-variant eval reuse: (losses (n,), per-node grads) at the
    # CURRENT params — next round's old-point pair.  () when disabled.
    cache: Any = ()


def _tree_norm(tree: PyTree) -> Array:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)))


class TrainMetrics(NamedTuple):
    loss: Array
    loss_old: Array
    grad_norm: Array      # ||g^{t+1}|| of the server estimator
    step: Array
    bits_sent: Array      # uplink bits this round, all nodes (engine-measured)
    participants: Array   # |S^t| this round


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    dasha: ShardedDashaConfig
    server: ServerOptimizer
    zero_init_variates: bool = True   # init_zero vs grads-at-x0 init
    fsdp: bool = True                 # shard params over the data axis too
    # page variant: per-node examples of the minibatch branch (the full
    # branch uses the whole node batch).
    page_mini_batch: int = 1
    # gradient variant: cache the old-point per-node gradients from the
    # previous round (None = auto: on iff variant == "gradient").
    # EXACT only when each node's batch is FIXED across rounds — which
    # is what the Alg. 2 deterministic-gradient setting means (the k_i
    # pair must be two evaluations of the same f_i).  Feed a constant
    # batch per node (launch/train.py does) or set this to False when
    # streaming data through the gradient variant anyway.
    cache_old_grads: Optional[bool] = None
    # finite_mvr variant (also a fixed-batch finite-sum setting):
    # m = examples per node in every batch (sizes the h_ij trackers)
    # and B = components sampled per round (without replacement).
    num_components: Optional[int] = None
    component_batch: int = 1


class Trainer:
    def __init__(self, model: Model, mesh: Mesh, cfg: TrainerConfig):
        rule = variants.get_rule(cfg.dasha.variant)
        if not rule.trainer_supported:
            raise ValueError(
                f"variant {cfg.dasha.variant!r} ({rule.algorithm}) is "
                "not supported by the LM trainer (DESIGN.md §8)")
        if rule.component_trackers:
            if cfg.num_components is None:
                raise ValueError(
                    "finite_mvr needs TrainerConfig.num_components "
                    "(= examples per node in every batch) to size the "
                    "h_ij component trackers")
            if not (1 <= cfg.component_batch <= cfg.num_components):
                raise ValueError(
                    f"need 1 <= component_batch <= num_components, got "
                    f"{cfg.component_batch} / {cfg.num_components}")
        self.model = model
        self.mesh = mesh
        self.cfg = cfg
        self.rule = rule
        self.cache_old = (cfg.cache_old_grads
                          if cfg.cache_old_grads is not None
                          else cfg.dasha.variant == "gradient")
        params_shape = jax.eval_shape(model.init_params, jax.random.key(0))
        self.param_specs = param_specs_like(
            params_shape, mesh, fsdp_axis="data" if cfg.fsdp else None)
        self.engine = ShardedDasha(mesh, self.param_specs, cfg.dasha)

    # ---- specs (for dry-run in_shardings) ------------------------------
    def state_specs(self) -> TrainState:
        ps = self.param_specs
        axes = self.cfg.dasha.data_axes
        lead = axes[0] if len(axes) == 1 else tuple(axes)
        nspec = jax.tree.map(
            lambda s: node_spec(s, axes), ps,
            is_leaf=lambda x: isinstance(x, P))
        espec = jax.tree.map(
            lambda s: estimator_spec(s, axes), ps,
            is_leaf=lambda x: isinstance(x, P))
        hij_spec = None
        if self.rule.component_trackers:
            hij_spec = jax.tree.map(
                lambda s: component_spec(s, axes), ps,
                is_leaf=lambda x: isinstance(x, P))
        params_shape = jax.eval_shape(self.model.init_params,
                                      jax.random.key(0))
        opt_state_shape = jax.eval_shape(self.cfg.server.init, params_shape)
        opt_spec = jax.tree.map(lambda _: P(), opt_state_shape)
        # mu/nu of adamw mirror params
        if hasattr(opt_state_shape, "mu"):
            opt_spec = type(opt_state_shape)(count=P(), mu=ps, nu=ps)
        cache_spec = (P(lead), nspec) if self.cache_old else ()
        return TrainState(
            params=ps,
            dasha=ShardedDashaState(g=espec, g_i=nspec, h_i=nspec,
                                    step=P(), h_ij=hij_spec),
            opt=opt_spec,
            step=P(),
            cache=cache_spec)

    def state_shapes(self, batch_shapes: PyTree) -> TrainState:
        del batch_shapes
        return jax.eval_shape(self._init_abstract, jax.random.key(0))

    def _init_abstract(self, key: Array) -> TrainState:
        params = self.model.init_params(key)
        dasha = self.engine.init_zero(
            params, num_components=self.cfg.num_components)
        opt = self.cfg.server.init(params)
        cache = ()
        if self.cache_old:
            n = self.engine.n_nodes
            cache = (jnp.zeros((n,), jnp.float32),
                     jax.tree.map(
                         lambda p: jnp.zeros((n,) + p.shape, p.dtype),
                         params))
        return TrainState(params=params, dasha=dasha, opt=opt,
                          step=jnp.zeros((), jnp.int32), cache=cache)

    # ---- init -----------------------------------------------------------
    def init(self, key: Array) -> TrainState:
        specs = self.state_specs()
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(self._init_abstract,
                       out_shardings=shardings)(key)

    # ---- the step --------------------------------------------------------
    def _advance_and_grads(self, state: TrainState, batch: PyTree,
                           key: Array):
        """Phases (1)-(2) of the step — the server update of the params
        with g^t plus the variant's per-node gradient oracles — shared
        verbatim between the sync :meth:`train_step` and the async
        :meth:`dispatch_step` (DESIGN.md §10)."""
        params_new, opt_new = self._server_step(state)
        return (params_new, opt_new) + self._grad_pair(state, batch, key,
                                                       params_new)

    @phase_scope("server_step")
    def _server_step(self, state: TrainState):
        """(1) server step with g^t, cast back to the params' dtype."""
        delta, opt_new = self.cfg.server.update(state.dasha.g, state.opt,
                                                state.params)
        params_new = jax.tree.map(
            lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
            state.params, delta)
        return params_new, opt_new

    @phase_scope("grad_pair")
    def _grad_pair(self, state: TrainState, batch: PyTree, key: Array,
                   params_new: PyTree):
        """(2) the variant's per-node gradient oracles at x^{t+1}
        (``params_new``) and x^t."""
        model, eng, cfg = self.model, self.engine, self.cfg

        def node_loss(p, node_batch):
            return model.loss(p, node_batch)

        node_kwargs: Dict[str, Any] = {}
        cache_new = state.cache
        if self.rule.needs_minibatch:        # page: two batch-shape paths
            mini = jax.tree.map(lambda x: x[:, :cfg.page_mini_batch], batch)
            # Same coin derivation as the engine consumes inside
            # node_update (core/variants.py round-key contract), so the
            # branch we evaluate is the branch the kernel selects.
            _, k_oracle, _ = variants.round_keys(key, state.dasha.step)
            coin = variants.page_coin(variants.page_keys(k_oracle)[0],
                                      cfg.dasha.p_page)

            def full_pass(_):
                ln, gn = per_node_value_and_grads(node_loss, params_new,
                                                  batch)
                lo, go = per_node_value_and_grads(node_loss, state.params,
                                                  batch)
                z = jax.tree.map(jnp.zeros_like, gn)
                return ln, lo, gn, go, z, z

            def mini_pass(_):
                ln, bn = per_node_value_and_grads(node_loss, params_new,
                                                  mini)
                lo, bo = per_node_value_and_grads(node_loss, state.params,
                                                  mini)
                z = jax.tree.map(jnp.zeros_like, bn)
                return ln, lo, z, z, bn, bo

            # Only the taken branch runs: the full pass is paid with
            # probability p_page (the unused pair enters the kernel as
            # zeros and is discarded by the coin select).
            (losses_new, losses_old, g_new, g_old, b_new,
             b_old) = jax.lax.cond(coin, full_pass, mini_pass, None)
            node_kwargs = dict(mini_new=b_new, mini_old=b_old)
        elif self.rule.component_trackers:   # finite_mvr: per-example pair
            n, m_comp, B = (eng.n_nodes, cfg.num_components,
                            cfg.component_batch)
            # Alg. 4 randomness: the engine's canonical k_oracle draws
            # the without-replacement component indices (same derivation
            # node_update consumes for its own bookkeeping).
            _, k_oracle, _ = variants.round_keys(key, state.dasha.step)
            idx = sample_batch_indices(k_oracle, n, m_comp, B,
                                       replace=False)
            sel = jax.tree.map(
                lambda x: jnp.take_along_axis(
                    x, idx.reshape(idx.shape + (1,) * (x.ndim - 2)),
                    axis=1),
                batch)

            def comp_loss(p, example):
                # one example, re-batched to size 1 for the model loss
                return model.loss(
                    p, jax.tree.map(lambda v: v[None], example))

            vg = jax.vmap(jax.vmap(jax.value_and_grad(comp_loss),
                                   in_axes=(None, 0)),
                          in_axes=(None, 0))
            losses_new_c, g_new = vg(params_new, sel)   # (n, B, *param)
            losses_old_c, g_old = vg(state.params, sel)
            losses_new = jnp.mean(losses_new_c, axis=1)
            losses_old = jnp.mean(losses_old_c, axis=1)
            node_kwargs = dict(component_idx=idx)
        elif self.cache_old:                 # gradient: reuse old grads
            losses_new, g_new = per_node_value_and_grads(
                node_loss, params_new, batch)

            def fresh(_):
                return per_node_value_and_grads(node_loss, state.params,
                                                batch)

            losses_old, g_old = jax.lax.cond(
                state.step == 0, fresh, lambda _: state.cache, None)
            cache_new = (losses_new, g_new)
        else:                                # mvr: same-sample pair
            losses_new, g_new = per_node_value_and_grads(
                node_loss, params_new, batch)
            losses_old, g_old = per_node_value_and_grads(
                node_loss, state.params, batch)

        return cache_new, losses_new, losses_old, g_new, g_old, node_kwargs

    def train_step(self, state: TrainState, batch: PyTree, key: Array
                   ) -> Tuple[TrainState, TrainMetrics]:
        (params_new, opt_new, cache_new, losses_new, losses_old,
         g_new, g_old, node_kwargs) = self._advance_and_grads(
            state, batch, key)

        # (3) DASHA-PP node/aggregation update
        # repro: ignore[prng-reuse] -- deliberate: the engine derives
        # its own (k_part, k_oracle, k_comp) streams from the round key
        # via variants.round_keys, domain-separated from the oracle
        # draws _advance_and_grads consumed
        dasha_new, wire = self.engine.node_update(
            g_new, g_old, state.dasha, key, **node_kwargs)

        gn = _tree_norm(dasha_new.g)
        metrics = TrainMetrics(loss=jnp.mean(losses_new),
                               loss_old=jnp.mean(losses_old),
                               grad_norm=gn,
                               step=state.step,
                               bits_sent=wire.bits_sent,
                               participants=wire.participants)
        return TrainState(params=params_new, dasha=dasha_new, opt=opt_new,
                          step=state.step + 1, cache=cache_new), metrics

    def dispatch_step(self, state: TrainState, batch: PyTree, key: Array,
                      participation_mask: Array
                      ) -> Tuple[TrainState, ShardedDispatch, TrainMetrics]:
        """One gang-scheduled round (DESIGN.md §10): the server update
        of the params with the CURRENT g plus the cohort's client-side
        work (:meth:`ShardedDasha.dispatch` over the mesh), WITHOUT
        applying the cohort's contribution — the scheduler buffers the
        returned :class:`ShardedDispatch` by virtual arrival time and
        commits it later through :meth:`commit_step`.

        ``participation_mask`` is the (n,) cohort the scheduler settled
        on (``sampled & idle & available``); the engine's round counter
        advances here so the key stream stays aligned with the sync
        path.  ``metrics.grad_norm`` reports ‖g^t‖ — the estimator this
        dispatch consumed (commits change g between rounds)."""
        (params_new, opt_new, cache_new, losses_new, losses_old,
         g_new, g_old, node_kwargs) = self._advance_and_grads(
            state, batch, key)

        # repro: ignore[prng-reuse] -- deliberate: same round_keys
        # domain separation as node_update above; the dispatch's
        # internal draw must match the scheduler's mask preview
        disp, wire = self.engine.dispatch(
            g_new, g_old, state.dasha, key,
            participation_mask=participation_mask, **node_kwargs)

        metrics = TrainMetrics(loss=jnp.mean(losses_new),
                               loss_old=jnp.mean(losses_old),
                               grad_norm=_tree_norm(state.dasha.g),
                               step=state.step,
                               bits_sent=wire.bits_sent,
                               participants=wire.participants)
        dasha_new = state.dasha._replace(step=state.dasha.step + 1)
        new_state = TrainState(params=params_new, dasha=dasha_new,
                               opt=opt_new, step=state.step + 1,
                               cache=cache_new)
        return new_state, disp, metrics

    def commit_step(self, state: TrainState, disp: ShardedDispatch,
                    weight: Array) -> TrainState:
        """Apply one buffered cohort with staleness weight ``w(s)``
        (:meth:`ShardedDasha.commit`)."""
        return state._replace(
            dasha=self.engine.commit(state.dasha, disp, weight))

    def jit_train_step(self, batch_example: PyTree):
        """jit with explicit shardings (used by train loop and dry-run)."""
        specs = self.state_specs()
        bspecs = batch_specs(batch_example, self.cfg.dasha.data_axes)
        to_shard = lambda tree: jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(
            self.train_step,
            in_shardings=(to_shard(specs), to_shard(bspecs), None),
            out_shardings=(to_shard(specs), None),
            donate_argnums=(0,),
        )

    # ---- the async (gang-scheduled) halves -------------------------------
    def dispatch_specs(self) -> ShardedDispatch:
        """PartitionSpecs of one cohort's :class:`ShardedDispatch`."""
        ps = self.param_specs
        axes = self.cfg.dasha.data_axes
        lead = axes[0] if len(axes) == 1 else tuple(axes)
        nspec = jax.tree.map(lambda s: node_spec(s, axes), ps,
                             is_leaf=lambda x: isinstance(x, P))
        espec = jax.tree.map(lambda s: estimator_spec(s, axes), ps,
                             is_leaf=lambda x: isinstance(x, P))
        hij_spec = None
        if self.rule.component_trackers:
            hij_spec = jax.tree.map(lambda s: component_spec(s, axes), ps,
                                    is_leaf=lambda x: isinstance(x, P))
        return ShardedDispatch(h_new=nspec, g_i_inc=nspec, g_delta=espec,
                               h_ij_new=hij_spec, part=P(lead))

    def jit_dispatch_step(self, batch_example: PyTree):
        """jit of :meth:`dispatch_step` with explicit shardings; the
        (n,) participation mask rides the data axes."""
        specs = self.state_specs()
        bspecs = batch_specs(batch_example, self.cfg.dasha.data_axes)
        axes = self.cfg.dasha.data_axes
        lead = axes[0] if len(axes) == 1 else tuple(axes)
        to_shard = lambda tree: jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(
            self.dispatch_step,
            in_shardings=(to_shard(specs), to_shard(bspecs), None,
                          NamedSharding(self.mesh, P(lead))),
            out_shardings=(to_shard(specs), to_shard(self.dispatch_specs()),
                           None),
        )

    def jit_commit_step(self):
        """jit of :meth:`commit_step`; the weight is a traced scalar so
        one compilation serves every staleness level."""
        return jax.jit(self.commit_step, donate_argnums=(0,))
