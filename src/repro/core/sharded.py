"""SPMD production runtime for DASHA-PP on TPU meshes.

Mapping (DESIGN.md §3, §5): one *node* of the paper = one slice of the
``data`` mesh axes (``("data",)`` single-pod, ``("pod", "data")``
multi-pod).  The parameter server is an abstraction realized by
collectives over those axes.

Pieces:

* :func:`per_node_value_and_grads` — per-node gradients (no cross-node
  mean!) via ``vmap(value_and_grad)`` over an explicit node dimension of
  the batch; runs under GSPMD so the ``model`` axis (tensor/expert
  parallelism) needs no manual collectives.
* :class:`ShardedDasha` — the Algorithm-1 node/server update as a
  ``shard_map`` over the data axes.  Per-node control variates ``h_i,
  g_i`` are param-shaped arrays with a leading node dimension sharded
  over the data axes (each device stores only its own node's variates:
  no replication).
* **All four k_i rules** (Algs. 2-5) via ``ShardedDashaConfig.variant``,
  consumed from the :mod:`repro.core.variants` registry — the same
  objects the reference engine uses, so the two engines' trajectories
  coincide for matched keys (DESIGN.md §8; asserted by
  tests/test_sharded.py).  ``gradient``/``mvr`` take one gradient pair,
  ``page`` adds a minibatch pair + the shared coin (derived in here
  from the round key), ``finite_mvr`` takes component gradients + the
  selected indices and carries ``h_ij`` component trackers in the
  state.
* Aggregation modes:
    - ``dense_psum``       — uncompressed baseline: ``psum`` of dense
      messages over the data axes (bytes ∝ d).
    - ``sparse_allgather`` — RandK/BlockRandK wire format: all-gather of
      ``(values, block indices)`` (bytes ∝ n·K ≪ n·d) + local
      scatter-add.  This is the paper's communication saving made
      visible to the roofline.
* **BlockRandK** (TPU adaptation, DESIGN.md §3): RandK at (128,)-block
  granularity — blocks partition coordinates, so choosing ``K/bs`` of
  ``D/bs`` blocks uniformly without replacement and scaling by ``D/K``
  is unbiased with exactly the Definition-1 bound ``omega = D/K - 1``
  (blocks are super-coordinates).  The draw/scatter helpers live in
  :mod:`repro.core.variants` (re-exported here for compatibility).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import participation, variants
from repro.obs.trace import phase_scope
# Re-exported: the BlockRandK wire helpers moved to the rule layer
# (core/variants.py); existing imports from this module keep working.
from repro.core.variants import (block_plan, block_randk_dense,
                                 block_randk_indices, block_randk_select,
                                 block_scatter_add)

Array = jax.Array
PyTree = Any


# ----------------------------------------------------------------------
# Per-node gradients
# ----------------------------------------------------------------------

def per_node_value_and_grads(loss_fn: Callable, params: PyTree,
                             batch: PyTree, *args) -> Tuple[Array, PyTree]:
    """``loss_fn(params, node_batch, *args) -> scalar``; ``batch`` leaves
    carry a leading node dimension.  Returns ``(losses (n,), grads)`` with
    grad leaves shaped ``(n, *param_shape)`` — the *unreduced* per-node
    gradients the DASHA-PP update consumes."""
    vg = jax.value_and_grad(loss_fn)
    in_axes = (None, 0) + tuple(None for _ in args)
    return jax.vmap(vg, in_axes=in_axes)(params, batch, *args)


# ----------------------------------------------------------------------
# Config / state
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedDashaConfig:
    gamma: float
    a: float                       # compressor momentum (Alg.1 line 11)
    b: float                       # VR momentum
    p_a: float = 1.0
    sampler: str = "independent"   # independent | s_nice | full
    compression_ratio: Optional[float] = 0.01   # K/D; None => identity
    block_size: int = 128          # BlockRandK block (TPU lane width)
    aggregation: str = "sparse_allgather"       # or dense_psum
    data_axes: Tuple[str, ...] = ("data",)
    # Which k_i rule (Algs. 2-5) the node update runs; see
    # core/variants.py.  "mvr" (same-sample pair) and "gradient" (full
    # pair) share one leaf formula — they differ in what gradients the
    # caller feeds and in accounting; "page" additionally needs the
    # minibatch pair (node_update(..., mini_new=, mini_old=)) and
    # "finite_mvr" component gradients + indices and h_ij state.
    variant: str = "mvr"
    p_page: float = 1.0            # page only: full-pass probability
    # Wire format of the sparse_allgather aggregation (DESIGN.md §8):
    #   block_randk — kb of nb (block_size,)-blocks, unbiased (default);
    #   topk        — ceil(ratio * d_local) largest coordinates (biased
    #                 baseline; coordinate-level (value, index) wire);
    #   dithering   — QSGD random dithering: dense but quantized to
    #                 ``dithering_levels`` levels (+ norm); the ratio is
    #                 ignored for the wire size but must stay non-None
    #                 to enable the compressed path.
    wire_format: str = "block_randk"
    dithering_levels: int = 4
    # Dispatch the fused Pallas update path (kernels/, DESIGN.md §6) in
    # every aggregation mode.  sparse_allgather additionally fuses
    # BlockRandK into the update: the line-11 payload is evaluated only
    # at the selected blocks, never dense in HBM.  On CPU the kernels
    # run in interpret mode automatically (kernels/ops.py).
    use_pallas: bool = False
    # Force interpret mode on/off; None = auto (interpret unless TPU).
    pallas_interpret: Optional[bool] = None

    def __post_init__(self):
        variants.get_rule(self.variant)   # raises on unknown names
        if self.wire_format not in variants.WIRE_FORMATS:
            raise ValueError(
                f"unknown wire_format {self.wire_format!r}; choose from "
                f"{sorted(variants.WIRE_FORMATS)}")
        if self.wire_format != "block_randk":
            if self.aggregation != "sparse_allgather":
                raise ValueError(
                    f"wire_format {self.wire_format!r} requires the "
                    "sparse_allgather aggregation (dense_psum moves "
                    "dense vectors regardless)")
            if self.compression_ratio is None:
                raise ValueError(
                    f"wire_format {self.wire_format!r} requires a "
                    "non-None compression_ratio — ratio None is the "
                    "dense uncompressed baseline and would silently "
                    "bypass the requested wire format")

    @property
    def compressed(self) -> bool:
        return (self.compression_ratio is not None
                and self.aggregation == "sparse_allgather")


class ShardedDashaState(NamedTuple):
    g: PyTree      # server estimator, sharded like params
    g_i: PyTree    # per-node estimators, leading node dim over data axes
    h_i: PyTree    # per-node gradient trackers, same layout
    step: Array
    # finite_mvr only: per-node per-component trackers, leaves
    # (n, m, *param_shape) sharded like g_i with an extra (m,) dim.
    h_ij: Optional[PyTree] = None


class NodeUpdateMetrics(NamedTuple):
    """Per-round wire accounting, measured inside the update (the
    reference engine's StepMetrics counterpart)."""
    participants: Array   # |S^t|, the realized participant count
    bits_sent: Array      # total uplink bits this round (all nodes)


class ShardedDispatch(NamedTuple):
    """Everything one gang-scheduled round of client work produces
    BEFORE the server applies it — the sharded counterpart of
    :class:`repro.core.dasha_pp.DispatchOutputs` (DESIGN.md §10).

    The sync :meth:`ShardedDasha.node_update` commits it immediately;
    the cohort scheduler (:mod:`repro.fl.cohorts`) buffers it by
    virtual arrival time and commits it with a staleness weight.  All
    leaves are float32 (the update's internal precision), so a
    deferred commit loses nothing to an intermediate cast."""
    h_new: PyTree          # (n, *shape) tracker rows after the update
    g_i_inc: PyTree        # (n, *shape) masked uplink increments m_i
    g_delta: PyTree        # (*shape,)  server-estimator increment
    h_ij_new: Optional[PyTree]   # (n, m, *shape) component trackers
    part: Array            # (n,) float32 realized participation mask


def _num_nodes(mesh: Mesh, data_axes: Sequence[str]) -> int:
    return int(math.prod(mesh.shape[a] for a in data_axes))


def node_spec(param_spec: P, data_axes: Sequence[str]) -> P:
    """Spec for a per-node array: prepend the (tuple of) node axes and
    strip them from the param dims (a per-node array cannot FSDP over the
    axis that indexes nodes)."""
    lead = data_axes[0] if len(data_axes) == 1 else tuple(data_axes)

    def strip(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return None if entry in data_axes else entry
        kept = tuple(a for a in entry if a not in data_axes)
        return kept if kept else None

    return P(lead, *(strip(e) for e in param_spec))


def component_spec(param_spec: P, data_axes: Sequence[str]) -> P:
    """Spec for a per-node, per-component array (n, B|m, *param_shape):
    like :func:`node_spec` with an unsharded component dim inserted."""
    ns = node_spec(param_spec, data_axes)
    return P(ns[0], None, *tuple(ns)[1:])


def estimator_spec(param_spec: P, data_axes: Sequence[str]) -> P:
    """Spec for the server estimator g: like params but never sharded over
    the node axes (every node must see the full (model-sharded) g)."""

    def strip(entry):
        if entry is None:
            return None
        if isinstance(entry, str):
            return None if entry in data_axes else entry
        kept = tuple(a for a in entry if a not in data_axes)
        return kept if kept else None

    return P(*(strip(e) for e in param_spec))


# ----------------------------------------------------------------------
# The sharded DASHA-PP engine
# ----------------------------------------------------------------------

class ShardedDasha:
    """Algorithm 1 over a mesh.  Usage::

        engine = ShardedDasha(mesh, param_specs, cfg)
        state  = engine.init(grads_like)       # under jit, sharded
        params_new = engine.server_step(params, state)   # x - gamma g
        state, wire = engine.node_update(gn, go, state, key)  # lines 7-19

    Variant-specific extra inputs to :meth:`node_update`:

    * ``page``: ``mini_new=/mini_old=`` — the same-sample minibatch
      gradient pair (``gn/go`` are the full-pass pair; the shared coin
      is derived in here from the round key).
    * ``finite_mvr``: ``gn/go`` are component gradients
      ``(n, B, *shape)`` and ``component_idx`` the ``(n, B)`` selected
      indices; ``state.h_ij`` must be initialized (``init(...,
      h_ij0=...)``).
    """

    def __init__(self, mesh: Mesh, param_specs: PyTree,
                 cfg: ShardedDashaConfig):
        self.mesh = mesh
        self.param_specs = param_specs
        self.cfg = cfg
        self.rule = variants.get_rule(cfg.variant)
        self.n_nodes = _num_nodes(mesh, cfg.data_axes)

    # -- state ----------------------------------------------------------
    def init(self, grads0: PyTree,
             h_ij0: Optional[PyTree] = None) -> ShardedDashaState:
        """Paper line 2 / Theorem 2: g_i^0 = h_i^0 = ∇f_i(x^0); the server
        holds g^0 = mean_i g_i^0.  ``grads0`` = per-node grads (n, *shape).
        ``finite_mvr`` additionally takes the component trackers
        ``h_ij0`` with leaves (n, m, *shape)."""
        if self.rule.component_trackers and h_ij0 is None:
            raise ValueError(
                f"variant {self.cfg.variant!r} needs component trackers: "
                "pass h_ij0 with leaves (n, m, *param_shape)")
        g0 = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads0)
        return ShardedDashaState(
            g=g0, g_i=grads0, h_i=grads0,
            step=jnp.zeros((), jnp.int32), h_ij=h_ij0)

    def init_zero(self, params: PyTree,
                  num_components: Optional[int] = None
                  ) -> ShardedDashaState:
        """Zero-initialized variant (g_i^0 = h_i^0 = 0) — admissible for
        MVR (Theorem 4 allows any h^0; adds a transient O(||∇f(x^0)||²/bT)
        term).  Cheaper when an extra init pass is undesirable.
        ``finite_mvr`` additionally zero-inits the (n, m, *shape)
        component trackers; pass ``num_components`` = m."""
        zeros_node = jax.tree.map(
            lambda p: jnp.zeros((self.n_nodes,) + p.shape, p.dtype), params)
        zeros = jax.tree.map(lambda p: jnp.zeros_like(p), params)
        h_ij = None
        if self.rule.component_trackers:
            if num_components is None:
                raise ValueError(
                    f"variant {self.cfg.variant!r} needs num_components "
                    "(= m) to size the h_ij trackers")
            h_ij = jax.tree.map(
                lambda p: jnp.zeros(
                    (self.n_nodes, num_components) + p.shape, p.dtype),
                params)
        return ShardedDashaState(g=zeros, g_i=zeros_node, h_i=zeros_node,
                                 step=jnp.zeros((), jnp.int32), h_ij=h_ij)

    # -- server ----------------------------------------------------------
    def server_step(self, params: PyTree, state: ShardedDashaState) -> PyTree:
        """Line 5: x^{t+1} = x^t - gamma * g^t (g is replicated over data)."""
        return jax.tree.map(
            lambda p, g: (p - self.cfg.gamma * g.astype(p.dtype)),
            params, state.g)

    # -- wire size of one node's message -----------------------------------
    def _leaf_model_shards(self, spec: P) -> int:
        """Number of distinct shards one node's copy of a leaf is split
        into over the non-data mesh axes (replicated leaves: 1)."""
        axes = set()
        for entry in spec:
            if entry is None:
                continue
            for a in ((entry,) if isinstance(entry, str) else entry):
                if a not in self.cfg.data_axes:
                    axes.add(a)
        return int(math.prod(self.mesh.shape[a] for a in axes))

    def _per_node_message_bits(self, h_i: PyTree) -> float:
        """Uplink bits one participating node pays per round: compression
        is applied per local shard, so each leaf contributes
        (#model shards) x message_bits(local size).  Computed statically
        from the specs — counting inside the shard_map would tally
        model-replicated leaves once per model shard."""
        cfg, total = self.cfg, 0.0
        spec_leaves = jax.tree.leaves(self.param_specs,
                                      is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(jax.tree.leaves(h_i), spec_leaves):
            d_leaf = int(math.prod(leaf.shape[1:]))
            shards = self._leaf_model_shards(spec)
            total += shards * variants.message_bits(
                max(1, d_leaf // shards), aggregation=cfg.aggregation,
                compression_ratio=cfg.compression_ratio,
                block_size=cfg.block_size,
                wire_format=cfg.wire_format,
                dithering_levels=cfg.dithering_levels)
        return total

    # -- participation ----------------------------------------------------
    def _participates(self, key: Array, node_idx: Array) -> Array:
        """Node-local view of the participation mask — delegates to the
        shared draw in core/participation.py so the mask coincides with
        the reference samplers for a matched key."""
        return participation.participates(self.cfg.sampler, key, node_idx,
                                          self.n_nodes, self.cfg.p_a)

    # -- host-side view of the round's participation draw ------------------
    def participation_mask(self, key: Array, step) -> Array:
        """The (n,) participation mask :meth:`dispatch` would draw
        internally for ``(key, step)`` — the same
        ``round_keys``/``participates`` derivation, vmapped over nodes,
        so a host-side scheduler can intersect it with its own
        idle/availability state and pass the result back as
        ``participation_mask=`` without perturbing the randomness
        contract (sync limit: external mask == internal draw)."""
        k_part, _, _ = variants.round_keys(key, jnp.asarray(step))
        return jax.vmap(
            lambda i: participation.participates(
                self.cfg.sampler, k_part, i, self.n_nodes, self.cfg.p_a)
        )(jnp.arange(self.n_nodes))

    # -- node + aggregation ------------------------------------------------
    @phase_scope("dasha_dispatch")
    def dispatch(self, grads_new: PyTree, grads_old: PyTree,
                 state: ShardedDashaState, key: Array, *,
                 mini_new: Optional[PyTree] = None,
                 mini_old: Optional[PyTree] = None,
                 component_idx: Optional[Array] = None,
                 participation_mask: Optional[Array] = None,
                 ) -> Tuple[ShardedDispatch, NodeUpdateMetrics]:
        """Lines 7-11 of Algorithm 1 as a shard_map over the data axes:
        all client-side work of one round WITHOUT applying it to the
        server estimators (the sharded analog of
        :meth:`repro.core.dasha_pp.DashaPP.dispatch`).

        ``grads_new/old`` leaves: (n_nodes, *param_shape) per-node
        gradients at x^{t+1} and x^t — full pair (``gradient``),
        same-sample minibatch pair (``mvr``), full pair + ``mini_new/
        mini_old`` minibatch pair (``page``), or component gradients
        (n, B, *shape) + ``component_idx`` (``finite_mvr``).

        ``participation_mask`` overrides the internal sampler draw (the
        cohort scheduler passes ``sampled & idle & available``); ``None``
        draws from ``(key, state.step)`` exactly as before.

        Returns ``(ShardedDispatch, NodeUpdateMetrics)``.
        """
        cfg, rule = self.cfg, self.rule
        if rule.needs_minibatch and (mini_new is None or mini_old is None):
            raise ValueError(f"variant {cfg.variant!r} needs the "
                             "mini_new=/mini_old= minibatch gradient pair")
        if rule.component_trackers:
            if component_idx is None:
                raise ValueError(f"variant {cfg.variant!r} needs "
                                 "component_idx (n, B)")
            if state.h_ij is None:
                raise ValueError("state.h_ij is None — initialize with "
                                 "init(grads0, h_ij0=...)")
        data_axes = cfg.data_axes
        lead = data_axes[0] if len(data_axes) == 1 else tuple(data_axes)
        pa = cfg.p_a

        node_specs = jax.tree.map(lambda s: node_spec(s, data_axes),
                                  self.param_specs,
                                  is_leaf=lambda x: isinstance(x, P))
        est_specs = jax.tree.map(lambda s: estimator_spec(s, data_axes),
                                 self.param_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        comp_specs = jax.tree.map(lambda s: component_spec(s, data_axes),
                                  self.param_specs,
                                  is_leaf=lambda x: isinstance(x, P))

        grad_specs = comp_specs if rule.component_trackers else node_specs
        has_mask = participation_mask is not None

        operands = [grads_new, grads_old, state.h_i, state.g_i, state.g,
                    key, state.step]
        in_specs = [grad_specs, grad_specs, node_specs, node_specs,
                    est_specs, P(), P()]
        if rule.needs_minibatch:
            operands += [mini_new, mini_old]
            in_specs += [node_specs, node_specs]
        if rule.component_trackers:
            operands += [component_idx, state.h_ij]
            in_specs += [P(lead, None), comp_specs]
        if has_mask:
            operands += [participation_mask]
            in_specs += [P(lead)]

        out_specs = [node_specs, node_specs, est_specs]
        if rule.component_trackers:
            out_specs += [comp_specs]
        out_specs += [P(lead), P()]      # part mask, participants

        def update(gn, go, h_i, g_i, g, key, step, *extra):
            # Inside shard_map: leaves of gn/go/h_i/g_i are (1, *local);
            # g leaves are (*local) replicated over data axes.
            node_idx = jax.lax.axis_index(data_axes) if len(data_axes) > 1 \
                else jax.lax.axis_index(data_axes[0])
            # Shared per-round key derivation (DESIGN.md §8): identical
            # to the reference engine's, so masks/coins/compressor draws
            # coincide for matched keys.
            k_part, k_oracle, k_comp = variants.round_keys(key, step)
            if has_mask:
                part = extra[-1][0]      # local (1,) slice of the mask
            else:
                part = self._participates(k_part, node_idx)
            partf = part.astype(jnp.float32)
            coin = None
            if rule.needs_coin:
                coin = variants.page_coin(
                    variants.page_keys(k_oracle)[0],
                    cfg.p_page).astype(jnp.float32)
            b_new = b_old = idx = h_ij = None
            pos = 0
            if rule.needs_minibatch:
                b_new, b_old = extra[0], extra[1]
                pos = 2
            if rule.component_trackers:
                idx, h_ij = extra[pos], extra[pos + 1]

            leaves_gn, _ = jax.tree.flatten(gn)
            _, treedef = jax.tree.flatten(h_i)
            leaves_go = jax.tree.leaves(go)
            leaves_h = jax.tree.leaves(h_i)
            leaves_gi = jax.tree.leaves(g_i)
            leaves_g = jax.tree.leaves(g)
            leaves_bn = jax.tree.leaves(b_new) if b_new is not None else None
            leaves_bo = jax.tree.leaves(b_old) if b_old is not None else None
            leaves_hij = jax.tree.leaves(h_ij) if h_ij is not None else None

            interp = cfg.pallas_interpret
            hp = dict(b=cfg.b, a=cfg.a, pa=pa, p_page=cfg.p_page)
            new_h, new_gi, new_g, new_hij = [], [], [], []
            for li, (tn, to, th, tgi, tg) in enumerate(zip(
                    leaves_gn, leaves_go, leaves_h, leaves_gi, leaves_g)):
                fh = th[0].reshape(-1).astype(jnp.float32)
                fgi = tgi[0].reshape(-1).astype(jnp.float32)
                fg = tg.reshape(-1).astype(jnp.float32)
                d_loc = fh.shape[0]

                # ---- line 9 inputs: the rule's oracle leaf view ------
                if rule.component_trackers:
                    # tn/to: (1, B, *loc); h_ij leaf: (1, m, *loc).
                    m_comp = leaves_hij[li].shape[1]
                    B = tn.shape[1]
                    fij = leaves_hij[li][0].reshape(
                        m_comp, -1).astype(jnp.float32)
                    fn2 = tn[0].reshape(B, -1).astype(jnp.float32)
                    fo2 = to[0].reshape(B, -1).astype(jnp.float32)
                    iloc = idx[0]                        # (B,)
                    k_ij = variants.k_finite_mvr_components(
                        fn2, fo2, fij[iloc], iloc, m_comp, b=cfg.b)
                    fij_new = fij + partf * (k_ij / pa)
                    ox = variants.OracleBatch(k=jnp.mean(k_ij, axis=0))
                elif rule.needs_minibatch:
                    ox = variants.OracleBatch(
                        gn=tn[0].reshape(-1).astype(jnp.float32),
                        go=to[0].reshape(-1).astype(jnp.float32),
                        bn=leaves_bn[li][0].reshape(-1).astype(jnp.float32),
                        bo=leaves_bo[li][0].reshape(-1).astype(jnp.float32),
                        coin=coin)
                else:
                    ox = variants.OracleBatch(
                        gn=tn[0].reshape(-1).astype(jnp.float32),
                        go=to[0].reshape(-1).astype(jnp.float32))

                lkey = variants.leaf_node_key(k_comp, li, node_idx)

                def dense_update(ox=ox, fh=fh, fgi=fgi):
                    """Lines 9-11 over the full local vector (fused
                    Pallas or jnp) -> (h_new, dense payload).  Every
                    wire below consumes this EXCEPT the BlockRandK
                    sparse path, whose fused form evaluates the payload
                    only at the selected blocks."""
                    if cfg.use_pallas:
                        return rule.fused_flat(ox, fh, fgi, partf,
                                               interpret=interp, **hp)
                    k = rule.k(ox, fh, b=cfg.b, p_page=cfg.p_page)
                    return variants.control_variate_tail(
                        k, fh, fgi, a=cfg.a, pa=pa, part=partf)

                # ---- lines 10-11 + compress + aggregate --------------
                # Every branch yields the node's g_i INCREMENT (the
                # masked compressed message m_i, dense-scattered) and
                # the server-estimator increment delta = mean_i m_i —
                # commit() applies them (weighted); the sync
                # node_update applies them immediately with weight 1.
                if cfg.compression_ratio is None:
                    fh_new, payload = dense_update()
                    m_i = partf * payload
                    total = jax.lax.psum(m_i, data_axes)
                    delta = total / self.n_nodes
                    gi_inc = m_i
                elif cfg.aggregation == "dense_psum":
                    bs, nb, kb = block_plan(d_loc, cfg.block_size,
                                            cfg.compression_ratio)
                    # The compress step is already dense here, so
                    # BlockRandK has no traffic to save and stays jnp
                    # in both paths.
                    fh_new, payload = dense_update()
                    m_i = partf * block_randk_dense(lkey, payload, kb, bs)
                    total = jax.lax.psum(m_i, data_axes)
                    delta = total / self.n_nodes
                    gi_inc = m_i
                elif cfg.wire_format == "topk":
                    # Coordinate-level TopK wire: ceil(ratio * d_local)
                    # largest-|payload| coordinates as (value, index)
                    # pairs.  Biased baseline — needs the dense payload,
                    # so the fused path stops at the update (no
                    # never-materialize win to fuse into).
                    from repro.core.compressors import TopK
                    kk = max(1, min(d_loc, math.ceil(
                        cfg.compression_ratio * d_loc)))
                    fh_new, payload = dense_update()
                    vals, cidx = TopK(k=kk).compress_sparse(lkey, payload)
                    vals = partf * vals
                    all_vals = jax.lax.all_gather(vals, data_axes,
                                                  tiled=False)
                    all_idx = jax.lax.all_gather(cidx, data_axes,
                                                 tiled=False)
                    delta = jnp.zeros_like(fg).at[
                        all_idx.reshape(-1)].add(
                        all_vals.reshape(-1)) / self.n_nodes
                    gi_inc = jnp.zeros_like(fgi).at[cidx].add(vals)
                elif cfg.wire_format == "dithering":
                    # QSGD wire: dense message, quantized coordinates.
                    # The all-gather carries what the server would
                    # decode from (norm, sign, level) packets.
                    from repro.core.compressors import RandomDithering
                    q = RandomDithering(s=cfg.dithering_levels)
                    fh_new, payload = dense_update()
                    m_i = partf * q.compress(lkey, payload)
                    all_m = jax.lax.all_gather(m_i, data_axes,
                                               tiled=False)
                    delta = jnp.sum(all_m.reshape(-1, d_loc),
                                    axis=0) / self.n_nodes
                    gi_inc = m_i
                else:  # sparse_allgather, BlockRandK — the paper's wire
                    bs, nb, kb = block_plan(d_loc, cfg.block_size,
                                            cfg.compression_ratio)
                    if cfg.use_pallas:
                        # Fused update+compress (DESIGN.md §6): the h
                        # tracker gets its own dense pass (k stays
                        # in-register) and the line-11 payload is
                        # evaluated ONLY at the kb selected blocks —
                        # the dense payload never exists in HBM
                        # (finite_mvr: tail+gather, its k is dense).
                        bidx = block_randk_indices(lkey, nb, kb)
                        fh_new, vals = rule.fused_flat_blocks(
                            ox, fh, fgi, partf, bidx, scale=nb / kb,
                            block_size=bs, interpret=interp, **hp)
                    else:
                        fh_new, payload = dense_update()   # jnp here
                        vals, bidx = block_randk_select(lkey, payload,
                                                        kb, bs)
                    vals = partf * vals
                    # wire: (n·kb·bs values + n·kb indices) over data axes
                    all_vals = jax.lax.all_gather(vals, data_axes,
                                                  tiled=False)
                    all_idx = jax.lax.all_gather(bidx, data_axes,
                                                 tiled=False)
                    delta = block_scatter_add(
                        jnp.zeros_like(fg),
                        all_vals.reshape(-1, bs), all_idx.reshape(-1),
                        bs) / self.n_nodes
                    gi_inc = block_scatter_add(jnp.zeros_like(fgi),
                                               vals, bidx, bs)

                new_h.append(fh_new.reshape(th.shape))
                new_gi.append(gi_inc.reshape(tgi.shape))
                new_g.append(delta.reshape(tg.shape))
                if rule.component_trackers:
                    hl = leaves_hij[li]
                    new_hij.append(fij_new.reshape(hl.shape))

            participants = jax.lax.psum(partf, data_axes)
            outs = [jax.tree.unflatten(treedef, new_h),
                    jax.tree.unflatten(treedef, new_gi),
                    jax.tree.unflatten(treedef, new_g)]
            if rule.component_trackers:
                outs.append(jax.tree.unflatten(treedef, new_hij))
            return tuple(outs) + (partf.reshape(1), participants)

        results = compat.shard_map(
            update, mesh=self.mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
        )(*operands)

        if rule.component_trackers:
            h_new, gi_inc, g_delta, h_ij_new, part, parts = results
        else:
            h_new, gi_inc, g_delta, part, parts = results
            h_ij_new = None
        disp = ShardedDispatch(h_new=h_new, g_i_inc=gi_inc,
                               g_delta=g_delta, h_ij_new=h_ij_new,
                               part=part)
        bits = parts * self._per_node_message_bits(state.h_i)
        return disp, NodeUpdateMetrics(participants=parts,
                                       bits_sent=bits)

    # -- the server-side apply ---------------------------------------------
    @phase_scope("dasha_commit")
    def commit(self, state: ShardedDashaState, disp: ShardedDispatch,
               weight=1.0) -> ShardedDashaState:
        """Lines 12/19 of Algorithm 1 for one dispatched round: apply a
        :class:`ShardedDispatch` to the estimators.  ``weight`` is the
        staleness weight ``w(s)`` of the async commit (DESIGN.md §9/§10)
        — it scales the compressed increments to BOTH ``g_i`` and ``g``
        (preserving ``g = mean_i g_i``), while the node trackers
        ``h_i``/``h_ij`` are *set* unweighted for participating rows
        (they are the clients' local state, already stepped).  Leaves
        ``state.step`` untouched — the caller owns the round counter."""
        w = jnp.asarray(weight, jnp.float32)

        def add_w(x, d):
            return (x.astype(jnp.float32) + w * d).astype(x.dtype)

        def set_rows(x, new):
            m = disp.part.reshape((-1,) + (1,) * (x.ndim - 1)) > 0
            return jnp.where(m, new.astype(jnp.float32),
                             x.astype(jnp.float32)).astype(x.dtype)

        g = jax.tree.map(add_w, state.g, disp.g_delta)
        g_i = jax.tree.map(add_w, state.g_i, disp.g_i_inc)
        h_i = jax.tree.map(set_rows, state.h_i, disp.h_new)
        h_ij = state.h_ij
        if disp.h_ij_new is not None:
            h_ij = jax.tree.map(set_rows, state.h_ij, disp.h_ij_new)
        return state._replace(g=g, g_i=g_i, h_i=h_i, h_ij=h_ij)

    def node_update(self, grads_new: PyTree, grads_old: PyTree,
                    state: ShardedDashaState, key: Array, *,
                    mini_new: Optional[PyTree] = None,
                    mini_old: Optional[PyTree] = None,
                    component_idx: Optional[Array] = None,
                    ) -> Tuple[ShardedDashaState, NodeUpdateMetrics]:
        """Lines 7-19 of Algorithm 1: :meth:`dispatch` + immediate
        :meth:`commit` with weight 1 — the synchronous round, exactly
        as before the split (the async cohort runtime is a buffered
        re-composition of the same two halves, DESIGN.md §10)."""
        disp, metrics = self.dispatch(
            grads_new, grads_old, state, key, mini_new=mini_new,
            mini_old=mini_old, component_idx=component_idx)
        new_state = self.commit(state, disp, weight=1.0)
        return new_state._replace(step=state.step + 1), metrics

    # -- wire accounting ---------------------------------------------------
    def uplink_bits_per_round(self, d_total: int) -> float:
        """Expected uplink bits per node per round (Tables 1-2 metric),
        aggregation-aware: only ``sparse_allgather`` has a sparse wire;
        ``dense_psum`` moves dense messages regardless of the
        compression ratio (core/variants.py accounting)."""
        cfg = self.cfg
        return variants.uplink_bits_per_node(
            d_total, aggregation=cfg.aggregation,
            compression_ratio=cfg.compression_ratio,
            block_size=cfg.block_size, p_a=cfg.p_a,
            wire_format=cfg.wire_format,
            dithering_levels=cfg.dithering_levels)
