"""Plain reference of synchronous DASHA-PP-MVR rounds (Algorithm 1 with
the Algorithm 5 oracle) with BlockRandK compression, independent
participation and the plain server step ``x <- x - gamma g``.

Written from the paper and the configuration file; it imports nothing
of the program.  The loss is that of the plain model of the
configuration's ``model_type`` (``chipbench/reference/<model_type>.py``,
the ``ref`` argument; where none is given, this checkout's module).
Estimators are stored at the parameters' dtype and updated in float32,
as the configuration states.  The randomness is
the documented per-round contract of a DASHA-PP run, so that both sides
draw the same participants and blocks from the same round key:

* ``k_part, k_oracle, k_comp = split(fold_in(key, round), 3)``;
* node ``i`` takes part iff ``bernoulli(fold_in(k_part, i), p_a)``;
* node ``i`` sends, for the ``l``-th parameter leaf in tree order, the
  first ``kb`` entries of ``permutation(fold_in(fold_in(k_comp, l), i),
  nb)`` of its ``nb`` blocks, scaled by ``nb / kb``.

Per round it evaluates each node's gradient at the old and the new
point (the MVR pair) one after the other, and keeps the estimators that
the gradients do not read (``x0``, ``g``, each ``g_i``) in host memory
meanwhile, so that one float32 gradient and its accumulator are what is
live on the chip beside the parameters.
"""
from __future__ import annotations

import math
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness

F32 = jnp.float32


def round_keys(key, step):
    ks = jax.random.split(jax.random.fold_in(key, step), 3)
    return ks[0], ks[1], ks[2]


def block_plan(d: int, block_size: int, ratio: float):
    bs = min(block_size, d)
    nb = -(-d // bs)
    return bs, nb, max(1, math.ceil(ratio * nb))


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


class Reference:
    """``steps`` rounds from ``x0`` with zero-initialised estimators.

    ``trainer`` is the configuration's ``trainer`` section; ``mm`` the
    matrix product (the plain model's ``f32_mm`` where None, or the
    float8 control); ``tokens_view`` maps a node's (seqs, T) tokens to
    what its loss reads (the identity, or a planted fault); ``ref`` the
    plain model's module."""

    def __init__(self, model: Dict, trainer: Dict, n_nodes: int,
                 mm: Optional[Callable] = None,
                 tokens_view: Callable = lambda t: t,
                 ref: Optional[ModuleType] = None):
        ref = harness.load_reference(model) if ref is None else ref
        mm = ref.f32_mm if mm is None else mm
        self.m, self.t, self.n = model, trainer, n_nodes
        rows = int(model.get("embedding_rows", model["vocab_size"]))
        gamma = float(trainer["gamma"])
        b, a, pa = (float(trainer["b"]), float(trainer["a"]),
                    float(trainer["p_a"]))
        ratio, block = float(trainer["compression_ratio"]), \
            int(trainer["block_size"])

        def vg(p16, toks):
            p32 = jax.tree.map(lambda x: x.astype(F32), p16)
            return jax.value_and_grad(
                lambda p: ref.loss(p, tokens_view(toks), model, rows,
                                   mm))(p32)

        self._vg = jax.jit(vg)
        self._advance = jax.jit(lambda x, g: jax.tree.map(
            lambda p, gg: (p.astype(F32) - gamma * gg.astype(F32)
                           ).astype(p.dtype), x, g))
        self._acc = jax.jit(lambda go, h: jax.tree.map(
            lambda gg, hh: -(1 - b) * gg - b * hh.astype(F32), go, h),
            donate_argnums=_donate(0))

        def node_step(gn, acc, h, gi, part, k_comp, node):
            partf = part.astype(F32)
            leaves = []
            treedef = jax.tree.structure(h)
            for li, (n_, c_, h_, g_) in enumerate(zip(
                    jax.tree.leaves(gn), jax.tree.leaves(acc),
                    jax.tree.leaves(h), jax.tree.leaves(gi))):
                k = n_ + c_
                hf, gf = h_.astype(F32), g_.astype(F32)
                h_new = hf + partf * k / pa
                payload = (k / pa - (a / pa) * (gf - hf)).reshape(-1)
                d = payload.shape[0]
                bs, nb, kb = block_plan(d, block, ratio)
                key = jax.random.fold_in(jax.random.fold_in(k_comp, li),
                                         node)
                idx = jax.random.permutation(key, nb)[:kb]
                blocks = jnp.pad(payload, (0, nb * bs - d)).reshape(nb, bs)
                vals = blocks[idx] * (nb / kb) * partf
                inc = jnp.zeros((nb, bs), F32).at[idx].add(vals)
                inc = inc.reshape(-1)[:d].reshape(h_.shape)
                leaves.append((jnp.where(part, h_new, hf).astype(h_.dtype),
                               (gf + inc).astype(g_.dtype), inc))
            un = lambda j: jax.tree.unflatten(treedef, [x[j] for x in leaves])
            return un(0), un(1), un(2)

        # the gradient's buffer becomes the increment, h and g_i their
        # successors
        self._node_step = jax.jit(node_step, donate_argnums=_donate(0, 2, 3))
        self._commit = jax.jit(lambda g, delta: jax.tree.map(
            lambda gg, dd: (gg.astype(F32) + dd / n_nodes).astype(gg.dtype),
            g, delta), donate_argnums=_donate(0))
        self._add = jax.jit(lambda a_, b_: jax.tree.map(jnp.add, a_, b_),
                            donate_argnums=_donate(0))
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda x, g, x0: leaf_norms(jax.tree.map(
            lambda p, gg, p0: (p.astype(F32) - gamma * gg.astype(F32)
                               ).astype(p.dtype).astype(F32)
            - p0.astype(F32), x, g, x0)))
        self._ratio, self._block = ratio, block
        self._pa = pa

    def bits_per_node(self, params) -> float:
        total = 0.0
        for x in jax.tree.leaves(params):
            bs, _, kb = block_plan(int(np.prod(x.shape)), self._block,
                                   self._ratio)
            total += kb * (bs * 32.0 + 32.0)
        return total

    def run(self, make_x0: Callable, batches: Sequence, keys: Sequence,
            steps: int = 3) -> Dict:
        """``make_x0()``: the starting parameters, on the device;
        ``batches[t]``: (n, seqs, T) tokens of round ``t``; ``keys[t]``
        its round key.  Returns the per-round losses, participants and
        uplink bits, the per-leaf norms of the node-0 gradient at ``x0``
        (``grad0``), of the server estimator after the first round
        (``g1``), and of the parameters' change after ``steps`` rounds
        as the next round's server step leaves them (``change``)."""
        n = self.n
        x = make_x0()
        x0 = jax.device_get(x)
        zeros = jax.tree.map(np.zeros_like, x0)
        g = zeros
        g_i = [zeros for _ in range(n)]
        h_i = [jax.tree.map(jnp.zeros_like, x) for _ in range(n)]
        out: Dict = {"loss": [], "participants": [], "bits": []}
        per_node_bits = self.bits_per_node(x0)
        for t in range(steps):
            x_new = self._advance(x, jax.device_put(g))
            k_part, _, k_comp = round_keys(keys[t], t)
            accs = []
            for i in range(n):
                _, go = _ready(self._vg(x, batches[t][i]))
                if t == 0 and i == 0:
                    out["grad0"] = np.asarray(self._norms(go))
                accs.append(_ready(self._acc(go, h_i[i])))
                del go
            del x
            delta, losses, parts = None, [], 0
            for i in range(n):
                ln, gn = _ready(self._vg(x_new, batches[t][i]))
                part = jax.random.bernoulli(jax.random.fold_in(k_part, i),
                                            self._pa)
                h_i[i], gi, inc = self._node_step(
                    gn, accs[i], h_i[i], jax.device_put(g_i[i]), part,
                    k_comp, i)
                accs[i] = gn = None
                g_i[i] = jax.device_get(gi)
                del gi
                delta = inc if delta is None else self._add(delta, inc)
                del inc
                losses.append(float(ln))
                parts += int(part)
            g = jax.device_get(self._commit(jax.device_put(g), delta))
            del delta
            x = x_new
            out["loss"].append(float(np.mean(losses)))
            out["participants"].append(float(parts))
            out["bits"].append(parts * per_node_bits)
            if t == 0:
                out["g1"] = np.asarray(self._norms(g))
        del h_i
        out["change"] = np.asarray(self._change(x, jax.device_put(g),
                                                jax.device_put(x0)))
        return out


def _donate(*argnums):
    """Donation where the backend reuses the buffers (the CPU warns)."""
    return argnums if jax.default_backend() != "cpu" else ()


def _ready(x):
    return jax.block_until_ready(x)


def run_reference(model: Dict, trainer: Dict, make_x0: Callable,
                  batches: List, keys: List, n_nodes: int,
                  mm: Optional[Callable] = None,
                  tokens_view: Callable = lambda t: t, steps: int = 3,
                  ref: Optional[ModuleType] = None) -> Dict:
    """One-shot :class:`Reference` run, at the highest matmul precision
    for float32 products; ``make_x0()`` makes the starting parameters
    on the device."""
    with jax.default_matmul_precision("highest"):
        return Reference(model, trainer, n_nodes, mm, tokens_view,
                         ref).run(make_x0, batches, keys, steps)
