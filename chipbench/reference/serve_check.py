"""Reference readings of served tokens: run the plain model once over a
request's prompt and the tokens it was served, and read how far each
served token's logit lies below the reference's best at its position.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness


def bucket(n: int, least: int = 512) -> int:
    b = least
    while b < n:
        b *= 2
    return b


def _row(prompt: Sequence[int], served: Sequence[int]):
    """(padded tokens, padded next tokens, first and past-last position
    whose next token was served)."""
    full = list(prompt) + list(served)
    L = bucket(len(full) - 1)
    toks = np.zeros(L, np.int32)
    nxt = np.zeros(L, np.int32)
    toks[:len(full) - 1] = full[:-1]
    nxt[:len(full) - 1] = full[1:]
    return toks, nxt, len(prompt) - 1, len(full) - 1


class ServedGaps:
    """``gaps(params, [(prompt, served), ...])`` -> widest gap of the
    served tokens (float32 reference).  ``control`` reads, at the same
    positions, the gap of the token that the float8 model puts first.
    ``ref`` is the plain model's module (``chipbench/reference/
    <model_type>.py``; where None, this checkout's)."""

    def __init__(self, model: Dict, ref: Optional[ModuleType] = None):
        ref = harness.load_reference(model) if ref is None else ref
        rows = model["vocab_size"]

        def served(params, toks, nxt, lo, hi):
            lg = ref.logits(params, toks, model, rows)
            pos = jnp.arange(toks.shape[0])
            ok = (pos >= lo) & (pos < hi)
            got = jnp.take_along_axis(lg, nxt[:, None], 1)[:, 0]
            return jnp.max(jnp.where(ok, lg.max(-1) - got, 0.0))

        def control(params, toks, nxt, lo, hi):
            del nxt
            lg = ref.logits(params, toks, model, rows)
            lg8 = ref.logits(params, toks, model, rows, ref.fp8_mm)
            pick = jnp.argmax(lg8, axis=-1)
            pos = jnp.arange(toks.shape[0])
            ok = (pos >= lo) & (pos < hi)
            got = jnp.take_along_axis(lg, pick[:, None], 1)[:, 0]
            return jnp.max(jnp.where(ok, lg.max(-1) - got, 0.0))

        self._served = jax.jit(served)
        self._control = jax.jit(control)

    def _widest(self, fn, params, rows: List) -> float:
        worst = 0.0
        with jax.default_matmul_precision("highest"):
            for prompt, out in rows:
                toks, nxt, lo, hi = _row(prompt, out)
                worst = max(worst, float(fn(params, toks, nxt, lo, hi)))
        return worst

    def gaps(self, params, rows: List) -> float:
        return self._widest(self._served, params, rows)

    def control(self, params, rows: List) -> float:
        return self._widest(self._control, params, rows)
