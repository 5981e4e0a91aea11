"""The comparisons that decide ``correct``.

Each returns ``{name: value}``; a run is correct when every value is at
or under the limit the cell's limits file gives it (``chipbench/limits/
<cell>.json``), and every compared number is printed beside its limit.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

# A gap where the reference reads exactly zero and the program does not,
# or where there is no number to compare (JSON has no infinity or NaN).
NO_SCALE = 1e30


def leaf_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Optional[Sequence[bool]] = None) -> float:
    """Worst leaf of |‖prog‖ − ‖ref‖| measured against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    if p.shape != r.shape:
        raise ValueError(f"{p.shape} leaves against {r.shape}")
    if keep is not None:
        k = np.asarray(keep, bool)
        p, r = p[k], r[k]
    if p.size == 0:
        return 0.0
    scale = np.maximum(np.abs(r), np.median(np.abs(r)))
    gap = np.abs(p - r)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.where(scale > 0, gap / np.where(scale > 0, scale, 1.0),
                       np.where(gap > 0, NO_SCALE, 0.0))
    return float(np.max(rel))


def moving_leaves(grad0: Sequence[float]) -> np.ndarray:
    """Leaves that count for the change: the reference's gradient norm
    at the first point is at least a thousandth of the median leaf's
    (a leaf under it moves by round-off alone)."""
    g = np.asarray(grad0, np.float64)
    return g >= 1e-3 * np.median(g)


def train_gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Program against reference over the first rounds: the loss of each
    round, the server estimator after round one, the parameters' change
    after the last, and the uplink accounting (exact)."""
    loss = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    wire = sum(abs(a - b) for a, b in zip(prog["participants"],
                                          ref["participants"]))
    wire += sum(abs(a - b) for a, b in zip(prog["bits"], ref["bits"]))
    return {
        "loss_gap": float(loss),
        "grad_gap": leaf_gap(prog["g1"], ref["g1"]),
        "change_gap": leaf_gap(prog["change"], ref["change"],
                               moving_leaves(ref["grad0"])),
        "wire_gap": float(wire),
    }


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """{name: {"value", "limit"}} for every compared number; raises when
    a number has no limit."""
    missing = sorted(set(values) - set(limits))
    if missing:
        raise ValueError(f"no limit for {missing}")
    return {k: {"value": float(v) if np.isfinite(v) else NO_SCALE,
                "limit": float(limits[k])}
            for k, v in values.items()}


def is_correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
