"""Roofline analysis from the dry-run artifacts (deliverable g).

For each (arch x input-shape x mesh) JSON produced by
``repro.launch.dryrun`` we derive the three roofline terms in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = sum_links collective_bytes / link_bw

with the target chip's published peaks looked up by ``device_kind`` in
:data:`PEAKS` (an unknown device is an error, never a default).

``cost_analysis`` supplies per-device FLOPs and bytes; collective bytes
are parsed from the SPMD-partitioned HLO (dryrun.collective_bytes) —
ring all-gather/reduce-scatter move ~(n-1)/n of the payload across the
slowest link, all-reduce ~2(n-1)/n, all-to-all ~1/n per link; we apply
these factors per op class using the data-axis size.

Also reported per pair: MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D
(MoE) and the usefulness ratio MODEL_FLOPS / (chips · HLO_FLOPs) which
catches remat/redundancy waste, the dominant term, and a one-line
actionable note.
"""
from __future__ import annotations

import glob
import json
import math
import os
from typing import Dict, List, Optional

from repro.models.registry import ARCH_IDS, INPUT_SHAPES, get_config

# Published per-chip peaks keyed by jax's ``device_kind``.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": dict(
        flops_bf16=197e12, hbm_bw=819e9,
        link_bw=50e9,     # 1,600 Gbit/s of interconnect over 4 links
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
               'bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s '
               'chip-to-chip interconnect'),
}
# The chip the dry-run lowers its 16x16 / 2x16x16 pod meshes for.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; raises for a device the
    table does not list."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         f"source (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def active_params(arch_id: str) -> float:
    """N (dense) or N_active (MoE: shared + top-k routed + non-FFN)."""
    cfg = get_config(arch_id)
    from repro.models import Model, count_params
    import jax
    total = count_params(jax.eval_shape(
        Model(cfg).init_params, jax.random.key(0)))
    if cfg.moe is None:
        return float(total)
    m = cfg.moe
    expert_p = 3 * cfg.d_model * m.d_expert          # gate/up/down
    routed_total = cfg.num_layers * m.num_experts * expert_p
    routed_active = cfg.num_layers * m.experts_per_token * expert_p
    return float(total - routed_total + routed_active)


def attention_flops(arch_id: str, shape_name: str) -> float:
    """Analytic attention score+value flops (the 6·N·D rule misses the
    O(T²) term).  Causal halves the work; SWA replaces T by the window;
    SSM/xLSTM mixers are linear in T (folded into the 6·N·D count)."""
    cfg = get_config(arch_id)
    shape = INPUT_SHAPES[shape_name]
    if shape.name == "long_500k":
        cfg = cfg.for_long_context()
    if cfg.arch_type == "ssm":
        return 0.0
    H, hd, L = cfg.num_heads, cfg.hd, cfg.num_layers
    if cfg.mla is not None:
        hd = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
    B, T = shape.global_batch, shape.seq_len
    w = cfg.attention_window
    if shape.kind == "decode":
        t_q, t_kv = 1, min(T, w) if w else T
        causal = 1.0
    else:
        t_q = T
        t_kv = min(T, w) if w else T
        causal = 0.5 if not cfg.is_encoder else 1.0
    fwd = 4.0 * B * t_q * t_kv * H * hd * causal * L   # QK^T + PV, mul+add
    return 3.0 * fwd if shape.kind == "train" else fwd


def model_flops(arch_id: str, shape_name: str) -> float:
    """Exact algorithmic flops: 6·N_active·tokens (train fwd+bwd) or
    2·N_active·tokens (inference) + the analytic attention term; train
    additionally x2 for the DASHA-PP-MVR gradient pair (same batch at
    x^{t+1} and x^t)."""
    shape = INPUT_SHAPES[shape_name]
    n_act = active_params(arch_id)
    attn = attention_flops(arch_id, shape_name)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * (6.0 * n_act * tokens + attn)
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len + attn
    return 2.0 * n_act * shape.global_batch * 1.0 + attn   # one token


def roofline_terms(rec: Dict, chips: int) -> Dict:
    """Three terms in seconds per step.

    * compute: ANALYTIC model flops / peak.  (XLA's cost_analysis counts
      while-loop bodies once — scanned models' HLO flops are ~num_layers
      too small, verified by flops_hlo*L/flops_model ≈ 1-2; the analytic
      count is exact and is the standard MFU denominator.)
    * memory: HLO bytes-accessed / HBM bw.  Stacked-layer buffers are
      accounted at the loop boundary, so this is order-correct (see
      EXPERIMENTS.md §Roofline note).
    * collective: per-class payload bytes from the SPMD HLO with ring
      factors.  The DASHA-PP aggregation collectives live OUTSIDE the
      layer scan (whole-gradient leaves) and are exact; in-scan tensor-
      parallel collectives are counted once per step (lower bound),
      noted per pair via hlo_undercount.
    """
    pk = peaks(rec.get("device_kind", DRYRUN_DEVICE_KIND))
    n_data = 32 if rec["mesh"] == "2x16x16" else 16
    mf = model_flops(rec["arch"], rec["shape"])
    comp = mf / chips / pk["flops_bf16"]
    mem = rec["bytes_per_device"] / pk["hbm_bw"]
    coll = rec.get("collectives", {})
    ring = (n_data - 1) / n_data
    coll_bytes_link = (
        coll.get("all-gather", 0) * ring
        + coll.get("reduce-scatter", 0) * ring
        + coll.get("all-reduce", 0) * 2 * ring
        + coll.get("all-to-all", 0) / n_data
        + coll.get("collective-permute", 0))
    collective = coll_bytes_link / pk["link_bw"]
    dom = max(("compute", comp), ("memory", mem),
              ("collective", collective), key=lambda kv: kv[1])
    # how much of compiled compute the HLO reports vs analytic — ≈1/L for
    # scanned models (cost-analysis loop undercount), ≈1 for unrolled
    hlo_ratio = (chips * rec["flops_per_device"] / mf) if mf else float("nan")
    return dict(compute_s=comp, memory_s=mem, collective_s=collective,
                dominant=dom[0], bound_s=dom[1], model_flops=mf,
                useful_ratio=hlo_ratio)


_NOTES = {
    "compute": ("compute-bound: raise arithmetic efficiency — larger "
                "matmul tiles, drop the MVR double-backward via "
                "gradient-pair reuse, or reduce remat recompute"),
    "memory": ("memory-bound: fuse elementwise chains (dasha_update "
               "kernel), cut temp materialization (blockwise attention, "
               "chunked scans), store variates in bf16"),
    "collective": ("collective-bound: raise the compression ratio "
                   "(smaller K), move aggregation to sparse all-gather, "
                   "overlap collectives with compute, or coarsen node "
                   "granularity to the pod axis"),
}


def load_records(dryrun_dir: str, tag: str = "baseline") -> List[Dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, f"{tag}__*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def analyze(dryrun_dir: str = "results/dryrun", tag: str = "baseline",
            mesh: Optional[str] = "16x16") -> List[Dict]:
    rows = []
    for rec in load_records(dryrun_dir, tag):
        if mesh and rec.get("mesh") != mesh:
            continue
        row = {k: rec.get(k) for k in ("arch", "shape", "mesh", "status")}
        if rec.get("status") == "ok":
            chips = 512 if rec["mesh"] == "2x16x16" else 256
            row.update(roofline_terms(rec, chips))
            row["note"] = _NOTES[row["dominant"]]
            row["temp_gib"] = rec["memory"]["temp_bytes"] / 2**30
        elif rec.get("status") == "skipped":
            row["note"] = rec.get("reason")
        rows.append(row)
    return rows


def format_table(rows: List[Dict]) -> str:
    hdr = (f"{'arch':<22}{'shape':<13}{'compute_s':>11}{'memory_s':>11}"
           f"{'coll_s':>11}{'dominant':>11}{'hlo/an':>8}{'temp GiB':>10}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if r.get("status") == "ok":
            lines.append(
                f"{r['arch']:<22}{r['shape']:<13}{r['compute_s']:>11.4f}"
                f"{r['memory_s']:>11.4f}{r['collective_s']:>11.4f}"
                f"{r['dominant']:>11}{r['useful_ratio']:>8.2f}"
                f"{r['temp_gib']:>10.1f}")
        else:
            lines.append(f"{r['arch']:<22}{r['shape']:<13}  "
                         f"[{r.get('status')}] {r.get('note', '')}")
    return "\n".join(lines)


def main(quick: bool = True):
    for mesh in ("16x16", "2x16x16"):
        rows = analyze(mesh=mesh)
        if rows:
            print(f"# Roofline ({mesh}, from dry-run artifacts)")
            print(format_table(rows))
        else:
            print(f"# Roofline ({mesh}): no dry-run artifacts found — run "
                  "PYTHONPATH=src python -m repro.launch.dryrun first")
        yield rows


if __name__ == "__main__":
    list(main(quick=False))
