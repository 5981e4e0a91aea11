"""Tiny cells for the CPU tests: the configurations' keys at small
sizes, so every driver runs in-process in seconds."""
from __future__ import annotations

import copy
import json
import os

from chipbench import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Limits at the tiny size, set as the cells' are (between the program's
# readings and the float8 control's, seeds 2**33 + 5, 7 and 11 on the
# CPU): the program reads loss 3.5e-4 to 5.9e-4, grad 3.4e-3 to 7.5e-3,
# change 8.1e-3 to 3.9e-2; the control loss 3.4e-3 to 7.9e-3, grad 2.0e-2
# to 3.7e-2; half of each batch left out loss 3.0e-2 to 5.5e-2 and
# change 0.43 to 0.72.  Served tokens: the program 0 to 8.0e-4, the
# control 5.6e-3 to 1.1e-2; a token served one id off reads far more.
TRAIN_LIMITS = {"loss_gap": 2e-3, "grad_gap": 1.5e-2, "change_gap": 0.2,
                "wire_gap": 0.0}
SERVE_LIMITS = {"served_logit_gap": 3e-3}


def _config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def tiny_model(model: dict, layers: int = 2) -> dict:
    m = copy.deepcopy(model)
    m.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, num_hidden_layers=layers,
             vocab_size=500, embedding_rows=512)
    return m


def train_cell(limits=None, seq_len: int = 32) -> harness.Cell:
    conf = _config("granite-3-2b.train-d8")
    conf["model"] = tiny_model(conf["model"])
    return harness.Cell(
        name="tiny-train", chips=1, config=conf,
        traffic={"driver": "train", "seq_len": seq_len, "seqs_per_node": 1,
                 "distinct_batches": 4},
        limits=limits or {}, end_to_end=[], per_layer=[])


def serve_cell(limits=None) -> harness.Cell:
    conf = _config("granite-3-2b.serve")
    conf["model"] = tiny_model(conf["model"])
    conf["engine"].update(slots=4, max_seq_len=128, num_pages=64,
                          prefill_chunk_tokens=16)
    return harness.Cell(
        name="tiny-serve", chips=1, config=conf,
        traffic={"driver": "serve", "rate_per_s": 8.0, "lead_s": 0.5,
                 "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
                 "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
                 "max_total": 96, "shape_seed": 3, "drain_s": 60,
                 "check_tokens": 24, "check_max_requests": 3},
        limits=limits or {}, end_to_end=[], per_layer=[])
