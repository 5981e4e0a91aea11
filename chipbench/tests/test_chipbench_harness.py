"""Cells and per-layer metrics are found by name: every entry of
``BENCHMARK.json`` has its files, and a cell or metric added as files
alone is found and read with no code changed."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from chipbench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_file_keeps_its_contract():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in b["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= \
        max(1, len(b["workloads"]) // 2)


def test_every_cell_and_metric_resolves():
    b = _bench()
    for w in b["workloads"]:
        cell = harness.resolve(w["name"], ROOT)
        assert cell.driver in ("train", "serve")
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.limits
    for m in b["per_layer"]:
        assert callable(harness.reader(m["name"], ROOT))


def _copy_checkout(dst) -> str:
    root = str(dst)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    return root


def _ctx(**counts) -> harness.LayerContext:
    trace = types.SimpleNamespace(window_s=2.0, devices={"/device:TPU:0": []})
    return harness.LayerContext(trace=trace, units=4, counts=counts,
                                peaks={"flops_bf16": 197e12,
                                       "hbm_bytes_per_s": 819e9}, chips=1)


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    root = _copy_checkout(tmp_path)
    here = os.path.join(root, "chipbench")
    with open(os.path.join(here, "traffic", "seq1k.json"), "w") as f:
        json.dump({"driver": "train", "seq_len": 1024, "seqs_per_node": 1,
                   "distinct_batches": 4}, f)
    with open(os.path.join(here, "limits", "train-d8-seq1k.json"), "w") as f:
        json.dump({"limits": {"loss_gap": 0.5}}, f)
    with open(os.path.join(here, "metrics", "flops_per_unit.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.counts['model_flops'] / ctx.units\n")
    with open(os.path.join(here, "metrics", "finds_nothing.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    b = _bench()
    b["workloads"].append({"name": "train-d8-seq1k",
                           "config": "granite-3-2b.train-d8",
                           "traffic": "seq1k", "chips": 1, "why": "test"})
    for name in ("flops_per_unit", "finds_nothing"):
        b["per_layer"].append({"name": name, "unit": "FLOP", "better":
                               "higher", "source": "program_counter",
                               "layer": "trainer step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["train-d8-seq1k"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    cell = harness.resolve("train-d8-seq1k", root)
    assert cell.traffic["seq_len"] == 1024
    assert cell.limits == {"loss_gap": 0.5}
    assert cell.config["model"]["num_hidden_layers"] == 8
    assert [m["name"] for m in cell.per_layer] == ["flops_per_unit",
                                                   "finds_nothing"]
    out = harness.read_layers(cell, _ctx(model_flops=8.0))
    # a reader that finds nothing leaves its metric out of the line
    assert out == {"flops_per_unit": {"value": 2.0, "unit": "FLOP"}}
    # the cells that were there are untouched by the addition
    assert harness.resolve("train-d8-seq4k", root).per_layer == \
        harness.resolve("train-d8-seq4k", ROOT).per_layer


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve("no-such-cell", ROOT)


def _run_cmd(root, cell, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_report_off_a_tpu():
    for cell in [w["name"] for w in _bench()["workloads"]]:
        p = _run_cmd(ROOT, cell)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
        assert "not 'tpu'" in p.stderr


def test_the_command_refuses_without_the_program(tmp_path):
    # a checkout that holds only BENCHMARK.json and the benchmark's files
    root = _copy_checkout(tmp_path)
    p = _run_cmd(root, "train-d8-seq4k")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_device_check_refuses_interpret_mode_and_too_few_chips(monkeypatch):
    import jax

    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert harness.tpu_devices(1) == [tpu]
    with pytest.raises(harness.NoChip, match="4 TPU chips"):
        harness.tpu_devices(4)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(harness.NoChip, match="interpret"):
        harness.tpu_devices(1)
