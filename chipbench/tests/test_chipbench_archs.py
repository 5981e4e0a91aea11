"""An architecture enters by files alone.  In a copied checkout, a
``model_type`` whose two modules re-export Granite's, its configurations,
traffic, limits and workload entries, and a reader of a count that only
the new architecture's ``train_layer_counts`` gives: the tiny train and
serve drivers run the new cells on the CPU, and no file that was there
changes.  A ``model_type`` without its modules is refused where the cell
resolves."""
from __future__ import annotations

import io
import json
import os
import time
import types
from contextlib import redirect_stdout

import jax
import pytest

from chipbench import harness, run
from chipbench.drivers import serve, train
from chipbench.tests import tiny
from chipbench.tests.test_chipbench_harness import _copy_checkout

SEED = 2 ** 33 + 5

ARCH = '''"""Granite's program side under another model_type, with counts of
its own."""
from chipbench.archs.granite import *  # noqa: F401,F403


def train_layer_counts(model, seq_len, seqs):
    return {"alias_tokens": float(seq_len * seqs)}


def serve_layer_counts(model, page_size, kv_bytes, act_bytes, starts,
                       q_lens):
    return {"alias_rows": float(sum(1 for q in q_lens if q > 0))}
'''
REFERENCE = "from chipbench.reference.granite import *  # noqa: F401,F403\n"
READER = '''"""Tokens a round, from the alias architecture's own count."""


def read(ctx):
    if "alias_tokens" not in ctx.counts or not ctx.units:
        return None
    return ctx.counts["alias_tokens"] / ctx.units
'''


def _files(root: str) -> dict:
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            with open(os.path.join(d, n), "rb") as f:
                out[os.path.relpath(os.path.join(d, n), root)] = f.read()
    return out


def _write(root: str, rel: str, text: str) -> None:
    path = os.path.join(root, rel)
    assert not os.path.exists(path), rel
    with open(path, "w") as f:
        f.write(text)


def _add_cell(root: str, bench: dict, cell: harness.Cell, model_type: str,
              name: str) -> None:
    """The configuration, traffic, limits and entries of the tiny
    ``cell`` under ``model_type``, as the cell ``name``."""
    conf = dict(cell.config, name=name)
    conf["model"] = dict(conf["model"], model_type=model_type)
    rel = f"chipbench/configs/{name}.json"
    _write(root, rel, json.dumps(conf))
    _write(root, f"chipbench/traffic/{name}.json", json.dumps(cell.traffic))
    _write(root, f"chipbench/limits/{name}.json",
           json.dumps({"limits": cell.limits}))
    bench["configs"].append({"name": name, "source": "test", "file": rel,
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": name, "chips": 1, "why": "test"})


@pytest.fixture(scope="module")
def alias_root(tmp_path_factory):
    root = _copy_checkout(tmp_path_factory.mktemp("checkout"))
    before = _files(os.path.join(root, "chipbench"))
    _write(root, "chipbench/archs/granite_alias.py", ARCH)
    _write(root, "chipbench/reference/granite_alias.py", REFERENCE)
    _write(root, "chipbench/metrics/alias_tokens_per_round.py", READER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    _add_cell(root, bench, tiny.train_cell(tiny.TRAIN_LIMITS),
              "granite_alias", "alias-train")
    _add_cell(root, bench, tiny.serve_cell(tiny.SERVE_LIMITS),
              "granite_alias", "alias-serve")
    bench["per_layer"].append({
        "name": "alias_tokens_per_round", "unit": "tokens",
        "better": "higher", "source": "program_counter",
        "layer": "trainer step", "moves": "train_tokens_per_s",
        "workloads": ["alias-train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _files(os.path.join(root, "chipbench"))
    assert {k: after[k] for k in before} == before
    return root


def _run(cell, driver, monkeypatch, seconds: float):
    """The result line of ``run._run`` and the driver's own result."""
    seen = {}
    real = driver.run

    def spy(*a, **kw):
        seen["out"] = real(*a, **kw)
        return seen["out"]

    monkeypatch.setattr(driver, "run", spy)
    args = types.SimpleNamespace(seconds=seconds, seed=SEED, trace=0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run._run(cell, args, jax.devices()[:1], None) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1]), seen["out"]


def _module_file(root: str, kind: str) -> str:
    return os.path.join(root, "chipbench", kind, "granite_alias.py")


def test_a_new_model_type_trains_as_granite_does(alias_root, monkeypatch):
    cell = harness.resolve("alias-train", alias_root)
    assert cell.arch.__file__ == _module_file(alias_root, "archs")
    assert cell.reference.__file__ == _module_file(alias_root, "reference")
    line, out = _run(cell, train, monkeypatch, 0.5)
    assert line["correct"], line["checks"]
    with redirect_stdout(io.StringIO()):
        granite = train.run(tiny.train_cell(tiny.TRAIN_LIMITS), 0.5, SEED,
                            jax.devices()[:1], time.perf_counter())
    assert out["program"]["loss"] == granite["program"]["loss"]
    assert out["reference"]["loss"] == granite["reference"]["loss"]
    ctx = harness.LayerContext(trace=None, units=out["units"],
                               counts=out["layer_counts"], peaks={},
                               chips=1)
    assert harness.read_layers(cell, ctx) == {
        "alias_tokens_per_round": {"value": 32.0, "unit": "tokens"}}


def test_a_new_model_type_serves(alias_root, monkeypatch):
    cell = harness.resolve("alias-serve", alias_root)
    line, out = _run(cell, serve, monkeypatch, 2.0)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 8 and line["failed"] == 0
    assert 0 < out["layer_counts"]["alias_rows"] <= \
        out["layer_counts"]["tokens"]


@pytest.mark.parametrize("missing", ["archs", "reference"])
def test_a_model_type_without_its_modules_is_refused(tmp_path, missing):
    root = _copy_checkout(tmp_path)
    if missing == "reference":
        _write(root, "chipbench/archs/half_arch.py", ARCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    _add_cell(root, bench, tiny.train_cell(), "half_arch", "half-train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    want = os.path.join(root, "chipbench", missing, "half_arch.py")
    with pytest.raises(FileNotFoundError) as e:
        harness.resolve("half-train", root)
    assert want in str(e.value)
