"""``BENCHMARK.json`` agrees with the benchmark's data files: every cell
finds its configuration, traffic and limits; every per-layer metric a
cell lists has a reader; every end-to-end metric names only cells that
exist; and a serving cell's time-to-first-token metric is the
percentile the driver reads from the requests its window holds."""
from __future__ import annotations

import json
import os

import pytest

from chipbench import harness, traffic as gen
from chipbench.drivers import serve

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
CELL_METRICS = [(w, m["name"]) for w in CELLS for m in BENCH["per_layer"]
                if w in m.get("workloads", CELLS)]


@pytest.mark.parametrize("name", CELLS)
def test_the_cell_finds_its_config_traffic_and_limits(name):
    w = next(x for x in BENCH["workloads"] if x["name"] == name)
    here = os.path.join(ROOT, "chipbench")
    for path in (os.path.join(here, "traffic", w["traffic"] + ".json"),
                 os.path.join(here, "limits", name + ".json")):
        assert os.path.isfile(path), path
    cell = harness.resolve(name, ROOT)
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert cell.config["name"] == conf["name"]
    assert cell.config["reduced"] == conf["reduced"]
    assert cell.driver in ("train", "serve")
    assert cell.limits and all(v >= 0 for v in cell.limits.values())


@pytest.mark.parametrize("cell, metric", CELL_METRICS)
def test_every_listed_per_layer_metric_has_a_reader(cell, metric):
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                       metric + ".py"))
    assert callable(harness.reader(metric, ROOT))
    assert metric in {m["name"] for m in harness.resolve(cell, ROOT).per_layer}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]])
def test_end_to_end_metrics_name_only_cells_that_exist(metric):
    m = next(x for x in BENCH["end_to_end"] if x["name"] == metric)
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert m.get("workloads", CELLS), metric


def test_every_config_is_used_by_a_cell():
    used = {w["config"] for w in BENCH["workloads"]}
    assert {c["name"] for c in BENCH["configs"]} == used


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]
                                  if harness.resolve(w["name"], ROOT).driver
                                  == "serve"])
def test_a_serving_cell_reports_the_latency_the_driver_reads(name):
    cell = harness.resolve(name, ROOT)
    reqs = gen.requests(cell.traffic, BENCH["run_seconds"], 2 ** 31 + 11,
                        cell.config["model"]["vocab_size"])
    due = {q.uid: q.due_s for q in reqs if q.due_s >= 0}
    stamps = {u: [d + 1.0, d + 1.2] for u, d in due.items()}
    e2e, _ = serve.latency(due, stamps, set(due), gave_up=1e3)
    wanted = {m["name"] for m in cell.end_to_end} - {"setup_s"}
    assert wanted and wanted <= set(e2e), (wanted, sorted(e2e))
