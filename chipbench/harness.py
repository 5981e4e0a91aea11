"""What every cell shares: finding a cell's files by name, the device
check, the compile cache, the per-layer metric readers and the result
line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its files are
found by name under the checkout's ``chipbench/``: the configuration (the file the
``configs`` entry names), ``traffic/<traffic>.json`` and
``limits/<cell>.json``; each per-layer metric is the ``read`` function
of ``metrics/<metric>.py``.  The configuration's ``model.model_type``
names its architecture's two modules, ``archs/<model_type>.py`` (the
program's config, the weights' layout and init, the counts) and
``reference/<model_type>.py`` (the plain model).  Adding a cell, a
metric or an architecture adds files and entries; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: str = ROOT
    arch: ModuleType = dataclasses.field(init=False, repr=False)
    reference: ModuleType = dataclasses.field(init=False, repr=False)

    def __post_init__(self):
        model = self.config["model"]
        self.arch = load_arch(model, self.root)
        self.reference = load_reference(model, self.root)

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell_name: str, root: str) -> Cell:
    """The cell ``cell_name`` of ``root/BENCHMARK.json`` with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    here = os.path.join(root, "chipbench")
    traffic = _read_json(os.path.join(here, "traffic",
                                      w["traffic"] + ".json"))
    limits = _read_json(os.path.join(here, "limits", cell_name + ".json"))
    return Cell(
        name=cell_name, chips=int(w["chips"]), config=config,
        traffic=traffic, limits=limits["limits"],
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, cell_name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, cell_name)],
        root=root)


def _load(root: str, kind: str, name: str) -> ModuleType:
    """``<root>/chipbench/<kind>/<name>.py``, executed as a module."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r}: {path} is "
                                f"missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}." + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: str = ROOT) -> Callable:
    """``read(ctx)`` of ``<root>/chipbench/metrics/<metric>.py``."""
    return _load(root, "metrics", metric).read


def load_arch(model: Dict, root: str = ROOT) -> ModuleType:
    """The program side of ``model``'s architecture,
    ``<root>/chipbench/archs/<model_type>.py``: ``program_arch(model,
    name)``, ``layout(model)``, ``init_leaf(path, shape, z, model,
    dtype)``, ``train_round_flops(model, seq_len, seqs)``,
    ``serve_pass_counts(model, page_size, kv_bytes, act_bytes, starts,
    q_lens)`` and, optionally, ``train_layer_counts`` and
    ``serve_layer_counts`` with the same arguments as those two, whose
    keys the drivers add to the cell's counts."""
    return _load(root, "archs", model["model_type"])


def load_reference(model: Dict, root: str = ROOT) -> ModuleType:
    """The plain model of ``model``'s architecture,
    ``<root>/chipbench/reference/<model_type>.py``: ``loss``, ``logits``,
    ``f32_mm`` and ``fp8_mm``.  It imports nothing of the program."""
    return _load(root, "reference", model["model_type"])


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader reads: the reduced trace of the traced
    window, how many timed units (rounds or passes) it holds, the cell's
    counts over those units, the chip's peaks and the chip count."""
    trace: object
    units: int
    counts: Dict[str, float]
    peaks: Dict
    chips: int


def read_layers(cell: Cell, ctx: LayerContext) -> Dict[str, Dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        v = reader(m["name"], cell.root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ----------------------------------------------------------------------
# the device and the compile cache
# ----------------------------------------------------------------------

class NoChip(RuntimeError):
    """Raised where the run would not measure the chip it asks for."""


def tpu_devices(count: int):
    """The first ``count`` TPU devices; raises :class:`NoChip` on any
    other platform, on too few chips, or where the kernels would run in
    interpret mode."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"platform is {devs[0].platform!r}, not 'tpu'")
    if len(devs) < count:
        raise NoChip(f"the cell needs {count} TPU chips, found {len(devs)}")
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None and env not in ("0", "false", "False"):
        raise NoChip("REPRO_PALLAS_INTERPRET forces interpret mode")
    return devs[:count]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``<checkout>/.jax_cache``, for every program however quick;
    from here on :func:`compiles` counts the programs compiled."""
    import jax
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    # a program is compiled or loaded from the cache under
    # BACKEND_COMPILE_EVENT; a load also records a cache hit
    def on_duration(event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            _COMPILES[0] += 1

    def on_event(event, **kw):
        if event == CACHE_HIT_EVENT:
            _COMPILES[1] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return path


CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILES = [0, 0]          # programs compiled or loaded, cache hits


def compiles() -> int:
    """Programs compiled, not loaded from the persistent cache, so far
    in this process, once :func:`enable_compile_cache` has run."""
    return _COMPILES[0] - _COMPILES[1]


def device_info(devices, peak_bytes: int) -> Dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


def emit(correct: bool, attempted: int, failed: int, metrics: Dict,
         device: Dict, checks: Dict, breakdown: Optional[Dict] = None
         ) -> str:
    """Print the compared numbers beside their limits as the last lines
    of standard error, then the result as the last line of standard
    output; returns that line."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line: Dict = {"correct": bool(correct), "attempted": int(attempted),
                  "failed": int(failed), "metrics": metrics,
                  "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    text = json.dumps(line)
    print(text, flush=True)
    return text
