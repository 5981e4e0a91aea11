"""The PR 8 observability layer (repro/obs/, DESIGN.md §13): span
tracing with dual clocks and Chrome export, the typed metrics registry
+ jsonl sink + JSON snapshot, the MetricsLogger shim, live
invariant monitors, artifact validation, and the traced smokes whose
``fleet.tier_bits`` / ``train.bits_sent`` totals must reconcile
exactly with the engines' own ledgers."""
import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import monitors as obs_monitors
from repro.obs import provenance as obs_provenance
from repro.obs import trace as obs_trace
from repro.obs import validate as obs_validate
from repro.obs.metrics import JsonlSink, Registry
from repro.obs.monitors import ObsWarning
from repro.training.metrics import MetricsLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracer():
    """A fresh installed tracer, uninstalled afterwards."""
    t = obs_trace.configure(meta={"test": "obs"})
    yield t
    obs_trace.uninstall()


@pytest.fixture
def registry():
    """A fresh global registry, original restored afterwards."""
    old = obs_metrics.get_registry()
    reg = obs_metrics.set_registry(Registry())
    yield reg
    obs_metrics.set_registry(old)


# ----------------------------------------------------------------------
# trace: spans, clocks, export
# ----------------------------------------------------------------------

def test_disabled_tracing_is_a_shared_null_span():
    """With no tracer installed the module helpers are free: span()
    returns one shared singleton (no allocation) and instant/counter
    return immediately — the contract bench_obs.py prices."""
    obs_trace.uninstall()
    s1 = obs_trace.span("a", track="x", step=1)
    s2 = obs_trace.span("b")
    assert s1 is s2 is obs_trace._NULL_SPAN
    with s1 as sp:
        sp.set(anything=1)   # no-op, no error
    obs_trace.instant("nope")
    obs_trace.counter("nope", 1.0)
    obs_trace.set_virtual_time(3.0)
    assert not obs_trace.active()


def test_span_nesting_and_export_roundtrip(tracer, tmp_path):
    with obs_trace.span("outer", track="t", a=1) as outer:
        with obs_trace.span("inner", track="t"):
            pass
        outer.set(b=2)
    obs_trace.instant("tick", track="t", k="v")
    obs_trace.counter("depth", 3.0, track="t")
    # inner closes first (trace-event order), args accumulate on outer
    names = [e["name"] for e in tracer.events]
    assert names == ["inner", "outer", "tick", "depth"]
    outer_ev = tracer.events[1]
    assert outer_ev["args"] == {"a": 1, "b": 2}
    assert outer_ev["dur"] >= tracer.events[0]["dur"]

    path = os.path.join(tmp_path, "t.trace.json")
    assert obs_trace.export(path) == path
    with open(path) as f:
        doc = json.load(f)
    assert obs_validate.validate_trace(doc) == []
    assert doc["metadata"]["test"] == "obs"
    # thread-name metadata for the one track, on both clock pids
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {(e["name"], e["pid"]) for e in meta} >= {
        ("thread_name", obs_trace.WALL_PID),
        ("thread_name", obs_trace.VIRTUAL_PID)}


def test_virtual_clock_emits_dual_pid_twins(tracer):
    """While a virtual time is published every event appears twice —
    wall pid 1 and virtual pid 2 with ts = virtual_seconds * 1e6."""
    obs_trace.set_virtual_time(2.0)
    with obs_trace.span("round", track="fleet"):
        obs_trace.set_virtual_time(5.0)
    obs_trace.counter("bits", 7.0, track="fleet")
    spans = [e for e in tracer.events if e["name"] == "round"]
    assert [e["pid"] for e in spans] == [obs_trace.WALL_PID,
                                         obs_trace.VIRTUAL_PID]
    vspan = spans[1]
    assert vspan["ts"] == pytest.approx(2.0 * 1e6)
    assert vspan["dur"] == pytest.approx(3.0 * 1e6)
    ctrs = [e for e in tracer.events if e["name"] == "bits"]
    assert {e["pid"] for e in ctrs} == {obs_trace.WALL_PID,
                                        obs_trace.VIRTUAL_PID}
    assert ctrs[1]["ts"] == pytest.approx(5.0 * 1e6)


def test_traced_decorator_and_export_without_tracer(tmp_path):
    obs_trace.uninstall()
    assert obs_trace.export(os.path.join(tmp_path, "x.json")) is None

    calls = []

    @obs_trace.traced("named.op", track="t")
    def op(x):
        calls.append(x)
        return x + 1

    assert op(1) == 2          # disabled: still just calls through
    t = obs_trace.configure()
    try:
        assert op(2) == 3
        assert [e["name"] for e in t.events] == ["named.op"]
    finally:
        obs_trace.uninstall()
    assert calls == [1, 2]


def test_kernel_scope_is_jit_compatible():
    """kernel_scope wraps jax.named_scope — must work under tracing."""
    @jax.jit
    def f(x):
        with obs_trace.kernel_scope("unit_test"):
            return x * 2.0

    assert float(f(jnp.float32(3.0))) == 6.0


PHASES = ("server_step", "grad_pair", "dasha_dispatch", "dasha_commit")


def _tiny_trainer(use_pallas: bool):
    from repro.compat import make_mesh
    from repro.core.sharded import ShardedDashaConfig
    from repro.models import Model, get_smoke_config
    from repro.training.optim import paper_server
    from repro.training.trainer import Trainer, TrainerConfig

    mesh = make_mesh((1, 1), ("data", "model"))
    model = Model(get_smoke_config("granite-3-2b").with_overrides(
        vocab_size=64))
    dcfg = ShardedDashaConfig(
        gamma=1e-3, a=0.02, b=0.9, p_a=0.5, sampler="independent",
        compression_ratio=0.25, block_size=128, data_axes=("data",),
        variant="mvr", use_pallas=use_pallas)
    return Trainer(model, mesh, TrainerConfig(dasha=dcfg,
                                              server=paper_server(1e-3)))


@pytest.mark.parametrize("use_pallas", [True, False])
def test_phase_scopes_name_the_compiled_step(use_pallas):
    """Every phase of the DASHA-PP step names its ops in the compiled
    program's metadata, and the update kernels' scopes sit inside the
    dispatch phase."""
    import re

    from repro.compat import use_mesh

    tr = _tiny_trainer(use_pallas)
    batch = {"tokens": jnp.zeros((1, 1, 16), jnp.int32)}
    with use_mesh(tr.mesh):
        state = jax.eval_shape(tr.init, jax.random.key(0))
        compiled = tr.jit_train_step(batch).lower(
            state, batch, jax.random.key(1)).compile()
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    for phase in PHASES:
        assert any(f"repro.phase.{phase}/" in n for n in names), phase
    # phases do not nest in each other
    assert all(len(re.findall(r"repro\.phase\.", n)) <= 1 for n in names)
    kernels = [n for n in names
               if re.search(r"repro\.kernel\.(dasha_|block_)", n)]
    assert bool(kernels) == use_pallas
    assert all("repro.phase.dasha_dispatch/" in n for n in kernels)


def test_spans_are_profiler_annotations_while_a_profile_is_open(tmp_path):
    """Under ``obs.profiler_trace`` the program's spans are host events
    of the ``.xplane.pb``, with no tracer installed; once the profile
    closes, span() is the shared null span again."""
    import glob

    from jax.profiler import ProfileData

    from repro.compat import use_mesh
    from repro.obs import profiler_trace
    from repro.training.loop import train
    from repro.training.metrics import MetricsLogger

    obs_trace.uninstall()
    tr = _tiny_trainer(use_pallas=False)
    toks = jnp.tile(jnp.arange(16) % 7, (1, 1, 1)).astype(jnp.int32)

    def fixed():
        while True:
            yield {"tokens": toks}

    state = tr.init(jax.random.key(0))
    logger = MetricsLogger(print_every=1000)
    with use_mesh(tr.mesh), profiler_trace(str(tmp_path)):
        assert obs_trace.span("x") is not obs_trace._NULL_SPAN
        state = train(tr, state, fixed(), num_steps=2, log_every=1000,
                      logger=logger)
        jax.block_until_ready(state)
    logger.close()
    assert obs_trace.span("x") is obs_trace._NULL_SPAN
    assert obs_trace.get_tracer() is None

    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    pd = ProfileData.from_file(files[0])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        host = [e.name for plane in pd.planes
                if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events]
    assert host.count("train.dispatch") == 2


def test_profiled_spans_still_reach_an_installed_tracer(tracer, tmp_path):
    from repro.obs import profiler_trace

    with profiler_trace(str(tmp_path)):
        with obs_trace.span("outer", track="t") as sp:
            sp.set(k=1)
    assert obs_trace.span("y") is not obs_trace._NULL_SPAN  # tracer on
    ev = [e for e in tracer.events if e["name"] == "outer"]
    assert len(ev) == 1 and ev[0]["args"] == {"k": 1}


# ----------------------------------------------------------------------
# metrics: registry, sink, exposition
# ----------------------------------------------------------------------

def test_registry_types_and_kind_mismatch(registry):
    c = registry.counter("a.hits")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = registry.gauge("a.level")
    g.set(4.0)
    g.inc()
    assert g.value == 5.0
    h = registry.histogram("a.lat")
    for v in [1.0, 2.0, 3.0, 4.0]:
        h.observe(v)
    h.observe(10.0, n=3)
    assert h.count == 7 and h.sum == pytest.approx(40.0)
    assert h.min == 1.0 and h.max == 10.0
    assert h.percentile(50) == 4.0
    # get-or-create returns the same object; kind mixups are errors
    assert registry.counter("a.hits") is c
    with pytest.raises(TypeError, match="counter"):
        registry.gauge("a.hits")
    with pytest.raises(TypeError, match="gauge"):
        registry.histogram("a.level")


def test_snapshot_validates(registry, tmp_path):
    registry.counter("train.steps").inc(6)
    registry.gauge("fleet.tier_bits").set(128.0)
    registry.histogram("fleet.staleness").observe(1.0, n=4)
    path = os.path.join(tmp_path, "m.json")
    registry.write_snapshot(path, extra={"provenance": {"x": 1}})
    with open(path) as f:
        doc = json.load(f)
    assert obs_validate.validate_metrics(doc) == []
    assert doc["provenance"] == {"x": 1}
    assert doc["metrics"]["fleet.tier_bits"]["value"] == 128.0


def test_jsonl_sink_roundtrip_and_idempotent_close(tmp_path):
    path = os.path.join(tmp_path, "logs", "x.jsonl")
    sink = JsonlSink(path)     # creates parent dirs
    sink.write({"step": 0, "loss": 1.5})
    sink.write({"step": 1, "loss": 1.25})
    sink.close()
    sink.close()               # idempotent
    assert sink.closed
    with pytest.raises(ValueError, match="closed"):
        sink.write({"step": 2})
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["step"] for r in recs] == [0, 1]
    # append mode: a second sink extends, never truncates
    with JsonlSink(path) as sink2:
        sink2.write({"step": 2})
    with open(path) as f:
        assert len(f.readlines()) == 3


def test_metrics_logger_shim_roundtrip(tmp_path, capsys):
    """The MetricsLogger public contract (jsonl format, stdout lines,
    idempotent close) survives the PR 8 reroute through obs.metrics,
    and logged fields now mirror into the registry as gauges."""
    reg = Registry()
    lg = MetricsLogger(out_dir=str(tmp_path), name="train",
                       print_every=2, registry=reg)
    lg.log(0, loss=2.0, bits_sent=64, note="warm")
    lg.log(1, loss=1.5, bits_sent=32)
    lg.close()
    lg.close()                 # idempotent (pre-PR 8 double-closed a fd)

    with open(os.path.join(tmp_path, "train.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    assert [r["step"] for r in recs] == [0, 1]
    assert recs[0]["loss"] == 2.0 and recs[0]["note"] == "warm"
    assert all("wall_s" in r for r in recs)
    # the registry mirror: latest value per field + the step gauge
    assert reg.gauge("train.step").value == 1.0
    assert reg.gauge("train.loss").value == 1.5
    assert reg.gauge("train.bits_sent").value == 32.0
    out = capsys.readouterr().out
    assert "[step      0]" in out and "loss=2" in out
    assert "[step      1]" not in out      # print_every=2


# ----------------------------------------------------------------------
# monitors
# ----------------------------------------------------------------------

class _FakeResult:
    """Minimal FleetRunResult stand-in for the ledger monitors."""

    def __init__(self, tier_bits, bits_cum, msg_bits):
        self.tier_bits = np.asarray(tier_bits, np.float64)
        self.bits_cum = np.asarray(bits_cum, np.float64)
        self.message_log = [type("M", (), {"bits": b})() for b in msg_bits]
        self.commit_log = []


def test_fleet_ledger_monitor_detects_tampering():
    good = _FakeResult([64.0, 32.0], [0.0, 96.0], [32.0])
    assert obs_monitors.check_fleet_ledger(good).ok
    # tamper the cumulative ledger: reconciliation must fire
    bad = _FakeResult([64.0, 32.0], [0.0, 97.0], [32.0])
    res = obs_monitors.check_fleet_ledger(bad)
    assert not res.ok
    assert "VIOLATED" in res.message()
    with pytest.warns(ObsWarning, match="fleet_ledger"):
        out = obs_monitors.emit([res], registry=Registry())
    assert out == [res]


def test_monitor_emit_counts_checks_and_failures(registry):
    good = _FakeResult([8.0], [0.0, 8.0], [])
    bad = _FakeResult([8.0], [0.0, 9.0], [])
    with pytest.warns(ObsWarning):
        obs_monitors.run_fleet_monitors(bad, registry=registry)
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # a clean result must not warn
        obs_monitors.run_fleet_monitors(good, registry=registry)
    assert registry.counter("obs.monitor_checks").value == 4.0
    assert registry.counter("obs.monitor_failures").value == 1.0


def test_hops_monotone_monitor_rejects_time_travel():
    rec = type("C", (), {"client": 3, "dispatch_round": 5,
                         "hops": ((0, 4),), "commit_round": 6,
                         "staleness": 1})()
    res = obs_monitors.check_hops_monotone([rec])   # hop before dispatch
    assert not res.ok and res.detail["n_violations"] == 1
    ok_rec = type("C", (), {"client": 3, "dispatch_round": 5,
                            "hops": ((0, 5),), "commit_round": 6,
                            "staleness": 1})()
    assert obs_monitors.check_hops_monotone([ok_rec]).ok


# ----------------------------------------------------------------------
# validation + provenance
# ----------------------------------------------------------------------

def test_validate_rejects_malformed_artifacts(tmp_path):
    assert obs_validate.validate_trace({"traceEvents": [
        {"ph": "Z", "pid": 1, "name": "x"}]}) != []
    assert obs_validate.validate_trace({"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "x", "ts": -1.0,
         "dur": 1.0}]}) != []
    assert obs_validate.validate_metrics(
        {"ts": 0.0, "metrics": {"m": {"kind": "dial", "value": 1}}}) != []
    bad = os.path.join(tmp_path, "bad.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": []}, f)
    assert obs_validate.main([bad]) == 1
    assert obs_validate.main([]) == 2


def test_provenance_collects_required_keys():
    p = obs_provenance.collect(cwd=REPO)
    assert {"git_sha", "backend", "jax_version",
            "hostname", "platform", "python"} <= set(p)
    assert p["jax_version"] == jax.__version__
    assert p["backend"] == jax.default_backend()
    assert isinstance(p["git_sha"], str) and len(p["git_sha"]) == 40


# ----------------------------------------------------------------------
# traced smokes: the §13 reconciliation acceptance
# ----------------------------------------------------------------------

def test_paged_engine_empty_latency_summary_has_none_fields():
    """Regression: latency_summary on an engine with no completed
    requests used to drop keys / crash np.percentile on []. All five
    keys must be present with None values."""
    from repro.models import Model, get_smoke_config
    from repro.serving import PagedEngine

    cfg = get_smoke_config("granite-3-2b")
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    eng = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                      page_size=8)
    summ = eng.latency_summary()
    assert summ == {"requests": 0, "latency_p50": None,
                    "latency_p95": None, "ttft_p50": None,
                    "ttft_p95": None}
    m = eng.metrics()          # and metrics() carries them through
    assert m["latency_p50"] is None and m["ttft_p95"] is None


def test_traced_serve_smoke_reconciles_and_validates(registry, tmp_path):
    """A traced PagedEngine run: serve.pass spans + the pool counter in
    the trace, serving.decode_tokens published into the registry equal
    to the engine's own ledger, pool-conservation monitor clean."""
    from repro.models import Model, get_smoke_config
    from repro.serving import PagedEngine, Request

    cfg = get_smoke_config("granite-3-2b")
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    tracer = obs_trace.configure()
    try:
        eng = PagedEngine(model, params, batch_size=2, max_seq_len=32,
                          page_size=8)
        rng = np.random.default_rng(0)
        with warnings.catch_warnings():
            warnings.simplefilter("error", ObsWarning)   # monitors clean
            eng.run([Request(uid=i,
                             prompt=rng.integers(
                                 1, cfg.vocab_size, 4).tolist(),
                             max_new_tokens=4) for i in range(3)])
    finally:
        obs_trace.uninstall()
    names = {e["name"] for e in tracer.events}
    assert {"serve.run", "serve.pass", "serve.admit",
            "pool.pages_live"} <= names
    # the registry mirrors the engine's ledgers exactly
    m = eng.metrics()
    assert registry.gauge("serving.decode_tokens").value == \
        float(m["decode_tokens"]) > 0
    assert registry.gauge("serving.clock").value == float(m["clock"])
    assert registry.gauge("pool.utilization").value == \
        pytest.approx(m["pool_utilization"])
    assert registry.counter("obs.monitor_checks").value >= 1.0
    assert registry.counter("obs.monitor_failures").value == 0.0

    path = os.path.join(tmp_path, "serve.trace.json")
    tracer.export_chrome(path)
    kind, errors = obs_validate.validate_file(path)
    assert (kind, errors) == ("trace", [])


def test_traced_fleet_smoke_reconciles_ledgers(registry, tmp_path):
    """The §13 acceptance for the fleet: a traced hierarchical run's
    ``fleet.tier_bits`` gauge equals BOTH the result's tier_bits sum
    and bits_cum[-1] exactly, the monitors pass, and the trace (with
    its virtual-clock twin track) validates."""
    from repro.core import (LogisticSigmoidProblem, RandK, SNice,
                            make_synthetic_classification)
    from repro.core.dasha_pp import DashaPPConfig
    from repro.fl import (ConstantLatency, DenseProblemWorkload,
                          FleetConfig, HierarchicalFleet, TierConfig)

    n, d = 6, 16
    feats, y = make_synthetic_classification(jax.random.key(0),
                                             n_nodes=n, m_per_node=5, d=d)
    prob = LogisticSigmoidProblem(feats, y)
    wl = DenseProblemWorkload(
        prob, RandK(k=4), SNice(n=n, s=3),
        DashaPPConfig("gradient", gamma=0.02, a=0.1, b=0.3, p_page=0.4,
                      batch_size=2))
    fleet = HierarchicalFleet(wl, FleetConfig(tiers=(TierConfig(
        aggregators=2),)), ConstantLatency(compute_s=1.0))
    tracer = obs_trace.configure()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", ObsWarning)
            fs, res = fleet.run(jax.random.key(7), jnp.zeros(d), 4)
    finally:
        obs_trace.uninstall()

    tier_total = float(np.sum(np.asarray(res.tier_bits)))
    assert registry.gauge("fleet.tier_bits").value == tier_total \
        == float(res.bits_cum[-1]) > 0
    assert registry.gauge("fleet.committed").value == \
        float(res.committed.sum())
    assert registry.histogram("fleet.staleness").count == \
        sum(res.staleness_hist.values())
    assert registry.counter("obs.monitor_failures").value == 0.0

    names = {e["name"] for e in tracer.events}
    assert {"fleet.dispatch", "fleet.flush", "fleet.commit",
            "fleet.bits_cum"} <= names
    # the virtual clock was published: twin events on pid 2
    assert {e["pid"] for e in tracer.events} == {obs_trace.WALL_PID,
                                                 obs_trace.VIRTUAL_PID}
    path = os.path.join(tmp_path, "fleet.trace.json")
    tracer.export_chrome(path)
    kind, errors = obs_validate.validate_file(path)
    assert (kind, errors) == ("trace", [])


@pytest.mark.slow
def test_traced_train_smoke_reconciles_bits_ledger():
    """The §13 acceptance for the trainer: with log_every=1 the
    ``train.bits_sent`` gauge equals the sum of the per-step jsonl
    ``bits_sent`` fields exactly, and the trace validates.  Subprocess
    + host mesh, same pattern as tests/test_training_resume.py."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent("""
        import json, os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, use_mesh
        from repro.models import Model, get_smoke_config
        from repro.core.sharded import ShardedDashaConfig
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        from repro.obs.validate import validate_file
        from repro.training.loop import train
        from repro.training.metrics import MetricsLogger
        from repro.training.trainer import Trainer, TrainerConfig
        from repro.training.optim import adamw_server

        mesh = make_mesh((2, 2), ('data', 'model'))
        cfg = get_smoke_config('granite-3-2b').with_overrides(vocab_size=64)
        model = Model(cfg)
        dcfg = ShardedDashaConfig(gamma=0.0, a=0.02, b=0.9, p_a=0.5,
                                  sampler='independent',
                                  compression_ratio=0.1, block_size=64,
                                  data_axes=('data',), variant='gradient')
        tr = Trainer(model, mesh, TrainerConfig(
            dasha=dcfg, server=adamw_server(lr=3e-3, warmup=5)))
        toks = jnp.tile(jnp.arange(32) % 7, (2, 2, 1)).astype(jnp.int32)
        def fixed():
            while True:
                yield {'tokens': toks}
        out = tempfile.mkdtemp()
        obs_trace.configure()
        with use_mesh(mesh):
            train(tr, tr.init(jax.random.key(0)), fixed(), num_steps=4,
                  log_every=1, seed=3,
                  logger=MetricsLogger(out_dir=out, print_every=1000))
        tracer = obs_trace.uninstall()
        tpath = os.path.join(out, 'train.trace.json')
        tracer.export_chrome(tpath)
        kind, errors = validate_file(tpath)
        assert (kind, errors) == ('trace', []), errors
        assert sum(1 for e in tracer.events
                   if e['name'] == 'train.dispatch') == 4
        with open(os.path.join(out, 'train.jsonl')) as f:
            recs = [json.loads(line) for line in f]
        assert len(recs) == 4
        jsonl_bits = sum(r['bits_sent'] for r in recs)
        gauge = obs_metrics.get_registry().gauge('train.bits_sent').value
        assert gauge == jsonl_bits > 0, (gauge, jsonl_bits)
        oracle = obs_metrics.get_registry().gauge('train.oracle_calls')
        assert oracle.value == sum(r['participants'] for r in recs)
        print('RECONCILED', gauge)
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=520,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "RECONCILED" in out.stdout
