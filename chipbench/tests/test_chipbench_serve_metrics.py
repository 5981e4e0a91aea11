"""The serve driver's latency statistics on synthetic pass times: time to
first token at the highest percentile its sample supports, named by
it; the gaps between tokens pooled over every request due; a refused
request counted as failed, in the statistics and in a whole run."""
from __future__ import annotations

import io
import json
import types
from contextlib import redirect_stdout

import jax
import numpy as np
import pytest

from chipbench import run, traffic as gen
from chipbench.drivers import serve
from chipbench.tests import tiny


@pytest.mark.parametrize("n, q", [(99, None), (100, 90), (199, 90),
                                  (200, 95), (999, 95), (1000, 99)])
def test_the_tail_has_ten_samples_beyond_it(n, q):
    assert serve.tail_percentile(n) == q


def _schedule(n: int, pass_s: float = 0.2, tokens: int = 5):
    """``n`` requests due 0.1 s apart, each served ``tokens`` tokens by
    back-to-back passes of ``pass_s`` seconds from the pass after it was
    due."""
    due = {u: 0.1 * u for u in range(n)}
    stamps = {}
    for u, d in due.items():
        start = (np.floor(d / pass_s) + 1) * pass_s
        stamps[u] = [start + pass_s * k for k in range(tokens)]
    return due, stamps


@pytest.mark.parametrize("n, name", [(199, "serve_ttft_p90_s"),
                                     (200, "serve_ttft_p95_s")])
def test_time_to_first_token_is_named_by_its_percentile(n, name):
    due, stamps = _schedule(n)
    e2e, counts = serve.latency(due, stamps, set(due), gave_up=1e3)
    tails = [k for k in e2e if k.startswith("serve_ttft_p")
             and k != "serve_ttft_p50_s"]
    assert tails == [name]
    ttft = [stamps[u][0] - due[u] for u in due]
    q = int(name[len("serve_ttft_p"):-len("_s")])
    assert e2e[name] == pytest.approx(np.percentile(ttft, q))
    assert e2e["serve_ttft_p50_s"] == pytest.approx(np.median(ttft))
    assert counts == {"requests": n, "gaps": 4 * n, "failed": 0}


def test_gaps_between_tokens_are_pooled_not_averaged_per_request():
    # 20 requests, each with 18 gaps of 0.1 s and 2 of 2.0 s: every
    # request's mean gap is 0.29 s, but a tenth of all gaps read 2.0 s
    due = {u: float(u) for u in range(20)}
    widths = [0.1] * 18 + [2.0] * 2
    stamps = {u: list(d + 1.0 + np.concatenate([[0.0], np.cumsum(widths)]))
              for u, d in due.items()}
    e2e, counts = serve.latency(due, stamps, set(due), gave_up=1e3)
    assert counts["gaps"] == 400
    assert e2e["serve_tpot_p95_s"] == pytest.approx(2.0)
    assert e2e["serve_tpot_p50_s"] == pytest.approx(0.1)
    means = [np.mean(np.diff(s)) for s in stamps.values()]
    assert np.percentile(means, 95) == pytest.approx(0.29)


def test_only_requests_due_in_the_window_count():
    due, stamps = _schedule(120)
    # a request of the lead, before the window, with one very late token
    stamps[-1] = [0.0, 500.0]
    e2e, counts = serve.latency(due, stamps, set(due), gave_up=1e3)
    assert counts["gaps"] == 4 * 120
    assert e2e["serve_tpot_p95_s"] == pytest.approx(0.2)


def test_a_refused_request_fails_and_misses_its_first_token():
    due, stamps = _schedule(150)
    refused = 149
    del stamps[refused]
    finished = set(due) - {refused}
    e2e, counts = serve.latency(due, stamps, finished, gave_up=1e3)
    assert counts["failed"] == 1 and counts["requests"] == 150
    ttft = [stamps[u][0] - due[u] for u in finished] + [1e3 - due[refused]]
    assert e2e["serve_ttft_p90_s"] == pytest.approx(np.percentile(ttft, 90))


def test_a_run_counts_a_refused_request_as_failed(monkeypatch):
    from repro.serving import PagedEngine

    cell = tiny.serve_cell(tiny.SERVE_LIMITS)
    args = types.SimpleNamespace(seconds=2.0, seed=2 ** 33 + 7, trace=0)
    reqs = gen.requests(cell.traffic, args.seconds, args.seed,
                        cell.config["model"]["vocab_size"])
    # the first request due in the window, refused as the engine refuses
    # a prompt it cannot hold
    refused = min(q.uid for q in reqs if q.due_s >= 0)
    real = PagedEngine.enqueue

    def enqueue(self, req):
        if req.uid == refused:
            raise ValueError("refused")
        return real(self, req)

    monkeypatch.setattr(PagedEngine, "enqueue", enqueue)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run._run(cell, args, jax.devices()[:1], None) == 0
    lines = buf.getvalue().strip().splitlines()
    line = json.loads(lines[-1])
    assert any(", 1 refused;" in s for s in lines), lines
    assert line["failed"] == 1
    assert line["attempted"] == sum(q.due_s >= 0 for q in reqs)
    assert line["correct"], line["checks"]


def test_a_traced_run_records_the_window_span(tmp_path):
    from chipbench import trace_reduce

    cell = tiny.serve_cell(tiny.SERVE_LIMITS)
    with redirect_stdout(io.StringIO()):
        out = serve.run(cell, 1.0, 2 ** 33 + 9, jax.devices()[:1], 0.0,
                        trace_dir=str(tmp_path))
    tr = trace_reduce.load(str(tmp_path))
    assert tr.window[1] > tr.window[0]
    assert {"engine.step", "serve.enqueue"} <= {n for n, _, _ in tr.host}
    assert out["units"] > 0
