"""The trace reduction: on a small trace written here, whose every number
is known, and on a trace recorded on a TPU v5e (trimmed to a few
rounds, ``chipbench/tests/data``)."""
from __future__ import annotations

import os

import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000  # ns


def _xspace(device_events, host_events) -> str:
    """Text XSpace: ``device_events`` (name, start_ns, dur_ns, tf_op,
    category) on ``/device:TPU:0``'s ``XLA Ops`` line, ``host_events``
    (name, start_ns, dur_ns) on the host's ``python`` line."""
    def plane(pid, name, line, events, with_stats):
        names = sorted({e[0] for e in events})
        mid = {n: i + 1 for i, n in enumerate(names)}
        out = [f'planes {{ id: {pid} name: "{name}"',
               f'  lines {{ id: 1 name: "{line}" timestamp_ns: 0']
        for e in events:
            out.append(f"    events {{ metadata_id: {mid[e[0]]} "
                       f"offset_ps: {e[1] * 1000} "
                       f"duration_ps: {e[2] * 1000} }}")
        out.append("  }")
        for e in {e[0]: e for e in events}.values():
            stats = ""
            if with_stats:
                stats = (f' stats {{ metadata_id: 1 str_value: "{e[3]}" }}'
                         f' stats {{ metadata_id: 2 str_value: "{e[4]}" }}')
            out.append(f'  event_metadata {{ key: {mid[e[0]]} value {{ '
                       f'id: {mid[e[0]]} name: "{e[0]}"{stats} }} }}')
        if with_stats:
            out.append('  stat_metadata { key: 1 value { id: 1 '
                       'name: "tf_op" } }')
            out.append('  stat_metadata { key: 2 value { id: 2 '
                       'name: "hlo_category" } }')
        out.append("}")
        return "\n".join(out)

    return "\n".join([plane(1, "/device:TPU:0", "XLA Ops", device_events,
                            True),
                      plane(2, "/host:CPU", "python", host_events, False)])


def _trace():
    from jax.profiler import ProfileData

    k = "jit(step)/repro.kernel.dasha_h_update/mul"
    dev = [
        # a loop whose body holds two ops: its self time is 2 ms
        ("%while.1 = (f32[]) while()", 10 * MS, 10 * MS, "jit(step)/while",
         "while"),
        ("%fusion.2 = f32[8] fusion()", 11 * MS, 4 * MS, "jit(step)/dot",
         "convolution fusion"),
        ("%dasha_h_update_pallas.3 = f32[8] custom-call()", 16 * MS,
         4 * MS, k, "custom-call"),
        ("%all-gather.4 = f32[8] all-gather()", 24 * MS, 4 * MS,
         "jit(step)/all_gather", "all-gather"),
        ("%fusion.5 = f32[8] fusion()", 26 * MS, 6 * MS, "jit(step)/add",
         "loop fusion"),
        # outside the window
        ("%fusion.6 = f32[8] fusion()", 45 * MS, 3 * MS, "jit(step)/add",
         "loop fusion"),
    ]
    host = [("bench.window", 5 * MS, 35 * MS),
            ("train.dispatch", 20 * MS, 4 * MS),
            ("train.wait", 32 * MS, 8 * MS)]
    text = _xspace(dev, host)
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    return tr.from_profile(ProfileData.from_serialized_xspace(raw),
                           tr.read_op_metadata(raw)), raw


def test_metadata_walk_reads_scopes_and_categories():
    _, raw = _trace()
    meta = tr.read_op_metadata(raw)
    assert set(meta) == {"/device:TPU:0"}
    ops = meta["/device:TPU:0"]
    assert ops["%all-gather.4 = f32[8] all-gather()"]["hlo_category"] == \
        "all-gather"
    assert "repro.kernel.dasha_h_update" in \
        ops["%dasha_h_update_pallas.3 = f32[8] custom-call()"]["tf_op"]


def test_window_busy_and_self_times():
    t, _ = _trace()
    assert t.window == (5 * MS, 40 * MS)
    assert t.window_s == pytest.approx(0.035)
    # busy: [10, 20) and [24, 32) inside the window
    assert tr.busy_s(t) == pytest.approx(0.018)
    ops = {o.name: o for o in t.devices["/device:TPU:0"]}
    assert ops["while.1"].self_ns == 2 * MS
    assert ops["fusion.2"].self_ns == 4 * MS


def test_kernel_scope_collectives_and_breakdown():
    t, _ = _trace()
    assert tr.scope_s(t, ["dasha_"]) == pytest.approx(0.004)
    assert tr.scope_s(t, ["paged_attention"]) is None
    top = dict(tr.top_ops(t))
    assert top["repro.kernel.dasha_h_update/dasha_h_update_pallas.3"] == \
        pytest.approx(0.004)
    assert "fusion.6" not in top
    gaps = dict(tr.idle_gaps(t, ["train.dispatch", "train.wait"]))
    # idle: [5, 10) host.other, [20, 24) dispatch, [32, 40) wait
    assert gaps == pytest.approx({"host.other": 0.005,
                                  "train.dispatch": 0.004,
                                  "train.wait": 0.008})


def test_recorded_train_slice():
    # 0.18 s of a DASHA-PP round of train-d8-seq4k: the end of one
    # round's update kernels and the dispatch of the next
    t = tr.load(os.path.join(DATA, "train_slice.xplane.pb.gz"))
    assert list(t.devices) == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(0.18)
    assert tr.busy_s(t) == pytest.approx(0.177244251)
    assert tr.scope_s(t, ["dasha_", "block_"]) == pytest.approx(0.026837737)
    assert tr.scope_s(t, ["paged_attention"]) is None
    name, sec = tr.top_ops(t)[0]
    assert name.startswith("repro.kernel.dasha_h_update/")
    assert sec == pytest.approx(0.003140693)
    assert dict(tr.idle_gaps(t, ["train.dispatch", "train.wait"])) == \
        pytest.approx({"train.wait": 0.001738533,
                       "train.dispatch": 0.001017216})


def test_recorded_serve_slice():
    # 0.3 s of serve traffic: one fused 40-layer pass and the wait for
    # the next arrival
    t = tr.load(os.path.join(DATA, "serve_slice.xplane.pb.gz"))
    assert t.window_s == pytest.approx(0.3)
    assert tr.busy_s(t) == pytest.approx(0.244956924)
    assert tr.scope_s(t, ["paged_attention"]) == pytest.approx(0.111070661)
    top = tr.top_ops(t)
    assert top[0][0] == ("repro.kernel.paged_attention_batched/"
                         "paged_attention_batched_pallas.8")
    assert len(top) == 10
    gaps = dict(tr.idle_gaps(t, ["engine.step", "serve.admit",
                                 "serve.enqueue", "serve.idle"]))
    assert gaps == pytest.approx({"serve.idle": 0.051996059,
                                  "engine.step": 0.003047017})
    # busy and idle account for the whole window
    assert tr.busy_s(t) + sum(gaps.values()) == pytest.approx(t.window_s)


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        _xspace([("%f.1 = f32[] fusion()", 0, MS, "x", "y")],
                [("other", 0, MS)]))
    with pytest.raises(ValueError, match="bench.window"):
        tr.from_profile(ProfileData.from_serialized_xspace(raw),
                        tr.read_op_metadata(raw))
