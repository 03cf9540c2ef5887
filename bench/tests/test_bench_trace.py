"""The reduction from a profiler trace to busy time, op self time and
idle gaps by host span, on hand-made traces with known answers, and the
capture of host spans from a trace the profiler wrote."""
import pytest

import benchtest  # noqa: F401  (paths)
from benchlib import trace

DEV = "/device:TPU:0"


def captured(ops, modules=(), host=()):
    return {"devices": {DEV: {"XLA Ops": list(ops),
                              "XLA Modules": list(modules)}},
            "host": [(trace.WINDOW_SPAN, 0, 1000)] + list(host)}


def test_union():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_self_time_subtracts_nested_ops():
    st = trace.self_times([("A", 100, 200), ("B", 200, 50),
                           ("C", 500, 100)])
    assert st == {"A": 150, "B": 50, "C": 100}


def test_reduce_busy_idle_and_labels():
    r = trace.reduce(captured(
        ops=[("A", 100, 200), ("B", 200, 50), ("C", 500, 100)],
        modules=[("jit_step", 100, 200), ("jit_step", 500, 100)],
        host=[("bench.drain", 0, 400), ("serve.batch", 100, 250),
              ("bench.wait_arrival", 400, 600)]))
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["device_ops"] == [["A", 150e-9], ["C", 100e-9],
                               ["B", 50e-9]]
    assert r["modules"] == [["jit_step", 300e-9]]
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench.drain": 100e-9,
                                  "bench.wait_arrival": 600e-9})
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_reduce_clips_to_the_window_and_averages_chips():
    c = captured(ops=[("A", -100, 300), ("B", 900, 300)])
    c["devices"]["/device:TPU:1"] = {"XLA Ops": [("A", 0, 1000)]}
    r = trace.reduce(c)
    assert r["chips"] == 2
    # chip 0: 200 + 100 busy; chip 1: 1000 busy.
    assert r["busy_s"] == pytest.approx((300 + 1000) / 2 * 1e-9)
    assert dict(r["idle_gaps"]) == pytest.approx({"no_span": 350e-9})


def test_reduce_needs_the_window_and_a_device():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": []})
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": [(trace.WINDOW_SPAN, 0, 10)]})


def test_capture_reads_host_spans_from_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.drain"):
                jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    cap = trace.capture(trace.find_xplane(str(tmp_path)))
    names = [e[0] for e in cap["host"]]
    assert names.count("bench.drain") == 3
    assert names.count(trace.WINDOW_SPAN) == 1
    win = next(e for e in cap["host"] if e[0] == trace.WINDOW_SPAN)
    for name, start, dur in cap["host"]:
        assert win[1] <= start and start + dur <= win[1] + win[2]


def test_op_label_keeps_name_and_shape():
    assert trace.op_label("%copy.88 = s32[16384,3]{1,0:T(8,128)} copy("
                          "s32[16384,3]{1,0:T(8,128)} %gte.801)") \
        == "copy.88 s32[16384,3]"
    assert trace.op_label("%cond.43 = (s32[2048]{0}, pred[]) "
                          "conditional(%x)") == "cond.43"
    assert trace.op_label("fusion.1") == "fusion.1"


def test_reduce_a_recorded_chip_trace():
    # 50 ms of the served cell on one TPU v5e, as
    # bench/tools/record_trace.py captured it.
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "serve-openb-grmu.trace.json.gz")
    with gzip.open(path, "rt") as fh:
        rec = json.load(fh)
    r = trace.reduce(rec["captured"])
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(rec["device"]["busy_s"])
    assert r["window_s"] == pytest.approx(rec["device"]["window_s"])
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert set(gaps) <= {"bench.drain", "serve.batch", "no_span"}
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    names = [n for n, _ in r["device_ops"]]
    assert all(" = " not in n for n in names)
    assert any(n.startswith("jit_step") for n, _ in r["modules"])


def test_serve_readers_keep_the_traced_batches():
    from types import SimpleNamespace
    from benchlib import spec
    # Three drains, the profiler stopped after the second: the third
    # batch ran behind the stall and is left out.
    run = SimpleNamespace(
        spans=[("bench.drain", 0.0, 0.010), ("bench.wait_arrival", 0.010,
                                             0.011),
               ("bench.drain", 0.011, 0.019), ("bench.drain", 5.0, 5.5)],
        program_spans=[{"name": "serve.batch", "dur_s": d}
                       for d in (0.006, 0.005, 0.4)],
        trace_closed=1.0)
    assert spec.metric_reader("serve.roundtrip_ms")(run) \
        == pytest.approx(5.5)
    assert spec.metric_reader("serve.assembly_ms")(run) \
        == pytest.approx(3.5)
