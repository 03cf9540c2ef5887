"""The mixed-generation fleet (``openb-mixed-grmu``) on the CPU at small
sizes: its stream, its plain reference against the program and against
the one-model reference, the readers of the program's ``replay.prepare``
span, and the cell's sound and control runs."""
import json
import os
import time

import numpy as np
import pytest

import benchtest
from benchlib import (harness, reference, reference_mixed, spec, stream,
                      stream_mixed)
from benchlib.drivers import common, replay, replay_mixed

CELL = "replay-openb-mixed"
CFG = json.load(open(os.path.join(benchtest.BENCH, "configs",
                                  "openb-mixed-grmu.json")))
ONE = json.load(open(os.path.join(benchtest.BENCH, "configs",
                                  "openb-grmu.json")))


def small(cfg=CFG, hosts=242, vms=1612, consolidation=None, **stream_over):
    cfg = json.loads(json.dumps(cfg))
    cfg["fleet"]["hosts"] = hosts
    cfg["stream"]["vms"] = vms
    cfg["stream"].update(stream_over)
    cfg["policy"]["consolidation_interval"] = consolidation
    return cfg


def one_model(cfg):
    """``cfg`` with ``fleet.devices`` cut to its reference device."""
    out = json.loads(json.dumps(cfg))
    out["fleet"]["devices"] = [dict(out["fleet"]["devices"][0], share=1.0)]
    return out


def test_profile_tables_are_the_programs():
    from repro.core.mig import get_model
    models = replay_mixed.device_models(CFG)
    assert [m.name for m in models] == [d["device"]
                                        for d in CFG["fleet"]["devices"]]
    assert models == tuple(get_model(n) for n in
                           ("A100-40GB", "A30-24GB", "H100-80GB"))


def test_stream_is_the_one_model_stream():
    a = stream_mixed.generate(CFG, 2**31 + 77)
    b = stream.generate(ONE, 2**31 + 77)
    for k in ("gpu_counts", "arrival", "duration", "cpu", "ram"):
        assert np.array_equal(a[k], b[k]), k
    assert a["pid"].shape == (8063, 3)
    assert np.array_equal(a["pid"][:, 0], b["pid"])
    assert np.array_equal(a["gpu_model"],
                          np.repeat(a["host_model"], a["gpu_counts"]))
    share = np.bincount(a["host_model"], minlength=3) / len(a["host_model"])
    assert np.allclose(share, [0.50, 0.25, 0.25], atol=0.05)


def test_stream_is_the_program_generators_fleet_mix():
    from repro.workload.alibaba import TraceConfig, generate
    cfg = small(hosts=121, vms=806)
    s = stream_mixed.generate(cfg, 3)
    names = [d["device"] for d in cfg["fleet"]["devices"]]
    shares = {d["device"]: d["share"] for d in cfg["fleet"]["devices"]}
    cluster, vms = generate(TraceConfig(scale=0.1, seed=3, fleet=shares))
    assert [m.name for m in cluster.models] == names
    assert np.array_equal(s["gpu_model"], cluster.gpu_model_id)
    assert np.array_equal(s["pid"], [v.profile_ids for v in vms])
    assert np.array_equal(s["arrival"], [v.arrival for v in vms])


def _program(cfg, data):
    from repro.core import batched as B
    from repro.core.bucketing import pad_events
    pv = pad_events(replay_mixed.build_events(cfg, data))
    return replay.as_answers(B.replay(
        pv, B.GRMU, heavy_capacity=B.default_heavy_capacity(
            pv, cfg["policy"]["heavy_capacity_frac"]),
        **common.replay_knobs(cfg)))


@pytest.mark.parametrize("seed,consolidation,stream_over", [
    (11, None, {}),
    (2**33 + 1, None, {}),
    # Short lifetimes leave half-full GPUs, so Alg. 5 has pairs to merge.
    (9, 6.0, {"mean_duration_hours": 48.0}),
])
def test_program_matches_the_mixed_reference(seed, consolidation,
                                             stream_over):
    cfg = small(consolidation=consolidation, **stream_over)
    data = stream_mixed.generate(cfg, seed)
    assert set(data["gpu_model"]) == {0, 1, 2}
    ref = replay_mixed.ref_answers(cfg, reference_mixed.simulate(
        cfg["fleet"], cfg["policy"], data))
    moved = ref["migrations"][1 if consolidation else 0]
    assert moved > 0
    assert _program(cfg, data) == ref


@pytest.mark.parametrize("seed,consolidation,stream_over", [
    (11, None, {}),
    (9, 6.0, {"mean_duration_hours": 48.0}),
])
def test_one_model_list_is_the_one_model_reference(seed, consolidation,
                                                   stream_over):
    cfg = one_model(small(consolidation=consolidation, **stream_over))
    flat = small(ONE, consolidation=consolidation, **stream_over)
    mixed = reference_mixed.simulate(
        cfg["fleet"], cfg["policy"], stream_mixed.generate(cfg, seed))
    plain = reference.simulate(flat["fleet"], flat["policy"],
                               stream.generate(flat, seed))
    assert mixed["intra"] + mixed["inter"] > 0
    assert mixed.keys() == plain.keys()
    for k in mixed:
        if isinstance(mixed[k], np.ndarray):
            assert np.array_equal(mixed[k], plain[k]), k
        else:
            assert mixed[k] == plain[k], k


def test_control_breaks_alg1_on_every_model():
    for good, bad in zip(
            [reference.MigTables(d) for d in CFG["fleet"]["devices"]],
            replay_mixed.control_tables(CFG)):
        assert np.array_equal(good.fits, bad.fits)
        assert (good.start != bad.start).any()


def test_readers_read_the_programs_prepare_spans(tmp_path):
    """A traced window opens the program's recorder; the readers take
    ``replay.prepare``'s counter and mean time from its spans, and give
    nothing where the program records no such span."""
    from repro.core.mig import get_model
    cell = spec.Cell(CELL, overrides=benchtest.SMALL)
    run = harness.Run(cell, 5, 0.3, traced=True)
    run.trace_start = run.trace_stop = lambda: None
    run.out_dir = str(tmp_path)
    driver = harness._driver(cell.traffic["driver"])
    st = driver.setup(run)
    driver.window(run, st, time.perf_counter())
    spans = [s for s in run.program_spans if s["name"] == "replay.prepare"]
    assert len(spans) == run.info["replays"] > 0
    slots = sum(get_model(d["device"]).num_slots
                for d in CFG["fleet"]["devices"])
    assert slots == 45
    G = len(st.padded.gpu_model_id)
    assert spec.metric_reader("replay.slot_tests")(run) == G * slots
    ms = spec.metric_reader("replay.prepare_ms")(run)
    assert ms == pytest.approx(
        1e3 * sum(s["dur_s"] for s in spans) / len(spans))
    run.program_spans = []
    assert spec.metric_reader("replay.slot_tests")(run) is None
    assert spec.metric_reader("replay.prepare_ms")(run) is None


def test_sound_run_is_correct():
    res = benchtest.run(CELL)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())


def test_control_is_not_correct():
    res = benchtest.run(CELL, control=True)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
