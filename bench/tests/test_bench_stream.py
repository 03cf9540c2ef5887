"""The stream generator: one seed, one stream; the paper's shape."""
import json
import os

import numpy as np
import pytest

import benchtest
from benchlib import stream

CFG = json.load(open(os.path.join(benchtest.BENCH, "configs",
                                  "openb-grmu.json")))


def test_one_seed_gives_identical_streams():
    a = stream.generate(CFG, 2**31 + 12345)
    b = stream.generate(CFG, 2**31 + 12345)
    for k in ("gpu_counts", "arrival", "duration", "pid", "cpu", "ram"):
        assert np.array_equal(a[k], b[k]), k


def test_seeds_differ():
    a = stream.generate(CFG, 1)
    b = stream.generate(CFG, 2)
    assert not np.array_equal(a["arrival"], b["arrival"])


def test_paper_shape():
    s = stream.generate(CFG, 7)
    assert len(s["gpu_counts"]) == 1213
    assert len(s["arrival"]) == 8063
    assert s["arrival"].max() == pytest.approx(720.0)
    assert np.all(np.diff(s["arrival"]) > 0)
    assert set(np.unique(s["gpu_counts"])) <= {1, 2, 4}
    # 7g.40gb is the largest share of the Fig. 5 mix.
    assert np.bincount(s["pid"], minlength=6).argmax() == 5


def test_a_longer_stream_keeps_the_hourly_rate():
    s = stream.generate(CFG, 7, n_vms=4 * 8063)
    assert s["arrival"].max() == pytest.approx(4 * 720.0)
    assert s["horizon"] == pytest.approx(4 * 720.0)


def test_same_stream_as_the_program_generator():
    from repro.core.mig import A100_40GB
    from repro.workload.alibaba import TraceConfig, generate
    cfg = json.loads(json.dumps(CFG))
    cfg["fleet"]["hosts"] = 121
    cfg["stream"]["vms"] = 806
    s = stream.generate(cfg, 3)
    cluster, vms = generate(TraceConfig(scale=0.1, seed=3))
    assert np.array_equal(s["arrival"], [v.arrival for v in vms])
    assert np.array_equal(s["duration"], [v.duration for v in vms])
    assert np.array_equal(
        s["pid"], [A100_40GB.profile_index[v.profile.name] for v in vms])
    assert np.array_equal(s["cpu"], [v.cpu for v in vms])
    assert np.array_equal(s["ram"], [v.ram for v in vms])
    assert int(s["gpu_counts"].sum()) == cluster.num_gpus

