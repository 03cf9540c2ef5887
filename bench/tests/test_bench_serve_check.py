"""The served cell's comparison: a decision is held to the positions the
reference gave the VM while its micro-batch could have been ending, and
a configuration the reference cannot follow is refused."""
import json
import os

import numpy as np
import pytest

import benchtest
from benchlib import reference, stream
from benchlib.drivers import serve

CFG = json.load(open(os.path.join(benchtest.BENCH, "configs",
                                  "openb-grmu.json")))


def test_other_policy_is_refused():
    cfg = json.loads(json.dumps(CFG))
    cfg["stream"]["vms"] = 50
    cfg["policy"]["name"] = "FF"
    with pytest.raises(ValueError, match="GRMU only"):
        reference.simulate(cfg["fleet"], cfg["policy"],
                           stream.generate(cfg, 1))


@pytest.mark.parametrize("cell", ["serve-openb-grmu", "replay-openb-grmu",
                                  "sweep-openb-baskets"])
def test_driver_refuses_other_policy(cell):
    with pytest.raises(ValueError, match="GRMU only"):
        benchtest.run(cell, overrides={"config": {"policy": {"name": "FF"}}})


@pytest.mark.parametrize("popped,submitted,want", [
    (2, 2, (2, 2)),      # queue empty: the batch ended inside step 1
    (2, 5, (2, 2)),      # next request in the same step: no step closed
    (3, 5, (2, 7)),      # next one in step 4: steps 1 to 3 may close
])
def test_batch_stamps(popped, submitted, want):
    steps = np.array([0, 1, 1, 4, 4])
    assert serve.batch_stamps(steps, popped, submitted) == want


def test_held_keeps_the_positions_inside_the_batch():
    # Placed at step 1, moved by defrag at the end of steps 3 and 6.
    hist = [(2, 5, 6), (7, 5, 0), (13, 5, 4)]
    assert serve.held(hist, 2, 2) == {(5, 6)}
    assert serve.held(hist, 2, 7) == {(5, 6), (5, 0)}
    assert serve.held(hist, 8, 13) == {(5, 0), (5, 4)}
    assert serve.held(hist, 14, 20) == {(5, 4)}


def _ref(hist):
    return {"accepted": np.array([True]), "history": {0: hist}}


def test_a_later_position_is_wrong():
    steps = np.array([1, 1, 2])
    ref = _ref([(2, 5, 6), (7, 5, 0)])
    # Decided with two requests popped and one queued in step 2: the
    # batch ended in step 1 or at its end, never at the end of step 3.
    ok = (True, 5, 6, 0.0, 2)
    late = (True, 5, 0, 0.0, 2)
    assert not serve.decision_wrong(ok, ref, 0, steps, {}, 3)
    assert serve.decision_wrong(late, ref, 0, steps, {}, 3)
    assert serve.decision_wrong((False, -1, 0, 0.0, 2), ref, 0, steps,
                                {}, 3)


def test_no_gpu_only_for_a_vm_that_left_in_its_batch():
    steps = np.array([1, 2, 3])
    ref = _ref([(2, 5, 6)])
    gone = (True, -1, 0, 0.0, 3)
    assert not serve.decision_wrong(gone, ref, 0, steps, {0: 2}, 3)
    assert serve.decision_wrong(gone, ref, 0, steps, {0: 5}, 3)
    assert serve.decision_wrong(gone, ref, 0, steps, {}, 3)


def test_reference_stamps_follow_the_steps():
    cfg = json.loads(json.dumps(CFG))
    cfg["fleet"]["hosts"] = 120
    cfg["stream"]["vms"] = 800
    data = stream.generate(cfg, 5)
    ref = reference.simulate(cfg["fleet"], cfg["policy"], data)
    for vm, hist in ref["history"].items():
        first = hist[0][0]
        assert first == 2 * reference.arrival_step(data["arrival"][vm])
        stamps = [h[0] for h in hist]
        assert stamps == sorted(stamps)
        assert all(s % 2 == 1 for s in stamps[1:])   # moved at step ends
