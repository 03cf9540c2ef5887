"""The plain reference against the program, on the CPU at small sizes:
the same decisions, hourly series and migrations."""
import json
import os

import numpy as np
import pytest

import benchtest
from benchlib import reference, stream
from benchlib.drivers import common, replay

CFG = json.load(open(os.path.join(benchtest.BENCH, "configs",
                                  "openb-grmu.json")))


def small(hosts=242, vms=1612, **stream_over):
    cfg = json.loads(json.dumps(CFG))
    cfg["fleet"]["hosts"] = hosts
    cfg["stream"]["vms"] = vms
    cfg["stream"].update(stream_over)
    return cfg


def test_alg1_and_fragmentation_tables_match_the_program():
    from repro.core.mig import A100_40GB
    from repro.core.tables import tables_for_model
    mine = reference.MigTables(CFG["fleet"])
    theirs = tables_for_model(A100_40GB)
    assert np.array_equal(mine.fits, theirs.fits)
    assert np.array_equal(np.where(mine.fits, mine.start, -1),
                          theirs.assign_start)
    assert np.array_equal(np.where(mine.fits, mine.after, 0),
                          theirs.assign_mask)
    assert np.array_equal(mine.frag, theirs.frag)


def test_control_tables_break_alg1():
    good = reference.MigTables(CFG["fleet"])
    bad = reference.MigTables(CFG["fleet"], block_rule="first")
    assert np.array_equal(good.fits, bad.fits)
    assert (good.start != bad.start).any()
    # The paper's §7.1 example: a 1g.5gb on an empty GPU goes to block 6.
    assert good.start[0xFF, 0] == 6 and bad.start[0xFF, 0] == 0


def _program(cfg, data):
    from repro.core import batched as B
    from repro.core.bucketing import pad_events
    pv = pad_events(common.build_events(cfg, data))
    return replay.as_answers(B.replay(
        pv, B.GRMU, heavy_capacity=B.default_heavy_capacity(
            pv, cfg["policy"]["heavy_capacity_frac"]),
        **common.replay_knobs(cfg)))


@pytest.mark.parametrize("seed", [11, 2**33 + 1])
def test_grmu_with_defrag_matches_the_program(seed):
    cfg = small()
    data = stream.generate(cfg, seed)
    ref = replay.ref_answers(cfg, reference.simulate(
        cfg["fleet"], cfg["policy"], data))
    assert ref["migrations"][0] > 0          # defrag moved something
    assert _program(cfg, data) == ref


def test_consolidation_matches_the_program():
    # Short lifetimes leave half-full GPUs, so Alg. 5 has pairs to merge.
    cfg = small(mean_duration_hours=48.0)
    cfg["policy"]["consolidation_interval"] = 6.0
    data = stream.generate(cfg, 9)
    ref = replay.ref_answers(cfg, reference.simulate(
        cfg["fleet"], cfg["policy"], data))
    assert ref["migrations"][1] > 0
    assert _program(cfg, data) == ref
