"""Each cell, on the CPU at a small size: a sound run comes out correct,
and the control (the reference with Alg. 1 broken, in the program's
place) comes out not correct."""
import pytest

import benchtest

CELLS = ["serve-openb-grmu", "replay-openb-grmu", "sweep-openb-baskets"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = benchtest.run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["limit"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = benchtest.run(cell, control=True)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
