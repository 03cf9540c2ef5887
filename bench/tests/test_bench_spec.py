"""BENCHMARK.json against the benchmark's contract, and every cell's
files found by name."""
import json
import os
import re

import pytest

import benchtest
from benchlib import spec

B = spec.benchmark(benchtest.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(benchtest.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines():
    names = ([c["name"] for c in B["configs"]] + CELLS
             + [m["name"] for m in B["end_to_end"] + B["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in B["workloads"]]
                 + [c["why"] for c in B["configs"]]
                 + [c["source"] for c in B["configs"]]
                 + [m["layer"] for m in B["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text


def test_entry_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_each_config_is_used_once_and_pairs_are_unique():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    from benchlib import harness
    c = spec.Cell(cell)
    driver = harness._driver(c.traffic["driver"])
    assert driver.__file__ == os.path.join(
        spec.BENCH, "benchlib", "drivers", c.traffic["driver"] + ".py")
    assert all(callable(getattr(driver, f))
               for f in ("setup", "window", "check"))
    assert c.config["name"] == c.entry["config"]
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in e2e, (cell, m["name"])


def test_setup_bound_and_budget():
    setup = [m for m in B["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25
    # A full check of 24 cells fits its 43,200 s.
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_config_files_state_their_cut():
    for c in B["configs"]:
        cfg = json.load(open(os.path.join(benchtest.ROOT, c["file"])))
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["source"] == c["source"]
        assert cfg["control"]["block_rule"] in ("cc", "first")
