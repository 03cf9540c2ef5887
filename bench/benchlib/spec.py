"""Finding a cell's files by name.

``BENCHMARK.json`` at the checkout root lists the cells (``workloads``),
the configurations and the metrics.  Everything that belongs to one of
them sits in a file of its own under ``bench/``:

  * ``configs/<config>.json``   — the deployment (fleet, stream shape,
                                  policy knobs, the control's broken
                                  guarantee);
  * ``traffic/<mix>.json``      — the mix: which driver runs it and its
                                  parameters (rates, lengths, lanes);
  * ``workloads/<cell>.json``   — per-cell settings, such as how long the
                                  traced run profiles;
  * ``metrics/<metric>.py``     — a per-layer metric's reader.

Adding a cell, a configuration, a mix or a metric adds files and entries;
no code here changes.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
from typing import Callable, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def merge(base: dict, over: Optional[dict]) -> dict:
    """A deep copy of ``base`` with ``over``'s keys laid on top."""
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One ``workloads`` entry with its configuration, mix and settings
    read from their files."""

    def __init__(self, name: str, bench: Optional[dict] = None,
                 root: str = ROOT, overrides: Optional[dict] = None):
        bench = bench or benchmark(root)
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(entries)})")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        cfg_entry = configs[self.entry["config"]]
        over = overrides or {}
        self.config = merge(load_json(os.path.join(root, cfg_entry["file"])),
                            over.get("config"))
        self.traffic = merge(load_json(os.path.join(
            BENCH, "traffic", self.entry["traffic"] + ".json")),
            over.get("traffic"))
        cell_file = os.path.join(BENCH, "workloads", name + ".json")
        self.settings = merge(load_json(cell_file)
                              if os.path.exists(cell_file) else {},
                              over.get("settings"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def metric_reader(name: str) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


__all__ = ["BENCH", "ROOT", "Cell", "merge", "benchmark", "metric_reader",
           "load_json"]
