"""What the drivers share: handing the generated rows to the program,
the program's flight recorder in traced runs, and the control."""
from __future__ import annotations

import contextlib
import json
import os

import numpy as np

from .. import reference


def grmu_policy(cfg: dict) -> int:
    """The program's GRMU id.  The reference is GRMU alone, so a
    configuration that names another policy is refused, not run as GRMU."""
    from repro.core import batched as B

    reference.require_grmu(cfg["policy"])
    return B.GRMU


def device_model(cfg: dict):
    """The program's device model named by the configuration, checked
    against the configuration's own profile table."""
    from repro.core.mig import get_model

    fleet = cfg["fleet"]
    model = get_model(fleet["device"])
    mine = [(p["name"], p["size"], p["compute"], tuple(p["starts"]))
            for p in fleet["profiles"]]
    theirs = [(p.name, p.size, p.compute, tuple(p.start_blocks))
              for p in model.profiles]
    if mine != theirs or model.num_blocks != fleet["num_blocks"]:
        raise ValueError(f"{fleet['device']}: the configuration's profile "
                         "table differs from the program's")
    return model


def build_events(cfg: dict, data: dict, n_vms=None):
    """The program's event trace of the first ``n_vms`` generated VMs."""
    from repro.core import batched as B

    fleet = cfg["fleet"]
    n = len(data["arrival"]) if n_vms is None else int(n_vms)
    counts = data["gpu_counts"]
    H, G = len(counts), int(counts.sum())
    return B.build_events_arrays(
        arrival=data["arrival"][:n], duration=data["duration"][:n],
        cpu=data["cpu"][:n], ram=data["ram"][:n],
        vm_ids=np.arange(n, dtype=np.int64),
        pids=data["pid"][:n].reshape(n, 1), models=(device_model(cfg),),
        gpu_model_id=np.zeros(G, np.int32),
        gpu_host_id=np.repeat(np.arange(H), counts).astype(np.int32),
        cpu_cap=np.full(H, fleet["host_cpu"], np.float32),
        ram_cap=np.full(H, fleet["host_ram"], np.float32),
        step_hours=cfg["stream"]["step_hours"])


def replay_knobs(cfg: dict) -> dict:
    pol = cfg["policy"]
    return dict(defrag=pol["defrag"], defrag_trigger=pol["defrag_trigger"],
                consolidation_interval=pol["consolidation_interval"])


@contextlib.contextmanager
def recorder(run):
    """In a traced run, the program's flight recorder for the window;
    its spans land in ``run.program_spans``."""
    if not run.traced:
        yield None
        return
    from repro.obs import recorder as obs_recorder

    os.makedirs(run.out_dir, exist_ok=True)
    path = os.path.join(run.out_dir, "spans.jsonl")
    if os.path.exists(path):
        os.remove(path)
    os.environ.pop("REPRO_TRACE", None)
    with obs_recorder.record(path, meta={"cell": run.cell.name}) as rec:
        yield rec
    with open(path) as fh:
        run.program_spans = [r for r in map(json.loads, fh)
                             if r.get("kind") == "span"]
    os.remove(path)


def control_tables(cfg: dict) -> reference.MigTables:
    """The control's broken Alg. 1 (``control.block_rule``)."""
    return reference.MigTables(cfg["fleet"],
                               block_rule=cfg["control"]["block_rule"])


def control_serve(cfg, data, n, horizon, program: dict):
    """The control's answers in the service's form, each in the
    micro-batch where the program made that decision (``program``)."""
    ctl = reference.simulate(cfg["fleet"], cfg["policy"], data, n_vms=n,
                             horizon=horizon, tables=control_tables(cfg))
    got = {}
    for vm, d in program.items():
        acc = bool(ctl["accepted"][vm])
        g, s = ctl["history"][vm][0][1:] if acc else (-1, 0)
        got[vm] = (acc, g, s, None, d[4])
    ids = [int(v) for v in np.flatnonzero(ctl["accepted"])]
    return got, (ctl["intra"], ctl["inter"]), ids
