"""Offline replay on a fleet that mixes MIG device generations.

The window is ``replay``'s: back-to-back ``core.batched`` replays of one
trace, each ``make_replay`` (statics, trace upload), the jitted run ended
by ``block_until_ready`` (``bench.replay``) and ``result_from_arrays``.
What differs is the fleet: set-up hands the program every device model
of ``fleet.devices`` (``models``), each GPU's model (``gpu_model_id``)
and each VM's profile on every model (``pids``, (n, models)), from
``stream_mixed``; and the answers are held to ``reference_mixed``.  In a
traced run the program's flight recorder is open over the window, so
the program's ``replay.prepare`` spans reach the readers.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .. import reference, reference_mixed, stream_mixed
from . import common, replay
from .replay import State, as_answers, compare


def device_models(cfg: dict) -> tuple:
    """The program's model of each ``fleet.devices`` entry, each checked
    against the configuration's own profile table."""
    return tuple(common.device_model({"fleet": d})
                 for d in cfg["fleet"]["devices"])


def build_events(cfg: dict, data: dict):
    """The program's event trace of the generated stream."""
    from repro.core import batched as B

    fleet = cfg["fleet"]
    counts = data["gpu_counts"]
    n, H = len(data["arrival"]), len(counts)
    models = device_models(cfg)
    return B.build_events_arrays(
        arrival=data["arrival"], duration=data["duration"],
        cpu=data["cpu"], ram=data["ram"],
        vm_ids=np.arange(n, dtype=np.int64),
        pids=data["pid"].reshape(n, len(models)), models=models,
        gpu_model_id=data["gpu_model"].astype(np.int32),
        gpu_host_id=np.repeat(np.arange(H), counts).astype(np.int32),
        cpu_cap=np.full(H, fleet["host_cpu"], np.float32),
        ram_cap=np.full(H, fleet["host_ram"], np.float32),
        step_hours=cfg["stream"]["step_hours"])


def setup(run) -> State:
    import jax
    from repro.core import batched as B
    from repro.core.bucketing import pad_events

    cfg = run.cell.config
    data = stream_mixed.generate(cfg, run.seed)
    ev = build_events(cfg, data)
    pv = pad_events(ev)
    blank = dataclasses.replace(pv, kind=np.full_like(pv.kind, B.PAD))
    policy = common.grmu_policy(cfg)
    knobs = common.replay_knobs(cfg)
    cap = B.default_heavy_capacity(ev, cfg["policy"]["heavy_capacity_frac"])
    with run.span("bench.warm"):
        out = B.make_replay(blank, policy, **knobs)(cap)
        B.result_from_arrays(blank, policy, jax.device_get(out))
    run.info["rows_real"] = len(ev.kind)
    run.info["rows_padded"] = len(pv.kind)
    run.info["gpus_per_model"] = np.bincount(
        data["gpu_model"], minlength=len(cfg["fleet"]["devices"])).tolist()
    return State(padded=pv, policy=policy, cap=cap, knobs=knobs, data=data,
                 rows=len(ev.kind))


def window(run, st: State, t0: float) -> None:
    with common.recorder(run):
        replay.window(run, st, t0)


def ref_answers(cfg: dict, ref: dict) -> dict:
    """``reference_mixed``'s result in the comparison's terms, with the
    per-profile counts named by the reference device's profiles."""
    return replay.ref_answers({"fleet": cfg["fleet"]["devices"][0]}, ref)


def control_tables(cfg: dict) -> list:
    """The control's broken Alg. 1 (``control.block_rule``) on every
    model."""
    rule = cfg["control"]["block_rule"]
    return [reference.MigTables(d, block_rule=rule)
            for d in cfg["fleet"]["devices"]]


def check(run, st: State, control: bool = False) -> dict:
    cfg = run.cell.config
    answers = [as_answers(r) for r in st.results]
    st.results = []
    differing = sum(1 for a in answers[1:] if a != answers[0])
    ref = ref_answers(cfg, reference_mixed.simulate(
        cfg["fleet"], cfg["policy"], st.data))
    got = answers[0]
    if control:
        got = ref_answers(cfg, reference_mixed.simulate(
            cfg["fleet"], cfg["policy"], st.data,
            tables=control_tables(cfg)))
    checks = compare(got, ref)
    checks["replays_differing"] = {"value": differing, "limit": 0}
    run.failed = sum(1 for a in answers if a != ref)
    return checks
