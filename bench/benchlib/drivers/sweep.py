"""The planner's basket sweep: ``sweep_heavy_capacity`` vmapped over the
mix's heavy-basket fractions, back to back.

Set-up is the replay driver's (the paper-length trace, padded, and a
warm-up on its all-padding copy).  Window: sweeps of the real trace, one
after another (the ``bench.sweep`` span), each begun before the deadline
counted, the one in flight included.  ``replay_events_per_s`` counts the
real rows times the lanes.  Each lane's per-profile acceptance is
compared with the reference run at that lane's fraction.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from .. import reference
from ..stats import rate_over_window
from . import common
from .replay import prepare


@dataclasses.dataclass
class State:
    padded: object
    fracs: np.ndarray
    knobs: dict
    data: dict
    rows: int
    outputs: list = dataclasses.field(default_factory=list)


def setup(run) -> State:
    from repro.core import batched as B

    cfg = run.cell.config
    common.grmu_policy(cfg)       # sweep_heavy_capacity runs GRMU
    data, ev, pv, blank = prepare(run)
    fracs = np.asarray(run.cell.traffic["heavy_fracs"], np.float64)
    knobs = common.replay_knobs(cfg)
    with run.span("bench.warm"):
        B.sweep_heavy_capacity(blank, fracs, **knobs)
    run.info["rows_real"] = len(ev.kind)
    run.info["rows_padded"] = len(pv.kind)
    run.info["lanes"] = len(fracs)
    return State(padded=pv, fracs=fracs, knobs=knobs, data=data,
                 rows=len(ev.kind))


def window(run, st: State, t0: float) -> None:
    from repro.core import batched as B

    units = []
    run.trace_start()
    while time.perf_counter() - t0 < run.seconds:
        b = time.perf_counter()
        with run.span("bench.sweep"):
            acc = B.sweep_heavy_capacity(st.padded, st.fracs, **st.knobs)
        units.append((b, time.perf_counter(), st.rows * len(st.fracs)))
        st.outputs.append(np.asarray(acc))
        if run.trace_due():
            run.trace_stop()
    run.trace_stop()
    rate, span, n = rate_over_window(units, t0)
    run.metrics["replay_events_per_s"] = rate
    run.attempted = n
    run.info["sweeps"] = n
    run.info["window_to_last_end_s"] = span


def reference_lanes(cfg: dict, data: dict, fracs, tables=None
                    ) -> np.ndarray:
    rows = []
    for f in fracs:
        pol = dict(cfg["policy"], heavy_capacity_frac=float(f))
        rows.append(reference.simulate(cfg["fleet"], pol, data,
                                       tables=tables)
                    ["per_profile_accepted"])
    return np.asarray(rows, np.int64)


def check(run, st: State, control: bool = False) -> dict:
    cfg = run.cell.config
    outs, st.outputs = st.outputs, []
    ref = reference_lanes(cfg, st.data, st.fracs)
    got = outs[0]
    if control:
        got = reference_lanes(cfg, st.data, st.fracs,
                              common.control_tables(cfg))
    differing = sum(1 for o in outs[1:] if not np.array_equal(o, outs[0]))
    wrong_lanes = [i for i in range(len(ref))
                   if not np.array_equal(got[i], ref[i])]
    run.failed = sum(1 for o in outs if not np.array_equal(o, ref))
    return {
        "lanes_wrong": {"value": len(wrong_lanes), "limit": 0},
        "profile_counts_off": {"value": int(np.abs(
            np.asarray(got, np.int64) - ref).sum()), "limit": 0},
        "sweeps_differing": {"value": differing, "limit": 0},
    }
