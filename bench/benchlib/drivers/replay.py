"""Offline replay: back-to-back ``core.batched`` replays of one trace.

Set-up builds the paper-length trace of the seed, pads it to its shape
bucket, and warms the compiled replay on a copy of the same shapes whose
rows are all padding (the same program, none of the work).

Window: replays of the real trace, one after another, each the steps of
``batched.replay``: ``make_replay`` (statics, trace upload), the jitted
run ended by ``block_until_ready`` (the ``bench.replay`` span), then
``result_from_arrays``.  Every replay begun before the deadline counts,
the one in flight included, which runs to its end.
``replay_events_per_s`` is the real (non-PAD) rows of all of them over
the time from the window's start to the end of the last.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from .. import reference, stream
from ..stats import rate_over_window
from . import common


@dataclasses.dataclass
class State:
    padded: object
    policy: int
    cap: int
    knobs: dict
    data: dict
    rows: int
    results: list = dataclasses.field(default_factory=list)


def prepare(run):
    """Stream, trace, padded trace and the all-padding copy."""
    from repro.core import batched as B
    from repro.core.bucketing import pad_events

    cfg = run.cell.config
    data = stream.generate(cfg, run.seed)
    ev = common.build_events(cfg, data)
    pv = pad_events(ev)
    blank = dataclasses.replace(pv, kind=np.full_like(pv.kind, B.PAD))
    return data, ev, pv, blank


def setup(run) -> State:
    import jax
    from repro.core import batched as B

    cfg = run.cell.config
    data, ev, pv, blank = prepare(run)
    policy = common.grmu_policy(cfg)
    knobs = common.replay_knobs(cfg)
    cap = B.default_heavy_capacity(ev, cfg["policy"]["heavy_capacity_frac"])
    with run.span("bench.warm"):
        out = B.make_replay(blank, policy, **knobs)(cap)
        B.result_from_arrays(blank, policy, jax.device_get(out))
    run.info["rows_real"] = len(ev.kind)
    run.info["rows_padded"] = len(pv.kind)
    return State(padded=pv, policy=policy, cap=cap, knobs=knobs, data=data,
                 rows=len(ev.kind))


def window(run, st: State, t0: float) -> None:
    import jax
    from repro.core import batched as B

    units = []
    run.trace_start()
    while time.perf_counter() - t0 < run.seconds:
        b = time.perf_counter()
        with run.span("bench.upload"):
            fn = B.make_replay(st.padded, st.policy, **st.knobs)
        with run.span("bench.replay"):
            out = jax.block_until_ready(fn(st.cap))
        with run.span("bench.result"):
            res = B.result_from_arrays(st.padded, st.policy,
                                       jax.device_get(out))
        units.append((b, time.perf_counter(), st.rows))
        st.results.append(res)
        if run.trace_due():
            run.trace_stop()
    run.trace_stop()
    rate, span, n = rate_over_window(units, t0)
    run.metrics["replay_events_per_s"] = rate
    run.attempted = n
    run.info["replays"] = n
    run.info["window_to_last_end_s"] = span


def as_answers(res) -> dict:
    """A replay's ``SimResult`` in the comparison's terms."""
    return dict(accepted=list(res.accepted_ids),
                per_profile=(dict(res.per_profile_accepted),
                             dict(res.per_profile_total)),
                acceptance=list(res.hourly_acceptance),
                active=list(res.hourly_active_hw),
                migrations=(res.intra_migrations, res.inter_migrations))


def ref_answers(cfg: dict, ref: dict) -> dict:
    names = [p["name"] for p in cfg["fleet"]["profiles"]]
    h = ref["hourly"]
    denom = ref["num_hosts"] + ref["num_gpus"]
    return dict(
        accepted=[int(v) for v in np.flatnonzero(ref["accepted"])],
        per_profile=(
            {n: int(v) for n, v in zip(names, ref["per_profile_accepted"])},
            {n: int(v) for n, v in zip(names, ref["per_profile_total"])}),
        acceptance=[int(a) / max(1, int(t)) for a, t in h[:, :2]],
        active=[(int(p) + int(g)) / denom for p, g in h[:, 2:]],
        migrations=(ref["intra"], ref["inter"]))


def compare(got: dict, want: dict) -> dict:
    """Exact comparison of two answer sets, as counts of what differs."""
    a, b = set(got["accepted"]), set(want["accepted"])
    hours = max(len(got["acceptance"]), len(want["acceptance"]))
    hours_wrong = sum(
        1 for i in range(hours)
        if i >= len(got["acceptance"]) or i >= len(want["acceptance"])
        or got["acceptance"][i] != want["acceptance"][i]
        or got["active"][i] != want["active"][i])
    prof = sum(abs(got["per_profile"][j].get(k, 0)
                   - want["per_profile"][j].get(k, 0))
               for j in (0, 1) for k in want["per_profile"][j])
    return {
        "vms_wrong": {"value": len(a ^ b), "limit": 0},
        "profile_counts_off": {"value": prof, "limit": 0},
        "hours_wrong": {"value": hours_wrong, "limit": 0},
        "migrations_off": {"value": abs(got["migrations"][0]
                                        - want["migrations"][0])
                           + abs(got["migrations"][1]
                                 - want["migrations"][1]), "limit": 0},
    }


def check(run, st: State, control: bool = False) -> dict:
    cfg = run.cell.config
    answers = [as_answers(r) for r in st.results]
    st.results = []
    differing = sum(1 for a in answers[1:] if a != answers[0])
    ref = ref_answers(cfg, reference.simulate(
        cfg["fleet"], cfg["policy"], st.data))
    got = answers[0]
    if control:
        got = ref_answers(cfg, reference.simulate(
            cfg["fleet"], cfg["policy"], st.data,
            tables=common.control_tables(cfg)))
    checks = compare(got, ref)
    checks["replays_differing"] = {"value": differing, "limit": 0}
    run.failed = sum(1 for a in answers if a != ref)
    return checks
