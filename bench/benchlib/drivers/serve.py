"""Served placement: ``PlacementService`` on an open loop.

Set-up:
  * the stream: ``setup_arrivals`` VMs to bring the fleet to its
    operating state, then ``round(rate * seconds)`` VMs for the window,
    plus one that marks the window's end, all one stream of the
    configuration's shape;
  * the program gets the rows through ``build_events_arrays`` and
    ``requests_from_trace``; the service is sized by ``for_trace`` for the
    whole stream (its tables cannot recycle slots);
  * the set-up arrivals are queued and drained one micro-batch at a
    time, with no clock, which also compiles every program the window
    runs.

Window (open loop): the window's arrivals keep the trace's own gaps,
scaled so that the window's first arrival is due at its start and the
end marker at its end: the mean rate is exactly ``rate``.  A
departure is due with the arrival that follows it.  The loop submits
every request that is due, then drains one micro-batch; with nothing
queued it sleeps until the next is due.  At the deadline every window
arrival has come due: it queues the rest and drains what is queued, for
at most ``max_wait_s``.

A decision's latency runs from its arrival's due time to the return of
the ``drain`` call that made it, so time lost anywhere (a late submit, a
slow batch ahead of it) counts.  ``decision_p50_ms``, ``decision_p95_ms``
and ``decision_p99_ms`` are over every arrival due in the window; one
never decided counts with its wait up to the give-up time.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from .. import reference, stream
from ..stats import percentile
from . import common


@dataclasses.dataclass
class State:
    svc: object
    reqs: list
    unit_end: np.ndarray       # index in reqs one past each unit
    due: np.ndarray            # window units' due offsets (s)
    first: int                 # first window unit
    n_window: int
    data: dict
    horizon_of: np.ndarray     # bucket start of each arrival
    # vm_id -> (accepted, gpu, start, ready time, requests popped by the
    # end of the drain that decided it)
    decisions: Dict[int, tuple]
    submitted_units: int = 0
    submitted: int = 0         # requests handed to ``submit``


def setup(run) -> State:
    from repro.serve import PlacementService, ServeConfig, \
        requests_from_trace
    from repro.serve.queue import Arrival

    cfg, mix = run.cell.config, run.cell.traffic
    common.grmu_policy(cfg)
    setup_n = int(mix["setup_arrivals"])
    n_window = int(round(mix["rate"] * run.seconds))
    data = stream.generate(cfg, run.seed, n_vms=setup_n + n_window + 1)
    ev = common.build_events(cfg, data)
    reqs, _ = requests_from_trace(ev)
    # A unit is an arrival with the departures queued just before it.
    arrival_pos = [i for i, r in enumerate(reqs) if isinstance(r, Arrival)]
    unit_end = np.asarray(arrival_pos, np.int64) + 1
    if len(unit_end) != len(data["arrival"]):
        raise RuntimeError("a VM of the stream arrives past its horizon")
    t = data["arrival"][setup_n:setup_n + n_window + 1]
    x = (t - t[0]) / (t[-1] - t[0])
    due = x * run.seconds

    pol = cfg["policy"]
    scfg = ServeConfig(
        policy=pol["name"], tiers=(pol["name"],),
        micro_batch=int(mix["micro_batch"]),
        queue_capacity=len(reqs) + 1,
        heavy_capacity_frac=pol["heavy_capacity_frac"],
        defrag=pol["defrag"], defrag_trigger=pol["defrag_trigger"],
        consolidation_interval=pol["consolidation_interval"])
    svc = PlacementService.for_trace(ev, scfg)
    st = State(svc=svc, reqs=reqs, unit_end=unit_end, due=due[:-1],
               first=setup_n, n_window=n_window, data=data,
               horizon_of=np.floor(data["arrival"] + 1e-9),
               decisions={})
    with run.span("bench.setup_stream"):
        for r in reqs[:unit_end[setup_n - 1]]:
            while not svc.submit(r):
                _record(st, svc.drain(max_batches=1), time.perf_counter())
            st.submitted += 1
        while len(svc.queue):
            _record(st, svc.drain(max_batches=1), time.perf_counter())
    st.submitted_units = setup_n
    run.info["service_vm_slots"] = svc.cfg.max_vms
    run.info["window_arrivals"] = n_window
    return st


def _record(st: State, decisions, t_ready: float) -> None:
    """Keep one micro-batch's decisions, each with how many requests had
    left the queue when it was made (which fixes the steps it spans)."""
    popped = st.submitted - len(st.svc.queue)
    for d in decisions:
        st.decisions[d.vm_id] = (d.accepted, d.gpu, d.start, t_ready,
                                 popped)


def window(run, st: State, t0: float) -> None:
    svc, reqs, due = st.svc, st.reqs, st.due
    seconds = run.seconds
    u = st.first                       # next unit to submit
    last = st.first + st.n_window
    lateness: List[float] = []
    rec = common.recorder(run)
    run.trace_start()
    with rec:
        while True:
            now = time.perf_counter() - t0
            u0 = u
            while u < last and due[u - st.first] <= now:
                lateness.append(now - due[u - st.first])
                u += 1
            for r in reqs[st.unit_end[u0 - 1]:st.unit_end[u - 1]]:
                svc.submit(r)
                st.submitted += 1
            if now >= seconds:
                break                  # every window arrival is queued
            if len(svc.queue):
                with run.span("bench.drain"):
                    out = svc.drain(max_batches=1)
                _record(st, out, time.perf_counter())
            else:
                nxt = due[u - st.first] if u < last else seconds
                with run.span("bench.wait_arrival"):
                    _sleep_until(t0 + min(nxt, seconds))
            if run.trace_due():
                run.trace_stop()
        run.trace_stop()
        st.submitted_units = u
        backlog = len(svc.queue)
        close = time.perf_counter()
        while len(svc.queue) and time.perf_counter() - close \
                < float(run.cell.traffic["max_wait_s"]):
            with run.span("bench.drain"):
                out = svc.drain(max_batches=1)
            _record(st, out, time.perf_counter())
    give_up = time.perf_counter()
    run.info["backlog_at_close"] = backlog
    run.info["drain_after_close_s"] = give_up - close
    run.info["submit_lateness_p99_ms"] = (
        percentile(lateness, 99) * 1e3 if lateness else 0.0)

    lat = []
    missing = 0
    for k in range(st.n_window):
        vm = st.first + k
        d = st.decisions.get(vm)
        ready = d[3] if d is not None else give_up
        missing += d is None
        lat.append((ready - t0) - due[k])
    run.attempted = st.n_window
    run.failed = missing
    run.info["undecided"] = missing
    for q in (50, 95, 99):
        run.metrics[f"decision_p{q}_ms"] = percentile(lat, q) * 1e3
        run.info[f"decision_p{q}_ms"] = run.metrics[f"decision_p{q}_ms"]
    run.info["decisions_in_window"] = st.n_window - missing


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.0005))


def request_steps(reqs) -> tuple:
    """The reference's step of every request (``arrival_step`` for an
    arrival, ``release_step`` for a departure), from the times the
    program was fed, and the index of each VM's departure."""
    from repro.serve.queue import Arrival

    steps = np.zeros(len(reqs), np.int64)
    arrived, leaves = {}, {}
    for i, r in enumerate(reqs):
        if isinstance(r, Arrival):
            steps[i] = arrived[r.vm_id] = reference.arrival_step(r.time)
        else:
            steps[i] = reference.release_step(r.time, arrived[r.vm_id])
            leaves[r.vm_id] = i
    return steps, leaves


def batch_stamps(steps: np.ndarray, popped: int, submitted: int) -> tuple:
    """The first and last reference stamp a micro-batch can have ended
    at.  It popped the requests before ``popped``, so every step before
    the last one's has ended (stamp ``2 * step``).  With requests still
    queued it may also have closed the steps up to the one before the
    next request's (stamp ``2 * step + 1`` of that step)."""
    lo = 2 * int(steps[popped - 1])
    hi = lo
    if popped < submitted and steps[popped] > steps[popped - 1]:
        hi = 2 * int(steps[popped] - 1) + 1
    return lo, hi


def held(history, lo: int, hi: int) -> set:
    """The positions a VM held at some moment between stamps lo and hi."""
    out = set()
    for i, (stamp, g, s) in enumerate(history):
        nxt = history[i + 1][0] if i + 1 < len(history) else None
        if stamp <= hi and (nxt is None or nxt > lo):
            out.add((g, s))
    return out


def decision_wrong(d: tuple, ref: dict, vm: int, steps, leaves,
                   submitted: int) -> bool:
    """A decision against the reference.  The service reports where the
    VM sits when its micro-batch ends: any position the reference gave
    it between that batch's first and last possible end, or GPU -1 where
    the VM's own departure was in that batch."""
    acc, gpu, start, _, popped = d
    if acc != bool(ref["accepted"][vm]):
        return True
    if not acc:
        return False
    if gpu == -1:
        return not leaves.get(vm, len(steps)) < popped
    lo, hi = batch_stamps(steps, popped, submitted)
    return (gpu, start) not in held(ref["history"][vm], lo, hi)


def check(run, st: State, control: bool = False) -> dict:
    """Every decision of the set-up and the window against the plain
    reference over the same arrivals, and the migrations."""
    cfg = run.cell.config
    n = st.submitted_units
    horizon = float(st.horizon_of[n - 1])
    st.svc.flush(horizon)
    intra, inter = st.svc.migrations()
    got = dict(st.decisions)
    ids = st.svc.accepted_ids()
    st.svc = None
    ref = reference.simulate(cfg["fleet"], cfg["policy"], st.data,
                             n_vms=n, horizon=horizon)
    if control:
        got, (intra, inter), ids = common.control_serve(cfg, st.data, n,
                                                        horizon, got)
    steps, leaves = request_steps(st.reqs)
    wrong = sum(decision_wrong(got[vm], ref, vm, steps, leaves,
                               st.submitted)
                for vm in range(n) if vm in got)
    undecided = sum(1 for vm in range(n) if vm not in got)
    run.failed = max(run.failed, undecided)
    ref_ids = [int(v) for v in np.flatnonzero(ref["accepted"][:n])]
    return {
        "undecided": {"value": undecided, "limit": 0},
        "decisions_wrong": {"value": wrong, "limit": 0},
        "accepted_order_off": {"value": int(ids != ref_ids), "limit": 0},
        "migrations_off": {"value": abs(intra - ref["intra"])
                           + abs(inter - ref["inter"]), "limit": 0},
    }
