"""Online placement service (repro.serve) — the serving-layer contract.

Pins the five behaviors the control plane is built on:

  * the bounded request queue sheds load instead of growing (submit
    returns False at capacity; backpressure via drain);
  * micro-batch draining is size-invariant — batch {1, 8, 64} produce
    identical decisions (the scan body is position-independent);
  * online decisions are bit-identical to an offline replay of the same
    arrival order, for every registry policy AND the ILP tier;
  * the admission governor degrades on SLO breach, records the switch
    through the flight recorder, and recovers when healthy again;
  * checkpoint/restore mid-stream resumes to the exact decisions of an
    uninterrupted run.
"""
import json
import os
import tempfile

import numpy as np
import pytest

import repro.workload.synthetic as syn
from repro.core import batched as B
from repro.core.bucketing import pad_events
from repro.obs import recorder as obs_recorder
from repro.serve import (Arrival, BoundedRequestQueue, PlacementService,
                         ServeConfig, requests_from_trace)

pytestmark = pytest.mark.filterwarnings(
    "ignore:Some donated buffers were not usable")


@pytest.fixture(scope="module")
def trace():
    """One small synthetic stream shared by every test: 200 VMs on a
    12-GPU homogeneous fleet, dense enough to reject some arrivals."""
    cfg = syn.SyntheticConfig(n_vms=200, n_gpus=12, horizon_hours=30.0,
                              mean_duration_hours=6.0, seed=5)
    events = syn.generate_events(cfg)
    reqs, horizon = requests_from_trace(events)
    return events, reqs, horizon


def _stream(svc, reqs, horizon):
    for r in reqs:
        while not svc.submit(r):
            svc.drain(max_batches=1)
    svc.drain()
    svc.flush(horizon)
    return svc


# ---------------------------------------------------------------------------
# Queue bounding / backpressure
# ---------------------------------------------------------------------------

def test_queue_bounds_and_counters():
    q = BoundedRequestQueue(capacity=4)
    reqs = [Arrival(vm_id=i, time=float(i), profile_ids=(0,))
            for i in range(6)]
    assert [q.submit(r) for r in reqs] == [True] * 4 + [False] * 2
    assert len(q) == 4 and q.fill == 1.0 and q.dropped == 2
    assert q.high_watermark == 4
    assert q.pop()[0].vm_id == 0       # FIFO of (request, enqueue-time)
    assert q.submit(reqs[4])           # space freed -> accepted again
    assert q.accepted_total == 5


def test_service_backpressure(trace):
    events, reqs, horizon = trace
    svc = PlacementService.for_trace(
        events, ServeConfig(policy="FF", micro_batch=4, queue_capacity=4))
    rejected = 0
    for r in reqs:
        while not svc.submit(r):
            rejected += 1
            svc.drain(max_batches=1)   # shed: drain one batch, retry
    svc.drain()
    svc.flush(horizon)
    assert rejected > 0                # the tiny queue really filled
    assert svc.queue.high_watermark <= 4
    # shed-and-retry loses nothing: every arrival got a decision
    n_arr = sum(1 for r in reqs if isinstance(r, Arrival))
    assert len(svc.decisions) == n_arr


# ---------------------------------------------------------------------------
# Online == offline parity (all registry policies), batch-size invariance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["FF", "BF", "MCC", "MECC", "GRMU"])
def test_online_matches_offline(policy, trace):
    events, reqs, horizon = trace
    res = B.replay(pad_events(events), B.__dict__[policy])
    svc = _stream(PlacementService.for_trace(
        events, ServeConfig(policy=policy, micro_batch=16)), reqs, horizon)
    assert svc.accepted_ids() == list(res.accepted_ids)
    assert svc.stats()["accepted"] == res.accepted


@pytest.mark.parametrize("micro_batch", [1, 8, 64])
def test_micro_batch_size_invariant(micro_batch, trace):
    """Decisions cannot depend on how the stream is chopped into
    micro-batches: the decision kernel is a position-independent fold."""
    events, reqs, horizon = trace
    res = B.replay(pad_events(events), B.GRMU)
    svc = _stream(PlacementService.for_trace(
        events, ServeConfig(policy="GRMU", micro_batch=micro_batch)),
        reqs, horizon)
    assert svc.accepted_ids() == list(res.accepted_ids)


def test_online_grmu_consolidation_migrations(trace):
    """With periodic consolidation on, the online service reproduces the
    offline accepted set AND migration counts."""
    events, reqs, horizon = trace
    res = B.replay(pad_events(events), B.GRMU, consolidation_interval=6.0)
    svc = _stream(PlacementService.for_trace(
        events, ServeConfig(policy="GRMU", micro_batch=16,
                            consolidation_interval=6.0)), reqs, horizon)
    assert svc.accepted_ids() == list(res.accepted_ids)
    assert svc.migrations() == (res.intra_migrations, res.inter_migrations)


def _ilp_case():
    """A mixed 5-GPU cluster, 15 VMs: the sequential engine's ILPPolicy
    result and the same stream as service requests."""
    from repro.core.policies import ILPPolicy
    from repro.sim.cluster import VM, make_cluster
    from repro.sim.engine import simulate

    rng = np.random.default_rng(11)
    cluster = make_cluster([2, 1, 2], cpu=24.0, ram=96.0)
    model = cluster.models[0]
    vms = []
    for i in range(15):
        pid = int(rng.integers(0, model.num_profiles))
        vms.append(VM(vm_id=100 + i, profile=model.profiles[pid],
                      arrival=float(rng.uniform(0, 10)),
                      duration=float(rng.uniform(2, 8)),
                      cpu=2.0, ram=4.0, profile_ids=(pid,)))
    horizon = 20.0

    ref_cluster = make_cluster([2, 1, 2], cpu=24.0, ram=96.0)
    ref = simulate(ref_cluster, ILPPolicy(ref_cluster, window=4,
                                          time_limit=2.0),
                   sorted(vms, key=lambda v: (v.arrival, v.vm_id)),
                   horizon=horizon)

    events = B.build_events(vms, cluster, step_hours=1.0, horizon=horizon)
    reqs, h = requests_from_trace(events)
    cfg = ServeConfig(tiers=("ILP",), micro_batch=8, ilp_window=4,
                      ilp_time_limit=2.0)
    return events, reqs, h, cfg, ref


def test_ilp_tier_matches_sequential_engine():
    """The ILP (object-backend) tier replays the sequential engine's
    ILPPolicy decisions exactly, on a mixed 5-GPU cluster."""
    events, reqs, h, cfg, ref = _ilp_case()
    svc = _stream(PlacementService.for_trace(events, cfg), reqs, h)
    assert svc.accepted_ids() == list(ref.accepted_ids)
    assert svc.migrations() == (ref.intra_migrations, ref.inter_migrations)


def test_ilp_tier_batches_are_recorded(tmp_path):
    """An ILP-tier micro-batch is one ``serve.drain_batch`` root holding
    one ``serve.batch``, numbered like the array tier's batches."""
    events, reqs, h, cfg, ref = _ilp_case()
    with obs_recorder.record(tmp_path / "rec.jsonl") as rec:
        svc = _stream(PlacementService.for_trace(events, cfg), reqs, h)
    assert svc.accepted_ids() == list(ref.accepted_ids)
    spans = [r for r in rec.records if r["kind"] == "span"]
    roots = [s for s in spans if s["name"] == "serve.drain_batch"]
    assert [r["batch"] for r in roots] == list(range(len(roots)))
    assert all(r["parent"] is None and r["tier"] == "ILP" for r in roots)
    kids = {r["id"]: [] for r in roots}
    for s in spans:
        if s["parent"] in kids:
            kids[s["parent"]].append(s["name"])
    assert all(k == ["serve.batch"] for k in kids.values())
    assert {d.batch for d in svc.decisions.values()} \
        <= {r["batch"] for r in roots}


# ---------------------------------------------------------------------------
# The flight recorder's span tree of a served micro-batch
# ---------------------------------------------------------------------------

def _drain_batches(svc, reqs, per_submit=24):
    """Feed ``reqs`` in slices, draining one micro-batch at a time;
    returns the number of micro-batches drained."""
    batches = 0
    for i in range(0, len(reqs), per_submit):
        for r in reqs[i:i + per_submit]:
            assert svc.submit(r)
        while len(svc.queue):
            svc.drain(max_batches=1)
            batches += 1
    return batches


def test_recorded_batches_form_the_span_tree(trace, tmp_path):
    events, reqs, _ = trace
    cfg = ServeConfig(policy="GRMU", micro_batch=16)
    path = tmp_path / "rec.jsonl"
    with obs_recorder.record(path):
        svc = PlacementService.for_trace(events, cfg)
        batches = _drain_batches(svc, reqs)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    spans = [r for r in recs if r["kind"] == "span"]
    by_id = {s["id"]: s for s in spans}
    roots = [s for s in spans if s["name"] == "serve.drain_batch"]
    assert len(roots) == batches
    assert [r["batch"] for r in roots] == list(range(batches))
    assert all(r["parent"] is None and r["tier"] == "GRMU" for r in roots)

    kids = {s["id"]: [] for s in spans}
    for s in spans:
        if s["parent"] is not None:
            kids[s["parent"]].append(s)
    pops = []
    for r in roots:
        names = [c["name"] for c in kids[r["id"]]]
        assert names == ["serve.pop", "serve.batch", "serve.emit"]
        pop, batch, _ = kids[r["id"]]
        pops.append(pop)
        inner = [c["name"] for c in kids[batch["id"]]]
        want = (["serve.ingest"] if pop["arrivals"] else []) \
            + ["serve.step", "serve.readback"]
        assert inner == want
        assert batch["rows"] == pop["rows"]
        assert batch["arrivals"] == pop["arrivals"]
        for c in kids[r["id"]] + kids[batch["id"]]:
            parent = by_id[c["parent"]]
            assert parent["t0"] <= c["t0"] <= c["t1"] <= parent["t1"]

    # The pop's counters: every row and arrival of the stream, once.
    assert sum(p["arrivals"] for p in pops) == len(svc.decisions)
    # Every request is one row; step ends add rows of their own.
    assert sum(p["rows"] for p in pops) >= len(reqs)
    for p in pops:
        assert 0 < p["rows"] <= p["batch_rows"] == svc._batch_rows
        assert p["arrivals"] <= p["rows"]
        assert (p["wait_sum_s"] > 0) == (p["arrivals"] > 0)
    root_ids = {r["batch"] for r in roots}
    assert all(d.batch in root_ids for d in svc.decisions.values())
    # Decisions sharing a batch number came out of that batch.
    per_batch = {}
    for d in svc.decisions.values():
        per_batch[d.batch] = per_batch.get(d.batch, 0) + 1
    assert per_batch == {r["batch"]: p["arrivals"]
                         for r, p in zip(roots, pops) if p["arrivals"]}


def test_recorder_leaves_decisions_unchanged(trace, tmp_path):
    events, reqs, horizon = trace
    cfg = ServeConfig(policy="GRMU", micro_batch=16,
                      consolidation_interval=6.0)

    def run(record):
        svc = PlacementService.for_trace(events, cfg)
        if record:
            with obs_recorder.record(tmp_path / "rec.jsonl"):
                _drain_batches(svc, reqs)
        else:
            _drain_batches(svc, reqs)
        svc.flush(horizon)
        return svc

    plain, recorded = run(False), run(True)
    key = lambda svc: {v: (d.accepted, d.gpu, d.start, d.tier, d.batch)
                       for v, d in svc.decisions.items()}
    assert key(plain) == key(recorded)
    assert plain.accepted_ids() == recorded.accepted_ids()
    assert plain.migrations() == recorded.migrations()
    assert all(d.batch >= 0 for d in plain.decisions.values())


def test_unrecorded_batch_opens_no_span(trace, monkeypatch):
    """With no recorder, a micro-batch asks for the recorder once and
    reads the clock only for the decisions' latency."""
    from repro.serve import placement

    events, reqs, _ = trace
    svc = PlacementService.for_trace(events, ServeConfig(policy="FF",
                                                         micro_batch=16))
    for r in reqs[:40]:
        assert svc.submit(r)
    assert svc.drain(max_batches=1)    # compiles outside the count
    asks, clock = [], []
    monkeypatch.setattr(placement.obs_recorder, "active",
                        lambda: asks.append(1))
    real = placement.time.perf_counter
    monkeypatch.setattr(placement.time, "perf_counter",
                        lambda: clock.append(1) or real())
    out = svc.drain(max_batches=1)
    assert out and len(asks) == 1 and len(clock) == 1


# ---------------------------------------------------------------------------
# Graceful degradation + recovery, through the flight recorder
# ---------------------------------------------------------------------------

def test_degradation_on_slo_breach(trace):
    """An unmeetable SLO (0 s) breaches on the first governed batch:
    the service degrades GRMU -> FF, serves the rest on FF, and the
    switch lands in the flight recorder as a `service` record."""
    events, reqs, horizon = trace
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "rec.jsonl")
        with obs_recorder.record(path):
            svc = _stream(PlacementService.for_trace(
                events, ServeConfig(tiers=("GRMU", "FF"), micro_batch=16,
                                    slo_s=0.0)), reqs, horizon)
        assert svc.tier_name == "FF"
        assert [e["event"] for e in svc.switch_events] == ["degrade"]
        occ = svc.tier_occupancy
        assert occ["GRMU"] >= 1 and occ["FF"] > occ["GRMU"]
        with open(path) as f:
            recs = [json.loads(line) for line in f]
        service = [r for r in recs if r["kind"] == "service"]
        assert any(r["event"] == "degrade" and r["from"] == "GRMU"
                   and r["to"] == "FF" for r in service)
        assert any(r["kind"] == "span" for r in recs)  # serve.batch spans


def test_recovery_after_healthy_batches(trace):
    """Degrade under slo_s=0, then lift the SLO: after `recover_after`
    consecutive healthy batches the governor climbs back to GRMU."""
    events, reqs, horizon = trace
    svc = PlacementService.for_trace(
        events, ServeConfig(tiers=("GRMU", "FF"), micro_batch=16,
                            slo_s=0.0, recover_after=2))
    half = len(reqs) // 2
    for r in reqs[:half]:
        assert svc.submit(r)
    svc.drain()
    assert svc.tier_name == "FF"
    svc.governor.slo_s = 1e9           # operator relaxes the SLO
    for r in reqs[half:]:
        assert svc.submit(r)
    svc.drain()
    svc.flush(horizon)
    assert svc.tier_name == "GRMU"
    assert [e["event"] for e in svc.switch_events] == ["degrade", "recover"]


# ---------------------------------------------------------------------------
# Checkpoint / restore mid-stream
# ---------------------------------------------------------------------------

def test_checkpoint_restore_roundtrip(trace):
    """Checkpoint after half the stream, restore into a FRESH service,
    feed the second half: decisions equal an uninterrupted run."""
    events, reqs, horizon = trace
    cfg = ServeConfig(policy="GRMU", micro_batch=16)
    ref = _stream(PlacementService.for_trace(events, cfg), reqs, horizon)

    half = len(reqs) // 2
    with tempfile.TemporaryDirectory() as d:
        a = PlacementService.for_trace(events, cfg)
        for r in reqs[:half]:
            assert a.submit(r)
        a.drain()                       # queue must be empty to snapshot
        a.checkpoint(d)
        b = PlacementService.for_trace(events, cfg)
        assert b.restore(d)
        for r in reqs[half:]:
            assert b.submit(r)
        b.drain()
        b.flush(horizon)
    assert b.accepted_ids() == ref.accepted_ids()
    # decisions{} is per-process latency bookkeeping, not restored state:
    # the resumed service only holds decisions for the second half.
    n_second = sum(1 for r in reqs[half:] if isinstance(r, Arrival))
    assert len(b.decisions) == n_second


def test_checkpoint_refuses_nonempty_queue(trace):
    events, reqs, horizon = trace
    svc = PlacementService.for_trace(events,
                                     ServeConfig(policy="FF",
                                                 micro_batch=16))
    assert svc.submit(reqs[0])
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(RuntimeError):
            svc.checkpoint(d)          # undrained requests would be lost
        svc.drain()
        assert svc.checkpoint(d)
