"""Flight-recorder tests: telemetry decision-neutrality across engines,
cross-engine rejection-reason parity, compile-cache counters, recorder
JSONL round-trips and the report CLI.

The load-bearing invariant: a telemetry-enabled replay must be
decision-for-decision identical to the telemetry-off replay — the
in-scan plane only *reads* decision state and accumulates into its own
``tele_*`` carry entries.  Asserted here for all five registry policies
on the plain scan and for the chunked + sharded twins.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import batched as B
from repro.core import compile_cache
from repro.core import sharded as SH
from repro.core import streaming as ST
from repro.core.bucketing import pad_events
from repro.core.grmu import GRMU
from repro.core.policies import POLICY_REGISTRY
from repro.obs import inscan, reasons, recorder, report
from repro.sim.engine import simulate
from repro.sim import metrics
from test_bucketing import POLICIES, assert_same_replay
from test_equivalence import hetero_scenario, random_scenario

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GRMU_KW = dict(defrag=True, consolidation_interval=6.0)


def _events(seed=0):
    cluster, vms = hetero_scenario(seed)
    ev = B.build_events(vms, cluster)
    return cluster, vms, ev, int(round(0.3 * cluster.num_gpus))


# ---------------------------------------------------------------------------
# Decision-neutrality: telemetry on == telemetry off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(POLICIES))
def test_telemetry_is_decision_neutral_plain(name):
    pid, kw = POLICIES[name]
    _, _, ev, cap = _events()
    r0 = B.replay(ev, pid, cap, **kw)
    r1, tele = inscan.replay_with_telemetry(ev, pid, cap, **kw)
    assert_same_replay(r0, r1)
    assert sum(r1.rejection_reasons.values()) == r1.rejected
    assert tele.rejection_reasons == r1.rejection_reasons


@pytest.mark.parametrize("name", ["FF", "GRMU"])
def test_telemetry_is_decision_neutral_chunked(name):
    pid, kw = POLICIES[name]
    _, _, ev, cap = _events()
    r0 = B.replay(ev, pid, cap, **kw)
    r1 = ST.replay_chunked(ev, pid, cap, chunk_events=64,
                           telemetry=True, **kw)
    assert_same_replay(r0, r1)
    assert sum(r1.rejection_reasons.values()) == r1.rejected


@pytest.mark.parametrize("name", ["FF", "GRMU"])
def test_telemetry_is_decision_neutral_sharded_k1(name):
    pid, kw = POLICIES[name]
    _, _, ev, cap = _events()
    pv = pad_events(ev, shards=1)
    r0 = B.replay(pv, pid, cap, **kw)
    r1 = SH.replay_sharded(pv, pid, cap, num_shards=1,
                           telemetry=True, **kw)
    assert_same_replay(r0, r1)
    assert sum(r1.rejection_reasons.values()) == r1.rejected


_K2_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, "tests")
    from test_equivalence import hetero_scenario
    from repro.core import batched as B
    from repro.core import sharded as SH
    from repro.core.bucketing import pad_events
    import jax
    assert len(jax.devices()) == 2, jax.devices()
    cluster, vms = hetero_scenario(0)
    pv = pad_events(B.build_events(vms, cluster), shards=2)
    cap = B.default_heavy_capacity(pv)
    for pid, kw in ((B.FF, {}),
                    (B.GRMU, dict(defrag=True,
                                  consolidation_interval=6.0))):
        r0 = B.replay(pv, pid, cap, **kw)
        r1 = SH.replay_sharded(pv, pid, cap, num_shards=2,
                               telemetry=True, **kw)
        assert r0.accepted_ids == r1.accepted_ids, pid
        assert r0.hourly_active_hw == r1.hourly_active_hw, pid
        assert sum(r1.rejection_reasons.values()) == r1.rejected, pid
    print("K2_TELEMETRY_PARITY_OK")
""")


def test_telemetry_sharded_k2_subprocess():
    """Replicated telemetry under a real 2-shard mesh: identical on every
    shard, so the P() out-spec returns it unchanged (fresh process so the
    XLA device-count flag lands before jax init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                        ).strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", _K2_SCRIPT],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=480, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "K2_TELEMETRY_PARITY_OK" in proc.stdout


# ---------------------------------------------------------------------------
# Cross-engine rejection-reason parity (sequential vs batched)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(POLICIES))
def test_rejection_reasons_match_sequential_engine(name):
    pid, kw = POLICIES[name]
    cluster, vms, ev, cap = _events()
    policy = (GRMU(cluster, **GRMU_KW) if name == "GRMU"
              else POLICY_REGISTRY[name](cluster))
    rs = simulate(cluster, policy, vms)
    rb, _ = inscan.replay_with_telemetry(ev, pid, cap, **kw)
    assert rs.accepted_ids == rb.accepted_ids
    assert rs.rejection_reasons == rb.rejection_reasons
    assert set(rs.rejection_reasons) == set(reasons.REJECTION_REASONS)


# ---------------------------------------------------------------------------
# In-scan telemetry invariants
# ---------------------------------------------------------------------------

def test_telemetry_invariants_grmu():
    _, _, ev, cap = _events()
    res, tele = inscan.replay_with_telemetry(ev, B.GRMU, cap, **GRMU_KW)
    S = len(ev.step_times)
    M = len(ev.models)
    mid = np.asarray(ev.gpu_model_id)[:ev.num_gpus]
    gpus_per_model = np.bincount(mid, minlength=M)
    # Histogram rows partition each model's fleet at every step.
    assert tele.free_hist.shape[0] == S
    assert (tele.free_hist.sum(axis=-1) == gpus_per_model[None, :]).all()
    # Final cumulative rejection row == the per-reason tally.
    assert tele.rej_hourly[-1].tolist() == [
        res.rejection_reasons[n] for n in reasons.REJECTION_REASONS]
    assert int(tele.rej_hourly[-1].sum()) == res.rejected
    # Per-VM codes: every VM was offered; accepted <=> code 0.
    assert (tele.vm_reason >= 0).all()
    acc = set(res.accepted_ids)
    vm_ids = np.asarray(ev.vm_ids)
    accepted_mask = np.isin(vm_ids, list(acc))
    assert (tele.vm_reason[accepted_mask] == reasons.ACCEPTED).all()
    assert (tele.vm_reason[~accepted_mask] > 0).all()
    assert (~accepted_mask).sum() == res.rejected
    # Derived series stay in range; baskets partition the fleet.
    assert (tele.util >= 0).all() and (tele.util <= 1).all()
    assert (tele.basket_hourly.sum(axis=1) == ev.num_gpus).all()
    assert (tele.active_gpus <= gpus_per_model[None, :]).all()


def test_telemetry_baselines_have_empty_baskets():
    _, _, ev, cap = _events()
    _, tele = inscan.replay_with_telemetry(ev, B.FF, cap)
    assert (tele.basket_hourly == 0).all()
    # FF never migrates.
    assert (tele.intra_hourly == 0).all()
    assert (tele.inter_hourly == 0).all()


# ---------------------------------------------------------------------------
# Compile-cache counters
# ---------------------------------------------------------------------------

def test_cache_counts_hits_misses_and_distinct_telemetry_statics():
    _, _, ev, cap = _events()
    # A never-before-seen statics bucket: unique MECC window.
    kw = dict(mecc_window=23.5)
    before = compile_cache.cache_stats()
    B.replay(ev, B.MECC, cap, **kw)
    after_first = compile_cache.cache_stats()
    assert after_first["misses"] > before["misses"]
    B.replay(ev, B.MECC, cap, **kw)
    after_second = compile_cache.cache_stats()
    assert after_second["misses"] == after_first["misses"]
    assert after_second["hits"] > after_first["hits"]
    # telemetry=True is a distinct ReplayStatics -> its own cache entry.
    B.replay(ev, B.MECC, cap, telemetry=True, **kw)
    after_tele = compile_cache.cache_stats()
    assert after_tele["misses"] > after_second["misses"]
    assert after_tele["entries"] > after_second["entries"]


def test_cache_lru_eviction_counter():
    """Hermetic LRU check on an emptied cache (evicted replay wrappers
    just rebuild on the next miss, so clearing is safe)."""
    prev = compile_cache.set_max_entries(None)
    try:
        compile_cache.clear_cache()
        compile_cache.set_max_entries(2)
        key = lambda k: ("obs-test-evict", k)
        compile_cache.cached_replay_fn(key(0), lambda: "f0")
        compile_cache.cached_replay_fn(key(1), lambda: "f1")
        compile_cache.cached_replay_fn(key(0), lambda: "f0")  # refresh 0
        compile_cache.cached_replay_fn(key(2), lambda: "f2")  # evicts 1
        stats = compile_cache.cache_stats()
        assert stats == {"hits": 1, "misses": 3, "evictions": 1,
                         "entries": 2}
        # Key 0 survived (it was refreshed); key 1 was the LRU victim.
        compile_cache.cached_replay_fn(key(0), lambda: "f0")
        assert compile_cache.cache_stats()["misses"] == 3
        compile_cache.cached_replay_fn(key(1), lambda: "f1")
        assert compile_cache.cache_stats()["misses"] == 4
        assert compile_cache.cache_stats()["evictions"] == 2
    finally:
        compile_cache.set_max_entries(prev)
        compile_cache.clear_cache()


# ---------------------------------------------------------------------------
# Recorder + report round-trip
# ---------------------------------------------------------------------------

def test_recorder_jsonl_roundtrip_and_report(tmp_path, capsys):
    _, _, ev, cap = _events()
    path = tmp_path / "obs.jsonl"
    with recorder.record(path, run_id="t1",
                         meta={"policy": "GRMU"}) as rec:
        assert recorder.active() is rec
        res = ST.replay_chunked(ev, B.GRMU, cap, chunk_events=64,
                                telemetry=True, **GRMU_KW)
        _, tele = inscan.replay_with_telemetry(ev, B.GRMU, cap, **GRMU_KW)
        rec.result(res)
        rec.telemetry(tele)
    assert recorder.active() is None

    runs = report.load([str(path)])
    assert len(runs) == 1 and runs[0]["run_id"] == "t1"
    spans = report._agg_spans(runs[0]["spans"])
    n_chunks = ST.make_chunked_replay(ev, B.GRMU, chunk_events=64,
                                      **GRMU_KW).num_chunks
    assert spans["chunk.step"]["count"] == n_chunks
    assert spans["chunk.prefetch"]["count"] == n_chunks
    assert spans["finalize"]["count"] == 1
    assert spans["chunk.step"]["bytes"] > 0
    assert runs[0]["cache"] is not None           # emitted by the loop

    summ = report.summarize(runs[0])
    assert summ["acceptance_rate"] == res.summary()["acceptance_rate"]
    assert summ["rejection_reasons"] == res.rejection_reasons
    text = report.render_text(runs[0])
    assert "util[" in text and "chunk.step" in text

    # CLI: text mode then --json mode.
    assert report.main([str(path)]) == 0
    capsys.readouterr()
    assert report.main([str(path), "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed[0]["run_id"] == "t1"
    assert parsed[0]["final_baskets"] is not None


def test_recorder_writes_only_at_close_in_order(tmp_path):
    path = tmp_path / "rec.jsonl"
    rec = recorder.Recorder(path, run_id="r", meta={"who": "test"})
    for i in range(3):
        with rec.span("s", i=i):
            pass
    rec.service("degrade", step=1)
    assert not path.exists()           # nothing written while recording
    rec.close()
    rec.close()                        # a second close writes nothing
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["meta", "span", "span", "span",
                                         "service"]
    assert [r["i"] for r in recs if r["kind"] == "span"] == [0, 1, 2]
    assert recs[0]["who"] == "test" and recs[-1]["step"] == 1
    assert all(r["run_id"] == "r" for r in recs)


def test_span_records_carry_times_ids_and_parents(tmp_path):
    path = tmp_path / "rec.jsonl"
    with recorder.record(path) as rec:
        with rec.span("outer", tag="o"):
            with rec.span("inner") as f:
                f["rows"] = 7          # a field known only at the end
            with rec.span("inner"):
                with rec.span("leaf"):
                    pass
        with rec.span("outer"):
            pass
    spans = [json.loads(line) for line in path.read_text().splitlines()]
    spans = [r for r in spans if r["kind"] == "span"]
    assert len({s["id"] for s in spans}) == len(spans) == 5
    for s in spans:
        assert s["t0"] <= s["t1"]
        assert s["dur_s"] == s["t1"] - s["t0"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    o0, o1 = by_name["outer"]
    i0, i1 = by_name["inner"]
    (leaf,) = by_name["leaf"]
    assert o0["parent"] is None and o1["parent"] is None
    assert i0["parent"] == i1["parent"] == o0["id"]
    assert leaf["parent"] == i1["id"]
    assert o0["tag"] == "o" and i0["rows"] == 7
    assert o0["t0"] <= i0["t0"] and i1["t1"] <= o0["t1"]
    assert o0["t1"] <= o1["t0"]


def test_span_left_by_an_exception_restores_the_parent(tmp_path):
    with recorder.record(tmp_path / "rec.jsonl") as rec:
        with pytest.raises(RuntimeError):
            with rec.span("fails"):
                raise RuntimeError("boom")
        with rec.span("after"):
            pass
    spans = [r for r in rec.records if r["kind"] == "span"]
    assert [(s["name"], s["parent"]) for s in spans] == [("after", None)]


def test_recorder_starts_no_profiler(tmp_path, monkeypatch):
    """The one who opens a profiler window owns it: the recorder starts
    none, whatever the environment says."""
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: started.append(a))
    monkeypatch.setenv("REPRO_TRACE", "1")
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path / "trace"))
    with recorder.record(tmp_path / "rec.jsonl") as rec:
        with rec.span("s"):
            pass
    assert started == []
    assert not (tmp_path / "trace").exists()
    kinds = [json.loads(line)["kind"] for line in
             (tmp_path / "rec.jsonl").read_text().splitlines()]
    assert kinds == ["meta", "span"]


def test_report_rejects_newer_schema(tmp_path):
    p = tmp_path / "future.jsonl"
    p.write_text(json.dumps({"schema": inscan.SCHEMA_VERSION + 1,
                             "kind": "meta", "run_id": "x"}) + "\n")
    with pytest.raises(ValueError, match="newer"):
        report.load([str(p)])


def test_unrecorded_chunked_replay_has_no_spans(tmp_path):
    """Default path: no active recorder -> the plain loop runs and no
    JSONL appears (the observability layer is strictly opt-in)."""
    _, _, ev, cap = _events()
    assert recorder.active() is None
    ST.replay_chunked(ev, B.FF, cap, chunk_events=64)
    assert list(tmp_path.iterdir()) == []


def _fleet_events(fleet):
    """A padded trace on a one-model (A100-40GB) or three-model (A30,
    A100-40GB, H100-80GB) fleet."""
    scenario = random_scenario if fleet == "one" else hetero_scenario
    cluster, vms = scenario(3)
    return pad_events(B.build_events(vms, cluster))


@pytest.mark.parametrize("fleet", ["one", "three"])
def test_make_replay_records_prepare_span(tmp_path, fleet):
    """Under a recorder, ``make_replay`` records one ``replay.prepare``
    span whose counters size the fleet: ``slot_tests`` is the (GPU,
    slot) tests one arrival's ``fit_mask`` makes, every padded GPU
    against each model's slots."""
    pv = _fleet_events(fleet)
    M = len(pv.models)
    assert M == (1 if fleet == "one" else 3)
    path = tmp_path / "run.jsonl"
    with recorder.record(path):
        B.make_replay(pv, B.GRMU)
    spans = [r for r in map(json.loads, open(path))
             if r["kind"] == "span"]
    assert [s["name"] for s in spans] == ["replay.prepare"]
    G = len(pv.gpu_model_id)
    assert G > pv.num_gpus                   # padded
    s = spans[0]
    assert (s["models"], s["gpus"], s["pid_cols"]) == (M, G, M)
    assert s["slot_tests"] == G * sum(len(m.slot_masks) for m in pv.models)
    assert s["parent"] is None and s["dur_s"] > 0


def test_unrecorded_make_replay_emits_nothing_and_decides_alike(
        tmp_path, monkeypatch):
    """With no recorder nothing is emitted; with one, the replay's
    outputs are the same arrays."""
    pv = _fleet_events("three")
    cap = B.default_heavy_capacity(pv)
    emitted = []
    monkeypatch.setattr(recorder.Recorder, "emit",
                        lambda self, kind, **f: emitted.append(kind))
    assert recorder.active() is None
    plain = jax.device_get(B.make_replay(pv, B.GRMU, **GRMU_KW)(cap))
    assert emitted == []
    with recorder.record(tmp_path / "run.jsonl"):
        recorded = jax.device_get(
            B.make_replay(pv, B.GRMU, **GRMU_KW)(cap))
    assert "span" in emitted
    assert plain.keys() == recorded.keys()
    for k in plain:
        assert np.array_equal(plain[k], recorded[k]), k


# ---------------------------------------------------------------------------
# SimResult serialization
# ---------------------------------------------------------------------------

def test_simresult_json_roundtrip():
    cluster, vms, _, _ = _events()
    res = simulate(cluster, POLICY_REGISTRY["FF"](cluster), vms)
    clone = metrics.SimResult.from_json(res.to_json())
    assert clone == res
    assert clone.rejection_reasons == res.rejection_reasons
    d = res.to_dict()
    assert d["schema_version"] == metrics.SCHEMA_VERSION


def test_simresult_rejects_unknown_schema():
    d = metrics.SimResult(policy="FF").to_dict()
    d["schema_version"] = 99
    with pytest.raises(ValueError, match="schema_version"):
        metrics.SimResult.from_dict(d)
    with pytest.raises(ValueError, match="schema_version"):
        metrics.SimResult.from_json(json.dumps(d))
