"""Shape bucketing is decision-neutral.

Property: padding a trace to its power-of-two bucket
(``repro.core.bucketing.pad_events`` — PAD events, zero-capacity hosts,
never-feasible GPUs, +inf MECC observations) changes *nothing* about the
replay: per-VM decisions, per-profile tallies, hourly series, and
migration counts are identical for every registry policy, on two seeds,
on a mixed A30+A100+H100 fleet.  Also pins the cache contract (same
bucket + same statics = no recompile) and the Pallas scoring backend's
decision parity with the table path.
"""
import numpy as np
import pytest

from repro.core import batched as B
from repro.core import compile_cache
from repro.core.bucketing import bucket_shape, next_pow2, pad_events
from test_equivalence import hetero_scenario, random_scenario

POLICIES = {
    "FF": (B.FF, {}),
    "BF": (B.BF, {}),
    "MCC": (B.MCC, {}),
    "MECC": (B.MECC, {}),
    "GRMU": (B.GRMU, dict(defrag=True, consolidation_interval=6.0)),
}


def assert_same_replay(r0, r1):
    assert r1.accepted_ids == r0.accepted_ids
    assert r1.per_profile_accepted == r0.per_profile_accepted
    assert r1.per_profile_total == r0.per_profile_total
    assert r1.hourly_acceptance == r0.hourly_acceptance
    assert r1.hourly_active_hw == r0.hourly_active_hw
    assert r1.intra_migrations == r0.intra_migrations
    assert r1.inter_migrations == r0.inter_migrations


@pytest.mark.parametrize("policy", list(POLICIES))
@pytest.mark.parametrize("seed", [0, 1])
def test_padded_replay_decision_identical_hetero(policy, seed):
    pid, kw = POLICIES[policy]
    cluster, vms = hetero_scenario(seed)
    ev = B.build_events(vms, cluster)
    pv = pad_events(ev)
    assert all(b >= a for a, b in zip(bucket_shape(ev),
                                      bucket_shape(pv)))
    cap = B.default_heavy_capacity(ev)
    assert_same_replay(B.replay(ev, pid, cap, **kw),
                       B.replay(pv, pid, cap, **kw))


def test_pad_events_is_idempotent_and_pow2():
    cluster, vms = hetero_scenario(0)
    ev = B.build_events(vms, cluster)
    pv = pad_events(ev)
    assert all(x == next_pow2(x) for x in bucket_shape(pv))
    pv2 = pad_events(pv)
    assert bucket_shape(pv2) == bucket_shape(pv)
    assert np.array_equal(pv2.kind, pv.kind)
    # Logical sizes survive padding (results are keyed off them).
    assert pv.num_vms == ev.num_vms
    assert pv.num_gpus == ev.num_gpus
    assert pv.num_hosts == ev.num_hosts
    assert np.array_equal(pv.vm_ids, ev.vm_ids)
    assert np.array_equal(pv.step_times, ev.step_times)


def test_same_bucket_same_statics_reuses_compiled_replay():
    """Two different traces in one shape bucket share one executable:
    the process cache returns the same jitted fn and the second trace's
    shapes hit XLA's jit cache (the bucketing tentpole's whole point)."""
    caps = []
    outs = []
    before = dict(compile_cache.cache_stats())
    for seed in (0, 1):
        cluster, vms = random_scenario(seed)
        pv = pad_events(B.build_events(vms, cluster))
        caps.append(bucket_shape(pv))
        fn = B.make_replay(pv, B.FF)
        outs.append(fn(0))
    after = compile_cache.cache_stats()
    assert caps[0] == caps[1]            # same bucket by construction
    # Second make_replay with identical statics must not rebuild.
    assert after["misses"] - before["misses"] <= 1
    assert after["hits"] >= before["hits"] + 1


def test_min_shape_and_shards_constraints():
    cluster, vms = random_scenario(0)
    ev = B.build_events(vms, cluster)
    pv = pad_events(ev, shards=4, min_gpus=128)
    assert len(pv.gpu_model_id) % 4 == 0
    assert len(pv.gpu_model_id) >= 128
    forced = pad_events(ev, min_shape=bucket_shape(pv))
    assert bucket_shape(forced) == bucket_shape(pv)
    with pytest.raises(ValueError):
        pad_events(ev, shards=3)


@pytest.mark.parametrize("policy", ["MCC", "MECC"])
def test_pallas_backend_matches_tables(policy):
    """score_backend='pallas_interpret' (the CPU-exact kernel path) picks
    the same GPU as the table gathers on every arrival."""
    pid, _ = POLICIES[policy]
    cluster, vms = random_scenario(2)
    pv = pad_events(B.build_events(vms, cluster), min_gpus=128)
    rt = B.replay(pv, pid, score_backend="tables")
    rp = B.replay(pv, pid, score_backend="pallas_interpret")
    assert_same_replay(rt, rp)


def test_kernels_interpret_only_when_asked():
    """Off a TPU, "auto" takes the table path and an explicit "pallas"
    fails to lower instead of silently running the interpreter."""
    cluster, vms = random_scenario(2)
    pv = pad_events(B.build_events(vms, cluster), min_gpus=128)
    assert B.replay_statics(pv, B.MCC).score_backend == "tables"
    with pytest.raises(Exception, match="interpret"):
        B.replay(pv, B.MCC, score_backend="pallas")


def test_pallas_backend_requires_lane_aligned_single_model():
    cluster, vms = hetero_scenario(0)          # M=3 fleet
    pv = pad_events(B.build_events(vms, cluster), min_gpus=128)
    with pytest.raises(ValueError):
        B.replay(pv, B.MCC, score_backend="pallas_interpret")
    cluster, vms = random_scenario(0)          # single model, G=16
    ev = pad_events(B.build_events(vms, cluster))
    with pytest.raises(ValueError):
        B.replay(ev, B.MCC, score_backend="pallas_interpret")
