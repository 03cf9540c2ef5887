"""The backend-agnostic policy core: numpy and jax.numpy agree, and the
table-driven repack matches the object-level default policy.

The policy core is fleet-parameterized: every call takes the per-GPU
model-id vector ``mid`` and per-model profile ids.  These tests run the
single-model (A100-40GB) fleet — ``mid`` all zero, profile ids (1,) —
which is the paper's configuration; heterogeneous fleets are covered by
tests/test_device_models.py and tests/test_equivalence.py."""
import functools
import itertools

import numpy as np
import pytest

from repro.core import policy_core as pc
from repro.core.mig import (A30_24GB, A100_40GB, DEVICE_MODELS, GPU,
                            H100_80GB, PROFILES)

jax = pytest.importorskip("jax")
jnp = pytest.importorskip("jax.numpy")

_TN = pc.tables_for(np)
_TJ = pc.tables_for(jnp)


def _mid(n, xp=np):
    return xp.zeros(n, dtype=xp.int32)


def _pid(p, xp=np):
    """Single-model fleet: the request's per-model profile-id vector."""
    return xp.asarray([p], dtype=xp.int32)


def _random_state(rng, n_gpus=12):
    free = rng.integers(0, 256, size=n_gpus).astype(np.uint8)
    host_ok = rng.random(n_gpus) < 0.8
    return free, host_ok


@pytest.mark.parametrize("policy", [pc.FF, pc.BF, pc.MCC, pc.MECC])
def test_select_gpu_backends_agree(policy):
    rng = np.random.default_rng(0)
    for _ in range(50):
        free, host_ok = _random_state(rng)
        p = int(rng.integers(0, 6))
        w = (rng.integers(0, 40, size=(1, 6)) if policy == pc.MECC
             else None)
        got_np = int(pc.select_gpu(policy, np, _TN, _mid(free.size), free,
                                   _pid(p), host_ok, w))
        got_j = int(pc.select_gpu(
            policy, jnp, _TJ, _mid(free.size, jnp),
            jnp.asarray(free.astype(np.int32)), _pid(p, jnp),
            jnp.asarray(host_ok),
            jnp.asarray(w.astype(np.int32)) if w is not None else None))
        assert got_np == got_j


def test_grmu_select_backends_agree():
    rng = np.random.default_rng(1)
    for _ in range(50):
        free, host_ok = _random_state(rng)
        basket = rng.integers(0, 3, size=free.size).astype(np.int32)
        p = int(rng.integers(0, 6))
        heavy = p == pc.HEAVY_PROFILE
        r_np = pc.grmu_select(np, _TN, _mid(free.size), free, _pid(p),
                              heavy, host_ok, basket, 3, 5)
        r_j = pc.grmu_select(jnp, _TJ, _mid(free.size, jnp),
                             jnp.asarray(free.astype(np.int32)),
                             _pid(p, jnp), heavy, jnp.asarray(host_ok),
                             jnp.asarray(basket), 3, 5)
        assert tuple(int(x) for x in r_np) == tuple(int(x) for x in r_j)


def test_grmu_select_caps_are_strict():
    """Growth requires strictly fewer members than the cap (Alg. 3)."""
    free = np.full(4, 0, dtype=np.uint8)       # all full
    host_ok = np.ones(4, dtype=bool)
    basket = np.array([2, 2, 0, 0], np.int32)  # light at cap 2
    pick, grew, _ = pc.grmu_select(np, _TN, _mid(4), free, _pid(0), False,
                                   host_ok, basket, heavy_cap=2,
                                   light_cap=2)
    assert int(pick) == -1 and not bool(grew)
    pick, grew, gidx = pc.grmu_select(np, _TN, _mid(4), free, _pid(0),
                                      False, host_ok, basket, heavy_cap=2,
                                      light_cap=3)
    assert bool(grew) and int(gidx) == 2 and int(pick) == 2


def test_repack_matches_object_level_default_policy():
    """repack_gpu == replaying residents through GPU.assign in block
    order, for random reachable occupancy patterns."""
    rng = np.random.default_rng(2)
    for _ in range(100):
        # Build a random occupied GPU via the default policy itself.
        gpu = GPU()
        for vm in range(rng.integers(1, 6)):
            p = PROFILES[int(rng.integers(0, 6))]
            gpu.assign(("vm", vm), p)
        prof_by_block = np.full(8, -1, np.int32)
        for owner, (prof, start) in gpu.placements.items():
            prof_by_block[start] = PROFILES.index(prof)
        starts, ok, final_mask, moved = pc.repack_gpu(np, _TN, 0,
                                                      prof_by_block)
        # Object-level replay on a mock GPU, ascending current start.
        mock = GPU()
        expect_ok, n_moved = True, 0
        for b in range(8):
            if prof_by_block[b] < 0:
                continue
            ns = mock.assign(("m", b), PROFILES[int(prof_by_block[b])])
            if ns is None:
                expect_ok = False
                break
            assert int(starts[b]) == ns
            n_moved += int(ns != b)
        assert bool(ok) == expect_ok
        if expect_ok:
            assert int(moved) == n_moved
            assert int(final_mask) == mock.free_mask()


def test_defrag_target_skips_empty_and_nonpositive():
    free = np.array([255, 255, 255], np.uint8)   # all empty
    light = np.array([True, True, False])
    assert int(pc.defrag_target(np, _TN, _mid(3), free, light)) == -1
    # No light GPUs at all.
    assert int(pc.defrag_target(np, _TN, _mid(3), free,
                                np.zeros(3, bool))) == -1


def _sole_pids(sole_p):
    """(G,) own-model profiles -> (G, 1) per-model matrix (1-model fleet)."""
    return np.asarray(sole_p, np.int32)[:, None]


def test_consolidation_plan_pairs_in_index_order():
    # Four candidate GPUs, single host, all feasible: (0,1) and (2,3).
    G = 4
    free = np.full(G, pc.UPPER_HALF_FREE, np.uint8)  # lower half busy
    cand = np.ones(G, bool)
    sole_p = np.full(G, 3, np.int32)                 # 3g.20gb fits start 4
    zeros = np.zeros(G, np.float32)
    tgt, _, _ = pc.consolidation_plan(
        np, _TN, _mid(G), free, cand, _sole_pids(sole_p), zeros, zeros,
        np.zeros(G, np.int32), np.zeros(1, np.float32),
        np.zeros(1, np.float32), np.full(1, 100, np.float32),
        np.full(1, 100, np.float32))
    assert tgt.tolist() == [1, -1, 3, -1]


def test_consolidation_plan_respects_profile_feasibility():
    # 4g.20gb (start 0 only) cannot move onto a busy lower half.
    G = 2
    free = np.full(G, pc.UPPER_HALF_FREE, np.uint8)
    cand = np.ones(G, bool)
    sole_p = np.full(G, 4, np.int32)
    zeros = np.zeros(G, np.float32)
    tgt, _, _ = pc.consolidation_plan(
        np, _TN, _mid(G), free, cand, _sole_pids(sole_p), zeros, zeros,
        np.zeros(G, np.int32), np.zeros(1, np.float32),
        np.zeros(1, np.float32), np.full(1, 100, np.float32),
        np.full(1, 100, np.float32))
    assert tgt.tolist() == [-1, -1]


def test_consolidation_plan_respects_host_headroom():
    # Cross-host move blocked by CPU; same-host move always allowed.
    G = 2
    free = np.full(G, pc.UPPER_HALF_FREE, np.uint8)
    cand = np.ones(G, bool)
    sole_p = np.full(G, 3, np.int32)
    cpu = np.full(G, 4.0, np.float32)
    zeros = np.zeros(G, np.float32)
    hosts = np.array([0, 1], np.int32)
    cpu_used = np.array([4.0, 7.0], np.float32)
    cpu_cap = np.array([8.0, 8.0], np.float32)
    big = np.full(2, 100.0, np.float32)
    tgt, cpu_out, _ = pc.consolidation_plan(
        np, _TN, _mid(G), free, cand, _sole_pids(sole_p), cpu, zeros,
        hosts, cpu_used, np.zeros(2, np.float32), cpu_cap, big)
    assert tgt.tolist() == [-1, -1]          # 7 + 4 > 8 on host 1
    cpu_used = np.array([4.0, 3.0], np.float32)
    tgt, cpu_out, _ = pc.consolidation_plan(
        np, _TN, _mid(G), free, cand, _sole_pids(sole_p), cpu, zeros,
        hosts, cpu_used, np.zeros(2, np.float32), cpu_cap, big)
    assert tgt.tolist() == [1, -1]
    assert cpu_out.tolist() == [0.0, 7.0]    # resources moved with the VM


# ---------------------------------------------------------------------------
# fit_mask: feasibility from the free bitmask equals the fits table
# ---------------------------------------------------------------------------

_FLEETS = {m.name: (m,) for m in DEVICE_MODELS.values()}
_FLEETS["A30+A100+H100"] = (A30_24GB, A100_40GB, H100_80GB)


@pytest.mark.parametrize("fleet", list(_FLEETS))
def test_fit_mask_equals_fits_table(fleet):
    """Every reachable mask of every model, every per-model profile-id
    combination (ids past a smaller model's profiles included), GPUs of
    the models in random order, and padded GPUs (free 0, model 0): both
    backends give ``T.fits[mid, free, pids[mid]]`` exactly."""
    models = _FLEETS[fleet]
    TN, TJ = pc.tables_for(np, models), pc.tables_for(jnp, models)
    rng = np.random.default_rng(len(models))
    mid = np.concatenate([np.full(m.num_masks, i, np.int32)
                          for i, m in enumerate(models)])
    free = np.concatenate([np.arange(m.num_masks) for m in models])
    order = rng.permutation(mid.size)
    pad = 8
    mid = np.concatenate([mid[order], np.zeros(pad, np.int32)])
    free = np.concatenate([free[order], np.zeros(pad)]).astype(np.uint8)
    jfit = jax.jit(lambda mid, free, pids: pc.fit_mask(jnp, TJ, mid, free,
                                                       pids))
    for pids in itertools.product(range(TN.num_profiles),
                                  repeat=len(models)):
        pids = np.asarray(pids, np.int32)
        want = TN.fits[mid, free, pids[mid]]
        assert not want[-pad:].any()
        got_np = pc.fit_mask(np, TN, mid, free, pids)
        got_j = np.asarray(jfit(jnp.asarray(mid),
                                jnp.asarray(free.astype(np.int32)),
                                jnp.asarray(pids)))
        np.testing.assert_array_equal(got_np, want)
        np.testing.assert_array_equal(got_j, want)


def _table_gathers(jaxpr, table):
    """Per-GPU gathers (non-scalar output) whose operand has ``table``'s
    shape and dtype, in ``jaxpr`` and every sub-jaxpr (scan, while, cond
    bodies)."""
    found = []
    for eqn in jaxpr.eqns:
        src = eqn.invars[0].aval if eqn.invars else None
        if (eqn.primitive.name == "gather"
                and (src.shape, src.dtype) == (table.shape, table.dtype)
                and eqn.outvars[0].aval.shape != ()):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _table_gathers(sub, table)
    return found


def _grmu_call(T):
    return lambda mid, free, pids, ok, basket: pc.grmu_select(
        jnp, T, mid, free, pids, False, ok, basket, 3, 5)[0]


def _ff_call(T):
    return lambda mid, free, pids, ok, basket: pc.select_gpu(
        pc.FF, jnp, T, mid, free, pids, ok)


def _table_call(T):
    """The per-GPU table gather itself: the check must see this one."""
    return lambda mid, free, pids, ok, basket: T.fits[mid, free, pids[mid]]


@pytest.mark.parametrize("fleet", ["A100-40GB", "A30+A100+H100"])
@pytest.mark.parametrize("call,expect", [(_grmu_call, 0), (_ff_call, 0),
                                         (_table_call, 1)])
def test_selection_does_not_gather_the_fits_table(fleet, call, expect):
    """GRMU and FF selection on the ``jnp`` tables test fit elementwise:
    no gather from an operand of ``T.fits``'s shape."""
    T = pc.tables_for(jnp, _FLEETS[fleet])
    G = 64
    args = (jnp.zeros(G, jnp.int32), jnp.zeros(G, jnp.int32),
            jnp.zeros(T.num_models, jnp.int32), jnp.ones(G, bool),
            jnp.zeros(G, jnp.int32))
    jaxpr = jax.make_jaxpr(call(T))(*args).jaxpr
    assert len(_table_gathers(jaxpr, T.fits)) == expect


@pytest.mark.parametrize("policy", ["FF", "BF", "MCC", "MECC", "GRMU"])
def test_replay_scan_does_not_gather_the_fits_table_per_gpu(policy):
    """The whole replay scan (GRMU with defrag and consolidation) keeps
    only scalar lookups of ``T.fits`` (the defrag repack's)."""
    from repro.core import batched as B
    from repro.core.bucketing import pad_events
    from repro.workload.alibaba import TraceConfig, generate

    cluster, vms = generate(TraceConfig(scale=0.02, seed=0))
    pv = pad_events(B.build_events(vms, cluster))
    cfg = (dict(defrag=True, consolidation_interval=6.0)
           if policy == "GRMU" else {})
    st = B.replay_statics(pv, getattr(B, policy), score_backend="tables",
                          **cfg)
    jaxpr = jax.make_jaxpr(functools.partial(B._scan_fn, st))(
        B.init_state(pv, st), B.trace_arrays(pv), jnp.int32(3)).jaxpr
    fits = pc.tables_for(jnp, st.models).fits
    assert _table_gathers(jaxpr, fits) == []
