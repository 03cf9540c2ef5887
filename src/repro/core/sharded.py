"""Sharded-fleet replay: shard_map over GPU partitions + argmax reconcile.

The replay scan is inherently sequential over events, but the per-arrival
work — gathering feasibility and scores for every GPU — is embarrassingly
parallel over the fleet.  This module runs the *same* scan body
(``repro.core.batched._scan_fn``) under ``jax.shard_map``
with the cluster state replicated on every shard and only the expensive
per-arrival table gathers computed on each shard's contiguous GPU slice:

  * baseline policies (FF/BF/MCC/MECC): each shard scores its ``G/K``
    GPUs and contributes ``(best local score, global index, any-fit)``;
    an ``all_gather`` + argmax over the K candidates picks the winner.
    Shards cover contiguous index ranges in order and ``argmax`` returns
    the first maximizer, so ties resolve to the lowest global index —
    exactly the single-shard first-maximizer semantics;
  * GRMU first-fit: each shard reports its first in-basket fit as a
    global index (or a +inf sentinel); the reconcile is a cheap ``min``.
    Growth/defrag/consolidation touch O(G) masks, not O(G·tables), and
    run replicated — every shard computes the identical update.

Because every reconcile provably picks the same GPU the single-shard
engine would, the sharded path is decision-identical by construction —
and asserted so in tests/test_sharded.py and the benchmark ladder's
``sharded_decisions_match`` equivalence mode.

Sharding composes with chunk streaming: ``repro.core.streaming`` wraps
the per-chunk scan body in the same fleet-partition shard_map
(``make_chunked_replay(..., num_shards=K)``), so a sharded fleet can
also stream its event chunks with only O(chunk) trace bytes resident.

In-scan telemetry (``repro.obs.inscan``, ``telemetry=True`` statics)
needs **no** cross-shard reconcile of its own: every telemetry
accumulator is computed from replicated operands (the post-reconcile
cluster state, the replicated ``T`` tables and growth flags), so all K
shards hold bit-identical telemetry arrays and the replicated-out
``P()`` spec returns any one of them unchanged — merging is the
identity, preserving the O(K) reconcile budget.

Run with virtual host devices for CPU testing/benchmarks:
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set *before*
importing jax — ``benchmarks/run.py --perf-env`` or
``benchmarks/perf_env.sh`` do this).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..sim.metrics import SimResult
from . import compile_cache
from . import policy_core as pc
from .batched import (EventTrace, _scan_fn, default_heavy_capacity,
                      init_state, replay_statics, result_from_arrays,
                      trace_arrays)

FLEET_AXIS = "fleet"

_INT_SENTINEL = np.iinfo(np.int32).min  # below every feasible int score
_BIG_IDX = np.iinfo(np.int32).max


def _local_slice(arr, start, size):
    return jax.lax.dynamic_slice_in_dim(arr, start, size, axis=0)


def select_gpu_sharded(policy, T, mid, free, pids, host_ok, mecc_w,
                       axis_name, num_shards):
    """Sharded FF/BF/MCC/MECC pick — decision-identical to
    ``policy_core.select_gpu``.

    All operands are replicated; each shard computes fits and scores only
    for its contiguous ``G/K`` slice.  Feasible scores always rank strictly
    above infeasible sentinels (policy_core's invariant), so the local
    argmax is the local first maximizer; the cross-shard argmax over
    (score, first-shard-wins) is then the global first maximizer."""
    G = free.shape[0]
    Gl = G // num_shards
    start = jax.lax.axis_index(axis_name) * Gl
    prof_g = pids[mid]
    lmid = _local_slice(mid, start, Gl)
    lfree = _local_slice(free, start, Gl)
    lprof = _local_slice(prof_g, start, Gl)
    lhost = _local_slice(host_ok, start, Gl)
    lfits = pc.fit_mask(jnp, T, lmid, lfree, pids) & lhost
    lscores = pc.placement_scores(policy, jnp, T, lmid, lfree, lprof,
                                  lfits, mecc_w)
    lbest = jnp.argmax(lscores)
    lany = jnp.any(lfits)
    cand_s = jax.lax.all_gather(
        jnp.where(lany, lscores[lbest].astype(jnp.int32),
                  jnp.int32(_INT_SENTINEL)), axis_name)
    cand_i = jax.lax.all_gather((start + lbest).astype(jnp.int32),
                                axis_name)
    cand_any = jax.lax.all_gather(lany, axis_name)
    win = jnp.argmax(cand_s)
    return jnp.where(jnp.any(cand_any), cand_i[win], -1)


def grmu_select_sharded(T, mid, free, pids, is_heavy, host_ok, basket,
                        heavy_cap, light_cap, axis_name, num_shards):
    """Sharded Alg. 3 — decision-identical to ``policy_core.grmu_select``.

    The first-fit scan over the request's basket is sharded (each shard
    reports its first fit as a global index, reconcile = min); the growth
    decision reads only the replicated basket labels and is computed
    identically on every shard."""
    G = free.shape[0]
    Gl = G // num_shards
    start = jax.lax.axis_index(axis_name) * Gl
    is_heavy = jnp.asarray(is_heavy)
    want = jnp.where(is_heavy, pc.HEAVY_BASKET, pc.LIGHT_BASKET)
    cap = jnp.where(is_heavy, heavy_cap, light_cap)
    in_basket = basket == want
    lmid = _local_slice(mid, start, Gl)
    lfree = _local_slice(free, start, Gl)
    lok = (_local_slice(host_ok, start, Gl)
           & _local_slice(in_basket, start, Gl))
    lfits = pc.fit_mask(jnp, T, lmid, lfree, pids) & lok
    lpick = pc.first_true(jnp, lfits)
    cand = jax.lax.all_gather(
        jnp.where(lpick >= 0, (start + lpick).astype(jnp.int32),
                  jnp.int32(_BIG_IDX)), axis_name)
    first = jnp.min(cand)
    pick = jnp.where(first < _BIG_IDX, first, -1)
    # Replicated growth (Alg. 3's fetch-then-place, as in grmu_select).
    pool_free = basket == pc.POOL
    grew = (pick < 0) & (in_basket.sum() < cap) & jnp.any(pool_free)
    grow_idx = jnp.argmax(pool_free)
    grown_pick = jnp.where(grew & host_ok[grow_idx], grow_idx, -1)
    return jnp.where(pick >= 0, pick, grown_pick), grew, grow_idx


# ---------------------------------------------------------------------------
# Replay drivers
# ---------------------------------------------------------------------------

def fleet_mesh(num_shards: Optional[int] = None) -> Mesh:
    """1-D fleet mesh over the first ``num_shards`` visible devices: one
    shard per chip on a TPU host; on CPU, visible-device count comes from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    devs = jax.devices()
    k = num_shards or len(devs)
    if k > len(devs):
        raise ValueError(
            f"num_shards={k} but only {len(devs)} "
            f"{devs[0].platform} devices are visible: the host has fewer "
            "chips than shards, or on CPU "
            "XLA_FLAGS=--xla_force_host_platform_device_count is unset or "
            "too small (it must be set before jax is imported; "
            "benchmarks/run.py --perf-env sets it)")
    return Mesh(np.array(devs[:k]), (FLEET_AXIS,))


def make_sharded_replay(events: EventTrace, policy: int,
                        num_shards: Optional[int] = None,
                        **cfg) -> Callable:
    """Sharded twin of ``batched.make_replay`` — same signature, same
    outputs, same decisions.  Requires the padded GPU count to divide by
    ``num_shards`` (bucket with ``pad_events(events, shards=K)``)."""
    compile_cache.ensure_persistent_cache()
    mesh = fleet_mesh(num_shards)
    k = mesh.devices.size
    G = len(events.gpu_model_id)
    if G % k:
        raise ValueError(
            f"num_gpus={G} does not divide over {k} shards; bucket the "
            f"trace first: repro.core.bucketing.pad_events(ev, shards={k})")
    st = replay_statics(events, policy, score_backend="tables",
                        axis_name=FLEET_AXIS, num_shards=k, **cfg)

    def build():
        body = jax.shard_map(functools.partial(_scan_fn, st), mesh=mesh,
                             in_specs=(P(), P(), P()), out_specs=P(),
                             check_vma=False)
        return jax.jit(body, donate_argnums=(0,))

    jfn = compile_cache.cached_replay_fn((st, k, "shard"), build)
    tr = {key: jnp.asarray(v) for key, v in trace_arrays(events).items()}

    def run(heavy_capacity):
        return jfn(init_state(events, st), tr,
                   jnp.asarray(heavy_capacity, jnp.int32))

    return run


def replay_sharded(events: EventTrace, policy: int, heavy_capacity=None,
                   num_shards: Optional[int] = None, **cfg) -> SimResult:
    """Sharded twin of ``batched.replay`` (full SimResult)."""
    if heavy_capacity is None:
        heavy_capacity = default_heavy_capacity(events)
    fn = make_sharded_replay(events, policy, num_shards, **cfg)
    return result_from_arrays(events, policy,
                              jax.device_get(fn(heavy_capacity)))


__all__ = ["FLEET_AXIS", "fleet_mesh", "select_gpu_sharded",
           "grmu_select_sharded", "make_sharded_replay", "replay_sharded"]
