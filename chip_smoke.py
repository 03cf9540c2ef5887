"""Chip smoke run: the placement engine and service on a TPU, at the
paper's fleet and stream (1,213 hosts, 8,063 VMs; ``workload/alibaba.py``).

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: sharded replay only

One chip runs, in order:

  1. device      — a TPU must be JAX's default device, else exit non-zero;
  2. replay      — ``B.replay`` for FF, BF, MCC, MECC, GRMU and GRMU with
                   6 h consolidation, each equal (accepted ids, hourly
                   active hardware, migrations) to the sequential engine
                   (``sim/engine.simulate``); MCC/MECC take the compiled
                   Pallas kernels (``score_backend="auto"``) and must also
                   equal the table-gather path;
  3. chunked     — GRMU through ``core.streaming`` in >= 8 chunks, equal
                   to the unchunked replay;
  4. served      — the trace's request stream through ``PlacementService``
                   (GRMU, micro-batch 64), equal to the offline replay.

``--four-chips`` runs only ``replay_sharded`` over a 4-chip fleet mesh for
the five policies, each equal to ``B.replay`` on one chip.

Every phase prints one line; times and latencies are informational.  Any
mismatch raises, so the exit code is non-zero.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0            # trace seed (workload.alibaba.TraceConfig)
MIN_CHUNKS = 8      # the chunked phase streams the trace in >= 8 chunks
MICRO_BATCH = 64


def log(phase: str, msg: str) -> None:
    print(f"[chip_smoke] {phase}: {msg}", flush=True)


def require_tpu(n_chips: int) -> dict:
    """The device JAX runs on; exits non-zero unless it is a TPU with at
    least ``n_chips`` chips."""
    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX found "
                         f"{len(devs)}")
    from repro.core import compile_cache
    log("device", f"kind={devs[0].device_kind!r} count={len(devs)} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"compile_cache={compile_cache.ensure_persistent_cache()}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def paper_trace(scale: float = 1.0):
    """(cluster, vms, events) of the paper-shaped Alibaba trace; a fresh
    cluster per call (the sequential engine mutates it)."""
    from repro.core import batched as B
    from repro.workload.alibaba import TraceConfig, generate

    cluster, vms = generate(TraceConfig(scale=scale, seed=SEED))
    return cluster, vms, B.build_events(vms, cluster)


def assert_same(what: str, got, want) -> None:
    """Decision-for-decision equality of two SimResults."""
    for field in ("accepted_ids", "hourly_active_hw", "intra_migrations",
                  "inter_migrations"):
        a, b = getattr(got, field), getattr(want, field)
        if a != b:
            raise AssertionError(f"{what}: {field} differs")


# The five registry policies plus GRMU with periodic consolidation:
# (label, policy name, replay cfg).
REPLAY_CASES = (
    ("FF", "FF", {}),
    ("BF", "BF", {}),
    ("MCC", "MCC", {}),
    ("MECC", "MECC", {}),
    ("GRMU", "GRMU", {}),
    ("GRMU+cons6h", "GRMU", {"consolidation_interval": 6.0}),
)


def sequential_reference(name: str, cfg: dict, scale: float):
    from repro.core.grmu import GRMU
    from repro.core.policies import POLICY_REGISTRY
    from repro.sim.engine import simulate

    cluster, vms, _ = paper_trace(scale)
    if name == "GRMU":
        pol = GRMU(cluster, heavy_capacity_frac=0.30, **cfg)
    else:
        pol = POLICY_REGISTRY[name](cluster)
    return simulate(cluster, pol, vms)


def replay_phase(scale: float = 1.0, kernel_backend: str = "auto"):
    """Offline replay of every case against the sequential engine;
    returns the events and the GRMU (default knobs) result for the later
    phases."""
    from repro.core import batched as B
    from repro.core.bucketing import pad_events

    _, _, ev = paper_trace(scale)
    pv = pad_events(ev)
    log("replay", f"{ev.num_hosts} hosts, {ev.num_gpus} GPUs (padded "
        f"{len(pv.gpu_model_id)}), {ev.num_vms} VMs, {len(ev.kind)} events "
        f"(padded {len(pv.kind)})")
    out = {}
    for label, name, cfg in REPLAY_CASES:
        pid = getattr(B, name)
        backend = kernel_backend if name in ("MCC", "MECC") else "auto"
        resolved = B.replay_statics(pv, pid, score_backend=backend,
                                    **cfg).score_backend
        if name in ("MCC", "MECC") and resolved == "tables":
            raise AssertionError(f"{label}: score_backend={backend!r} did "
                                 "not take the Pallas kernels")
        t0 = time.perf_counter()
        res = B.replay(pv, pid, score_backend=backend, **cfg)
        t1 = time.perf_counter()
        again = B.replay(pv, pid, score_backend=backend, **cfg)
        t2 = time.perf_counter()
        assert_same(f"{label} repeat", again, res)
        ref = sequential_reference(name, cfg, scale)
        t3 = time.perf_counter()
        assert_same(f"{label} vs sequential", res, ref)
        extra = ""
        if name in ("MCC", "MECC"):
            tab = B.replay(pv, pid, score_backend="tables", **cfg)
            assert_same(f"{label} {resolved} vs tables", res, tab)
            extra = ", == tables"
        log("replay", f"{label} [{resolved}] parity ok (== sequential"
            f"{extra}): accepted {res.accepted}/{res.total_requests}, "
            f"migrations {res.migrations}; first call {t1 - t0:.3f}s, "
            f"warm {t2 - t1:.3f}s, sequential {t3 - t2:.3f}s "
            "(informational)")
        out[label] = res
    return ev, out["GRMU"]


def chunked_phase(ev, grmu) -> None:
    import jax
    from repro.core import batched as B
    from repro.core.streaming import make_chunked_replay

    # The largest power-of-two chunk that still gives MIN_CHUNKS chunks.
    chunk = 1 << ((len(ev.kind) // MIN_CHUNKS).bit_length() - 1)
    t0 = time.perf_counter()
    run = make_chunked_replay(ev, B.GRMU, chunk_events=chunk)
    out = run(B.default_heavy_capacity(ev))
    res = B.result_from_arrays(run.events, B.GRMU, jax.device_get(out))
    dt = time.perf_counter() - t0
    if run.num_chunks < MIN_CHUNKS:
        raise AssertionError(f"chunked: only {run.num_chunks} chunks")
    assert_same("chunked GRMU vs unchunked", res, grmu)
    if res.hourly_acceptance != grmu.hourly_acceptance:
        raise AssertionError("chunked GRMU: hourly_acceptance differs")
    log("chunked", f"GRMU {run.num_chunks} chunks x {chunk} events "
        f"parity ok (== unchunked); {dt:.3f}s incl. compile "
        "(informational)")


def served_phase(ev, grmu) -> None:
    from repro.serve import PlacementService, ServeConfig, requests_from_trace

    reqs, horizon = requests_from_trace(ev)
    cfg = ServeConfig(policy="GRMU", micro_batch=MICRO_BATCH)
    svc = PlacementService.for_trace(ev, cfg)
    if svc.cfg.max_vms < ev.num_vms:
        raise AssertionError("served: max_vms below the trace's VMs")
    t0 = time.perf_counter()
    for r in reqs:
        while not svc.submit(r):      # backpressure: drain, retry
            svc.drain(max_batches=1)
    svc.drain()
    svc.flush(horizon)
    wall = time.perf_counter() - t0
    if svc.accepted_ids() != list(grmu.accepted_ids):
        raise AssertionError("served GRMU: accepted ids differ from the "
                             "offline replay")
    if svc.migrations() != (grmu.intra_migrations, grmu.inter_migrations):
        raise AssertionError("served GRMU: migrations differ from the "
                             "offline replay")
    st = svc.stats()
    log("served", f"GRMU micro_batch={MICRO_BATCH} parity ok (== offline): "
        f"{st['decisions']} decisions, {st['accepted']} accepted; "
        f"informational, cold (first batch compiles): {wall:.3f}s, "
        f"{st['decisions'] / wall:.1f} arrivals/s, p50 {st['p50_ms']:.3f} "
        f"ms, p99 {st['p99_ms']:.3f} ms")


def sharded_phase(scale: float = 1.0, num_shards: int = 4) -> None:
    """Sharded replay over ``num_shards`` chips vs ``B.replay`` on one."""
    import jax
    from repro.core import batched as B
    from repro.core import sharded as SH
    from repro.core.bucketing import pad_events

    _, _, ev = paper_trace(scale)
    pv = pad_events(ev, shards=num_shards)
    cap = B.default_heavy_capacity(pv)
    for name in ("FF", "BF", "MCC", "MECC", "GRMU"):
        pid = getattr(B, name)
        single = B.replay(pv, pid)
        t0 = time.perf_counter()
        B.replay(pv, pid)
        t1 = time.perf_counter()
        run = SH.make_sharded_replay(pv, pid, num_shards=num_shards)
        out = jax.block_until_ready(run(cap))
        t2 = time.perf_counter()
        jax.block_until_ready(run(cap))
        t3 = time.perf_counter()
        devs = out["accepted"].sharding.device_set
        if len({d.id for d in devs}) != num_shards:
            raise AssertionError(f"sharded {name}: ran on {len(devs)} "
                                 f"devices, want {num_shards}")
        res = B.result_from_arrays(pv, pid, jax.device_get(out))
        assert_same(f"sharded {name} vs one chip", res, single)
        log("sharded", f"{name} x{num_shards} on devices "
            f"{sorted(d.id for d in devs)} parity ok (== one chip); "
            f"informational: one chip warm {t1 - t0:.3f}s, {num_shards} "
            f"chips first call {t2 - t1:.3f}s, warm {t3 - t2:.3f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded replay parity phase")
    args = ap.parse_args(argv)
    device = require_tpu(4 if args.four_chips else 1)
    t0 = time.perf_counter()
    if args.four_chips:
        sharded_phase()
    else:
        ev, grmu = replay_phase()
        chunked_phase(ev, grmu)
        served_phase(ev, grmu)
    log("done", f"{time.perf_counter() - t0:.1f}s (informational)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
