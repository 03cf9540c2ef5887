"""JAX-vectorized trace replay — the framework's on-device sweep engine.

The Python engine (``repro.sim.engine``) is the faithful sequential
reference.  This module replays the same event stream as a single
``lax.scan`` over (departure | arrival | step-end) events with the cluster
state held in arrays, so that:

  * one replay jit-compiles end to end (no Python in the loop),
  * ``jax.vmap`` over policy knobs (e.g. heavy-basket capacity) runs the
    paper's §8.2 parameter sweeps as one device program,
  * on TPU the per-event scoring can use the Pallas kernels instead of the
    (CPU-friendly) per-model mask-table gathers.

Heterogeneous fleets replay in the same single scan: every per-model
table is padded to a common shape and stacked along a leading model axis
(``policy_core.Tables``), the trace carries the per-GPU model-id vector
plus each VM's Eq. 27-30 profile mapping onto every fleet model, and all
table lookups gather by ``(model_id, free_mask, profile)``.

Scale path (hyperscale replay; see docs/ARCHITECTURE.md):

  * the scan body is compiled as a function of the *trace arrays* — the
    event stream, fleet topology and VM metadata are jit **arguments**
    (one pytree, ``trace_arrays``), not closed-over constants, so two
    traces with the same padded shapes share one executable;
  * ``repro.core.bucketing.pad_events`` pads every trace dimension to a
    power-of-two bucket with provably decision-neutral padding (PAD
    events, zero-capacity hosts, never-feasible GPUs), making the
    compile cache effective across scales and fleets;
  * the initial scan state is built per call (``init_state``) and
    **donated** to the compiled function, so XLA reuses the state
    buffers in place across the scan instead of copying them;
  * all in-scan state is 32-bit (int32/float32) and every metric series
    is accumulated into preallocated in-scan buffers (``hourly``,
    ``counts``) — a 1M-VM / 10k-GPU trace fits comfortably on host CPU;
  * the trace itself is **bit-packed** (uint8 event kinds, int16 profile
    columns; int32 only for VM/GPU indices) and widened per gathered
    scalar inside the scan step, and ``repro.core.streaming`` drives the
    same step over fixed-size event *chunks* with a donated carry, so
    only O(chunk) trace bytes are resident at once — trace size no
    longer bounds replay size (the 10M-VM / 100k-GPU ladder rung);
  * ``repro.core.sharded`` wraps the same scan body in ``shard_map`` so
    the per-arrival scoring gathers run on fleet partitions with a cheap
    cross-shard argmax reconcile (decision-identical to this module);
  * ``score_backend="pallas"`` routes MCC/MECC scoring through the
    compiled Pallas kernels (``repro.kernels.policy_score``); ``"auto"``
    takes them on a TPU for fleets they tile, and ``"pallas_interpret"``
    runs them in interpret mode (the CPU test path).

Feature parity with the sequential engine (validated decision-for-decision
in tests/test_equivalence.py, including on mixed A30+A100+H100 clusters):

  * host CPU/RAM constraints, carried as per-host float32 headroom arrays
    (the sequential ``Cluster`` accumulates in float32 in the same event
    order, so feasibility comparisons are bit-identical);
  * all five policies — FF/BF/MCC/MECC/GRMU — via the shared
    ``repro.core.policy_core`` scoring/selection functions;
  * MECC's windowed profile-frequency estimate, maintained *inside* the
    scan with a two-pointer over the (static) arrival schedule, counted
    per (model, profile);
  * GRMU defragmentation and periodic consolidation as table-driven
    in-scan operations at step-end events (ASSIGN_MASK/ASSIGN_START/FRAG
    gathers — no object state);
  * hourly acceptance / active-hardware series, sampled at step-end events
    exactly where the sequential engine samples, so ``replay`` returns a
    full ``SimResult``.

Within each step (1 h bucket): departures are processed first, then
arrivals, then the step-end hook (defrag -> consolidation -> metrics);
scans resolve ties by lowest globalIndex.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import warnings
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

# The replay donates its initial state (see init_state) so XLA may reuse
# the carry buffers in place.  The replay's *outputs* are small reductions
# of the carry, so no output can alias a donated input — jax warns about
# exactly that on every compile; the donation is still what lets the scan
# run the 1M-VM state without a second live copy, so the warning is noise
# here.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

from ..sim.cluster import VM, Cluster
from ..sim.metrics import SimResult
from .mig import A100_40GB, DeviceModel, PROFILE_INDEX
from . import policy_core as pc
from . import compile_cache
from ..obs import inscan as obs_inscan
from ..obs import reasons as obs_reasons
from ..obs import recorder as obs_recorder

# Policy ids re-exported for callers of this module.  The old engine's
# "GRMU-DB" policy id is gone: the DB point is GRMU with defrag=False,
# consolidation_interval=None (``sweep_heavy_capacity``'s defaults).
FF, BF, MCC, MECC, GRMU = pc.FF, pc.BF, pc.MCC, pc.MECC, pc.GRMU

HEAVY_PROFILE = pc.HEAVY_PROFILE

# Event kinds, in within-bucket processing order.  PAD rows are appended
# by ``repro.core.bucketing.pad_events`` and are a proven no-op: the
# scan's PAD branch returns the state unchanged.
DEPARTURE, ARRIVAL, STEP_END, PAD = 0, 1, 2, 3

# Basket label of GPUs that only exist as shape padding: never selectable,
# never grown, never a defrag/consolidation candidate.
PAD_BASKET = -1

_EPS = 1e-9


@dataclasses.dataclass
class EventTrace:
    """Host-precomputed event stream + static cluster/VM metadata.

    The big arrays are **bit-packed**: event kinds are ``uint8`` and
    profile indices ``int16`` (profile counts are tiny), with ``int32``
    reserved for the VM/GPU indices that actually need the range.  The
    scan widens every gathered scalar back to int32 before any decision
    arithmetic (``_scan_fn``), so packing changes bytes-at-rest only —
    decisions are bit-identical to the legacy int32 layout.

    ``num_vms`` / ``num_gpus`` / ``num_hosts`` / ``vm_ids`` /
    ``step_times`` always describe the *logical* (unpadded) trace; after
    ``repro.core.bucketing.pad_events`` the array fields may be longer
    (power-of-two buckets, or multiples of a streaming chunk) and
    ``hourly_slots`` carries the padded metric-buffer length."""
    # Per-event rows (E,), sorted by (bucket, kind, time, vm_id):
    kind: np.ndarray         # uint8: DEPARTURE | ARRIVAL | STEP_END | PAD
    vm_index: np.ndarray     # int32 dense 0..N-1 (0 for step-end rows)
    profile: np.ndarray      # int16 reference-model profile (0 for step-end)
    time: np.ndarray         # float32 step start t of the row's bucket
    idx: np.ndarray          # int32: arrival order (arrivals),
    #                          step index (step ends), 0 otherwise
    # Static per-VM arrays in dense (arrival, vm_id) order (N,):
    vm_ids: np.ndarray       # int64 original vm_id per dense index
    vm_pids: np.ndarray      # (N, M) int16 profile per fleet model
    #                          (column 0 = the reference-model profile)
    vm_heavy: np.ndarray     # (N,) bool — full-GPU request on every model
    vm_cpu: np.ndarray       # float32
    vm_ram: np.ndarray       # float32
    # MECC observation schedule over *included* arrivals (A,):
    arr_times: np.ndarray    # float32 observation time (bucket start)
    arr_pids: np.ndarray     # (A, M) int16 profile per fleet model
    # Step sampling times (S,):
    step_times: np.ndarray   # float64
    # Cluster shape:
    num_vms: int
    num_gpus: int
    num_hosts: int
    models: Tuple[DeviceModel, ...]  # fleet models; [0] is the reference
    gpu_model_id: np.ndarray  # (G,) int32 index into models
    gpu_host_id: np.ndarray  # (G,) int32
    cpu_cap: np.ndarray      # (H,) float32
    ram_cap: np.ndarray      # (H,) float32
    step_hours: float = 1.0
    # Padded metric-buffer rows (None = len(step_times), i.e. unpadded).
    hourly_slots: Optional[int] = None


def _arr_bucket(t: float, step: float) -> int:
    # Bucket in which the sequential engine offers an arrival:
    # smallest b with t < (b+1)*step - eps.
    return int(math.floor((t + _EPS) / step))


def _dep_bucket(t: float, step: float) -> int:
    # Bucket at whose start the sequential engine pops a departure:
    # smallest b with t <= (b+1)*step - eps.
    return int(math.ceil((t + _EPS) / step)) - 1


def step_grid(horizon: float, step_hours: float) -> np.ndarray:
    """Exactly the sequential engine's sampling loop (accumulated float64
    grid, inclusive of the first step at/after ``horizon``)."""
    times = []
    t = 0.0
    while t < horizon + _EPS:
        times.append(t)
        t += step_hours
    return np.asarray(times, np.float64)


def build_events_arrays(*, arrival: np.ndarray, duration: np.ndarray,
                        cpu: np.ndarray, ram: np.ndarray,
                        vm_ids: np.ndarray, pids: np.ndarray,
                        models: Tuple[DeviceModel, ...],
                        gpu_model_id: np.ndarray, gpu_host_id: np.ndarray,
                        cpu_cap: np.ndarray, ram_cap: np.ndarray,
                        step_hours: float = 1.0,
                        horizon: Optional[float] = None) -> EventTrace:
    """Vectorized trace lowering from plain arrays (no VM objects).

    This is the million-VM path: every per-VM quantity arrives as a numpy
    array and the event rows are built and sorted with numpy — identical
    ordering semantics to :func:`build_events` (which now delegates here).
    ``pids`` is (N, M): each VM's Eq. 27-30 profile per fleet model.

    Trace-construction RSS is kept O(packed trace): every temporary that
    used to default to int64 (bucket indices, dense VM indices, kind
    columns, profile columns) is carried at the narrowest provably-safe
    width — event counts and VM indices fit int32 up to 2^31 rows, kinds
    fit uint8, profiles int16 — and the sort tiebreak reuses the vm_ids
    column at int32 when the ids fit.  The two ``np.lexsort`` permutation
    outputs are numpy's intp and stay int64; everything else is packed.
    """
    arrival = np.asarray(arrival, np.float64).reshape(-1)
    duration = np.asarray(duration, np.float64).reshape(-1)
    n = arrival.shape[0]
    if n >= np.iinfo(np.int32).max:
        raise ValueError(f"trace has {n} VMs; int32 VM indices overflow")
    M = len(models)
    pids = (np.asarray(pids, np.int16).reshape(n, M) if n
            else np.zeros((0, M), np.int16))
    vm_ids = np.asarray(vm_ids, np.int64).reshape(-1)
    cpu = np.asarray(cpu, np.float32).reshape(-1)
    ram = np.asarray(ram, np.float32).reshape(-1)

    # Dense (arrival, vm_id) order — the engines' globalIndex order.
    order = np.lexsort((vm_ids, arrival))
    arrival, duration = arrival[order], duration[order]
    vm_ids, pids = vm_ids[order], pids[order]
    cpu, ram = cpu[order], ram[order]
    del order
    departure = arrival + duration

    # Heavy iff the request maps to the full-GPU profile on EVERY model
    # (vectorized pc.heavy_request).
    hp = np.array([m.heavy_profile for m in models], np.int16)
    heavy = (np.all((pids == hp[None, :]) & (hp[None, :] >= 0), axis=1)
             if n else np.zeros(0, bool))

    if horizon is None:
        horizon = (float(arrival.max()) if n else 0.0) + step_hours
    st64 = step_grid(horizon, step_hours)
    S = len(st64)

    # Bucket math — identical float64 expressions to the scalar helpers;
    # bucket ordinals are step counts, comfortably int32.
    ab = np.floor((arrival + _EPS) / step_hours).astype(np.int32)
    db = (np.ceil((departure + _EPS) / step_hours).astype(np.int32) - 1)
    # A same-bucket departure is heap-popped one bucket later (the heap
    # push happens after the bucket's departure phase).
    db = np.maximum(db, ab + 1)
    inc = ab < S            # past-horizon arrivals are never offered
    dep_inc = inc & (db < S)
    # inc has < 2^31 rows (checked above), so the running count fits
    # int32 — no O(N) int64 temporary.
    a_ord = np.cumsum(inc, dtype=np.int32) - 1

    dense = np.arange(n, dtype=np.int32)
    ref_p = pids[:, 0] if n else np.zeros(0, np.int16)
    # Sort tiebreak: vm_ids, at int32 when the id range allows it.
    tb = (vm_ids.astype(np.int32)
          if n == 0 or (vm_ids.min() >= np.iinfo(np.int32).min
                        and vm_ids.max() <= np.iinfo(np.int32).max)
          else vm_ids)

    def rows(sel, kind, t_actual, tiebreak, bucket, idx):
        return dict(bucket=bucket[sel],
                    kind=np.full(int(sel.sum()), kind, np.uint8),
                    t=t_actual[sel], tb=tiebreak[sel],
                    vm=dense[sel], p=ref_p[sel],
                    idx=idx[sel])

    arr = rows(inc, ARRIVAL, arrival, tb, ab, a_ord)
    dep = rows(dep_inc, DEPARTURE, departure, tb, db,
               np.zeros(n, np.int32))
    si = np.arange(S, dtype=np.int32)
    stp = dict(bucket=si, kind=np.full(S, STEP_END, np.uint8),
               t=np.full(S, np.inf), tb=np.zeros(S, tb.dtype),
               vm=np.zeros(S, np.int32), p=np.zeros(S, np.int16), idx=si)

    cat = {k: np.concatenate([arr[k], dep[k], stp[k]]) for k in arr}
    del arr, dep, stp
    perm = np.lexsort((cat["tb"], cat["t"], cat["kind"], cat["bucket"]))
    for k in cat:
        cat[k] = cat[k][perm]
    del perm

    return EventTrace(
        kind=cat["kind"],
        vm_index=cat["vm"],
        profile=cat["p"],
        time=st64[cat["bucket"]].astype(np.float32),
        idx=cat["idx"],
        vm_ids=vm_ids,
        vm_pids=pids,
        vm_heavy=heavy,
        vm_cpu=cpu,
        vm_ram=ram,
        arr_times=st64[ab[inc]].astype(np.float32),
        arr_pids=pids[inc],
        step_times=st64,
        num_vms=n,
        num_gpus=len(gpu_model_id), num_hosts=len(cpu_cap),
        models=tuple(models),
        gpu_model_id=np.asarray(gpu_model_id, np.int32),
        gpu_host_id=np.asarray(gpu_host_id, np.int32),
        cpu_cap=np.asarray(cpu_cap, np.float32),
        ram_cap=np.asarray(ram_cap, np.float32),
        step_hours=step_hours)


def build_events(vms: List[VM], cluster: Union[Cluster, int],
                 step_hours: float = 1.0,
                 horizon: Optional[float] = None) -> EventTrace:
    """Lower a VM list + cluster onto the scan's event stream.

    ``cluster`` may be a ``Cluster`` (host topology + CPU/RAM caps +
    fleet device models are honored) or a bare GPU count (one
    unconstrained A100-40GB host per GPU — the legacy GPU-only replay).
    ``horizon`` defaults to the sequential engine's (max arrival + step).

    Bucket times reuse the sequential engine's accumulated step grid but
    are carried as float32 in the scan; exact cross-engine decision
    parity for MECC expiry / consolidation-due checks therefore holds
    when step times are float32-representable (any integral
    ``step_hours``, e.g. the default 1 h grid — asserted by
    tests/test_equivalence.py)."""
    if isinstance(cluster, Cluster):
        num_gpus = cluster.num_gpus
        num_hosts = len(cluster.hosts)
        models = cluster.models
        gpu_model_id = cluster.gpu_model_id.astype(np.int32)
        gpu_host_id = cluster.gpu_host_id.astype(np.int32)
        cpu_cap = cluster.host_cpu_cap.copy()
        ram_cap = cluster.host_ram_cap.copy()

        def pids_of(vm: VM) -> np.ndarray:
            return cluster.vm_pids(vm)
    else:
        num_gpus = int(cluster)
        num_hosts = num_gpus
        models = (A100_40GB,)
        gpu_model_id = np.zeros(num_gpus, dtype=np.int32)
        gpu_host_id = np.arange(num_gpus, dtype=np.int32)
        cpu_cap = np.full(num_hosts, np.inf, dtype=np.float32)
        ram_cap = np.full(num_hosts, np.inf, dtype=np.float32)

        def pids_of(vm: VM) -> np.ndarray:
            return np.array([PROFILE_INDEX[vm.profile.name]], np.int32)

    M = len(models)
    all_pids = (np.stack([pids_of(v) for v in vms])
                if vms else np.zeros((0, M), np.int32)).astype(np.int32)
    return build_events_arrays(
        arrival=np.array([v.arrival for v in vms], np.float64),
        duration=np.array([v.duration for v in vms], np.float64),
        cpu=np.array([v.cpu for v in vms], np.float32),
        ram=np.array([v.ram for v in vms], np.float32),
        vm_ids=np.array([v.vm_id for v in vms], np.int64),
        pids=all_pids, models=tuple(models),
        gpu_model_id=gpu_model_id, gpu_host_id=gpu_host_id,
        cpu_cap=cpu_cap, ram_cap=ram_cap,
        step_hours=step_hours, horizon=horizon)


# ---------------------------------------------------------------------------
# Replay statics — the compile-cache key
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplayStatics:
    """Everything the scan body specializes on.  One jitted function per
    distinct value; XLA then caches one executable per (statics, bucket
    shape) — which is exactly the replay compile-cache key
    ``(bucket_shape, policy, cfg, model-set)``."""
    policy: int
    models: Tuple[DeviceModel, ...]
    defrag: bool = True
    consolidation_interval: Optional[float] = None
    defrag_trigger: str = "light"
    mecc_window: float = 24.0
    # "tables" = per-model mask-table gathers (jnp; the CPU path);
    # "pallas" / "pallas_interpret" = fused MCC/MECC scoring kernels.
    score_backend: str = "tables"
    # Sharded-fleet replay (repro.core.sharded): shard_map axis + count.
    axis_name: Optional[str] = None
    num_shards: int = 0
    # In-scan telemetry (repro.obs.inscan).  Off by default: the default
    # jaxpr — and thus the lint jaxpr-gate fingerprints — is unchanged.
    # On/off are distinct statics, so each keys its own compiled replay.
    telemetry: bool = False


def replay_statics(events: EventTrace, policy: int, *,
                   defrag: bool = True,
                   consolidation_interval: Optional[float] = None,
                   defrag_trigger: str = "light",
                   mecc_window: float = 24.0,
                   score_backend: str = "auto",
                   axis_name: Optional[str] = None,
                   num_shards: int = 0,
                   telemetry: bool = False) -> ReplayStatics:
    """Resolve user cfg (including ``score_backend="auto"``) against the
    trace's shapes/fleet into a hashable :class:`ReplayStatics`."""
    from ..kernels.policy_score import LANES, kernel_fits
    G = len(events.gpu_model_id)
    kernel_ok = (policy in (MCC, MECC) and len(events.models) == 1
                 and kernel_fits(G))
    if score_backend == "auto":
        # The fused kernels run compiled only on a TPU; elsewhere the jnp
        # table gathers are the engine's path.
        score_backend = ("pallas" if kernel_ok and not num_shards
                         and jax.default_backend() == "tpu" else "tables")
    if score_backend != "tables":
        if not kernel_ok:
            raise ValueError(
                f"score_backend={score_backend!r} needs a single-model "
                f"fleet, policy MCC/MECC and num_gpus a multiple of "
                f"{LANES} that the kernels can tile (got policy={policy}, "
                f"M={len(events.models)}, G={G}); bucket the trace "
                "(repro.core.bucketing.pad_events)")
        if num_shards:
            raise ValueError("Pallas scoring is not supported on the "
                             "sharded path; use score_backend='tables'")
    return ReplayStatics(
        policy=policy, models=tuple(events.models), defrag=defrag,
        consolidation_interval=consolidation_interval,
        defrag_trigger=defrag_trigger, mecc_window=mecc_window,
        score_backend=score_backend, axis_name=axis_name,
        num_shards=num_shards, telemetry=telemetry)


def _gpu_full(events: EventTrace) -> np.ndarray:
    """Per-GPU all-free mask; 0 on padded GPUs, so padding is both
    never-feasible (no free blocks) and never-active (free == full)."""
    full = np.array([m.full_mask for m in events.models], np.int32)
    out = full[events.gpu_model_id]
    out[events.num_gpus:] = 0
    return out


def trace_arrays(events: EventTrace) -> Dict[str, np.ndarray]:
    """The scan's traced-argument pytree (host numpy; callers move it to
    device).  Everything shape-padded lives here; two traces in the same
    bucket produce identical shapes/dtypes and share one executable.

    The event stream and per-VM/arrival tables keep the packed dtypes
    (uint8 kinds, int16 profiles) on device — ``_scan_fn`` widens each
    gathered scalar to int32 inside the scan step, so device bytes track
    the packed layout while decision arithmetic stays int32/float32."""
    M = len(events.models)
    n_vm_rows = len(events.vm_pids)
    return dict(
        kind=np.clip(events.kind, 0, 3).astype(np.uint8),
        vm_index=events.vm_index.astype(np.int32),
        profile=events.profile.astype(np.int16),
        time=events.time.astype(np.float32),
        idx=events.idx.astype(np.int32),
        vm_pids=(events.vm_pids.astype(np.int16) if n_vm_rows
                 else np.zeros((1, M), np.int16)),
        vm_heavy=(events.vm_heavy.astype(bool) if n_vm_rows
                  else np.zeros(1, bool)),
        # Per-VM (cpu, ram) rows, so host feasibility is one gather + one
        # fused compare.
        vm_res=(np.stack([events.vm_cpu, events.vm_ram],
                         axis=1).astype(np.float32) if n_vm_rows
                else np.zeros((1, 2), np.float32)),
        gpu_mid=events.gpu_model_id.astype(np.int32),
        gpu_host=events.gpu_host_id.astype(np.int32),
        gpu_full=_gpu_full(events),
        cpu_cap=events.cpu_cap.astype(np.float32),
        ram_cap=events.ram_cap.astype(np.float32),
        arr_times=(events.arr_times.astype(np.float32)
                   if len(events.arr_times)
                   else np.full(1, np.inf, np.float32)),
        arr_pids=(events.arr_pids.astype(np.int16)
                  if len(events.arr_times) else np.zeros((1, M), np.int16)),
        # Logical fleet size: basket capacities are counted against the
        # real fleet, not the padded one.
        n_gpus=np.asarray(events.num_gpus, np.int32),
    )


def init_state(events: EventTrace, st: ReplayStatics) -> Dict[str, jax.Array]:
    """Fresh initial scan state.  Built per call and *donated* to the
    compiled replay, so XLA aliases these buffers through the scan.

    Donation invariant: after a replay returns, the state0 passed to it
    must be treated as consumed — never read it again; build a new one
    per call (this function is cheap: a handful of zero-fills)."""
    T = pc.tables_for(jnp, st.models)
    N = max(len(events.vm_pids), 1)
    G = len(events.gpu_model_id)
    H = len(events.cpu_cap)
    S = events.hourly_slots or len(events.step_times)
    NP, M = T.num_profiles, T.num_models

    # Telemetry never widens or adds a buffer the inner lax.scan carries
    # through the event switch — such a buffer costs pass-through copies
    # in every branch, per event (see repro.obs.inscan).  vmrow gains a
    # reason-code column (-1 = arrival not yet processed) written by the
    # same row scatter the arrival branch always does; the per-step
    # snapshots leave the scan as ys and are folded into the
    # ``tele_steps``/``tele_masks`` accumulators, which only ever cross
    # the *outer* jit (or chunk-step) boundary.
    vm0 = [-1, 0, 0, -1] if st.telemetry else [-1, 0, 0]
    state0 = dict(
        free=jnp.asarray(_gpu_full(events), jnp.int32),
        # Per-VM row: [gpu, start, accepted] (+ telemetry reason code).
        vmrow=jnp.tile(jnp.asarray(vm0, jnp.int32), (N, 1)),
        # Per-reference-profile row: [accepted, total].
        counts=jnp.zeros((NP, 2), jnp.int32),
        # Per-host row: [cpu_used, ram_used].
        host_used=jnp.zeros((H, 2), jnp.float32),
        # Per-step row: [accepted_cum, total_cum, pms, gpus].
        hourly=jnp.zeros((S, 4), jnp.int32),
    )
    if st.telemetry:
        state0["tele_steps"] = jnp.zeros(
            (S, obs_inscan.NUM_STEP_COLS), jnp.int32)
        state0["tele_masks"] = jnp.zeros((S, G), obs_inscan.MASK_DTYPE)
    need_defrag = st.policy == GRMU and st.defrag
    need_consolidation = (st.policy == GRMU
                          and st.consolidation_interval is not None)
    if st.policy == GRMU:
        ar = np.arange(G)
        basket = np.where(ar == 0, pc.HEAVY_BASKET,
                          np.where(ar == 1, pc.LIGHT_BASKET,
                                   pc.POOL)).astype(np.int32)
        basket[events.num_gpus:] = PAD_BASKET
        state0["basket"] = jnp.asarray(basket)
        state0["intra"] = jnp.asarray(0, jnp.int32)
        state0["inter"] = jnp.asarray(0, jnp.int32)
    if need_defrag:
        state0["rej"] = jnp.asarray(False)
    if need_consolidation:
        state0["vm_count"] = jnp.zeros((G,), jnp.int32)
        state0["last_cons"] = jnp.asarray(0.0, jnp.float32)
    if st.policy == MECC:
        state0["mecc_counts"] = jnp.zeros((M, NP), jnp.int32)
        state0["mecc_ptr"] = jnp.asarray(0, jnp.int32)
    return state0


# ---------------------------------------------------------------------------
# The scan
# ---------------------------------------------------------------------------

def _kernel_pick(st: ReplayStatics, free, prof0, host_ok, mecc_w):
    """MCC/MECC pick via the fused Pallas scoring kernels (single-model
    fleets).  The kernel returns -1 on infeasible masks, so feasibility
    and scoring collapse into one fused pass; the winner's assign tables
    are then gathered for that one GPU only."""
    from ..kernels.policy_score import (LANES, engine_ecc_scores,
                                       engine_mcc_scores)
    model = st.models[0]
    # Interpret mode only when asked for: an explicit "pallas" off a TPU
    # fails to lower rather than silently interpreting.
    interpret = st.score_backend == "pallas_interpret"
    if st.policy == MCC:
        cc = engine_mcc_scores(free, prof0, model=model,
                               interpret=interpret)
        scores = jnp.where(host_ok, cc, -1)
    else:  # MECC — integer windowed counts as f32 weights (exact < 2^24)
        w = mecc_w[0].astype(jnp.float32)
        row = jnp.zeros((1, LANES), jnp.float32).at[0, :w.shape[0]].set(w)
        ecc = engine_ecc_scores(free, prof0, row, model=model,
                                interpret=interpret)
        scores = jnp.where(host_ok, ecc, jnp.float32(-1))
    return jnp.where(jnp.any(scores >= 0), jnp.argmax(scores), -1)


# Keys of the E-sized event-stream arrays inside the trace pytree — the
# only arrays ``repro.core.streaming`` slices into chunks; everything
# else ("rest") stays resident across chunks.
EVENT_KEYS = ("kind", "vm_index", "profile", "time", "idx")


def _scan_body(st: ReplayStatics, state0: Dict[str, jax.Array],
               tr: Dict[str, jax.Array], heavy_capacity
               ) -> Dict[str, jax.Array]:
    """Scan the event stream in ``tr`` through the replay step and return
    the **final carry** (the whole cluster state).  With telemetry
    statics, returns ``(final carry, stacked per-event telemetry ys)``
    instead — ``state0`` must then *not* contain the ``tele_steps`` /
    ``tele_masks`` accumulators (callers pop them and fold the ys into
    them post-scan).

    This is the chunk-streaming unit: because the carry is the complete
    state and the step function never looks at an event's position, a
    scan over ``tr`` equals any composition of scans over consecutive
    slices of ``tr`` — chunk boundaries are decision-neutral by
    construction (asserted in tests/test_streaming.py).

    Shapes come from the arguments; ``st`` carries every static.  jit
    once per ``st`` — XLA's cache then keys executables on the bucket
    (or chunk) shapes, and ``state0`` may be donated.  Packed trace
    dtypes (uint8 kinds, int16 profiles) are widened to int32 per
    gathered scalar here, so decision arithmetic is identical to the
    unpacked layout."""
    T = pc.tables_for(jnp, st.models)
    G = tr["gpu_mid"].shape[0]
    N = state0["vmrow"].shape[0]
    M = T.num_models
    NP = T.num_profiles
    MAXB = T.max_blocks
    H = state0["host_used"].shape[0]
    A = tr["arr_times"].shape[0]
    need_defrag = st.policy == GRMU and st.defrag
    need_consolidation = (st.policy == GRMU
                          and st.consolidation_interval is not None)
    sharded = None
    if st.num_shards:
        from . import sharded as sharded  # lazy: avoids an import cycle

    ev = dict(kind=tr["kind"], vm_index=tr["vm_index"],
              profile=tr["profile"], time=tr["time"], idx=tr["idx"])
    _vmpids, _vmheavy, _vmres = tr["vm_pids"], tr["vm_heavy"], tr["vm_res"]
    _ghost, _gmid, _gfull = tr["gpu_host"], tr["gpu_mid"], tr["gpu_full"]
    _cap_g = jnp.stack([tr["cpu_cap"][_ghost], tr["ram_cap"][_ghost]],
                       axis=1)
    _ccap, _rcap = tr["cpu_cap"], tr["ram_cap"]
    _atimes, _apids = tr["arr_times"], tr["arr_pids"]
    _marange = jnp.arange(M)
    _garange = jnp.arange(G)

    heavy_cap = jnp.asarray(heavy_capacity, jnp.int32)
    light_cap = tr["n_gpus"].astype(jnp.int32) - heavy_cap

    # -- arrival ---------------------------------------------------------
    def arrival(state, e):
        p, vi = e["profile"], e["vm_index"]
        pids = _vmpids[vi].astype(jnp.int32)            # (M,)
        mecc_w = None
        if st.policy == MECC:
            # on_arrival_observed: count the arrival (once per fleet
            # model), then expire history older than (now - window)
            # with a two-pointer over the static observation schedule.
            counts = state["mecc_counts"].at[_marange, pids].add(1)
            cutoff = e["time"] - jnp.float32(st.mecc_window)

            def cond(c):
                ptr, _ = c
                return (ptr < A) & (_atimes[jnp.minimum(ptr, A - 1)]
                                    < cutoff)

            def body(c):
                ptr, cnt = c
                obs = _apids[ptr].astype(jnp.int32)
                return ptr + 1, cnt.at[_marange, obs].add(-1)

            ptr, counts = jax.lax.while_loop(
                cond, body, (state["mecc_ptr"], counts))
            state = dict(state, mecc_counts=counts, mecc_ptr=ptr)
            mecc_w = pc.mecc_weights(jnp, counts)

        need = _vmres[vi]                               # (2,) cpu, ram
        host_ok = jnp.all(state["host_used"][_ghost] + need <= _cap_g,
                          axis=1)
        # Telemetry reads decision-time state: the free masks before any
        # placement and the GRMU flags before any basket growth.
        tele_free = state["free"] if st.telemetry else None
        tele_grew = tele_quota = None
        if st.policy == GRMU:
            heavy = _vmheavy[vi]
            if st.num_shards:
                pick, grew, grow_idx = sharded.grmu_select_sharded(
                    T, _gmid, state["free"], pids, heavy, host_ok,
                    state["basket"], heavy_cap, light_cap,
                    st.axis_name, st.num_shards)
            else:
                pick, grew, grow_idx = pc.grmu_select(
                    jnp, T, _gmid, state["free"], pids, heavy, host_ok,
                    state["basket"], heavy_cap, light_cap)
            want = jnp.where(heavy, pc.HEAVY_BASKET, pc.LIGHT_BASKET)
            if st.telemetry:
                tele_grew = grew
                tele_quota = ((state["basket"] == want).sum()
                              >= jnp.where(heavy, heavy_cap, light_cap))
            basket = jnp.where(
                grew, state["basket"].at[grow_idx].set(want),
                state["basket"])
            state = dict(state, basket=basket)
        elif st.num_shards:
            pick = sharded.select_gpu_sharded(
                st.policy, T, _gmid, state["free"], pids, host_ok,
                mecc_w, st.axis_name, st.num_shards)
        elif st.score_backend != "tables":
            pick = _kernel_pick(st, state["free"], pids[0], host_ok,
                                mecc_w)
        else:
            pick = pc.select_gpu(st.policy, jnp, T, _gmid, state["free"],
                                 pids, host_ok, mecc_w)
        ok = pick >= 0
        okc = ok.astype(jnp.int32)
        g = jnp.maximum(pick, 0)
        mask = state["free"][g]
        p_g = pids[_gmid[g]]      # profile under the chosen GPU's model
        row = [jnp.where(ok, pick, -1),
               jnp.where(ok, T.assign_start[_gmid[g], mask, p_g], 0),
               okc]
        if st.telemetry:
            # Telemetry column of the SAME vmrow write — never a
            # separate buffer (see repro.obs.inscan on why).
            false = jnp.asarray(False)
            row.append(obs_inscan.arrival_reason_code(
                T, _gmid, tele_free, pids, host_ok, ok,
                false if tele_grew is None else tele_grew,
                false if tele_quota is None else tele_quota))
        row = jnp.stack(row)
        state = dict(
            state,
            free=state["free"].at[g].set(
                jnp.where(ok, T.assign_mask[_gmid[g], mask, p_g],
                          mask)),
            vmrow=state["vmrow"].at[vi].set(row),
            counts=state["counts"].at[p].add(jnp.stack([okc, 1])),
            host_used=state["host_used"].at[_ghost[g]].add(
                jnp.where(ok, need, jnp.float32(0.0))),
        )
        if need_consolidation:
            state = dict(state,
                         vm_count=state["vm_count"].at[g].add(okc))
        if need_defrag:
            rej = (~ok & ~_vmheavy[vi]
                   if st.defrag_trigger == "light" else ~ok)
            state = dict(state, rej=state["rej"] | rej)
        return state

    # -- departure --------------------------------------------------------
    def departure(state, e):
        vi = e["vm_index"]
        r = state["vmrow"][vi]
        gpu, start = r[0], r[1]
        ok = gpu >= 0
        okc = ok.astype(jnp.int32)
        g = jnp.maximum(gpu, 0)
        p_g = _vmpids[vi, _gmid[g]].astype(jnp.int32)
        blocks = ((jnp.int32(1) << T.sizes[_gmid[g], p_g]) - 1) << start
        state = dict(
            state,
            free=state["free"].at[g].set(
                jnp.where(ok, state["free"][g] | blocks,
                          state["free"][g])),
            vmrow=state["vmrow"].at[vi, 0].set(-1),
            host_used=state["host_used"].at[_ghost[g]].add(
                jnp.where(ok, -_vmres[vi], jnp.float32(0.0))),
        )
        if need_consolidation:
            state = dict(state,
                         vm_count=state["vm_count"].at[g].add(-okc))
        return state

    # -- GRMU step-end operations ----------------------------------------
    def do_defrag(state):
        light = state["basket"] == pc.LIGHT_BASKET
        tgt = pc.defrag_target(jnp, T, _gmid, state["free"], light)
        do = tgt >= 0
        g = jnp.maximum(tgt, 0)
        mid_g = _gmid[g]
        on_g = state["vmrow"][:, 0] == g
        vm_start = state["vmrow"][:, 1]
        prof_blk, vi_blk = [], []
        for b in range(MAXB):
            sel = on_g & (vm_start == b)
            has = sel.any()
            vi = jnp.argmax(sel)
            prof_blk.append(jnp.where(
                has, _vmpids[vi, mid_g].astype(jnp.int32), -1))
            vi_blk.append(jnp.where(has, vi, N))
        prof_blk = jnp.stack(prof_blk)
        vi_blk = jnp.stack(vi_blk)
        starts, ok, final_mask, moved = pc.repack_gpu(jnp, T, mid_g,
                                                      prof_blk)
        apply = do & ok & (moved > 0)
        cur = vm_start[jnp.clip(vi_blk, 0, N - 1)]
        vals = jnp.where(apply & (starts >= 0), starts, cur)
        return dict(
            state,
            free=state["free"].at[g].set(
                jnp.where(apply, final_mask, state["free"][g])),
            vmrow=state["vmrow"].at[vi_blk, 1].set(vals, mode="drop"),
            intra=state["intra"] + jnp.where(apply, moved, 0),
        )

    def do_consolidate(state):
        free, basket = state["free"], state["basket"]
        vm_gpu = state["vmrow"][:, 0]
        # Sole resident per GPU (valid only where vm_count == 1).
        owner = jnp.full(G + 1, -1, jnp.int32).at[
            jnp.where(vm_gpu >= 0, vm_gpu, G)
        ].set(jnp.arange(N, dtype=jnp.int32))[:G]
        owner_c = jnp.clip(owner, 0, N - 1)
        # The sole VM mapped onto every fleet model, (G, M); and onto
        # its own GPU's model, (G,).
        sole_pids = jnp.where((owner >= 0)[:, None],
                              _vmpids[owner_c].astype(jnp.int32), -1)
        sole_own = sole_pids[_garange, _gmid]
        sole_res = jnp.where((owner >= 0)[:, None], _vmres[owner_c],
                             jnp.float32(0.0))
        cand = pc.consolidation_candidates(
            jnp, T, _gmid, free, basket == pc.LIGHT_BASKET,
            state["vm_count"], sole_own)
        tgt_of, cpu_used, ram_used = pc.consolidation_plan(
            jnp, T, _gmid, free, cand, sole_pids, sole_res[:, 0],
            sole_res[:, 1], _ghost, state["host_used"][:, 0],
            state["host_used"][:, 1], _ccap, _rcap)
        valid = tgt_of >= 0
        tgt_c = jnp.clip(tgt_of, 0, G - 1)
        # Each source's profile under its *target's* model.
        p_tgt = jnp.clip(sole_pids[_garange, _gmid[tgt_c]], 0, NP - 1)
        starts = T.assign_start[_gmid[tgt_c], free[tgt_c], p_tgt]
        # Scatter receive side: each target gets exactly one source
        # (profile already expressed in the target's own model).
        recv_idx = jnp.where(valid, tgt_of, G)
        recv_p = jnp.full(G + 1, -1, jnp.int32).at[recv_idx].set(
            jnp.where(valid, p_tgt, -1))[:G]
        recv_pc = jnp.clip(recv_p, 0, NP - 1)
        new_free = jnp.where(valid, _gfull, free)
        new_free = jnp.where(recv_p >= 0,
                             T.assign_mask[_gmid, free, recv_pc],
                             new_free)
        vi = jnp.where(valid, owner, N)
        vmrow = state["vmrow"].at[vi, 0].set(tgt_of, mode="drop")
        vmrow = vmrow.at[vi, 1].set(starts, mode="drop")
        return dict(
            state,
            free=new_free,
            basket=jnp.where(valid, pc.POOL, basket),
            vmrow=vmrow,
            vm_count=jnp.where(valid, 0, state["vm_count"])
            + (recv_p >= 0).astype(jnp.int32),
            host_used=jnp.stack([cpu_used, ram_used], axis=1),
            inter=state["inter"] + valid.sum().astype(jnp.int32),
        )

    # -- step end ----------------------------------------------------------
    def step_end(state, e):
        if need_defrag:
            state = jax.lax.cond(state["rej"], do_defrag, lambda s: s,
                                 state)
            state = dict(state, rej=jnp.asarray(False))
        if need_consolidation:
            due = (e["time"] - state["last_cons"]
                   >= jnp.float32(st.consolidation_interval))
            state = jax.lax.cond(due, do_consolidate, lambda s: s,
                                 state)
            state = dict(state, last_cons=jnp.where(
                due, e["time"], state["last_cons"]))
        gpu_active = (state["free"] != _gfull).astype(jnp.int32)
        pms = (jax.ops.segment_sum(gpu_active, _ghost,
                                   num_segments=H) > 0)
        sample = jnp.stack([state["counts"][:, 0].sum(),
                            state["counts"][:, 1].sum(),
                            pms.sum().astype(jnp.int32),
                            gpu_active.sum()])
        state = dict(state,
                     hourly=state["hourly"].at[e["idx"]].set(sample))
        if st.telemetry:
            # The telemetry sample leaves as this step's scan output —
            # never through the carry (see repro.obs.inscan on why).
            return state, obs_inscan.step_row(state)
        return state

    # -- padding -----------------------------------------------------------
    def pad_noop(state, e):
        return state

    def step(state, e):
        # Widen the packed per-event scalars once; every branch then
        # computes in int32 exactly as the legacy layout did.
        e = dict(e, kind=e["kind"].astype(jnp.int32),
                 profile=e["profile"].astype(jnp.int32))
        if st.telemetry:
            # Every branch emits a telemetry row (zeros outside
            # step-end) as the scan's per-event output; scan machinery
            # writes it once into the stacked ys — no branch ever
            # copies it through a carry.
            zrow = (jnp.zeros((obs_inscan.NUM_STEP_COLS,), jnp.int32),
                    jnp.zeros((G,), obs_inscan.MASK_DTYPE))
            return jax.lax.switch(
                e["kind"],
                [lambda s, ee: (departure(s, ee), zrow),
                 lambda s, ee: (arrival(s, ee), zrow),
                 step_end,
                 lambda s, ee: (pad_noop(s, ee), zrow)],
                state, e)
        state = jax.lax.switch(
            e["kind"],
            [departure, arrival, step_end, pad_noop],
            state, e)
        return state, None

    # Telemetry scans unroll the loop body: the per-iteration cost of
    # emitting the ys row (output-buffer bookkeeping per event) is
    # fixed-size, so amortizing it over 8 events cuts most of the
    # telemetry overhead.  The default path keeps unroll=1 — its jaxpr
    # (and the lint fingerprint gate over it) is byte-identical.
    final, ys = jax.lax.scan(step, state0, ev,
                             unroll=8 if st.telemetry else 1)
    return (final, ys) if st.telemetry else final


def _finalize(st: ReplayStatics, final: Dict[str, jax.Array]
              ) -> Dict[str, jax.Array]:
    """Reduce a final scan carry to the replay's small output arrays.
    When the statics enabled telemetry, ``final`` also holds the folded
    ``tele_steps``/``tele_masks`` series and vmrow's code column; all
    are split into the ``tele_*`` output series."""
    zero = jnp.asarray(0, jnp.int32)
    out = dict(
        accepted=final["counts"][:, 0], total=final["counts"][:, 1],
        vm_accepted=final["vmrow"][:, 2] > 0,
        h_acc=final["hourly"][:, 0], h_tot=final["hourly"][:, 1],
        h_pms=final["hourly"][:, 2], h_gpus=final["hourly"][:, 3],
        intra=final.get("intra", zero), inter=final.get("inter", zero),
    )
    if st.telemetry:
        out.update(obs_inscan.unpack_finalize(final))
    return out


def _scan_fn(st: ReplayStatics, state0: Dict[str, jax.Array],
             tr: Dict[str, jax.Array], heavy_capacity
             ) -> Dict[str, jax.Array]:
    """The whole replay as a pure function of (state0, trace, cap) —
    :func:`_scan_body` followed by the output reductions.  With
    telemetry statics the per-event ys are folded into the
    ``tele_steps``/``tele_masks`` accumulators (one scatter per replay)
    before finalize."""
    if st.telemetry:
        state0 = dict(state0)
        steps0 = state0.pop("tele_steps")
        masks0 = state0.pop("tele_masks")
        final, ys = _scan_body(st, state0, tr, heavy_capacity)
        is_step = tr["kind"].astype(jnp.int32) == STEP_END
        steps, masks = obs_inscan.fold_step_rows(
            (steps0, masks0), is_step, tr["idx"], ys)
        final = dict(final, tele_steps=steps, tele_masks=masks)
        return _finalize(st, final)
    return _finalize(st, _scan_body(st, state0, tr, heavy_capacity))


def _jitted_run(st: ReplayStatics) -> Callable:
    """One donating jitted scan per statics value (process-level cache);
    XLA's jit cache then holds one executable per bucket shape."""
    def build():
        return jax.jit(functools.partial(_scan_fn, st),
                       donate_argnums=(0,))
    return compile_cache.cached_replay_fn(st, build)


def make_decision_step(st: ReplayStatics) -> Callable:
    """The online placement service's micro-batch decision kernel: one
    donating jitted pass of :func:`_scan_body` over a fixed-size slice of
    event rows, returning ``(final carry, vmrow rows gathered at
    batch_vi)`` so the service can read each arrival's (gpu, start,
    accepted) decision without pulling the whole carry off device.

    Compile-once / serve-many: the function is cached per statics value
    (``(st, "serve-step")`` in the replay compile cache) and XLA's jit
    cache then keys one executable per (batch, state-bucket) shape — a
    service processes millions of requests through a single compile.
    Because ``_scan_body`` is position-independent, a stream of
    micro-batches computes exactly the single-scan fixpoint: decisions
    are bit-identical to an offline replay of the same event order for
    any batch size (tests/test_serve.py).

    ``batch_vi`` carries the dense VM index per batch row (the padded-VM
    count as a sentinel for non-arrival rows — the gather clamps, and the
    service ignores those rows).  The carry is donated: callers must
    treat the passed state as consumed, exactly like ``init_state``'s
    donation invariant."""
    if st.telemetry:
        raise ValueError("the serving decision step does not support "
                         "in-scan telemetry statics")
    compile_cache.ensure_persistent_cache()
    # Materialize the fleet's jnp tables eagerly: constructing them for
    # the first time *inside* the jit trace would cache tracers
    # (offline replay warms this via init_state; the service must too).
    pc.tables_for(jnp, st.models)

    def build():
        def step(state, ev, rest, heavy_capacity, batch_vi):
            final = _scan_body(st, state, dict(rest, **ev),
                               heavy_capacity)
            return final, final["vmrow"][batch_vi]
        return jax.jit(step, donate_argnums=(0,))

    return compile_cache.cached_replay_fn((st, "serve-step"), build)


def default_heavy_capacity(events: EventTrace,
                           frac: float = 0.30) -> int:
    # Same rounding as the sequential GRMU constructor (no floor), so a
    # replay and a GRMU(cluster, frac) run the identical cap.
    return int(round(frac * events.num_gpus))


def make_replay(events: EventTrace, policy: int, **cfg) -> Callable:
    """Jit-compiled ``run(heavy_capacity) -> dict of output arrays``.

    The compiled executable is shared across traces with the same bucket
    shapes and (policy, cfg, model-set) — replaying a new trace from an
    already-seen bucket skips XLA entirely.

    With a recorder installed (``repro.obs.recorder``), the statics (the
    fleet's models among them), the compiled function's lookup and the
    trace's upload run inside a ``replay.prepare`` span whose counters
    size the fleet: ``models``, padded ``gpus``, ``pid_cols`` (profile
    columns per VM) and ``slot_tests``, the (GPU, slot) tests that one
    arrival's :func:`policy_core.fit_mask` makes (every GPU against each
    model's slots)."""
    compile_cache.ensure_persistent_cache()
    rec = obs_recorder.active()
    with (contextlib.nullcontext() if rec is None
          else rec.span("replay.prepare", **_fleet_counters(events))):
        st = replay_statics(events, policy, **cfg)
        jfn = _jitted_run(st)
        tr = {k: jnp.asarray(v) for k, v in trace_arrays(events).items()}

    def run(heavy_capacity):
        return jfn(init_state(events, st), tr,
                   jnp.asarray(heavy_capacity, jnp.int32))

    return run


def _fleet_counters(events: EventTrace) -> Dict[str, int]:
    G = len(events.gpu_model_id)
    return dict(models=len(events.models), gpus=G,
                slot_tests=G * sum(m.num_slots for m in events.models),
                pid_cols=int(events.vm_pids.shape[1]))


def replay(events: EventTrace, policy: int,
           heavy_capacity=None, **cfg) -> SimResult:
    """Replay the trace under ``policy`` and return a full ``SimResult``
    (same fields the sequential engine fills).  ``heavy_capacity`` is only
    used by GRMU; GRMU knobs (``defrag``, ``consolidation_interval``,
    ``defrag_trigger``), MECC's ``mecc_window`` and the scoring backend
    (``score_backend``: auto|tables|pallas|pallas_interpret) pass through
    ``cfg``."""
    if heavy_capacity is None:
        heavy_capacity = default_heavy_capacity(events)
    out = jax.device_get(make_replay(events, policy, **cfg)(heavy_capacity))
    return result_from_arrays(events, policy, out)


def result_from_arrays(events: EventTrace, policy: int, out: dict
                       ) -> SimResult:
    """Assemble a SimResult from ``run``'s output arrays (host side, in
    float64, exactly how the sequential engine derives its series).
    Slices every padded buffer back to the trace's logical sizes."""
    ref_profiles = events.models[0].profiles
    # Device outputs are int32; per-profile tallies convert through
    # Python ints below, so no widening cast is needed here.
    accepted = np.asarray(out["accepted"])
    total = np.asarray(out["total"])
    res = SimResult.for_model(
        pc.POLICY_NAMES.get(policy, str(policy)), events.models[0])
    res.total_requests = int(total.sum())
    res.accepted = int(accepted.sum())
    res.rejected = res.total_requests - res.accepted
    for i, p in enumerate(ref_profiles):
        res.per_profile_total[p.name] = int(total[i])
        res.per_profile_accepted[p.name] = int(accepted[i])
    S = len(events.step_times)
    res.hourly_times = [float(t) for t in events.step_times]
    h_acc = np.asarray(out["h_acc"])[:S]
    h_tot = np.asarray(out["h_tot"])[:S]
    res.hourly_acceptance = [int(a) / max(1, int(t))
                             for a, t in zip(h_acc, h_tot)]
    denom = events.num_hosts + events.num_gpus
    res.hourly_active_hw = [(int(p) + int(g)) / denom
                            for p, g in zip(out["h_pms"][:S],
                                            out["h_gpus"][:S])]
    res.intra_migrations = int(out["intra"])
    res.inter_migrations = int(out["inter"])
    res.migrations = res.intra_migrations + res.inter_migrations
    acc_mask = np.asarray(out["vm_accepted"], bool)[:len(events.vm_ids)]
    res.accepted_ids = [int(v) for v in events.vm_ids[acc_mask]]
    if "tele_rej" in out:       # telemetry-enabled replay: reason tally
        rej = np.asarray(out["tele_rej"])
        res.rejection_reasons = {
            obs_reasons.REASON_NAMES[c]: int(rej[c])
            for c in range(1, obs_reasons.NUM_CODES)}
    return res


def sweep_heavy_capacity(events: EventTrace, fracs: np.ndarray,
                         **cfg) -> np.ndarray:
    """Fig. 6 on-device: vmap the GRMU replay over basket capacities.
    Defaults to the 'DB' configuration (defrag & consolidation off — the
    point whose acceptance the paper's sweep explores); pass
    ``defrag=True`` / ``consolidation_interval=...`` for full GRMU.
    Returns (len(fracs), num_profiles) accepted-per-reference-profile."""
    cfg.setdefault("defrag", False)
    cfg.setdefault("consolidation_interval", None)
    st = replay_statics(events, GRMU, **cfg)
    caps = jnp.asarray(np.round(
        np.asarray(fracs) * events.num_gpus).astype(np.int32))
    tr = {k: jnp.asarray(v) for k, v in trace_arrays(events).items()}
    s0 = init_state(events, st)

    # The state and trace are jit *arguments* (not closed-over
    # constants), and the vmapped sweep is cached per statics like every
    # other replay entry point — two sweeps over traces from the same
    # shape bucket share one executable (repro-lint: recompile-hazard).
    def build():
        def sweep(s0, tr, caps):
            return jax.vmap(
                lambda c: _scan_fn(st, s0, tr, c)["accepted"])(caps)
        return jax.jit(sweep)

    fn = compile_cache.cached_replay_fn((st, "sweep"), build)
    return np.asarray(fn(s0, tr, caps))


__all__ = ["EventTrace", "build_events", "build_events_arrays",
           "make_replay", "make_decision_step", "replay",
           "result_from_arrays",
           "sweep_heavy_capacity", "default_heavy_capacity",
           "trace_arrays", "init_state", "replay_statics",
           "ReplayStatics", "step_grid", "EVENT_KEYS",
           "FF", "BF", "MCC", "MECC", "GRMU",
           "DEPARTURE", "ARRIVAL", "STEP_END", "PAD", "PAD_BASKET"]
