"""Online placement service: a latency-bounded control plane over the
batched replay engine.

``PlacementService`` turns the offline trace replayer into the paper's
*online* GRMU framework: streaming VM arrivals/departures enter a bounded
request queue (``repro.serve.queue``) and are drained in **micro-batches**
through the batched engine's compile-cached table-driven step
(``repro.core.batched.make_decision_step``) against live cluster state —
the same donated carry the offline scan threads, held resident on device
between batches.

Compile-once / serve-many: the service pads a zero-event skeleton trace
of its fleet to fixed capacity buckets (``pad_events(min_shape=...)``),
so every micro-batch has one shape signature and the whole serving life
of a tier runs on a single compiled executable.  Because the scan body is
position-independent, the stream of micro-batches computes exactly the
single-scan fixpoint: **decisions are bit-identical to an offline replay
of the same arrival order**, for every registry policy and any batch
size (pinned by tests/test_serve.py).

Event semantics mirror the offline lowering exactly: the service tracks
the current step bucket, auto-inserts STEP_END rows when a request's
bucket advances past it (defrag / consolidation / hourly sampling run in
scan, exactly where the offline stream places them), stamps arrivals
with the bucket's accumulated float64 grid time, and applies the offline
same-bucket departure rule.  New arrivals' per-VM rows and MECC
observation-schedule rows are scattered into the resident trace tables
by a small donating ingest jit before the decision kernel runs.

Graceful degradation: an admission :class:`Governor` walks a tier ladder
(e.g. ``("ILP", "GRMU", "FF")``) — degrading when queue depth or the
rolling p99 decision latency breaches the SLO, recovering after a run of
healthy batches.  Registry-policy tiers run on the array backend (one
cached decision step per tier's ``ReplayStatics``); the ``"ILP"`` tier
runs the rolling-horizon :class:`~repro.core.policies.ILPPolicy` against
an object-level ``Cluster`` rebuilt from the same canonical state
snapshot that moves between tiers.  Switches are recorded through the
flight recorder (``service`` JSONL records beside the ``serve.*`` span
tree of every micro-batch; see :meth:`PlacementService._drain_batch_array`).

Checkpoint/restore rides ``repro.launch.checkpoint``: the canonical
snapshot (carry + host-side VM/arrival tables + stream counters) is an
atomic numpy-pytree checkpoint, and a freshly constructed service with
the same config restores mid-stream and continues bit-identically.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import batched as B
from ..core import compile_cache
from ..core import policy_core as pc
from ..core.bucketing import next_pow2, pad_events
from ..core.mig import GPU, DeviceModel
from ..launch import checkpoint as ckpt
from ..obs import recorder as obs_recorder
from ..sim.cluster import VM, Cluster, Host
from .queue import (Arrival, BoundedRequestQueue, Departure, Request,
                    arrival_bucket, departure_bucket)

_EPS = 1e-9

# The object-backed oracle tier (rolling-horizon MILP); every other tier
# name must be a registry policy id (FF/BF/MCC/MECC/GRMU).
ILP_TIER = "ILP"

# What a micro-batch enters in place of ``Recorder.span`` when no
# recorder is installed: one shared no-op, so nothing is built per span.
_NO_SPAN = contextlib.nullcontext()


def _no_span(name: str, **fields) -> contextlib.nullcontext:
    return _NO_SPAN


@dataclasses.dataclass
class ServeConfig:
    """Service capacities, policy knobs and governor thresholds.

    Capacities size the padded state buckets (then pow2-rounded by
    ``pad_events``): ``max_vms`` bounds total arrivals over the service's
    life, ``max_steps`` the step-grid horizon, ``max_arrivals`` the MECC
    observation schedule (defaults to ``max_vms``).  ``micro_batch`` is
    the decision kernel's event-row count per dispatch (pow2-rounded).
    """
    policy: str = "GRMU"
    tiers: Optional[Tuple[str, ...]] = None   # degradation ladder;
    #                                           None = (policy,) only
    micro_batch: int = 64
    queue_capacity: int = 1024
    max_vms: int = 4096
    max_steps: int = 1024
    max_arrivals: Optional[int] = None
    step_hours: float = 1.0
    # Policy knobs (mirror repro.core.batched.replay defaults).
    heavy_capacity: Optional[int] = None      # None = round(0.30 * G)
    heavy_capacity_frac: float = 0.30
    defrag: bool = True
    consolidation_interval: Optional[float] = None
    defrag_trigger: str = "light"
    mecc_window: float = 24.0
    # Admission governor.
    slo_s: float = 0.050          # rolling-p99 decision-latency SLO
    degrade_depth: float = 0.75   # queue fill fraction that breaches
    recover_after: int = 8        # healthy batches before stepping up
    latency_window: int = 256     # rolling decision-latency samples
    # ILP-tier knobs (object backend).
    ilp_window: int = 8
    ilp_time_limit: float = 5.0


@dataclasses.dataclass(frozen=True)
class Decision:
    """One arrival's placement decision.  ``latency_s`` is submit ->
    decision-ready wall time (queue wait + kernel + readback); ``batch``
    numbers the service's micro-batch that made it, as the ``batch``
    field of that batch's ``serve.drain_batch`` span does."""
    vm_id: int
    accepted: bool
    gpu: int                  # global GPU index, -1 when rejected
    start: int                # start block on the chosen GPU
    tier: str                 # tier that made the decision
    latency_s: float
    batch: int = -1


class Governor:
    """Admission governor: walks the tier ladder on SLO breach.

    A batch *breaches* when the queue fill is at/above ``degrade_depth``
    or the rolling p99 of decision latencies exceeds ``slo_s``.  A breach
    degrades one tier (toward the cheap end of the ladder);
    ``recover_after`` consecutive healthy batches recover one tier.  The
    latency window is cleared on every switch so the new tier is judged
    on its own samples.  ``slo_s`` is mutable at runtime (operators
    retune SLOs; tests drive the trigger with it)."""

    def __init__(self, cfg: ServeConfig, n_tiers: int):
        self.slo_s = float(cfg.slo_s)
        self.degrade_depth = float(cfg.degrade_depth)
        self.recover_after = int(cfg.recover_after)
        self.n_tiers = int(n_tiers)
        self.tier = 0
        self._healthy = 0
        self._lats = deque(maxlen=int(cfg.latency_window))

    def p99_s(self) -> float:
        if not self._lats:
            return 0.0
        return float(np.percentile(np.asarray(self._lats), 99.0))

    def note_batch(self, latencies: Sequence[float],
                   fill: float) -> Optional[Tuple[str, int, int]]:
        """Feed one batch's decision latencies + queue fill; returns a
        ``("degrade"|"recover", from_tier, to_tier)`` switch or None."""
        self._lats.extend(latencies)
        breach = fill >= self.degrade_depth or self.p99_s() > self.slo_s
        if breach:
            self._healthy = 0
            if self.tier < self.n_tiers - 1:
                old, self.tier = self.tier, self.tier + 1
                self._lats.clear()
                return ("degrade", old, self.tier)
            return None
        self._healthy += 1
        if self.tier > 0 and self._healthy >= self.recover_after:
            old, self.tier = self.tier, self.tier - 1
            self._healthy = 0
            self._lats.clear()
            return ("recover", old, self.tier)
        return None


def _skeleton_trace(models: Tuple[DeviceModel, ...],
                    gpu_model_id: np.ndarray, gpu_host_id: np.ndarray,
                    cpu_cap: np.ndarray, ram_cap: np.ndarray,
                    step_hours: float) -> B.EventTrace:
    """A zero-event EventTrace of the fleet — the shape seed that
    ``pad_events(min_shape=...)`` grows into the service's fixed-capacity
    state buckets."""
    M = len(models)
    return B.EventTrace(
        kind=np.zeros(0, np.uint8), vm_index=np.zeros(0, np.int32),
        profile=np.zeros(0, np.int16), time=np.zeros(0, np.float32),
        idx=np.zeros(0, np.int32), vm_ids=np.zeros(0, np.int64),
        vm_pids=np.zeros((0, M), np.int16), vm_heavy=np.zeros(0, bool),
        vm_cpu=np.zeros(0, np.float32), vm_ram=np.zeros(0, np.float32),
        arr_times=np.zeros(0, np.float32),
        arr_pids=np.zeros((0, M), np.int16),
        step_times=np.zeros(0, np.float64),
        num_vms=0, num_gpus=len(gpu_model_id), num_hosts=len(cpu_cap),
        models=tuple(models),
        gpu_model_id=np.asarray(gpu_model_id, np.int32),
        gpu_host_id=np.asarray(gpu_host_id, np.int32),
        cpu_cap=np.asarray(cpu_cap, np.float32),
        ram_cap=np.asarray(ram_cap, np.float32),
        step_hours=step_hours)


@dataclasses.dataclass
class _Rows:
    """One array-tier micro-batch popped from the queue: the step's
    ``E`` event rows, of which the first ``n`` are real, the ingest rows
    of its ``n_new`` arrivals, and each arrival's (row, slot, vm_id,
    submit stamp)."""
    kind: np.ndarray
    vi: np.ndarray
    prof: np.ndarray
    tim: np.ndarray
    idx: np.ndarray
    batch_vi: np.ndarray
    g_vm: np.ndarray
    g_arr: np.ndarray
    n: int
    n_new: int
    pending: List[Tuple[int, int, int, float]]


def _ingest_fn():
    """Donating scatter of new per-VM / MECC-schedule rows into the
    resident trace tables (sentinel indices drop — padding rows)."""
    def ingest(rest, vm_slots, vm_pids, vm_heavy, vm_res,
               a_slots, a_times, a_pids):
        return dict(
            rest,
            vm_pids=rest["vm_pids"].at[vm_slots].set(vm_pids,
                                                     mode="drop"),
            vm_heavy=rest["vm_heavy"].at[vm_slots].set(vm_heavy,
                                                       mode="drop"),
            vm_res=rest["vm_res"].at[vm_slots].set(vm_res, mode="drop"),
            arr_times=rest["arr_times"].at[a_slots].set(a_times,
                                                        mode="drop"),
            arr_pids=rest["arr_pids"].at[a_slots].set(a_pids,
                                                      mode="drop"))
    return jax.jit(ingest, donate_argnums=(0,))


def requests_from_trace(events: B.EventTrace
                        ) -> Tuple[List[Request], float]:
    """Convert an offline EventTrace's rows into the canonical request
    stream (STEP_END rows skipped — the service regenerates them) plus
    the horizon to :meth:`PlacementService.flush` to.  Feeding this
    stream reproduces the offline replay's decisions bit-for-bit."""
    reqs: List[Request] = []
    for j in range(len(events.kind)):
        k = int(events.kind[j])
        if k == B.ARRIVAL:
            i = int(events.vm_index[j])
            reqs.append(Arrival(
                vm_id=int(events.vm_ids[i]), time=float(events.time[j]),
                profile_ids=tuple(int(x) for x in events.vm_pids[i]),
                cpu=float(events.vm_cpu[i]),
                ram=float(events.vm_ram[i])))
        elif k == B.DEPARTURE:
            i = int(events.vm_index[j])
            reqs.append(Departure(vm_id=int(events.vm_ids[i]),
                                  time=float(events.time[j])))
    horizon = (float(events.step_times[-1])
               if len(events.step_times) else 0.0)
    return reqs, horizon


class PlacementService:
    """See the module docstring.  Build with :meth:`from_cluster` /
    :meth:`for_trace`, or directly from fleet arrays."""

    def __init__(self, *, models: Sequence[DeviceModel],
                 gpu_model_id: np.ndarray, gpu_host_id: np.ndarray,
                 cpu_cap: np.ndarray, ram_cap: np.ndarray,
                 config: Optional[ServeConfig] = None):
        cfg = config or ServeConfig()
        self.cfg = cfg
        self.models = tuple(models)
        self._M = len(self.models)
        self._G = len(gpu_model_id)
        self._H = len(cpu_cap)
        self._step_hours = float(cfg.step_hours)

        batch = next_pow2(max(int(cfg.micro_batch), 1))
        max_arr = cfg.max_arrivals or cfg.max_vms
        skeleton = _skeleton_trace(self.models, gpu_model_id,
                                   gpu_host_id, cpu_cap, ram_cap,
                                   self._step_hours)
        self._padded = pad_events(
            skeleton,
            min_shape=(batch, max(cfg.max_vms, 1), 1, 1,
                       max(max_arr, 1), max(cfg.max_steps, 1)))
        self._batch_rows = len(self._padded.kind)          # E
        self._Ncap = len(self._padded.vm_pids)
        self._Acap = len(self._padded.arr_times)
        self._Scap = self._padded.hourly_slots
        self._Gp = len(self._padded.gpu_model_id)
        self._Hp = len(self._padded.cpu_cap)
        self._NP = pc.tables_for(np, self.models).num_profiles
        self._heavy_profiles = np.array(
            [m.heavy_profile for m in self.models], np.int16)

        # Tier ladder -> statics / backends.
        self._tier_names: Tuple[str, ...] = tuple(cfg.tiers or
                                                  (cfg.policy,))
        self._statics: Dict[str, B.ReplayStatics] = {}
        for name in self._tier_names:
            if name == ILP_TIER:
                # Object-backend topology is validated on tier entry
                # (_enter_object): gpu_host_id must be host-grouped.
                continue
            if name not in pc.POLICY_IDS:
                raise ValueError(f"unknown tier policy {name!r} (want "
                                 f"one of {list(pc.POLICY_IDS)} or "
                                 f"{ILP_TIER!r})")
            self._statics[name] = B.ReplayStatics(
                policy=pc.POLICY_IDS[name], models=self.models,
                defrag=cfg.defrag,
                consolidation_interval=cfg.consolidation_interval,
                defrag_trigger=cfg.defrag_trigger,
                mecc_window=cfg.mecc_window, score_backend="tables")
        if cfg.heavy_capacity is not None:
            self.heavy_capacity = int(cfg.heavy_capacity)
        else:
            # Same rounding as default_heavy_capacity / the GRMU class.
            self.heavy_capacity = int(round(cfg.heavy_capacity_frac
                                            * self._G))

        # Resident trace tables on device + host mirrors of the mutable
        # ones (checkpoint source, object-tier rebuild source).
        rest_np = {k: v for k, v in
                   B.trace_arrays(self._padded).items()
                   if k not in B.EVENT_KEYS}
        self._h_vm_pids = rest_np["vm_pids"].copy()
        self._h_vm_heavy = rest_np["vm_heavy"].copy()
        self._h_vm_res = rest_np["vm_res"].copy()
        self._h_arr_times = rest_np["arr_times"].copy()
        self._h_arr_pids = rest_np["arr_pids"].copy()
        self._rest = {k: jnp.asarray(v) for k, v in rest_np.items()}
        self._ingest = compile_cache.cached_replay_fn(
            ("serve-ingest",), _ingest_fn)

        # Per-slot stream bookkeeping (host only).
        self._h_vm_ids = np.full(self._Ncap, -1, np.int64)
        self._h_vm_arrival = np.zeros(self._Ncap, np.float64)
        self._h_vm_abucket = np.zeros(self._Ncap, np.int32)
        self._h_accepted = np.zeros(self._Ncap, bool)
        self._slot_of: Dict[int, int] = {}
        self._n_vms = 0
        self._n_arr = 0
        self._bucket = 0
        self._step_t = 0.0          # accumulated float64 step grid
        self.late_requests = 0

        # Migration totals carried across tier switches; the live tier's
        # own counters start at 0 after every switch.
        self._mig_intra = 0
        self._mig_inter = 0

        self.queue = BoundedRequestQueue(cfg.queue_capacity)
        self.governor = Governor(cfg, len(self._tier_names))
        self.decisions: Dict[int, Decision] = {}
        self.tier_occupancy: Dict[str, int] = {n: 0
                                               for n in self._tier_names}
        self.switch_events: List[dict] = []
        self._ckpt_seq = 0
        self._n_batches = 0         # micro-batches drained so far

        # Object-tier state (populated by _enter_object).
        self._cluster: Optional[Cluster] = None
        self._policy = None
        self._h_counts = np.zeros((self._NP, 2), np.int32)
        self._h_hourly = np.zeros((self._Scap, 4), np.int32)
        self._rejected_step: List[VM] = []

        # Array-tier state.
        self._state: Optional[dict] = None
        self._step_fn: Optional[Callable] = None

        self._enter_tier(0, self._initial_snapshot())

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_cluster(cls, cluster: Cluster,
                     config: Optional[ServeConfig] = None
                     ) -> "PlacementService":
        return cls(models=cluster.models,
                   gpu_model_id=cluster.gpu_model_id,
                   gpu_host_id=cluster.gpu_host_id,
                   cpu_cap=cluster.host_cpu_cap,
                   ram_cap=cluster.host_ram_cap, config=config)

    @classmethod
    def for_trace(cls, events: B.EventTrace,
                  config: Optional[ServeConfig] = None
                  ) -> "PlacementService":
        """A service sized to replay ``events``' fleet and stream (the
        parity-test / benchmark constructor)."""
        cfg = dataclasses.replace(
            config or ServeConfig(),
            max_vms=max(events.num_vms, 1),
            max_steps=max(len(events.step_times), 1),
            max_arrivals=max(len(events.arr_times), 1),
            step_hours=events.step_hours)
        return cls(models=events.models,
                   gpu_model_id=events.gpu_model_id[:events.num_gpus],
                   gpu_host_id=events.gpu_host_id[:events.num_gpus],
                   cpu_cap=events.cpu_cap[:events.num_hosts],
                   ram_cap=events.ram_cap[:events.num_hosts],
                   config=cfg)

    # -- public surface ----------------------------------------------------
    @property
    def tier_name(self) -> str:
        return self._tier_names[self.governor.tier]

    def submit(self, req: Request) -> bool:
        """Enqueue a request; False = queue full (backpressure)."""
        return self.queue.submit(req)

    def drain(self, max_batches: Optional[int] = None) -> List[Decision]:
        """Drain queued requests through the decision kernel in
        micro-batches; returns the arrival decisions made."""
        out: List[Decision] = []
        batches = 0
        while len(self.queue) and (max_batches is None
                                   or batches < max_batches):
            out.extend(self._drain_batch())
            batches += 1
        return out

    def flush(self, horizon: float) -> None:
        """Drain everything queued, then emit trailing STEP_END events
        through the step grid up to ``horizon`` (inclusive) — the online
        equivalent of the offline trace's trailing step rows."""
        self.drain()
        if self.tier_name == ILP_TIER:
            while self._step_t < horizon + _EPS:
                self._object_step_end()
            return
        while self._step_t < horizon + _EPS:
            self._dispatch_steps_only(horizon)

    def accepted_ids(self) -> List[int]:
        """Accepted vm_ids in arrival order (== offline
        ``SimResult.accepted_ids`` for the same stream)."""
        return [int(self._h_vm_ids[i]) for i in range(self._n_vms)
                if self._h_accepted[i]]

    def migrations(self) -> Tuple[int, int]:
        """(intra, inter) migration totals across all tiers so far."""
        snap = self._snapshot()
        return int(snap["intra"]), int(snap["inter"])

    def stats(self) -> dict:
        lats = [d.latency_s for d in self.decisions.values()]
        arr = np.asarray(lats) if lats else np.zeros(1)
        return {
            "tier": self.tier_name,
            "decisions": len(self.decisions),
            "accepted": sum(d.accepted for d in
                            self.decisions.values()),
            "p50_ms": float(np.percentile(arr, 50.0)) * 1e3,
            "p99_ms": float(np.percentile(arr, 99.0)) * 1e3,
            "queue_high_watermark": self.queue.high_watermark,
            "queue_dropped": self.queue.dropped,
            "tier_occupancy": dict(self.tier_occupancy),
            "switches": len(self.switch_events),
        }

    # -- checkpoint / restore ----------------------------------------------
    def _checkpoint_tree(self, snap: Optional[dict] = None) -> dict:
        snap = snap or self._snapshot()
        return {
            "snap": snap,
            "vm": {"pids": self._h_vm_pids, "heavy": self._h_vm_heavy,
                   "res": self._h_vm_res, "ids": self._h_vm_ids,
                   "arrival": self._h_vm_arrival,
                   "abucket": self._h_vm_abucket,
                   "accepted": self._h_accepted},
            "arr": {"times": self._h_arr_times,
                    "pids": self._h_arr_pids},
            "scalars": np.array(
                [self._n_vms, self._n_arr, self._bucket,
                 self.governor.tier, self.late_requests],
                np.int64),
            "step_t": np.float64(self._step_t),
        }

    def checkpoint(self, ckpt_dir: str) -> str:
        """Atomically snapshot the full service state (drained queue
        required — in-flight requests are not part of the state)."""
        if len(self.queue):
            raise RuntimeError("drain() the queue before checkpointing "
                               f"({len(self.queue)} requests in flight)")
        self._ckpt_seq += 1
        path = ckpt.save(ckpt_dir, self._ckpt_seq,
                         self._checkpoint_tree())
        rec = obs_recorder.active()
        if rec is not None:
            rec.service("checkpoint", dir=ckpt_dir, seq=self._ckpt_seq,
                        bucket=self._bucket, n_vms=self._n_vms)
        return path

    def restore(self, ckpt_dir: str) -> bool:
        """Restore the newest checkpoint into this (identically
        configured, freshly built) service.  Returns False when no valid
        checkpoint exists."""
        out = ckpt.restore_latest(ckpt_dir, self._checkpoint_tree())
        if out is None:
            return False
        seq, tree = out
        tree = jax.tree.map(np.asarray, tree)
        self._ckpt_seq = int(seq)
        self._h_vm_pids = tree["vm"]["pids"].copy()
        self._h_vm_heavy = tree["vm"]["heavy"].copy()
        self._h_vm_res = tree["vm"]["res"].copy()
        self._h_vm_ids = tree["vm"]["ids"].copy()
        self._h_vm_arrival = tree["vm"]["arrival"].copy()
        self._h_vm_abucket = tree["vm"]["abucket"].copy()
        self._h_accepted = tree["vm"]["accepted"].copy()
        self._h_arr_times = tree["arr"]["times"].copy()
        self._h_arr_pids = tree["arr"]["pids"].copy()
        n_vms, n_arr, bucket, tier, late = (int(x) for x in
                                            tree["scalars"])
        self._n_vms, self._n_arr = n_vms, n_arr
        self._bucket, self.late_requests = bucket, late
        self._step_t = float(tree["step_t"])
        self._slot_of = {int(self._h_vm_ids[i]): i
                         for i in range(n_vms)}
        # Rebuild the resident device tables from the restored mirrors.
        rest_np = {k: v for k, v in
                   B.trace_arrays(self._padded).items()
                   if k not in B.EVENT_KEYS}
        rest_np["vm_pids"] = self._h_vm_pids
        rest_np["vm_heavy"] = self._h_vm_heavy
        rest_np["vm_res"] = self._h_vm_res
        rest_np["arr_times"] = self._h_arr_times
        rest_np["arr_pids"] = self._h_arr_pids
        self._rest = {k: jnp.asarray(v) for k, v in rest_np.items()}
        snap = {k: np.asarray(v) for k, v in tree["snap"].items()}
        self._mig_intra = int(snap["intra"])
        self._mig_inter = int(snap["inter"])
        self.governor.tier = min(tier, len(self._tier_names) - 1)
        self._enter_tier(self.governor.tier, snap)
        rec = obs_recorder.active()
        if rec is not None:
            rec.service("restore", dir=ckpt_dir, seq=self._ckpt_seq,
                        bucket=self._bucket, n_vms=self._n_vms)
        return True

    # -- canonical state snapshot ------------------------------------------
    def _initial_snapshot(self) -> dict:
        """Fresh-service snapshot — value-identical to
        ``batched.init_state`` on the padded skeleton."""
        ar = np.arange(self._Gp)
        basket = np.where(ar == 0, pc.HEAVY_BASKET,
                          np.where(ar == 1, pc.LIGHT_BASKET,
                                   pc.POOL)).astype(np.int32)
        basket[self._G:] = B.PAD_BASKET
        return {
            "free": np.asarray(B._gpu_full(self._padded), np.int32),
            "vmrow": np.tile(np.array([-1, 0, 0], np.int32),
                             (self._Ncap, 1)),
            "counts": np.zeros((self._NP, 2), np.int32),
            "host_used": np.zeros((self._Hp, 2), np.float32),
            "hourly": np.zeros((self._Scap, 4), np.int32),
            "basket": basket,
            "intra": np.int32(0), "inter": np.int32(0),
            "rej": np.bool_(False),
            "vm_count": np.zeros(self._Gp, np.int32),
            "last_cons": np.float32(0.0),
            "mecc_counts": np.zeros((self._M, self._NP), np.int32),
            "mecc_ptr": np.int32(0),
        }

    def _snapshot(self) -> dict:
        """The canonical host-side cluster state: every key every tier
        could need, synthesized deterministically where the live tier
        doesn't track it.  ``intra``/``inter`` are service-lifetime
        totals (tier bases folded in)."""
        snap = self._initial_snapshot()
        if self.tier_name == ILP_TIER:
            cl, pol = self._cluster, self._policy
            free = snap["free"]
            free[:self._G] = cl.free_masks.astype(np.int32)
            vmrow = snap["vmrow"]
            for vm_id, (host, gpu) in cl.placements.items():
                i = self._slot_of[vm_id]
                vmrow[i, 0] = gpu.global_index
                vmrow[i, 1] = int(gpu.placements[vm_id][1])
            vmrow[:self._Ncap, 2] = self._h_accepted
            host_used = snap["host_used"]
            host_used[:self._H, 0] = cl.host_cpu_used
            host_used[:self._H, 1] = cl.host_ram_used
            snap["counts"] = self._h_counts.copy()
            snap["hourly"] = self._h_hourly.copy()
            snap["intra"] = np.int32(self._mig_intra
                                     + pol.intra_migrations)
            snap["inter"] = np.int32(self._mig_inter
                                     + pol.inter_migrations)
        else:
            live = jax.device_get(self._state)
            for k, v in live.items():
                snap[k] = np.asarray(v)
            snap["vmrow"] = snap["vmrow"].copy()
            snap["vmrow"][:, 2] = self._h_accepted
            snap["intra"] = np.int32(self._mig_intra
                                     + int(live.get("intra", 0)))
            snap["inter"] = np.int32(self._mig_inter
                                     + int(live.get("inter", 0)))
        # Keys the leaving tier didn't track keep their deterministic
        # initial-snapshot synthesis (documented loss: GRMU basket
        # evolution and MECC observation history do not survive an
        # intervening tier that doesn't carry them; the consolidation
        # clock restarts at the switch).
        return snap

    # -- tier transitions --------------------------------------------------
    def _switch_tier(self, kind: str, old: int, new: int) -> None:
        snap = self._snapshot()
        self._mig_intra = int(snap["intra"])
        self._mig_inter = int(snap["inter"])
        event = {"event": kind, "from": self._tier_names[old],
                 "to": self._tier_names[new], "bucket": self._bucket,
                 "queue_depth": len(self.queue),
                 "p99_ms": self.governor.p99_s() * 1e3}
        self.switch_events.append(event)
        rec = obs_recorder.active()
        if rec is not None:
            rec.service(**event)
        self._enter_tier(new, snap)

    def _enter_tier(self, tier: int, snap: dict) -> None:
        name = self._tier_names[tier]
        if name == ILP_TIER:
            self._enter_object(snap)
        else:
            self._enter_array(name, snap)

    def _enter_array(self, name: str, snap: dict) -> None:
        st = self._statics[name]
        self._cluster = None
        self._policy = None
        state = dict(
            free=jnp.asarray(snap["free"], jnp.int32),
            vmrow=jnp.asarray(snap["vmrow"], jnp.int32),
            counts=jnp.asarray(snap["counts"], jnp.int32),
            host_used=jnp.asarray(snap["host_used"], jnp.float32),
            hourly=jnp.asarray(snap["hourly"], jnp.int32),
        )
        if st.policy == B.GRMU:
            state["basket"] = jnp.asarray(snap["basket"], jnp.int32)
            state["intra"] = jnp.asarray(0, jnp.int32)
            state["inter"] = jnp.asarray(0, jnp.int32)
            if st.defrag:
                state["rej"] = jnp.asarray(False)
            if st.consolidation_interval is not None:
                vm_gpu = snap["vmrow"][:, 0]
                state["vm_count"] = jnp.asarray(np.bincount(
                    vm_gpu[vm_gpu >= 0], minlength=self._Gp
                ).astype(np.int32))
                state["last_cons"] = jnp.asarray(
                    np.float32(snap["last_cons"]))
        if st.policy == B.MECC:
            state["mecc_counts"] = jnp.asarray(snap["mecc_counts"],
                                               jnp.int32)
            state["mecc_ptr"] = jnp.asarray(snap["mecc_ptr"],
                                            jnp.int32)
        self._state = state
        self._step_fn = B.make_decision_step(st)
        self._cap = jnp.asarray(self.heavy_capacity, jnp.int32)

    def _enter_object(self, snap: dict) -> None:
        from ..core.policies import ILPPolicy
        ghid = self._padded.gpu_host_id[:self._G]
        if self._G and np.any(np.diff(ghid) < 0):
            raise ValueError(
                "the ILP tier rebuilds an object-level Cluster, which "
                "numbers GPUs host-by-host — gpu_host_id must be "
                "grouped (non-decreasing)")
        hosts = []
        g = 0
        for h in range(self._H):
            gpus = []
            while g < self._G and int(ghid[g]) == h:
                gpus.append(GPU(
                    model=self.models[
                        int(self._padded.gpu_model_id[g])]))
                g += 1
            hosts.append(Host(h, gpus,
                              float(self._padded.cpu_cap[h]),
                              float(self._padded.ram_cap[h])))
        cluster = Cluster(hosts, models=self.models)
        order = []
        vmrow = snap["vmrow"]
        for i in range(self._n_vms):
            if vmrow[i, 0] < 0:
                continue
            vm = self._vm_object(i)
            gidx = int(vmrow[i, 0])
            cluster.place_at(vm, cluster.gpu_index[gidx][1],
                             int(vmrow[i, 1]))
            order.append(vm.vm_id)
        policy = ILPPolicy(cluster, window=self.cfg.ilp_window,
                           time_limit=self.cfg.ilp_time_limit)
        # Residents in dense (acceptance) order define the rolling
        # window, exactly as if the policy had placed them itself.
        policy._order = order
        self._cluster = cluster
        self._policy = policy
        self._h_counts = snap["counts"].copy()
        self._h_hourly = snap["hourly"].copy()
        self._rejected_step = []
        self._state = None
        self._step_fn = None

    def _vm_object(self, slot: int) -> VM:
        pids = tuple(int(x) for x in self._h_vm_pids[slot])
        # profile is cosmetic when profile_ids is set (placement resolves
        # per-model via vm_pids); clamp -1 ("no GI on reference model").
        return VM(vm_id=int(self._h_vm_ids[slot]),
                  profile=self.models[0].profiles[max(pids[0], 0)],
                  arrival=float(self._h_vm_arrival[slot]),
                  duration=0.0,
                  cpu=float(self._h_vm_res[slot, 0]),
                  ram=float(self._h_vm_res[slot, 1]),
                  profile_ids=pids)

    # -- stream bookkeeping ------------------------------------------------
    def _request_bucket(self, req: Request) -> int:
        if isinstance(req, Arrival):
            b = arrival_bucket(req.time, self._step_hours)
            if b < self._bucket:
                self.late_requests += 1
                b = self._bucket
            return b
        slot = self._slot_of.get(req.vm_id)
        if slot is None:
            raise KeyError(f"departure for unknown vm_id {req.vm_id}")
        b = departure_bucket(req.time,
                             int(self._h_vm_abucket[slot]),
                             self._step_hours)
        if b < self._bucket:
            self.late_requests += 1
            b = self._bucket
        return b

    def _admit_slot(self, req: Arrival) -> Tuple[int, int]:
        """Assign the next dense VM slot + arrival ordinal and record the
        request in the host tables.  Returns (slot, arrival ordinal)."""
        if req.vm_id in self._slot_of:
            raise ValueError(f"duplicate arrival for vm_id {req.vm_id}")
        if self._n_vms >= self._Ncap:
            raise RuntimeError(
                f"VM capacity exhausted ({self._Ncap} slots; raise "
                "ServeConfig.max_vms)")
        if self._n_arr >= self._Acap:
            raise RuntimeError(
                f"arrival-schedule capacity exhausted ({self._Acap}; "
                "raise ServeConfig.max_arrivals)")
        if len(req.profile_ids) != self._M:
            raise ValueError(
                f"vm {req.vm_id}: profile_ids has "
                f"{len(req.profile_ids)} entries for a "
                f"{self._M}-model fleet")
        slot, a = self._n_vms, self._n_arr
        self._n_vms += 1
        self._n_arr += 1
        pids = np.asarray(req.profile_ids, np.int16)
        hp = self._heavy_profiles
        self._h_vm_pids[slot] = pids
        self._h_vm_heavy[slot] = bool(np.all((pids == hp) & (hp >= 0)))
        self._h_vm_res[slot] = (np.float32(req.cpu),
                                np.float32(req.ram))
        self._h_vm_ids[slot] = req.vm_id
        self._h_vm_arrival[slot] = req.time
        self._h_vm_abucket[slot] = self._bucket
        # MECC observation row: stamped with the bucket's grid start,
        # exactly like the offline arr_times column.
        self._h_arr_times[a] = np.float32(self._step_t)
        self._h_arr_pids[a] = pids
        self._slot_of[req.vm_id] = slot
        return slot, a

    def _advance_bucket(self) -> None:
        if self._bucket + 1 >= self._Scap:
            raise RuntimeError(
                f"step-grid capacity exhausted ({self._Scap} slots; "
                "raise ServeConfig.max_steps)")
        self._bucket += 1
        self._step_t += self._step_hours

    # -- the micro-batch ---------------------------------------------------
    def _drain_batch(self) -> List[Decision]:
        batch = self._n_batches
        self._n_batches += 1
        if self.tier_name == ILP_TIER:
            return self._drain_batch_object(batch)
        return self._drain_batch_array(batch)

    def _drain_batch_array(self, batch: int) -> List[Decision]:
        """One array-tier micro-batch.  With a recorder installed it is
        recorded as this span tree (fields after the names)::

            serve.drain_batch   batch, tier
              serve.pop         rows, arrivals, wait_sum_s, batch_rows
              serve.batch       tier, rows, arrivals: the round trip
                serve.ingest    (only with arrivals)
                serve.step
                serve.readback
              serve.emit

        An arrival's wait is the time from its submit to the start of
        the pop; ``batch_rows`` is ``E``, the rows the step scans.
        Without one, each span is the shared no-op and no clock is read
        for the waits."""
        rec = obs_recorder.active()
        span = rec.span if rec is not None else _no_span
        tier = self.tier_name
        with span("serve.drain_batch", batch=batch, tier=tier):
            with span("serve.pop") as f:
                t_pop = time.perf_counter() if rec is not None else 0.0
                rows = self._pop_rows()
                if rec is not None:
                    f.update(rows=rows.n, arrivals=len(rows.pending),
                             wait_sum_s=sum(t_pop - p[3]
                                            for p in rows.pending),
                             batch_rows=self._batch_rows)
            if rows.n == 0:
                return []
            with span("serve.batch", tier=tier, rows=rows.n,
                      arrivals=len(rows.pending)):
                if rows.n_new:
                    with span("serve.ingest"):
                        self._ingest_rows(rows)
                with span("serve.step"):
                    out = self._step_rows(rows)
                with span("serve.readback"):
                    got = jax.device_get(out)
            with span("serve.emit"):
                return self._emit(rows, got, batch)

    def _pop_rows(self) -> _Rows:
        """Pop up to ``E`` rows from the queue into step inputs: step
        ends where a request's bucket passes the current one, then the
        request's own row."""
        E = self._batch_rows
        rows = _Rows(
            kind=np.full(E, B.PAD, np.uint8), vi=np.zeros(E, np.int32),
            prof=np.zeros(E, np.int16), tim=np.zeros(E, np.float32),
            idx=np.zeros(E, np.int32),
            batch_vi=np.full(E, self._Ncap, np.int32),
            # Fixed-shape ingest rows (sentinel slots drop).
            g_vm=np.full(E, self._Ncap, np.int32),
            g_arr=np.full(E, self._Acap, np.int32),
            n=0, n_new=0, pending=[])
        kind, vi, prof, tim, idx = (rows.kind, rows.vi, rows.prof,
                                    rows.tim, rows.idx)
        batch_vi, g_vm, g_arr = rows.batch_vi, rows.g_vm, rows.g_arr
        pending = rows.pending
        n = 0
        n_new = 0
        while n < E:
            nxt = self.queue.peek()
            if nxt is None:
                break
            req, enq = nxt
            b = self._request_bucket(req)
            if b > self._bucket:
                kind[n] = B.STEP_END
                tim[n] = np.float32(self._step_t)
                idx[n] = self._bucket
                n += 1
                self._advance_bucket()
                continue
            self.queue.pop()
            if isinstance(req, Arrival):
                slot, a = self._admit_slot(req)
                kind[n] = B.ARRIVAL
                vi[n] = slot
                prof[n] = self._h_vm_pids[slot, 0]
                tim[n] = np.float32(self._step_t)
                idx[n] = a
                batch_vi[n] = slot
                g_vm[n_new] = slot
                g_arr[n_new] = a
                n_new += 1
                pending.append((n, slot, req.vm_id, enq))
            else:
                slot = self._slot_of[req.vm_id]
                kind[n] = B.DEPARTURE
                vi[n] = slot
                prof[n] = self._h_vm_pids[slot, 0]
                tim[n] = np.float32(self._step_t)
            n += 1
        rows.n, rows.n_new = n, n_new
        return rows

    def _ingest_rows(self, rows: _Rows) -> None:
        """Scatter the new arrivals' table rows before the decision
        kernel reads them (gathers by slot sentinel drop the padding
        rows)."""
        g_vm, g_arr = rows.g_vm, rows.g_arr
        vm = np.minimum(g_vm, self._Ncap - 1)
        arr = np.minimum(g_arr, self._Acap - 1)
        self._rest = self._ingest(
            self._rest, g_vm, self._h_vm_pids[vm], self._h_vm_heavy[vm],
            self._h_vm_res[vm], g_arr, self._h_arr_times[arr],
            self._h_arr_pids[arr])

    def _step_rows(self, rows: _Rows):
        """Dispatch the decision step on the batch; returns its per-row
        (gpu, start, accepted) output, still on the device."""
        ev = dict(kind=rows.kind, vm_index=rows.vi, profile=rows.prof,
                  time=rows.tim, idx=rows.idx)
        self._state, out = self._step_fn(
            self._state, ev, self._rest, self._cap, rows.batch_vi)
        return out

    def _emit(self, rows: _Rows, got: np.ndarray,
              batch: int) -> List[Decision]:
        """Turn the read-back rows into the arrivals' decisions, then let
        the governor see the batch."""
        t_done = time.perf_counter()
        tier = self.tier_name
        out: List[Decision] = []
        for j, slot, vm_id, enq in rows.pending:
            r = got[j]
            acc = int(r[2]) > 0
            self._h_accepted[slot] = acc
            d = Decision(vm_id=vm_id, accepted=acc,
                         gpu=int(r[0]) if acc else -1,
                         start=int(r[1]) if acc else 0,
                         tier=tier, latency_s=t_done - enq, batch=batch)
            self.decisions[vm_id] = d
            self.tier_occupancy[tier] += 1
            out.append(d)
        self._note_governor([d.latency_s for d in out])
        return out

    def _dispatch_steps_only(self, horizon: float) -> None:
        """One batch of trailing STEP_END rows (flush path)."""
        E = self._batch_rows
        kind = np.full(E, B.PAD, np.uint8)
        vi = np.zeros(E, np.int32)
        prof = np.zeros(E, np.int16)
        tim = np.zeros(E, np.float32)
        idx = np.zeros(E, np.int32)
        n = 0
        while n < E and self._step_t < horizon + _EPS:
            kind[n] = B.STEP_END
            tim[n] = np.float32(self._step_t)
            idx[n] = self._bucket
            n += 1
            self._advance_bucket()
        if n == 0:
            return
        ev = dict(kind=kind, vm_index=vi, profile=prof, time=tim,
                  idx=idx)
        self._state, rows = self._step_fn(
            self._state, ev, self._rest, self._cap,
            np.full(E, self._Ncap, np.int32))
        rows.block_until_ready()

    # -- object (ILP) tier -------------------------------------------------
    def _drain_batch_object(self, batch: int) -> List[Decision]:
        rec = obs_recorder.active()
        span = rec.span if rec is not None else _no_span
        tier = self.tier_name
        with span("serve.drain_batch", batch=batch, tier=tier):
            with span("serve.batch", tier=tier,
                      rows=min(self._batch_rows, len(self.queue))):
                out = self._object_batch(batch)
            self._note_governor([d.latency_s for d in out])
        return out

    def _object_batch(self, batch: int) -> List[Decision]:
        tier = self.tier_name
        cl, pol = self._cluster, self._policy
        out: List[Decision] = []
        n = 0
        while n < self._batch_rows:
            nxt = self.queue.peek()
            if nxt is None:
                break
            req, enq = nxt
            b = self._request_bucket(req)
            if b > self._bucket:
                self._object_step_end()
                n += 1
                continue
            self.queue.pop()
            n += 1
            if isinstance(req, Arrival):
                slot, _ = self._admit_slot(req)
                vm = self._vm_object(slot)
                pol.on_arrival_observed(vm, self._step_t)
                p0 = int(self._h_vm_pids[slot, 0])
                self._h_counts[p0, 1] += 1
                ok = pol.place(vm)
                if ok:
                    self._h_counts[p0, 0] += 1
                    self._h_accepted[slot] = True
                    _, gpu = cl.placements[vm.vm_id]
                    g = gpu.global_index
                    start = int(gpu.placements[vm.vm_id][1])
                else:
                    g, start = -1, 0
                    self._rejected_step.append(vm)
                d = Decision(vm_id=req.vm_id, accepted=ok, gpu=g,
                             start=start, tier=tier,
                             latency_s=time.perf_counter() - enq,
                             batch=batch)
                self.decisions[req.vm_id] = d
                self.tier_occupancy[tier] += 1
                out.append(d)
            else:
                if req.vm_id in cl.placements:
                    vm = cl.vms[req.vm_id]
                    cl.release(req.vm_id)
                    pol.on_departure(vm, self._step_t)
        return out

    def _object_step_end(self) -> None:
        self._policy.on_step_end(self._step_t, self._rejected_step)
        self._rejected_step = []
        pms, gpus = self._cluster.active_hardware()
        self._h_hourly[self._bucket] = (
            int(self._h_counts[:, 0].sum()),
            int(self._h_counts[:, 1].sum()), pms, gpus)
        self._advance_bucket()

    # -- governor ----------------------------------------------------------
    def _note_governor(self, latencies: List[float]) -> None:
        switch = self.governor.note_batch(latencies, self.queue.fill)
        if switch is not None:
            self._switch_tier(*switch)


__all__ = ["PlacementService", "ServeConfig", "Decision", "Governor",
           "requests_from_trace", "ILP_TIER"]
