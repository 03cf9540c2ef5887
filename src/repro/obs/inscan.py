"""In-scan telemetry plane: pure-array samples out of the replay scan.

When ``ReplayStatics.telemetry`` is on, ``repro.core.batched`` records
telemetry through exactly two channels, chosen so the scan **carry**
gains no step-indexed buffer and no buffer grows along the hot path.
This shape is load-bearing for the <= 5% overhead budget: a carry
buffer that only *one* ``lax.switch`` branch writes forces XLA to
materialize pass-through copies of it in every other branch, a
per-event cost proportional to the buffer's byte size (measured at
+40..110% per such buffer on the CPU backend — even widening the
existing (S, 4) ``hourly`` rows costs ~+40% because every non-step
branch then copies the wider buffer through).  The two channels:

  * ``vmrow`` grows a 4th column: the per-VM decision code
    (``reasons``), -1 until the VM's arrival is processed — written by
    the same ``.at[vi].set(row)`` the arrival branch always does, so
    the *write pattern* is unchanged and the widening is free
    (vmrow is row-scattered by every branch already);
  * the per-step samples leave the scan as stacked **ys outputs**:
    every branch returns a row pair (zeros except at step-end),
    ``lax.scan`` writes it once into the (E, ...) outputs — never
    carried, never copied branch-to-branch — and one post-scan
    gather (``fold_step_rows``) collapses the step-end rows into the
    step-indexed ``tele_steps``/``tele_masks`` series.  The rows are
    a *snapshot*, not a computation: the (5,) int32 scalar counters
    the carry already holds plus the (G,) per-GPU free-block masks,
    narrowed to uint8 (``num_blocks <= 8`` means every mask fits) —
    the switch copies each event's row through its output, so row
    bytes are a per-event cost worth 4x.  Deriving the per-model
    free-block histogram and fragmentation score from the masks
    happens on the host (``telemetry_from_arrays``), because inside a
    switch branch even a handful of small reduction thunks measured
    at several percent of whole-replay time — the branch body pays
    per-op dispatch, the host pays it once per replay.

Every update is a pure array op — no host callbacks, no ``io_callback``,
nothing that could de-jit the hot path (enforced repo-wide by the
``callback-purity`` lint rule) — and no decision input ever reads a
telemetry value, so the telemetry-on replay is decision-identical to
telemetry-off (tests/test_obs.py asserts this for all five policies on
the plain, chunked and sharded engines).

``unpack_finalize`` (called from the jitted finalize) emits the
``TELE_KEYS`` output arrays — the per-VM codes, the rejection tally
derived from them, and the folded step series — all by compare-and-sum
or slicing, never scatter (XLA CPU lowers scatter to a serialized
per-element loop; one scatter-add over the VM codes measured at a
percent of replay time by itself).  The per-step *cumulative
rejections by reason* series is reconstructed on the host
(``telemetry_from_arrays``) from the event stream: arrivals sort
strictly before their bucket's step-end row, so a cumulative count over
event positions is exact — keeping it out of the scan avoids a
per-arrival write to a step-indexed buffer.

Chunk streaming folds each chunk's ys into the step-indexed
accumulators *between* chunk scans (``streaming._chunk_fn``): the
accumulators ride the chunk-level carry, crossing the jit boundary once
per chunk —
not the ``lax.scan`` carry, which crosses the switch once per event.
Sharding: all telemetry inputs (``free``, ``basket``, the reason flags)
are replicated across shards under ``shard_map`` (in_specs ``P()``), so
every shard computes identical telemetry rows — the cross-shard "merge"
is the identity and the rows flow through ``out_specs=P()`` unchanged.

The host side (``telemetry_from_arrays``) slices the padded buffers back
to logical sizes and derives utilization / active-GPU series from the
free-block histogram.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..core import policy_core as pc
from . import reasons

SCHEMA_VERSION = 1

# Column layout of the per-step scalar row (``step_row`` head /
# ``tele_steps``).
COL_INTRA = 0
COL_INTER = 1
COL_HEAVY = 2         # cols 2..4: GRMU basket occupancy (0 otherwise)
COL_LIGHT = 3
COL_POOL = 4
NUM_STEP_COLS = 5

# The telemetry arrays a telemetry-enabled replay adds to its output
# dict (``unpack_finalize``), in one place so tests stay in sync.
TELE_KEYS = ("tele_vm_reason", "tele_rej", "tele_steps", "tele_masks")

# Free-mask snapshot dtype: DeviceModel enforces num_blocks <= 8, so
# every free mask is < 2**8 and the (G,)-per-event row stays 1 B/GPU.
MASK_DTYPE = jnp.uint8


def arrival_reason_code(T, gmid, free, pids, host_ok, ok, grew,
                        quota_full) -> jax.Array:
    """Classify one in-scan arrival decision (int32 code).

    ``free``/``host_ok`` must be the pre-placement state and
    ``grew``/``quota_full`` the pre-growth GRMU flags.  The fleet-wide
    fit mask runs unconditionally: it is the same elementwise (G,) test
    the arrival's selection makes, and keeping it branch-free lets XLA
    fuse it there — a ``lax.cond`` here costs far more in conditional
    dispatch than the mask it would skip.  The
    two feasibility flags come out of a single fused (G,) max reduction
    rather than two ``any`` passes (per-op dispatch in a switch branch
    is the dominant cost at this scale).
    """
    slot = pc.fit_mask(jnp, T, gmid, free, pids)
    best = jnp.max(jnp.where(slot, jnp.where(host_ok, 2, 1), 0))
    return reasons.arrival_code(jnp, ok, best >= 1, best >= 2,
                                grew, quota_full)


def step_row(state: Dict[str, jax.Array]):
    """One step-end telemetry row pair ``(scalars, free masks)`` — the
    step-end branch's scan output, sampled after defrag/consolidation
    (i.e. exactly what the next hour sees).

    Deliberately a *snapshot*, not a reduction: the branch body pays
    per-op dispatch on every execution, so even computing the
    per-model histogram here (a handful of gathers and matmuls)
    measured at several percent of whole-replay time.  Everything
    derivable from the masks is derived on the host instead
    (``telemetry_from_arrays``)."""
    zero = jnp.asarray(0, jnp.int32)
    basket = state.get("basket")
    if basket is None:
        heavy_n = light_n = pool_n = zero
    else:
        heavy_n = (basket == pc.HEAVY_BASKET).sum().astype(jnp.int32)
        light_n = (basket == pc.LIGHT_BASKET).sum().astype(jnp.int32)
        pool_n = (basket == pc.POOL).sum().astype(jnp.int32)
    head = jnp.stack([state.get("intra", zero), state.get("inter", zero),
                      heavy_n, light_n, pool_n])
    return head, state["free"].astype(MASK_DTYPE)


def fold_step_rows(rows, is_step: jax.Array, idx: jax.Array, ys):
    """Collapse a scan's stacked per-event telemetry ys (a tuple of
    (E, ...) arrays) into the step-indexed series ``rows`` (a matching
    tuple of (S, ...) arrays): each step-end event's rows land at its
    step index; steps with no step-end in this (chunk of the) stream
    keep their prior rows.  Runs once per scan/chunk — never per
    event — and scatters only scalar positions (a row scatter is ~cols
    times more serialized scatter work on the CPU backend; the rows
    themselves move via gather)."""
    E = is_step.shape[0]
    S = rows[0].shape[0]
    tgt = jnp.where(is_step, idx.astype(jnp.int32), jnp.int32(S))
    pos = jnp.full((S,), E, jnp.int32).at[tgt].set(
        jnp.arange(E, dtype=jnp.int32), mode="drop")
    has = (pos < E)[:, None]
    return tuple(
        jnp.where(has, y.at[pos].get(mode="fill", fill_value=0), r)
        for r, y in zip(rows, ys))


def unpack_finalize(final: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Emit the ``TELE_KEYS`` output arrays from the final carry and
    the folded ``tele_steps``/``tele_masks`` series (runs inside the
    jitted finalize; shapes are static).  The reason tally is a
    compare-and-sum, not a scatter-add — XLA CPU serializes scatter
    per element."""
    codes = final["vmrow"][:, 3]
    rej = ((codes[:, None] == jnp.arange(reasons.NUM_CODES)[None, :])
           & (codes >= 0)[:, None]).astype(jnp.int32).sum(axis=0)
    return dict(
        tele_vm_reason=codes,
        tele_rej=rej,
        tele_steps=final["tele_steps"],
        tele_masks=final["tele_masks"],
    )


# ---------------------------------------------------------------------------
# Host side: carry -> series
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReplayTelemetry:
    """Host-side view of one replay's telemetry (logical sizes, padding
    sliced away, derived series filled in).  ``to_json_dict`` is the
    schema-versioned JSONL payload the :class:`repro.obs.recorder`
    exports and ``repro.obs.report`` renders."""
    model_names: List[str]
    rejection_reasons: Dict[str, int]
    vm_reason: np.ndarray      # (N,) int32 code per VM, -1 = not offered
    step_times: np.ndarray     # (S,) float64
    rej_hourly: np.ndarray     # (S, 4) cumulative rejections by reason
    intra_hourly: np.ndarray   # (S,) cumulative intra migrations
    inter_hourly: np.ndarray   # (S,) cumulative inter migrations
    basket_hourly: np.ndarray  # (S, 3) heavy/light/pool GPU counts
    free_hist: np.ndarray      # (S, M, B+1) free-block histogram
    frag_mean: np.ndarray      # (S, M) mean frag score over model GPUs
    util: np.ndarray           # (S, M) used-block fraction in [0, 1]
    active_gpus: np.ndarray    # (S, M) GPUs with >= 1 block in use

    def to_json_dict(self) -> dict:
        return {
            "schema_version": SCHEMA_VERSION,
            "model_names": list(self.model_names),
            "rejection_reasons": dict(self.rejection_reasons),
            "vm_reason": self.vm_reason.tolist(),
            "step_times": self.step_times.tolist(),
            "rej_hourly": self.rej_hourly.tolist(),
            "intra_hourly": self.intra_hourly.tolist(),
            "inter_hourly": self.inter_hourly.tolist(),
            "basket_hourly": self.basket_hourly.tolist(),
            "free_hist": self.free_hist.tolist(),
            "frag_mean": self.frag_mean.tolist(),
            "util": self.util.tolist(),
            "active_gpus": self.active_gpus.tolist(),
        }


def _cum_rejections(events, vm_reason: np.ndarray) -> np.ndarray:
    """(S, 4) cumulative rejections by reason at each step-end row,
    reconstructed from event positions: arrivals sort strictly before
    their bucket's step-end event, so a cumsum over the event stream
    sampled at step-end rows equals what an in-carry counter would have
    held.  Pure host numpy — runs once per replay."""
    from ..core import batched as B  # deferred: batched imports us
    kind = np.asarray(events.kind)
    S = len(events.step_times)
    is_arr = kind == B.ARRIVAL
    is_step = kind == B.STEP_END
    onehot = np.zeros((len(kind), reasons.NUM_CODES), np.int64)
    codes = vm_reason[np.asarray(events.vm_index)[is_arr]]
    onehot[is_arr, np.clip(codes, 0, reasons.NUM_CODES - 1)] = codes >= 0
    cum = np.cumsum(onehot, axis=0)
    rows = np.zeros((S, 4), np.int64)
    rows[np.asarray(events.idx)[is_step]] = cum[is_step][:, 1:5]
    return rows


def telemetry_from_arrays(events, out: dict) -> ReplayTelemetry:
    """Assemble a :class:`ReplayTelemetry` from a telemetry-enabled
    replay's output arrays (``batched.make_replay(..., telemetry=True)``).
    Mirrors ``result_from_arrays``: everything is sliced back to the
    trace's logical N/S and derived in float64 on the host."""
    S = len(events.step_times)
    N = len(events.vm_ids)
    models = events.models
    M = len(models)
    steps = np.asarray(out["tele_steps"])[:S]
    rej = np.asarray(out["tele_rej"])
    vm_reason = np.asarray(out["tele_vm_reason"])[:N]

    mid = np.asarray(events.gpu_model_id)[:events.num_gpus]
    # Derive the per-model histogram / frag series from the raw
    # free-mask snapshots — one vectorized numpy pass per replay,
    # instead of per-step reduction thunks inside the scan.
    T = pc.tables_for(np, tuple(models))
    B = T.max_blocks
    masks = np.asarray(out["tele_masks"]).astype(
        np.int64)[:S, :events.num_gpus]                     # (S, G)
    pop = np.asarray(T.pop)[mid[None, :], masks]
    member = (mid[:, None] == np.arange(M)[None, :])        # (G, M)
    onehot = (pop[:, :, None] == np.arange(B + 1)[None, None, :])
    hist = np.einsum("sgb,gm->smb", onehot.astype(np.int64),
                     member.astype(np.int64))
    frag_sum = np.einsum(
        "sg,gm->sm", np.asarray(T.frag)[mid[None, :], masks],
        member.astype(np.float64)).astype(np.float64)
    gpus_per_model = np.bincount(mid, minlength=M).astype(np.float64)
    blocks_per_model = np.array(
        [bin(m.full_mask).count("1") for m in models], np.float64)
    total_blocks = gpus_per_model * blocks_per_model

    free_blocks = (hist * np.arange(hist.shape[-1])[None, None, :]
                   ).sum(axis=-1).astype(np.float64)
    denom = np.maximum(total_blocks, 1.0)[None, :]
    util = np.where(total_blocks[None, :] > 0,
                    1.0 - free_blocks / denom, 0.0)
    # A GPU is idle iff its free-block count equals its model's total.
    idle = np.stack([hist[:, m, int(blocks_per_model[m])]
                     for m in range(M)], axis=1).astype(np.float64)
    active_gpus = gpus_per_model[None, :] - idle
    frag_mean = np.where(gpus_per_model[None, :] > 0,
                         frag_sum / np.maximum(gpus_per_model, 1.0)[None, :],
                         0.0)
    return ReplayTelemetry(
        model_names=[m.name for m in models],
        rejection_reasons={reasons.REASON_NAMES[c]: int(rej[c])
                           for c in range(1, reasons.NUM_CODES)},
        vm_reason=vm_reason,
        step_times=np.asarray(events.step_times, np.float64),
        rej_hourly=_cum_rejections(events, vm_reason),
        intra_hourly=steps[:, COL_INTRA],
        inter_hourly=steps[:, COL_INTER],
        basket_hourly=steps[:, COL_HEAVY:COL_POOL + 1],
        free_hist=hist,
        frag_mean=frag_mean,
        util=util,
        active_gpus=active_gpus,
    )


def replay_with_telemetry(events, policy: int, heavy_capacity=None,
                          **cfg):
    """Convenience driver: telemetry-enabled replay returning
    ``(SimResult, ReplayTelemetry)``.  Accepts the same cfg as
    ``batched.replay``."""
    from ..core import batched as B  # deferred: batched imports us
    if heavy_capacity is None:
        heavy_capacity = B.default_heavy_capacity(events)
    out = jax.device_get(
        B.make_replay(events, policy, telemetry=True, **cfg)(heavy_capacity))
    return (B.result_from_arrays(events, policy, out),
            telemetry_from_arrays(events, out))


__all__ = ["SCHEMA_VERSION", "TELE_KEYS", "NUM_STEP_COLS", "MASK_DTYPE",
           "arrival_reason_code", "step_row", "fold_step_rows",
           "unpack_finalize", "ReplayTelemetry", "telemetry_from_arrays",
           "replay_with_telemetry"]
