"""Trace-streamed replay: chunked scans over a donated carry.

``repro.core.batched`` compiles the replay as one ``lax.scan`` over the
whole event stream, so the full packed trace must be resident on device
for the scan's lifetime — at 10M VMs (~20M event rows) that is the
binding constraint, not compute.  This module splits the *event stream*
(and only it — per-VM/fleet/MECC tables stay resident) into fixed-size
chunks and drives an outer host loop:

  * one jitted **chunk step** — ``_scan_body`` over a (C,)-shaped event
    slice, carry in / carry out, with the carry **donated** so XLA
    reuses the state buffers in place across every chunk;
  * only O(chunk) event bytes live on device at once; the next chunk is
    ``jax.device_put`` *before* the current chunk runs (double
    buffering), so the host->device copy overlaps the scan;
  * chunk boundaries are decision-neutral by construction: the carry is
    the complete cluster state and the step function never reads an
    event's position, so scanning chunks back-to-back computes exactly
    the single-scan fixpoint (asserted decision-for-decision in
    tests/test_streaming.py);
  * the compiled chunk step's shape signature is (chunk, state-bucket) —
    **independent of the trace length**.  Every trace padded to the same
    non-event buckets reuses one executable no matter how many chunks it
    spans (``pad_events(event_multiple=chunk)`` bounds the event padding
    by one chunk instead of pow2-doubling), composing with the
    ``ReplayStatics`` compile cache exactly like the unchunked path;
  * ``num_shards`` composes with ``repro.core.sharded``: the chunk step
    is wrapped in the same fleet-partition ``shard_map`` (replicated
    state, local gathers, O(k) reconcile), so sharded fleets stream
    chunks too.

The final ``SimResult`` is assembled from a separate jitted finalize
(the same output reductions as the unchunked scan), so the two paths
return byte-identical arrays.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import inscan as obs_inscan
from ..obs import recorder as obs_recorder
from ..sim.metrics import SimResult
from . import compile_cache
from .batched import (EVENT_KEYS, STEP_END, EventTrace, _finalize,
                      _scan_body, default_heavy_capacity, init_state,
                      replay_statics, result_from_arrays, trace_arrays)
from .bucketing import pad_events

# Default chunk length: big enough that per-chunk dispatch overhead is
# noise, small enough that a chunk of packed event rows (~15 B/row) stays
# around a megabyte.
DEFAULT_CHUNK_EVENTS = 65536


def split_trace(tr: Dict[str, np.ndarray]):
    """(event-stream arrays, resident arrays) — the chunked/static split
    of a :func:`repro.core.batched.trace_arrays` pytree."""
    ev = {k: tr[k] for k in EVENT_KEYS}
    rest = {k: v for k, v in tr.items() if k not in EVENT_KEYS}
    return ev, rest


def replay_bytes(events: EventTrace,
                 chunk_events: Optional[int] = None) -> Dict[str, int]:
    """Byte accounting for one replay: total packed event-stream bytes,
    the resident (non-chunked) trace bytes, and — when ``chunk_events``
    is given — the per-chunk event bytes actually on device at once."""
    ev, rest = split_trace(trace_arrays(events))
    ev_bytes = sum(int(a.nbytes) for a in ev.values())
    out = dict(event_bytes=ev_bytes,
               resident_bytes=sum(int(a.nbytes) for a in rest.values()))
    if chunk_events:
        n_rows = max(len(events.kind), 1)
        out["chunk_bytes"] = -(-ev_bytes * chunk_events // n_rows)
    return out


def _chunk_fn(st, state, ev_chunk, rest, heavy_capacity):
    """One chunk through the scan body: carry in, carry out.  With
    telemetry statics the ``tele_steps``/``tele_masks`` accumulators
    ride the chunk-level carry (this jit's boundary, crossed once per
    chunk) — never the inner ``lax.scan`` carry — and each chunk's
    stacked telemetry ys are folded into them with one scatter here."""
    if st.telemetry:
        state = dict(state)
        steps0 = state.pop("tele_steps")
        masks0 = state.pop("tele_masks")
        final, ys = _scan_body(st, state, dict(rest, **ev_chunk),
                               heavy_capacity)
        is_step = ev_chunk["kind"].astype(jnp.int32) == STEP_END
        steps, masks = obs_inscan.fold_step_rows(
            (steps0, masks0), is_step, ev_chunk["idx"], ys)
        return dict(final, tele_steps=steps, tele_masks=masks)
    return _scan_body(st, state, dict(rest, **ev_chunk), heavy_capacity)


def make_chunked_replay(events: EventTrace, policy: int, *,
                        chunk_events: int = DEFAULT_CHUNK_EVENTS,
                        num_shards: Optional[int] = None,
                        **cfg) -> Callable:
    """Chunk-streaming twin of ``batched.make_replay`` — same signature,
    same outputs, same decisions; only O(chunk) event bytes resident.

    The trace is (idempotently) padded so the event dimension splits
    evenly into ``chunk_events``-row chunks; all other dimensions get
    their usual pow2 buckets.  The returned ``run(heavy_capacity)``
    exposes ``run.num_chunks`` / ``run.chunk_events``.
    """
    if chunk_events < 1:
        raise ValueError(f"chunk_events must be >= 1, got {chunk_events}")
    compile_cache.ensure_persistent_cache()
    events = pad_events(events, event_multiple=chunk_events,
                        shards=num_shards or 1)
    if num_shards:
        from . import sharded as SH
        mesh = SH.fleet_mesh(num_shards)
        k = mesh.devices.size
        st = replay_statics(events, policy, score_backend="tables",
                            axis_name=SH.FLEET_AXIS, num_shards=k, **cfg)

        def build_chunk():
            from jax.sharding import PartitionSpec as P
            body = jax.shard_map(functools.partial(_chunk_fn, st),
                                 mesh=mesh,
                                 in_specs=(P(), P(), P(), P()),
                                 out_specs=P(), check_vma=False)
            return jax.jit(body, donate_argnums=(0,))

        chunk_key = (st, k, "shard-chunk", chunk_events)
    else:
        st = replay_statics(events, policy, **cfg)

        def build_chunk():
            return jax.jit(functools.partial(_chunk_fn, st),
                           donate_argnums=(0,))

        chunk_key = (st, "chunk", chunk_events)
    jfn = compile_cache.cached_replay_fn(chunk_key, build_chunk)
    # Finalize donates too: the carry is dead once reduced to outputs.
    ffn = compile_cache.cached_replay_fn(
        (st, "finalize"),
        lambda: jax.jit(functools.partial(_finalize, st),
                        donate_argnums=(0,)))

    ev_np, rest_np = split_trace(trace_arrays(events))
    E = len(events.kind)
    n_chunks = E // chunk_events
    # Per-chunk host views (contiguous axis-0 slices — no copies).
    chunks = [{k: v[i * chunk_events:(i + 1) * chunk_events]
               for k, v in ev_np.items()} for i in range(n_chunks)]
    rest = {k: jnp.asarray(v) for k, v in rest_np.items()}

    chunk_bytes = sum(int(v[:chunk_events].nbytes)
                      for v in ev_np.values())

    def run(heavy_capacity):
        cap = jnp.asarray(heavy_capacity, jnp.int32)
        state = init_state(events, st)
        rec = obs_recorder.active()
        if rec is not None:
            return _run_recorded(rec, state, cap)
        # Double buffering: stage chunk i+1 while chunk i scans.
        nxt = jax.device_put(chunks[0])
        for i in range(n_chunks):
            cur, nxt = nxt, (jax.device_put(chunks[i + 1])
                             if i + 1 < n_chunks else None)
            state = jfn(state, cur, rest, cap)
        return ffn(state)

    def _run_recorded(rec, state, cap):
        """Same loop with per-chunk flight-recorder spans.  A separate
        body so the default path stays branch-free per chunk; spans
        measure host dispatch time (see ``repro.obs.recorder``)."""
        with rec.span("chunk.prefetch", index=0, nbytes=chunk_bytes):
            nxt = jax.device_put(chunks[0])
        for i in range(n_chunks):
            cur = nxt
            if i + 1 < n_chunks:
                with rec.span("chunk.prefetch", index=i + 1,
                              nbytes=chunk_bytes):
                    nxt = jax.device_put(chunks[i + 1])
            else:
                nxt = None
            with rec.span("chunk.step", index=i, nbytes=chunk_bytes):
                state = jfn(state, cur, rest, cap)
        with rec.span("finalize"):
            out = ffn(state)
        rec.cache_stats()
        return out

    run.num_chunks = n_chunks
    run.chunk_events = chunk_events
    run.events = events
    return run


def replay_chunked(events: EventTrace, policy: int, heavy_capacity=None,
                   *, chunk_events: int = DEFAULT_CHUNK_EVENTS,
                   num_shards: Optional[int] = None, **cfg) -> SimResult:
    """Chunk-streaming twin of ``batched.replay`` (full ``SimResult``).
    Decision-for-decision identical to the unchunked engine for any
    chunk size (tests/test_streaming.py)."""
    if heavy_capacity is None:
        heavy_capacity = default_heavy_capacity(events)
    run = make_chunked_replay(events, policy, chunk_events=chunk_events,
                              num_shards=num_shards, **cfg)
    out = jax.device_get(run(heavy_capacity))
    return result_from_arrays(run.events, policy, out)


__all__ = ["DEFAULT_CHUNK_EVENTS", "split_trace", "replay_bytes",
           "make_chunked_replay", "replay_chunked"]
