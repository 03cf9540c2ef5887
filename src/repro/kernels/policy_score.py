"""Pallas TPU kernels for the placement-policy hot loops (Algs. 6-7).

``mcc_score``: for a requested profile, the best post-assignment CC per
GPU (what Algorithm 6 maximizes).  ``ecc_score``: the expectation-weighted
variant of Algorithm 7 — needs the default policy's chosen (first-max)
start, then re-counts each profile's slots weighted by arrival
probabilities.

The requested profile index is a *compile-time* parameter (one kernel
specialization per (model, profile) — at most 6 profiles per model), so
every slot template is again a constant and the body is straight-line VPU
code.  Templates come from the :class:`repro.core.mig.DeviceModel` slot
enumeration.  Probabilities arrive as a (1, 128)-padded f32 row broadcast
to every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.mig import A100_40GB, DeviceModel

BLOCK_ROWS = 64
LANES = 128
# Tallest whole-array tile taken when no multiple of 8 divides the row
# count: the v5e compiler refuses a 4,004-row tile (VMEM overflow) and
# accepts a 2,004-row one.
MAX_TILE_ROWS = 1024


def _cc_of(m, slot_masks):
    cc = jnp.zeros_like(m)
    for sm in slot_masks:
        cc = cc + ((m & sm) == sm).astype(jnp.int32)
    return cc


def _mcc_kernel(model: DeviceModel, profile_idx: int, mask_ref, out_ref):
    m = mask_ref[...]
    best = jnp.full(m.shape, -1, jnp.int32)
    for sm in model.profile_slot_masks[profile_idx]:
        fits = (m & sm) == sm
        cc_after = _cc_of(m & ~sm, model.slot_masks)
        best = jnp.where(fits, jnp.maximum(best, cc_after), best)
    out_ref[...] = best


def _ecc_kernel(model: DeviceModel, profile_idx: int, mask_ref, probs_ref,
                out_ref):
    m = mask_ref[...]
    best_cc = jnp.full(m.shape, -1, jnp.int32)
    best_after = m
    for sm in model.profile_slot_masks[profile_idx]:
        fits = (m & sm) == sm
        after = m & ~sm
        cc_after = jnp.where(fits, _cc_of(after, model.slot_masks), -1)
        better = cc_after > best_cc          # first maximizer kept
        best_after = jnp.where(better, after, best_after)
        best_cc = jnp.maximum(best_cc, cc_after)
    ecc = jnp.zeros(m.shape, jnp.float32)
    for pi in range(model.num_profiles):
        count = jnp.zeros(m.shape, jnp.int32)
        for sm in model.profile_slot_masks[pi]:
            count = count + ((best_after & sm) == sm).astype(jnp.int32)
        ecc = ecc + probs_ref[0, pi] * count.astype(jnp.float32)
    out_ref[...] = jnp.where(best_cc >= 0, ecc, -1.0)


def _block_rows(rows: int) -> int:
    """Tile height for a (rows, 128) mask array that the TPU compiler
    accepts: the largest multiple of 8 <= BLOCK_ROWS dividing ``rows``,
    else the whole array as one tile."""
    for br in range(BLOCK_ROWS, 0, -8):
        if rows % br == 0:
            return br
    return rows


def kernel_fits(num_gpus: int) -> bool:
    """Whether the engine kernels compile for a fleet of ``num_gpus``:
    whole 128-lane rows, tiled by :func:`_block_rows` within VMEM."""
    return (num_gpus % LANES == 0
            and _block_rows(num_gpus // LANES) <= MAX_TILE_ROWS)


def mcc_score_pallas(masks2d: jax.Array, profile_idx: int, *,
                     model: DeviceModel = A100_40GB,
                     interpret: bool = False) -> jax.Array:
    rows, lanes = masks2d.shape
    assert lanes == LANES
    br = _block_rows(rows)
    grid = (rows // br,)
    return pl.pallas_call(
        functools.partial(_mcc_kernel, model, profile_idx),
        grid=grid,
        in_specs=[pl.BlockSpec((br, LANES), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(masks2d)


def ecc_score_pallas(masks2d: jax.Array, profile_idx: int,
                     probs_row: jax.Array, *,
                     model: DeviceModel = A100_40GB,
                     interpret: bool = False) -> jax.Array:
    """probs_row: (1, 128) f32, first num_profiles lanes = probabilities."""
    rows, lanes = masks2d.shape
    assert lanes == LANES
    assert probs_row.shape == (1, LANES)
    br = _block_rows(rows)
    grid = (rows // br,)
    return pl.pallas_call(
        functools.partial(_ecc_kernel, model, profile_idx),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, LANES), lambda r: (r, 0)),
            pl.BlockSpec((1, LANES), lambda r: (0, 0)),  # broadcast row
        ],
        out_specs=pl.BlockSpec((br, LANES), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(masks2d, probs_row)


# ---------------------------------------------------------------------------
# Engine entry points (repro.core.batched score_backend="pallas")
# ---------------------------------------------------------------------------
#
# Inside the replay scan the requested profile is a *traced* scalar while
# the kernels specialize per profile at compile time; the bridge is a
# ``lax.switch`` over the <= 6 per-profile kernel specializations.  The
# fleet's flat (G,) free-mask vector is viewed as (G/128, 128) — bucketed
# fleets (pad_events(min_gpus=128)) are always lane-aligned.

def engine_mcc_scores(free: jax.Array, profile, *,
                      model: DeviceModel = A100_40GB,
                      interpret: bool = False) -> jax.Array:
    """Per-GPU best post-assignment CC for a traced ``profile`` scalar;
    -1 where the profile does not fit (Alg. 6's maximization target)."""
    G = free.shape[0]
    masks2d = free.astype(jnp.int32).reshape(G // LANES, LANES)
    branches = [
        functools.partial(mcc_score_pallas, profile_idx=p, model=model,
                          interpret=interpret)
        for p in range(model.num_profiles)]
    out = jax.lax.switch(jnp.clip(profile, 0, model.num_profiles - 1),
                         branches, masks2d)
    return out.reshape(G)


def engine_ecc_scores(free: jax.Array, profile, probs_row: jax.Array, *,
                      model: DeviceModel = A100_40GB,
                      interpret: bool = False) -> jax.Array:
    """Per-GPU expectation-weighted capacity after the default-policy
    assignment of a traced ``profile``; -1.0 where infeasible (Alg. 7)."""
    G = free.shape[0]
    masks2d = free.astype(jnp.int32).reshape(G // LANES, LANES)
    branches = [
        (lambda m, pr, p=p: ecc_score_pallas(m, p, pr, model=model,
                                             interpret=interpret))
        for p in range(model.num_profiles)]
    out = jax.lax.switch(jnp.clip(profile, 0, model.num_profiles - 1),
                         branches, masks2d, probs_row)
    return out.reshape(G)


__all__ = ["mcc_score_pallas", "ecc_score_pallas", "engine_mcc_scores",
           "engine_ecc_scores", "kernel_fits", "BLOCK_ROWS", "LANES",
           "MAX_TILE_ROWS"]
