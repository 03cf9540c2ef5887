"""Pallas kernels vs pure-jnp oracles vs the object-level ground truth.

Kernels run in interpret mode on CPU (TPU is the deployment target); the
oracle (ref.py) is additionally validated against repro.core.mig /
repro.core.tables, closing the loop kernel -> oracle -> object model.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.core import tables as T
from repro.core.mig import PROFILES
from repro.kernels import ref
from repro.kernels.ops import cc_scores, ecc_scores, frag_scores, mcc_scores

ALL_MASKS = np.arange(256, dtype=np.int32)


# ---------------------------------------------------------------------------
# Oracle vs object-level ground truth (exhaustive over all 256 masks)
# ---------------------------------------------------------------------------

def test_ref_cc_matches_tables():
    got = np.asarray(ref.cc_ref(jnp.asarray(ALL_MASKS)))
    np.testing.assert_array_equal(got, T.CC_TABLE)


def test_ref_frag_matches_tables():
    got = np.asarray(ref.frag_ref(jnp.asarray(ALL_MASKS)))
    np.testing.assert_allclose(got, T.FRAG_TABLE, rtol=0, atol=0)


@pytest.mark.parametrize("pi", range(6))
def test_ref_mcc_matches_tables(pi):
    got = np.asarray(ref.mcc_score_ref(jnp.asarray(ALL_MASKS), pi))
    np.testing.assert_array_equal(got, T.CC_AFTER_TABLE[:, pi])


@pytest.mark.parametrize("pi", range(6))
def test_ref_ecc_matches_tables(pi):
    probs = np.array([0.3, 0.1, 0.25, 0.15, 0.05, 0.15], np.float32)
    got = np.asarray(ref.ecc_score_ref(jnp.asarray(ALL_MASKS), pi,
                                       jnp.asarray(probs)))
    want = np.where(T.FITS_TABLE[:, pi],
                    T.COUNTS_AFTER_TABLE[:, pi] @ probs, -1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode) vs oracle
# ---------------------------------------------------------------------------

def test_kernel_cc_exhaustive():
    masks = jnp.asarray(ALL_MASKS)
    np.testing.assert_array_equal(np.asarray(cc_scores(masks)),
                                  np.asarray(ref.cc_ref(masks)))


def test_kernel_frag_exhaustive():
    masks = jnp.asarray(ALL_MASKS)
    np.testing.assert_allclose(np.asarray(frag_scores(masks)),
                               np.asarray(ref.frag_ref(masks)))


@pytest.mark.parametrize("pi", range(6))
def test_kernel_mcc_exhaustive(pi):
    masks = jnp.asarray(ALL_MASKS)
    np.testing.assert_array_equal(
        np.asarray(mcc_scores(masks, pi)),
        np.asarray(ref.mcc_score_ref(masks, pi)))


@pytest.mark.parametrize("pi", [0, 3, 5])
def test_kernel_ecc_exhaustive(pi):
    probs = jnp.asarray(np.array([0.42, 0.06, 0.16, 0.11, 0.06, 0.19],
                                 np.float32))
    masks = jnp.asarray(ALL_MASKS)
    np.testing.assert_allclose(
        np.asarray(ecc_scores(masks, pi, probs)),
        np.asarray(ref.ecc_score_ref(masks, pi, probs)), rtol=1e-6)


# ---------------------------------------------------------------------------
# Shape/dtype sweeps (ragged sizes exercise padding; dtypes exercise casts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 127, 128, 129, 8192, 8193, 20000])
@pytest.mark.parametrize("dtype", [np.uint8, np.int32])
def test_kernel_cc_shapes(n, dtype):
    rng = np.random.default_rng(n)
    masks = rng.integers(0, 256, size=n).astype(dtype)
    got = np.asarray(cc_scores(jnp.asarray(masks)))
    assert got.shape == (n,)
    np.testing.assert_array_equal(got, T.CC_TABLE[masks.astype(np.int64)])


@pytest.mark.parametrize("n", [5, 300, 9000])
def test_kernel_frag_shapes(n):
    rng = np.random.default_rng(n)
    masks = rng.integers(0, 256, size=n).astype(np.int32)
    got = np.asarray(frag_scores(jnp.asarray(masks)))
    np.testing.assert_allclose(got, T.FRAG_TABLE[masks])


@given(st.lists(st.integers(0, 255), min_size=1, max_size=600),
       st.integers(0, 5))
@settings(max_examples=20, deadline=None)
def test_kernel_mcc_property(mask_list, pi):
    masks = np.array(mask_list, np.int32)
    got = np.asarray(mcc_scores(jnp.asarray(masks), pi))
    np.testing.assert_array_equal(got, T.CC_AFTER_TABLE[masks, pi])


# ---------------------------------------------------------------------------
# Engine-kernel tiling: what the TPU compiler accepts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,want", [
    (100, 100),      # no multiple of 8 divides 100: one whole-array tile
    (1000, 40),      # largest multiple of 8 <= 64 dividing 1000
    *[(1 << k, min(1 << k, 64)) for k in range(12)],   # pow2 fleets
])
def test_block_rows_is_a_multiple_of_8_or_the_whole_array(rows, want):
    from repro.kernels.policy_score import BLOCK_ROWS, _block_rows
    br = _block_rows(rows)
    assert br == want
    assert rows % br == 0 and br <= max(rows, BLOCK_ROWS)
    assert br % 8 == 0 or br == rows


@pytest.mark.parametrize("num_gpus,fits", [
    (2048, True), (12800, True), (128 * 1000, True),
    (128 * 1004, True),            # a 1,004-row whole tile
    (128 * 4004, False),           # a 4,004-row tile overflows VMEM
    (2000, False),                 # not whole 128-lane rows
])
def test_kernel_fits_agrees_with_tiling(num_gpus, fits):
    from repro.kernels.policy_score import kernel_fits
    assert kernel_fits(num_gpus) == fits
