"""Pallas histogram kernel correctness (interpret mode on CPU) vs the segsum oracle.

Reference analog of what is being validated: dense_bin.hpp ConstructHistogramInner
semantics — per-slot (grad, hess, count) sums over bins, with invalid rows skipped."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import _hist_segsum, build_histograms
from lightgbm_tpu.pallas import hist_kernel as hk


def _mk(n, g, s, b, seed=0, frac_invalid=0.3):
    rs = np.random.RandomState(seed)
    bins = jnp.asarray(rs.randint(0, b, size=(n, g)), jnp.uint8)
    slot = rs.randint(0, s, size=n)
    slot[rs.rand(n) < frac_invalid] = -1
    slot = jnp.asarray(slot, jnp.int32)
    grad = jnp.asarray(rs.randn(n), jnp.float32)
    hess = jnp.asarray(rs.rand(n), jnp.float32)
    cnt = jnp.asarray((rs.rand(n) > 0.2), jnp.float32)
    return bins, slot, grad, hess, cnt


@pytest.mark.parametrize("bmax", [64, 100, 128])
def test_direct_kernel_matches_segsum(bmax):
    n, g, s = 3000, 5, 4
    bins, slot, grad, hess, cnt = _mk(n, g, s, bmax)
    ref = _hist_segsum(bins, slot, grad, hess, cnt, s, bmax)
    got = hk.build_histograms_sorted(bins, slot, grad, hess, cnt, s, bmax,
                                     block_rows=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("bmax", [200, 256])
def test_nibble_kernel_matches_segsum(bmax):
    n, g, s = 3000, 3, 4
    bins, slot, grad, hess, cnt = _mk(n, g, s, bmax)
    ref = _hist_segsum(bins, slot, grad, hess, cnt, s, bmax)
    got = hk.build_histograms_sorted(bins, slot, grad, hess, cnt, s, bmax,
                                     block_rows=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


def test_single_slot_root_plan():
    n, g, bmax = 2500, 4, 64
    bins, _, grad, hess, cnt = _mk(n, g, 1, bmax)
    slot = jnp.zeros(n, jnp.int32)
    ref = _hist_segsum(bins, slot, grad, hess, cnt, 1, bmax)
    got = hk.build_histograms_sorted(bins, slot, grad, hess, cnt, 1, bmax,
                                     block_rows=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


def test_empty_slots_are_zero():
    n, g, s, bmax = 1000, 3, 6, 64
    bins, _, grad, hess, cnt = _mk(n, g, s, bmax)
    # only slots 1 and 4 populated
    rs = np.random.RandomState(3)
    slot = jnp.asarray(rs.choice([-1, 1, 4], size=n), jnp.int32)
    got = hk.build_histograms_sorted(bins, slot, grad, hess, cnt, s, bmax,
                                     block_rows=256)
    got = np.asarray(got)
    for empty in (0, 2, 3, 5):
        assert np.all(got[empty] == 0.0)
    ref = _hist_segsum(bins, slot, grad, hess, cnt, s, bmax)
    np.testing.assert_allclose(got, np.asarray(ref), atol=1e-4)


def test_pallas_backend_reachable_via_build_histograms():
    n, g, s, bmax = 1200, 4, 3, 64
    bins, slot, grad, hess, cnt = _mk(n, g, s, bmax)
    ref = build_histograms(bins, slot, grad, hess, cnt, s, bmax, backend="segsum")
    got = build_histograms(bins, slot, grad, hess, cnt, s, bmax, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)
