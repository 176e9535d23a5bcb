"""The GOSS threshold by an exact select (models/sample_strategy.py
`kth_largest`, PR 40) against the sort it replaced.

The select and `jnp.sort(mag)[n - k]` must keep the same rows: the MASK
`mag >= thresh` is compared bit for bit everywhere, and the threshold's own
bits wherever no subnormal is present (the sort's comparator flushes
subnormals to zero and returns one of several "equal" elements; the `>=`
flushes too, so the masks still agree).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.models import sample_strategy
from lightgbm_tpu.models.sample_strategy import GOSSStrategy, kth_largest


def _sorted_cut(mag, k):
    """The parent's formulation: the k-th largest through one device sort."""
    return jnp.sort(mag)[mag.shape[0] - k]


def _abs_normal(n, seed=0):
    return np.abs(np.random.RandomState(seed).randn(n)).astype(np.float32)


def _ties(n):
    # a few distinct values, so the cut falls inside a run of equal keys
    return (np.random.RandomState(1).randint(0, 6, n) / 4).astype(np.float32)


def _inf_nan(n):
    m = _abs_normal(n, 2)
    m[::97] = np.inf
    m[5::131] = np.nan
    return m


def _subnormal(n):
    tiny = np.finfo(np.float32).smallest_subnormal
    m = (np.random.RandomState(3).randint(0, 40, n) * tiny).astype(np.float32)
    m[::9] = _abs_normal(n, 4)[::9]
    return m


def _multiclass(n):
    # the sampler's 2-D form: per-class |grad * hess| summed to one magnitude
    rs = np.random.RandomState(5)
    g = rs.randn(n, 3).astype(np.float32)
    h = rs.rand(n, 3).astype(np.float32)
    return np.asarray(jnp.sum(jnp.abs(jnp.asarray(g) * jnp.asarray(h)),
                              axis=1))


CASES = {
    "ties_straddling": (_ties, 4096, 1638, False),
    "all_zeros": (lambda n: np.zeros(n, np.float32), 1000, 200, False),
    "one_row": (lambda n: np.array([0.75], np.float32), 1, 1, False),
    "k_one": (_abs_normal, 4096, 1, False),
    "k_n": (_abs_normal, 4096, 4096, False),
    "k_n_minus_one": (_abs_normal, 4096, 4095, False),
    # 32 NaNs over 43 infs: the cut on a NaN, on an inf, and below both
    "nan_at_the_cut": (_inf_nan, 4096, 20, False),
    "inf_at_the_cut": (_inf_nan, 4096, 40, False),
    "inf_and_nan_above_the_cut": (_inf_nan, 4096, 819, False),
    "subnormals": (_subnormal, 4096, 819, True),
    "length_not_a_multiple_of_128": (_abs_normal, 5003, 1000, False),
    "multiclass_2d_magnitudes": (_multiclass, 3000, 600, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_select_keeps_the_sorts_rows(case):
    make, n, k, subnormal = CASES[case]
    mag = jnp.asarray(make(n))
    got = jax.jit(kth_largest, static_argnums=1)(mag, k)
    want = _sorted_cut(mag, k)
    np.testing.assert_array_equal(np.asarray(mag >= got),
                                  np.asarray(mag >= want))
    if not subnormal:
        assert (np.asarray(got).view(np.uint32)
                == np.asarray(want).view(np.uint32)), (got, want)
    # the rule the select is built on, over the bit patterns on the host:
    # at least k keys at or above the answer, fewer than k above it
    keys, t = np.asarray(mag).view(np.uint32), np.asarray(got).view(np.uint32)
    assert (keys >= t).sum() >= k > (keys > t).sum()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sampler_equals_the_sort_formulation(monkeypatch, seed):
    """`GOSSStrategy.sample_traced` under the select gives the mask and the
    amplified gradients of the sort formulation, bit for bit (binary and
    multiclass gradients)."""
    n = 6007
    cfg = Config.from_params({"data_sample_strategy": "goss",
                              "top_rate": 0.2, "other_rate": 0.1,
                              "bagging_seed": seed})
    rs = np.random.RandomState(seed)
    for shape in ((n,), (n, 3)):
        g = jnp.asarray(rs.randn(*shape).astype(np.float32))
        h = jnp.asarray(rs.rand(*shape).astype(np.float32))
        strategy = GOSSStrategy(cfg, n)
        key = strategy.traced_key(11 + seed)
        got = strategy.sample_traced(key, g, h)
        with monkeypatch.context() as m:
            m.setattr(sample_strategy, "kth_largest", _sorted_cut)
            want = strategy.sample_traced(key, g, h)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                          np.asarray(b).view(np.uint32))
        assert 0 < float(jnp.sum(got[0])) < n
