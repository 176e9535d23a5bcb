"""Criteo-click-log-shaped binary task: the 67 dense float32 columns of the
table LightGBM's Parallel Experiment trains on (docs/Experiments.rst: the
Criteo logs with their 26 categorical fields replaced by click-through rate
and count statistics), in the source's three kinds -

    13 integer counts   heavy-tailed, non-negative, a share of zeros and NaN
    26 rates            the categories' click-through rates, in [0, 1]
    26 counts           the categories' occurrence counts, heavy-tailed
     2 further dense columns

and a click label of about 3% positives from a FIXED label function (the
constants below; it knows nothing of the program).  Rows are drawn in fixed
chunks, each from its own child of SeedSequence([seed, stream]), so the data
depend on the seed alone and never on how many threads fill them.

Every seed is the same amount of work: each column's distribution is fixed
here and continuous or long-tailed enough that the binner's quantiles fill
all of `max_bin` bins whatever the draw, so the bin counts, the bucketed
M-axis and the group count do not move with the seed."""
from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np

CHUNK = 1 << 18
N_INT, N_CAT = 13, 26
# fixed per-column constants (the table's, not the seed's)
_K = np.random.default_rng(20240607)
INT_SCALE = _K.uniform(1.0, 3.5, N_INT).astype(np.float32)   # log-scale mean
INT_SIGMA = _K.uniform(1.2, 2.0, N_INT).astype(np.float32)
INT_ZERO = _K.uniform(0.05, 0.35, N_INT).astype(np.float32)  # share of zeros
INT_NAN = _K.uniform(0.0, 0.25, N_INT).astype(np.float32)    # share missing
RATE_POW = np.sort(_K.integers(1, 4, N_CAT))                 # u, u^2 or u^3
POW2, POW3 = (int(np.searchsorted(RATE_POW, k)) for k in (2, 3))
CNT_SCALE = _K.uniform(3.0, 9.0, N_CAT).astype(np.float32)
CNT_SIGMA = _K.uniform(1.0, 2.2, N_CAT).astype(np.float32)
W_INT = _K.normal(0.0, 0.35, N_INT).astype(np.float32)
W_RATE = _K.normal(0.0, 1.6, N_CAT).astype(np.float32)
W_CNT = _K.normal(0.0, 0.08, N_CAT).astype(np.float32)
BIAS = -6.1                                                  # about 3% clicks


def _logistic(rng, shape):
    """Standard logistic draws, float32: log(u / (1 - u)).  Heavier-tailed
    than a normal and a third of its cost (no ziggurat)."""
    u = rng.random(shape, dtype=np.float32)
    np.clip(u, 1e-7, 1.0 - 1e-7, out=u)
    lat = np.log(u)
    np.subtract(1.0, u, out=u)
    np.log(u, out=u)
    lat -= u
    return lat


def _fill(child, X, y):
    rng = np.random.default_rng(child)
    n = len(y)
    # the integer counts: floor of a log-logistic, a share of zeros, a share
    # missing; the label reads their logarithm (the latent, 0 for a zero)
    lat = _logistic(rng, (n, N_INT))
    lat *= 0.55 * INT_SIGMA
    lat += INT_SCALE
    u = rng.random((n, N_INT), dtype=np.float32)
    zero = u < INT_ZERO
    np.minimum(lat, 16.0, out=lat)              # counts under 1e7
    ints = np.floor(np.exp(lat))
    np.putmask(ints, zero, 0.0)
    np.putmask(lat, zero, 0.0)
    logit = lat @ W_INT
    np.putmask(ints, u > 1.0 - INT_NAN, np.nan)  # the logs' missing counts
    X[:, :N_INT] = ints
    # rates: u^k is Beta(1/k, 1) - mass near 0 for k > 1, as CTRs have
    rate = rng.random((n, N_CAT), dtype=np.float32)
    base = rate.copy()
    rate[:, POW2:] *= base[:, POW2:]
    rate[:, POW3:] *= base[:, POW3:]
    X[:, N_INT:N_INT + N_CAT] = rate
    lat = _logistic(rng, (n, N_CAT))
    lat *= 0.55 * CNT_SIGMA
    logit += lat @ W_CNT
    lat += CNT_SCALE
    np.minimum(lat, 20.0, out=lat)              # counts under 5e8
    X[:, N_INT + N_CAT:N_INT + 2 * N_CAT] = np.floor(np.exp(lat))
    extra = rng.standard_normal((n, X.shape[1] - N_INT - 2 * N_CAT),
                                dtype=np.float32)
    X[:, N_INT + 2 * N_CAT:] = extra
    logit += (rate - 0.5) @ W_RATE
    logit += 1.5 * rate[:, 0] * rate[:, 1] + 0.4 * extra[:, 0] \
        - 0.3 * np.abs(extra[:, -1])
    p = 1.0 / (1.0 + np.exp(-(BIAS + logit)))
    y[:] = rng.random(n, dtype=np.float32) < p


def make(seed, rows, shape, stream=0):
    """-> {"X": float32 [rows, features], "y": float32 [rows]}"""
    f = int(shape["features"])
    if f < N_INT + 2 * N_CAT + 1:
        raise ValueError(f"criteo_like needs at least "
                         f"{N_INT + 2 * N_CAT + 1} features, got {f}")
    X = np.empty((rows, f), np.float32)
    y = np.empty(rows, np.float32)
    starts = range(0, rows, CHUNK)
    children = np.random.SeedSequence([seed, stream]).spawn(len(starts))
    with ThreadPoolExecutor(min(32, os.cpu_count() or 1)) as pool:
        list(pool.map(lambda a, c: _fill(c, X[a:a + CHUNK], y[a:a + CHUNK]),
                      starts, children))
    return {"X": X, "y": y}
