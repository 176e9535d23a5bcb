"""Epsilon-shaped binary task (PASCAL large-scale challenge, the second row
of LightGBM's docs/GPU-Performance.rst): `features` dense float32 columns,
every row scaled to unit length as Epsilon's are, and a label whose logit is

  - a linear signal over ALL the columns, weak in each: weights of either
    sign that decay slowly with the column (from 1 to 1/2), as Epsilon's own
    signal is spread.  LINEAR, the standard deviation of this part of the
    logit, is set by the source and by nothing else: at 4.0 the best
    possible AUC of the label (ranking by the logit itself) is 0.9499, the
    0.949876 that GPU-Performance.rst publishes for Epsilon at 63 bins;
  - a few products of the signs of two columns, which no linear model sees.

Nothing in it knows how the program cuts the table: no column is marked out
and the trees are as unbalanced as such a signal leaves them.

The label function is fixed, as higgs_like's formula is: its weights come
from SIGNAL_KEY, not from the seed.  Rows are drawn in fixed chunks, each
from its own child of SeedSequence([seed, stream]), so the data depend on
the seed alone and never on how many threads fill them."""
from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np

CHUNK = 1 << 14          # x 2,000 columns x 4 B = 131 MB a thread
SIGNAL_KEY = 29          # the label function's own stream
LINEAR = 4.0             # logit's standard deviation from the linear part
PRODUCTS = 3             # pairs of columns whose signs' product counts
PRODUCT = 0.5


def signal(f):
    """(linear weights (f,) of unit length, PRODUCTS column pairs)."""
    rng = np.random.default_rng([SIGNAL_KEY, f])
    w = rng.choice([-1.0, 1.0], f) / (1.0 + np.arange(f) / f)
    pairs = rng.choice(f, (PRODUCTS, 2), replace=False)
    return (w / np.sqrt((w * w).sum())).astype(np.float32), pairs


def _fill(child, X, y, w, pairs):
    rng = np.random.default_rng(child)
    rng.standard_normal(out=X, dtype=np.float32)
    X /= np.sqrt(np.einsum("ij,ij->i", X, X))[:, None]
    z = np.sqrt(np.float32(X.shape[1]))       # a column back at unit scale
    logit = LINEAR * z * (X @ w)
    for a, b in pairs:
        logit += PRODUCT * np.sign(X[:, a]) * np.sign(X[:, b])
    p = 1.0 / (1.0 + np.exp(-logit))
    y[:] = rng.random(len(y), dtype=np.float32) < p


def make(seed, rows, shape, stream=0):
    """-> {"X": float32 [rows, features], "y": float32 [rows]}"""
    f = int(shape["features"])
    X = np.empty((rows, f), np.float32)
    y = np.empty(rows, np.float32)
    w, pairs = signal(f)
    starts = range(0, rows, CHUNK)
    children = np.random.SeedSequence([seed, stream]).spawn(len(starts))
    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(lambda a, c: _fill(c, X[a:a + CHUNK], y[a:a + CHUNK],
                                         w, pairs), starts, children))
    return {"X": X, "y": y}
