"""HIGGS-shaped binary task: `features` standard-normal float32 columns and a
nonlinear logit over the first eight (bench.py `make_higgs_like`, same
formula).  Rows are drawn in fixed chunks, each from its own child of
SeedSequence([seed, stream]), so the data depend on the seed alone and never
on how many threads fill them."""
from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np

CHUNK = 1 << 20


def _fill(child, X, y):
    rng = np.random.default_rng(child)
    rng.standard_normal(out=X, dtype=np.float32)
    logit = (2.0 * X[:, 0] - 1.4 * X[:, 1] + 1.2 * X[:, 2] * X[:, 3]
             + 0.8 * np.sin(3 * X[:, 4]) + 0.7 * X[:, 5] * X[:, 5]
             - 0.6 * np.abs(X[:, 6]) + 0.5 * X[:, 7])
    p = 1.0 / (1.0 + np.exp(-1.2 * logit))
    y[:] = rng.random(len(y), dtype=np.float32) < p


def make(seed, rows, shape, stream=0):
    """-> {"X": float32 [rows, features], "y": float32 [rows]}"""
    f = int(shape["features"])
    X = np.empty((rows, f), np.float32)
    y = np.empty(rows, np.float32)
    starts = range(0, rows, CHUNK)
    children = np.random.SeedSequence([seed, stream]).spawn(len(starts))
    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(lambda a, c: _fill(c, X[a:a + CHUNK], y[a:a + CHUNK]),
                      starts, children))
    return {"X": X, "y": y}
