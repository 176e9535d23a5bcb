"""A plain NumPy reference for the histogram passes and the split scan of
a boosted tree's growth: float64 / int64, `np.add.at` over every feature, no
kernels, nothing of the program.  tests/test_wide_stream.py holds the
program's tiled stream kernel to it on a table of several M-tiles.

bins: (N, F) integer bin of every row in every feature.  leaf: (N,) the
leaf each row sits in (negative: in none, the row counts nowhere)."""
import numpy as np


def histograms(bins, leaf, grad, hess, n_leaves, n_bins):
    """(n_leaves, F, n_bins, 3): per leaf, feature and bin the sums of
    grad and hess and the count of rows.  int64 where grad and hess are
    integers (exact), float64 otherwise."""
    bins = np.asarray(bins)
    leaf = np.asarray(leaf)
    exact = (np.issubdtype(np.asarray(grad).dtype, np.integer)
             and np.issubdtype(np.asarray(hess).dtype, np.integer))
    dtype = np.int64 if exact else np.float64
    n, f = bins.shape
    out = np.zeros((n_leaves, f, n_bins, 3), dtype)
    rows = np.nonzero(leaf >= 0)[0]
    weights = (np.asarray(grad, dtype)[rows], np.asarray(hess, dtype)[rows],
               np.ones(len(rows), dtype))
    for j in range(f):
        at = (leaf[rows], bins[rows, j])
        for c, w in enumerate(weights):
            np.add.at(out[:, j, :, c], at, w)
    return out


def route(bins, leaf, splits):
    """Rows of a split leaf whose bin in the split's feature is above its
    threshold bin move to the split's new leaf; every other row stays.
    splits: {leaf: (feature, threshold_bin, new_leaf)}."""
    bins = np.asarray(bins)
    out = np.array(leaf, np.int64)
    for at, (feature, threshold_bin, new_leaf) in splits.items():
        right = (np.asarray(leaf) == at) & (bins[:, feature] > threshold_bin)
        out[right] = new_leaf
    return out


def leaf_gain(g, h, lambda_l2):
    return g * g / (h + lambda_l2)


def best_split(hist, lambda_l2=0.0, min_data_in_leaf=20,
               min_sum_hessian_in_leaf=1e-3, last_bin=None):
    """LightGBM's numerical split of one leaf from its (F, n_bins, 3)
    histogram: over every feature and threshold bin t (rows with bin <= t
    go left), the gain GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2) where both
    children keep `min_data_in_leaf` rows and `min_sum_hessian_in_leaf`.
    last_bin[f]: the last bin of feature f that holds data (default: the
    histogram's last); a threshold at or past it splits nothing.
    -> (feature, threshold_bin, gain), the first of the best in (feature,
    bin) order, or None where nothing may split."""
    hist = np.asarray(hist, np.float64)
    f, b, _ = hist.shape
    left = np.cumsum(hist, axis=1)
    total = left[:, -1:, :]
    right = total - left
    ok = ((left[..., 2] >= min_data_in_leaf)
          & (right[..., 2] >= min_data_in_leaf)
          & (left[..., 1] >= min_sum_hessian_in_leaf)
          & (right[..., 1] >= min_sum_hessian_in_leaf))
    last = np.full(f, b - 1) if last_bin is None else np.asarray(last_bin)
    ok &= np.arange(b)[None, :] < last[:, None]
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = (leaf_gain(left[..., 0], left[..., 1], lambda_l2)
                + leaf_gain(right[..., 0], right[..., 1], lambda_l2)
                - leaf_gain(total[..., 0], total[..., 1], lambda_l2))
    gain = np.where(ok, gain, -np.inf)
    at = int(np.argmax(gain))
    if not np.isfinite(gain.flat[at]):
        return None
    return at // b, at % b, float(gain.flat[at])
