"""The plain reference of a row-sharded training job: one split of the grown
model recomputed over the WHOLE training table, in float64 NumPy, with
nothing of the program (no JAX).

For tree 0 of a binary-objective model boosted from the label average, the
first iteration's gradients are closed-form in the labels: with p the label
mean, g = p - y and h = p (1 - p) for every row.  Given the root's
(split_feature, threshold, default direction) as `dump_model()` prints them,
the rows that go left are decided as reference_walk.py decides them, and

    left_count = |L|
    gain = G_L^2 / H_L + G_R^2 / H_R - G^2 / H

over ALL rows.  A learner that summed the histograms of three shards of four
counts about a quarter fewer rows to the left and reads a gain about a
quarter low; one whose leaf counts round (float32 past 2^24 rows) misses the
count by a few rows.  The count is held to equality; the gain to a relative
tolerance the configuration states (the program's gradients are quantised
to a few integer levels with stochastic rounding, so its gain is the
reference's only to that noise)."""
import numpy as np

MISSING = {"None": 0, "Zero": 1, "NaN": 2}


def root_of(dump, tree=0):
    """The root split of tree `tree` as dumped."""
    node = dump["tree_info"][tree]["tree_structure"]
    if "split_feature" not in node:
        raise ValueError(f"tree {tree} has no split")
    if node["decision_type"] != "<=":
        raise ValueError("reference split: numeric splits only")
    left = node["left_child"]
    return {"feature": int(node["split_feature"]),
            "threshold": float(node["threshold"]),
            "default_left": bool(node["default_left"]),
            "missing_type": MISSING[node["missing_type"]],
            "gain": float(node["split_gain"]),
            "count": int(node["internal_count"]),
            "left_count": int(left.get("internal_count",
                                       left.get("leaf_count", 0)))}


def goes_left(column, root):
    """bool per row: LightGBM's numeric decision with its missing handling
    (reference_walk.walk_tree's, for one node)."""
    v = np.asarray(column, np.float64)
    nan = np.isnan(v)
    mt = root["missing_type"]
    if mt == 2:
        miss = nan
    elif mt == 1:
        miss = nan | (np.abs(v) < 1e-35)
    else:
        miss = np.zeros(len(v), bool)
    v = np.where(nan & (mt != 2), 0.0, v)
    return np.where(miss, root["default_left"], v <= root["threshold"])


def first_gradients(y):
    """(g, h) float64 of the binary objective's first iteration, boosted
    from the label average."""
    y = np.asarray(y, np.float64)
    p = y.mean()
    return p - y, np.full(len(y), p * (1.0 - p))


def split_stats(column, y, root, rows=None):
    """(left count, gain) of `root` over the table's rows (`rows`: a mask
    or slice of them, for the control that loses a shard)."""
    g, h = first_gradients(y)
    left = goes_left(column, root)
    if rows is not None:
        g, h, left = g[rows], h[rows], left[rows]
    gl, hl = g[left].sum(), h[left].sum()
    gt, ht = g.sum(), h.sum()
    gr, hr = gt - gl, ht - hl
    gain = gl * gl / hl + gr * gr / hr - gt * gt / ht
    return int(left.sum()), float(gain)


def check(dump, X, y, gain_rtol, rows=None):
    """The two checks on tree 0's root, and a line saying what was read."""
    root = root_of(dump)
    count, gain = split_stats(X[:, root["feature"]], y, root, rows=rows)
    gap = abs(root["gain"] - gain) / abs(gain)
    return {
        "root_left_count_is_the_whole_tables": root["left_count"] == count,
        "root_gain_is_the_whole_tables": bool(gap <= gain_rtol),
        "said": (f"feature {root['feature']} <= {root['threshold']:.6g}, "
                 f"left count {root['left_count']} (reference {count}), "
                 f"gain {root['gain']:.6g} (reference {gain:.6g}, relative "
                 f"gap {gap:.3g}, limit {gain_rtol})")}
