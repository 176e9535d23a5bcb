"""An independent NumPy walk of a dumped model (`Booster.dump_model()`), and
the tree invariants read from the same dump.  Uses nothing of the program:
numeric `value <= threshold` splits with LightGBM's missing handling."""
import numpy as np


def flatten(structure):
    """tree_structure (nested dicts) -> arrays over internal nodes, children
    as node index >= 0 or ~leaf < 0, plus leaf values and leaf counts."""
    feat, thr, dleft, mtype, left, right = [], [], [], [], [], []
    leaf_value, leaf_count = {}, {}

    def leaf(node):
        i = int(node.get("leaf_index", 0))
        leaf_value[i] = float(node["leaf_value"])
        leaf_count[i] = int(node.get("leaf_count", 0))
        return ~i

    if "split_feature" not in structure:
        leaf(structure)
    else:
        stack = [(structure, None, None)]
        while stack:
            node, parent, side = stack.pop()
            if node["decision_type"] != "<=":
                raise ValueError("reference walk: numeric splits only")
            me = len(feat)
            feat.append(int(node["split_feature"]))
            thr.append(float(node["threshold"]))
            dleft.append(bool(node["default_left"]))
            mtype.append({"None": 0, "Zero": 1, "NaN": 2}[node["missing_type"]])
            left.append(0)
            right.append(0)
            if parent is not None:
                side[parent] = me
            for child, arr in ((node["left_child"], left),
                               (node["right_child"], right)):
                if "split_feature" in child:
                    stack.append((child, me, arr))
                else:
                    arr[me] = leaf(child)
    n_leaf = max(leaf_value) + 1
    return {
        "feature": np.asarray(feat, np.int64),
        "threshold": np.asarray(thr, np.float64),
        "default_left": np.asarray(dleft, bool),
        "missing_type": np.asarray(mtype, np.int64),
        "left": np.asarray(left, np.int64),
        "right": np.asarray(right, np.int64),
        "leaf_value": np.asarray([leaf_value[i] for i in range(n_leaf)]),
        "leaf_count": np.asarray([leaf_count[i] for i in range(n_leaf)]),
    }


def walk_tree(t, X):
    n = X.shape[0]
    if len(t["feature"]) == 0:
        return np.full(n, t["leaf_value"][0])
    node = np.zeros(n, np.int64)
    rows = np.arange(n)
    while True:
        live = node >= 0
        if not live.any():
            break
        idx = node[live]
        v = X[rows[live], t["feature"][idx]].astype(np.float64)
        mt = t["missing_type"][idx]
        nan = np.isnan(v)
        miss = np.where(mt == 2, nan, (mt == 1) & (nan | (np.abs(v) < 1e-35)))
        v = np.where(nan & (mt != 2), 0.0, v)
        go_left = np.where(miss, t["default_left"][idx],
                           v <= t["threshold"][idx])
        node[live] = np.where(go_left, t["left"][idx], t["right"][idx])
    return t["leaf_value"][~node]


def walk(dump, X, num_trees=None):
    """Raw score of the first `num_trees` trees (all by default), float64."""
    trees = dump["tree_info"][:num_trees]
    out = np.zeros(X.shape[0], np.float64)
    for info in trees:
        out += walk_tree(flatten(info["tree_structure"]), X)
    return out


def tree_faults(dump, n_rows, num_leaves, first=0, count_slack=0):
    """[(tree, leaves, leaf-count sum - n_rows), ...] for the trees (from
    `first` on) that miss the invariants: every tree reaches `num_leaves`
    leaves and its leaf counts sum to `n_rows`, to within `count_slack` rows
    (0 wherever the program counts exactly); and the largest miss of the
    count sum over all trees looked at."""
    bad, worst = [], 0
    for i, info in enumerate(dump["tree_info"]):
        if i < first:
            continue
        t = flatten(info["tree_structure"])
        off = int(t["leaf_count"].sum()) - n_rows
        worst = max(worst, abs(off))
        if (info["num_leaves"] != num_leaves
                or len(t["leaf_count"]) != num_leaves
                or abs(off) > count_slack):
            bad.append((i, len(t["leaf_count"]), off))
    return bad, worst
