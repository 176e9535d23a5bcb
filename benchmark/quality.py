"""Quality gates in plain NumPy (copied from bench.py `auc_score` and
`ndcg_at_k`; tests/test_reference.py holds the copies to the originals)."""
import numpy as np


def auc(y, p):
    y = np.asarray(y, np.float64)
    order = np.argsort(p)
    r = np.empty(len(p), np.float64)
    r[order] = np.arange(len(p))
    npos = y.sum()
    nneg = len(y) - npos
    return float((r[y > 0.5].sum() - npos * (npos - 1) / 2) / (npos * nneg))


def ndcg_at_k(y, score, sizes, k=10):
    out = []
    start = 0
    gains = 2.0 ** np.asarray(y, np.float64) - 1.0
    for s in sizes:
        seg_g = gains[start:start + s]
        seg_s = score[start:start + s]
        if seg_g.max() > 0:
            order = np.argsort(-seg_s)[:k]
            disc = 1.0 / np.log2(np.arange(2, 2 + len(order)))
            dcg = float(np.sum(seg_g[order] * disc))
            ideal = np.sort(seg_g)[::-1][:k]
            idcg = float(np.sum(ideal * disc[:len(ideal)]))
            out.append(dcg / idcg)
        start += s
    return float(np.mean(out))


def evaluate(metric, y, score, sizes=None):
    """The gate metric a configuration's file names: "auc" or "ndcg_at_10"."""
    if metric == "auc":
        return auc(y, score)
    if metric == "ndcg_at_10":
        return ndcg_at_k(y, score, sizes, 10)
    raise ValueError(f"unknown quality metric {metric!r}")
