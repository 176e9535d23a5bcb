"""MSLR-WEB30K-shaped ranking task (bench.py `make_mslr_like`, same feature
structure): 5 text streams (body, anchor, title, url, whole document) x
retrieval statistics plus 11 query-independent web/click features — small
integer counts, anchor/url streams empty for many documents, zero-inflated
heavy-tailed click/link features — `docs_per_query` documents a query, grades
0-4 by rank inside the query.  Whole queries are drawn in fixed chunks, each
from its own child of SeedSequence([seed, stream]); the last query takes the
remainder, as in bench.py."""
from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np

QUERIES_PER_CHUNK = 1024
STREAMS = ("body", "anchor", "title", "url", "whole")


def _fill(child, X, y, dq, scales):
    rng = np.random.default_rng(child)
    n, f = X.shape
    X[:] = 0.0
    qlen = rng.integers(1, 6, n).astype(np.float32)
    presence = {
        "body": np.ones(n, bool),
        "anchor": rng.random(n) < 0.35,
        "title": rng.random(n) < 0.95,
        "url": rng.random(n) < 0.60,
        "whole": np.ones(n, bool),
    }
    lengths = {
        "body": np.maximum(rng.lognormal(6.0, 0.8, n), 30),
        "anchor": rng.poisson(6, n) + 1.0,
        "title": rng.integers(3, 13, n).astype(np.float64),
        "url": rng.integers(5, 21, n).astype(np.float64),
        "whole": np.maximum(rng.lognormal(6.1, 0.8, n), 35),
    }
    quality = rng.standard_normal(n)
    col = 0
    bm25 = {}
    for s in STREAMS:
        p = presence[s]
        ln = lengths[s]
        cov = np.minimum(rng.binomial(5, 0.55, n), qlen)
        tf_sum = rng.poisson(np.where(p, 2 + 0.02 * np.minimum(ln, 200), 0))
        idf = np.round(rng.gamma(4.0, 1.5, n), 2)
        bm = np.maximum(2.0 * quality + 0.4 * cov + rng.standard_normal(n),
                        0) * p
        bm25[s] = bm
        tf_max = np.minimum(tf_sum, rng.poisson(2, n) + 1)
        lmir = np.round(-rng.gamma(3.0, 1.0, n), 3) * p
        feats = [
            cov * p,                         # covered query term number (int)
            np.round(cov / qlen, 2) * p,     # covered query term ratio
            np.round(ln) * p,                # stream length (int)
            np.round(idf, 1) * p,            # IDF sum
            tf_sum * p,                      # sum of term frequency (int)
            tf_max * p,                      # max of term frequency (int)
            np.round(tf_sum / np.maximum(ln, 1), 4) * p,   # normalized tf
            np.round(bm, 3),                 # BM25
            lmir,                            # LMIR.ABS
            np.round(lmir * rng.uniform(0.8, 1.2, n), 3),  # LMIR.DIR
        ]
        for v in feats[:f - col]:
            X[:, col] = v
            col += 1
    # remaining retrieval statistics: tf-idf style scores driven by quality,
    # zeroed with the matching stream's presence; one scale a column, the
    # same in every chunk
    while col < f - 11:
        X[:, col] = (np.maximum(quality * scales[col]
                                + rng.standard_normal(n), 0)
                     * presence[STREAMS[col % 5]])
        col += 1
    web = [
        np.round(rng.pareto(2.5, n) * 40),                   # inlink number
        np.round(rng.pareto(2.5, n) * 15),                   # outlink number
        rng.integers(30, 130, n).astype(np.float64),         # url length
        rng.integers(1, 9, n).astype(np.float64),            # url slash count
        np.minimum(rng.poisson(0.8, n), 255),                # url click count
        np.where(rng.random(n) < 0.85, 0, rng.poisson(3, n)),  # query-url clicks
        np.where(rng.random(n) < 0.8, 0,                     # url dwell time
                 np.round(rng.gamma(2, 20, n))),
        np.round(np.maximum(quality + rng.standard_normal(n) * 0.7, 0) * 30),
        rng.integers(0, 256, n).astype(np.float64),          # QualityScore
        rng.integers(0, 256, n).astype(np.float64),          # QualityScore2
        np.round(rng.pareto(3.0, n) * 10),                   # SiteRank
    ]
    for v in web[:f - col]:
        X[:, col] = v
        col += 1
    rel = (0.9 * bm25["body"] + 0.5 * bm25["title"] + 0.3 * bm25["anchor"]
           + 0.015 * web[7] + 0.25 * np.minimum(web[5], 4)
           + 1.8 * rng.standard_normal(n))
    # grades by rank inside the query; the chunk's last query holds whatever
    # rows are beyond a whole number of queries
    whole = (n // dq - 1) * dq if n % dq else n
    for seg, ys in ((rel[:whole].reshape(-1, dq), y[:whole].reshape(-1, dq)),
                    (rel[whole:].reshape(1, -1), y[whole:].reshape(1, -1))):
        if seg.size == 0:
            continue
        ranks = np.argsort(np.argsort(seg, axis=1), axis=1)
        frac = ranks / max(seg.shape[1] - 1, 1)
        ys[:] = np.select([frac >= 0.98, frac >= 0.92, frac >= 0.80,
                           frac >= 0.55], [4, 3, 2, 1], default=0)


def make(seed, rows, shape, stream=0):
    """-> {"X": float32 [rows, features], "y": float32 [rows],
    "sizes": int64 [queries]} with sizes.sum() == rows."""
    f = int(shape["features"])
    dq = int(shape["docs_per_query"])
    nq = max(1, rows // dq)
    sizes = np.full(nq, dq, np.int64)
    sizes[-1] += rows - sizes.sum()
    X = np.empty((rows, f), np.float32)
    y = np.empty(rows, np.float32)
    step = QUERIES_PER_CHUNK * dq
    starts = list(range(0, nq * dq, step))
    ends = starts[1:] + [rows]
    root = np.random.SeedSequence([seed, stream])
    scales = np.random.default_rng(root.spawn(1)[0]).uniform(0.5, 1.5, f)
    children = root.spawn(len(starts))
    with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
        list(pool.map(lambda a, b, c: _fill(c, X[a:b], y[a:b], dq, scales),
                      starts, ends, children))
    return {"X": X, "y": y, "sizes": sizes}
