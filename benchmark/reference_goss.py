"""The plain reference of a tree grown under gradient-based one-side sampling
(Ke et al., NIPS 2017, section 3 and Algorithm 2; LightGBM
`data_sample_strategy=goss`, goss.hpp), in float64 NumPy, written from the
SOURCE's rule and with nothing of the program but ONE borrowed piece, named
below.

The source's rule.  Both rates are shares of ALL N rows: the top_rate * N
rows of largest |g h| are kept, other_rate * N rows are sampled from the
REST (Algorithm 2: randN = b x len(I); goss.hpp: other_k = cnt *
other_rate), and the sampled rows' gradients and hessians are amplified by
(1 - top_rate) / other_rate (goss.hpp: multiply = (cnt - top_k) / other_k),
which makes the rest's sums unbiased.  In-bag: (top_rate + other_rate) N,
30% at the documented 0.2 / 0.1.

Here: given the raw scores s before tree t of a binary-objective model and
the labels y, every row has g = p - y and h = p (1 - p) with p = 1 / (1 +
e^-s), and the magnitude |g h|.  With k = max(1, int(top_rate * rows
drawn)) the rows whose magnitude is at least the k-th largest are the TOP
set (every tie at the threshold is in it); a row of the rest is KEPT where
its uniform number is below other_rate / (1 - top_rate) - other_rate * N
rows of the rest in expectation, see the departure below - and amplified.
The tree is grown on top + kept (the in-bag rows); its root's
`internal_count` is their number, and a dumped root split (feature,
threshold, default direction) has

    left in-bag count = |L n in-bag|
    gain = G_L^2 / H_L + G_R^2 / H_R - G^2 / H

over the in-bag rows with the amplified weights, as reference_split.py has
for the dense tree 0.  The scores come from reference_walk.walk over the
dumped trees, on the host (`scores`): nothing the program computed after
the dump feeds the comparison.

The departures, both stated in the configuration's `assumed`.  (1) The
borrowed piece: the uniform numbers are the program's own draw, reproduced
on the host - `jax.random.uniform` under `PRNGKey(bagging_seed * 524287 +
iteration)` over the table's PADDED row count, on the CPU device
(`program_uniform`).  A sampler is defined by its draw; an independent
generator would make every count a statistical statement.  (2) The source
draws EXACTLY other_rate * N rows of the rest; a draw of one uniform number
a row can only keep each with that probability, so the count of kept rows
is binomial about other_rate * N (`count_bounds`).  What a wrong rate (the
keep rate taken over the rest and not over all rows reads 2% of N low: the
repo's sampler before PR 37), a lost amplification or a stale gradient do
to the counts and the gain is hidden by neither (tests/test_goss_reference.py
has the controls).

`sampled_tree_faults` holds every sampled tree of a dump to what needs no
scores: the tree is full, its leaf counts sum to its root's count, and that
count lies inside the analytic bounds of the sampler.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference_split
import reference_walk

SEED_STRIDE = 524287          # the program's key: bagging_seed * this + t
DEFAULT_BAGGING_SEED = 3      # LightGBM's documented default


def gradients(score, y):
    """(g, h) float64 of the binary objective at raw scores `score`."""
    s = np.asarray(score, np.float64)
    p = 1.0 / (1.0 + np.exp(-s))
    return p - np.asarray(y, np.float64), p * (1.0 - p)


def program_uniform(bagging_seed, iteration, rows_drawn):
    """The program's uniform draw for tree `iteration`, float32 of length
    `rows_drawn` (the padded row count, as the program draws it), made on
    the CPU device whatever the process's default device is."""
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.PRNGKey(bagging_seed * SEED_STRIDE + iteration)
        return np.asarray(jax.random.uniform(key, (rows_drawn,)))


def first_sampled_tree(learning_rate):
    """The sampler's documented warm-up: no sampling while t < 1 / lr."""
    t = 0
    while t < 1.0 / max(learning_rate, 1e-12):
        t += 1
    return t


def top_count(top_rate, rows_drawn):
    return max(1, int(top_rate * rows_drawn))


def keep_rate(top_rate, other_rate):
    """The share of the REST that is kept: other_rate of all rows."""
    return min(1.0, other_rate / max(1.0 - top_rate, 1e-12))


def scores(dump, X, first, last, chunk_rows=65536, workers=None):
    """Raw scores of the dumped trees first <= t < last over X by the NumPy
    walk (reference_walk.walk), float64; chunks of rows on a few host
    threads."""
    some = {"tree_info": dump["tree_info"][first:last]}
    workers = workers or max(1, min(8, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(workers) as pool:
        parts = pool.map(
            lambda a: reference_walk.walk(some, X[a:a + chunk_rows]),
            range(0, len(X), chunk_rows))
        return np.concatenate(list(parts))


def sample(g, h, u, top_rate, other_rate, rows_drawn=None, amplify=True):
    """One-side sampling of rows with gradients (g, h) and uniform numbers u
    (one a row).  `rows_drawn` is the length the program sampled over (its
    rows past len(g) are padding of magnitude 0).
    -> {"weight": float64 a row (0 out of bag, 1 top, the amplification a
    kept row), "top": bool a row, "k", "threshold", "ties"}"""
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    n = len(g)
    k = min(top_count(top_rate, rows_drawn or n), n)
    mag = np.abs(g * h)
    threshold = np.partition(mag, n - k)[n - k]      # the k-th largest
    top = mag >= threshold
    kept = ~top & (np.asarray(u)[:n] < np.float32(
        keep_rate(top_rate, other_rate)))
    amp = (1.0 - top_rate) / max(other_rate, 1e-12) if amplify else 1.0
    weight = np.where(kept, amp, 1.0) * (top | kept)
    return {"weight": weight, "top": top, "k": k,
            "threshold": float(threshold), "ties": int(top.sum()) - k}


def split_stats(column, root, g, h, weight):
    """(left in-bag count, gain) of `root` over the weighted rows."""
    left = reference_split.goes_left(column, root)
    gw, hw = g * weight, h * weight
    gl, hl = gw[left].sum(), hw[left].sum()
    gt, ht = gw.sum(), hw.sum()
    gr, hr = gt - gl, ht - hl
    gain = gl * gl / hl + gr * gr / hr - gt * gt / ht
    return int((left & (weight > 0)).sum()), float(gain)


def check(dump, tree, X, y, score, u, top_rate, other_rate, rows_drawn,
          count_rtol, gain_rtol, amplify=True, name="sampled"):
    """The three checks on sampled tree `tree` of `dump`, grown from raw
    scores `score` (named `<name>_root_...`), and a line saying what was
    read."""
    g, h = gradients(score, y)
    drawn = sample(g, h, u, top_rate, other_rate, rows_drawn, amplify)
    in_bag = int((drawn["weight"] > 0).sum())
    root = reference_split.root_of(dump, tree)
    left, gain = split_stats(X[:, root["feature"]], root, g, h,
                             drawn["weight"])
    slack = count_rtol * len(g)
    gap = abs(root["gain"] - gain) / abs(gain)
    control = ""
    if amplify:
        # the second reading the gain's limit lies under: the same split
        # over the same rows with the kept rows left unamplified
        bare = np.where(drawn["weight"] > 0, 1.0, 0.0)
        lost = split_stats(X[:, root["feature"]], root, g, h, bare)[1]
        control = (f"; with the amplification lost the reference would "
                   f"read {lost:.6g} (gap "
                   f"{abs(root['gain'] - lost) / abs(lost):.3g})")
    return {
        f"{name}_root_count_is_the_references":
            bool(abs(root["count"] - in_bag) <= slack),
        f"{name}_root_left_count_is_the_references":
            bool(abs(root["left_count"] - left) <= slack),
        f"{name}_root_gain_is_the_references": bool(gap <= gain_rtol),
        "ties": drawn["ties"],
        "said": (f"tree {tree}: k {drawn['k']} at |g h| >= "
                 f"{drawn['threshold']:.9g} ({drawn['ties']} ties), in-bag "
                 f"{root['count']} (reference {in_bag}, slack {slack:.0f}); "
                 f"root feature {root['feature']} <= "
                 f"{root['threshold']:.6g}, left {root['left_count']} "
                 f"(reference {left}), gain {root['gain']:.6g} (reference "
                 f"{gain:.6g}, relative gap {gap:.3g}, limit {gain_rtol})"
                 + control)}


def count_bounds(n_rows, rows_drawn, top_rate, other_rate, ties=0,
                 capacity=0, sigmas=6.0):
    """[lo, hi] of a sampled tree's in-bag count: k top rows (and up to
    `ties` more at the threshold) plus Binomial(rows not top, other_rate /
    (1 - top_rate)) within `sigmas`: about (top_rate + other_rate) n_rows;
    never under k, never over the compaction capacity the program streamed
    (0: it streamed the whole table)."""
    k = min(top_count(top_rate, rows_drawn), n_rows)
    rest = n_rows - k
    p = keep_rate(top_rate, other_rate)
    mean = k + p * rest
    dev = sigmas * np.sqrt(max(rest * p * (1.0 - p), 1.0))
    return max(k, mean - dev), min(mean + dev + ties, capacity or n_rows)


def sampled_tree_faults(dump, first, num_leaves, lo, hi, last=None,
                        count_slack=0):
    """[(tree, leaves, leaf-count sum - root count, root count), ...] for
    the trees first <= t < last that miss what every sampled tree keeps: it
    reaches `num_leaves` leaves, its leaf counts sum to its root's count to
    within `count_slack` rows (the configuration's `leaf_count_slack_rows`:
    0 while the in-bag rows are under 2^24), and that count lies in
    [lo, hi]."""
    bad = []
    for i, info in enumerate(dump["tree_info"][:last]):
        if i < first:
            continue
        t = reference_walk.flatten(info["tree_structure"])
        root = int(info["tree_structure"].get("internal_count", 0))
        off = int(t["leaf_count"].sum()) - root
        if (info["num_leaves"] != num_leaves
                or len(t["leaf_count"]) != num_leaves
                or abs(off) > count_slack or not lo <= root <= hi):
            bad.append((i, len(t["leaf_count"]), off, root))
    return bad
