"""binning._greedy_find_bin finds each bin's end by searching the running
counts (one step a bin) instead of walking every distinct value (one step a
value: 14 ms a column at 50,000 distinct values, and a wide table has
thousands of columns).  It must stay the exact port of GreedyFindBin
(bin.cpp:81): held here to the walk it replaced, kept below as the oracle."""
import numpy as np
import pytest

from lightgbm_tpu.binning import _greedy_find_bin, find_bin_mappers


def _walked(distinct, counts, max_bin, total_cnt, min_data_in_bin):
    """The value-by-value walk, as the module had it."""
    nd = len(distinct)
    bounds = []
    if min_data_in_bin > 0:
        max_bin = max(1, min(max_bin, total_cnt // min_data_in_bin))
    mean_bin_size = total_cnt / max_bin
    is_big = counts >= mean_bin_size
    rest_bin_cnt = max_bin - int(np.sum(is_big))
    rest_sample_cnt = int(total_cnt - counts[is_big].sum())
    mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)
    uppers, lowers = [], [float(distinct[0])]
    cur = 0
    for i in range(nd - 1):
        if not is_big[i]:
            rest_sample_cnt -= int(counts[i])
        cur += int(counts[i])
        if is_big[i] or cur >= mean_bin_size or \
                (is_big[i + 1] and cur >= max(1.0, mean_bin_size * 0.5)):
            uppers.append(float(distinct[i]))
            lowers.append(float(distinct[i + 1]))
            if len(uppers) >= max_bin - 1:
                break
            cur = 0
            if not is_big[i]:
                rest_bin_cnt -= 1
                mean_bin_size = rest_sample_cnt / max(rest_bin_cnt, 1)
    for i in range(len(uppers)):
        val = np.nextafter((uppers[i] + lowers[i + 1]) / 2.0, np.inf)
        if not bounds or val > np.nextafter(bounds[-1], np.inf):
            bounds.append(float(val))
    bounds.append(np.inf)
    return bounds


def _column(kind, rs):
    if kind == "continuous":
        v = rs.randn(20000)
    elif kind == "heavy_hitters":       # a few values hold most of the rows
        v = np.where(rs.rand(20000) < 0.7, rs.choice([-1.0, 0.5, 2.0], 20000),
                     rs.randn(20000))
    elif kind == "hitters_side_by_side":
        v = np.where(rs.rand(8000) < 0.9, rs.randint(0, 6, 8000),
                     rs.randint(0, 400, 8000) / 7.0)
    elif kind == "hitter_last":
        v = np.where(rs.rand(5000) < 0.5, 9.0, rs.rand(5000))
    elif kind == "few_rows":
        v = rs.randn(300)
    elif kind == "integers":
        v = rs.poisson(40, 20000).astype(float)
    else:                               # geometric: tiny counts in the tail
        v = rs.geometric(0.02, 20000).astype(float)
    return np.unique(v, return_counts=True)


KINDS = ["continuous", "heavy_hitters", "hitters_side_by_side",
         "hitter_last", "few_rows", "integers", "long_tail"]


@pytest.mark.parametrize("kind", KINDS)
def test_greedy_find_bin_is_the_walk(kind):
    rs = np.random.RandomState(sum(map(ord, kind)))
    distinct, counts = _column(kind, rs)
    total = int(counts.sum())
    for max_bin in (2, 3, 15, 63, 255):
        for min_data in (0, 1, 3, 20, 500):
            if len(distinct) <= max_bin:
                continue                # the per-value branch, untouched
            got = _greedy_find_bin(distinct, counts, max_bin, total, min_data)
            assert got == _walked(distinct, counts, max_bin, total, min_data), \
                (kind, max_bin, min_data)


def test_wide_table_finds_its_bins_quickly():
    """2,000 continuous columns, 20,000 sampled rows: seconds, where the
    walk took minutes."""
    import time
    X = np.random.RandomState(0).randn(20000, 2000).astype(np.float32)
    t = time.perf_counter()
    mappers = find_bin_mappers(X, max_bin=63, min_data_in_bin=3)
    took = time.perf_counter() - t
    assert len(mappers) == 2000 and all(m.num_bins == 63 for m in mappers)
    assert took < 60, took
