"""reference_split.py against a split computed by hand, the control that
loses one shard of four, and the Criteo-shaped generator it is run on."""
import numpy as np
import pytest

from conftest import BENCH, load_module

import quality
import reference_split


def dump_of(feature, threshold, gain, left_count, count, default_left=True,
            missing="NaN"):
    return {"tree_info": [{"tree_structure": {
        "split_feature": feature, "threshold": threshold,
        "decision_type": "<=", "default_left": default_left,
        "missing_type": missing, "split_gain": gain,
        "internal_count": count,
        "left_child": {"leaf_index": 0, "leaf_count": left_count,
                       "leaf_value": 0.0},
        "right_child": {"split_feature": 0, "internal_count":
                        count - left_count}}}]}


def test_split_computed_by_hand():
    # eight rows, two clicks: p = 1/4, g = p - y, h = 3/16 a row
    col = np.array([0.0, 1.0, 2.0, np.nan, 5.0, 6.0, 7.0, 8.0], np.float32)
    y = np.array([0, 0, 0, 0, 1, 0, 1, 0], np.float32)
    X = np.stack([np.zeros(8, np.float32), col], axis=1)
    # left: 0, 1, 2 and the NaN (default left): G_L = 4 * 1/4 = 1
    # right: four rows, two clicks: G_R = 4 * 1/4 - 2 = -1; G = 0
    h = 3.0 / 16.0
    gain = 1.0 / (4 * h) + 1.0 / (4 * h)
    root = reference_split.root_of(dump_of(1, 2.5, gain, 4, 8))
    assert reference_split.split_stats(col, y, root) == (4, pytest.approx(gain))
    got = reference_split.check(dump_of(1, 2.5, gain, 4, 8), X, y, 1e-9)
    assert got["root_left_count_is_the_whole_tables"]
    assert got["root_gain_is_the_whole_tables"]
    # the NaN row to the right instead: 3 left, and another gain
    right = dump_of(1, 2.5, gain, 4, 8, default_left=False)
    assert reference_split.split_stats(
        col, y, reference_split.root_of(right))[0] == 3
    got = reference_split.check(right, X, y, 1e-9)
    assert not got["root_left_count_is_the_whole_tables"]
    assert not got["root_gain_is_the_whole_tables"]
    # a count one row off (float32 past 2^24 rows) fails the count alone
    got = reference_split.check(dump_of(1, 2.5, gain, 5, 8), X, y, 1e-9)
    assert not got["root_left_count_is_the_whole_tables"]
    assert got["root_gain_is_the_whole_tables"]


def test_zero_as_missing_and_no_missing():
    col = np.array([0.0, 1.0, np.nan, 3.0], np.float32)
    for missing, default_left, want in (("Zero", False, [0, 1, 0, 0]),
                                        ("Zero", True, [1, 1, 1, 0]),
                                        ("None", False, [1, 1, 1, 0])):
        root = reference_split.root_of(
            dump_of(0, 1.5, 1.0, 1, 4, default_left, missing))
        assert list(reference_split.goes_left(col, root)) == want, missing


@pytest.fixture(scope="module")
def criteo():
    gen = load_module(BENCH / "generators" / "criteo_like.py")
    return gen, gen.make(2**31 + 12345, 400_000, {"features": 67})


def test_one_shard_of_four_masked_fails_the_gain(criteo):
    """The control: a learner that reduced three shards of four.  Its root
    gain over the rows it saw reads about a quarter low against the whole
    table's, far outside the cell's tolerance; the whole table passes."""
    import json
    _, data = criteo
    X, y = data["X"], data["y"]
    tol = json.loads((BENCH / "configs" / "criteo_dp_like.json").read_text()
                     )["reference_split"]["gain_rtol"]
    assert tol < 0.05
    col = 13 + int(np.argmax(np.abs(
        [np.corrcoef(X[:, 13 + j], y)[0, 1] for j in range(26)])))
    thr = float(np.median(X[:, col]))
    probe = {"feature": col, "threshold": thr, "default_left": True,
             "missing_type": 0}
    count, gain = reference_split.split_stats(X[:, col], y, probe)
    whole = dump_of(col, thr, gain * (1 + tol / 4), count, len(y),
                    missing="None")
    got = reference_split.check(whole, X, y, tol)
    assert got["root_left_count_is_the_whole_tables"]
    assert got["root_gain_is_the_whole_tables"]
    # what three shards of four read: their own rows' count and gain
    seen = slice(0, 3 * len(y) // 4)
    lost_count, lost_gain = reference_split.split_stats(X[:, col], y, probe,
                                                        rows=seen)
    assert 0.70 < lost_gain / gain < 0.80 and lost_count < count
    lost = dump_of(col, thr, lost_gain, lost_count, len(y), missing="None")
    got = reference_split.check(lost, X, y, tol)
    assert not got["root_gain_is_the_whole_tables"]
    assert not got["root_left_count_is_the_whole_tables"]
    assert "relative gap 0.2" in got["said"]


def test_criteo_like_is_deterministic_and_of_the_sources_kinds(criteo):
    gen, a = criteo
    seed, rows, shape = 2**31 + 12345, 400_000, {"features": 67}
    b = gen.make(seed, rows, shape)
    assert a["X"].shape == (rows, 67) and a["X"].dtype == np.float32
    for k in a:
        assert np.array_equal(a[k], b[k], equal_nan=True), k
    assert not np.array_equal(a["y"], gen.make(seed + 1, rows, shape)["y"])
    assert not np.array_equal(a["y"],
                              gen.make(seed, rows, shape, stream=1)["y"])
    X, y = a["X"], a["y"]
    assert set(np.unique(y)) == {0.0, 1.0} and 0.02 < y.mean() < 0.045
    ints, rates = X[:, :13], X[:, 13:39]
    counts, extra = X[:, 39:65], X[:, 65:]
    assert np.isnan(ints).any() and not np.isnan(X[:, 13:]).any()
    live = ints[~np.isnan(ints)]
    assert (live >= 0).all() and (live == np.floor(live)).all()
    assert (live == 0).mean() > 0.05 and live.max() > 1000     # heavy tail
    assert rates.min() >= 0.0 and rates.max() <= 1.0
    assert (counts >= 0).all() and (counts == np.floor(counts)).all()
    assert counts.max() > 1e5                                   # heavy tail
    assert np.isfinite(extra).all()
    # the fixed label function: the rates carry signal
    best = max(abs(quality.auc(y, rates[:, j]) - 0.5) for j in range(26))
    assert best > 0.03


def test_every_seed_is_the_same_amount_of_work(criteo):
    """The binner fills all of max_bin bins in every column whatever the
    seed, so the group count and the bucketed M-axis do not move (PR 29 was
    refused for a seed that changed the work)."""
    import os
    import subprocess
    import sys
    code = (
        "import sys, numpy as np\n"
        f"sys.path[:0] = [{str(BENCH)!r}, {str(BENCH.parent)!r}]\n"
        "import importlib.util as u\n"
        f"s = u.spec_from_file_location('g', {str(BENCH / 'generators' / 'criteo_like.py')!r})\n"
        "g = u.module_from_spec(s); s.loader.exec_module(g)\n"
        "import lightgbm_tpu as lgb\n"
        "for seed in (1, 2**31 + 7, 987654321):\n"
        "    d = g.make(seed, 250000, {'features': 67})\n"
        "    ds = lgb.Dataset(d['X'], label=d['y'], params={'max_bin': 63, 'verbosity': -1})\n"
        "    ds.construct()\n"
        "    print(ds.binned.num_groups, sorted(set(int(b) for b in ds.binned.feature_num_bins)))\n")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         capture_output=True, timeout=600,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines() == ["67 [63]"] * 3
