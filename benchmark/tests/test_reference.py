"""The yardstick's own pieces: generators, quality metrics, the NumPy walk."""
import numpy as np
import pytest

from conftest import BENCH, load_module

import quality
import reference_walk


@pytest.mark.parametrize("name,shape,rows", [
    ("higgs_like", {"features": 28}, 1_100_000),
    ("mslr_like", {"features": 136, "docs_per_query": 120}, 130_000),
])
def test_generator_is_deterministic_in_seed(name, shape, rows):
    gen = load_module(BENCH / "generators" / f"{name}.py")
    seed = 2**31 + 12345            # the driver's seeds pass 32 signed bits
    a, b = gen.make(seed, rows, shape), gen.make(seed, rows, shape)
    c = gen.make(seed + 1, rows, shape)
    d = gen.make(seed, rows, shape, stream=1)
    assert a["X"].shape == (rows, shape["features"])
    assert a["X"].dtype == np.float32 and len(a["y"]) == rows
    for k in a:
        assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["X"], c["X"])
    assert not np.array_equal(a["X"], d["X"])
    assert np.all(np.isfinite(a["X"]))


def test_higgs_like_labels():
    gen = load_module(BENCH / "generators" / "higgs_like.py")
    d = gen.make(7, 200_000, {"features": 28})
    assert set(np.unique(d["y"])) == {0.0, 1.0}
    assert 0.45 < d["y"].mean() < 0.6
    # the first feature carries the strongest signal
    assert quality.auc(d["y"], d["X"][:, 0]) > 0.7


def test_mslr_like_structure():
    gen = load_module(BENCH / "generators" / "mslr_like.py")
    rows = 2_270_000 // 20
    d = gen.make(11, rows, {"features": 136, "docs_per_query": 120})
    assert d["sizes"].sum() == rows and d["sizes"][0] == 120
    assert d["sizes"][-1] == 120 + rows % 120
    assert set(np.unique(d["y"])) == {0.0, 1.0, 2.0, 3.0, 4.0}
    # the copy keeps the original's feature structure: the same columns are
    # small integer counts, the same are continuous
    import bench
    Xo, _, _ = bench.make_mslr_like(rows, 136)
    few = [len(np.unique(d["X"][:, c])) < 32 for c in range(136)]
    assert few == [len(np.unique(Xo[:, c])) < 32 for c in range(136)]
    assert sum(few) >= 20
    assert np.mean(d["X"][:, 10:20] == 0) > 0.5     # anchor stream mostly empty


def test_quality_agrees_with_bench_py():
    import bench
    rs = np.random.RandomState(3)
    y = (rs.rand(5000) < 0.4).astype(np.float64)
    p = rs.randn(5000) + y
    assert quality.auc(y, p) == pytest.approx(bench.auc_score(y, p), abs=1e-12)
    sizes = np.full(50, 100)
    grades = rs.randint(0, 5, 5000).astype(np.float64)
    assert quality.ndcg_at_k(grades, p, sizes, 10) == pytest.approx(
        bench.ndcg_at_k(grades, p, sizes, 10), abs=1e-12)
    assert quality.evaluate("auc", y, p) == quality.auc(y, p)


def test_walk_equals_booster_predict():
    import lightgbm_tpu as lgb
    gen = load_module(BENCH / "generators" / "higgs_like.py")
    d = gen.make(5, 6000, {"features": 28})
    X = d["X"].copy()
    X[::7, 3] = np.nan                       # exercise the missing branch
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
              "verbosity": -1, "min_data_in_leaf": 5}
    bst = lgb.train(params, lgb.Dataset(X[:5000], label=d["y"][:5000]),
                    num_boost_round=6)
    dump = bst.dump_model()
    got = reference_walk.walk(dump, X[5000:])
    want = bst.predict(X[5000:], raw_score=True)
    assert np.allclose(got, want, rtol=1e-9, atol=1e-9)
    part = reference_walk.walk(dump, X[5000:], num_trees=2)
    assert np.allclose(part, bst.predict(X[5000:], raw_score=True,
                                         num_iteration=2), atol=1e-9)
    assert reference_walk.tree_faults(dump, 5000, 15) == ([], 0)
    assert reference_walk.tree_faults(dump, 4999, 15) == (
        [(i, 15, 1) for i in range(6)], 1)
    assert reference_walk.tree_faults(dump, 4999, 15, count_slack=1) == ([], 1)
    assert reference_walk.tree_faults(dump, 5000, 16, first=4) == (
        [(4, 15, 0), (5, 15, 0)], 0)
