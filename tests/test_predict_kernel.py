"""Device batch-prediction kernel vs the host predictor (interpret mode).

Reference analog: src/boosting/gbdt_prediction.cpp — batch predictions must
match the per-row walk."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.basic import Booster


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    # the kernel runs interpreted off the chip (runtime.pallas_interpret);
    # opt in to the device path there and lower its batch threshold
    monkeypatch.setattr(Booster, "_DEVICE_PREDICT_OFF_CHIP", True)
    monkeypatch.setattr(Booster, "_DEVICE_PREDICT_MIN_ROWS", 100)
    yield


def _train(n=2000, f=8, seed=3, **params):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f)
    X[rs.rand(n) < 0.1, 0] = np.nan
    y = X[:, 1] * 2 + np.nan_to_num(X[:, 0]) + 0.1 * rs.randn(n)
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5, **params},
                    lgb.Dataset(X, label=y), num_boost_round=5)
    return bst, X


def test_device_predict_matches_host():
    bst, X = _train()
    rs = np.random.RandomState(9)
    Xt = rs.randn(500, X.shape[1])
    Xt[rs.rand(500) < 0.1, 0] = np.nan
    p_dev = bst.predict(Xt)                       # device path (min rows 100)
    # force host path
    big = Booster._DEVICE_PREDICT_MIN_ROWS
    Booster._DEVICE_PREDICT_MIN_ROWS = 10 ** 9
    try:
        p_host = bst.predict(Xt)
    finally:
        Booster._DEVICE_PREDICT_MIN_ROWS = big
    np.testing.assert_allclose(p_dev, p_host, rtol=1e-4, atol=1e-5)


def test_device_predict_multiclass():
    rs = np.random.RandomState(5)
    X = rs.randn(1500, 6)
    y = (X[:, 0] + X[:, 1] > 0).astype(int) + (X[:, 2] > 0.5).astype(int)
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 15, "verbosity": -1,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y.astype(float)), num_boost_round=4)
    p_dev = bst.predict(X)
    big = Booster._DEVICE_PREDICT_MIN_ROWS
    Booster._DEVICE_PREDICT_MIN_ROWS = 10 ** 9
    try:
        p_host = bst.predict(X)
    finally:
        Booster._DEVICE_PREDICT_MIN_ROWS = big
    assert p_dev.shape == (1500, 3)
    np.testing.assert_allclose(p_dev, p_host, rtol=1e-4, atol=1e-5)


def _train_cat(n=1200, seed=6):
    rs = np.random.RandomState(seed)
    X = 0.01 * rs.randn(n, 5)
    X[:, 3] = rs.randint(0, 6, n)
    y = 3.0 * np.isin(X[:, 3], [1, 4]).astype(float) + 0.01 * rs.randn(n)
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5,
                     "max_cat_to_onehot": 1},
                    lgb.Dataset(X, label=y, categorical_feature=[3]),
                    num_boost_round=3)
    use = bst._all_trees()
    has_cat_split = any(
        (np.asarray(t.decision_type[:max(t.num_leaves - 1, 0)]) & 1).any()
        for t in use)
    assert has_cat_split, "model should contain categorical splits"
    return bst, X, y


def test_device_predict_categorical_matches_host():
    """Categorical splits walk on-device (bin-domain bitset side table);
    NaN / unseen / negative category values re-bin to the always-zero
    sentinel bit, reproducing the host walk's route-right."""
    bst, X, y = _train_cat()
    use = bst._all_trees()
    Xt = X.copy()
    # adversarial category column: NaN, unseen, negative, fractional,
    # and far-out-of-range values on top of the seen 0..5
    rs = np.random.RandomState(8)
    n = len(Xt)
    Xt[rs.rand(n) < 0.1, 3] = np.nan
    Xt[rs.rand(n) < 0.05, 3] = 77.0          # unseen category
    Xt[rs.rand(n) < 0.05, 3] = -3.0          # negative -> missing
    Xt[rs.rand(n) < 0.05, 3] = 2.7           # truncates to category 2
    Xt[rs.rand(n) < 0.02, 3] = 1e12          # far past any bitset span
    p_dev = bst._try_device_predict(Xt, use, 1)
    assert p_dev is not None, "categorical model must take the device path"
    big = Booster._DEVICE_PREDICT_MIN_ROWS
    Booster._DEVICE_PREDICT_MIN_ROWS = 10 ** 9
    try:
        p_host = bst.predict(Xt, raw_score=True)
    finally:
        Booster._DEVICE_PREDICT_MIN_ROWS = big
    np.testing.assert_allclose(np.asarray(p_dev), p_host,
                               rtol=1e-4, atol=1e-5)
    p = bst.predict(X)
    assert np.corrcoef(p, y)[0, 1] > 0.9


def test_linear_tree_model_falls_back():
    rs = np.random.RandomState(6)
    X = rs.randn(900, 4)
    y = X[:, 0] * 2 + X[:, 1] + 0.01 * rs.randn(900)
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbosity": -1, "min_data_in_leaf": 5,
                     "linear_tree": True},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    use = bst._all_trees()
    if not any(t.is_linear for t in use):
        import pytest
        pytest.skip("no linear trees were grown")
    assert bst._try_device_predict(X, use, 1) is None  # linear -> host


def test_device_predict_early_stop_matches_host():
    """pred_early_stop composes with the device batch walk (the kernel
    freezes cleared rows every es_freq trees — reference:
    prediction_early_stop.cpp CreateBinary) instead of forcing the host
    per-tree loop; outputs must match the host early-stop path."""
    rs = np.random.RandomState(11)
    n = 1200
    X = rs.randn(n, 6)
    y = ((X[:, 0] + 0.5 * X[:, 1] > 0)).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), num_boost_round=20)
    kw = dict(raw_score=True, pred_early_stop=True,
              pred_early_stop_freq=4, pred_early_stop_margin=2.0)
    # device path taken: _try_device_predict returns non-None
    assert bst._try_device_predict(X, bst._all_trees(), 1,
                                   es=(4, 2.0)) is not None
    p_dev = bst.predict(X, **kw)
    big = Booster._DEVICE_PREDICT_MIN_ROWS
    Booster._DEVICE_PREDICT_MIN_ROWS = 10 ** 9
    try:
        p_host = bst.predict(X, **kw)
    finally:
        Booster._DEVICE_PREDICT_MIN_ROWS = big
    # early stopping must actually bite (outputs differ from full walk)
    p_full = bst.predict(X, raw_score=True)
    assert np.abs(p_host - p_full).max() > 1e-6
    np.testing.assert_allclose(p_dev, p_host, rtol=1e-4, atol=1e-5)


def test_device_predict_early_stop_multiclass_stays_host():
    """Multiclass margins couple classes; the device walk declines and the
    host loop keeps the reference's top1-top2 margin semantics."""
    rs = np.random.RandomState(5)
    X = rs.randn(600, 6)
    y = (X[:, 0] > 0).astype(int) + (X[:, 2] > 0.5).astype(int)
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 7, "verbosity": -1,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y.astype(float)), num_boost_round=6)
    assert bst._try_device_predict(X, bst._all_trees(), 3,
                                   es=(2, 0.5)) is None
    p = bst.predict(X, pred_early_stop=True, pred_early_stop_freq=2,
                    pred_early_stop_margin=0.5)
    assert p.shape == (600, 3)


@pytest.mark.parametrize("rows, groups", [(77, 5), (1025, 28), (1024, 136),
                                          (3000, 2000)])
def test_host_pack_gives_the_device_packs_words(rows, groups):
    """predict packs its batch's words on the host: pack_bins_T, handed a
    NumPy table, gives NumPy and the same (GW_pad, N_pad) int32 words as it
    packs on the device, every bin value a uint8 holds, ragged rows and
    groups."""
    import jax.numpy as jnp
    from lightgbm_tpu.pallas.stream_kernel import pack_bins_T
    bins = np.random.RandomState(rows).randint(
        0, 256, (rows, groups)).astype(np.uint8)
    got = pack_bins_T(bins).bins_T
    want = pack_bins_T(jnp.asarray(bins)).bins_T
    assert isinstance(got, np.ndarray) and got.dtype == np.int32
    assert not isinstance(want, np.ndarray)
    np.testing.assert_array_equal(got, np.asarray(want))


def test_group_wider_than_a_byte_walks_on_the_host():
    """The kernel's words hold four 8-bit bins: a model whose groups hold
    more than 256 bins leaves the device path and says why, and the host
    walk's answer is the one predict returns."""
    rng = np.random.RandomState(11)
    X = rng.randn(800, 3)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 400,
                     "min_data_in_bin": 1, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    assert np.asarray(bst.engine.train_data.binned.bins).dtype != np.uint8
    assert bst._try_device_predict(X, bst._all_trees(), 1) is None
    assert bst.last_predict_path == \
        "host (a feature group of more than 256 bins)"
    p = bst.predict(X, raw_score=True)
    assert p.shape == (800,) and np.isfinite(p).all()
