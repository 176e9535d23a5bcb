"""binning.construct_binned's native row-major path (every group one
numerical feature, a row-major float32 / float64 matrix) gives the bins of
the column-at-a-time path byte for byte, and steps aside where it does not
apply."""
import numpy as np
import pytest

from lightgbm_tpu import native
from lightgbm_tpu.binning import (_construct_binned_rows, construct_binned,
                                  construct_binned_columns, find_bin_mappers)

pytestmark = pytest.mark.skipif(native.get_lib() is None,
                                reason="no native library on this box")


def _table(kind, dtype):
    rs = np.random.RandomState(sum(map(ord, kind)))
    n, f = 5000, 24
    X = rs.randn(n, f)
    X[:, 3] = rs.randint(0, 5, n)             # a low-cardinality column
    X[:, 7] = rs.randint(0, 20, n) / 4.0      # another bin bucket
    X[:, 11] = 0.0                            # a trivial column
    kw = {}
    if kind == "nan":
        X[rs.rand(n, f) < 0.05] = np.nan
    elif kind == "zero_as_missing":
        X[rs.rand(n, f) < 0.3] = 0.0
        kw = dict(zero_as_missing=True)
    elif kind == "no_missing":
        X[rs.rand(n, f) < 0.05] = np.nan
        kw = dict(use_missing=False)
    elif kind == "row_view":                  # the head of a larger table
        X = np.vstack([X, rs.randn(100, f)])[:n]
    elif kind == "column_view":               # rows wider than the table
        X = np.hstack([X, rs.randn(n, 3)]).astype(dtype)[:, :f]
    return X.astype(dtype), kw


KINDS = ["plain", "nan", "zero_as_missing", "no_missing", "row_view",
         "column_view"]


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("kind", KINDS)
def test_rows_path_is_the_columns_path(kind, dtype):
    X, kw = _table(kind, dtype)
    mappers = find_bin_mappers(X, max_bin=63, min_data_in_bin=3, **kw)
    got = _construct_binned_rows(X, mappers, None)
    assert got is not None and got.bins.dtype == np.uint8
    want = construct_binned_columns(lambda f: X[:, f], X.shape[0],
                                    X.shape[1], mappers, None)
    np.testing.assert_array_equal(got.bins, want.bins)
    assert got.group_features == want.group_features
    assert len({tuple(g) for g in got.group_features}) == X.shape[1]
    for name in ("group_offsets", "group_bin_counts", "feature_offsets",
                 "feature_num_bins"):
        np.testing.assert_array_equal(getattr(got, name), getattr(want, name))
    np.testing.assert_array_equal(construct_binned(X, mappers).bins,
                                  want.bins)


def test_rows_path_steps_aside():
    X, _ = _table("plain", np.float64)
    mappers = find_bin_mappers(X, max_bin=63, min_data_in_bin=3)
    # column-major storage, bundled groups, categorical columns, wide bins
    assert _construct_binned_rows(np.asfortranarray(X), mappers, None) is None
    bundles = [[0, 1]] + [[f] for f in range(2, X.shape[1])]
    assert _construct_binned_rows(X, mappers, bundles) is None
    cat = find_bin_mappers(X, max_bin=63, min_data_in_bin=3,
                           categorical_features=[3])
    assert _construct_binned_rows(X, cat, None) is None
    wide = find_bin_mappers(X, max_bin=1023, min_data_in_bin=1)
    assert _construct_binned_rows(X, wide, None) is None
    # and the public entry point still bins every one of them
    want = construct_binned_columns(lambda f: X[:, f], X.shape[0],
                                    X.shape[1], mappers, None)
    np.testing.assert_array_equal(
        construct_binned(np.asfortranarray(X), mappers).bins, want.bins)
