"""Out-of-core streaming ingest (docs/INGEST.md).

Covers the PR's gate surface: sketch-vs-exact boundary equivalence
(incl. NaN / zero / min_data_in_bin / zero_as_missing / categorical
edge cases), chunk-boundary and rank-split determinism, stream-vs-inmem
tree BIT-identity, the memory-mapped binned cache (hit, corruption
matrix, auto fallback), checkpoint/resume from a streamed ingest, the
chunked device ship, and the eager-memory fixes.
"""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import BinMapper, construct_binned
from lightgbm_tpu.ingest import (BottomKSample, FeatureSketch,
                                 _merge_rank_blobs, _pack_rank_blob,
                                 resolve_ingest_mode)
from lightgbm_tpu.runtime import child_env
from lightgbm_tpu.utils.log import LightGBMError

PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 5, "bin_construct_sample_cnt": 50000,
          "ingest_sketch_size": 65536}


def _write_csv(path, X, y, fmt="%.17g"):
    with open(path, "w") as f:
        for i in range(len(X)):
            f.write(f"{y[i]:.0f}," + ",".join(
                "" if np.isnan(v) else fmt % v for v in X[i]) + "\n")
    return str(path)


def _make_data(n=4000, F=5, seed=3, nan_frac=0.03):
    rng = np.random.RandomState(seed)
    X = np.round(rng.randn(n, F), 2)
    if nan_frac:
        X[rng.rand(n, F) < nan_frac] = np.nan
    y = (X[:, 0] + 0.5 * np.nan_to_num(X[:, 1])
         + rng.randn(n) * 0.3 > 0).astype(float)
    return X, y


def _train_env(csv, mode, chunk=None, extra=None, rounds=8):
    """Train with the ingest A/B env overrides so the recorded params —
    and therefore the model string — are byte-comparable across arms."""
    os.environ["LGBTPU_INGEST"] = mode
    if chunk:
        os.environ["LGBTPU_INGEST_CHUNK"] = str(chunk)
    try:
        p = {**PARAMS, **(extra or {})}
        ds = lgb.Dataset(csv, params=p)
        return lgb.train(p, ds, num_boost_round=rounds), ds
    finally:
        os.environ.pop("LGBTPU_INGEST", None)
        os.environ.pop("LGBTPU_INGEST_CHUNK", None)


# ---------------------------------------------------------------------------
# Sketch-vs-exact boundary equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_missing,zero_as_missing", [
    (True, False), (True, True), (False, False)])
@pytest.mark.parametrize("min_data_in_bin", [1, 3, 50])
def test_sketch_matches_find_numerical(use_missing, zero_as_missing,
                                       min_data_in_bin):
    rng = np.random.RandomState(0)
    col = rng.choice(np.round(rng.randn(300), 2), 20000)
    col[rng.rand(20000) < 0.05] = np.nan
    col[rng.rand(20000) < 0.2] = 0.0
    ref = BinMapper.find_numerical(col, 63, min_data_in_bin, use_missing,
                                   zero_as_missing)
    for chunk in (137, 4096, len(col)):
        sk = FeatureSketch(65536)
        for s in range(0, len(col), chunk):
            sk.update(col[s:s + chunk])
        assert sk.exact
        m = sk.find_mapper(63, min_data_in_bin, use_missing,
                           zero_as_missing)
        np.testing.assert_array_equal(m.upper_bounds, ref.upper_bounds)
        assert (m.num_bins, m.missing_type, m.default_bin,
                m.most_freq_bin, m.min_val, m.max_val) == \
               (ref.num_bins, ref.missing_type, ref.default_bin,
                ref.most_freq_bin, ref.min_val, ref.max_val)


def test_sketch_merge_equals_whole_and_is_order_invariant():
    rng = np.random.RandomState(1)
    col = rng.choice(np.round(rng.randn(400), 3), 9000)
    col[rng.rand(9000) < 0.1] = np.nan
    whole = FeatureSketch(65536)
    whole.update(col)
    for cut in (1, 1234, 8999):
        a, b = FeatureSketch(65536), FeatureSketch(65536)
        a.update(col[:cut])
        b.update(col[cut:])
        b.merge(a)  # reversed merge order too
        np.testing.assert_array_equal(b.values, whole.values)
        np.testing.assert_array_equal(b.counts, whole.counts)
        assert (b.na_cnt, b.total) == (whole.na_cnt, whole.total)


def test_sketch_categorical_matches_find_categorical():
    rng = np.random.RandomState(2)
    col = rng.choice([0, 1, 2, 5, 5.7, 100, -3, np.nan], 8000,
                     p=[.3, .2, .15, .1, .05, .05, .05, .1])
    ref = BinMapper.find_categorical(col, 10, 3, True)
    sk = FeatureSketch(65536, is_cat=True)
    for s in range(0, len(col), 997):
        sk.update(col[s:s + 997])
    m = sk.find_mapper(10, 3, True, False)
    np.testing.assert_array_equal(m.categories, ref.categories)
    assert (m.num_bins, m.missing_type) == (ref.num_bins, ref.missing_type)


def test_sketch_trivial_and_all_nan_columns():
    for col in (np.full(100, 7.0), np.full(100, np.nan),
                np.zeros(100)):
        ref = BinMapper.find_numerical(col, 255, 3, True, False)
        sk = FeatureSketch(1024)
        sk.update(col[:37])
        sk.update(col[37:])
        m = sk.find_mapper(255, 3, True, False)
        np.testing.assert_array_equal(m.upper_bounds, ref.upper_bounds)
        assert (m.num_bins, m.missing_type) == (ref.num_bins,
                                                ref.missing_type)


def test_compressed_sketch_tracks_quantiles():
    rng = np.random.RandomState(5)
    big = rng.randn(200000)
    sk = FeatureSketch(1024)
    for s in range(0, len(big), 4096):
        sk.update(big[s:s + 4096])
    assert not sk.exact
    m = sk.find_mapper(255, 3, True, False)
    assert m.num_bins <= 256
    assert np.all(np.diff(m.upper_bounds[:-1]) > 0)
    # every bin holds roughly uniform mass: boundary rank error small
    q = np.searchsorted(np.sort(big), m.upper_bounds[:-1]) / len(big)
    assert np.abs(np.diff(q) - 1.0 / m.num_bins).max() < 0.02
    # min/max survive compression exactly
    assert m.min_val == big.min() and m.max_val == big.max()


# ---------------------------------------------------------------------------
# Bottom-k pool + rank merge determinism
# ---------------------------------------------------------------------------

def test_bottom_k_pool_chunk_and_rank_invariant():
    rng = np.random.RandomState(7)
    X = rng.randn(5000, 4)
    ref = BottomKSample(600, seed=1)
    ref.offer(0, X)
    want = ref.finalize()
    # chunked offers
    p2 = BottomKSample(600, seed=1)
    for s in range(0, 5000, 333):
        p2.offer(s, X[s:s + 333])
    np.testing.assert_array_equal(p2.finalize(), want)
    # rank-split merge
    a, b = BottomKSample(600, seed=1), BottomKSample(600, seed=1)
    a.offer(0, X[:2100])
    b.offer(2100, X[2100:])
    merged = BottomKSample.merged([a.state(), b.state()], 600, seed=1)
    np.testing.assert_array_equal(merged.finalize(), want)


def test_bottom_k_pool_small_n_is_all_rows_in_order():
    X = np.arange(50, dtype=float).reshape(25, 2)
    p = BottomKSample(100, seed=9)
    p.offer(0, X[:11])
    p.offer(11, X[11:])
    np.testing.assert_array_equal(p.finalize(), X)


def test_rank_blob_pack_merge_roundtrip():
    """The ONE-collective payload: splitting rows across simulated ranks
    and merging the gathered blobs reproduces the single-rank state."""
    rng = np.random.RandomState(11)
    col = np.round(rng.randn(4000), 2)
    X = np.column_stack([col, rng.choice([1, 2, 3], 4000).astype(float)])
    F, budget, k = 2, 4096, 500
    whole_sk = [FeatureSketch(budget), FeatureSketch(budget, is_cat=True)]
    for f in range(F):
        whole_sk[f].update(X[:, f])
    whole_pool = BottomKSample(k, seed=1)
    whole_pool.offer(0, X)

    wire_w = FeatureSketch.wire_width(budget)
    blobs = []
    for (lo, hi) in ((0, 1500), (1500, 4000)):
        sks = [FeatureSketch(budget), FeatureSketch(budget, is_cat=True)]
        for f in range(F):
            sks[f].update(X[lo:hi, f])
        pool = BottomKSample(k, seed=1)
        pool.offer(lo, X[lo:hi])
        blobs.append(_pack_rank_blob(sks, pool, wire_w, k, F))
    gathered = np.stack(blobs)
    sks, pool = _merge_rank_blobs(gathered, budget, wire_w, k, F, seed=1,
                                  want_pool=True)
    for f in range(F):
        np.testing.assert_array_equal(sks[f].values, whole_sk[f].values)
        np.testing.assert_array_equal(sks[f].counts, whole_sk[f].counts)
        assert sks[f].na_cnt == whole_sk[f].na_cnt
        assert sks[f].total == whole_sk[f].total
    np.testing.assert_array_equal(pool.finalize(), whole_pool.finalize())


# ---------------------------------------------------------------------------
# End-to-end: stream vs inmem, chunk determinism, sources
# ---------------------------------------------------------------------------

def test_stream_vs_inmem_trees_bit_identical(tmp_path):
    X, y = _make_data()
    csv = _write_csv(tmp_path / "t.csv", X, y)
    b_in, _ = _train_env(csv, "inmem")
    b_st, ds = _train_env(csv, "stream", 700)
    assert b_in.model_to_string() == b_st.model_to_string()
    assert ds.ingest_stats["mode"] == "stream"
    assert ds.ingest_stats["sketch_exact"] is True
    # streamed file datasets never keep a raw matrix
    assert ds.raw_data is None


def test_chunk_boundary_determinism(tmp_path):
    X, y = _make_data(n=3000)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    models = []
    mats = []
    for chunk in (1000, 7000, 256):
        b, ds = _train_env(csv, "stream", chunk)
        models.append(b.model_to_string())
        mats.append(np.asarray(ds.binned.bins).copy())
    assert models[0] == models[1] == models[2]
    np.testing.assert_array_equal(mats[0], mats[1])
    np.testing.assert_array_equal(mats[0], mats[2])


def test_stream_binned_matrix_matches_construct_binned():
    X, y = _make_data(n=2000, F=4)
    ds = lgb.Dataset(X, label=y, params={**PARAMS,
                                         "ingest_mode": "stream",
                                         "ingest_chunk_rows": 333})
    ds.construct()
    ref = lgb.Dataset(X, label=y, params=dict(PARAMS)).construct()
    np.testing.assert_array_equal(np.asarray(ds.binned.bins),
                                  np.asarray(ref.binned.bins))
    for a, b in zip(ds.binned.bin_mappers, ref.binned.bin_mappers):
        np.testing.assert_array_equal(a.upper_bounds, b.upper_bounds)


def test_stream_sequence_and_arrow_sources():
    X, y = _make_data(n=1500, F=4)

    class Seq(lgb.Sequence):
        batch_size = 256

        def __getitem__(self, idx):
            return X[idx]

        def __len__(self):
            return len(X)

    p = {**PARAMS, "ingest_mode": "stream", "ingest_chunk_rows": 400}
    ds = lgb.Dataset(Seq(), label=y, params=p)
    ds.construct()
    ref = lgb.Dataset(X, label=y, params=dict(PARAMS)).construct()
    np.testing.assert_array_equal(np.asarray(ds.binned.bins),
                                  np.asarray(ref.binned.bins))
    pa = pytest.importorskip("pyarrow")
    tbl = pa.table({f"f{i}": X[:, i] for i in range(X.shape[1])})
    ds_a = lgb.Dataset(tbl, label=y, params=p)
    ds_a.construct()
    np.testing.assert_array_equal(np.asarray(ds_a.binned.bins),
                                  np.asarray(ref.binned.bins))


def test_stream_categorical_and_zero_as_missing(tmp_path):
    rng = np.random.RandomState(4)
    n = 3000
    X = np.column_stack([
        np.round(rng.randn(n), 2),
        rng.choice([0, 1, 2, 3, 7], n).astype(float),
        np.where(rng.rand(n) < 0.4, 0.0, np.round(rng.randn(n), 2)),
    ])
    y = (X[:, 0] + (X[:, 1] == 2) > 0).astype(float)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    extra = {"categorical_feature": [1], "zero_as_missing": True}
    b_in, _ = _train_env(csv, "inmem", extra=extra)
    b_st, _ = _train_env(csv, "stream", 500, extra=extra)
    assert b_in.model_to_string() == b_st.model_to_string()


def test_stream_valid_set_binned_with_reference(tmp_path):
    X, y = _make_data(n=2500)
    Xv, yv = _make_data(n=800, seed=19)
    tr_csv = _write_csv(tmp_path / "tr.csv", X, y)
    va_csv = _write_csv(tmp_path / "va.csv", Xv, yv)
    p = {**PARAMS, "ingest_mode": "stream", "ingest_chunk_rows": 600}
    ds = lgb.Dataset(tr_csv, params=p)
    vs = lgb.Dataset(va_csv, reference=ds, params=p)
    bst = lgb.train(p, ds, num_boost_round=5, valid_sets=[vs])
    assert bst.num_trees() == 5
    # valid set binned with the TRAINING mappers
    for a, b in zip(vs.binned.bin_mappers, ds.binned.bin_mappers):
        np.testing.assert_array_equal(np.asarray(a.upper_bounds),
                                      np.asarray(b.upper_bounds))


def test_auto_mode_resolution(tmp_path):
    X, y = _make_data(n=200)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    assert resolve_ingest_mode({}, csv) == "inmem"          # small file
    assert resolve_ingest_mode({"ingest_mode": "stream"}, csv) == "stream"
    assert resolve_ingest_mode({"ingest_cache": "auto"}, csv) == "stream"
    with pytest.raises(LightGBMError):
        resolve_ingest_mode({"ingest_mode": "bogus"}, csv)


def test_libsvm_falls_back_to_inmem(tmp_path):
    path = tmp_path / "t.libsvm"
    rng = np.random.RandomState(1)
    path.write_text("\n".join(
        f"{rng.randint(0, 2)} " + " ".join(
            f"{j}:{rng.rand():.3f}" for j in range(4))
        for _ in range(300)))
    ds = lgb.Dataset(str(path), params={"ingest_mode": "stream",
                                        "verbosity": -1})
    ds.construct()          # in-memory fallback, no crash
    assert ds.binned is not None and ds.num_data_ == 300


# ---------------------------------------------------------------------------
# Binned cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_bit_identical_and_memmap(tmp_path):
    X, y = _make_data()
    csv = _write_csv(tmp_path / "t.csv", X, y)
    extra = {"ingest_cache": "auto"}
    b1, d1 = _train_env(csv, "stream", 700, extra=extra)
    assert d1.ingest_stats.get("cache_written")
    b2, d2 = _train_env(csv, "stream", 700, extra=extra)
    assert d2.ingest_stats["cache_hit"] is True
    assert b1.model_to_string() == b2.model_to_string()
    assert isinstance(d2.binned.bins, np.memmap)
    # raw-vs-cache: also identical to the plain inmem loader (same
    # params in both arms; LGBTPU_INGEST=inmem bypasses the cache)
    b3, d3 = _train_env(csv, "inmem", extra=extra)
    assert d3.ingest_stats is None
    assert b3.model_to_string() == b1.model_to_string()


def test_cache_restores_metadata_without_raw_file(tmp_path):
    X, y = _make_data(n=1200)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    w = np.random.RandomState(0).rand(1200) + 0.5
    (tmp_path / "t.csv.weight").write_text(
        "\n".join(f"{v:.6f}" for v in w))
    extra = {"ingest_cache": "auto"}
    _, d1 = _train_env(csv, "stream", 500, extra=extra)
    _, d2 = _train_env(csv, "stream", 500, extra=extra)
    assert d2.ingest_stats["cache_hit"] is True
    np.testing.assert_allclose(d2.get_weight(), w, rtol=1e-6)
    np.testing.assert_array_equal(d2.get_label(), d1.get_label())


@pytest.mark.parametrize("corrupt,field", [
    ("truncate", "magic"),
    ("garbage", "magic"),
    ("version", "format_version"),
    ("tear", "col_sha256"),
])
def test_cache_corruption_raises_structured_error(tmp_path, corrupt, field):
    X, y = _make_data(n=1000)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    _train_env(csv, "stream", 500, extra={"ingest_cache": "auto"})
    cpath = csv + ".lgbcache"
    blob = bytearray(open(cpath, "rb").read())
    if corrupt == "truncate":
        blob = blob[:8]
    elif corrupt == "garbage":
        blob = b"GARBAGEGARBAGEGA" + bytes(blob[16:])
    elif corrupt == "version":
        blob = b"LGBTPU.CACHE.v9\n" + bytes(blob[16:])
    elif corrupt == "tear":
        blob[40] = blob[40] ^ 0xFF      # flip a bins byte
    open(cpath, "wb").write(bytes(blob))
    with pytest.raises(LightGBMError, match=field):
        _train_env(csv, "stream", 500, extra={"ingest_cache": "read"})
    # auto falls back to raw parsing and rewrites a fresh cache
    b, d = _train_env(csv, "stream", 500, extra={"ingest_cache": "auto"})
    assert d.ingest_stats["cache_hit"] is False
    assert d.ingest_stats.get("cache_written")
    b2, d2 = _train_env(csv, "stream", 500, extra={"ingest_cache": "auto"})
    assert d2.ingest_stats["cache_hit"] is True
    assert b.model_to_string() == b2.model_to_string()


def test_cache_read_requires_existing_cache(tmp_path):
    X, y = _make_data(n=600)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    with pytest.raises(LightGBMError, match="no binned cache"):
        _train_env(csv, "stream", 500, extra={"ingest_cache": "read"})


def test_cache_params_hash_mismatch(tmp_path):
    X, y = _make_data(n=1000)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    _train_env(csv, "stream", 500, extra={"ingest_cache": "auto"})
    with pytest.raises(LightGBMError, match="params_hash"):
        _train_env(csv, "stream", 500,
                   extra={"ingest_cache": "read", "max_bin": 63})
    # data change invalidates too (source signature feeds the hash)
    _write_csv(tmp_path / "t.csv", X + 1.0, y)
    b, d = _train_env(csv, "stream", 500, extra={"ingest_cache": "auto"})
    assert d.ingest_stats["cache_hit"] is False


# ---------------------------------------------------------------------------
# Checkpoint/resume + device ship + memory hygiene
# ---------------------------------------------------------------------------

def test_checkpoint_resume_bit_identity_from_stream(tmp_path):
    X, y = _make_data(n=2500)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    out = str(tmp_path / "m.txt")
    extra = {"snapshot_freq": 4, "output_model": out}
    full, _ = _train_env(csv, "stream", 600, extra=extra, rounds=10)
    snap = str(tmp_path / "m.txt.snapshot_iter_4")
    assert os.path.exists(snap)
    os.environ["LGBTPU_INGEST"] = "stream"
    os.environ["LGBTPU_INGEST_CHUNK"] = "600"
    try:
        p = {**PARAMS, **extra}
        resumed = lgb.train(p, lgb.Dataset(csv, params=p),
                            num_boost_round=10, resume_from=snap)
    finally:
        os.environ.pop("LGBTPU_INGEST", None)
        os.environ.pop("LGBTPU_INGEST_CHUNK", None)
    assert resumed.model_to_string() == full.model_to_string()


def test_chunked_device_ship_matches_oneshot():
    from lightgbm_tpu.device_data import ship_binned_chunks, to_device
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 17, (1000, 3)).astype(np.uint8)
    os.environ["LGBTPU_INGEST_SHIP"] = "1"
    try:
        arr = ship_binned_chunks(bins, n_pad=1024, chunk_rows=300)
    finally:
        os.environ.pop("LGBTPU_INGEST_SHIP", None)
    assert arr.shape == (1024, 3)
    np.testing.assert_array_equal(np.asarray(arr[:1000]), bins)
    np.testing.assert_array_equal(np.asarray(arr[1000:]), 0)


def test_file_dataset_frees_raw_after_train(tmp_path):
    X, y = _make_data(n=800)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    ds = lgb.Dataset(csv, params={"verbosity": -1})     # inmem path
    # construct() alone keeps raw_data: lgb.cv's subset() folds and the
    # linear-tree fitter still read it after construct
    ds.construct()
    assert ds.raw_data is not None
    # once a Booster owns the binned data, the raw matrix (largest host
    # allocation) is dropped
    lgb.train({"objective": "binary", "verbosity": -1}, ds,
              num_boost_round=1)
    assert ds.raw_data is None
    # in-memory containers keep their raw data (get_data contract)
    ds2 = lgb.Dataset(X, label=y, params={"verbosity": -1})
    lgb.train({"objective": "binary", "verbosity": -1}, ds2,
              num_boost_round=1)
    assert ds2.get_data() is not None
    # explicit opt-out wins
    ds3 = lgb.Dataset(csv, params={"verbosity": -1}, free_raw_data=False)
    lgb.train({"objective": "binary", "verbosity": -1}, ds3,
              num_boost_round=1)
    assert ds3.raw_data is not None
    # linear_tree keeps raw: the leaf fitter reads raw feature values
    ds4 = lgb.Dataset(csv, params={"verbosity": -1})
    lgb.train({"objective": "binary", "verbosity": -1,
               "linear_tree": True}, ds4, num_boost_round=1)
    assert ds4.raw_data is not None


def test_ingest_telemetry_gauges_and_spans(tmp_path):
    from lightgbm_tpu import telemetry
    X, y = _make_data(n=1500)
    csv = _write_csv(tmp_path / "t.csv", X, y)
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        _train_env(csv, "stream", 400, rounds=2)
        snap = telemetry.global_registry.snapshot()
        gauges = snap.get("gauges", {})
        assert gauges.get("ingest/rows_per_s", 0) > 0
        assert gauges.get("ingest/peak_rss_bytes", 0) > 0
        names = {e.get("name") for e in telemetry.global_tracer.events}
        assert "ingest/pass1" in names and "ingest/pass2" in names
        assert "ingest/chunk" in names
    finally:
        telemetry.configure(enabled=False, metrics_out="", trace_out="")
        telemetry.reset()


def test_construct_binned_matches_bin_rows_into_chunks():
    """bin_rows_into (the preallocated-buffer chunk fill both streaming
    paths use) is byte-identical to construct_binned, bundles included."""
    from lightgbm_tpu.binning import (bin_rows_into, binned_layout,
                                      find_bin_mappers,
                                      find_feature_groups)
    rng = np.random.RandomState(8)
    n = 2000
    X = np.zeros((n, 6))
    X[:, 0] = rng.randn(n)
    # mutually exclusive sparse columns -> zero EFB conflicts -> bundles
    owner = rng.randint(1, 6, n)
    active = rng.rand(n) < 0.6
    X[np.arange(n)[active], owner[active]] = rng.randn(int(active.sum()))
    mappers = find_bin_mappers(X, max_bin=63, min_data_in_bin=3)
    sample_bins = [mappers[f].transform(X[:, f]) for f in range(6)]
    groups = find_feature_groups(sample_bins, mappers, enable_bundle=True)
    assert any(len(g) > 1 for g in groups), "fixture should bundle"
    ref = construct_binned(X, mappers, groups)
    (og, _, _, fo, _, dtype) = binned_layout(mappers, groups)
    out = np.empty((n, len(og)), dtype)
    for s in range(0, n, 321):
        bin_rows_into(X[s:s + 321], mappers, og, out, s)
    np.testing.assert_array_equal(out, ref.bins)
    np.testing.assert_array_equal(fo, ref.feature_offsets)


# ---------------------------------------------------------------------------
# Distributed streaming ingest (2 real jax.distributed processes)
# ---------------------------------------------------------------------------

_DIST_CHILD = r"""
import os, sys
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
port, rank, data, out = sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4]
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=rank)
import lightgbm_tpu as lgb
os.environ["LGBTPU_INGEST"] = "stream"
os.environ["LGBTPU_INGEST_CHUNK"] = "700"
ds = lgb.Dataset(data)
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "min_data_in_leaf": 5, "tree_learner": "data",
                 "hist_backend": "stream"},
                ds, num_boost_round=5)
assert ds._dist is not None and ds._dist["nproc"] == 2
assert ds.ingest_stats["mode"] == "stream"
assert ds.ingest_stats["sketch_exact"] is True
# each rank parsed ONLY its shard
assert ds.ingest_stats["rows"] < 4000
if rank == 0:
    open(out, "w").write(bst.model_to_string())
"""


@pytest.mark.slow
def test_two_process_stream_ingest(tmp_path,
                                   require_two_process_collectives):
    """Each rank streams only its byte shard; the ONE-collective sketch
    sync must yield the same mappers — and structurally the same model —
    as a single-process streamed run over the whole file."""
    import socket
    import subprocess
    import sys as _sys
    rng = np.random.RandomState(0)
    Xd = rng.randn(4000, 6)
    yd = (Xd[:, 0] + np.sin(Xd[:, 1]) + 0.1 * rng.randn(4000) > 0)
    data = str(tmp_path / "train.csv")
    np.savetxt(data, np.column_stack([yd.astype(float), Xd]),
               delimiter=",", fmt="%.10g")
    out = str(tmp_path / "dist_model.txt")
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    # platform, device count and compile cache stated for the child
    env = child_env("cpu")
    procs = [subprocess.Popen(
        [_sys.executable, "-c", _DIST_CHILD, str(port), str(r), data, out],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=600)[0].decode() for p in procs]
    for p, o in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{o[-4000:]}"

    os.environ["LGBTPU_INGEST"] = "stream"
    os.environ["LGBTPU_INGEST_CHUNK"] = "700"
    try:
        ref_ds = lgb.Dataset(data)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "min_data_in_leaf": 5,
                         "hist_backend": "stream"},
                        ref_ds, num_boost_round=5)
    finally:
        os.environ.pop("LGBTPU_INGEST", None)
        os.environ.pop("LGBTPU_INGEST_CHUNK", None)
    dist_model = open(out).read()
    # same comparison discipline as test_dist_ingest: structural identity
    # with float tolerance (serial-vs-data f32 summation order)
    a = bst.model_to_string().split("\nparameters:")[0].splitlines()
    b = dist_model.split("\nparameters:")[0].splitlines()
    assert len(a) == len(b)
    for xa, xb in zip(a, b):
        if xa == xb:
            continue
        ka, _, va = xa.partition("=")
        kb, _, vb = xb.partition("=")
        assert ka == kb
        if ka == "tree_sizes":
            continue
        fa = np.array([float(t) for t in va.split()])
        fb = np.array([float(t) for t in vb.split()])
        np.testing.assert_allclose(fa, fb, rtol=3e-4, atol=3e-4,
                                   err_msg=ka)
