"""Sampled-vs-masked bit-identity suite (GOSS/bagging row compaction).

The tentpole claim of the row-compaction path (ops/compact.plan_sample_rows
+ the compacted grow programs in ops/grow.py): dropping the out-of-bag rows
from every histogram pass removes ONLY exact-zero work.  The A/B reference
is ``row_compaction=pad`` — the same per-tree stable partition at the FULL
row count, i.e. the dense-mask algorithm on the partitioned layout — and
compacted trees must be BYTE-IDENTICAL to it on every training layout
(binary, NaN bins, categorical, multiclass-batched lockstep, the 4-way CPU
mesh under both ``hist_comms`` modes) — the same model-string A/B
discipline as the PR-5 comms tests.

``row_compaction=off`` (the legacy natural-row-order dense mask) is held to
quality equivalence, not bytes: on CPU the blocked f32 dot accumulates in a
position-dependent order, so re-ordering rows legally drifts last-ulp
(exactly the serial-vs-mesh caveat documented in test_distributed.py).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.utils.log import LightGBMError

from conftest import make_synthetic_binary, make_synthetic_multiclass

N_DEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(N_DEV < 4, reason="needs a >=4-device mesh")

# learning_rate 0.5 keeps the GOSS warmup (no sampling for 1/lr iterations,
# goss.hpp) to 2 iterations so the suite actually exercises sampled trees
GOSS = {"data_sample_strategy": "goss", "learning_rate": 0.5}
BAG = {"bagging_fraction": 0.6, "bagging_freq": 1, "bagging_seed": 5}


def _strip_params(model_str: str) -> str:
    """Model text minus the parameters block (row_compaction differs by
    design; every tree byte must still match)."""
    return model_str.split("\nparameters:")[0]


def _train(params, X, y, mode, rounds=8, **ds_kw):
    p = dict(params, verbosity=-1, num_leaves=15, min_data_in_leaf=5,
             row_compaction=mode)
    bst = lgb.train(p, lgb.Dataset(X, label=y, **ds_kw),
                    num_boost_round=rounds)
    return bst


def _assert_compacted_equal(params, X, y, rounds=8, **ds_kw):
    """auto (compacted) vs pad (dense-mask on the partitioned layout) must
    be byte-equal, and auto must have actually engaged compaction."""
    a = _train(params, X, y, "auto", rounds, **ds_kw)
    p = _train(params, X, y, "pad", rounds, **ds_kw)
    assert a.engine._last_compact_rows > 0, "compaction never engaged"
    assert a.engine._last_sampled_rows > 0
    assert _strip_params(a.model_to_string()) == \
        _strip_params(p.model_to_string())
    return a


# ---------------------------------------------------------------------------
# compacted == dense-mask bit-identity (the tentpole A/B)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_goss_compacted_bit_identical_binary_stream():
    X, y = make_synthetic_binary(n=4000)
    _assert_compacted_equal(dict(GOSS, objective="binary",
                                 hist_backend="stream"), X, y)


@pytest.mark.slow
def test_goss_compacted_bit_identical_nan_bins():
    X, y = make_synthetic_binary(n=4000)
    X = X.copy()
    X[::7, 2] = np.nan                        # MissingType::NaN routing
    _assert_compacted_equal(dict(GOSS, objective="binary",
                                 hist_backend="stream"), X, y)


@pytest.mark.slow
def test_goss_compacted_bit_identical_categorical():
    rs = np.random.RandomState(3)
    X, y = make_synthetic_binary(n=4000)
    X = X.copy()
    X[:, 4] = rs.randint(0, 6, len(X))
    _assert_compacted_equal(dict(GOSS, objective="binary",
                                 hist_backend="stream"), X, y,
                            categorical_feature=[4])


@pytest.mark.slow
def test_goss_compacted_bit_identical_multiclass_batched():
    """The widened K-class lockstep program compacts once per iteration
    (the mask row is shared across classes) and must stay byte-equal."""
    X, y = make_synthetic_multiclass(n=4000, k=3)
    a = _assert_compacted_equal(
        dict(GOSS, objective="multiclass", num_class=3,
             hist_backend="stream"), X, y, rounds=6)
    assert a.engine._mc_batched_last


def test_bagging_compacted_bit_identical_stream():
    X, y = make_synthetic_binary(n=4000)
    _assert_compacted_equal(dict(BAG, objective="binary",
                                 hist_backend="stream"), X, y)


@pytest.mark.slow
def test_pad_mode_unaligned_row_count():
    """n=4500 Dataset-pads to 4608 — NOT a multiple of the stream kernel
    block (1024): pad mode must round its full-row capacity up to the
    block instead of handing the grower an unaligned count (regression:
    ValueError mid-training for ~3 of 4 dataset sizes)."""
    X, y = make_synthetic_binary(n=4500)
    _assert_compacted_equal(dict(GOSS, objective="binary",
                                 hist_backend="stream"), X, y)


@pytest.mark.slow
def test_goss_compacted_bit_identical_segsum():
    """Contraction/segsum backend (the CPU default): per-tree partition
    plan + O(sampled) histogram builds, same byte-equality contract."""
    X, y = make_synthetic_binary(n=4000)
    _assert_compacted_equal(dict(GOSS, objective="binary",
                                 hist_backend="segsum"), X, y)


@needs_mesh
@pytest.mark.parametrize("comms", ["psum", "reduce_scatter"])
@pytest.mark.slow
def test_goss_compacted_bit_identical_mesh_4dev(comms, monkeypatch):
    """4-way data-parallel mesh: every device stable-partitions its OWN
    row shard to the same static capacity (the capacity covers the
    fullest shard), under both histogram collectives.  The GOSS
    threshold itself is a global sort statistic, so the sampled set is
    shard-layout-independent.  256-row kernel blocks keep the per-shard
    slice several blocks deep at test scale (compaction only engages
    when it can actually drop whole blocks)."""
    monkeypatch.setenv("LGBTPU_BLOCK_ROWS", "256")
    X, y = make_synthetic_binary(n=4000)
    p = dict(GOSS, objective="binary", hist_backend="stream",
             tree_learner="data", mesh_shape="data:4", hist_comms=comms)
    a = _assert_compacted_equal(p, X, y)
    assert a.engine._mesh_stream
    assert a.engine._grow_params.hist_comms == comms


@needs_mesh
def test_goss_compacted_bit_identical_uneven_shards_mesh_4dev(monkeypatch):
    """4-way mesh, GOSS, shards whose in-bag counts differ by more than a
    kernel block: a third of shard 0's rows weigh three times as much (so
    they dominate the global top_rate cut), none of shard 3's.  Every
    device's passes stop at its OWN view's live row blocks, so the devices
    stream different row counts of one histogram; the model must equal
    the same views streamed whole (the passes before row_blocks) and the
    pad reference."""
    import lightgbm_tpu.ops.compact as compact
    T = 256
    monkeypatch.setenv("LGBTPU_BLOCK_ROWS", str(T))
    X, y = make_synthetic_binary(n=4000)
    rs = np.random.RandomState(0)
    heavy = np.concatenate([rs.rand(1000) < share
                            for share in (0.33, 0.2, 0.08, 0.0)])
    w = np.where(heavy, 3.0, 1.0)
    p = dict(GOSS, objective="binary", hist_backend="stream",
             tree_learner="data", mesh_shape="data:4")
    placed, live_row_blocks = [], compact.live_row_blocks

    def spy(count, block):
        jax.debug.callback(lambda c: placed.append(np.asarray(c)), count)
        return live_row_blocks(count, block)

    def whole(count, block):
        return jnp.full_like(count, capacity // block)

    monkeypatch.setattr(compact, "live_row_blocks", spy)
    jax.clear_caches()
    a = _train(p, X, y, "auto", weight=w)
    capacity = a.engine._last_compact_rows
    assert capacity == 2 * T and not a.engine._compact_overflow
    counts = np.array(placed)
    assert counts.shape[1] == 4 and counts.max() <= capacity
    assert (counts.max(1) - counts.min(1) > T).any()
    assert (-(-counts // T) == 1).any() and (-(-counts // T) == 2).any()
    monkeypatch.setattr(compact, "live_row_blocks", whole)
    jax.clear_caches()
    full = _train(p, X, y, "auto", weight=w)
    monkeypatch.setattr(compact, "live_row_blocks", live_row_blocks)
    jax.clear_caches()
    pad = _train(p, X, y, "pad", weight=w)
    assert full.engine._last_compact_rows == capacity
    assert pad.engine._last_compact_rows > capacity
    assert _strip_params(a.model_to_string()) == \
        _strip_params(full.model_to_string()) == \
        _strip_params(pad.model_to_string())


@needs_mesh
@pytest.mark.slow
def test_bagging_compacted_bit_identical_mesh_4dev(monkeypatch):
    monkeypatch.setenv("LGBTPU_BLOCK_ROWS", "256")
    X, y = make_synthetic_binary(n=4000)
    p = dict(BAG, objective="binary", hist_backend="stream",
             tree_learner="data", mesh_shape="data:4")
    # bagging_fraction 0.6 sits under the 75% engagement threshold
    _assert_compacted_equal(p, X, y)


# ---------------------------------------------------------------------------
# compacted vs legacy natural-order dense mask: quality equivalence
# ---------------------------------------------------------------------------

def test_goss_compacted_matches_legacy_quality():
    X, y = make_synthetic_binary(n=4000)
    params = dict(GOSS, objective="binary", hist_backend="stream")
    a = _train(params, X, y, "auto", rounds=10)
    o = _train(params, X, y, "off", rounds=10)
    assert o.engine._last_compact_rows == 0
    pa = np.asarray(a.predict(X))
    po = np.asarray(o.predict(X))
    # same algorithm, row order aside: predictions agree to f32 noise
    np.testing.assert_allclose(pa, po, rtol=2e-3, atol=2e-3)
    acc_a = np.mean((pa > 0.5) == y)
    acc_o = np.mean((po > 0.5) == y)
    assert abs(acc_a - acc_o) < 0.02
    assert acc_a > 0.7


def test_compaction_skips_when_not_worth_it():
    """A 0.9 bagging fraction saves <25% of rows — the engine must stay on
    the dense path rather than pay the partition + route-only overhead."""
    X, y = make_synthetic_binary(n=3000)
    p = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
         "hist_backend": "stream", "bagging_fraction": 0.9,
         "bagging_freq": 1}
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
    assert bst.engine._last_compact_rows == 0
    assert bst.engine._last_sampled_rows > 0     # telemetry still counted


def test_env_override_forces_mode():
    X, y = make_synthetic_binary(n=3000)
    params = dict(GOSS, objective="binary", hist_backend="stream",
                  verbosity=-1, num_leaves=15)
    os.environ["LGBTPU_COMPACT"] = "off"
    try:
        bst = lgb.train(dict(params, row_compaction="auto"),
                        lgb.Dataset(X, label=y), num_boost_round=4)
        assert bst.engine._last_compact_rows == 0
    finally:
        del os.environ["LGBTPU_COMPACT"]


# ---------------------------------------------------------------------------
# checkpoint/resume + rollback: sampling RNG position is the iteration
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_resume_bit_identity_goss_compacted(tmp_path):
    """Resume mid-run with GOSS sampling + compaction active: the
    strategy's RNG stream position is derived from the iteration counter
    the snapshot stores, so the continued run is byte-identical."""
    X, y = make_synthetic_binary(n=3000)
    M = tmp_path / "goss.txt"
    params = dict(GOSS, objective="binary", hist_backend="stream",
                  num_leaves=15, min_data_in_leaf=5, verbosity=-1,
                  snapshot_freq=4, output_model=str(M))
    full = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    assert full.engine._last_compact_rows > 0    # sampled trees were grown
    resumed = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                        resume_from=str(M) + ".snapshot_iter_4")
    assert resumed.model_to_string() == full.model_to_string()


def test_resume_bit_identity_bagging_midepoch_compacted(tmp_path):
    """bagging_freq=2 with a snapshot INSIDE a bagging epoch (iter 3):
    the resumed run must regenerate the epoch's cached mask, not draw a
    fresh one — the iteration-keyed cache fix."""
    X, y = make_synthetic_binary(n=3000)
    M = tmp_path / "bag.txt"
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "verbosity": -1, "bagging_fraction": 0.6, "bagging_freq": 2,
              "hist_backend": "stream", "snapshot_freq": 3,
              "output_model": str(M)}
    full = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8)
    assert full.engine._last_compact_rows > 0
    resumed = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=8,
                        resume_from=str(M) + ".snapshot_iter_3")
    assert resumed.model_to_string() == full.model_to_string()


def test_bagging_mask_cache_iteration_keyed():
    """Regression for the `_mask_iter` staleness bug: with bagging_freq>1
    the cache used to refresh only on `iteration % freq == 0`, so visiting
    iterations out of order (rollback_one_iter) reused a LATER epoch's
    mask.  The cache is now keyed on the bagging epoch."""
    from lightgbm_tpu.models.sample_strategy import BaggingSampleStrategy
    cfg = Config.from_params({"bagging_fraction": 0.5, "bagging_freq": 2,
                              "bagging_seed": 7})
    g = jnp.ones(512)
    h = jnp.ones(512)
    s = BaggingSampleStrategy(cfg, 512)
    m4 = np.asarray(s.sample(4, g, h)[0])        # epoch 2
    m3 = np.asarray(s.sample(3, g, h)[0])        # rollback into epoch 1
    fresh = BaggingSampleStrategy(cfg, 512)
    m3_fresh = np.asarray(fresh.sample(3, g, h)[0])
    assert np.array_equal(m3, m3_fresh)
    assert not np.array_equal(m4, m3)            # epochs genuinely differ


# ---------------------------------------------------------------------------
# config validation (reference: Config::CheckParamConflict)
# ---------------------------------------------------------------------------

def test_goss_rate_sum_rejected():
    with pytest.raises(LightGBMError, match=r"top_rate \+ other_rate"):
        Config.from_params({"data_sample_strategy": "goss",
                            "top_rate": 0.9, "other_rate": 0.2})


def test_goss_negative_rate_rejected():
    with pytest.raises(LightGBMError, match="non-negative"):
        Config.from_params({"boosting": "goss", "top_rate": -0.1})


def test_goss_with_bagging_rejected():
    with pytest.raises(LightGBMError, match="bagging"):
        Config.from_params({"data_sample_strategy": "goss",
                            "bagging_freq": 1, "bagging_fraction": 0.5})


def test_row_compaction_value_validated():
    with pytest.raises(LightGBMError, match="row_compaction"):
        Config.from_params({"row_compaction": "sometimes"})


def test_goss_without_bagging_accepted():
    cfg = Config.from_params({"data_sample_strategy": "goss",
                              "top_rate": 0.3, "other_rate": 0.2})
    assert cfg.top_rate == 0.3


def test_goss_with_posneg_bagging_rejected():
    """Balanced bagging (pos/neg fractions < 1) is active bagging too —
    GOSS must reject it, not silently drop the balancing request."""
    with pytest.raises(LightGBMError, match="bagging"):
        Config.from_params({"data_sample_strategy": "goss",
                            "bagging_freq": 1, "bagging_fraction": 1.0,
                            "pos_bagging_fraction": 0.5})


def test_rollback_invalidates_count_cache():
    """rollback_one_iter only f32-approximately restores the score, so a
    re-run of the iteration may draw a different GOSS mask under the same
    mask_key — the cached in-bag counts must be dropped (a stale
    undersized capacity would silently truncate in-bag rows)."""
    X, y = make_synthetic_binary(n=3000)
    p = dict(GOSS, objective="binary", hist_backend="stream",
             verbosity=-1, num_leaves=15)
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=5)
    eng = bst.engine
    assert eng._sample_count_cache is not None
    eng.rollback_one_iter()
    assert eng._sample_count_cache is None
    assert not eng.train_one_iter()          # retrains cleanly
    assert eng._sample_count_cache is not None


def test_goss_warmup_counts_cached_once():
    """All warmup iterations share one all-ones mask — mask_key returns a
    constant during warmup so the engine syncs the count once, not per
    iteration."""
    from lightgbm_tpu.models.sample_strategy import GOSSStrategy
    cfg = Config.from_params({"data_sample_strategy": "goss",
                              "learning_rate": 0.1})
    s = GOSSStrategy(cfg, 100)
    assert s.mask_key(0) == s.mask_key(9) == -1     # 1/lr = 10 warmup iters
    assert s.mask_key(10) == 10
    assert s.mask_key(11) != s.mask_key(12)


def test_goss_strategy_selection_case_insensitive():
    """Config validation matches 'GOSS' case-insensitively — the strategy
    factory must agree, or a non-lowercase spelling is blocked from
    bagging params while silently never running GOSS."""
    from lightgbm_tpu.models.sample_strategy import (GOSSStrategy,
                                                     create_sample_strategy)
    cfg = Config.from_params({"data_sample_strategy": "GOSS"})
    assert isinstance(create_sample_strategy(cfg, 100), GOSSStrategy)


def test_goss_with_inactive_bagging_accepted():
    """bagging_fraction=1.0 leaves bagging a no-op — the reference's
    CheckParamConflict only fires on an ACTIVE bagging config, so this
    param set must keep constructing (compatibility with existing
    configs that carry a vestigial bagging_freq)."""
    cfg = Config.from_params({"data_sample_strategy": "goss",
                              "bagging_freq": 5, "bagging_fraction": 1.0})
    assert cfg.bagging_freq == 5


def test_env_override_typo_rejected():
    """An LGBTPU_COMPACT typo bypasses Config validation — it must raise
    at train time, not silently run as 'auto'."""
    X, y = make_synthetic_binary(n=3000)
    params = dict(GOSS, objective="binary", hist_backend="stream",
                  verbosity=-1, num_leaves=15)
    os.environ["LGBTPU_COMPACT"] = "bogus"
    try:
        with pytest.raises(LightGBMError, match="LGBTPU_COMPACT"):
            lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    finally:
        del os.environ["LGBTPU_COMPACT"]


# ---------------------------------------------------------------------------
# telemetry: per-iteration sampled_rows
# ---------------------------------------------------------------------------

def test_sampled_rows_telemetry_field():
    from lightgbm_tpu.telemetry import global_registry
    global_registry.reset()
    X, y = make_synthetic_binary(n=3000)
    p = dict(GOSS, objective="binary", hist_backend="stream",
             num_leaves=15, verbosity=-1, telemetry=True)
    lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=6)
    recs = [r for r in global_registry.records
            if r.get("event") == "iteration"]
    assert recs, "no iteration records"
    last = recs[-1]
    assert 0 < last["sampled_rows"] < len(X)
    assert last["compact_rows"] > 0
    # warmup iterations (no sampling yet) report the full row count
    assert recs[0]["sampled_rows"] >= last["sampled_rows"]
