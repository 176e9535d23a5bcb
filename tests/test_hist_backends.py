"""The histogram formulations behind ``hist_backend`` / env
``LGBTPU_HIST_BACKEND``, and the options that ride on ``stream``.

  * the rule — ``ops.histogram.resolve_hist_backend`` (a requested name to
    the formulation that runs) and ``hist_backend_refusal`` (which jobs a
    formulation cannot run), held row by row on the pure functions, plus
    the engine-level refusals; ``segsum`` is the reference every trained
    model here is compared against.
  * ``hist_packed_width`` 16/8 — the quantized grad/hess pair rides one
    int32/int16 wire lane through the mesh collective, halving/quartering
    psum_scatter bytes.  Kernel arithmetic stays exact int32; only the
    collective seam packs.  w16 is drift-free at test scale; w8 is the
    documented-ulp opt-in.
  * ``route_fusion`` — GOSS+stream fusion: per-round full-data route-only
    passes are replaced by ONE post-growth replay launch
    (pallas/stream_kernel.route_replay), bit-identical by construction
    (the replay kernel shares _route_step with the fused route+hist
    kernel).  hist/route_only_passes telemetry is the A/B signal.

GOSS warmup gotcha baked into every sampled test here: sampling starts
after ceil(1/learning_rate) iterations (sample_strategy._is_warmup), so
fusion/compaction only engages with learning_rate=0.5 and >=4 rounds.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
import lightgbm_tpu.telemetry as tel
from lightgbm_tpu.ops import histogram as hist_ops
from lightgbm_tpu.ops.histogram import (build_histograms,
                                        hist_backend_refusal,
                                        resolve_hist_backend)
from lightgbm_tpu.utils.log import LightGBMError

from conftest import make_synthetic_binary, make_synthetic_regression

N_DEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(N_DEV < 4, reason="needs a >=4-device mesh")


def _strip_params(model_str: str) -> str:
    """Model text minus the parameters block (backend knobs differ by
    design; every tree byte must still match)."""
    return model_str.split("\nparameters:")[0]


def _datasets():
    """Identity-matrix layouts: numeric+NaN, categorical, EFB-bundled."""
    rs = np.random.RandomState(7)
    out = []

    X, y = make_synthetic_binary(n=1500, f=8)
    X = X.copy()
    X[::13, 2] = np.nan                       # MissingType::NaN routing
    out.append(("binary_nan", {"objective": "binary"},
                dict(data=X, label=y), {}))

    Xr, yr = make_synthetic_regression(n=1200, f=8, seed=7)
    Xr = Xr.copy()
    Xr[:, 3] = rs.randint(0, 6, len(Xr))      # categorical column
    out.append(("reg_cat", {"objective": "regression"},
                dict(data=Xr, label=yr), {"categorical_feature": [3]}))

    # sparse one-hot-ish block -> EFB bundles several features per group
    Xs = np.zeros((1000, 12))
    Xs[:, :4] = rs.randn(1000, 4)
    hot = rs.randint(4, 12, 1000)
    Xs[np.arange(1000), hot] = 1.0
    ys = Xs[:, 0] + 2.0 * (hot == 5) - (hot == 9) + 0.05 * rs.randn(1000)
    out.append(("reg_efb", {"objective": "regression"},
                dict(data=Xs, label=ys), {}))
    return out


def _train(params, data_kw, ds_kw, backend, rounds=6, **extra):
    p = dict(params, num_leaves=15, verbosity=-1, min_data_in_leaf=5,
             max_bin=63, hist_backend=backend, hist_precision="single",
             **extra)
    ds = lgb.Dataset(data_kw["data"], label=data_kw["label"],
                     weight=data_kw.get("weight"), **ds_kw)
    return lgb.train(p, ds, num_boost_round=rounds)


# ---------------------------------------------------------------------------
# the rule, row by row, on the pure functions
# ---------------------------------------------------------------------------

_REFUSED = "refused"
# (on a TPU, mesh kind, requested, stream kernel takes the job) -> resolved
_RESOLUTION = [
    # off the chip auto is the reference, under every mesh
    (False, "none", "auto", True, "segsum"),
    (False, "none", "segsum", True, "segsum"),
    (False, "none", "onehot", True, "onehot"),
    (False, "none", "stream", True, "stream"),
    (False, "rows", "auto", True, "segsum"),
    (False, "rows", "segsum", True, "segsum"),
    (False, "rows", "onehot", True, "onehot"),
    (False, "rows", "stream", True, "stream"),
    (False, "feature", "auto", True, "segsum"),
    (False, "feature", "segsum", True, "segsum"),
    (False, "feature", "onehot", True, "onehot"),
    (False, "feature", "stream", True, _REFUSED),
    (False, "rows_x_feature", "auto", True, "segsum"),
    (False, "rows_x_feature", "segsum", True, "segsum"),
    (False, "rows_x_feature", "onehot", True, "onehot"),
    (False, "rows_x_feature", "stream", True, _REFUSED),
    # the voting learner's own grower ignores the request
    (False, "voting", "auto", True, "segsum"),
    (False, "voting", "segsum", True, "segsum"),
    (False, "voting", "onehot", True, "segsum"),
    (False, "voting", "stream", True, "segsum"),
    # on the chip auto is stream wherever rows alone are sharded
    (True, "none", "auto", True, "stream"),
    (True, "none", "segsum", True, "segsum"),
    (True, "none", "onehot", True, "onehot"),
    (True, "none", "stream", True, "stream"),
    (True, "rows", "auto", True, "stream"),
    (True, "rows", "segsum", True, "segsum"),
    (True, "rows", "onehot", True, "onehot"),
    (True, "rows", "stream", True, "stream"),
    (True, "feature", "auto", True, "onehot"),
    (True, "feature", "segsum", True, "segsum"),
    (True, "feature", "onehot", True, "onehot"),
    (True, "feature", "stream", True, _REFUSED),
    (True, "rows_x_feature", "auto", True, "onehot"),
    (True, "rows_x_feature", "segsum", True, "segsum"),
    (True, "rows_x_feature", "onehot", True, "onehot"),
    (True, "rows_x_feature", "stream", True, _REFUSED),
    (True, "voting", "auto", True, "onehot"),
    (True, "voting", "segsum", True, "onehot"),
    (True, "voting", "onehot", True, "onehot"),
    (True, "voting", "stream", True, "onehot"),
    # the stream kernel does not take the job (over 2,048 leaves, over 255
    # splits a round, bins too wide for a tile): auto falls to onehot — the
    # row that read `pallas` until its kernel left — and a name still holds
    (True, "none", "auto", False, "onehot"),
    (True, "rows", "auto", False, "onehot"),
    (True, "none", "stream", False, "stream"),
    (False, "none", "auto", False, "segsum"),
]


@pytest.mark.parametrize(
    "tpu,mesh,requested,fits,expect", _RESOLUTION,
    ids=[f"{'tpu' if t else 'cpu'}-{m}-{r}{'' if f else '-nofit'}"
         for t, m, r, f, _ in _RESOLUTION])
def test_hist_backend_resolution(tpu, mesh, requested, fits, expect):
    kw = dict(tpu=tpu, mesh=mesh, stream_fits=fits)
    if expect is _REFUSED:
        with pytest.raises(LightGBMError, match="group sharding"):
            resolve_hist_backend(requested, **kw)
    else:
        assert resolve_hist_backend(requested, **kw) == expect


# (backend, job) -> a word of the refusal, or None where it runs
_CAPABILITY = [
    ("stream", dict(double=True), "double"),
    ("segsum", dict(double=True), None),
    ("onehot", dict(double=True), None),
    ("stream", dict(mesh="feature"), "group sharding"),
    ("stream", dict(mesh="rows_x_feature"), "group sharding"),
    ("stream", dict(mesh="rows"), None),
    ("stream", dict(mesh="rows", compact=True), None),
    ("segsum", dict(mesh="rows", compact=True), "compaction"),
    ("onehot", dict(mesh="rows_x_feature", compact=True), "compaction"),
    ("onehot", dict(mesh="feature", compact=True), None),
    ("segsum", dict(mesh="voting", compact=True), None),
    ("segsum", dict(compact=True), None),
    ("stream", dict(compact=True), None),
]


@pytest.mark.parametrize(
    "backend,job,word", _CAPABILITY,
    ids=[f"{b}-" + "-".join(f"{k}={v}" for k, v in j.items())
         for b, j, _ in _CAPABILITY])
def test_hist_backend_capability(backend, job, word):
    why = hist_backend_refusal(backend, **job)
    if word is None:
        assert why is None
        hist_ops.check_hist_backend(backend, **job)
    else:
        assert word in why
        with pytest.raises(LightGBMError, match=word):
            hist_ops.check_hist_backend(backend, **job)


def _op_inputs(n=4096, g=4, bmax=32, s=8, seed=0):
    rs = np.random.RandomState(seed)
    bins = jnp.asarray(rs.randint(0, bmax, size=(n, g)), jnp.uint8)
    slot = jnp.asarray(rs.randint(-1, s, size=(n,)), jnp.int32)
    grad = jnp.asarray(rs.randn(n), jnp.float32)
    hess = jnp.asarray(rs.rand(n) + 0.1, jnp.float32)
    cnt = jnp.asarray((rs.rand(n) > 0.1), jnp.float32)
    return bins, slot, grad, hess, cnt, s, bmax


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
def test_op_auto_is_the_engines_auto(tpu, monkeypatch):
    """build_histograms(backend="auto") asks the engine's rule (with no
    stream kernel on offer), so the two cannot disagree: the histogram is
    byte-for-byte the one the resolved name builds."""
    monkeypatch.setattr(hist_ops, "on_tpu", lambda: tpu)
    name = resolve_hist_backend("auto", tpu=tpu, mesh="none",
                                stream_fits=False)
    assert name == ("onehot" if tpu else "segsum")
    bins, slot, grad, hess, cnt, s, bmax = _op_inputs(n=1024)
    h_auto = build_histograms(bins, slot, grad, hess, cnt, s, bmax)
    h_name = build_histograms(bins, slot, grad, hess, cnt, s, bmax,
                              backend=name)
    assert np.array_equal(np.asarray(h_auto), np.asarray(h_name))
    k_auto = hist_ops.build_histograms_k(
        bins, slot[None], grad[None], hess[None], cnt, 1, s, bmax)
    np.testing.assert_allclose(np.asarray(k_auto[0]), np.asarray(h_auto),
                               rtol=1e-5, atol=1e-5)


def test_op_refuses_stream_and_unknown_names():
    bins, slot, grad, hess, cnt, s, bmax = _op_inputs(n=256)
    with pytest.raises(ValueError, match="fused"):
        build_histograms(bins, slot, grad, hess, cnt, s, bmax,
                         backend="stream")
    with pytest.raises(LightGBMError, match="unknown hist_backend"):
        build_histograms(bins, slot, grad, hess, cnt, s, bmax,
                         backend="vector")


# ---------------------------------------------------------------------------
# trained-model layout matrix (NaN routing, a categorical column, EFB
# bundles) on the formulations that remain, against the reference
# ---------------------------------------------------------------------------

_STRUCTURE = ("num_leaves", "split_feature", "threshold", "decision_type",
              "left_child", "right_child", "leaf_count")
# stream at one split a round grows segsum's trees when the gradients are
# quantized (exact int32 sums on both sides); at the default 64 splits a
# round it grows other trees by design
_BACKEND_EXTRA = {
    "onehot": {},
    "stream": {"use_quantized_grad": True, "num_grad_quant_bins": 64,
               "max_splits_per_round": 1},
}
_GOSS = {"data_sample_strategy": "goss", "top_rate": 0.2, "other_rate": 0.2,
         "learning_rate": 0.5}


def _assert_same_structure(a, b, X, atol=1e-5):
    ta, tb = a.engine.models, b.engine.models
    assert len(ta) == len(tb)
    for i, (x, y) in enumerate(zip(ta, tb)):
        for f in _STRUCTURE:
            assert np.array_equal(getattr(x, f), getattr(y, f)), (i, f)
    np.testing.assert_allclose(b.predict(X), a.predict(X), rtol=0, atol=atol)


@pytest.mark.parametrize("backend", sorted(_BACKEND_EXTRA))
@pytest.mark.parametrize("name,params,data_kw,ds_kw", _datasets(),
                         ids=[d[0] for d in _datasets()])
def test_model_structure_vs_segsum(name, params, data_kw, ds_kw, backend):
    extra = _BACKEND_EXTRA[backend]
    a = _train(params, data_kw, ds_kw, "segsum", **extra)
    b = _train(params, data_kw, ds_kw, backend, **extra)
    assert b.engine._grow_params.hist_backend == backend
    _assert_same_structure(a, b, data_kw["data"])


@pytest.mark.parametrize("backend", sorted(_BACKEND_EXTRA))
def test_model_structure_vs_segsum_goss(backend):
    X, y = make_synthetic_binary(n=2000, f=8)
    p = dict({"objective": "binary"}, **_GOSS)
    extra = _BACKEND_EXTRA[backend]
    a = _train(p, dict(data=X, label=y), {}, "segsum", **extra)
    b = _train(p, dict(data=X, label=y), {}, backend, **extra)
    assert b.engine._last_compact_rows > 0   # sampling actually engaged
    # same trees; the f32 leaf values' last digits, amplified by GOSS's
    # (1 - top_rate) / other_rate = 4 and learning_rate 0.5, move
    # probabilities by up to 3.8e-5 here, so 1e-5 does not hold
    _assert_same_structure(a, b, X, atol=1e-4)


@pytest.mark.slow
def test_checkpoint_resume_identity_per_backend(tmp_path):
    """Straight-through vs save_model+init_model continuation must agree
    under every CPU backend (text round-trip requantizes leaf values, so
    allclose rather than byte equality — test_continued.py's contract)."""
    X, y = make_synthetic_binary(n=1200, f=8)
    Xv = X[:200]
    for backend in ("segsum", "onehot", "stream"):
        params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
                  "min_data_in_leaf": 5, "max_bin": 63,
                  "hist_backend": backend, "hist_precision": "single"}
        ds = lgb.Dataset(X, label=y)
        full = lgb.train(params, ds, num_boost_round=8)
        half = lgb.train(params, ds, num_boost_round=4)
        path = str(tmp_path / f"ckpt_{backend}.txt")
        half.save_model(path)
        resumed = lgb.train(params, lgb.Dataset(X, label=y),
                            num_boost_round=4, init_model=path)
        np.testing.assert_allclose(resumed.predict(Xv), full.predict(Xv),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"backend={backend}")


# ---------------------------------------------------------------------------
# engine-first validation + env overrides
# ---------------------------------------------------------------------------

def _tiny():
    X, y = make_synthetic_binary(n=400, f=4)
    return lgb.Dataset(X, label=y)


def _expect_error(params, match):
    with pytest.raises(LightGBMError, match=match):
        lgb.train(dict(params, verbosity=-1, num_leaves=7), _tiny(),
                  num_boost_round=1)


def test_invalid_backend_rejected_before_training():
    _expect_error({"objective": "binary", "hist_backend": "vector"},
                  "hist_backend")


@pytest.mark.parametrize("how", ["param", "env"])
@pytest.mark.parametrize("name", ["pallas", "scatter"])
def test_removed_backends_are_refused(name, how, monkeypatch):
    """The two formulations that left with their kernels are names like
    any other unknown one, by parameter and by LGBTPU_HIST_BACKEND."""
    params = {"objective": "binary"}
    if how == "env":
        monkeypatch.setenv("LGBTPU_HIST_BACKEND", name)
    else:
        params["hist_backend"] = name
    _expect_error(params, f"unknown hist_backend='{name}'")


@needs_mesh
def test_stream_rejects_feature_parallel():
    _expect_error({"objective": "binary", "hist_backend": "stream",
                   "tree_learner": "feature"}, "group sharding")


def test_stream_rejects_double_precision():
    _expect_error({"objective": "binary", "hist_backend": "stream",
                   "hist_precision": "double"}, "double")


def test_packed_width_validation():
    _expect_error({"objective": "binary", "hist_packed_width": 12},
                  "hist_packed_width")
    _expect_error({"objective": "binary", "hist_packed_width": 16},
                  "use_quantized_grad")
    _expect_error({"objective": "regression", "hist_packed_width": 16,
                   "use_quantized_grad": True, "linear_tree": True},
                  "linear")


def test_route_fusion_validation():
    _expect_error({"objective": "binary", "route_fusion": "maybe"},
                  "route_fusion")


def test_env_override_hist_backend(monkeypatch):
    monkeypatch.setenv("LGBTPU_HIST_BACKEND", "onehot")
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "num_leaves": 7}, _tiny(), num_boost_round=1)
    assert bst.engine._grow_params.hist_backend == "onehot"
    monkeypatch.setenv("LGBTPU_HIST_BACKEND", "vector")
    with pytest.raises(LightGBMError, match="hist_backend"):
        lgb.train({"objective": "binary", "verbosity": -1,
                   "num_leaves": 7}, _tiny(), num_boost_round=1)


def test_env_override_packed_width(monkeypatch):
    monkeypatch.setenv("LGBTPU_HIST_PACKED_WIDTH", "16")
    X, y = make_synthetic_binary(n=400, f=4)
    bst = lgb.train({"objective": "binary", "verbosity": -1, "num_leaves": 7,
                     "use_quantized_grad": True},
                    lgb.Dataset(X, label=y), num_boost_round=1)
    assert bst.engine._grow_params.hist_packed_width == 16


# ---------------------------------------------------------------------------
# GOSS+stream fusion (single device)
# ---------------------------------------------------------------------------

_FUSION_PARAMS = {
    "objective": "binary", "num_leaves": 127, "verbosity": -1,
    "min_data_in_leaf": 5, "hist_backend": "stream",
    "data_sample_strategy": "goss", "top_rate": 0.1, "other_rate": 0.1,
    "learning_rate": 0.5, "max_splits_per_round": 64,
}


def _train_fusion(X, y, fusion, rounds=6, **extra):
    p = dict(_FUSION_PARAMS, route_fusion=fusion, **extra)
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=rounds)


def test_route_fusion_bitwise_identity():
    """Fusion on vs off grows byte-identical models: the replay kernel
    shares _route_step with the fused route+hist kernel, and unused
    zero-table buffer rows are exact no-op steps."""
    X, y = make_synthetic_binary(n=4096, f=10)
    a = _train_fusion(X, y, "off")
    b = _train_fusion(X, y, "on")
    assert a.engine._last_compact_rows > 0   # GOSS past warmup
    assert b.engine._route_only_passes_per_tree() == 1       # fused
    assert a.engine._route_only_passes_per_tree() > 1        # per-round
    assert _strip_params(a.model_to_string()) == \
        _strip_params(b.model_to_string())


@pytest.mark.slow
def test_route_fusion_gate_respects_categoricals():
    # categorical trees carry bitset overlays the round tables don't
    # encode -> the fusion gate must fall back to per-round routing
    rs = np.random.RandomState(3)
    X, y = make_synthetic_binary(n=4096, f=10)
    X = X.copy()
    X[:, 1] = rs.randint(0, 12, len(X))
    p = dict(_FUSION_PARAMS, route_fusion="on")
    bst = lgb.train(p, lgb.Dataset(X, label=y, categorical_feature=[1]),
                    num_boost_round=6)
    assert bst.engine._grow_params.has_categorical
    assert bst.engine._route_only_passes_per_tree() > 1


def test_route_only_passes_telemetry():
    tel.reset()
    tel.configure(enabled=True)
    try:
        X, y = make_synthetic_binary(n=4096, f=10)
        bst = _train_fusion(X, y, "off", telemetry=True)
        snap = tel.global_registry.snapshot()
        assert snap["counters"]["hist/route_only_passes"] > 0
        iters = [r for r in tel.global_registry.records
                 if r.get("event") == "iteration"]
        assert iters and all(r["hist_backend"] == "stream" for r in iters)
        # post-warmup iterations route per round; fused run drops to 1/tree
        per_tree = bst.engine._route_only_passes_per_tree()
        assert per_tree > 1
        assert any(r["route_only_passes"] == per_tree for r in iters)
    finally:
        tel.disable()
        tel.reset()
        tel.configure(enabled=False, metrics_out="", trace_out="")


# ---------------------------------------------------------------------------
# mesh tier: packed wire widths + fused replay under shard_map
# ---------------------------------------------------------------------------

def _train_mesh(params, X, y, rounds=6):
    p = dict(params, verbosity=-1, min_data_in_leaf=5,
             tree_learner="data", hist_backend="stream")
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=rounds)


_PACK_BASE = {"objective": "binary", "num_leaves": 31,
              "use_quantized_grad": True, "num_grad_quant_bins": 16}


@needs_mesh
@pytest.mark.slow
@pytest.mark.parametrize("comms", ["psum", "reduce_scatter"])
def test_packed16_mesh_identity_and_bytes(comms):
    """int16 packed wire halves the per-round collective payload and (at
    this scale/quant config) stays byte-identical to the exact int32 wire
    under BOTH hist_comms modes; int8 quarters the bytes (documented-ulp
    — structural sanity only)."""
    X, y = make_synthetic_binary(n=4096, f=10)
    models, bytes_ = {}, {}
    for w in (32, 16, 8):
        p = dict(_PACK_BASE, hist_comms=comms, hist_packed_width=w)
        bst = _train_mesh(p, X, y)
        cm = bst.engine._comms_model()
        assert cm["packed_width"] == w
        models[w], bytes_[w] = bst, cm["per_round_bytes"]
    # only the histogram payload packs; reduce_scatter also all_gathers
    # fixed-size best-split records (d * S * 7 fields * 4 bytes) that
    # ride outside the packed wire
    gp = models[32].engine._grow_params
    S = min(gp.max_splits_per_round, gp.num_leaves - 1)
    cm32 = models[32].engine._comms_model()
    rec = 0 if comms == "psum" else cm32["devices"] * S * 7 * 4
    assert (bytes_[16] - rec) * 2 == bytes_[32] - rec
    assert (bytes_[8] - rec) * 4 == bytes_[32] - rec
    assert _strip_params(models[16].model_to_string()) == \
        _strip_params(models[32].model_to_string())
    # w8 saturates the 8-bit lane at this quant config: different trees by
    # design, but still a usable model
    pred8 = models[8].predict(X[:256])
    assert np.all(np.isfinite(pred8))


def test_packed_width_single_device_noop():
    # no mesh -> no collective seam: packed widths must be a strict no-op
    X, y = make_synthetic_binary(n=1500, f=8)
    p = dict(_PACK_BASE, verbosity=-1, min_data_in_leaf=5)
    a = lgb.train(dict(p, hist_packed_width=32), lgb.Dataset(X, label=y),
                  num_boost_round=5)
    b = lgb.train(dict(p, hist_packed_width=16), lgb.Dataset(X, label=y),
                  num_boost_round=5)
    assert _strip_params(a.model_to_string()) == \
        _strip_params(b.model_to_string())


@needs_mesh
@pytest.mark.slow
def test_route_fusion_mesh_identity():
    # per-shard compaction needs enough local rows to beat the block
    # quantum: 32768 rows -> 4096/shard on the 8-device CPU mesh
    X, y = make_synthetic_binary(n=32768, f=10)
    p_off = dict(_FUSION_PARAMS, route_fusion="off", tree_learner="data")
    p_on = dict(_FUSION_PARAMS, route_fusion="on", tree_learner="data")
    a = lgb.train(p_off, lgb.Dataset(X, label=y), num_boost_round=5)
    b = lgb.train(p_on, lgb.Dataset(X, label=y), num_boost_round=5)
    assert b.engine._last_compact_rows > 0
    assert _strip_params(a.model_to_string()) == \
        _strip_params(b.model_to_string())


# ---------------------------------------------------------------------------
# unit tier: wire-packing algebra and the comms byte model — pure math, no
# training, so they stay in the fast tier even on a throttled box
# ---------------------------------------------------------------------------

from lightgbm_tpu.parallel.comms import (hist_comms_bytes_per_round,
                                         pack_gh_wire, unpack_gh_wire)


def _gh_block(rng, g_lo, g_hi, h_hi, shape=(4, 6, 8)):
    g = rng.integers(g_lo, g_hi, size=shape).astype(np.int32)
    h = rng.integers(0, h_hi, size=shape).astype(np.int32)
    return jnp.stack([jnp.asarray(g), jnp.asarray(h)], axis=-1)


def test_pack_roundtrip_exact_w16():
    # magnitudes under cap -> shift 0 -> bit-exact roundtrip
    h = _gh_block(np.random.default_rng(0), -2000, 2000, 1000)
    packed, scales = pack_gh_wire(h, None, 16, d=4)
    out = unpack_gh_wire(packed, scales, 16)
    assert np.array_equal(np.asarray(scales), [1.0, 1.0])
    assert np.array_equal(np.asarray(out), np.asarray(h, dtype=np.float32))


def test_pack_roundtrip_exact_w8():
    h = _gh_block(np.random.default_rng(1), -20, 20, 25)
    packed, scales = pack_gh_wire(h, None, 8, d=4)
    out = unpack_gh_wire(packed, scales, 8)
    assert np.array_equal(np.asarray(scales), [1.0, 1.0])
    assert np.array_equal(np.asarray(out), np.asarray(h, dtype=np.float32))


def test_pack_wire_dtypes():
    h = _gh_block(np.random.default_rng(2), -5, 5, 5)
    assert pack_gh_wire(h, None, 16, d=4)[0].dtype == jnp.int32
    assert pack_gh_wire(h, None, 8, d=4)[0].dtype == jnp.int16


@pytest.mark.parametrize("width", [16, 8])
def test_pack_requantized_error_bounded_by_half_scale(width):
    # magnitudes over cap -> pow2 shift with round-half-away: each field's
    # error is at most scale/2 (the documented-ulp contract)
    rng = np.random.default_rng(3)
    h = _gh_block(rng, -10 ** 6, 10 ** 6, 10 ** 6)
    packed, scales = pack_gh_wire(h, None, width, d=4)
    s = np.asarray(scales)
    assert s[0] > 1.0 and s[1] > 1.0  # really requantized
    assert float(np.log2(s[0])) % 1 == 0.0  # pow2 shift
    out = np.asarray(unpack_gh_wire(packed, scales, width))
    ref = np.asarray(h, dtype=np.float32)
    assert np.max(np.abs(out[..., 0] - ref[..., 0])) <= s[0] / 2
    assert np.max(np.abs(out[..., 1] - ref[..., 1])) <= s[1] / 2


@pytest.mark.parametrize("width", [16, 8])
def test_pack_sum_linearity_carry_free(width):
    # the collective sums PACKED lanes: with shift 0 on every shard the
    # unpacked sum must equal the sum of the unpacked shards exactly —
    # the hess field never carries into the grad field above it
    rng = np.random.default_rng(4)
    d = 4
    lim = (2000, 1000) if width == 16 else (20, 25)
    blocks = [_gh_block(rng, -lim[0], lim[0], lim[1]) for _ in range(d)]
    packed = []
    for b in blocks:
        p, scales = pack_gh_wire(b, None, width, d=d)
        assert np.array_equal(np.asarray(scales), [1.0, 1.0])
        packed.append(np.asarray(p, dtype=np.int32))
    summed = jnp.asarray(sum(packed))
    out = np.asarray(unpack_gh_wire(summed, scales, width))
    ref = np.asarray(sum(np.asarray(b, dtype=np.int64) for b in blocks),
                     dtype=np.float32)
    assert np.array_equal(out, ref)


def test_bytes_model_psum_halves_and_quarters():
    kw = dict(num_slots=64, num_groups=28, bmax=63, d=4, mode="psum")
    b32 = hist_comms_bytes_per_round(**kw, packed_width=32)
    assert b32 == 64 * 28 * 63 * 2 * 4
    assert hist_comms_bytes_per_round(**kw, packed_width=16) * 2 == b32
    assert hist_comms_bytes_per_round(**kw, packed_width=8) * 4 == b32


def test_bytes_model_psum_d_invariant_and_class_scaling():
    kw = dict(num_slots=32, num_groups=8, bmax=32, mode="psum")
    assert hist_comms_bytes_per_round(**kw, d=2) == \
        hist_comms_bytes_per_round(**kw, d=8)
    assert hist_comms_bytes_per_round(**kw, d=4, num_class=3) == \
        3 * hist_comms_bytes_per_round(**kw, d=4)


def test_bytes_model_reduce_scatter_packs_block_not_records():
    kw = dict(num_slots=64, num_groups=32, bmax=63, d=4,
              mode="reduce_scatter")
    rec = 4 * 64 * 7 * 4  # d shards x 7-field f32 best records
    b32 = hist_comms_bytes_per_round(**kw, packed_width=32)
    b16 = hist_comms_bytes_per_round(**kw, packed_width=16)
    b8 = hist_comms_bytes_per_round(**kw, packed_width=8)
    assert (b16 - rec) * 2 == b32 - rec
    assert (b8 - rec) * 4 == b32 - rec
    # bf16_pair also halves the slice, and only the slice
    bf = hist_comms_bytes_per_round(**kw, dtype="bf16_pair")
    assert (bf - rec) * 2 == b32 - rec


def test_unpack_floored_mod_keeps_low_field():
    # the low (hess) field is non-negative by construction; floored
    # mod/div must recover it even under a negative packed lane
    packed = jnp.asarray([[-3 * 65536 + 7, 5 * 65536 + 9]], dtype=jnp.int32)
    out = np.asarray(unpack_gh_wire(packed, jnp.asarray([1.0, 1.0]), 16))
    assert np.array_equal(out[..., 0], [[-3.0, 5.0]])
    assert np.array_equal(out[..., 1], [[7.0, 9.0]])


def test_pack_shift_is_exact_pow2_of_overflow():
    # one element at 4x the field cap -> shift exactly 2 -> scale 4.0
    d = 1
    cap = (2 ** 15 - 8) // d
    h = jnp.asarray([[4 * cap, 0]], dtype=jnp.int32)[None]
    _, scales = pack_gh_wire(h, None, 16, d=d)
    assert float(scales[0]) == 4.0


def test_bytes_model_rs_pads_groups_to_d():
    # G=30 over d=4 -> 8-group slices, same as G=32
    kw = dict(num_slots=16, bmax=32, d=4, mode="reduce_scatter")
    assert hist_comms_bytes_per_round(num_groups=30, **kw) == \
        hist_comms_bytes_per_round(num_groups=32, **kw)


def test_bytes_model_packed_width_overrides_bf16_pair():
    # a packed wire IS the narrow dtype: bf16_pair cannot narrow it again
    kw = dict(num_slots=16, num_groups=8, bmax=32, d=4,
              mode="reduce_scatter", packed_width=16)
    assert hist_comms_bytes_per_round(dtype="bf16_pair", **kw) == \
        hist_comms_bytes_per_round(dtype="f32", **kw)
