"""Fused-sharded iteration tests (docs/DISTRIBUTED.md "fused iteration &
sharded state").

Under a row-sharded stream mesh the default training step is ONE
`watched_jit` launch per boosting iteration (gradients -> sampling ->
growth -> score update) threading a ShardedTrainState whose out-shardings
equal its in-shardings.  This suite proves the fused path against the
unfused one (`LGBTPU_FUSE_ITER=0`) on 4- and 8-way CPU meshes with the
PR 6 identity discipline — the round-1 tree must match BYTE-for-byte
(low-mantissa round-1 gradients make every f32 summation order exact),
later rounds must match structurally with ulp tolerance (XLA re-fuses
the wider program's gradient chain with last-ulp differences) — covering
GOSS compaction, bagging, multiclass-batched lockstep, and
checkpoint/resume from a sharded state.  Runs on the conftest 8-device
CPU mesh and the 4-device tier run_all_tests.sh adds.
"""
import os

import numpy as np
import pytest

import jax

import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import launch_count

from conftest import make_synthetic_binary, make_synthetic_multiclass

N_DEV = len(jax.devices())
MESHES = [d for d in (4, 8) if d <= N_DEV]
needs_mesh = pytest.mark.skipif(N_DEV < 4, reason="needs a >=4-device mesh")


def _strip_params(model_str: str) -> str:
    return model_str.split("\nparameters:")[0]


def _assert_fused_identity(a: str, b: str):
    """Round-1 byte equality + full structural identity with ulp-tolerant
    float fields (the PR 6 non-associativity discipline)."""
    a, b = _strip_params(a), _strip_params(b)
    ta, tb = a.split("Tree="), b.split("Tree=")
    assert len(ta) == len(tb)
    assert ta[1] == tb[1], "round-1 tree must match byte-for-byte"
    la, lb = a.splitlines(), b.splitlines()
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        if xa == xb:
            continue
        ka, _, va = xa.partition("=")
        kb, _, vb = xb.partition("=")
        assert ka == kb, f"{ka!r} != {kb!r}"
        if ka == "tree_sizes":    # byte lengths of the float reprs
            continue
        fa = np.array([float(t) for t in va.split()])
        fb = np.array([float(t) for t in vb.split()])
        np.testing.assert_allclose(fa, fb, rtol=3e-4, atol=3e-4,
                                   err_msg=ka)


def _train(params, X, y, rounds=4, fuse=None, mesh_dev=None, **ds_kw):
    p = dict(params, verbosity=-1, tree_learner="data",
             hist_backend="stream")
    if mesh_dev:
        p["mesh_shape"] = f"data:{mesh_dev}"
    if fuse is not None:
        os.environ["LGBTPU_FUSE_ITER"] = fuse
    try:
        return lgb.train(p, lgb.Dataset(X, label=y, **ds_kw),
                         num_boost_round=rounds)
    finally:
        if fuse is not None:
            del os.environ["LGBTPU_FUSE_ITER"]


def _fused_vs_unfused(params, X, y, rounds=4, mesh_dev=None, **ds_kw):
    f = _train(params, X, y, rounds, None, mesh_dev, **ds_kw)
    assert f.engine._fused_last, "fused path did not engage by default"
    u = _train(params, X, y, rounds, "0", mesh_dev, **ds_kw)
    assert not u.engine._fused_last
    _assert_fused_identity(f.model_to_string(), u.model_to_string())
    return f


# ---------------------------------------------------------------------------
# fused == unfused identity across mesh widths and comms modes
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("mesh_dev", MESHES)
@pytest.mark.parametrize("mode", ["psum", "reduce_scatter"])
def test_fused_identity_binary(mesh_dev, mode):
    X, y = make_synthetic_binary(n=2000, f=8)
    _fused_vs_unfused({"objective": "binary", "num_leaves": 15,
                       "min_data_in_leaf": 5, "hist_comms": mode},
                      X, y, mesh_dev=mesh_dev)


@needs_mesh
@pytest.mark.parametrize("mesh_dev", MESHES)
def test_fused_identity_bagging(mesh_dev):
    """Epoch-cached bagging mask rides into the fused program as a jit
    argument — identical draw, identical trees."""
    X, y = make_synthetic_binary(n=2000, f=8)
    _fused_vs_unfused({"objective": "binary", "num_leaves": 15,
                       "min_data_in_leaf": 5,
                       "hist_comms": "reduce_scatter",
                       "bagging_fraction": 0.7, "bagging_freq": 2,
                       "seed": 3}, X, y, rounds=5, mesh_dev=mesh_dev)


@needs_mesh
@pytest.mark.parametrize("mesh_dev", MESHES)
@pytest.mark.slow
def test_fused_identity_goss_compacted(mesh_dev):
    """GOSS draws its mask IN-TRACE from the iteration's gradients (same
    key as the eager path) and compacts rows at the analytic capacity —
    compaction must actually engage, and any covering capacity grows the
    identical tree (out-of-bag pad rows carry exact-zero weights)."""
    X, y = make_synthetic_binary(n=4000, f=8)
    os.environ["LGBTPU_BLOCK_ROWS"] = "256"   # engage compaction at test n
    try:
        f = _fused_vs_unfused(
            {"objective": "binary", "num_leaves": 15,
             "min_data_in_leaf": 5, "hist_comms": "reduce_scatter",
             "data_sample_strategy": "goss", "learning_rate": 0.5,
             "top_rate": 0.1, "other_rate": 0.15},
            X, y, rounds=6, mesh_dev=mesh_dev)
    finally:
        del os.environ["LGBTPU_BLOCK_ROWS"]
    assert f.engine._last_compact_rows > 0, "compaction never engaged"
    assert f.engine._overflow_seen == 0
    assert f.engine._last_sampled_rows > 0


@needs_mesh
@pytest.mark.parametrize("mesh_dev", MESHES)
def test_fused_identity_multiclass_batched(mesh_dev):
    """All K class trees grow in lockstep INSIDE the fused launch
    (grow_tree_k + the stacked score add)."""
    X, y = make_synthetic_multiclass(n=2000, f=8, k=3)
    f = _fused_vs_unfused({"objective": "multiclass", "num_class": 3,
                           "num_leaves": 11, "min_data_in_leaf": 5,
                           "hist_comms": "reduce_scatter"},
                          X, y, rounds=3, mesh_dev=mesh_dev)
    assert f.engine._mc_batched_last


# ---------------------------------------------------------------------------
# sharded-state invariants
# ---------------------------------------------------------------------------

@needs_mesh
def test_state_stays_sharded_across_iterations():
    """Out-sharding == in-sharding: every row-axis state array keeps its
    row sharding across iterations (no implicit re-shard, no host
    round-trip materialization)."""
    X, y = make_synthetic_binary(n=2000, f=8)
    bst = _train({"objective": "binary", "num_leaves": 15,
                  "hist_comms": "reduce_scatter"}, X, y, rounds=4)
    eng = bst.engine
    st = eng._train_state
    assert st is not None and st.score is eng.score
    ax = eng._row_axis
    for name in ("score", "grad", "hess", "leaf_id", "mask"):
        arr = getattr(st, name)
        spec = arr.sharding.spec
        assert ax in tuple(spec), \
            f"state.{name} lost its row sharding: {arr.sharding}"
    # scalar tail stays replicated — one copy per device, no gather needed
    assert tuple(st.finished.sharding.spec) == ()


@needs_mesh
def test_fused_single_launch_per_iteration():
    """The dispatch-count contract: a steady-state fused iteration is ONE
    watched_jit launch (vs >= 3 unfused: gradients + grow + score ops)."""
    X, y = make_synthetic_binary(n=2000, f=8)
    p = {"objective": "binary", "num_leaves": 15,
         "hist_comms": "reduce_scatter"}
    bst = _train(p, X, y, rounds=2)   # warm the caches
    eng = bst.engine
    l0 = launch_count()
    for _ in range(4):
        bst.update()
    launches = (launch_count() - l0) / 4
    assert launches <= 1.5, f"fused path dispatched {launches}/iter"


# ---------------------------------------------------------------------------
# checkpoint / resume from a sharded state
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("sampling", ["plain", "goss"])
@pytest.mark.slow
def test_checkpoint_resume_from_sharded_state(tmp_path, sampling):
    """A snapshot taken mid-run from the device-sharded state must resume
    BIT-IDENTICALLY — same discipline as the single-chip resume suite,
    now with the score living sharded across the mesh."""
    X, y = make_synthetic_binary(n=2000, f=8)
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "tree_learner": "data", "hist_backend": "stream",
         "hist_comms": "reduce_scatter", "min_data_in_leaf": 5,
         "snapshot_freq": 3, "snapshot_keep": 8}
    if sampling == "goss":
        p.update({"data_sample_strategy": "goss", "learning_rate": 0.5,
                  "top_rate": 0.2, "other_rate": 0.2})
    out = str(tmp_path / "model.txt")
    full = lgb.train(dict(p, output_model=out), lgb.Dataset(X, label=y),
                     num_boost_round=6)
    assert full.engine._fused_last
    snap = out + ".snapshot_iter_3"
    assert os.path.exists(snap)
    resumed = lgb.train(dict(p, resume_from=snap, output_model=out),
                        lgb.Dataset(X, label=y), num_boost_round=6)
    assert _strip_params(full.model_to_string()) == \
        _strip_params(resumed.model_to_string())


@needs_mesh
def test_row_mesh_factored_root_equals_serial(monkeypatch):
    """The int8 path's root pass is the factored contraction; under a
    4-device row mesh every device contracts its own rows and the psum
    adds exact int32 sums, so the model equals the serial learner's byte
    for byte — and the factored kernel is what both programs traced."""
    from lightgbm_tpu.pallas import stream_kernel
    traced = []
    real = stream_kernel._root_hist_factored

    def spy(bins_T, *a, **k):
        traced.append(bins_T.shape)
        return real(bins_T, *a, **k)

    monkeypatch.setattr(stream_kernel, "_root_hist_factored", spy)
    X, y = make_synthetic_binary(n=3000, f=17)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "max_bin": 63, "hist_backend": "stream",
              "use_quantized_grad": True}
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=3)
    assert len(traced) >= 1 and serial.engine._root_pass == "factored"
    n_serial = len(traced)
    mesh = lgb.train(dict(params, tree_learner="data", mesh_shape="data:4"),
                     lgb.Dataset(X, label=y), num_boost_round=3)
    assert mesh.engine._fused_last and mesh.engine._root_pass == "factored"
    assert len(traced) > n_serial
    # the mesh program's kernel sees one device's quarter of the rows
    assert traced[-1][1] * 4 >= 3000 > traced[-1][1]
    assert (_strip_params(mesh.model_to_string())
            == _strip_params(serial.model_to_string()))


@needs_mesh
def test_row_mesh_small_slot_pass_equals_serial(monkeypatch):
    """The rounds that split one and two leaves take the small-slot pass
    under a 4-device row mesh as in the serial learner: the round's split
    count is replicated, every device takes the same branch over its own
    rows and the psum adds exact int32 sums - the branch is traced in both
    programs (S = 1 and S = 2), the device's count of small passes is the
    same, and the model equals the serial one byte for byte."""
    from lightgbm_tpu import telemetry as tel
    from lightgbm_tpu.pallas import stream_kernel
    traced = []
    real = stream_kernel._route_small_hist

    def spy(bins_T, leaf_id, w_T, tabs, bits, S, *a, **k):
        traced.append((S, bins_T.shape[1]))
        return real(bins_T, leaf_id, w_T, tabs, bits, S, *a, **k)

    monkeypatch.setattr(stream_kernel, "_route_small_hist", spy)
    # fused on one device too: the count of small passes rides its state
    monkeypatch.setenv("LGBTPU_FUSE_ITER", "1")
    # a width no other test of this process trains on, so route_and_hist's
    # own jit cache holds neither program: both are traced under the spy
    X, y = make_synthetic_binary(n=3000, f=19)
    params = {"objective": "binary", "verbosity": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "max_bin": 63, "hist_backend": "stream",
              "use_quantized_grad": True}
    tel.reset_counters()
    serial = lgb.train(dict(params), lgb.Dataset(X, label=y),
                       num_boost_round=3)
    assert serial.engine._fused_last
    assert {s for s, _ in traced} == {1, 2}
    n_serial, small_serial = len(traced), tel.hist_small_pass_count()
    assert small_serial == 2 * 3
    tel.reset_counters()
    mesh = lgb.train(dict(params, tree_learner="data", mesh_shape="data:4"),
                     lgb.Dataset(X, label=y), num_boost_round=3)
    assert mesh.engine._fused_last
    assert {s for s, _ in traced[n_serial:]} == {1, 2}
    # the mesh program's kernels see one device's quarter of the rows
    assert all(n * 4 >= 3000 > n for _, n in traced[n_serial:])
    assert tel.hist_small_pass_count() == small_serial
    assert (_strip_params(mesh.model_to_string())
            == _strip_params(serial.model_to_string()))
