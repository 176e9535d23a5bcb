"""The in-process row mesh of `tree_learner=data` (ISSUE 35): the table
shipped a shard to a device, the int8 path's guard counting a device's rows,
the histogram crossing the mesh in two 16-bit limbs where the whole table's
sum could pass 2^31, integer leaf counts under the mesh only, the score
update's gather kernel a shard, the objective's rows bound once, and the
comms counters as host arithmetic - on four virtual CPU devices, at small
sizes, seeded.  And the pins that keep a one-chip job the parent's: no field
added to any state it carries, three pass counters, float32 counts."""
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry as tel
from lightgbm_tpu.ops import grow as grow_mod
from lightgbm_tpu.parallel import comms
from lightgbm_tpu.parallel.mesh import shard_map_rows
from lightgbm_tpu.parallel.sharded_state import ShardedTrainState

REPO = Path(__file__).resolve().parent.parent
D = 4
needs_mesh = pytest.mark.skipif(len(jax.devices()) < D,
                                reason="needs a 4-device mesh")
SHAPE = {"published_rows": 1700000000, "features": 67,
         "deployment_machines": 16}
BASE = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
        "max_bin": 63, "use_quantized_grad": True,
        "num_grad_quant_bins": 64, "verbosity": -1}
MESHED = dict(BASE, tree_learner="data", hist_backend="stream",
              mesh_shape=f"data:{D}")
# the fields a one-chip job's states carry at the parent commit (9b3c4d7)
GROW_STATE = (
    "leaf_id leaf_id_c split_feature threshold_bin dir_flags left_child "
    "right_child split_gain internal_value internal_weight internal_count "
    "cat_bitset sum_g sum_h cnt depth leaf_parent out_lo out_hi leaf_out "
    "anc_left anc_right node_mono node_depth rect_lo rect_hi leaf_in_mono "
    "adv_vmin adv_vmax adv_split_ok used_feat cegb_used cegb_lazy round_idx "
    "hist_passes hist_small_passes scan_slots best_gain best_feat best_thr "
    "best_dir best_left_g best_left_h best_left_c hist num_leaves_cur "
    "progressed col_mask tabs_buf").split()
GROW_STATE_K = (
    "leaf_id leaf_id_c split_feature threshold_bin dir_flags left_child "
    "right_child split_gain internal_value internal_weight internal_count "
    "cat_bitset sum_g sum_h cnt depth leaf_parent best_gain best_feat "
    "best_thr best_dir best_left_g best_left_h best_left_c hist "
    "num_leaves_cur progressed hist_passes scan_slots").split()
FUSED_STATE = ("score grad hess leaf_id mask key sampled overflow finished "
               "ok hist_passes hist_small_passes scan_slots").split()


def _criteo(rows, seed=5):
    spec = importlib.util.spec_from_file_location(
        "criteo_like", REPO / "benchmark" / "generators" / "criteo_like.py")
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    data = gen.make(seed, rows, SHAPE)
    return data["X"], data["y"].astype(np.float64)


@pytest.fixture(scope="module")
def table():
    return _criteo(6000)


def _booster(params, table, rounds=0):
    X, y = table
    bst = lgb.Booster(dict(params), lgb.Dataset(X, label=y))
    for _ in range(rounds):
        bst.update()
    return bst


_EXACT = ("num_leaves", "split_feature", "threshold_bin", "threshold",
          "decision_type", "left_child", "right_child", "leaf_count",
          "internal_count")
_CLOSE = ("split_gain", "leaf_value", "internal_value", "leaf_weight")


def _assert_same_trees(a, b, rtol, atol):
    """Node for node: features, threshold bins, child links and every count
    exactly; gains, values and weights to the stated tolerance."""
    ta, tb = a.engine.models, b.engine.models
    assert len(ta) == len(tb) > 0
    for i, (x, y) in enumerate(zip(ta, tb)):
        assert x.num_leaves > 4
        for f in _EXACT:
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f),
                                          err_msg=f"tree {i} {f}")
        for f in _CLOSE:
            np.testing.assert_allclose(getattr(x, f), getattr(y, f),
                                       rtol=rtol, atol=atol,
                                       err_msg=f"tree {i} {f}")


@needs_mesh
def test_meshed_learner_grows_the_serial_learners_trees(table):
    """Three trees of criteo_like data at the default 64 splits a round:
    the row mesh against the serial learner through the same kernel.  The
    int32 histograms are the same sums in another order (exact), so only
    the float32 leaf sums (gradients summed a shard at a time) may move the
    gains and values, in their last digits."""
    meshed = _booster(MESHED, table, 3)
    serial = _booster(dict(BASE, hist_backend="stream"), table, 3)
    eng = meshed.engine
    assert eng._grow_params.int_hist and eng._mesh_stream
    assert eng._grow_params.max_splits_per_round == 64
    assert int(eng.models[0].internal_count[0]) == len(table[1])
    _assert_same_trees(meshed, serial, rtol=2e-5, atol=1e-7)


@needs_mesh
def test_meshed_learner_grows_the_plain_references_trees(table):
    """Against the repo's plain reference: segsum histograms in float64, one
    split a round, the whole table on one device.  The stream kernel grows
    segsum's trees at one split a round (tests/test_hist_backends.py; at 64
    a round it grows other trees by design), so the mesh takes one a round
    here too.  Rounding to the nearest level, not stochastically: the two
    backends' gradient programs differ in the last digit of g on a CPU and
    their stochastic draws land on other rows (104 of 8,192 here), which
    moves a root gain by 0.4% and the trees with it; to the nearest level
    the quantised gradients are the same integers on both sides.  float64
    against float32 in the gain formula and the leaf sums: 2e-4."""
    one = {"max_splits_per_round": 1, "stochastic_rounding": False}
    meshed = _booster(dict(MESHED, **one), table, 3)
    plain = _booster(dict(BASE, hist_backend="segsum",
                          hist_precision="double", **one), table, 3)
    assert meshed.engine._grow_params.int_hist
    assert plain.engine._grow_params.hist_double
    _assert_same_trees(meshed, plain, rtol=2e-4, atol=1e-6)


@needs_mesh
def test_every_device_holds_its_shard_and_nothing_else(table):
    before = {id(a) for a in jax.live_arrays()}
    ds = lgb.Dataset(table[0], label=table[1])
    bst = lgb.Booster(dict(MESHED), ds)
    eng = bst.engine
    n_pad, g = eng.dd.bins.shape
    assert g == 67 and n_pad % (D * eng._pack_block) == 0
    shards = eng.dd.bins.addressable_shards
    assert len({s.device for s in shards}) == D
    assert all(s.data.shape == (n_pad // D, g) for s in shards)
    packed = eng._packed.addressable_shards
    assert len({s.device for s in packed}) == D
    assert all(s.data.shape[1] == n_pad // D for s in packed)
    assert ds._device is None              # no single-device copy is cached
    # after an iteration no table made since sits on one device (2-D
    # arrays only: the objective's host label is, on a CPU, a view of the
    # device array it came from, which a sweep of 1-D arrays would flag)
    bst.update()
    n = len(table[1])
    for a in jax.live_arrays():
        if id(a) not in before and a.ndim == 2 and max(a.shape) >= n:
            assert len(a.sharding.device_set) == D, (a.shape, a.dtype)
            assert not a.sharding.is_fully_replicated, (a.shape, a.dtype)
    ship = tel.recent_spans(name="Dataset::Ship")[-1].args
    assert ship["shards"] == D
    assert ship["bytes_per_shard"] == n_pad // D * g
    # the rows the shards hold are the table's, the pad zeros
    np.testing.assert_array_equal(np.asarray(eng.dd.bins)[:len(table[1])],
                                  ds.binned.bins)
    assert not np.asarray(eng.dd.bins)[len(table[1]):].any()


@needs_mesh
def test_the_objectives_rows_are_bound_once_on_the_row_sharding(table):
    bst = _booster(MESHED, table, 2)
    eng = bst.engine
    bound = eng._bound_rows
    assert set(bound) == {"label"} or set(bound) >= {"label"}
    for a in bound.values():
        assert a.shape[0] == eng.dd.bins.shape[0]
        assert a.sharding == eng._row_sharding
    # the objective's own copy went back to the host
    assert isinstance(eng.objective.label, np.ndarray)
    spans = tel.recent_spans(name="GBDT::ShardBind")
    assert spans[-1].args == {"rows": eng.dd.bins.shape[0],
                              "arrays": len(bound)}
    # a one-chip engine binds what it binds now
    one = _booster(dict(BASE, hist_backend="stream"), table, 1)
    assert one.engine._bound_rows == {}
    assert isinstance(one.engine.objective.label, jax.Array)


@needs_mesh
def test_counts_past_2_24_are_exact_under_the_mesh(table):
    """Counts past 2^24 by construction: every row counts for a few
    thousand (the mask the grower counts with is the engine's to give), so
    the 6,000-row table's root holds 24.6M and no device's slot more than
    2^24.  int32 under the mesh: every leaf's count is the exact sum of its
    rows' weights; float32 (one chip) cannot hold the odd totals."""
    X, y = table
    n = len(y)
    w = np.where(np.arange(n) % 3 == 0, 4099, 4097).astype(np.float32)
    w[0] = 4098
    total = int(w.astype(np.int64).sum())
    assert total > 2 ** 24 and total % 2 == 1
    bst = _booster(MESHED, table)
    eng = bst.engine
    pad = eng.dd.bins.shape[0] - n
    eng._pad_mask = eng._shard_row_array(np.pad(w, (0, pad)))
    bst.update()
    tree = jax.device_get(eng._lazy_trees[0]["arrays"])
    assert tree.leaf_count.dtype == np.int32
    assert tree.internal_count.dtype == np.int32
    nl = int(tree.num_leaves)
    assert nl > 4
    leaf_id = np.asarray(eng._train_state.leaf_id)[:n]
    want = np.bincount(leaf_id, weights=w.astype(np.float64),
                       minlength=nl).astype(np.int64)
    np.testing.assert_array_equal(tree.leaf_count[:nl], want[:nl])
    assert int(tree.internal_count[0]) == total
    assert int(tree.leaf_count[:nl].sum()) == total
    assert int(np.float32(total)) != total     # what float32 would have said


@needs_mesh
def test_two_limbs_carry_sums_past_2_31_and_equal_one_limb_below_it():
    mesh = Mesh(np.array(jax.devices()[:D]), ("data",))
    rs = np.random.RandomState(3)
    G, B, S = 8, 16, 4
    plan = comms.ShardPlan(D, G, G // D, 1, *([None] * 8))

    def reduced(h, limbs, plan=None):
        spec = P(None, "data", None, None) if plan else P()
        fn = shard_map_rows(
            lambda x: comms.reduce_hist_rows(x[0], "data", 1, plan,
                                             limbs=limbs),
            mesh, (P("data"),), spec)
        return np.asarray(jax.jit(fn)(h))

    # below 2^31 in total: the two-limb reduce is the one-limb one, bit
    # for bit, under psum and under reduce_scatter
    small = rs.randint(-2 ** 20, 2 ** 20, (D, S, G, B, 2)).astype(np.int32)
    for p in (None, plan):
        one = reduced(small, 1, p)
        two = reduced(small, 2, p)
        assert one.dtype == np.int32 and two.dtype == np.float32
        np.testing.assert_array_equal(one.astype(np.float32), two)
        np.testing.assert_array_equal(one, small.sum(0))
    # past it: each device's part fits int32, the total does not
    big = rs.randint(2 ** 29, 2 ** 30, (D, S, G, B, 2)).astype(np.int32)
    big[:, 0] *= -1
    exact = big.astype(np.int64).sum(0)
    assert np.abs(exact).max() > 2 ** 31
    for p in (None, plan):
        np.testing.assert_array_equal(reduced(big, 2, p),
                                      exact.astype(np.float32))
        assert (reduced(big, 1, p).astype(np.int64) != exact).any()
    # split / join alone, on the extremes
    edge = np.array([[-2 ** 31, -1, 0, 1, 65535, 65536, 2 ** 31 - 1]],
                    np.int32)
    limbs = np.asarray(comms.split_limbs(jnp.asarray(edge)))
    assert limbs[1].min() >= 0 and limbs[1].max() <= 65535
    np.testing.assert_array_equal(
        limbs[0].astype(np.int64) * 65536 + limbs[1], edge[0])
    np.testing.assert_array_equal(np.asarray(comms.join_limbs(limbs)),
                                  edge.astype(np.float32))


@needs_mesh
def test_a_model_grown_through_two_limbs_is_the_one_limb_model(table,
                                                               monkeypatch):
    from lightgbm_tpu.models.gbdt import GBDT
    one = _booster(MESHED, table, 3)
    assert one.engine._grow_params.hist_reduce_limbs == 1
    monkeypatch.setattr(GBDT, "_resolved_reduce_limbs", lambda self: 2)
    for comm in ("psum", "reduce_scatter"):
        two = _booster(dict(MESHED, hist_comms=comm), table, 3)
        assert two.engine._grow_params.hist_reduce_limbs == 2
        assert (two.model_to_string().split("\nparameters:")[0]
                == one.model_to_string().split("\nparameters:")[0]), comm


@needs_mesh
def test_the_guard_counts_a_devices_rows_and_the_table_picks_the_limbs(
        table):
    """At the cell's real row counts (shapes only: no table is made)."""
    rows = 105_250_816                       # 105.25M trained, padded
    meshed = _booster(MESHED, table).engine
    one = _booster(dict(BASE, hist_backend="stream"), table).engine

    def at(eng, n):
        eng.dd = eng.dd._replace(
            bins=jax.ShapeDtypeStruct((n, 67), jnp.uint8))
        return eng._resolved_int_hist(), eng._resolved_reduce_limbs()

    assert meshed._int_hist_rows() == (meshed.dd.bins.shape[0] // D,
                                       meshed.dd.bins.shape[0])
    assert at(meshed, rows) == (True, 2)     # 26.3M x 32 < 2^31 < 105M x 32
    assert meshed._int_hist_rows() == (rows // D, rows)
    assert at(meshed, 64_000_000) == (True, 1)      # 64M x 32 < 2^31
    assert at(meshed, 67_108_864) == (True, 2)      # 2^26 x 32 = 2^31
    assert at(meshed, 4 * 67_108_864) == (False, 1)  # a device's own: float
    # one chip sums the whole table: unchanged
    assert at(one, rows) == (False, 1)
    assert at(one, 31_400_000) == (True, 1)
    assert one._int_hist_rows() == (31_400_000, 31_400_000)


@needs_mesh
def test_the_gather_kernel_a_shard_equals_values_of_leaf_id(table):
    eng = _booster(MESHED, table).engine
    one = _booster(dict(BASE, hist_backend="stream"), table).engine
    from lightgbm_tpu.pallas.stream_kernel import leaf_gather
    assert one._leaf_gather_fn() is leaf_gather
    n = eng.dd.bins.shape[0]
    rs = np.random.RandomState(11)
    leaf_id = rs.randint(0, 31, n).astype(np.int32)
    values = rs.randn(31).astype(np.float32)
    got = jax.jit(eng._leaf_gather_fn())(
        eng._shard_row_array(leaf_id), jnp.asarray(values))
    assert got.sharding == eng._row_sharding
    np.testing.assert_array_equal(np.asarray(got), values[leaf_id])


@needs_mesh
@pytest.mark.parametrize("comm", ["psum", "reduce_scatter"])
def test_comm_counts_are_rounds_times_bytes_per_round(table, comm):
    tel.reset_counters()
    bst = _booster(dict(MESHED, hist_comms=comm, eval_fetch_freq=2), table,
                   4)
    eng = bst.engine
    passes = tel.hist_pass_count()
    rounds, nbytes = tel.hist_comm_counts()
    assert rounds == passes >= 4 * 3
    per = functools.partial(comms.hist_comms_bytes_per_round, num_groups=67,
                            bmax=eng.dd.max_bins, d=D, mode=comm)
    S = min(64, BASE["num_leaves"] - 1)
    assert nbytes == 4 * per(1) + (passes - 4) * per(S)
    poll = tel.recent_spans(name="GBDT::FlagPoll")[-1].args
    assert (poll["mesh_devices"], poll["hist_reduce_limbs"]) == (D, 1)
    assert poll["hist_comm_bytes_root"] == per(1)
    assert poll["hist_comm_bytes_round"] == per(S)
    assert poll["hist_passes"] == passes


def test_one_device_reads_no_comms(table, monkeypatch):
    monkeypatch.setenv("LGBTPU_FUSE_ITER", "1")
    tel.reset_counters()
    bst = _booster(dict(BASE, hist_backend="stream", eval_fetch_freq=2),
                   table, 2)
    assert bst.engine._fused_last and tel.hist_pass_count() > 0
    assert tel.hist_comm_counts() == (0, 0)
    poll = tel.recent_spans(name="GBDT::FlagPoll")[-1].args
    assert [poll[k] for k in ("mesh_devices", "hist_reduce_limbs",
                              "hist_comm_bytes_root",
                              "hist_comm_bytes_round")] == [1, 1, 0, 0]
    # the one-device ship's record is the parent's
    assert tel.recent_spans(name="Dataset::Ship")[-1].args == {
        "rows": len(table[1]), "groups": 67}


def test_one_chip_states_have_the_parents_fields():
    assert list(grow_mod._GrowState._fields) == GROW_STATE
    assert list(grow_mod._GrowStateK._fields) == GROW_STATE_K
    assert list(ShardedTrainState._fields) == FUSED_STATE


def test_one_chip_counts_three_passes_and_float32_leaf_counts(
        table, monkeypatch):
    monkeypatch.setenv("LGBTPU_FUSE_ITER", "1")
    bst = _booster(dict(BASE, hist_backend="stream"), table)
    eng = bst.engine
    seen = []
    grow = eng._grow_partial

    def watching(*a, **kw):
        out = grow(*a, **kw)
        seen.append(out[-1])
        return out

    eng._grow_partial = watching
    bst.update()
    assert eng._fused_last
    assert [(s.shape, s.dtype) for s in seen] == [((3,), jnp.int32)]
    arrays = eng._lazy_trees[0]["arrays"]
    assert arrays.leaf_count.dtype == jnp.float32
    assert arrays.internal_count.dtype == jnp.float32
    assert set(eng._train_state._fields) == set(FUSED_STATE)
    assert not grow_mod._int_counts(True, None)
    assert not grow_mod._int_counts(False, object())
    assert grow_mod._int_counts(True, object())
