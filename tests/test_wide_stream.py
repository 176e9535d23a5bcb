"""A table too wide for one M-tile of the stream kernel (CPU interpret mode).

route_and_hist cuts the one-hot M-axis of such a table into tiles of whole
groups: the rows are routed once, every tile's sweep contracts against the
same slots, and a tile reads its own groups only.  Every pass is held
EXACTLY (integer weights) to benchmark/reference_hist.py, plain NumPy that
shares nothing with the program, at G = 600 groups of 64 bins: five tiles of
128 groups or three of 256, the last one ragged either way.  Tables of one
tile (G = 28, G = 136) must grow the models they grew before tiling existed."""
import hashlib
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.ops.histogram import _hist_segsum
from lightgbm_tpu.pallas.stream_kernel import (NUM_TAB, WIDE_ROUTE_GROUPS,
                                               build_route_tables,
                                               pack_bins_T, route_and_hist,
                                               route_and_hist_live,
                                               route_replay, stream_tiling)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))
import reference_hist  # noqa: E402

N, F, L, S = 3000, 600, 16, 8
# this round's splits: leaf -> (feature, threshold bin, new leaf), and the
# histogram slot (+1; 0: none) of the left child, the right child, or of an
# unsplit leaf.  Features from the first, a middle and the last (ragged) tile
SPLITS = {0: (5, 30, 6), 2: (599, 10, 7), 3: (130, 50, 8), 5: (257, 31, 9)}
SLOT_LEFT = {0: 1, 2: 2}
SLOT_RIGHT = {3: 3, 5: 4}
SLOT_KEEP = {1: 5}


def _table(n, f, seed=7):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, f).astype(np.float32)
    w = rs.randn(f) * (np.arange(f) % 7 == 0)
    y = ((X @ w) / np.sqrt((w ** 2).sum()) + 0.3 * rs.randn(n) > 0)
    return X, y.astype(np.float64)


def _route_tables(routing, splits=None, **slots):
    """This round's tables: `splits` is leaf -> (feature, threshold bin, new
    leaf); `sl` / `sr` / `sk` map a leaf to the slot (+1) of its left child,
    its right child, or of the leaf itself where it is not split."""
    cols = {k: np.zeros(L, np.int32) for k in
            ("chosen", "feat", "thr", "dir", "new", "sl", "sr", "sk")}
    for at, (feature, thr, new) in (splits or {}).items():
        cols["chosen"][at], cols["feat"][at] = 1, feature
        cols["thr"][at], cols["new"][at] = thr, new
    for name, by_leaf in slots.items():
        for at, slot in by_leaf.items():
            cols[name][at] = slot
    return build_route_tables(*(jnp.asarray(cols[k]) for k in cols),
                              routing, L)


@pytest.fixture(scope="module")
def wide():
    """600 columns binned by the program's own Dataset, rows spread over six
    leaves, integer grad / hess, and the round's route tables."""
    X, y = _table(N, F)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbosity": -1})
    ds.construct()
    dd = ds.device_data()
    bins = np.asarray(dd.bins)[:N]
    assert bins.shape == (N, F) and dd.max_bins == 63
    rs = np.random.RandomState(0)
    leaf = rs.randint(0, 6, N)
    grad = rs.randint(-32, 33, N)
    hess = rs.randint(0, 33, N)
    tabs = _route_tables(dd.routing, SPLITS, sl=SLOT_LEFT, sr=SLOT_RIGHT,
                         sk=SLOT_KEEP)
    # the reference's view of the same round: where each row goes, and the
    # slot it is histogrammed in (-1: none)
    new_leaf = reference_hist.route(bins, leaf, SPLITS)
    went_right = new_leaf != leaf
    slot = np.full(N, -1)
    for at in range(L):
        here = leaf == at
        if at in SPLITS:
            slot[here & ~went_right] = SLOT_LEFT.get(at, 0) - 1
            slot[here & went_right] = SLOT_RIGHT.get(at, 0) - 1
        else:
            slot[here] = SLOT_KEEP.get(at, 0) - 1
    return dict(bins=bins, leaf=leaf, grad=grad, hess=hess, tabs=tabs,
                new_leaf=new_leaf, slot=slot, ds=ds, X=X, y=y)


def _operands(wide, tile_groups, weights=None):
    bins_T = pack_bins_T(jnp.asarray(wide["bins"]), 1024, max_bins=63,
                         tile_groups=tile_groups).bins_T
    n_pad = bins_T.shape[1]
    grad, hess = weights or (wide["grad"], wide["hess"])
    w_T = (jnp.zeros((8, n_pad), jnp.float32)
           .at[0, :N].set(jnp.asarray(grad, jnp.float32))
           .at[1, :N].set(jnp.asarray(hess, jnp.float32)).at[2, :N].set(1.0))
    leaf = jnp.zeros((1, n_pad), jnp.int32).at[0, :N].set(wide["leaf"])
    return bins_T, leaf, w_T, wide["tabs"], jnp.zeros((64, L), jnp.bfloat16)


def _segsum(wide, slot, n_slots):
    """The one-tile program's own histogram (ops/histogram.py), as int64:
    rows of slot -1 count nowhere."""
    keep = np.asarray(slot) >= 0
    ref = _hist_segsum(jnp.asarray(wide["bins"][keep]),
                       jnp.asarray(np.asarray(slot)[keep], jnp.int32),
                       jnp.asarray(wide["grad"][keep], jnp.float32),
                       jnp.asarray(wide["hess"][keep], jnp.float32),
                       jnp.ones(int(keep.sum()), jnp.float32), n_slots, 63)
    return np.asarray(ref[..., :2]).astype(np.int64)


TILES = [pytest.param(128, id="5_tiles_of_128"),
         pytest.param(256, id="3_tiles_of_256")]


@pytest.mark.parametrize("tile_groups", TILES)
def test_tiled_slot_pass_exact(wide, tile_groups):
    """The 64-slot pass: new leaf ids, slot counts and every histogram sum."""
    bins_T, leaf, w_T, tabs, bits = _operands(wide, tile_groups)
    assert bins_T.shape[0] == -(-F // tile_groups) * tile_groups > F
    new_leaf, hist, cnt = route_and_hist(
        bins_T, leaf, w_T, tabs, bits, S, 63, F, L, has_cat=False,
        int_weights=True, tile_groups=tile_groups)
    want = reference_hist.histograms(wide["bins"], wide["slot"],
                                     wide["grad"], wide["hess"], S, 63)
    assert hist.shape == (S, F, 63, 2) and hist.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(new_leaf)[0, :N],
                                  wide["new_leaf"])
    np.testing.assert_array_equal(np.asarray(hist), want[..., :2])
    np.testing.assert_array_equal(np.asarray(cnt), want[:, 0, :, 2].sum(1))
    np.testing.assert_array_equal(np.asarray(hist),
                                  _segsum(wide, wide["slot"], S))


@pytest.mark.parametrize("tile_groups", TILES)
def test_tiled_word_built_pass_is_the_compare_built_one(wide, tile_groups):
    """The sweeps build their bin one-hot in words over u8 bins and integer
    weights (onehot_build_kind), a tile's rows b-major as the compare-built
    one's (whole 32-group word arrays: onehot_word_major); the same table
    in the packed-word layout takes the compare-built one: the same int32
    sums bit for bit, the ragged last tile's included."""
    from lightgbm_tpu.pallas.stream_kernel import onehot_build_kind
    bins_T, leaf, w_T, tabs, bits = _operands(wide, tile_groups)
    packed = pack_bins_T(jnp.asarray(wide["bins"]), 1024, max_bins=255,
                         tile_groups=tile_groups).bins_T
    assert onehot_build_kind(bins_T.dtype, True) == "words"
    assert onehot_build_kind(packed.dtype, True) == "compare"
    assert onehot_build_kind(bins_T.dtype, False) == "compare"
    static = dict(has_cat=False, int_weights=True, tile_groups=tile_groups)
    got = route_and_hist(bins_T, leaf, w_T, tabs, bits, S, 63, F, L, **static)
    ref = route_and_hist(packed, leaf, w_T, tabs, bits, S, 63, F, L, **static)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(got[1]).any()


@pytest.mark.parametrize("tile_groups", TILES)
def test_tiled_route_only_pass_exact(wide, tile_groups):
    """The route-only pass has no M-axis: one sweep, the same leaf ids and
    counts as the 64-slot pass gives, an all-zero histogram."""
    bins_T, leaf, w_T, tabs, bits = _operands(wide, tile_groups)
    new_leaf, hist, cnt = route_and_hist(
        bins_T, leaf, w_T, tabs, bits, S, 63, F, L, has_cat=False,
        int_weights=True, tile_groups=tile_groups, with_hist=False)
    np.testing.assert_array_equal(np.asarray(new_leaf)[0, :N],
                                  wide["new_leaf"])
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(wide["slot"][wide["slot"] >= 0],
                                     minlength=S))
    assert hist.shape == (S, F, 63, 2) and not np.asarray(hist).any()


@pytest.mark.parametrize("tile_groups", TILES)
def test_tiled_factored_root_exact(wide, tile_groups):
    """root=True on the int path: the factored contraction, a tile a sweep."""
    bins_T, leaf, w_T, tabs, bits = _operands(wide, tile_groups)
    leaf0 = jnp.zeros_like(leaf)
    new_leaf, hist, _ = route_and_hist(
        bins_T, leaf0, w_T, tabs, bits, 1, 63, F, L, has_cat=False,
        int_weights=True, tile_groups=tile_groups, root=True)
    want = reference_hist.histograms(wide["bins"], np.zeros(N, np.int64),
                                     wide["grad"], wide["hess"], 1, 63)
    assert hist.shape == (1, F, 63, 2) and hist.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(hist), want[..., :2])
    np.testing.assert_array_equal(np.asarray(hist),
                                  _segsum(wide, np.zeros(N, np.int32), 1))
    assert not np.asarray(new_leaf).any()


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("tile_groups", TILES)
def test_tiled_small_slot_pass_exact(wide, tile_groups, k):
    """A round of k = 1 or 2 splits on the tiled table: the route-only
    pre-pass writes the slots, the factored contraction sweeps the rows a
    tile.  The splits test features of the first and the last (ragged) tile;
    slot 0's smaller child is the left one, slot 1's the right one."""
    splits = dict(list(SPLITS.items())[:k])
    sl, sr = {0: 1}, ({2: 2} if k == 2 else {})
    bins_T, leaf, w_T, _, bits = _operands(wide, tile_groups)
    tabs = _route_tables(wide["ds"].device_data().routing, splits, sl=sl,
                         sr=sr)
    new_leaf = reference_hist.route(wide["bins"], wide["leaf"], splits)
    went_right = new_leaf != wide["leaf"]
    slot = np.full(N, -1)
    slot[(wide["leaf"] == 0) & ~went_right] = 0
    if k == 2:
        slot[(wide["leaf"] == 2) & went_right] = 1
    want = reference_hist.histograms(wide["bins"], slot, wide["grad"],
                                     wide["hess"], k, 63)
    kw = dict(has_cat=False, int_weights=True, tile_groups=tile_groups)
    got = route_and_hist_live(jnp.int32(k), bins_T, leaf, w_T, tabs, bits,
                              64, 63, F, L, **kw)
    full = route_and_hist(bins_T, leaf, w_T, tabs, bits, 64, 63, F, L, **kw)
    for a, b in zip(got, full):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    got_leaf, hist, cnt = got
    np.testing.assert_array_equal(np.asarray(got_leaf)[0, :N], new_leaf)
    np.testing.assert_array_equal(np.asarray(hist[:k]), want[..., :2])
    assert not np.asarray(hist[k:]).any()
    np.testing.assert_array_equal(np.asarray(cnt[:k]),
                                  want[:, 0, :, 2].sum(1))
    assert 0 < (slot == k - 1).sum() < N


@pytest.mark.parametrize("tile_groups", TILES)
def test_the_tiles_add_up(wide, tile_groups):
    """Each tile's block is what the ONE-tile kernel gives for that tile's
    columns alone with the same slots, the blocks side by side are the whole
    table's histogram, and every group is counted once: a row falls in one
    bin of each group, so each group's bins sum to its slot's totals."""
    bins_T, leaf, w_T, tabs, bits = _operands(wide, tile_groups)
    _, hist, cnt = route_and_hist(
        bins_T, leaf, w_T, tabs, bits, S, 63, F, L, has_cat=False,
        int_weights=True, tile_groups=tile_groups)
    hist = np.asarray(hist)
    # the slots as leaf ids (-1: leaf S, which keeps no slot), no split
    at_slot = jnp.zeros_like(leaf).at[0, :N].set(
        np.where(wide["slot"] >= 0, wide["slot"], S))
    blocks = []
    keep = _route_tables(wide["ds"].device_data().routing,
                         sk={at: at + 1 for at in range(S)})
    for g0 in range(0, F, tile_groups):
        part = wide["bins"][:, g0:g0 + tile_groups]
        one = pack_bins_T(jnp.asarray(part), 1024, max_bins=63).bins_T
        _, block, _ = route_and_hist(
            one, at_slot, w_T, keep, bits, S, 63, part.shape[1], L,
            has_cat=False, int_weights=True)
        assert block.shape == (S, part.shape[1], 63, 2)
        np.testing.assert_array_equal(
            hist[:, g0:g0 + tile_groups], np.asarray(block))
        blocks.append(np.asarray(block))
    assert len(blocks) == -(-F // tile_groups)
    np.testing.assert_array_equal(np.concatenate(blocks, axis=1), hist)
    grad = np.bincount(wide["slot"] + 1, wide["grad"], S + 1)[1:]
    hess = np.bincount(wide["slot"] + 1, wide["hess"], S + 1)[1:]
    for c, total in enumerate((grad, hess)):
        np.testing.assert_array_equal(
            hist[..., c].sum(2), np.broadcast_to(total[:, None], (S, F)))
    np.testing.assert_array_equal(np.asarray(cnt),
                                  np.bincount(wide["slot"] + 1,
                                              minlength=S + 1)[1:])


def test_replay_routes_a_wide_table_with_a_ragged_last_run(wide):
    """route_replay (GOSS / bagging with route_fusion, refit) packs no tiles:
    608 group rows are one run of WIDE_ROUTE_GROUPS and a ragged one of 96,
    and both rounds split on features of the ragged run."""
    rounds = [{0: (599, 10, 1)}, {0: (5, 30, 2), 1: (530, 40, 3)}]
    routing = wide["ds"].device_data().routing
    tabs = [_route_tables(routing, splits) for splits in rounds]
    bins_T = pack_bins_T(jnp.asarray(wide["bins"]), 1024, max_bins=63).bins_T
    assert bins_T.dtype == jnp.int8
    assert WIDE_ROUTE_GROUPS < bins_T.shape[0] < 2 * WIDE_ROUTE_GROUPS
    got = route_replay(bins_T, jnp.concatenate(tabs), jnp.int32(2), L,
                       block_rows=1024, rounds_buf=2)
    want = np.zeros(N, np.int64)
    for splits in rounds:
        want = reference_hist.route(wide["bins"], want, splits)
    assert tabs[0].shape == (NUM_TAB, L) and set(want) == {0, 1, 2, 3}
    np.testing.assert_array_equal(np.asarray(got)[:N], want)


def test_tiled_float_pass_close(wide):
    """Float weights (two bf16 passes, f32 sums) through the same tiles."""
    rs = np.random.RandomState(1)
    grad = rs.randn(N).astype(np.float32)
    hess = np.abs(grad) + 0.5
    bins_T, leaf, w_T, tabs, bits = _operands(wide, 128, (grad, hess))
    new_leaf, hist, _ = route_and_hist(
        bins_T, leaf, w_T, tabs, bits, S, 63, F, L, has_cat=False,
        tile_groups=128)
    want = reference_hist.histograms(wide["bins"], wide["slot"], grad, hess,
                                     S, 63)
    np.testing.assert_array_equal(np.asarray(new_leaf)[0, :N],
                                  wide["new_leaf"])
    np.testing.assert_allclose(np.asarray(hist), want[..., :2], rtol=2e-3,
                               atol=2e-3)


def test_tiles_refuse_what_they_cannot_take(wide):
    bins_T, leaf, w_T, tabs, bits = _operands(wide, 128)
    args = (bins_T, leaf, w_T, tabs, bits, S, 63, F, L)
    with pytest.raises(ValueError, match="bin_buckets"):
        route_and_hist(*args, has_cat=False, int_weights=True,
                       tile_groups=128, bin_buckets=((64, F),))
    with pytest.raises(ValueError, match="not packed to tiles of 96"):
        route_and_hist(*args, has_cat=False, int_weights=True,
                       tile_groups=96)


# ------------------------------------------------------------- the planner
@pytest.mark.parametrize("groups, int_hist, want", [
    (28, True, (1024, 0, 1, 28 * 64)),
    (136, True, (1024, 0, 1, 136 * 64)),
    (136, False, (1024, 0, 1, 136 * 64)),
    (600, True, (1024, 128, 5, 128 * 64)),
    (600, False, (1024, 64, 10, 64 * 64)),
    (2000, True, (1024, 128, 16, 128 * 64)),
])
def test_tiling_plan(groups, int_hist, want):
    """One tile wherever one was enough before; beyond it the chip's tiles,
    at the interpreter's 1024-row block."""
    assert tuple(stream_tiling(63, groups, int_hist)) == want


def test_bucketed_axis_counts_while_it_makes_one_tile():
    buckets = ((64, 100), (32, 20), (16, 10), (8, 6))
    assert stream_tiling(63, 136, True, bin_buckets=buckets).tile_groups == 0
    wide_buckets = ((64, 500), (8, 100))
    plan = stream_tiling(63, 600, True, bin_buckets=wide_buckets)
    assert (plan.tile_groups, plan.num_tiles) == (128, 5)


# ------------------------------------------------- through Booster.update()
def _grow(features, backend, leaves=15, trees=3, **extra):
    X, y = _table(N, features)
    params = dict(objective="binary", num_leaves=leaves, max_bin=63,
                  learning_rate=0.1, verbosity=-1, hist_backend=backend,
                  max_splits_per_round=64, use_quantized_grad=True,
                  num_grad_quant_bins=64, stochastic_rounding=False, **extra)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    for _ in range(trees):
        bst.update()
    return bst


def _structure(node, out):
    if "split_feature" in node:
        out.append((node["split_feature"], node["threshold"],
                    node["internal_count"]))
        _structure(node["left_child"], out)
        _structure(node["right_child"], out)
    else:
        out.append(("leaf", node["leaf_count"], round(node["leaf_value"], 5)))
    return out


def test_wide_trees_equal_segsums():
    """Three trees of a 600-column table through the public path: the tiled
    stream backend and segsum split the same features at the same
    thresholds with the same counts."""
    telemetry.reset_counters()
    # the fused iteration, as on the chip, polled once after tree 3
    stream = _grow(F, "stream", fused_iter="on", eval_fetch_freq=3)
    polls = telemetry.recent_spans(name="GBDT::FlagPoll")
    assert polls and polls[-1].args["hist_tiles"] == 5
    assert "tile_m_rows" not in polls[-1].args
    assert polls[-1].args["hist_m_rows"] == F * 64    # 40 groups are padding
    assert polls[-1].args["root_pass"] == "factored"
    # passes, not sweeps: the root and four doubling rounds (2, 4, 8, 15
    # leaves) a tree, whatever the tiles
    assert polls[-1].args["hist_passes"] == 3 * 5
    segsum = _grow(F, "segsum")
    got, want = (b.dump_model()["tree_info"] for b in (stream, segsum))
    assert len(got) == len(want) == 3
    for a, b in zip(got, want):
        sa = _structure(a["tree_structure"], [])
        assert len(sa) == 2 * 15 - 1
        assert sa == _structure(b["tree_structure"], [])


def test_wide_model_is_byte_equal_to_the_one_tile_kernels(monkeypatch):
    """The same quantised gradients, exact integer sums: the model text of
    the tiled path is, to the byte, what the ONE-tile kernel grows (the
    interpreter has no VMEM to run out of, so it can be asked to).  segsum
    sums the dequantised floats and agrees in every split, threshold and
    count (above) but not in the last digits of a leaf value."""
    from lightgbm_tpu.pallas import stream_kernel
    tiled = _grow(F, "stream", fused_iter="on", eval_fetch_freq=3)
    assert tiled.engine._stream_tiling.num_tiles == 5
    assert tiled.engine._packed.shape[0] == 5 * 128
    plan = stream_kernel.StreamTiling(1024, 0, 1, F * 64)
    monkeypatch.setattr(stream_kernel, "stream_tiling",
                        lambda *a, **k: plan)
    whole = _grow(F, "stream", fused_iter="on", eval_fetch_freq=3)
    assert whole.engine._stream_tiling.num_tiles == 1
    assert whole.engine._packed.shape[0] == 608      # 600 groups, 32 a pad
    texts = [b.model_to_string().split("\nparameters:")[0]
             for b in (tiled, whole)]
    assert len(texts[0]) > 3000 and texts[0].encode() == texts[1].encode()


# model digests of the PARENT commit (before the kernel tiled anything) for
# _grow(features, "stream") without max_splits_per_round, on this table
PARENT_DIGEST = {28: "cb623faf2135f052", 136: "386eb9b78d8c3a16"}


@pytest.mark.parametrize("features", sorted(PARENT_DIGEST))
def test_one_tile_models_are_the_parents(features):
    X, y = _table(N, features)
    params = dict(objective="binary", num_leaves=15, max_bin=63,
                  learning_rate=0.1, verbosity=-1, hist_backend="stream",
                  min_data_in_leaf=20, use_quantized_grad=True,
                  num_grad_quant_bins=64, stochastic_rounding=False)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    assert bst.engine._stream_tiling.num_tiles == 1
    for _ in range(3):
        bst.update()
    model = bst.model_to_string().split("\nparameters:")[0]
    assert hashlib.sha256(model.encode()).hexdigest()[:16] \
        == PARENT_DIGEST[features]


def test_one_tile_fused_fixture_still_matches(monkeypatch):
    """tests/fixtures/fused_parent_binary_int.model, written before the root
    was factored and long before anything tiled: a table of one tile takes
    the program it took, through the fused iteration."""
    from conftest import make_synthetic_binary
    monkeypatch.setenv("LGBTPU_FUSE_ITER", "1")
    X, y = make_synthetic_binary(n=2000, f=8)
    bst = lgb.train({"verbosity": -1, "hist_backend": "stream",
                     "num_leaves": 15, "min_data_in_leaf": 5, "max_bin": 63,
                     "learning_rate": 0.1, "objective": "binary",
                     "use_quantized_grad": True},
                    lgb.Dataset(X, label=y), num_boost_round=5)
    assert bst.engine._fused_last
    assert tuple(bst.engine._stream_tiling)[1:3] == (0, 1)
    got = bst.model_to_string().split("\nparameters:")[0]
    want = (Path(__file__).parent / "fixtures"
            / "fused_parent_binary_int.model").read_text()
    for a, b in zip(got.splitlines(), want.splitlines(), strict=True):
        ka, _, va = a.partition("=")
        if a == b or ka == "tree_sizes":
            continue
        # integer structure to the byte; floats to the last digits XLA:CPU
        # may round differently on another host
        assert ka == b.partition("=")[0]
        np.testing.assert_allclose(
            [float(t) for t in va.split()],
            [float(t) for t in b.partition("=")[2].split()],
            rtol=1e-6, atol=1e-9, err_msg=ka)


@pytest.mark.parametrize("groups", [28, 136, 600, 2000])
def test_auto_is_stream_at_every_width_on_a_tpu(monkeypatch, groups):
    """hist_backend=auto on a TPU: never a fall-back for want of width.  The
    engine is built here on the CPU (auto -> segsum) and then asked what it
    would resolve where runtime.on_tpu() says yes."""
    from lightgbm_tpu.models import gbdt
    from lightgbm_tpu.pallas import stream_kernel
    X, y = _table(300, groups)
    params = dict(objective="binary", num_leaves=255, max_bin=63,
                  verbosity=-1, use_quantized_grad=True,
                  num_grad_quant_bins=64)
    eng = lgb.Booster(params, lgb.Dataset(X, label=y, params=params)).engine
    assert eng.dd.num_groups == groups
    assert eng._resolve_hist_backend() == "segsum"
    monkeypatch.setattr(gbdt, "on_tpu", lambda: True)
    monkeypatch.setattr(stream_kernel, "on_tpu", lambda: True)
    assert eng._stream_fits()
    assert eng._resolve_hist_backend() == "stream"
    plan = stream_kernel.stream_tiling(eng.dd.max_bins, groups,
                                       eng._resolved_int_hist())
    assert plan.num_tiles == {28: 1, 136: 1, 600: 5, 2000: 16}[groups]


def test_root_split_is_the_references(wide):
    """The first split of a wide table, float gradients: the feature and
    the threshold LightGBM's gain formula picks from the reference's own
    float64 histogram."""
    X, y = wide["X"], wide["y"]
    params = dict(objective="binary", num_leaves=2, max_bin=63,
                  verbosity=-1, hist_backend="stream")
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    bst.update()
    p = y.mean()
    hist = reference_hist.histograms(wide["bins"], np.zeros(N, np.int64),
                                     p - y, np.full(N, p * (1 - p)), 1, 63)
    feature, threshold_bin, _ = reference_hist.best_split(hist[0])
    root = bst.dump_model()["tree_info"][0]["tree_structure"]
    mapper = wide["ds"].binned.bin_mappers[feature]
    assert root["split_feature"] == feature
    assert root["threshold"] == pytest.approx(
        mapper.bin_to_threshold(threshold_bin))
    left = int((wide["bins"][:, feature] <= threshold_bin).sum())
    assert root["left_child"]["leaf_count"] == left
