"""Fused streaming route+hist kernel correctness (CPU interpret mode).

The kernel's ROUTING is integer arithmetic and must match the XLA oracle
EXACTLY; its histogram uses a two-pass bf16 weight split (hi+lo) and is
checked to ~1e-3 relative (reference analog: the CUDA learner's float hists
vs the CPU double hists, src/treelearner/cuda/*)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.grow import feature_local_bin
from lightgbm_tpu.ops.histogram import _hist_segsum
from lightgbm_tpu.pallas import stream_kernel as sk
from lightgbm_tpu.pallas.stream_kernel import (NUM_TAB, T_SLOT_KEEP,
                                               build_route_tables, pack_bins_T,
                                               root_pass_kind, route_and_hist,
                                               route_and_hist_live,
                                               small_pass_index)


def _dataset(n=2000, seed=11):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, 6)
    X[:, 4] = rs.randint(0, 7, n)
    X[:, 5] = rs.randint(0, 3, n)
    X[rs.rand(n) < 0.1, 0] = np.nan
    y = ((X[:, 1] > 0) ^ (np.nan_to_num(X[:, 0]) > 0.3)
         | (X[:, 4] == 2)).astype(np.float64)
    ds = lgb.Dataset(X, label=y, categorical_feature=[4, 5],
                     params={"max_bin": 31, "verbosity": -1})
    ds.construct()
    return ds, X, y


def _xla_route(bins, leaf_id, routing, leaf_chosen, leaf_feat, leaf_thr,
               leaf_dir, leaf_new, leaf_bits, Bmax):
    r_chosen = leaf_chosen[leaf_id]
    r_feat = leaf_feat[leaf_id]
    r_grp = routing.feat_group[r_feat]
    gb = jnp.take_along_axis(bins, r_grp[:, None].astype(jnp.int32), axis=1)[:, 0]
    fb = feature_local_bin(gb, r_feat, routing)
    r_thr = leaf_thr[leaf_id]
    r_dir = leaf_dir[leaf_id]
    is_cat = (r_dir & 2) != 0
    default_left = (r_dir & 1) != 0
    is_nan = (routing.nan_bin[r_feat] >= 0) & (fb == routing.nan_bin[r_feat])
    go_left_num = jnp.where(is_nan, default_left, fb <= r_thr)
    go_left_cat = leaf_bits.reshape(-1)[leaf_id * Bmax + fb]
    go_left = jnp.where(is_cat, go_left_cat, go_left_num)
    return jnp.where(r_chosen & ~go_left, leaf_new[leaf_id], leaf_id), go_left


@pytest.mark.slow
def test_route_exact_and_hist_close():
    ds, X, y = _dataset()
    dd = ds.device_data()
    bins = dd.bins
    routing = dd.routing
    N, G = bins.shape
    Bmax = dd.max_bins
    L, S = 8, 4
    rs = np.random.RandomState(3)
    i32 = jnp.int32

    leaf_id = jnp.asarray(rs.randint(0, 4, N).astype(np.int32))
    # leaf 0: numeric split on feature 1; leaf 1: categorical on feature 4;
    # leaf 2: numeric split on (possibly bundled/NaN) feature 0; leaf 3: no split
    leaf_chosen = jnp.asarray(np.array([1, 1, 1, 0, 0, 0, 0, 0], bool))
    leaf_feat = jnp.asarray(np.array([1, 4, 0, 0, 0, 0, 0, 0], np.int32))
    leaf_thr = jnp.asarray(np.array([7, 2, 3, 0, 0, 0, 0, 0], np.int32))
    leaf_dir = jnp.asarray(np.array([0, 2, 1, 0, 0, 0, 0, 0], np.int32))
    leaf_new = jnp.asarray(np.array([4, 5, 6, 0, 0, 0, 0, 0], np.int32))
    bits_np = np.zeros((L, Bmax), bool)
    bits_np[1, [1, 2, 4]] = True          # cat leaf: bins 1,2,4 go left
    leaf_bits = jnp.asarray(bits_np)

    grad = jnp.asarray(rs.randn(N).astype(np.float32))
    hess = jnp.abs(grad) + 0.25
    cnt = jnp.asarray((rs.rand(N) > 0.3).astype(np.float32))
    grad = grad * cnt
    hess = hess * cnt

    # oracle: XLA route then segsum hist of the smaller-child slots
    new_leaf_ref, _ = _xla_route(bins, leaf_id, routing, leaf_chosen, leaf_feat,
                                 leaf_thr, leaf_dir, leaf_new, leaf_bits, Bmax)
    # slots: smaller child of split i gets slot i; say children 4,5,6 are smaller
    slot_map = np.full(L, -1, np.int32)
    for i, smaller in enumerate([4, 5, 6]):
        slot_map[smaller] = i
    slot_ref = jnp.asarray(slot_map)[new_leaf_ref]
    hist_ref = _hist_segsum(bins, slot_ref, grad, hess, cnt, S, Bmax)

    # streaming kernel
    slay = pack_bins_T(bins)
    n_pad = slay.n_pad
    w_T = jnp.zeros((8, n_pad), jnp.float32)
    w_T = w_T.at[0, :N].set(grad).at[1, :N].set(hess).at[2, :N].set(cnt)
    # smaller child is the NEW (right) child for all three splits
    sl1 = jnp.zeros(L, i32)
    sr1 = jnp.zeros(L, i32).at[0].set(1).at[1].set(2).at[2].set(3)
    tabs = build_route_tables(leaf_chosen.astype(i32), leaf_feat, leaf_thr,
                              leaf_dir, leaf_new, sl1, sr1, jnp.zeros(L, i32),
                              routing, L)
    Bpad = -(-Bmax // 8) * 8
    bits_T = jnp.pad(leaf_bits.astype(jnp.bfloat16),
                     ((0, 0), (0, Bpad - Bmax))).T
    leaf_row = jnp.pad(leaf_id, (0, n_pad - N)).reshape(1, -1)
    new_leaf, hist, slot_cnt = route_and_hist(slay.bins_T, leaf_row, w_T, tabs,
                                              bits_T, S, Bmax, G, L,
                                              has_cat=True)

    np.testing.assert_array_equal(np.asarray(new_leaf[0, :N]),
                                  np.asarray(new_leaf_ref))
    np.testing.assert_allclose(np.asarray(hist),
                               np.asarray(hist_ref[..., :2]),
                               rtol=2e-3, atol=2e-3)
    # per-slot exact counts (0/1 weights are bf16-exact); any single group's
    # bins partition each slot's rows
    np.testing.assert_allclose(np.asarray(slot_cnt),
                               np.asarray(hist_ref[:, 0, :, 2].sum(-1)),
                               atol=1e-6)


def test_root_pass_matches_segsum():
    ds, X, y = _dataset(n=1500, seed=5)
    dd = ds.device_data()
    bins = dd.bins
    N, G = bins.shape
    Bmax = dd.max_bins
    L = 8
    rs = np.random.RandomState(0)
    grad = jnp.asarray(rs.randn(N).astype(np.float32))
    hess = jnp.abs(grad) + 0.5
    cnt = jnp.ones(N, jnp.float32)

    slay = pack_bins_T(bins)
    n_pad = slay.n_pad
    w_T = jnp.zeros((8, n_pad), jnp.float32)
    w_T = w_T.at[0, :N].set(grad).at[1, :N].set(hess).at[2, :N].set(cnt)
    zL = jnp.zeros(L, jnp.int32)
    tabs = build_route_tables(zL, zL, zL, zL, zL, zL, zL, zL.at[0].set(1),
                              dd.routing, L)
    Bpad = -(-Bmax // 8) * 8
    bits = jnp.zeros((Bpad, L), jnp.bfloat16)
    leaf_row = jnp.zeros((1, n_pad), jnp.int32)
    new_leaf, hist, slot_cnt = route_and_hist(slay.bins_T, leaf_row, w_T, tabs,
                                              bits, 1, Bmax, G, L, has_cat=True)
    hist_ref = _hist_segsum(bins, jnp.zeros(N, jnp.int32), grad, hess, cnt,
                            1, Bmax)
    np.testing.assert_array_equal(np.asarray(new_leaf[0, :N]), 0)
    np.testing.assert_allclose(np.asarray(hist),
                               np.asarray(hist_ref[..., :2]),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(slot_cnt), [float(N)], atol=1e-6)


# ---------------------------------------------------------------- root pass
def _root_case(name):
    """(bins (N, G) uint8, grad, hess int-valued f32, Bmax, bin_buckets,
    block) of one factored-root case."""
    rs = np.random.RandomState(sum(map(ord, name)))
    n, bmax, bb, block = 2048, 64, None, 1024
    if name == "g136_bin_buckets":
        # bucket-sorted mixed cardinalities, as binning.device_group_order
        # lays an MSLR-like table out
        bb = ((64, 100), (32, 20), (16, 10), (8, 6))
        bins = np.concatenate([rs.randint(0, b, (n, g)) for b, g in bb], 1)
    elif name in ("g5", "g17"):
        bins = rs.randint(0, 64, (n, int(name[1:])))
    elif name == "n_not_a_block_multiple":
        n = 1500
        bins = rs.randint(0, 64, (n, 28))
    elif name == "bins_at_digit_edges":
        bins = rs.choice([0, 7, 8, 62, 63], (n, 28))
    elif name == "bmax_odd_digits":          # 5 high digits, 7 bits a bin
        bmax = 37
        bins = rs.randint(0, bmax, (n, 9))
    elif name == "bmax_127":
        bmax = 127
        bins = rs.choice([0, 63, 64, 120, 126], (n, 6))
    else:                                    # HIGGS-like: G = 28, B = 64
        bins = rs.randint(0, 64, (n, 28))
    gi = rs.randint(-32, 33, n)
    hi = rs.randint(0, 33, n)
    if name == "weights_at_int8_limits":
        gi = rs.choice([-127, 127], n)
        hi = np.full(n, 127)
    if name == "out_of_bag_zero_weights":
        out = rs.rand(n) < 0.6
        gi[out] = 0
        hi[out] = 0
    return (bins.astype(np.uint8), gi.astype(np.float32),
            hi.astype(np.float32), bmax, bb, block)


def _root_operands(bins, grad, hess, Bmax, block, L=8):
    """(bins_T, leaf ids, w_T, tabs, bits) as the grower hands them to its
    root pass: every row in leaf 0, tables that split nothing and keep leaf
    0 in slot 0."""
    N = bins.shape[0]
    slay = pack_bins_T(jnp.asarray(bins), block, max_bins=Bmax)
    w_T = jnp.zeros((8, slay.n_pad), jnp.float32)
    w_T = (w_T.at[0, :N].set(jnp.asarray(grad))
              .at[1, :N].set(jnp.asarray(hess)).at[2, :N].set(1.0))
    tabs = jnp.zeros((NUM_TAB, L), jnp.float32).at[T_SLOT_KEEP, 0].set(1.0)
    bits = jnp.zeros((-(-Bmax // 8) * 8, L), jnp.bfloat16)
    return (slay.bins_T, jnp.zeros((1, slay.n_pad), jnp.int32), w_T, tabs,
            bits)


ROOT_CASES = ["g28_b64", "g136_bin_buckets", "g5", "g17",
              "n_not_a_block_multiple", "bins_at_digit_edges",
              "bmax_odd_digits", "bmax_127", "weights_at_int8_limits",
              "out_of_bag_zero_weights", "compacted_view"]


@pytest.mark.parametrize("case", ROOT_CASES)
def test_factored_root_exact(case):
    """The factored root pass (route_and_hist(root=True) on the int path
    over u8-layout bins) against _hist_segsum and against the S = 1 call of
    the 64-slot kernel it replaces: the same shape and dtype, every sum
    EXACT."""
    bins, gi, hi, Bmax, bb, block = _root_case(case)
    N, G = bins.shape
    L = 8
    bins_T, lid, w_T, tabs, bits = _root_operands(bins, gi, hi, Bmax, block)
    assert bins_T.dtype == jnp.int8
    assert root_pass_kind(bins_T.dtype, True) == "factored"
    in_bag = np.ones(N, bool)
    if case == "compacted_view":
        # what the grower streams under GOSS / bagging: in-bag rows
        # partitioned to the front, the rest truncated or zero-weighted
        from lightgbm_tpu.ops.compact import compact_transposed_view
        in_bag = np.random.RandomState(3).rand(N) < 0.4
        keep = jnp.asarray(in_bag, jnp.float32)
        w_T = w_T.at[:3, :N].multiply(keep[None, :])
        bins_T, w_T, placed = compact_transposed_view(bins_T, w_T, 2, block,
                                                      block)
        assert int(placed[0]) == in_bag.sum()
        lid = lid[:, :block]
        assert bins_T.shape[1] == block and in_bag.sum() <= block
    args = (bins_T, lid, w_T, tabs, bits, 1, Bmax, G, L)
    kw = dict(block_rows=block, has_cat=False, int_weights=True,
              bin_buckets=bb)
    lid_new, hist, _ = route_and_hist(*args, root=True, **kw)
    _, hist_onehot, _ = route_and_hist(*args, **kw)
    w = jnp.asarray(in_bag, jnp.float32)
    hist_ref = _hist_segsum(jnp.asarray(bins), jnp.zeros(N, jnp.int32),
                            jnp.asarray(gi) * w, jnp.asarray(hi) * w, w, 1,
                            Bmax)
    assert hist.shape == hist_onehot.shape == (1, G, Bmax, 2)
    assert hist.dtype == hist_onehot.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_onehot))
    np.testing.assert_array_equal(np.asarray(hist, np.float64),
                                  np.asarray(hist_ref[..., :2], np.float64))
    np.testing.assert_array_equal(np.asarray(lid_new), 0)


def test_factored_root_exact_over_sixteen_tiles():
    """G = 2,000 at the benchmark's wide cell's tile shape: the factored
    root a tile a sweep, against the tiled S = 1 one-hot root and
    _hist_segsum, every sum exact; the last tile holds 80 groups of 128."""
    from lightgbm_tpu.pallas.stream_kernel import stream_tiling
    rs = np.random.RandomState(29)
    N, G, Bmax, L = 1024, 2000, 63, 8
    plan = stream_tiling(Bmax, G, True)
    assert (plan.tile_groups, plan.num_tiles) == (128, 16)
    bins = rs.randint(0, Bmax, (N, G)).astype(np.uint8)
    gi = rs.randint(-32, 33, N).astype(np.float32)
    hi = rs.randint(0, 33, N).astype(np.float32)
    bins_T = pack_bins_T(jnp.asarray(bins), plan.block_rows, max_bins=Bmax,
                         tile_groups=plan.tile_groups).bins_T
    assert bins_T.shape == (2048, N) and bins_T.dtype == jnp.int8
    w_T = (jnp.zeros((8, N), jnp.float32).at[0].set(jnp.asarray(gi))
              .at[1].set(jnp.asarray(hi)).at[2].set(1.0))
    tabs = jnp.zeros((NUM_TAB, L), jnp.float32).at[T_SLOT_KEEP, 0].set(1.0)
    bits = jnp.zeros((64, L), jnp.bfloat16)
    args = (bins_T, jnp.zeros((1, N), jnp.int32), w_T, tabs, bits, 1, Bmax,
            G, L)
    kw = dict(block_rows=plan.block_rows, has_cat=False, int_weights=True,
              tile_groups=plan.tile_groups)
    _, hist, _ = route_and_hist(*args, root=True, **kw)
    _, hist_onehot, cnt = route_and_hist(*args, **kw)
    ref = _hist_segsum(jnp.asarray(bins), jnp.zeros(N, jnp.int32),
                       jnp.asarray(gi), jnp.asarray(hi),
                       jnp.ones(N, jnp.float32), 1, Bmax)
    assert hist.shape == hist_onehot.shape == (1, G, Bmax, 2)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_onehot))
    np.testing.assert_array_equal(np.asarray(hist, np.float64),
                                  np.asarray(ref[..., :2], np.float64))
    assert float(cnt[0]) == N


def test_root_flag_leaves_the_other_paths_alone():
    """root=True changes nothing where the factored form does not engage:
    float weights, the packed-word layout, a multiclass program."""
    assert root_pass_kind(jnp.int8, False) == "onehot"
    assert root_pass_kind(jnp.int32, True) == "onehot"
    assert root_pass_kind(jnp.int8, True, num_class=3) == "onehot"
    bins, gi, hi, Bmax, _, block = _root_case("g5")
    operands = _root_operands(bins, gi * 0.37, hi * 0.11, Bmax, block)
    args = (*operands, 1, Bmax, bins.shape[1], 8)
    kw = dict(block_rows=block, has_cat=False)
    a = route_and_hist(*args, **kw)
    b = route_and_hist(*args, root=True, **kw)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------- small-slot pass
SMALL_L = 8
# (leaf, group, threshold, new leaf id, smaller child is the left one) of
# the leaves a round of k splits, in slot order; rows start spread over
# leaves 0..3, so leaves 1 and 3 (and 2 at k = 1) hold rows of no slot
SMALL_SPLITS = [(0, 3, 24, 4, True), (2, 1, 40, 5, False),
                (1, 0, 9, 6, True)]


def _small_operands(case, k, block=None):
    """A round that splits k leaves over a _root_case table: (operands of
    route_and_hist, the case's static arguments, NumPy's new leaf ids and
    slots).  case "categorical": leaf 0's split is a bitset over its bins."""
    name = "g28_b64" if case == "categorical" else case
    bins, gi, hi, Bmax, bb, blk = _root_case(name)
    block = block or blk
    N, G = bins.shape
    L = SMALL_L
    rs = np.random.RandomState(k + sum(map(ord, case)))
    lid = rs.randint(0, 4, N).astype(np.int32)
    tabs = np.zeros((NUM_TAB, L), np.float32)
    bits = np.zeros((-(-Bmax // 8) * 8, L), np.float32)
    new_lid, slot = lid.copy(), np.full(N, -1, np.int32)
    for s, (leaf, grp, thr, new, left_smaller) in enumerate(SMALL_SPLITS[:k]):
        grp = min(grp, G - 1)
        col = bins[:, grp].astype(np.int64)
        go_left = col <= thr
        tabs[sk.T_CHOSEN, leaf] = 1
        tabs[sk.T_NEWID_LO, leaf] = new
        tabs[sk.T_WORD_LO, leaf] = grp >> 2
        tabs[sk.T_SHIFT, leaf] = (grp & 3) << 3
        tabs[sk.T_NBINS, leaf] = Bmax
        tabs[sk.T_THR, leaf] = thr
        if case == "categorical" and s == 0:
            cats = rs.rand(Bmax) < 0.4
            bits[:Bmax, leaf] = cats
            tabs[sk.T_ISCAT, leaf] = 1
            go_left = cats[col]
        tabs[sk.T_SLOT_L if left_smaller else sk.T_SLOT_R, leaf] = s + 1
        rows = lid == leaf
        new_lid[rows & ~go_left] = new
        slot[rows & (go_left == left_smaller)] = s
    slay = pack_bins_T(jnp.asarray(bins), block, max_bins=Bmax)
    pad = slay.n_pad - N
    w_T = jnp.zeros((8, slay.n_pad), jnp.float32)
    w_T = (w_T.at[0, :N].set(jnp.asarray(gi))
              .at[1, :N].set(jnp.asarray(hi)).at[2, :N].set(1.0))
    operands = (slay.bins_T, jnp.asarray(np.pad(lid, (0, pad)))[None, :],
                w_T, jnp.asarray(tabs), jnp.asarray(bits, jnp.bfloat16))
    static = dict(bmax=Bmax, num_groups=G, num_leaves=L, block_rows=block,
                  has_cat=case == "categorical", int_weights=True,
                  bin_buckets=bb)
    return operands, static, (bins, gi, hi, new_lid, slot)


SMALL_CASES = ["g28_b64", "g136_bin_buckets", "g5", "g17",
               "n_not_a_block_multiple", "bins_at_digit_edges",
               "weights_at_int8_limits", "out_of_bag_zero_weights",
               "categorical"]


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("case", SMALL_CASES)
def test_small_slot_pass_exact(case, k):
    """A round of k = 1 or 2 splits through route_and_hist_live takes the
    small-slot pass and returns what the 64-slot pass returns on the same
    rows — new leaf ids, int32 histograms (zeros in the slots past k), exact
    slot counts — and what NumPy's route + _hist_segsum give: every sum
    EXACT.  Slot 0's smaller child is the left one, slot 1's the right one;
    rows of the leaves that are not split sit in neither slot."""
    operands, static, (bins, gi, hi, new_lid, slot) = _small_operands(case, k)
    N, G = bins.shape
    Bmax = static["bmax"]
    assert int(small_pass_index(jnp.int32(k), jnp.int8, True, 64)) == k
    full = route_and_hist(*operands, num_slots=64, **static)
    live = route_and_hist_live(jnp.int32(k), *operands, num_slots=64,
                               **static)
    small = route_and_hist(*operands, num_slots=64, small_slots=k, **static)
    for a, b, c in zip(full, live, small):
        assert a.shape == b.shape == c.shape and a.dtype == b.dtype == c.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    new_leaf, hist, cnt = live
    assert hist.shape == (64, G, Bmax, 2) and hist.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(new_leaf[0, :N]), new_lid)
    ref = _hist_segsum(jnp.asarray(bins), jnp.asarray(slot), jnp.asarray(gi),
                       jnp.asarray(hi), jnp.ones(N, jnp.float32), k, Bmax)
    np.testing.assert_array_equal(np.asarray(hist[:k], np.float64),
                                  np.asarray(ref[..., :2], np.float64))
    assert not np.asarray(hist[k:]).any()
    np.testing.assert_array_equal(
        np.asarray(cnt), np.bincount(slot[slot >= 0], minlength=64))
    assert 0 < (slot >= 0).sum() < N and len(set(slot)) == k + 1


@pytest.mark.parametrize("k", [0, 3, 64])
def test_other_split_counts_take_the_64_slot_pass(k):
    """k = 0, 3 and 64: small_pass_index says 0 and the switch's result is
    the 64-slot pass's — at k = 3 on tables whose third slot the small pass
    would lose."""
    assert int(small_pass_index(jnp.int32(k), jnp.int8, True, 64)) == 0
    operands, static, (bins, _, _, _, slot) = _small_operands(
        "g5", min(k, 3))
    full = route_and_hist(*operands, num_slots=64, **static)
    live = route_and_hist_live(jnp.int32(k), *operands, num_slots=64,
                               **static)
    for a, b in zip(full, live):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if k == 3:
        assert np.asarray(live[1][2]).any() and (slot == 2).any()


@pytest.mark.parametrize("path", ["float", "packed_words", "num_class_3"])
def test_small_slot_pass_engages_on_the_factored_path_only(path):
    """Float gradients, the packed-word layout and a multiclass program keep
    the one-hot pass at every k: one kernel is traced, not a switch over
    three, and small_slots is refused, as root_pass_kind() says "onehot" there."""
    dtype = jnp.int32 if path == "packed_words" else jnp.int8
    int_w = path != "float"
    K = 3 if path == "num_class_3" else 1
    assert root_pass_kind(dtype, int_w, K) == "onehot"
    assert small_pass_index(jnp.int32(1), dtype, int_w, 64, K) is None
    if path == "num_class_3":
        return
    operands, static, _ = _small_operands("g5", 1)
    static["int_weights"] = int_w
    if path == "packed_words":
        # four groups a word, the layout of max_bin > 127
        b8 = np.asarray(operands[0]).view(np.uint8).astype(np.uint32)
        words = sum(b8[j::4] << (8 * j) for j in range(4))
        operands = (jnp.asarray(words.astype(np.int32)),) + operands[1:]

    def live(k, *ops):
        return route_and_hist_live(k, *ops, num_slots=64, **static)

    for k in (1, 2):
        text = str(jax.make_jaxpr(live)(jnp.int32(k), *operands))
        assert text.count("pallas_call[") == 1
    full = route_and_hist(*operands, num_slots=64, **static)
    for a, b in zip(full, live(jnp.int32(1), *operands)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="small-slot"):
        route_and_hist(*operands, num_slots=64, small_slots=1, **static)
    # and the factored path does trace one
    operands, static, _ = _small_operands("g5", 1)
    assert str(jax.make_jaxpr(
        lambda k, *ops: route_and_hist_live(k, *ops, num_slots=64, **static)
    )(jnp.int32(1), *operands)).count("pallas_call[") == 3


def test_int8_hist_exact():
    """int_weights path: integer grad/hess rows accumulate EXACTLY (int32)."""
    ds, X, y = _dataset(n=1500, seed=5)
    dd = ds.device_data()
    bins = dd.bins
    N, G = bins.shape
    Bmax = dd.max_bins
    L = 8
    rs = np.random.RandomState(0)
    gi = rs.randint(-32, 33, N).astype(np.float32)   # integer-valued
    hi = rs.randint(0, 33, N).astype(np.float32)
    cnt = jnp.ones(N, jnp.float32)

    slay = pack_bins_T(bins)
    n_pad = slay.n_pad
    w_T = jnp.zeros((8, n_pad), jnp.float32)
    w_T = (w_T.at[0, :N].set(jnp.asarray(gi)).at[1, :N].set(jnp.asarray(hi))
              .at[2, :N].set(cnt))
    zL = jnp.zeros(L, jnp.int32)
    tabs = build_route_tables(zL, zL, zL, zL, zL, zL, zL, zL.at[0].set(1),
                              dd.routing, L)
    Bpad = -(-Bmax // 8) * 8
    bits = jnp.zeros((Bpad, L), jnp.bfloat16)
    leaf_row = jnp.zeros((1, n_pad), jnp.int32)
    _, hist, slot_cnt = route_and_hist(slay.bins_T, leaf_row, w_T, tabs,
                                       bits, 1, Bmax, G, L, has_cat=True,
                                       int_weights=True)
    hist_ref = _hist_segsum(bins, jnp.zeros(N, jnp.int32), jnp.asarray(gi),
                            jnp.asarray(hi), cnt, 1, Bmax)
    assert hist.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(hist, np.float64),
                                  np.asarray(hist_ref[..., :2], np.float64))
    np.testing.assert_allclose(np.asarray(slot_cnt), [float(N)], atol=1e-6)


@pytest.mark.slow
def test_stream_end_to_end_close():
    """Full training with the stream backend matches segsum predictions to
    bf16-accumulation tolerance."""
    ds_params = {"max_bin": 31, "verbosity": -1}
    rs = np.random.RandomState(11)
    n = 1200
    X = rs.randn(n, 6)
    X[:, 4] = rs.randint(0, 7, n)
    X[rs.rand(n) < 0.1, 0] = np.nan
    y = ((X[:, 1] > 0) ^ (np.nan_to_num(X[:, 0]) > 0.3)
         | (X[:, 4] == 2)).astype(np.float64)
    preds = {}
    for backend in ("segsum", "stream"):
        ds = lgb.Dataset(X, label=y, categorical_feature=[4],
                         params=ds_params)
        bst = lgb.train({"objective": "binary", "num_leaves": 8,
                         "verbosity": -1, "max_bin": 31,
                         "min_data_in_leaf": 5, "hist_backend": backend,
                         "max_splits_per_round": 4}, ds, num_boost_round=3)
        preds[backend] = bst.predict(X, raw_score=True)
    # bf16 two-pass hist sums can flip near-tie splits for a few rows; demand
    # distribution-level agreement rather than per-row equality
    diff = np.abs(preds["stream"] - preds["segsum"])
    assert np.mean(diff < 0.05) > 0.95
    assert np.corrcoef(preds["stream"], preds["segsum"])[0, 1] > 0.99


@pytest.mark.slow
def test_stream_final_sprint_completes_tree():
    """num_leaves >= 130 with the stream backend engages the FINAL-SPRINT
    schedule (ops/grow.py: the hist loop exits once one route-only round can
    finish, batching up to 2S splits without histograms).  The tree must
    still reach the full leaf budget with exact leaf counts."""
    rs = np.random.RandomState(5)
    n = 6000
    X = rs.randn(n, 8)
    y = (X[:, 0] + np.sin(3 * X[:, 1]) + 0.3 * rs.randn(n) > 0).astype(
        np.float64)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbosity": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": 140,
                     "verbosity": -1, "max_bin": 63, "min_data_in_leaf": 2,
                     "hist_backend": "stream", "max_splits_per_round": 64},
                    ds, num_boost_round=2)
    dumped = bst.dump_model()
    for t in dumped["tree_info"]:
        assert t["num_leaves"] == 140
        # exact per-leaf counts from the sprint round's count dot
        counts = []
        def walk(node):
            if "leaf_count" in node:
                counts.append(node["leaf_count"])
            else:
                walk(node["left_child"]); walk(node["right_child"])
        walk(t["tree_structure"])
        assert sum(counts) == n
        assert min(counts) >= 2
    # quality smoke: the model actually separates the classes
    auc_ranks = np.argsort(np.argsort(bst.predict(X, raw_score=True)))
    pos = auc_ranks[y > 0.5].mean()
    neg = auc_ranks[y < 0.5].mean()
    assert pos > neg + n / 10


def test_bucketed_m_axis_exact():
    """The bucketed one-hot M-axis (bin_buckets runs over bucket-sorted
    groups) must produce BIT-IDENTICAL int32 histograms, routes and counts
    to the uniform G*Bmax layout on mixed-cardinality data."""
    rs = np.random.RandomState(7)
    n = 1800
    X = np.column_stack([
        rs.randint(0, 2, (n, 2)).astype(float),      # 8-bucket
        rs.randint(0, 10, (n, 3)).astype(float),     # 16-bucket
        rs.randint(0, 25, (n, 2)).astype(float),     # 32-bucket
        rs.randn(n, 3)])                             # 64-bucket
    y = (X[:, 0] + X[:, 9] > 0.5).astype(float)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbosity": -1})
    ds.construct()
    dd = ds.device_data()
    bins = dd.bins
    N, G = bins.shape
    Bmax = dd.max_bins
    L = 8
    counts = np.asarray(ds.binned.group_bin_counts)
    # groups must be bucket-sorted descending by construction
    buckets = []
    for cnt in counts:
        b = 8
        while b < int(cnt):
            b *= 2
        if buckets and buckets[-1][0] == b:
            buckets[-1][1] += 1
        else:
            buckets.append([b, 1])
    bb = tuple((int(b), int(g)) for b, g in buckets)
    assert len(bb) >= 3 and sum(g for _, g in bb) == G
    assert [b for b, _ in bb] == sorted([b for b, _ in bb], reverse=True)

    gi = rs.randint(-32, 33, N).astype(np.float32)
    hi = rs.randint(0, 33, N).astype(np.float32)
    slay = pack_bins_T(bins)
    n_pad = slay.n_pad
    w_T = jnp.zeros((8, n_pad), jnp.float32)
    w_T = (w_T.at[0, :N].set(jnp.asarray(gi)).at[1, :N].set(jnp.asarray(hi))
              .at[2, :N].set(1.0))
    zL = jnp.zeros(L, jnp.int32)
    # a real split on feature 0 so routing is exercised too
    chosen = zL.at[0].set(1)
    feats = zL
    thrs = zL.at[0].set(0)
    newid = zL.at[0].set(1)
    tabs = build_route_tables(chosen, feats, thrs, zL, newid,
                              zL.at[0].set(1), zL, zL, dd.routing, L)
    Bpad = -(-Bmax // 8) * 8
    bits = jnp.zeros((Bpad, L), jnp.bfloat16)
    leaf_row = jnp.zeros((1, n_pad), jnp.int32)
    args = (slay.bins_T, leaf_row, w_T, tabs, bits, 2, Bmax, G, L)
    kw = dict(has_cat=False, int_weights=True)
    nl_u, hist_u, cnt_u = route_and_hist(*args, **kw)
    nl_b, hist_b, cnt_b = route_and_hist(*args, bin_buckets=bb, **kw)
    np.testing.assert_array_equal(np.asarray(nl_u), np.asarray(nl_b))
    np.testing.assert_array_equal(np.asarray(hist_u), np.asarray(hist_b))
    np.testing.assert_allclose(np.asarray(cnt_u), np.asarray(cnt_b),
                               atol=1e-6)


# ---- the 64-slot pass's bin one-hot built in words (onehot_build_kind) ----

def _word_case(G, bmax, K, tile_groups, n=1300, L=8, S=4, block=1024):
    """Operands of a 64-slot-style pass over a random (n, G) table of `bmax`
    bins for K classes, with rows in no slot (leaf 3 keeps none, leaf 1's
    left child has none) and rows past the data (n is no multiple of the
    block; they carry zero weights), and the np.add.at histograms."""
    from lightgbm_tpu.ops.grow import RoutingLayout
    rs = np.random.RandomState(1000 * G + 10 * bmax + K)
    bins = rs.randint(0, bmax, size=(n, G)).astype(np.uint8)
    bins[:, G - 1] = bmax - 1          # the last group, the last bin: the
    bins[: n // 2, 0] = 0              # corners of the M-axis are hit
    routing = RoutingLayout(
        feat_group=jnp.arange(G, dtype=jnp.int32),
        span_start=jnp.zeros(G, jnp.int32),
        default_bin=jnp.zeros(G, jnp.int32), bundled=jnp.zeros(G, bool),
        nan_bin=jnp.full(G, -1, jnp.int32),
        num_bins=jnp.full(G, bmax, jnp.int32))
    i32 = jnp.int32
    n_pad = -(-n // block) * block
    leaf = np.zeros((K, n_pad), np.int32)
    w = np.zeros((2 * K + 6, n_pad), np.float32)
    w[2 * K, :n] = 1.0
    tabs, want = [], np.zeros((K, S, G, bmax, 2), np.int64)
    counts = np.zeros((K, S), np.int64)
    for k in range(K):
        leaf[k, :n] = rs.randint(0, 4, n)
        w[2 * k, :n] = rs.randint(-32, 33, n)
        w[2 * k + 1, :n] = rs.randint(0, 33, n)
        feat = rs.randint(0, G, L).astype(np.int32)
        thr = rs.randint(0, bmax - 1, L).astype(np.int32)
        chosen = np.array([1, 1, 0, 0] + [0] * (L - 4), np.int32)
        # slot + 1 of the left child, the right child, an unsplit leaf
        sl1 = np.array([1, 0, 0, 0] + [0] * (L - 4), np.int32)
        sr1 = np.array([2, 3, 0, 0] + [0] * (L - 4), np.int32)
        sk1 = np.array([0, 0, 4, 0] + [0] * (L - 4), np.int32)
        newid = np.array([4, 5, 0, 0] + [0] * (L - 4), np.int32)
        tabs.append(build_route_tables(
            *(jnp.asarray(a, i32) for a in (chosen, feat, thr, np.zeros(L),
                                            newid, sl1, sr1, sk1)),
            routing, L))
        lk = leaf[k, :n]
        left = bins[np.arange(n), feat[lk]] <= thr[lk]
        slot = np.where(chosen[lk] > 0, np.where(left, sl1[lk], sr1[lk]),
                        sk1[lk]) - 1
        keep = slot >= 0
        assert (~keep).sum() > n // 8
        counts[k] = np.bincount(slot[keep], minlength=S)
        for g in range(G):
            for c in range(2):
                np.add.at(want[k, :, g, :, c], (slot[keep], bins[keep, g]),
                          w[2 * k + c, :n][keep].astype(np.int64))
    B = -(-bmax // 8) * 8
    static = dict(num_slots=S, bmax=bmax, num_groups=G, num_leaves=L,
                  block_rows=block, has_cat=False, int_weights=True,
                  num_class=K, tile_groups=tile_groups)
    operands = (jnp.asarray(leaf), jnp.asarray(w),
                jnp.concatenate(tabs, axis=1),
                jnp.zeros((B, K * L), jnp.bfloat16))
    return bins, operands, static, (want if K > 1 else want[0]), counts


WORD_CASES = [
    pytest.param(5, 15, 1, 0, id="g5_b15"),
    pytest.param(28, 63, 1, 0, id="g28_b63"),
    pytest.param(67, 63, 1, 0, id="g67_b63_last_word_part_filled"),
    pytest.param(136, 127, 1, 0, id="g136_b127_uniform"),
    pytest.param(28, 63, 3, 0, id="g28_b63_k3"),
    pytest.param(5, 127, 3, 0, id="g5_b127_k3"),
    pytest.param(32, 63, 1, 0, id="g32_whole_word_array_b_major"),
    pytest.param(30, 15, 1, 0, id="g30_axis_of_32_b_major"),
    pytest.param(136, 63, 1, 128, id="g136_two_tiles_of_128"),
    pytest.param(136, 15, 3, 128, id="g136_b15_k3_two_tiles_of_128"),
]


@pytest.mark.parametrize("G, bmax, K, tile_groups", WORD_CASES)
def test_word_built_pass_exact(G, bmax, K, tile_groups):
    """The pass whose bin one-hot is built in words (u8 bins, integer
    weights, the uniform axis) against the compare-built one over the same
    table in the packed-word layout and against np.add.at: int32, bit for
    bit, leaf ids and counts too."""
    bins, operands, static, want, counts = _word_case(G, bmax, K,
                                                      tile_groups)
    out = {}
    for kind, max_bins in (("words", bmax), ("compare", 255)):
        bins_T = pack_bins_T(jnp.asarray(bins), static["block_rows"],
                             max_bins=max_bins,
                             tile_groups=tile_groups).bins_T
        assert sk.onehot_build_kind(bins_T.dtype, True) == kind
        out[kind] = route_and_hist(bins_T, *operands, **static)
    new_leaf, hist, cnt = out["words"]
    assert hist.dtype == jnp.int32 and hist.shape == want.shape
    np.testing.assert_array_equal(np.asarray(hist), want)
    for a, b in zip(out["words"], out["compare"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(cnt).reshape(K, -1), counts)


@pytest.mark.parametrize("dtype, int_weights, buckets, want", [
    pytest.param(jnp.int8, True, None, "words", id="u8_int_uniform"),
    pytest.param(jnp.int8, False, None, "compare", id="u8_float"),
    pytest.param(jnp.int32, True, None, "compare", id="packed_words_int"),
    pytest.param(jnp.int8, True, ((64, 3), (16, 2)), "compare",
                 id="u8_int_bucketed"),
    pytest.param(jnp.int32, False, None, "compare", id="packed_words_float"),
])
def test_onehot_build_kind_rule(dtype, int_weights, buckets, want):
    """Which programs build the one-hot in words, and that the M-axis and the
    tiling follow the same rule: whole words of four groups there."""
    assert sk.onehot_build_kind(dtype, int_weights, buckets) == want
    assert sk.onehot_rows(67, 64, want == "words") == (
        4352 if want == "words" else 4288)
    # the word form's row order: word-major unless the axis' groups fill
    # whole 32-group word arrays (the M-tiles), which keep the b-major one
    assert [sk.onehot_word_major(g) for g in (28, 68, 32, 128, 136)] == [
        True, True, False, False, True]
    if buckets is None:
        bmax = 63 if dtype == jnp.int8 else 255
        plan = sk.stream_tiling(bmax, 19, int_weights)
        assert plan.num_tiles == 1 and plan.tile_m_rows == (
            (20 if want == "words" else 19) * (bmax + 1))


@pytest.mark.parametrize("params, kind", [
    pytest.param({"use_quantized_grad": True}, "words", id="quantized_u8"),
    pytest.param({}, "compare", id="float"),
    pytest.param({"use_quantized_grad": True, "max_bin": 255}, "compare",
                 id="quantized_packed_words"),
])
def test_flag_poll_record_carries_the_onehot_build(params, kind):
    """The engine's static poll fields say how the program's 64-slot passes
    build their one-hot, beside root_pass / hist_tiles."""
    rs = np.random.RandomState(5)
    X = rs.randn(600, 6)
    ds = lgb.Dataset(X, label=(X[:, 0] > 0).astype(float),
                     params={"max_bin": params.get("max_bin", 63),
                             "verbosity": -1})
    bst = lgb.Booster({"objective": "binary", "num_leaves": 7,
                       "hist_backend": "stream", "verbosity": -1, **params},
                      ds)
    eng = bst.engine
    assert eng._poll_tiling["onehot_build"] == kind
    assert eng._poll_tiling["hist_m_rows"] == eng._stream_tiling.tile_m_rows
