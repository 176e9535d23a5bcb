"""A pass over a compact view stops at its live row blocks (CPU interpret).

ops/compact.py moves a sampled tree's in-bag rows to the front of a view of
fixed capacity; every column past them carries zero weights.  The grower
hands each pass over the view `row_blocks` = the blocks that hold in-bag
rows, and the one-tile pass calls stream those alone.  Here every variant -
the factored root, the small-slot pass, the 64-slot pass (one tree and three
class trees) and the route-only pass - is held to the same call over the
whole view: histograms and slot counts equal, leaf ids equal on every
streamed column, and the columns past them left as they came in.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.compact import compact_transposed_view, live_row_blocks
from lightgbm_tpu.pallas import stream_kernel as sk
from lightgbm_tpu.pallas.stream_kernel import (NUM_TAB, pack_bins_T,
                                               route_and_hist,
                                               route_and_hist_live)

T = 256                        # rows a kernel block
N = 8 * T                      # rows of the table
CAPACITY = 4 * T               # columns of the compact view
G, BMAX, L = 28, 64, 8
# (leaf, group, threshold, new leaf id) of the leaves a round splits, in
# slot order: the left child takes the slot
SPLITS = [(0, 3, 24, 4), (2, 1, 40, 5), (1, 0, 9, 6)]

IN_BAG = {
    "block_boundary": 2 * T,
    "mid_block": 2 * T + 77,
    "none": 0,
    "capacity": CAPACITY,
    "over_capacity": CAPACITY + 100,
}
PASSES = ["root", "small_1", "small_2", "slots_64", "slots_64_3_classes",
          "route_only"]


def _tables(k, K):
    """A round that splits the first k of SPLITS in each of K class trees
    (tables side by side); k = 0 keeps every row of leaf 0 in slot 0."""
    tabs = np.zeros((NUM_TAB, K * L), np.float32)
    for c in range(K):
        if not k:
            tabs[sk.T_SLOT_KEEP, c * L] = 1
        for s, (leaf, grp, thr, new) in enumerate(SPLITS[:k]):
            at = c * L + leaf
            tabs[sk.T_CHOSEN, at] = 1
            tabs[sk.T_NEWID_LO, at] = new
            tabs[sk.T_WORD_LO, at] = grp >> 2
            tabs[sk.T_SHIFT, at] = (grp & 3) << 3
            tabs[sk.T_NBINS, at] = BMAX
            tabs[sk.T_THR, at] = thr
            tabs[sk.T_SLOT_L, at] = s + 1
    return jnp.asarray(tabs)


def _compact_operands(in_bag, K, root):
    """(compact bins_T, leaf ids over the view, w_T, the in-bag columns
    placed, row_blocks) for `in_bag` rows of N in the bag, scattered."""
    rs = np.random.RandomState(in_bag + 7 * K)
    bins = rs.randint(0, BMAX, (N, G)).astype(np.uint8)
    mask = np.zeros(N, np.float32)
    mask[rs.permutation(N)[:in_bag]] = 1
    w_T = np.zeros((8, N), np.float32)
    w_T[:2 * K] = rs.randint(-32, 33, (2 * K, N)) * mask
    w_T[1:2 * K:2] = np.abs(w_T[1:2 * K:2])
    w_T[2 * K] = mask
    bins_T = pack_bins_T(jnp.asarray(bins), T, max_bins=BMAX).bins_T
    bins_c, w_c, placed = compact_transposed_view(
        bins_T, jnp.asarray(w_T), 2 * K, CAPACITY, T)
    assert int(placed[0]) == min(in_bag, CAPACITY)
    lid = (np.zeros((K, CAPACITY), np.int32) if root
           else rs.randint(0, 4, (K, CAPACITY)).astype(np.int32))
    return bins_c, jnp.asarray(lid), w_c, placed, live_row_blocks(placed, T)


@pytest.mark.parametrize("which", PASSES)
@pytest.mark.parametrize("count", list(IN_BAG))
def test_a_pass_over_the_live_blocks_is_the_whole_views(count, which):
    K = 3 if which == "slots_64_3_classes" else 1
    root = which == "root"
    k = {"root": 0, "small_1": 1, "small_2": 2}.get(which, 3)
    bins_c, lid, w_c, placed, rb = _compact_operands(IN_BAG[count], K, root)
    streamed = int(rb[0]) * T
    assert int(rb[0]) == max(1, -(-int(placed[0]) // T))
    bits = jnp.zeros((BMAX, K * L), jnp.bfloat16)
    kw = dict(num_slots=1 if root else 64, bmax=BMAX, num_groups=G,
              num_leaves=L, block_rows=T, has_cat=False, int_weights=True,
              num_class=K, root=root, with_hist=which != "route_only")
    if which.startswith("small"):
        kw["small_slots"] = k
    operands = (bins_c, lid, w_c, _tables(k, K), bits)
    whole = route_and_hist(*operands, **kw)
    live = route_and_hist(*operands, row_blocks=rb, **kw)
    (lid_w, hist_w, cnt_w), (lid_l, hist_l, cnt_l) = whole, live
    assert hist_l.shape == hist_w.shape and hist_l.dtype == hist_w.dtype
    np.testing.assert_array_equal(np.asarray(hist_l), np.asarray(hist_w))
    np.testing.assert_array_equal(np.asarray(cnt_l), np.asarray(cnt_w))
    np.testing.assert_array_equal(np.asarray(lid_l)[:, :streamed],
                                  np.asarray(lid_w)[:, :streamed])
    # past the live blocks the leaf ids are the ones that went in
    np.testing.assert_array_equal(np.asarray(lid_l)[:, streamed:],
                                  np.asarray(lid)[:, streamed:])
    if which != "route_only" and int(placed[0]):
        assert np.asarray(hist_l).any()
    if not root:
        # the route moved in-bag rows: the comparison saw routing
        moved = np.asarray(lid_w)[:, :int(placed[0])] != np.asarray(lid)[
            :, :int(placed[0])]
        assert moved.any() == bool(int(placed[0]))


@pytest.mark.parametrize("live_slots", [1, 2, 3])
def test_the_round_switch_carries_the_live_blocks(live_slots):
    """route_and_hist_live (the grower's entry) hands row_blocks to whichever
    pass its switch takes."""
    bins_c, lid, w_c, placed, rb = _compact_operands(IN_BAG["mid_block"], 1,
                                                     False)
    operands = (bins_c, lid, w_c, _tables(live_slots, 1),
                jnp.zeros((BMAX, L), jnp.bfloat16))
    kw = dict(block_rows=T, has_cat=False, int_weights=True)
    whole = route_and_hist_live(jnp.int32(live_slots), *operands, 64, BMAX,
                                G, L, **kw)
    live = route_and_hist_live(jnp.int32(live_slots), *operands, 64, BMAX, G,
                               L, row_blocks=rb, **kw)
    streamed = int(rb[0]) * T
    assert streamed < CAPACITY
    for a, b in zip(whole[1:], live[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(live[0])[:, :streamed],
                                  np.asarray(whole[0])[:, :streamed])
    np.testing.assert_array_equal(np.asarray(live[0])[:, streamed:],
                                  np.asarray(lid)[:, streamed:])


def test_a_tiled_table_streams_every_block():
    """A table the kernel cuts into M-tiles keeps the whole stream: its view
    comes from the sorted route, whose tail holds out-of-bag rows, and
    route_and_hist drops row_blocks there - the leaf ids past the in-bag
    columns are routed as every other."""
    rs = np.random.RandomState(5)
    bins = rs.randint(0, BMAX, (N, G)).astype(np.uint8)
    mask = (rs.rand(N) < 0.3).astype(np.float32)
    w_T = np.zeros((8, N), np.float32)
    w_T[0] = rs.randint(-32, 33, N) * mask
    w_T[1] = rs.randint(0, 33, N) * mask
    w_T[2] = mask
    bins_T = pack_bins_T(jnp.asarray(bins), T, max_bins=BMAX,
                         tile_groups=32).bins_T
    bins_c, w_c, placed = compact_transposed_view(
        bins_T, jnp.asarray(w_T), 2, CAPACITY, T, tile_groups=32)
    rb = live_row_blocks(placed, T)
    assert int(rb[0]) * T < CAPACITY
    lid = jnp.asarray(rs.randint(0, 4, (1, CAPACITY)).astype(np.int32))
    kw = dict(num_slots=64, bmax=BMAX, num_groups=G, num_leaves=L,
              block_rows=T, has_cat=False, int_weights=True, tile_groups=32)
    operands = (bins_c, lid, w_c, _tables(3, 1),
                jnp.zeros((BMAX, L), jnp.bfloat16))
    whole = route_and_hist(*operands, **kw)
    live = route_and_hist(*operands, row_blocks=rb, **kw)
    for a, b in zip(whole, live):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(live[0]) != np.asarray(lid))[:, int(rb[0]) * T:].any()
