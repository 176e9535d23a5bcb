"""The part of a histogram round that follows the kernel call is sized by
the round's own split count (ops/grow.py `tail_chunk`; CPU interpret mode).

A round of k splits subtracts, updates the cache and scans its children's
splits a chunk of 8 pairs a step, ceil(k / 8) steps; pairs are independent
of one another, so the tree must be the one every round grows with its
whole budget of 64 pairs at once.  The trees here grow through the chip's program (the
fused iteration) with a depth limit that nothing reaches: it takes the
route-only last round away, so a tree of 64 + k leaves ends on a histogram
round of exactly k splits after six full ones (1 .. 32).
"""
import functools

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry as tel
from lightgbm_tpu.ops import grow

BUDGET = 64
# the last histogram round's split count -> (leaves, rows): six full rounds
# reach 64 leaves, the seventh splits what the leaf budget leaves; 64 more
# make 128 (12,000 rows keep all 64 leaves splittable) and a round of two
# follows.  k = 0: sixty rows stop splitting (min_data_in_leaf) before the
# budget does, and the round that finds no candidate still runs
CASES = {0: (67, 60), 1: (67, 3000), 2: (67, 3000), 3: (67, 3000),
         8: (67, 3000), 9: (73, 3000), 33: (97, 3000), 64: (130, 12000)}


def _table(shape, n):
    rs = np.random.RandomState(11)
    if shape == "tiled":          # 600 groups: five M-tiles of 128
        X = rs.randn(n, 600).astype(np.float32)
    elif shape == "bucketed":     # mixed cardinalities: a bucketed M-axis
        X = np.concatenate(
            [rs.randn(n, 10), rs.randint(0, 12, (n, 8)),
             rs.randint(0, 5, (n, 6))], axis=1).astype(np.float32)
    else:
        X = rs.randn(n, 6).astype(np.float32)
    w = rs.randn(X.shape[1])
    y = ((X - X.mean(0)) @ w / np.sqrt((w ** 2).sum())
         + 0.3 * rs.randn(n) > 0)
    return X, y.astype(np.float64)


@functools.lru_cache(maxsize=None)
def _grown(shape, leaves, n, adapt):
    """One tree through the fused iteration: (model text, every row's leaf,
    the poll's counts, the engine's static shape).  adapt=False: every round
    at the budget's width, uncut: tail_chunk() patched in the test's
    process."""
    X, y = _table(shape, n)
    params = dict(objective="binary", num_leaves=leaves, max_depth=40,
                  max_bin=63, min_data_in_leaf=2, verbosity=-1,
                  hist_backend="stream", max_splits_per_round=BUDGET,
                  use_quantized_grad=True, num_grad_quant_bins=64,
                  stochastic_rounding=False, eval_fetch_freq=1)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("LGBTPU_FUSE_ITER", "1")
        if not adapt:
            mp.setattr(grow, "tail_chunk", lambda budget: 0)
        tel.reset_counters()
        bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
        bst.update()
        eng = bst.engine
        assert eng._fused_last and eng._root_pass == "factored"
        poll = tel.recent_spans(name="GBDT::FlagPoll")[-1].args
        counts = {k: poll[k] for k in ("hist_passes", "hist_small_passes",
                                       "scan_slots")}
        assert counts["scan_slots"] == tel.scan_slot_count()
        tiles = eng._stream_tiling.num_tiles
        return (bst.model_to_string().split("\nparameters:")[0],
                np.asarray(eng._train_state.leaf_id)[:n], counts,
                (tiles, eng._grow_params.bin_buckets),
                bst.dump_model()["tree_info"][0]["num_leaves"])


def _slots(splits):
    chunk = grow.tail_chunk(BUDGET)
    return sum(-(-k // chunk) * chunk for k in splits)


@pytest.mark.parametrize("shape, k", [
    *[("one_tile", k) for k in sorted(CASES)],
    ("tiled", 3), ("bucketed", 9)])
def test_the_tree_is_the_one_every_round_grows_at_width_64(shape, k):
    leaves, n = CASES[k]
    text, leaf, counts, static, grew = _grown(shape, leaves, n, True)
    text64, leaf64, counts64, static64, grew64 = _grown(shape, leaves, n,
                                                        False)
    assert static == static64 == {
        "one_tile": (1, None), "tiled": (5, None),
        "bucketed": (1, static[1])}[shape]
    assert (static[1] is not None) == (shape == "bucketed")
    assert text == text64 and grew == grew64
    np.testing.assert_array_equal(leaf, leaf64)
    assert counts["hist_passes"] == counts64["hist_passes"]
    assert counts["hist_small_passes"] == counts64["hist_small_passes"] >= 1
    rounds = counts["hist_passes"] - 1
    assert counts64["scan_slots"] == BUDGET * rounds
    if k == 0:
        # the rows run out first: the last round splits nothing, in no step
        assert grew < leaves and counts["scan_slots"] <= 8 * (rounds - 1)
        return
    # the leaf budget is met, so the rounds split 1, 2, .. 32 and then k
    # (after 64, where the budget allows them; one or two stragglers after)
    assert grew == leaves
    splits = [1, 2, 4, 8, 16, 32] + ([64] if k == 64 else []) + [
        leaves - (128 if k == 64 else 64)]
    assert rounds == len(splits) and sum(splits) == leaves - 1
    assert k in splits or (k == 64 and 64 in splits)
    assert counts["scan_slots"] == _slots(splits)


def test_scan_slots_counts_the_chunks_taken():
    """8 + 8 + 8 + 8 + 16 + 32 for the six full rounds, then the last
    round's splits in whole chunks of 8; 64 a round where nothing adapts."""
    assert [grow.tail_chunk(b) for b in (BUDGET, 40, 16)] == [8, 8, 8]
    # not cut: under two chunks, or not whole chunks
    assert [grow.tail_chunk(b) for b in (1, 2, 8, 14, 39)] == [0] * 5
    assert [_slots([k]) for k in (0, 1, 2, 3, 8, 9, 32, 33, 64)] == [
        0, 8, 8, 8, 8, 16, 32, 40, 64]
    for k, last in ((3, 8), (9, 16), (33, 40)):
        leaves, n = CASES[k]
        assert _grown("one_tile", leaves, n, True)[2]["scan_slots"] == 80 + last
        assert _grown("one_tile", leaves, n, False)[2]["scan_slots"] == 7 * 64


@pytest.mark.parametrize("program", [
    "plain", "budget_of_one", "float", "packed_words", "categorical",
    "multiclass"])
def test_programs_that_trace_no_dispatch(program, monkeypatch):
    """A budget of one, float gradients, the packed-word layout (max_bin >
    127), a categorical column and the K-lockstep multiclass grower keep the
    whole budget a step: no chunk is asked for, or none is given.  The
    plain int8 program asks for one of 64 and gets 8."""
    monkeypatch.setenv("LGBTPU_FUSE_ITER", "1")
    asked = []
    real = grow.tail_chunk
    monkeypatch.setattr(
        grow, "tail_chunk",
        lambda budget: asked.append((budget, real(budget))) or asked[-1][1])
    X, y = _table("one_tile", 1500)
    params = dict(objective="binary", num_leaves=70, max_bin=63,
                  verbosity=-1, hist_backend="stream",
                  max_splits_per_round=BUDGET, use_quantized_grad=True,
                  num_grad_quant_bins=64, stochastic_rounding=False)
    data = {}
    if program == "budget_of_one":
        params.update(max_splits_per_round=1, num_leaves=5)
    elif program == "float":
        params.update(use_quantized_grad=False)
    elif program == "packed_words":
        params.update(max_bin=255)
    elif program == "categorical":
        X[:, 5] = np.random.RandomState(2).randint(0, 9, len(X))
        data = dict(categorical_feature=[5])
    elif program == "multiclass":
        y = np.random.RandomState(3).randint(0, 3, len(X)).astype(np.float64)
        params.update(objective="multiclass", num_class=3)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params, **data))
    bst.update()
    assert bst.engine._fused_last
    if program == "plain":
        assert (64, 8) in asked
        return
    assert not any(chunk for _, chunk in asked), asked
    if program in ("float", "packed_words"):
        assert bst.engine._root_pass == "onehot" and not asked
    if program == "multiclass":
        assert bst.engine._mc_batched_last and not asked
