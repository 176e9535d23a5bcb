"""Gradient-based one-side sampling against its plain reference (ISSUE 37):
benchmark/reference_goss.py (float64 NumPy, written from the source's rule:
other_rate is a share of ALL rows) row for row against
`GOSSStrategy.sample_traced`; the fused sampled iteration through the stream
kernel, with row compaction and the route replay, against the repo's plain
configuration; and the benchmark loop's own check function on a sampled
tree, with the two controls that must fail - at small sizes on the CPU,
seeded."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.models.sample_strategy import GOSSStrategy

from conftest import make_synthetic_binary

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmark"


def _bench_module(relative):
    """A module of benchmark/, loaded as run.py loads it (benchmark/ on the
    path for its own imports)."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    path = BENCH / relative
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference_goss():
    return _bench_module("reference_goss.py")


@pytest.fixture(scope="module")
def loop():
    return _bench_module("loops/train_sampled.py")


# ---------------------------------------------------------------------------
# (a) the reference's sampler against the program's, row for row
# ---------------------------------------------------------------------------

def _gradients(n, ties, seed):
    """(g, h) float32 whose product is exact in float32 (h a power of two),
    so that the program's float32 magnitudes order as the reference's
    float64 ones do; `ties`: g on a grid of halves, which puts hundreds
    of rows AT the threshold."""
    rs = np.random.RandomState(seed)
    g = rs.randn(n).astype(np.float32)
    if ties:
        g = (np.round(g * 2) / 2).astype(np.float32)
    return g, np.full(n, 0.25, np.float32)


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("top_rate, other_rate", [(0.2, 0.1), (0.05, 0.3)])
def test_reference_draws_the_programs_sample(reference_goss, top_rate,
                                             other_rate, ties):
    n, iteration = 5000, 13
    cfg = Config.from_params({"data_sample_strategy": "goss",
                              "top_rate": top_rate,
                              "other_rate": other_rate, "bagging_seed": 7})
    strategy = GOSSStrategy(cfg, n)
    g, h = _gradients(n, ties, seed=3)
    mask, gs, hs = strategy.sample_traced(strategy.traced_key(iteration),
                                          jnp.asarray(g), jnp.asarray(h))
    u = reference_goss.program_uniform(7, iteration, n)
    want = reference_goss.sample(g, h, u, top_rate, other_rate)
    assert want["k"] == max(1, int(top_rate * n))
    assert (want["ties"] > 20) == ties
    np.testing.assert_array_equal(np.asarray(mask) > 0, want["weight"] > 0)
    np.testing.assert_allclose(np.asarray(gs), g * want["weight"], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(hs), h * want["weight"], rtol=1e-6)
    amp = (1.0 - top_rate) / other_rate
    assert set(np.unique(np.round(want["weight"], 6))) == {
        0.0, 1.0, round(amp, 6)}
    # the top set is everything at or over the k-th largest magnitude
    assert want["top"].sum() == want["k"] + want["ties"]


@pytest.mark.parametrize("top_rate, other_rate", [(0.2, 0.1), (0.05, 0.3),
                                                   (0.5, 0.5)])
def test_reference_keeps_other_rate_of_all_rows(reference_goss, top_rate,
                                                other_rate):
    """The source's rule (Ke et al. 2017, Algorithm 2; goss.hpp), held by
    what it means and not by the program: about (top_rate + other_rate) N
    rows are in bag, and the amplified kept rows stand for the whole rest
    (their weights sum to the rest's count).  A keep rate of other_rate
    over the REST - the repo's sampler before PR 37 - reads (1 - top_rate)
    of both and is told apart."""
    n = 200_000
    rs = np.random.RandomState(11)
    g, h = rs.randn(n), np.full(n, 0.25)
    got = reference_goss.sample(g, h, rs.rand(n).astype(np.float32),
                                top_rate, other_rate)
    rest = n - got["k"]
    kept = (got["weight"] > 0).sum() - got["k"]
    p = other_rate / (1.0 - top_rate)
    four_sigma = 4 * np.sqrt(rest * p * (1 - p)) + 1
    assert abs(kept - other_rate * n) <= four_sigma
    assert abs((got["weight"] > 0).mean() - (top_rate + other_rate)) < 0.005
    amp = (1.0 - top_rate) / other_rate
    assert abs(got["weight"][~got["top"]].sum() - rest) <= amp * four_sigma
    if top_rate < 0.5:
        assert abs(kept - other_rate * rest) > four_sigma
    lo, hi = reference_goss.count_bounds(n, n, top_rate, other_rate)
    assert lo <= got["k"] + kept <= hi


def test_draw_and_warm_up_are_the_programs(reference_goss):
    """The borrowed draw is a pure function of (seed, tree): float32 in
    [0, 1), the same numbers every time; the loop hands the reference the
    program's PADDED row count because k is int(top_rate x that count)."""
    a = reference_goss.program_uniform(3, 10, 4096)
    b = reference_goss.program_uniform(3, 10, 4096)
    assert a.dtype == np.float32 and np.array_equal(a, b)
    assert 0.0 <= a.min() and a.max() < 1.0
    assert not np.array_equal(a, reference_goss.program_uniform(3, 11, 4096))
    key = jax.random.PRNGKey(3 * reference_goss.SEED_STRIDE + 10)
    np.testing.assert_array_equal(a, jax.random.uniform(key, (4096,)))
    assert reference_goss.top_count(0.2, 31_400_192) \
        == reference_goss.top_count(0.2, 31_400_000) + 38
    assert reference_goss.first_sampled_tree(0.1) == 10
    assert reference_goss.first_sampled_tree(0.5) == 2
    assert reference_goss.first_sampled_tree(0.3) == 4


# ---------------------------------------------------------------------------
# (b) the fused sampled iteration against the plain configuration
# ---------------------------------------------------------------------------

_SAMPLED = {"objective": "binary", "num_leaves": 80, "learning_rate": 0.5,
            "max_bin": 63, "use_quantized_grad": True,
            "num_grad_quant_bins": 64, "stochastic_rounding": False,
            "data_sample_strategy": "goss", "top_rate": 0.2,
            "other_rate": 0.1, "min_data_in_leaf": 40, "verbosity": -1}
_FUSED_STREAM = {"hist_backend": "stream", "fused_iter": "on"}
_STRUCTURE = ("num_leaves", "split_feature", "threshold_bin", "left_child",
              "right_child", "leaf_count", "internal_count")


@pytest.fixture(scope="module")
def table():
    return make_synthetic_binary(n=6000, f=10)


def _grow(table, extra, rounds=4):
    X, y = table
    bst = lgb.Booster(dict(_SAMPLED, **extra), lgb.Dataset(X, label=y))
    for _ in range(rounds):
        bst.update()
    return bst


def _assert_same_structure(a, b, first=0):
    ta, tb = a.engine.models, b.engine.models
    assert len(ta) == len(tb) > first
    for i in range(first, len(ta)):
        for f in _STRUCTURE:
            np.testing.assert_array_equal(getattr(ta[i], f),
                                          getattr(tb[i], f),
                                          err_msg=f"tree {i} {f}")


@pytest.mark.parametrize("splits, other", [
    pytest.param(1, {"hist_backend": "segsum", "hist_precision": "double",
                     "row_compaction": "off"}, id="plain_one_split_a_round"),
    pytest.param(64, dict(_FUSED_STREAM, row_compaction="off",
                          route_fusion="off"), id="dense_masked_64_a_round"),
])
def test_fused_sampled_iteration_grows_the_references_trees(table, splits,
                                                            other):
    """The one-launch sampled iteration (sample_mode goss, stream kernel
    interpreted, the compact view under every histogram pass) grows, node
    for node and count for count, the trees of (1) the repo's plain
    configuration - segsum histograms in float64, one split a round, no
    compaction - at one split a round, where the compacted path routes the
    whole table a round; and (2) of the same kernel over the whole masked
    table at 64 splits a round, where the compacted path stashes the
    rounds' tables and `route_replay` routes every row once.  (At 64 a
    round the stream kernel grows other trees than at one by design,
    tests/test_hist_backends.py, so the plain configuration meets it at
    one; the replay's gate opens at 64.)  Nearest-level rounding: two
    programs' stochastic draws land on other rows (PERF.md section 7)."""
    sampled = _grow(table, dict(_FUSED_STREAM, max_splits_per_round=splits))
    eng = sampled.engine
    assert eng._fused_last and eng._last_sample_mode == "goss"
    assert 0 < eng._last_compact_rows < len(table[1])
    assert eng._route_replay_fused() == (splits == 64)
    assert eng._route_only_passes_per_tree() == (1 if splits == 64 else 80)
    plain = _grow(table, dict(other, max_splits_per_round=splits))
    assert plain.engine._last_compact_rows == 0
    first = 2                      # learning_rate 0.5: trees 0, 1 unsampled
    for t in eng.models[first:]:
        assert 0 < int(t.internal_count[0]) < 0.4 * len(table[1])
    _assert_same_structure(sampled, plain)


def test_flag_poll_and_sample_plan_records_carry_the_sampler(table):
    """What the benchmark's readers read: every `GBDT::FlagPoll` record of
    the fused path holds the sampler's mode, the newest in-bag count and
    the overflow count (words the poll fetched anyway), the static
    capacity and how the compacted tree routes the whole table; the host's
    choice of capacity is a `GBDT::SamplePlan` span.  Host statics and
    fetched words only: the states a dense and a sampled program hand each
    other gained no field (tests/test_sharded_ship.py pins them)."""
    import time
    from lightgbm_tpu import telemetry as tel
    t0 = time.time_ns()
    bst = _grow(table, dict(_FUSED_STREAM), rounds=1)
    eng = bst.engine
    eng._poll_device_flags()
    dense = tel.recent_spans(name="GBDT::FlagPoll", since_unix_ns=t0)[-1].args
    assert dense["sample_mode"] == "none" and dense["compact_rows"] == 0
    assert dense["route_only_passes"] == 0 and "route_replay" not in dense
    assert dense["sampled_rows"] == len(table[1])
    assert not tel.recent_spans(name="GBDT::SamplePlan", since_unix_ns=t0)
    for _ in range(2):
        bst.update()
    eng._poll_device_flags()
    poll = tel.recent_spans(name="GBDT::FlagPoll", since_unix_ns=t0)[-1].args
    assert poll["sample_mode"] == "goss" and poll["iteration"] == 3
    assert poll["compact_rows"] == eng._last_compact_rows > 0
    assert poll["compact_overflow"] == 0
    assert poll["route_replay"] == "fused" and poll["route_only_passes"] == 1
    # the compact view came from the streaming kernel, not sort + take
    assert poll["compact_kind"] == "stream" and "compact_kind" not in dense
    # the top_rate cut came from the select's count passes, not a sort
    assert poll["goss_threshold"] == "select"
    assert poll["threshold_passes"] == 8 and "goss_threshold" not in dense
    assert poll["sampled_rows"] == int(eng.models[2].internal_count[0])
    assert {"hist_passes", "scan_slots", "onehot_build"} <= set(poll)
    plans = tel.recent_spans(name="GBDT::SamplePlan", since_unix_ns=t0)
    assert len(plans) == 1                      # chosen once, then sticky
    n_pad = eng.dd.bins.shape[0]
    assert plans[0].args == {"rows": n_pad, "expected_fraction":
                             pytest.approx(0.30), "capacity":
                             poll["compact_rows"]}


def test_flag_poll_says_the_rows_a_compacted_pass_streamed(table):
    """A compacted tree's passes stop at the row blocks that hold its
    in-bag rows: its `GBDT::FlagPoll` record (and the iteration's log
    record) says how many rows a pass streamed, `pass_rows` - whole kernel
    blocks, no fewer than the in-bag rows and no more than the capacity.
    A dense tree's record has none."""
    import time
    from lightgbm_tpu import telemetry as tel
    t0 = time.time_ns()
    bst = _grow(table, dict(_FUSED_STREAM), rounds=1)
    eng = bst.engine
    eng._poll_device_flags()
    dense = tel.recent_spans(name="GBDT::FlagPoll", since_unix_ns=t0)[-1].args
    assert "pass_rows" not in dense
    for _ in range(2):
        bst.update()
    eng._poll_device_flags()
    poll = tel.recent_spans(name="GBDT::FlagPoll", since_unix_ns=t0)[-1].args
    T = eng._pack_block
    assert poll["compact_rows"] > 0 and poll["compact_rows"] % T == 0
    assert poll["pass_rows"] % T == 0
    assert poll["sampled_rows"] <= poll["pass_rows"] <= poll["compact_rows"]
    assert poll["pass_rows"] < poll["sampled_rows"] + T
    assert eng._pass_rows(poll["sampled_rows"]) == poll["pass_rows"]


# ---------------------------------------------------------------------------
# (c) the loop's own check on a sampled tree, and its controls
# ---------------------------------------------------------------------------

_REF = {"count_rtol": 1e-3, "gain_rtol": 0.05, "dense_count_slack_rows": 0,
        "walk_chunk_rows": 2048}


@pytest.fixture(scope="module")
def grown(table):
    own = {"num_leaves": 31, "stochastic_rounding": True}
    bst = _grow(table, own, rounds=4)
    return bst, dict(_SAMPLED, **own), bst.dump_model()


def _checks(loop, grown, table, params=None, **kw):
    bst, own, dump = grown
    said = []
    checks, faults = loop.sampled_checks(
        bst, params or own, table[0], table[1], dump, _REF, said.append,
        **kw)
    return checks, faults, said


def test_loop_check_holds_a_sampled_tree_to_the_reference(loop, grown,
                                                          table):
    checks, faults, said = _checks(loop, grown, table)
    assert checks == {k: True for k in (
        "unsampled_trees_full_and_counts_sum_to_n",
        "two_sampled_trees_were_grown",
        "sampled_root_count_is_the_references",
        "sampled_root_left_count_is_the_references",
        "sampled_root_gain_is_the_references",
        "next_sampled_root_count_is_the_references",
        "next_sampled_root_left_count_is_the_references",
        "next_sampled_root_gain_is_the_references",
        "sampled_trees_full_and_counts_in_bounds")}, said
    assert not faults and "tree 2:" in said[0] and "tree 3:" in said[1]


def test_loop_check_sees_a_tree_grown_from_misrouted_scores(loop, grown,
                                                            table):
    """What holding the NEXT sampled tree buys: the program grew tree 3
    from training scores that tree 2's routing of the whole table updated.
    A dump whose tree 2 carries other leaf values than the ones the program
    added - what a replay that sent rows to the wrong leaves amounts to -
    leaves tree 2's own checks standing and fails tree 3's."""
    import copy
    bst, own, dump = grown
    bad = copy.deepcopy(dump)

    def swap(node, leaves):
        if "leaf_value" in node:
            leaves.append(node)
        else:
            swap(node["left_child"], leaves)
            swap(node["right_child"], leaves)
        return leaves
    leaves = swap(bad["tree_info"][2]["tree_structure"], [])
    values = [leaf["leaf_value"] for leaf in leaves]
    for leaf, v in zip(leaves, values[::-1]):
        leaf["leaf_value"] = v
    said = []
    checks, _ = loop.sampled_checks(bst, own, table[0], table[1], bad, _REF,
                                    said.append)
    assert checks["sampled_root_count_is_the_references"], said
    assert checks["sampled_root_gain_is_the_references"]
    assert not (checks["next_sampled_root_count_is_the_references"]
                and checks["next_sampled_root_left_count_is_the_references"]
                and checks["next_sampled_root_gain_is_the_references"]), said


def test_loop_check_fails_without_the_amplification(loop, grown, table):
    """A reference that leaves the kept rows unamplified stands for a
    histogram that dropped the weights: the gain is far off, the counts
    (which no weight enters) are not."""
    checks, _, said = _checks(loop, grown, table, amplify=False)
    assert not checks["sampled_root_gain_is_the_references"], said
    assert checks["sampled_root_count_is_the_references"]
    assert checks["sampled_root_left_count_is_the_references"]


def test_loop_check_fails_at_half_the_other_rate(loop, grown, table):
    """A sampler that kept half as many of the small-gradient rows: the
    in-bag count misses by hundreds of rows, and every sampled tree's count
    falls out of the analytic bounds."""
    _, own, _ = grown
    checks, faults, said = _checks(
        loop, grown, table, params=dict(own, other_rate=own["other_rate"] / 2))
    assert not checks["sampled_root_count_is_the_references"], said
    assert not checks["sampled_trees_full_and_counts_in_bounds"]
    assert len(faults) == 2


def test_loop_check_fails_at_a_keep_rate_over_the_rest(loop, grown, table):
    """The repo's sampler before PR 37 kept other_rate of the REST, 8% of
    the rows at 0.2 / 0.1 where the source keeps 10%: a reference at that
    count (other_rate x (1 - top_rate)) misses the grown trees by 2% of the
    rows, so the check tells the two rules apart."""
    _, own, _ = grown
    rest_rate = own["other_rate"] * (1.0 - own["top_rate"])
    checks, faults, said = _checks(loop, grown, table,
                                   params=dict(own, other_rate=rest_rate))
    assert not checks["sampled_root_count_is_the_references"], said
    assert not checks["next_sampled_root_count_is_the_references"]
    # (the analytic bounds tell them apart at the cell's size - 628,000 rows
    # against six sigma of 11,000 - not at this one's 6,000 rows)


def test_loop_ends_at_once_on_a_sampler_of_another_rule(loop, grown):
    """`require_the_sources_rule`: a program whose sampler expects another
    in-bag share than top_rate + other_rate ends the run with exit code 1
    before a tree is grown (what the parent of PR 37 does on the cell)."""
    bst, own, _ = grown
    loop.require_the_sources_rule(bst, own)            # 0.3: passes

    class Older:
        class engine:
            class sample_strategy:
                @staticmethod
                def expected_fraction(iteration):
                    return 0.2 + 0.8 * 0.1
    with pytest.raises(SystemExit, match="0.28 of the rows in bag"):
        loop.require_the_sources_rule(Older, own)


def test_count_bounds_and_capacity(reference_goss):
    lo, hi = reference_goss.count_bounds(31_400_000, 31_400_192, 0.2, 0.1)
    k = int(0.2 * 31_400_192)
    mean = k + 0.125 * (31_400_000 - k)      # 0.1 / (1 - 0.2) of the rest
    assert abs(mean - 0.3 * 31_400_000) < 40      # k counts the 192 pad rows
    assert lo < mean < hi and hi - lo == pytest.approx(
        12 * np.sqrt((31_400_000 - k) * 0.125 * 0.875), rel=1e-9)
    # never over the capacity the program streamed, ties widen the top
    assert reference_goss.count_bounds(
        31_400_000, 31_400_192, 0.2, 0.1, capacity=8_000_000)[1] == 8_000_000
    assert reference_goss.count_bounds(
        31_400_000, 31_400_192, 0.2, 0.1, ties=5000)[1] == hi + 5000
