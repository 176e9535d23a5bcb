"""Loop kind `train_sampled`: loops/train.py's window and result exactly (its
`run` is called, unedited; the pattern of loops/train_checked.py) for a job
whose trees are grown on a SAMPLE of the rows (`data_sample_strategy=goss`),
with a `check_model` of its own.

What differs from `train`, and why.  A sampled tree's leaf counts count its
in-bag rows, as upstream's do, so harness.check_model's
`trees_full_and_counts_sum_to_n` holds for none of them.  Every other check
of harness.check_model is kept as it is (its own call makes them); that one
is replaced by what the configuration's plain reference, reference_goss.py,
can say of a sampled tree - all after the window, outside every timed
quantity:

  * the unsampled trees of the sampler's warm-up (t < 1 / learning_rate) keep
    the dense invariant: full, leaf counts summing to the rows trained on;
  * the FIRST sampled tree t: the raw scores before it (the NumPy walk of
    the dumped trees 0..t-1 over the training table, on the host: nothing
    the program computed after the dump) give the reference its gradients;
    the program's uniform draw is reproduced on the host (the reference's
    one borrowed piece); then the dumped root's in-bag count, its left
    count and its gain are held to the reference's
    (`sampled_root_count_is_the_references`, `..._left_count_...`,
    `..._gain_...`; tolerances and their reasons in the configuration's
    `reference_goss` block);
  * the NEXT sampled tree t + 1, likewise, from the walk of trees 0..t
    (`next_sampled_root_...`): the program grew it from training scores
    that tree t's routing of the WHOLE table had updated - `route_replay`
    where the tree was compacted - so a replay that sent rows to the wrong
    leaves moves this tree's top set, its left count and its gain;
  * EVERY sampled tree: full, leaf counts summing to its root's count
    exactly, that count inside the sampler's analytic bounds and under the
    compaction capacity the program's flag polls report
    (`sampled_trees_full_and_counts_in_bounds`).

`no_program_compiled_in_window` and `no_retrace_after_warmup` are
loops/train.py's: a compaction fall-back that recompiles inside the window
fails the run.  The raw table outlives `lgb.Dataset` for the check (host
memory, not the device's).  A program whose sampler keeps another share of
the rows than the source's top_rate + other_rate cannot run the cell: the
loop exits 1 as soon as the Booster stands, before a tree is grown
(`require_the_sources_rule`).

traffic parameters: loops/train.py's; `warmup_trees` covers the sampler's
warm-up and the sampled program's compile, and may have a `rehearse`
override.  configuration: `reference_goss` {"count_rtol", "gain_rtol",
"dense_count_slack_rows", "walk_chunk_rows"}, `leaf_count_slack_rows`."""
import importlib.util
from pathlib import Path

import numpy as np

import program_spans
import reference_goss
import reference_walk

_spec = importlib.util.spec_from_file_location(
    "bench_train", Path(__file__).with_name("train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)

NO_TREE = 1 << 30          # harness.check_model's dense invariant: no tree


def require_the_sources_rule(bst, params):
    """Before any tree is grown: the deployment's `other_rate` is a share of
    ALL rows (reference_goss.py), so a program whose sampler expects another
    in-bag share than top_rate + other_rate runs another deployment.  Such a
    run would end `correct: false` four minutes later on the first sampled
    tree's count; it ends here instead, with exit code 1 and no result."""
    first = reference_goss.first_sampled_tree(params["learning_rate"])
    want = params["top_rate"] + params["other_rate"]
    got = bst.engine.sample_strategy.expected_fraction(first)
    if abs(got - want) > 1e-9:
        raise SystemExit(
            f"train_sampled: this program's sampler expects {got:.4g} of the "
            f"rows in bag where data_sample_strategy=goss at top_rate "
            f"{params['top_rate']}, other_rate {params['other_rate']} keeps "
            f"{want:.4g} (other_rate is a share of all rows: Ke et al. 2017, "
            f"Algorithm 2; goss.hpp): it cannot run this cell")


def sampled_checks(bst, params, X, y, dump, ref, say, capacity=0,
                   amplify=True, count_slack=0):
    """The checks this loop kind adds, on a grown model and the table it was
    trained on.  -> (checks dict, trees at fault)"""
    n = len(y)
    leaves = params["num_leaves"]
    top, other = params["top_rate"], params["other_rate"]
    seed = params.get("bagging_seed", reference_goss.DEFAULT_BAGGING_SEED)
    first = reference_goss.first_sampled_tree(params["learning_rate"])
    drawn = bst.engine.sample_strategy.num_data     # the padded row count
    warm = {"tree_info": dump["tree_info"][:first]}
    dense_faults, worst = reference_walk.tree_faults(
        warm, n, leaves, count_slack=ref["dense_count_slack_rows"])
    checks = {"unsampled_trees_full_and_counts_sum_to_n": not dense_faults,
              "two_sampled_trees_were_grown":
                  len(dump["tree_info"]) > first + 1}
    ties = 0
    if checks["two_sampled_trees_were_grown"]:
        # the first sampled tree, and the one after it: grown from scores
        # the first one's full-table routing (the replay) had updated
        score = reference_goss.scores(dump, X, 0, first,
                                      ref["walk_chunk_rows"])
        for name, tree in (("sampled", first), ("next_sampled", first + 1)):
            got = reference_goss.check(
                dump, tree, X, y, score,
                reference_goss.program_uniform(seed, tree, drawn), top, other,
                drawn, ref["count_rtol"], ref["gain_rtol"], amplify=amplify,
                name=name)
            say("reference_goss (scores from the NumPy walk of the dump): "
                + got.pop("said"))
            ties = max(ties, got.pop("ties"))
            checks.update(got)
            if tree == first:
                score += reference_goss.scores(dump, X, first, first + 1,
                                               ref["walk_chunk_rows"])
    lo, hi = reference_goss.count_bounds(n, drawn, top, other, ties=ties,
                                         capacity=capacity)
    faults = reference_goss.sampled_tree_faults(dump, first, leaves, lo, hi,
                                                count_slack=count_slack)
    checks["sampled_trees_full_and_counts_in_bounds"] = not faults
    say(f"sampled trees {first}..{len(dump['tree_info']) - 1}: "
        f"{len(faults)} at fault (tree, leaves, count sum - root count, "
        f"root count: {faults[:6]}); bounds [{lo:.0f}, {hi:.0f}], capacity "
        f"{capacity}; unsampled trees: {len(dense_faults)} at fault, "
        f"largest |count sum - N| {worst}")
    return checks, dense_faults + faults


def polled_capacity(run):
    """The compaction capacity the window's flag polls report (0: dense, or
    a program whose polls do not say)."""
    polls = program_spans.in_window(run, "GBDT::FlagPoll") or []
    caps = [r.args["compact_rows"] for r in polls
            if r.args and "compact_rows" in r.args]
    return min(caps) if caps else 0


def run(run):
    kept = {}
    make, check_model, traffic = run.make, train.check_model, run.traffic
    build = train.build_booster

    def keeping(rows, stream=0):
        kept["data"] = make(rows, stream=stream)
        return kept["data"]

    def building(run_):
        built = build(run_)
        require_the_sources_rule(built[0], built[1])
        return built

    def checking(run_, bst, params, n_train, holdout, first_tree=0):
        checks, dump, _ = check_model(run_, bst, params, n_train, holdout,
                                      first_tree=NO_TREE)
        del checks["trees_full_and_counts_sum_to_n"]
        data = kept.pop("data")
        got, faults = sampled_checks(
            bst, params, data["X"][:n_train], data["y"][:n_train], dump,
            run_.sized("reference_goss"), run_.say,
            capacity=polled_capacity(run_),
            count_slack=run_.sized("leaf_count_slack_rows"))
        checks.update(got)
        return checks, dump, faults

    run.make, train.check_model = keeping, checking
    train.build_booster = building
    run.traffic = dict(traffic, warmup_trees=run.mix("warmup_trees"))
    try:
        return train.run(run)
    finally:
        run.make, train.check_model, run.traffic = make, check_model, traffic
        train.build_booster = build
