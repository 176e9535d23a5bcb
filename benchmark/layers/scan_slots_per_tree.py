"""Pairs (a split leaf and its new sibling) per tree that the histogram
rounds' subtraction, cache update and child split scan ran over (ops/grow.py
`tail_chunk`: whole chunks of 8 pairs, as many as hold the round's own
split count; the round's budget, 64, on a program that does not adapt),
counted on the device beside `hist_passes` and published on the same
`GBDT::FlagPoll` records as `scan_slots`: `hist_passes_per_tree`'s arithmetic
on that field.  A program whose records lack it (a commit from before the
count) gives None."""
import program_spans
from layers import hist_passes_per_tree as passes

NAME = "scan_slots_per_tree"
UNIT = "slots/tree"
LAYER = "ops.grow"
MOVES = "train_s_per_tree"
FIELD = "scan_slots"


def readings(records):
    return [(r.args["iteration"], r.args[FIELD]) for r in records
            if r.args and FIELD in r.args]


def read(run):
    polls = program_spans.in_window(run, passes.POLL)
    if polls is None:
        return None
    got = readings(polls)
    if len(got) == 1:
        # as hist_passes_per_tree: count from the poll before the window,
        # or from the counter's zero at iteration 0
        before = readings(program_spans.in_setup(run, passes.POLL) or [])
        got = (before[-1:] or [(0, 0)]) + got
    if len(got) < 2 or got[-1][0] <= got[0][0]:
        return None
    return (got[-1][1] - got[0][1]) / (got[-1][0] - got[0][0])
