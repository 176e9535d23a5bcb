"""Reduced histogram payload a device materialises per tree in the passes'
collectives (parallel/comms.py `hist_comms_bytes_per_round` a pass that
reduces, a limb).  Host arithmetic, not a count of the device's: under a row
mesh every histogram pass reduces one block - a tree's first the root's one
slot, the others a round's budget - so over the window's `GBDT::FlagPoll`
records, each of which holds `iteration`, `hist_passes` and the static fields
`hist_reduce_limbs`, `hist_comm_bytes_root`, `hist_comm_bytes_round`,

    bytes = limbs x (trees x root + (passes - trees) x round)

with passes and trees (one a boosting iteration of a one-tree objective) the
differences `hist_passes_per_tree` takes.  The small-slot passes reduce the
round's whole block too (their histogram is padded to the budget before the
`psum`), so `hist_small_passes` does not enter.  A program whose records lack
the fields (a commit from before them) gives None; one chip reads 0."""
import program_spans
from layers import hist_passes_per_tree as passes

NAME = "hist_comm_bytes_per_tree"
UNIT = "bytes/tree"
LAYER = "parallel.comms"
MOVES = "train_s_per_tree"
FIELDS = ("hist_reduce_limbs", "hist_comm_bytes_root",
          "hist_comm_bytes_round")


def readings(records):
    return [(r.args["iteration"], r.args["hist_passes"])
            + tuple(r.args[f] for f in FIELDS) for r in records
            if r.args and "hist_passes" in r.args
            and all(f in r.args for f in FIELDS)]


def read(run):
    polls = program_spans.in_window(run, passes.POLL)
    if polls is None:
        return None
    got = readings(polls)
    if len(got) == 1:
        # as hist_passes_per_tree: count from the poll before the window,
        # or from the counter's zero at iteration 0
        before = readings(program_spans.in_setup(run, passes.POLL) or [])
        got = (before[-1:] or [(0, 0) + got[0][2:]]) + got
    if len(got) < 2 or got[-1][0] <= got[0][0]:
        return None
    trees = got[-1][0] - got[0][0]
    grown = got[-1][1] - got[0][1]
    limbs, root, a_round = got[-1][2:]
    return limbs * (trees * root + (grown - trees) * a_round) / trees
