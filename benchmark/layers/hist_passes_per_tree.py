"""Histogram-building passes over the rows per tree, counted on the device
(the fused iteration's state) and published by the program at each flag poll
as telemetry.hist_pass_count(): the count at the window's last poll minus at
its first, over the iterations between.  Each `GBDT::FlagPoll` record holds
the count and the iteration it was read at."""
import program_spans

NAME = "hist_passes_per_tree"
UNIT = "passes/tree"
LAYER = "ops.grow"
MOVES = "train_s_per_tree"
POLL = "GBDT::FlagPoll"


def readings(records):
    return [(r.args["iteration"], r.args["hist_passes"]) for r in records
            if r.args and "hist_passes" in r.args]


def read(run):
    polls = program_spans.in_window(run, POLL)
    if polls is None:
        return None
    got = readings(polls)
    if len(got) == 1:
        # a window too short for two polls (a rehearsal): count from the
        # poll before it, or from the counter's zero at iteration 0
        before = readings(program_spans.in_setup(run, POLL) or [])
        got = (before[-1:] or [(0, 0)]) + got
    if len(got) < 2 or got[-1][0] <= got[0][0]:
        return None
    return (got[-1][1] - got[0][1]) / (got[-1][0] - got[0][0])
