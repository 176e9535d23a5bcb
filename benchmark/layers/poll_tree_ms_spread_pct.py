"""How unevenly the window's 16-tree intervals ran: (largest - smallest) /
median of ms a tree over the intervals from one `GBDT::FlagPoll`'s end to
the next one's (poll_timeline.py).  Two to five intervals a 30 s window: the
number tells a run from a run, the series in the log says which interval."""
import poll_timeline

NAME = "poll_tree_ms_spread_pct"
UNIT = "%"
LAYER = "models.gbdt"
MOVES = "train_s_per_tree"


def read(run):
    ivs = poll_timeline.window_intervals(run)
    if ivs is None:
        return None
    run.say(f"{NAME}: " + poll_timeline.tree_series(ivs))
    return poll_timeline.spread_pct(poll_timeline.ms_per_tree(ivs))
