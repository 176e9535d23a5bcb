"""The same spread over ms a histogram pass (an interval's time over its
`hist_passes` delta).  Low where `poll_tree_ms_spread_pct` is high: the
window's noise is ninth passes, i.e. work; high: the device or the
machine."""
import poll_timeline

NAME = "poll_pass_ms_spread_pct"
UNIT = "%"
LAYER = "ops.grow"
MOVES = "train_s_per_tree"


def read(run):
    ivs = poll_timeline.window_intervals(run)
    if ivs is None:
        return None
    run.say(f"{NAME}: " + poll_timeline.pass_series(ivs))
    return poll_timeline.spread_pct(poll_timeline.ms_per_pass(ivs))
