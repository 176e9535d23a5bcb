"""The idle a poll causes, bounded from the host: the summed gap from a
`GBDT::FlagPoll`'s end (the device is drained) to the end of the first
`GBDT::FusedIter` that starts after it (the next launch is enqueued), over
the iterations of the window - both counted after the profiled stretch.
An upper bound: the device starts inside that dispatch, not at its end."""
import poll_timeline
import program_spans

NAME = "poll_gap_ms_per_tree"
UNIT = "ms/tree"
LAYER = "models.gbdt"
MOVES = "train_s_per_tree"


def read(run):
    polls = poll_timeline.window_polls(run)
    launches = program_spans.in_window(run, poll_timeline.LAUNCH)
    if polls is None or not launches:
        return None
    run.say(f"{NAME}: from a poll's end to the next dispatch's end (ms): "
            + "; ".join(f"after {it}: {ns / 1e6:.3f}"
                        for it, ns in poll_timeline.gaps(polls, launches)))
    after = poll_timeline.traced_end_ns(run) or 0
    counted = poll_timeline.gaps(
        [p for p in polls if poll_timeline.end_ns(p) >= after], launches)
    trees = [r for r in launches if r.start_unix_ns >= after]
    if not counted or not trees:
        return None
    return sum(ns for _, ns in counted) / len(trees) / 1e6
