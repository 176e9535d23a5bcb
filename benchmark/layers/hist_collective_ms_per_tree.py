"""Device time of the cross-chip collectives per traced tree, from the device
trace: the summed duration, mean over the device lanes, of the all-reduce,
reduce-scatter and all-gather operations of the traced stretch (the `-start`
and `-done` halves of an asynchronous one included) - the histogram `psum`
or `psum_scatter`, the slot-count `psum`, the best-split record gather.  On
the one "XLA Ops" line of a device a collective's own duration is time in
which nothing else ran there, i.e. the part of it that is exposed.  A
one-chip trace has none and gives None."""
NAME = "hist_collective_ms_per_tree"
UNIT = "ms/tree"
LAYER = "parallel.comms"
MOVES = "train_s_per_tree"
# an operation is named by its whole HLO text: `%all-reduce.3 = ...`,
# `%all-reduce-start.1 = ...`, `%reduce-scatter.2 = ...`
PATTERN = r"^%(all-reduce|reduce-scatter|all-gather)(-start|-done)?[.\d]* = "


def read(run):
    trees = run.spans.get("traced_trees")
    took = run.reduced.kernel_s(PATTERN) if run.reduced and trees else 0
    return 1e3 * took / trees if took else None
