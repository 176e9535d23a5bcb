"""Device time of the histogram kernel (pallas/stream_kernel.py
`_route_hist_kernel`) per traced tree, from the device trace."""
NAME = "hist_kernel_ms_per_tree"
UNIT = "ms/tree"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"
# the pallas_calls carry no name= today: the trace names the custom call after
# the jitted function round it (stream_kernel.route_and_hist), and an
# operation by its whole HLO text
PATTERN = r"^%route_and_hist[.\d]* = "


def read(run):
    trees = run.spans.get("traced_trees")
    took = run.reduced.kernel_s(PATTERN) if run.reduced and trees else 0
    return 1e3 * took / trees if took else None
