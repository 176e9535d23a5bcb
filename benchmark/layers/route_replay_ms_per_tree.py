"""Device time of the route replay per traced tree, from the device trace:
pallas/stream_kernel.py `route_replay`, the one launch a compacted tree
takes after growth to route EVERY row through the stored round tables.  The
trace names the custom call after the jitted function round it, so it reads
`%route_replay`, with one bare result `s32[1,N]` (the leaf ids) - no
`^%route_and_hist` pattern of the other readers matches it, and it is NOT
part of `hist_kernel_ms_per_tree`.  A trace without it (a dense tree, a tree
whose routing was not fused, a commit without the kernel) gives None."""
NAME = "route_replay_ms_per_tree"
UNIT = "ms/tree"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"
PATTERN = r"^%route_replay[.\d]* = s32\[1,\d+\]"


def read(run):
    trees = run.spans.get("traced_trees")
    took = run.reduced.kernel_s(PATTERN) if run.reduced and trees else 0
    return 1e3 * took / trees if took else None
