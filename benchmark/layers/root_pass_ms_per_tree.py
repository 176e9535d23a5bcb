"""Device time of the ROOT histogram pass per traced tree, from the device
trace: the one `route_and_hist` operation a tree that has a single slot
(every row in leaf 0, nothing routed).  It is part of
`hist_kernel_ms_per_tree`, whose pattern matches it too; this reader splits
it out by the operation's result type, which the trace carries in its name
(an operation's whole HLO text)."""
NAME = "root_pass_ms_per_tree"
UNIT = "ms/tree"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"
# the factored root (pallas/stream_kernel.py `_root_hist_kernel`): one int32
# block of (groups x 2 x high digits x 16, 128), no leaf-id result
FACTORED = r"^%route_and_hist[.\d]* = s32\[\d+,128\]"
# the one-hot root (the 64-slot kernel called with one slot): new leaf ids,
# a histogram of 2 columns, one slot count
ONEHOT = r"^%route_and_hist[.\d]* = \(s32\[1,\d+\]\S*, [sf]32\[\d+,2\]"


def read(run):
    trees = run.spans.get("traced_trees")
    if not run.reduced or not trees:
        return None
    took = run.reduced.kernel_s(FACTORED) + run.reduced.kernel_s(ONEHOT)
    return 1e3 * took / trees if took else None
