"""Device busy time outside Pallas custom calls per traced tree: split scans,
gradients, score update, routing glue — everything XLA compiled itself."""
NAME = "xla_other_ms_per_tree"
UNIT = "ms/tree"
LAYER = "ops.grow"
MOVES = "train_s_per_tree"
# every Pallas kernel is a Mosaic custom call, whatever it is named
PALLAS = r"custom_call_target=\"tpu_custom_call\""


def read(run):
    trees = run.spans.get("traced_trees")
    if not run.reduced or not trees:
        return None
    return 1e3 * (run.reduced.busy_s - run.reduced.kernel_s(PALLAS)) / trees
