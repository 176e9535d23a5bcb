"""Harness clock round each bst.update() return (the enqueue, and whatever the
program waits for inside it), mean over the traced trees."""
NAME = "host_dispatch_ms_per_tree"
UNIT = "ms/tree"
LAYER = "models.gbdt"
MOVES = "train_s_per_tree"


def read(run):
    took = run.spans.get("update_return_s")
    return 1e3 * sum(took) / len(took) if took else None
