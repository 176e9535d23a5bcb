"""How unevenly the chips of a cell are busy: (largest - smallest) / mean of
the device lanes' busy time inside the traced window, in percent.  Rows are
sharded evenly, so a skew says one chip does work the others do not (a table
shipped through it, an array placed on it again every launch) or waits less
for the others in the collectives.  One lane gives None."""
from trace_reduction import busy_ns

NAME = "shard_busy_skew_pct"
UNIT = "%"
LAYER = "device"
MOVES = "train_s_per_tree"


def read(run):
    if not run.reduced or len(run.reduced.lanes) < 2:
        return None
    busy = [busy_ns(ops, run.reduced.lo, run.reduced.hi)
            for ops in run.reduced.lanes.values()]
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean else None
