"""Sweeps of the rows the histogram kernel makes per tree: a table whose
one-hot does not fit VMEM whole is contracted one M-tile a sweep
(pallas/stream_kernel.py `_hist_tiles_kernel`), so a histogram pass costs
`hist_tiles` sweeps.  `hist_passes_per_tree`'s reading times the
`hist_tiles` the same `GBDT::FlagPoll` records state (static per compiled
program).  A program whose records carry no `hist_tiles` (a commit from
before the kernel tiled) gives None."""
import program_spans
from layers import hist_passes_per_tree as passes

NAME = "hist_tile_sweeps_per_tree"
UNIT = "sweeps/tree"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"


def read(run):
    per_tree = passes.read(run)
    tiles = [r.args["hist_tiles"]
             for r in program_spans.in_window(run, passes.POLL) or []
             if r.args and "hist_tiles" in r.args]
    if per_tree is None or not tiles:
        return None
    return tiles[-1] * per_tree
