"""Device time per traced tree of what the sampler and the row partition add
OUTSIDE the Pallas calls (models/sample_strategy.py `sample_traced`,
ops/compact.py `compact_transposed_view`): the device-wide sorts (the
threshold's and the partition's) and every XLA operation that makes an array
of the compact view's length (the two gathers along the row axis, their
transposes, the permutation's slices) - by the patterns below, from the
device trace.  The compact length is the window's `compact_rows`
(`GBDT::FlagPoll`); where the records lack it, or it is 0, only the sorts
count.  A dense tree's trace matches none (it sorts nothing of a million
rows): None."""
import program_spans
from layers import sampled_rows_pct as sampled

NAME = "sample_compact_ms_per_tree"
UNIT = "ms/tree"
LAYER = "ops.compact"
MOVES = "train_s_per_tree"
# every Pallas kernel is a Mosaic custom call: never this metric's
NOT_PALLAS = r"^(?!.*custom_call_target=\"tpu_custom_call\")"
# a sort whose first result holds a million elements or more
SORT = r"%sort[.\d]* = \(?\w+\[\d{7,}\]"
# an operation whose result has the compact view's length as a dimension
COMPACT = r"%[\w.\-]+ = \(?[^ ]*\[(?:\d+,)*{rows}(?:,\d+)*\]"


def compact_rows(run):
    polls = program_spans.in_window(run, sampled.POLL) or []
    caps = [a["compact_rows"] for a in sampled.sampled_polls(polls)
            if a.get("compact_rows")]
    return caps[-1] if caps else 0


def pattern(rows):
    """One expression for the trace reduction: the sorts, and with a
    compact length the operations that make an array of it."""
    kinds = [SORT] + ([COMPACT.format(rows=rows)] if rows else [])
    return NOT_PALLAS + "(?:" + "|".join(kinds) + ")"


def read(run):
    trees = run.spans.get("traced_trees")
    if not run.reduced or not trees:
        return None
    took = run.reduced.kernel_s(pattern(compact_rows(run)))
    return 1e3 * took / trees if took else None
