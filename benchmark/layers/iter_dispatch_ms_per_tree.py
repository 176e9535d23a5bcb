"""Mean duration of the program's own `GBDT::FusedIter` span (round the one
launch of a boosting iteration) over the traced trees: the inside twin of
host_dispatch_ms_per_tree, read on the same update() calls (the window's
first `traced_trees` iterations, those under `bench.update`), without
Booster.update()'s own bookkeeping.

Later in the window the same span is mostly waiting: once about seven
launches are in flight the runtime blocks the next dispatch until the device
retires one, so its mean over the whole window follows the device, not the
host.  That mean goes to the log, not into the metric."""
import program_spans

NAME = "iter_dispatch_ms_per_tree"
UNIT = "ms/tree"
LAYER = "models.gbdt"
MOVES = "train_s_per_tree"
SPAN = "GBDT::FusedIter"


def read(run):
    took = program_spans.in_window(run, SPAN)
    traced = run.spans.get("traced_trees")
    if not took or not traced:
        return None
    ms = [r.duration_ns / 1e6 for r in took]
    run.say(f"{SPAN}: mean {sum(ms) / len(ms):.3f} ms over the window's "
            f"{len(ms)} iterations, {sum(ms[traced:]) / max(len(ms) - traced, 1):.3f} "
            f"ms after the traced {traced} (a dispatch blocks while the "
            "device's queue is full)")
    return sum(ms[:traced]) / traced
