"""Share of the traced window in which no leaf operation ran on the device
(mean over the chips used)."""
NAME = "device_idle_pct.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_s_per_tree"


def read(run):
    return run.reduced.idle_pct if run.reduced else None
