"""Share of the traced window in which no leaf operation ran on the device
(mean over the chips used)."""
NAME = "device_idle_pct.score"
UNIT = "%"
LAYER = "device"
MOVES = "score_rows_per_s"


def read(run):
    return run.reduced.idle_pct if run.reduced else None
