"""The program's `Dataset::Ship` spans of set-up: the binned matrix from the
host to the device, ended by the array being there."""
import program_spans

NAME = "dataset_ship_s"
UNIT = "s"
LAYER = "basic"
MOVES = "setup_s"
SPAN = "Dataset::Ship"


def read(run):
    took = program_spans.in_setup(run, SPAN)
    if not took:
        return None
    return sum(r.duration_ns for r in took) / 1e9
