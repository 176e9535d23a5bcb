"""Harness clock round lgb.Dataset(...).construct() and lgb.Booster(...), ended
by block_until_ready on the device bins: host binning plus the ship."""
NAME = "dataset_construct_s"
UNIT = "s"
LAYER = "basic"
MOVES = "setup_s"


def read(run):
    return run.setup.get("dataset_construct_s")
