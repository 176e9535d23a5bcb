"""Loop kind `train_checked`: loops/train.py's window and result exactly (its
`run` is called, unedited), and after `check_model` the configuration's plain
reference, reference_split.py: tree 0's root split recomputed in float64 NumPy
over the WHOLE training table on the host.  A histogram that lost a shard of
a row-sharded table counts fewer rows to the left and reads a lower gain;
`check_model`'s own checks read the model alone and cannot see it.

The raw table outlives `lgb.Dataset` for this (the generator's arrays are
held until the check has read one column of them): host memory, not the
device's.

traffic parameters: loops/train.py's.  configuration: `reference_split`
{"gain_rtol"}."""
import importlib.util
from pathlib import Path

import reference_split

_spec = importlib.util.spec_from_file_location(
    "bench_train", Path(__file__).with_name("train.py"))
train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(train)


def run(run):
    kept = {}
    make, check_model = run.make, train.check_model

    def keeping(rows, stream=0):
        kept["data"] = make(rows, stream=stream)
        return kept["data"]

    def checking(run_, bst, params, n_train, holdout, first_tree=0):
        checks, dump, faults = check_model(run_, bst, params, n_train,
                                           holdout, first_tree=first_tree)
        data = kept.pop("data")
        got = reference_split.check(
            dump, data["X"][:n_train], data["y"][:n_train],
            gain_rtol=run_.sized("reference_split")["gain_rtol"])
        run_.say("reference split (tree 0 root): " + got.pop("said"))
        run_.say("per-device peak_bytes_in_use: " + str(
            [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in run_.devices]))
        checks.update(got)
        return checks, dump, faults

    run.make, train.check_model = keeping, checking
    try:
        return train.run(run)
    finally:
        run.make, train.check_model = make, check_model
