"""Host binning inside lgb.Dataset.construct(): the program's
`Dataset::FindBins` (bin mappers and feature groups from the row sample) plus
`Dataset::Bin` (the full fill) spans of set-up."""
import program_spans

NAME = "dataset_bin_s"
UNIT = "s"
LAYER = "basic"
MOVES = "setup_s"
SPANS = ("Dataset::FindBins", "Dataset::Bin")


def read(run):
    took = [program_spans.in_setup(run, s) for s in SPANS]
    if any(t is None for t in took) or not took[1]:
        return None
    return sum(r.duration_ns for t in took for r in t) / 1e9
