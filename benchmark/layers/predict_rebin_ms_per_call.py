"""Mean over the window's `Predict` spans of the program's own time in
the float64 re-bin of the batch with the training mappers
(`Predict::Rebin`)."""
import program_spans

NAME = "predict_rebin_ms_per_call"
UNIT = "ms"
LAYER = "basic"
MOVES = "score_rows_per_s"
PARENT = "Predict"
SPANS = ("Predict::Rebin",)


def read(run):
    return program_spans.mean_child_ms(run, PARENT, SPANS)
