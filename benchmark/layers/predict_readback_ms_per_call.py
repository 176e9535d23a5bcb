"""Mean over the window's `Predict` spans of the program's own time in
the scores read back from the device (`Predict::Readback`), which waits
out the kernel's walk."""
import program_spans

NAME = "predict_readback_ms_per_call"
UNIT = "ms"
LAYER = "basic"
MOVES = "score_rows_per_s"
PARENT = "Predict"
SPANS = ("Predict::Readback",)


def read(run):
    return program_spans.mean_child_ms(run, PARENT, SPANS)
