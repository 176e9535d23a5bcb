"""Mean over the window's `Predict` spans of the program's own time in
the binned batch packed and shipped to the device (`Predict::PackShip`)."""
import program_spans

NAME = "predict_ship_ms_per_call"
UNIT = "ms"
LAYER = "basic"
MOVES = "score_rows_per_s"
PARENT = "Predict"
SPANS = ("Predict::PackShip",)


def read(run):
    return program_spans.mean_child_ms(run, PARENT, SPANS)
