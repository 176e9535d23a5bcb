"""Mean over the window's `Predict` spans of the program's own time in
the routing tables pulled back from the device plus the node tables rebuilt
from the trees (`Predict::RoutingTables`, `Predict::NodeTables`)."""
import program_spans

NAME = "predict_tables_ms_per_call"
UNIT = "ms"
LAYER = "basic"
MOVES = "score_rows_per_s"
PARENT = "Predict"
SPANS = ("Predict::RoutingTables", "Predict::NodeTables")


def read(run):
    return program_spans.mean_child_ms(run, PARENT, SPANS)
