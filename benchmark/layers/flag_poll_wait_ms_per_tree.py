"""How long the host stood at the program's flag poll (`GBDT::FlagPoll`, the
one blocking read of the training loop) for the device's backlog, summed
over the window and divided by its `GBDT::Iteration` count."""
import program_spans

NAME = "flag_poll_wait_ms_per_tree"
UNIT = "ms/tree"
LAYER = "models.gbdt"
MOVES = "train_s_per_tree"
POLL = "GBDT::FlagPoll"
STEP = "GBDT::Iteration"


def read(run):
    polls = program_spans.in_window(run, POLL)
    steps = program_spans.in_window(run, STEP)
    if polls is None or not steps:
        return None
    return sum(r.duration_ns for r in polls) / len(steps) / 1e6
