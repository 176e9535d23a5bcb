"""Share of the trained rows that are in-bag in a sampled tree: the mean, over
the window's flag polls, of the `sampled_rows` each `GBDT::FlagPoll` record
holds (the in-bag count of the newest iteration the poll saw, a word the
poll fetches with its flags) over the rows trained on.  Only polls of a
sampled iteration count (`sample_mode` goss or bagging).  A program whose
records lack the fields (a commit from before them) gives None."""
import program_spans

NAME = "sampled_rows_pct"
UNIT = "%"
LAYER = "models.gbdt"
MOVES = "train_s_per_tree"
POLL = "GBDT::FlagPoll"


def trained_rows(run):
    return run.sized("rows") - run.sized("holdout")["rows"]


def sampled_polls(records):
    """The records of polls that saw a sampled iteration."""
    return [r.args for r in records
            if r.args and r.args.get("sample_mode", "none") != "none"]


def read(run):
    polls = program_spans.in_window(run, POLL)
    if polls is None:
        return None
    got = [a["sampled_rows"] for a in sampled_polls(polls)
           if "sampled_rows" in a]
    if not got:
        return None
    return 100.0 * sum(got) / len(got) / trained_rows(run)
