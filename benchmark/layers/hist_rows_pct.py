"""Share of the table's rows that a histogram pass of a sampled tree streams:
the compaction capacity (`compact_rows` of the window's `GBDT::FlagPoll`
records, a host static of the compiled program) over the table's padded rows
(`rows` of the newest `GBDT::SamplePlan` span, recorded where the host chose
the capacity; the rows trained on where the ring no longer holds one).  100
where a sampled iteration ran without compaction (`compact_rows` 0: off, not
worth it, or the overflow fall-back).  Mean over the window's sampled polls;
None from a program whose records lack the fields."""
import program_spans
from layers import sampled_rows_pct as sampled

NAME = "hist_rows_pct"
UNIT = "%"
LAYER = "models.gbdt"
MOVES = "train_s_per_tree"
PLAN = "GBDT::SamplePlan"


def table_rows(run):
    plans = [r for r in (program_spans.in_setup(run, PLAN) or [])
             + (program_spans.in_window(run, PLAN) or [])
             if r.args and r.args.get("rows")]
    return plans[-1].args["rows"] if plans else sampled.trained_rows(run)


def share(compact_rows, rows):
    return 100.0 * compact_rows / rows if compact_rows else 100.0


def read(run):
    polls = program_spans.in_window(run, sampled.POLL)
    if polls is None:
        return None
    caps = [a["compact_rows"] for a in sampled.sampled_polls(polls)
            if "compact_rows" in a]
    if not caps:
        return None
    rows = table_rows(run)
    return sum(share(c, rows) for c in caps) / len(caps)
