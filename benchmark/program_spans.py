"""The program's own boundary spans and counters, read from inside.

lightgbm_tpu records a span at each layer boundary (`GBDT::FusedIter`,
`GBDT::FlagPoll`, `Dataset::Bin`, `Predict::Rebin`, ...) into a bounded
in-process ring, whatever its telemetry switch says:
`lightgbm_tpu.telemetry.recent_spans()` gives the records oldest first,
each `(seq, name, parent, start_unix_ns, duration_ns, args)` with
`start_unix_ns` from `time.time_ns()` - the domain of `run.window_start`.
The readers under layers/ whose source is `program_span` or
`program_counter` go through this file.

A program without the ring (a commit from before it) gives `None`
everywhere: the metric is then left out of the line, not raised over.

The arithmetic (`cut`, `calls`) works on plain records so that a
hand-made ring can check it (tests/test_program_spans.py).
"""
from collections import namedtuple

# the shape of the program's records, for hand-made rings
Record = namedtuple("Record", "seq name parent start_unix_ns duration_ns args")


def cut(records, overwritten, name, lo_ns=None, hi_ns=None):
    """The records called `name` that started in [lo_ns, hi_ns), oldest
    first - or None where the ring may have lost some of them.

    The ring keeps the newest records in the order the spans ENDED.  With
    `overwritten` > 0, what was lost ended before the oldest record that
    is left did: an interval that starts after that moment is whole, any
    other is not."""
    if overwritten:
        oldest = records[0] if records else None
        whole = (lo_ns is not None and oldest is not None
                 and oldest.start_unix_ns + oldest.duration_ns <= lo_ns)
        if not whole:
            return None
    return [r for r in records if r.name == name
            and (lo_ns is None or r.start_unix_ns >= lo_ns)
            and (hi_ns is None or r.start_unix_ns < hi_ns)]


def calls(records, parent):
    """[(parent record, {child name: summed duration_ns})] for every
    `parent` span in `records`.  A span's children end before it does, so
    they sit between the previous `parent` record and this one."""
    out, children = [], {}
    for r in records:
        if r.name == parent:
            out.append((r, children))
            children = {}
        elif r.parent == parent:
            children[r.name] = children.get(r.name, 0) + r.duration_ns
    return out


def ring():
    """(records, overwritten) of the program's ring, or None where the
    program has none."""
    try:
        from lightgbm_tpu import telemetry
        return (telemetry.recent_spans(),
                telemetry.global_tracer.ring_overwritten)
    except (ImportError, AttributeError):
        return None


def _window_ns(run):
    """The first measured instant, or None for a run that never had one."""
    start = getattr(run, "window_start", None)
    return None if start is None else int(start * 1e9)


def _cut(run, name, lo_ns, hi_ns, what):
    got = ring()
    if got is None:
        return None
    found = cut(got[0], got[1], name, lo_ns, hi_ns)
    if found is None:
        run.say(f"program_spans: THE RING OVERWROTE {got[1]} RECORDS, some "
                f"of them {what}: no {name} metric from this run")
    return found


def in_window(run, name):
    """The ring's `name` records from the first measured instant on, or a
    loud None if the ring overwrote records of the window."""
    lo = _window_ns(run)
    return None if lo is None else _cut(run, name, lo, None, "of the window")


def in_setup(run, name):
    """The ring's `name` records from before the first measured instant."""
    hi = _window_ns(run)
    return None if hi is None else _cut(run, name, None, hi, "of set-up")


def mean_child_ms(run, parent, names):
    """Mean over the window's `parent` spans of the time under the
    children called `names`, in ms; None without such a span.  Where the
    traffic mix states `batch_rows`, only calls of that many rows count
    (the checks after the window score a holdout of another size)."""
    if in_window(run, parent) is None:      # no ring, no window, or lost
        return None
    records, lo = ring()[0], _window_ns(run)
    rows = run.traffic.get("batch_rows") and run.mix("batch_rows")
    took = [sum(children.get(n, 0) for n in names)
            for rec, children in calls(
                [r for r in records if r.start_unix_ns >= lo], parent)
            if not rows or (rec.args or {}).get("rows") == rows]
    return sum(took) / len(took) / 1e6 if took else None
