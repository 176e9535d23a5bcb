"""The window's flag polls read as its timeline, and the two other records
the program writes where the host stands still (`Runtime::Compile`, the HBM
fields): what the readers of set-up, memory and the 16-tree intervals share.

The training loop's one blocking read is `GBDT::FlagPoll`, every
`eval_fetch_freq` (16) iterations.  It returns when the device has finished
everything dispatched, and the device is never idle between two polls, so
the END of one poll to the END of the next is the device's time for the
trees between - exactly, on the host's clock.  Each record holds
`iteration` and `hist_passes` (cumulative), so an interval also knows its
trees and its passes.  After a poll the device is drained and waits for the
next dispatch: from the poll's end to the end of the first `GBDT::FusedIter`
that starts after it is a host-side upper bound of that idle.

A traced run profiles the window's first `traced_trees` iterations and then
drains and stops the profiler (seconds): an interval that began before that
moment holds it and is left out, and so are the gaps of polls inside the
profiled stretch (the profiler slows the host).

Readers give None - the metric is left out, nothing raises - where the
records lack the HBM fields (`hbm_in_use_bytes`, `hbm_peak_bytes`: a program
from before them, or a platform without allocator statistics, i.e. a CPU
rehearsal), where the ring lost records, and where the window holds fewer
than three polls (the set-up readers too: such a window is a rehearsal).

The arithmetic works on plain records so that a hand-made ring can check
it (tests/test_poll_timeline.py).
"""
import statistics
from collections import namedtuple

import program_spans

POLL = "GBDT::FlagPoll"
LAUNCH = "GBDT::FusedIter"
STEP = "GBDT::Iteration"
COMPILE = "Runtime::Compile"
HBM_IN_USE = "hbm_in_use_bytes"
HBM_PEAK = "hbm_peak_bytes"
ITER_ENTRY = "fused_iter"
MIN_POLLS = 3

# from the poll before to the poll at `iteration`
Interval = namedtuple("Interval", "iteration trees passes ns")


def end_ns(record):
    return record.start_unix_ns + record.duration_ns


def with_hbm(records):
    """The records that carry both HBM fields."""
    return [r for r in records
            if r.args and HBM_IN_USE in r.args and HBM_PEAK in r.args]


# ---------------------------------------------------------------- arithmetic
def intervals(polls, not_before_ns=None):
    """[Interval] of consecutive `polls` (oldest first), without those
    that began (their first poll ended) before `not_before_ns`."""
    out = []
    for a, b in zip(polls, polls[1:]):
        if not_before_ns is not None and end_ns(a) < not_before_ns:
            continue
        trees = b.args["iteration"] - a.args["iteration"]
        if trees > 0:
            out.append(Interval(b.args["iteration"], trees,
                                b.args["hist_passes"] - a.args["hist_passes"],
                                end_ns(b) - end_ns(a)))
    return out


def gaps(polls, launches):
    """[(iteration, ns)]: from each poll's end to the end of the first of
    `launches` that starts after it; a poll nothing follows has none."""
    out = []
    launches = sorted(launches, key=lambda r: r.start_unix_ns)
    for p in polls:
        nxt = next((f for f in launches if f.start_unix_ns >= end_ns(p)),
                   None)
        if nxt is not None:
            out.append((p.args["iteration"], end_ns(nxt) - end_ns(p)))
    return out


def spread_pct(values):
    """(largest - smallest) / median, in percent; None under two values."""
    if len(values) < 2 or statistics.median(values) <= 0:
        return None
    return 100.0 * (max(values) - min(values)) / statistics.median(values)


def ms_per_tree(ivs):
    return [iv.ns / iv.trees / 1e6 for iv in ivs]


def ms_per_pass(ivs):
    return [iv.ns / iv.passes / 1e6 for iv in ivs if iv.passes > 0]


def tree_series(ivs):
    """The intervals as one line of log: which interval was the slow one."""
    return "; ".join(f"to {iv.iteration}: {iv.trees} trees, "
                     f"{iv.ns / iv.trees / 1e6:.3f} ms/tree" for iv in ivs)


def pass_series(ivs):
    """... and whether it was slow a pass or had more passes."""
    return "; ".join(
        f"to {iv.iteration}: {iv.passes} passes, "
        + (f"{iv.ns / iv.passes / 1e6:.3f} ms/pass" if iv.passes > 0
           else "none to divide by") for iv in ivs)


# ------------------------------------------------------------- from the run
def traced_end_ns(run):
    """When the profiled stretch's last iteration returned (its drain and
    the profiler's stop follow), or None for a run that profiled none."""
    traced = getattr(run, "spans", {}).get("traced_trees")
    steps = program_spans.in_window(run, STEP) if traced else None
    if not steps or len(steps) < traced:
        return None
    return end_ns(steps[traced - 1])


def window_polls(run):
    """The window's polls that hold what the timeline reads, or None."""
    polls = program_spans.in_window(run, POLL)
    if polls is None:
        return None
    polls = [r for r in with_hbm(polls)
             if "iteration" in r.args and "hist_passes" in r.args]
    return polls if len(polls) >= MIN_POLLS else None


def window_intervals(run):
    """The window's whole poll-to-poll intervals, or None under two."""
    polls = window_polls(run)
    if polls is None:
        return None
    ivs = intervals(polls, traced_end_ns(run))
    return ivs if len(ivs) >= 2 else None


def setup_compiles(run):
    """Set-up's `Runtime::Compile` records, oldest first, or None - also
    for a window under three polls: that is a rehearsal, not a measured
    run, and none of the readers here gives it a number."""
    if window_polls(run) is None:
        return None
    found = program_spans.in_setup(run, COMPILE)
    return with_hbm(found) or None if found else None


def missed(compiles):
    """Those the run paid a backend compile for (`cache: miss`)."""
    return [r for r in compiles if r.args.get("cache") == "miss"]


def of_iteration(compiles):
    """Those a `fused_iter` entry asked for: the fused iteration's."""
    return [r for r in compiles if r.args.get("entry") == ITER_ENTRY]
