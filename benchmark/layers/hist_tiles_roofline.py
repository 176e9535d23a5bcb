"""The tiled histogram passes' share of the int8 roofline, on a table whose
one-hot does not fit VMEM whole (pallas/stream_kernel.py
`_hist_tiles_kernel`).  Such a 64-slot pass is two operations: a route-only
pre-pass that routes the rows and writes their slots, then one call whose
grid sweeps the rows once an M-tile.  Work: the table's OWN one-hot rows —
`hist_m_rows` of the program's `GBDT::FlagPoll` records, the groups that pad
the last tile not counted — x 128 weight columns x N rows a pass, 2
operations a MAC, over the chip's int8 peak.  Time: the sweeps' calls AND
their pre-passes, so that routing is on the time side as it is inside the
one-tile kernel that `hist_kernel_roofline` reads.  The root pass and the
route-only last round are on neither side, as there.  Bound by int8 compute.

A trace without such calls (a table of one tile) or a program whose records
state no `hist_m_rows` (a commit from before the kernel tiled) gives None."""
import re

import program_spans

NAME = "hist_tiles_roofline"
UNIT = "%"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"
POLL = "GBDT::FlagPoll"
# the sweeps: one int32 (tiles, one tile's one-hot rows, C) histogram
SWEEPS = re.compile(r"^%route_and_hist[.\d]* = s32\[\d+,\d+,(\d+)\]")
# their pre-pass: (new leaf ids s32[1,N], slot counts f32[1,S], slots
# s32[1,N]); a tree's last round has no third result and no sweeps
PREPASS = re.compile(r"^%route_and_hist[.\d]* = \(s32\[1,(\d+)\]\S*, "
                     r"f32\[1,(\d+)\]\S*, s32\[1,\d+\]")
FULL_COLUMNS = 128


def macs(m_rows, columns, n_rows):
    return m_rows * columns * n_rows


def read(run):
    if not run.reduced or not run.reduced.lanes:
        return None
    records = (program_spans.in_window(run, POLL) or []) \
        + (program_spans.in_setup(run, POLL) or [])
    m_rows = [r.args["hist_m_rows"] for r in records
              if r.args and "hist_m_rows" in r.args]
    if not m_rows:
        return None
    ops = next(iter(run.reduced.lanes.values()))
    sweeps = took = n_rows = 0
    for name, start, dur in ops:
        if not run.reduced.lo <= start < run.reduced.hi:
            continue
        m = SWEEPS.match(name)
        if m and int(m[1]) == FULL_COLUMNS:
            sweeps += 1
            took += dur
            continue
        m = PREPASS.match(name)
        if m and 2 * int(m[2]) == FULL_COLUMNS:
            n_rows = int(m[1])
            took += dur
    if not sweeps or not n_rows:
        return None
    work = 2 * macs(m_rows[-1], FULL_COLUMNS, n_rows) * sweeps
    return 100.0 * (work / run.peak()["int8_ops_per_s"]) / (took / 1e9)
