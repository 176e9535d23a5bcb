"""The histogram passes' share of the int8 roofline: the MACs the one-hot
formulation does — M one-hot rows x C weight columns x N rows a pass, 2
operations a MAC, every shape read off the kernel's own HLO text in the trace
— over the chip's int8 peak, divided by those passes' device time.  Only the
passes that contract against a full 128-column operand count (the 64-slot
rounds); the root pass (2 columns) and the route-only last round do little
of this work and are left out of both sides.  Bound by int8 compute."""
import re

NAME = "hist_kernel_roofline"
UNIT = "%"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"
# result tuple of a histogram pass: (new leaf ids s32[1,N], histogram
# s32[M,C], slot counts)
PASS = re.compile(r"^%route_and_hist[.\d]* = \(s32\[1,(\d+)\]\S*, "
                  r"s32\[(\d+),(\d+)\]")
FULL_COLUMNS = 128


def macs(m_rows, columns, n_rows):
    return m_rows * columns * n_rows


def read(run):
    if not run.reduced or not run.reduced.lanes:
        return None
    ops = next(iter(run.reduced.lanes.values()))
    work = took = 0
    for name, start, dur in ops:
        m = PASS.match(name)
        if m and int(m[3]) == FULL_COLUMNS \
                and run.reduced.lo <= start < run.reduced.hi:
            work += 2 * macs(int(m[2]), int(m[3]), int(m[1]))
            took += dur
    if not took:
        return None
    return 100.0 * (work / run.peak()["int8_ops_per_s"]) / (took / 1e9)
