"""The route replay's share of the published peak its own work binds to.

The replay streams the packed table once and writes a leaf id a row:

    bytes = table rows x N x bytes an element + 4 N        (819 GB/s)

and a round of it gathers every row's split record out of the per-leaf
tables with a one-hot contraction, (table rows, L) x (L, T) a block:

    MACs = rounds x 24 x L x N, 2 operations a MAC          (197 TFLOP/s bf16)

Every shape is read off the kernel's own HLO text in the trace (the table
`s8[G,N]` or `s32[W,N]`, the round tables `f32[R x 24, L]`); 24 is the
number of rows of a round's table (pallas/stream_kernel.py NUM_TAB).  The
rounds are a LOWER bound of the replay's trip count, which the trace does
not carry: the window's `hist_passes_per_tree` less the root's pass (every
round that built a histogram was replayed; the route-only last round is left
out though the replay runs it too: on `higgs_goss_train` 7.25 rounds are
counted where about 8.25 run, so the share reads about an eighth low), so
the share cannot be counted too high.  The least time the chip could take
is the larger of the two; at 255 leaves and 7.25 rounds the contraction
binds (14.2 ms against 1.4 ms of bytes at 31.4M rows x 28).
Bound by bf16 compute, then; where a shallow tree makes the bytes bind, by
HBM bandwidth."""
import re

from layers import hist_passes_per_tree as passes

NAME = "route_replay_roofline"
UNIT = "%"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"
CALL = re.compile(r"^%route_replay[.\d]* = s32\[1,(\d+)\]")
TABLE = re.compile(r"custom-call\(.*?\b(s8|s32)\[(\d+),(\d+)\]")
ROUND_TABLES = re.compile(r"\bf32\[(\d+),(\d+)\]")
TABLE_ROWS = 24


def table_bytes(kind, rows, n_rows):
    return rows * n_rows * (1 if kind == "s8" else 4) + 4 * n_rows


def macs(rounds, leaves, n_rows):
    return rounds * TABLE_ROWS * leaves * n_rows


def least_seconds(name, rounds, peak):
    """The least time one replay call could take, or None where the call's
    text does not carry its operands."""
    call, table, tabs = (CALL.match(name), TABLE.search(name),
                         ROUND_TABLES.search(name))
    if not (call and table and tabs):
        return None
    n_rows = int(call[1])
    by_bytes = table_bytes(table[1], int(table[2]), n_rows) \
        / peak["hbm_bytes_per_s"]
    by_ops = 2 * macs(rounds, int(tabs[2]), n_rows) \
        / peak["bf16_flops_per_s"]
    return max(by_bytes, by_ops)


def read(run):
    if not run.reduced or not run.reduced.lanes:
        return None
    per_tree = passes.read(run)
    rounds = max(per_tree - 1.0, 0.0) if per_tree else 0.0
    least = took = 0.0
    for name, start, dur in next(iter(run.reduced.lanes.values())):
        if CALL.match(name) and run.reduced.lo <= start < run.reduced.hi:
            floor = least_seconds(name, rounds, run.peak())
            if floor is None:
                return None
            least += floor
            took += dur / 1e9
    return 100.0 * least / took if took else None
