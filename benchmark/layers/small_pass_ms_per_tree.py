"""Device time of the small-slot histogram passes per traced tree, from the
device trace: the `route_and_hist` operations of a round that split one or
two leaves (pallas/stream_kernel.py `_route_small_hist`).  They are part of
`hist_kernel_ms_per_tree`, whose pattern matches them too; this reader
splits them out by the operations' result types, which no other pass of the
kernel has.  A trace without them (a commit from before the pass) gives
None."""
NAME = "small_pass_ms_per_tree"
UNIT = "ms/tree"
LAYER = "pallas.stream_kernel"
MOVES = "train_s_per_tree"
# a table of one M-tile: one fused call, (histogram block s32[R,128], new
# leaf ids s32[1,N], slot counts f32[1,S]) - the histogram FIRST, where
# every other pass's tuple opens with the leaf ids
FUSED = r"^%route_and_hist[.\d]* = \(s32\[\d+,128\]\S*, s32\[1,\d+\]"
# a tiled table: the factored call over grid (tile, row block), one
# 4-D block s32[tiles,1,R,128] (the 64-slot sweeps' is 3-D) ...
TILES = r"^%route_and_hist[.\d]* = s32\[\d+,1,\d+,128\]"
# ... and its route pre-pass, whose counts are 8 wide: (new leaf ids, slot
# counts f32[1,8], slots) - the 64-slot pre-pass counts 64
PREPASS = (r"^%route_and_hist[.\d]* = \(s32\[1,\d+\]\S*, f32\[1,8\]\S*, "
           r"s32\[1,\d+\]")


def read(run):
    trees = run.spans.get("traced_trees")
    if not run.reduced or not trees:
        return None
    took = sum(run.reduced.kernel_s(p) for p in (FUSED, TILES, PREPASS))
    return 1e3 * took / trees if took else None
