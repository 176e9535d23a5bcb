"""The three readers of the four-chip cell on hand-made events with known
answers: `hist_collective_ms_per_tree` and `shard_busy_skew_pct` on device
lanes, `hist_comm_bytes_per_tree` on `GBDT::FlagPoll` records - and on what
the parent commit gives (one lane, records without the fields): nothing,
and no raise."""
import types

import pytest

from conftest import BENCH, load_module

import program_spans
import trace_reduction as tr
from program_spans import Record

MS = 1_000_000
S = 10**9
SPANS = [("bench.update", 0, 10 * MS), ("bench.drain", 10 * MS, 990 * MS)]
KERNEL = ('%route_and_hist.3 = (s32[1,26312704]{1,0}, s32[4288,128]{1,0}, '
          'f32[1,64]{1,0}) custom-call(%a), '
          'custom_call_target="tpu_custom_call"')

collective = load_module(BENCH / "layers" / "hist_collective_ms_per_tree.py")
skew = load_module(BENCH / "layers" / "shard_busy_skew_pct.py")
nbytes = load_module(BENCH / "layers" / "hist_comm_bytes_per_tree.py")


def lane(shift=0, stretch=0):
    """One device's tree: a kernel, the histogram all-reduce (both halves
    of an asynchronous one), the count all-reduce, a reduce-scatter and the
    record all-gather, and a fusion that is none of them."""
    return [
        (KERNEL, shift, 100 * MS + stretch),
        ("%all-reduce-start.1 = s32[64,67,63,2]{3,2,1,0} "
         "all-reduce-start(%h)", 200 * MS, 3 * MS),
        ("%all-reduce-done.1 = s32[64,67,63,2]{3,2,1,0} "
         "all-reduce-done(%s)", 210 * MS, 1 * MS),
        ("%all-reduce.7 = s32[64]{0} all-reduce(%c)", 220 * MS, 2 * MS),
        ("%reduce-scatter.2 = s32[64,17,63,2]{3,2,1,0} reduce-scatter(%h)",
         230 * MS, 5 * MS),
        ("%all-gather.4 = f32[4,4,128]{2,1,0} all-gather(%r)", 240 * MS,
         1 * MS),
        ("%fusion.9 = f32[26312704]{0} fusion(%x)", 300 * MS, 50 * MS),
        # outside the window: not counted
        ("%all-reduce.8 = s32[64]{0} all-reduce(%c)", 2000 * MS, 9 * MS),
    ]


def run_with(lanes, trees=2):
    reduced = tr.Reduced(lanes, SPANS) if lanes is not None else None
    return types.SimpleNamespace(reduced=reduced,
                                 spans={"traced_trees": trees})


def test_collective_time_is_the_lanes_mean_over_the_traced_trees():
    run = run_with({"/device:TPU:0": lane(), "/device:TPU:1": lane()})
    assert collective.read(run) == pytest.approx((3 + 1 + 2 + 5 + 1) / 2)
    # a lane whose collectives take twice as long: the mean of the lanes
    slow = [(n, s, 2 * d if "all-" in n or "reduce-scatter" in n else d)
            for n, s, d in lane()]
    run = run_with({"/device:TPU:0": lane(), "/device:TPU:1": slow})
    assert collective.read(run) == pytest.approx(1.5 * 12 / 2)
    assert (collective.NAME, collective.UNIT, collective.LAYER,
            collective.MOVES) == ("hist_collective_ms_per_tree", "ms/tree",
                                  "parallel.comms", "train_s_per_tree")


def test_one_chips_trace_has_no_collective_and_no_skew():
    ops = [e for e in lane() if "all-" not in e[0]
           and "reduce-scatter" not in e[0]]
    run = run_with({"/device:TPU:0": ops})
    assert collective.read(run) is None and skew.read(run) is None
    assert collective.read(run_with(None)) is None
    assert skew.read(run_with(None)) is None
    # names that only look like collectives are not
    for name in ("%fusion.3 = f32[8]{0} fusion(%all-reduce.1)",
                 "%all-reduce-scatter-fusion = f32[8]{0} fusion(%x)"):
        assert collective.read(run_with(
            {"/device:TPU:0": [(name, 0, 5 * MS)]})) is None


def test_skew_is_the_spread_of_the_lanes_busy_time_over_their_mean():
    even = {f"/device:TPU:{i}": lane() for i in range(4)}
    assert skew.read(run_with(even)) == pytest.approx(0.0)
    busy = 100 + 3 + 1 + 2 + 5 + 1 + 50                # ms a lane
    uneven = dict(even)
    uneven["/device:TPU:0"] = lane(stretch=8 * MS)     # 8 ms more on chip 0
    want = 100.0 * 8 / (busy + 8 / 4)
    assert skew.read(run_with(uneven)) == pytest.approx(want)
    assert (skew.NAME, skew.UNIT, skew.LAYER, skew.MOVES) == (
        "shard_busy_skew_pct", "%", "device", "train_s_per_tree")


A_ROUND, ROOT = 64 * 67 * 63 * 2 * 4, 67 * 63 * 2 * 4


def poll(seq, at_s, iteration, hist_passes, comm=None):
    """`comm`: (limbs, root bytes, round bytes), the static fields of a
    program that has them."""
    args = dict(iteration=iteration, hist_passes=hist_passes,
                hist_small_passes=2 * iteration, scan_slots=200 * iteration,
                root_pass="factored", hist_tiles=1, hist_m_rows=4288)
    if comm is not None:
        args.update(mesh_devices=4 if comm[2] else 1,
                    hist_reduce_limbs=comm[0], hist_comm_bytes_root=comm[1],
                    hist_comm_bytes_round=comm[2])
    return Record(seq, "GBDT::FlagPoll", "GBDT::Iteration", int(at_s * S),
                  S // 2, args)


def fake_run(window_start_s):
    return types.SimpleNamespace(window_start=float(window_start_s),
                                 traffic={}, say=lambda _: None,
                                 spans={"traced_trees": 5})


@pytest.mark.parametrize("limbs", [1, 2])
def test_comm_bytes_are_arithmetic_on_the_polls_pass_counts(monkeypatch,
                                                            limbs):
    comm = (limbs, ROOT, A_ROUND)
    tree = limbs * (ROOT + 8 * A_ROUND)        # a nine-pass tree
    ring = [poll(0, 10.0, 16, 144, comm), poll(1, 20.0, 32, 288, comm),
            poll(2, 30.0, 48, 440, comm)]      # 8 ten-pass trees at the end
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert nbytes.read(fake_run(5.0)) == pytest.approx(
        tree + limbs * A_ROUND / 4)
    assert nbytes.read(fake_run(15.0)) == pytest.approx(
        tree + limbs * A_ROUND / 2)
    # one poll in the window counts from the one before it
    monkeypatch.setattr(program_spans, "ring", lambda: (ring[:2], 0))
    assert nbytes.read(fake_run(15.0)) == pytest.approx(tree)
    # and from the counter's zero where there is none before it
    monkeypatch.setattr(program_spans, "ring", lambda: (ring[:1], 0))
    assert nbytes.read(fake_run(5.0)) == pytest.approx(tree)
    assert (nbytes.NAME, nbytes.UNIT, nbytes.LAYER, nbytes.MOVES) == (
        "hist_comm_bytes_per_tree", "bytes/tree", "parallel.comms",
        "train_s_per_tree")


def test_the_parents_records_read_nothing(monkeypatch):
    ring = [poll(0, 10.0, 16, 144), poll(1, 20.0, 32, 288)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert nbytes.read(fake_run(5.0)) is None
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert nbytes.read(fake_run(5.0)) is None
    # one chip: the fields are there and read 1, 0, 0
    ring = [poll(0, 10.0, 16, 144, (1, 0, 0)),
            poll(1, 20.0, 32, 288, (1, 0, 0))]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert nbytes.read(fake_run(5.0)) == 0
