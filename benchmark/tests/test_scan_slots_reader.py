"""`scan_slots_per_tree` on `GBDT::FlagPoll` records as the program writes
them since the rounds' tails adapt to their split count (iteration,
hist_passes, hist_small_passes, scan_slots, root_pass, hist_tiles,
hist_m_rows), and on the parent's records, which lack the field."""
from types import SimpleNamespace

import pytest

from conftest import BENCH, load_module

import program_spans
from program_spans import Record

S = 10**9
reader = load_module(BENCH / "layers" / "scan_slots_per_tree.py")
passes = load_module(BENCH / "layers" / "hist_passes_per_tree.py")


def poll(seq, at_s, iteration, hist_passes, slots=None):
    args = dict(iteration=iteration, hist_passes=hist_passes,
                hist_small_passes=2 * iteration, root_pass="factored",
                hist_tiles=16, hist_m_rows=126000)
    if slots is not None:
        args["scan_slots"] = slots
    return Record(seq, "GBDT::FlagPoll", "GBDT::Iteration", int(at_s * S),
                  S // 2, args)


def fake_run(window_start_s):
    return SimpleNamespace(window_start=float(window_start_s), traffic={},
                           say=lambda _: None, spans={"traced_trees": 5})


def test_difference_of_polls_over_the_iterations_between(monkeypatch):
    # 8 + 8 + 8 + 8 + 16 + 32 + 64 + 64 = 208 a tree up to tree 32; a
    # straggler round of one more chunk in half of the next 16
    ring = [poll(0, 10.0, 16, 144, 3328), poll(1, 20.0, 32, 288, 6656),
            poll(2, 30.0, 48, 440, 10048)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    run = fake_run(5.0)
    assert reader.read(run) == pytest.approx((10048 - 3328) / 32)
    assert passes.read(run) == pytest.approx((440 - 144) / 32)
    assert reader.read(fake_run(15.0)) == pytest.approx(212.0)
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
        "scan_slots_per_tree", "slots/tree", "ops.grow", "train_s_per_tree")


def test_one_poll_in_the_window_counts_from_the_one_before(monkeypatch):
    ring = [poll(0, 10.0, 16, 144, 3328), poll(1, 20.0, 32, 288, 6656)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert reader.read(fake_run(15.0)) == pytest.approx(208.0)
    monkeypatch.setattr(program_spans, "ring", lambda: (ring[:1], 0))
    assert reader.read(fake_run(5.0)) == pytest.approx(208.0)
    assert reader.read(fake_run(11.0)) is None


def test_the_parents_records_read_nothing(monkeypatch):
    """A commit from before the count: `hist_passes_per_tree` reads, this
    metric is left out of the line, and nothing is raised."""
    ring = [poll(0, 10.0, 16, 144), poll(1, 20.0, 32, 288)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert passes.read(fake_run(5.0)) == pytest.approx(9.0)
    assert reader.read(fake_run(5.0)) is None
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert reader.read(fake_run(5.0)) is None


def test_listed_for_the_three_training_cells(manifest):
    entry = [m for m in manifest["per_layer"] if m["name"] == reader.NAME]
    assert len(entry) == 1 and entry[0] == dict(
        name="scan_slots_per_tree", unit=reader.UNIT, better="lower",
        source="program_counter", layer=reader.LAYER, moves=reader.MOVES,
        workloads=["higgs_train", "mslr_train", "epsilon_train"])
