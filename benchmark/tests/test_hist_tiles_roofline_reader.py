"""`hist_tiles_roofline` on a hand-made event list with a known answer: the
operations are written as the fused iteration of the wide cell compiles them
for the v5e (tests/test_tpu_aot_compile.py pins the same result types from
the program's side), the `GBDT::FlagPoll` records as the program writes them
for 2,000 groups of 64 bins in sixteen tiles of 128."""
import types

import pytest

from conftest import BENCH, load_module

import program_spans
import trace_reduction as tr
from program_spans import Record

MS = 1_000_000
N = 1100800
LEAF = f"s32[1,{N}]{{1,0:T(1,128)}}"
TAIL = ' custom-call(%a, %b), custom_call_target="tpu_custom_call"'
ROOT = f"%route_and_hist.7 = s32[32768,128]{{1,0:T(8,128)}}{TAIL}"
PREPASS = (f"%route_and_hist.3 = ({LEAF}, f32[1,64]{{1,0:T(1,128)S(1)}}, "
           f"{LEAF}){TAIL}")
SWEEPS = f"%route_and_hist.4 = s32[16,8192,128]{{2,1,0:T(8,128)}}{TAIL}"
ROUTE_ONLY = (f"%route_and_hist.9 = ({LEAF}, "
              f"f32[1,128]{{1,0:T(1,128)S(1)}}){TAIL}")
ONE_TILE = (f"%route_and_hist.2 = ({LEAF}, s32[8704,128]{{1,0:T(8,128)S(1)}}, "
            f"f32[1,64]{{1,0:T(1,128)S(1)}}){TAIL}")
OTHER = f"%leaf_gather.1 = f32[1,{N}]{{1,0:T(1,128)}}{TAIL}"
SPANS = [("bench.update", 0, 10 * MS), ("bench.drain", 10 * MS, 2000 * MS)]

reader = load_module(BENCH / "layers" / "hist_tiles_roofline.py")
one_tile = load_module(BENCH / "layers" / "hist_kernel_roofline.py")
root = load_module(BENCH / "layers" / "root_pass_ms_per_tree.py")
whole = load_module(BENCH / "layers" / "hist_kernel_ms_per_tree.py")


def poll(seq, at_s, **args):
    return Record(seq, "GBDT::FlagPoll", "GBDT::Iteration", int(at_s * 1e9),
                  10**8, dict(iteration=16 * (seq + 1), hist_passes=128,
                              **args))


TILED = dict(root_pass="factored", hist_tiles=16, hist_m_rows=128000)


def _run(ops, trees=2):
    return types.SimpleNamespace(
        reduced=tr.Reduced({"/device:TPU:0": ops}, SPANS),
        spans={"traced_trees": trees}, setup={}, window_start=5.0,
        traffic={}, say=lambda _: None,
        peak=lambda: {"int8_ops_per_s": 393e12})


def _two_trees(passes=7):
    ops, at = [], MS
    for _ in range(2):
        tree = [(ROOT, 26)] + [(PREPASS, 3), (SWEEPS, 107)] * passes \
            + [(ROUTE_ONLY, 3), (OTHER, 1)]
        for name, ms in tree:
            ops.append((name, at, ms * MS))
            at += (ms + 1) * MS
    return ops


def test_real_rows_over_sweeps_and_their_pre_passes(monkeypatch):
    ring = [poll(0, 3.0, **TILED), poll(1, 10.0, **TILED)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    run = _run(_two_trees())
    # 128,000 one-hot rows (not the tiles' 131,072), 128 columns, N rows a
    # pass; the pre-pass's 3 ms is on the time side
    want = 100 * (2 * 128000 * 128 * N / 393e12) / 0.110
    assert reader.read(run) == pytest.approx(want)
    assert 80 < reader.read(run) < 90
    # the accepted readers: a sweep is no root and no one-tile pass, and the
    # whole goes on summing every route_and_hist operation
    assert root.read(run) == pytest.approx(26)
    assert one_tile.read(run) is None
    assert whole.read(run) == pytest.approx(26 + 7 * 110 + 3)
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
        "hist_tiles_roofline", "%", "pallas.stream_kernel",
        "train_s_per_tree")


def test_the_setup_polls_state_the_rows_too(monkeypatch):
    monkeypatch.setattr(program_spans, "ring",
                        lambda: ([poll(0, 3.0, **TILED)], 0))
    assert reader.read(_run(_two_trees())) is not None


def test_nothing_to_read_is_none_and_never_raises(monkeypatch):
    # the parent's records (no hist_m_rows), whatever the trace holds
    old = dict(root_pass="factored")
    monkeypatch.setattr(program_spans, "ring",
                        lambda: ([poll(0, 10.0, **old)], 0))
    assert reader.read(_run(_two_trees())) is None
    # a table of one tile: records state the rows, the trace has no sweeps
    monkeypatch.setattr(
        program_spans, "ring",
        lambda: ([poll(0, 10.0, root_pass="factored", hist_tiles=1,
                       hist_m_rows=8704)], 0))
    ops = [(ONE_TILE, MS, 70 * MS), (ROUTE_ONLY, 80 * MS, 7 * MS)]
    assert reader.read(_run(ops)) is None
    assert one_tile.read(_run(ops)) is not None
    # no ring, no trace
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert reader.read(_run(_two_trees())) is None
    assert reader.read(types.SimpleNamespace(reduced=None)) is None


def test_sweeps_outside_the_window_are_not_counted(monkeypatch):
    monkeypatch.setattr(program_spans, "ring",
                        lambda: ([poll(0, 10.0, **TILED)], 0))
    late = [(PREPASS, 2100 * MS, 30 * MS), (SWEEPS, 2200 * MS, 500 * MS)]
    assert reader.read(_run(_two_trees() + late)) \
        == pytest.approx(reader.read(_run(_two_trees())))


def test_listed_for_the_wide_cell_alone_and_appended(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-2:] == ["hist_tile_sweeps_per_tree", reader.NAME]
    entry = manifest["per_layer"][-1]
    assert entry["workloads"] == ["epsilon_train"]
    assert (entry["unit"], entry["source"], entry["moves"]) == (
        "%", "device_trace", "train_s_per_tree")
    # the one-tile share stays with the cells whose kernel it reads
    one = next(m for m in manifest["per_layer"]
               if m["name"] == one_tile.NAME)
    assert one["workloads"] == ["higgs_train", "mslr_train"]
