"""`small_pass_ms_per_tree` on hand-made event lists with known answers:
the operations of a round that splits one or two leaves, written as the
three cells' programs compile them for the v5e (tests/test_tpu_aot_compile.py
pins the same result types from the program's side), beside the accepted
readers on the SAME lists: both rooflines and `root_pass_ms_per_tree` read
what they read before, and only `hist_kernel_ms_per_tree` sums the small
passes too."""
import types

import pytest

from conftest import BENCH, load_module

import program_spans
import trace_reduction as tr
from program_spans import Record

MS = 1_000_000
TAIL = ' custom-call(%a, %b), custom_call_target="tpu_custom_call"'
SPANS = [("bench.update", 0, 10 * MS), ("bench.drain", 10 * MS, 2000 * MS)]

reader = load_module(BENCH / "layers" / "small_pass_ms_per_tree.py")
whole = load_module(BENCH / "layers" / "hist_kernel_ms_per_tree.py")
root = load_module(BENCH / "layers" / "root_pass_ms_per_tree.py")
one_tile = load_module(BENCH / "layers" / "hist_kernel_roofline.py")
tiles = load_module(BENCH / "layers" / "hist_tiles_roofline.py")


def _leaf(n):
    return f"s32[1,{n}]{{1,0:T(1,128)}}"


def _one_tile_ops(n, m_rows, root_rows, small_rows):
    """The operations of a one-tile cell's tree, by what they are."""
    leaf = _leaf(n)
    return dict(
        root=f"%route_and_hist.7 = s32[{root_rows},128]{{1,0:T(8,128)}}{TAIL}",
        full=(f"%route_and_hist.3 = ({leaf}, s32[{m_rows},128]"
              f"{{1,0:T(8,128)S(1)}}, f32[1,64]{{1,0:T(1,128)S(1)}}){TAIL}"),
        small1=(f"%route_and_hist.4 = (s32[{small_rows},128]{{1,0:T(8,128)}}, "
                f"{leaf}, f32[1,1]{{1,0:T(1,128)S(1)}}){TAIL}"),
        small2=(f"%route_and_hist.5 = (s32[{2 * small_rows},128]"
                f"{{1,0:T(8,128)}}, {leaf}, f32[1,2]{{1,0:T(1,128)S(1)}})"
                f"{TAIL}"),
        route_only=(f"%route_and_hist.9 = ({leaf}, "
                    f"f32[1,128]{{1,0:T(1,128)S(1)}}){TAIL}"),
        other=f"%leaf_gather.1 = f32[1,{n}]{{1,0:T(1,128)}}{TAIL}")


N_WIDE = 401408
WIDE = dict(
    root=f"%route_and_hist.7 = s32[32768,128]{{1,0:T(8,128)}}{TAIL}",
    prepass=(f"%route_and_hist.3 = ({_leaf(N_WIDE)}, "
             f"f32[1,64]{{1,0:T(1,128)S(1)}}, {_leaf(N_WIDE)}){TAIL}"),
    sweeps=f"%route_and_hist.4 = s32[16,8192,128]{{2,1,0:T(8,128)}}{TAIL}",
    small_prepass=(f"%route_and_hist.8 = ({_leaf(N_WIDE)}, "
                   f"f32[1,8]{{1,0:T(1,128)S(1)}}, {_leaf(N_WIDE)}){TAIL}"),
    small1=f"%route_and_hist.9 = s32[16,1,2048,128]{{3,2,1,0:T(8,128)}}{TAIL}",
    small2=f"%route_and_hist.11 = s32[16,1,4096,128]{{3,2,1,0:T(8,128)}}{TAIL}",
    route_only=(f"%route_and_hist.12 = ({_leaf(N_WIDE)}, "
                f"f32[1,128]{{1,0:T(1,128)S(1)}}){TAIL}"),
    other=f"%fusion.12 = f32[64,2000,63,2]{{3,2,1,0}} fusion(%a), kind=kLoop")


def _run(ops, trees=2):
    return types.SimpleNamespace(
        reduced=tr.Reduced({"/device:TPU:0": ops}, SPANS),
        spans={"traced_trees": trees}, setup={}, window_start=5.0,
        traffic={}, say=lambda _: None,
        peak=lambda: {"int8_ops_per_s": 393e12})


def _events(tree, trees=2):
    ops, at = [], MS
    for _ in range(trees):
        for name, ms in tree:
            ops.append((name, at, ms * MS))
            at += (ms + 1) * MS
    return ops


CELLS = {
    # name: (rows, one-hot rows M, root rows, S = 1 rows, ms of a full pass)
    "higgs_train": (31404032, 1792, 512, 512, 50),
    "mslr_train": (11351040, 8704, 2304, 2304, 70),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_one_tile_small_passes_are_split_out(cell):
    n, m_rows, root_rows, small_rows, full_ms = CELLS[cell]
    op = _one_tile_ops(n, m_rows, root_rows, small_rows)
    parent = [(op["root"], 12)] + [(op["full"], full_ms)] * 7 \
        + [(op["route_only"], 9), (op["other"], 8)]
    change = [(op["root"], 12), (op["small1"], 18), (op["small2"], 30)] \
        + [(op["full"], full_ms)] * 5 + [(op["route_only"], 9),
                                         (op["other"], 8)]
    before, after = _run(_events(parent)), _run(_events(change))
    assert reader.read(before) is None
    assert reader.read(after) == pytest.approx(18 + 30)
    # the accepted readers, on the same lists: the roofline counts the
    # 128-column passes alone on both sides, so it reads the same over five
    # passes as over seven; the root is the root alone; the whole sums all
    want = 100 * (2 * m_rows * 128 * n / 393e12) / (full_ms / 1e3)
    assert one_tile.read(before) == pytest.approx(want)
    assert one_tile.read(after) == pytest.approx(want)
    assert 0 < want < 100
    assert root.read(before) == root.read(after) == pytest.approx(12)
    assert whole.read(before) == pytest.approx(12 + 7 * full_ms + 9)
    assert whole.read(after) == pytest.approx(12 + 48 + 5 * full_ms + 9)
    assert tiles.read(after) is None


def test_tiled_small_passes_are_split_out_with_their_pre_passes(monkeypatch):
    polls = [Record(i, "GBDT::FlagPoll", "GBDT::Iteration", int(at * 1e9),
                    10**8, dict(iteration=16 * (i + 1), hist_passes=144,
                                root_pass="factored", hist_tiles=16,
                                hist_m_rows=128000))
             for i, at in enumerate((3.0, 10.0))]
    monkeypatch.setattr(program_spans, "ring", lambda: (polls, 0))
    full = [(WIDE["prepass"], 1), (WIDE["sweeps"], 39)]
    parent = [(WIDE["root"], 10)] + full * 8 \
        + [(WIDE["route_only"], 1), (WIDE["other"], 20)]
    change = [(WIDE["root"], 10), (WIDE["small_prepass"], 1),
              (WIDE["small1"], 9), (WIDE["small_prepass"], 1),
              (WIDE["small2"], 17)] + full * 6 \
        + [(WIDE["route_only"], 1), (WIDE["other"], 20)]
    before, after = _run(_events(parent)), _run(_events(change))
    assert reader.read(before) is None
    assert reader.read(after) == pytest.approx(1 + 9 + 1 + 17)
    want = 100 * (2 * 128000 * 128 * N_WIDE / 393e12) / 0.040
    assert tiles.read(before) == pytest.approx(want)
    assert tiles.read(after) == pytest.approx(want)
    assert 0 < want < 100
    assert root.read(before) == root.read(after) == pytest.approx(10)
    assert one_tile.read(after) is None
    assert whole.read(before) == pytest.approx(10 + 8 * 40 + 1)
    assert whole.read(after) == pytest.approx(10 + 28 + 6 * 40 + 1)


def test_nothing_to_read_reads_nothing():
    op = _one_tile_ops(31404032, 1792, 512, 512)
    assert reader.read(types.SimpleNamespace(
        reduced=None, spans={"traced_trees": 5}, setup={})) is None
    assert reader.read(_run(_events([(op["small1"], 18)]), trees=0)) is None
    # a small pass outside the traced window is not counted
    ops = _events([(op["small1"], 18)]) + [(op["small1"], 2500 * MS, 18 * MS)]
    assert reader.read(_run(ops)) == pytest.approx(18)


def test_listed_for_the_three_training_cells(manifest):
    entry = [m for m in manifest["per_layer"] if m["name"] == reader.NAME]
    assert len(entry) == 1 and entry[0] == dict(
        name="small_pass_ms_per_tree", unit=reader.UNIT, better="lower",
        source="device_trace", layer=reader.LAYER, moves=reader.MOVES,
        workloads=["higgs_train", "mslr_train", "epsilon_train"])
