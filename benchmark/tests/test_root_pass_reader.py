"""`root_pass_ms_per_tree` on a hand-made event list with a known answer,
and on the recorded v5e trace (whose root is the one-hot form, 48.06 ms)."""
import types

import pytest

from conftest import BENCH, load_module

import trace_reduction as tr

MS = 1_000_000
N = 31404032
LEAF = f"s32[1,{N}]{{1,0:T(1,128)}}"
TAIL = ' custom-call(%a, %b), custom_call_target="tpu_custom_call"'
FACTORED = f"%route_and_hist.7 = s32[512,128]{{1,0:T(8,128)S(1)}}{TAIL}"
ONEHOT = (f"%route_and_hist.2 = ({LEAF}, s32[1792,2]{{1,0:T(8,128)S(1)}}, "
          f"f32[1,1]{{1,0:T(1,128)}}){TAIL}")
FULL = (f"%route_and_hist.3 = ({LEAF}, s32[1792,128]{{1,0:T(8,128)S(1)}}, "
        f"f32[1,64]{{1,0:T(1,128)S(1)}}){TAIL}")
ROUTE_ONLY = (f"%route_and_hist.9 = ({LEAF}, "
              f"f32[1,128]{{1,0:T(1,128)S(1)}}){TAIL}")
OTHER = f"%leaf_gather.1 = f32[1,{N}]{{1,0:T(1,128)}}{TAIL}"
SPANS = [("bench.update", 0, 10 * MS), ("bench.drain", 10 * MS, 380 * MS)]

reader = load_module(BENCH / "layers" / "root_pass_ms_per_tree.py")
whole = load_module(BENCH / "layers" / "hist_kernel_ms_per_tree.py")
roofline = load_module(BENCH / "layers" / "hist_kernel_roofline.py")


def _run(ops, trees=2):
    return types.SimpleNamespace(
        reduced=tr.Reduced({"/device:TPU:0": ops}, SPANS),
        spans={"traced_trees": trees}, setup={},
        peak=lambda: {"int8_ops_per_s": 393e12})


def _two_trees(root, root_ms):
    ops, at = [], MS
    for _ in range(2):
        for name, ms in ((root, root_ms), (FULL, 50), (FULL, 50),
                         (ROUTE_ONLY, 9), (OTHER, 8)):
            ops.append((name, at, ms * MS))
            at += (ms + 1) * MS
    return ops


@pytest.mark.parametrize("root,root_ms", [(FACTORED, 12), (ONEHOT, 48)],
                         ids=["factored", "onehot"])
def test_root_is_split_out_and_stays_in_the_whole(root, root_ms):
    run = _run(_two_trees(root, root_ms))
    assert reader.read(run) == pytest.approx(root_ms)
    # the accepted reader goes on summing every pass, the root among them
    assert whole.read(run) == pytest.approx(root_ms + 50 + 50 + 9)
    # and the roofline counts the 128-column passes alone, on both sides
    want = 100 * (2 * 1792 * 128 * N / 393e12) / 0.050
    assert roofline.read(run) == pytest.approx(want)
    assert 0 < roofline.read(run) < 100


def test_no_root_operation_reads_nothing():
    ops = [(FULL, MS, 50 * MS), (ROUTE_ONLY, 60 * MS, 9 * MS),
           (OTHER, 70 * MS, 8 * MS)]
    assert reader.read(_run(ops)) is None
    assert reader.read(types.SimpleNamespace(
        reduced=None, spans={"traced_trees": 5}, setup={})) is None
    assert reader.read(_run(_two_trees(FACTORED, 12), trees=0)) is None


def test_root_outside_the_window_is_not_counted():
    ops = _two_trees(FACTORED, 12) + [(FACTORED, 400 * MS, 12 * MS)]
    assert reader.read(_run(ops)) == pytest.approx(12)


RECORDED = BENCH / "tests" / "data" / "v5e_train.xplane.pb"


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_recorded_trace_reads_the_one_hot_root():
    run = types.SimpleNamespace(reduced=tr.load(str(RECORDED)),
                                spans={"traced_trees": 5}, setup={})
    assert reader.read(run) == pytest.approx(48.0579848)
    assert reader.read(run) < whole.read(run)
