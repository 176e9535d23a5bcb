"""The reduction from a trace to numbers, on a hand-made event list with a
known answer and on a small trace recorded on the v5e (tests/data/)."""
import json

import pytest

from conftest import BENCH

import trace_reduction as tr

MS = 1_000_000

# One lane, a 100 ms window made of two harness spans.  A `while` encloses
# two kernels with a 5 ms hole between them; a fusion overlaps nothing; the
# device then idles until the drain ends.
OPS = [
    ("while.3", 10 * MS, 40 * MS),
    ("_route_hist_kernel.1", 10 * MS, 20 * MS),
    ("_route_hist_kernel.2", 35 * MS, 15 * MS),
    ("fusion.7", 50 * MS, 10 * MS),
    ("fusion.9", 70 * MS, 10 * MS),
    ("copy.1", 300 * MS, 10 * MS),          # outside the window
]
SPANS = [("bench.update", 0, 60 * MS), ("bench.drain", 60 * MS, 40 * MS)]


def test_leaves_drop_the_enclosing_op():
    names = [n for n, _, _ in tr.leaves(OPS)]
    assert "while.3" not in names and len(names) == 5


def test_busy_union_and_idle_share():
    assert tr.busy_ns(tr.leaves(OPS), 0, 100 * MS) == 55 * MS
    red = tr.Reduced({"/device:TPU:0": OPS}, SPANS)
    assert red.window_s == pytest.approx(0.100)
    assert red.busy_s == pytest.approx(0.055)
    assert red.idle_pct == pytest.approx(45.0)
    # a second chip that did nothing halves the mean
    two = tr.Reduced({"a": OPS, "b": []}, SPANS)
    assert two.busy_s == pytest.approx(0.0275)


def test_overlapping_ops_count_once():
    ops = [("a", 0, 10 * MS), ("b", 5 * MS, 10 * MS), ("c", 15 * MS, 5 * MS)]
    assert tr.busy_ns(ops, 0, 30 * MS) == 20 * MS
    assert tr.busy_ns(ops, 8 * MS, 12 * MS) == 4 * MS      # clipped


def test_kernel_sum_and_count():
    red = tr.Reduced({"d": OPS}, SPANS)
    assert red.kernel_s(r"_route_hist_kernel") == pytest.approx(0.035)
    assert red.kernel_s(r"fusion") == pytest.approx(0.020)
    assert red.kernel_s(r"copy") == 0.0


def test_gaps_go_to_the_span_the_host_was_in():
    assert tr.gaps(tr.leaves(OPS), 0, 100 * MS) == [
        (0, 10 * MS), (30 * MS, 35 * MS), (60 * MS, 70 * MS),
        (80 * MS, 100 * MS)]
    red = tr.Reduced({"d": OPS}, SPANS)
    b = red.breakdown()
    assert b["idle_gaps"] == [["bench.drain", pytest.approx(0.030)],
                              ["bench.update", pytest.approx(0.015)]]
    assert b["device_ops"][0] == ["bench.update/_route_hist_kernel",
                                  pytest.approx(0.035)]
    assert ["bench.drain/fusion", pytest.approx(0.010)] in b["device_ops"]
    assert len(b["device_ops"]) <= 10


def test_gap_across_two_spans_is_split():
    ops = [("k", 0, 10 * MS), ("k", 90 * MS, 10 * MS)]
    spans = [("bench.a", 0, 50 * MS), ("bench.b", 50 * MS, 50 * MS)]
    assert tr.top_gaps(ops, spans, 0, 100 * MS) == [
        ["bench.a", pytest.approx(0.040)], ["bench.b", pytest.approx(0.040)]]


def test_busy_inside_one_span():
    red = tr.Reduced({"d": OPS}, SPANS)
    assert red.busy_inside(SPANS[0]) == pytest.approx(0.045)
    assert red.busy_inside(SPANS[1]) == pytest.approx(0.010)


def test_no_span_is_an_error():
    with pytest.raises(ValueError):
        tr.Reduced({"d": OPS}, [])


RECORDED = BENCH / "tests" / "data" / "v5e_train.xplane.pb"
EXPECTED = BENCH / "tests" / "data" / "v5e_train.expected.json"


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_recorded_v5e_trace():
    """A stretch of the HIGGS-shaped training trace from the chip, cut down
    to the device's XLA Ops lane and the harness's spans; the expected
    numbers were read off the same file when it was recorded."""
    want = json.loads(EXPECTED.read_text())
    red = tr.load(str(RECORDED))
    assert list(red.lanes) == want["lanes"]
    assert [s[0] for s in red.spans] == want["spans"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < red.busy_s <= red.window_s
    for pattern, secs in want["kernels"].items():
        assert red.kernel_s(pattern) == pytest.approx(secs, rel=1e-9)
        assert 0.0 < red.kernel_s(pattern) <= red.busy_s
    b = red.breakdown()
    assert b["device_ops"][0][0] == want["top_op"]
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_layer_readers_on_the_recorded_trace():
    """The kernel patterns are data in the readers' own files: here they meet
    the names a v5e trace really carries (an operation's whole HLO text)."""
    import types
    from conftest import load_module
    red = tr.load(str(RECORDED))
    run = types.SimpleNamespace(
        reduced=red, spans={"traced_trees": 5}, setup={},
        peak=lambda: {"int8_ops_per_s": 393e12})
    read = {f.name[:-3]: load_module(f).read(run)
            for f in (BENCH / "layers").glob("*.py")}
    hist = read["hist_kernel_ms_per_tree"]
    assert hist == pytest.approx(1e3 * 2.043597727 / 5)
    assert read["xla_other_ms_per_tree"] == pytest.approx(
        1e3 * (2.220641557 - 2.083961587) / 5)
    # 7 of a tree's 9 passes contract against 128 columns: 28 x 64 one-hot
    # rows x 128 x 31,404,032 rows, 2 ops a MAC, over 393 TOP/s is 36.7 ms a
    # pass against 50.2 ms measured
    assert read["hist_kernel_roofline"] == pytest.approx(73.065, abs=0.01)
    assert 0 < read["hist_kernel_roofline"] < 100
    assert read["device_idle_pct.train"] == pytest.approx(0.2495, abs=1e-3)
    assert read["predict_kernel_ms_per_call"] is None     # not in this trace
