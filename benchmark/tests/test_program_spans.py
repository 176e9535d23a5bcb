"""The readers that take their numbers from inside the program: the ring
arithmetic on hand-made rings (window edge, overwritten records, a program
without a ring), the four predict readers that wait for their cell, and the
rehearsal of the training cells with the fused iteration on, which prints the
new metrics by name."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH, REPO, load_module

import program_spans
from program_spans import Record

NEW_TRAIN = {"iter_dispatch_ms_per_tree", "flag_poll_wait_ms_per_tree",
             "hist_passes_per_tree", "dataset_bin_s", "dataset_ship_s"}
PREDICT_READERS = ("predict_rebin_ms_per_call", "predict_tables_ms_per_call",
                   "predict_ship_ms_per_call", "predict_readback_ms_per_call")
S = 10**9


def rec(seq, name, start_s, dur_s, parent=None, **args):
    return Record(seq, name, parent, int(start_s * S), int(dur_s * S),
                  args or None)


def fake_run(window_start_s, traffic=None, traced_trees=5):
    said = []
    run = SimpleNamespace(window_start=float(window_start_s),
                          traffic=traffic or {}, say=said.append, said=said,
                          spans={"traced_trees": traced_trees})
    run.mix = lambda key: run.traffic[key]
    return run


def reader(name):
    return load_module(BENCH / "layers" / f"{name}.py")


# ------------------------------------------------------------ ring arithmetic
def test_cut_keeps_what_started_at_the_window_edge_or_after():
    ring = [rec(0, "A", 1.0, 0.5), rec(1, "A", 9.999999999, 0.1),
            rec(2, "A", 10.0, 0.2), rec(3, "B", 10.1, 0.1),
            rec(4, "A", 12.0, 0.3)]
    assert [r.seq for r in program_spans.cut(ring, 0, "A", lo_ns=10 * S)] \
        == [2, 4]
    assert [r.seq for r in program_spans.cut(ring, 0, "A", hi_ns=10 * S)] \
        == [0, 1]
    assert [r.seq for r in program_spans.cut(ring, 0, "A")] == [0, 1, 2, 4]
    assert program_spans.cut([], 0, "A", lo_ns=0) == []


def test_cut_refuses_an_interval_the_ring_may_have_lost_part_of():
    ring = [rec(7, "A", 8.0, 1.0), rec(8, "A", 10.5, 0.2)]
    # what was overwritten ended before second 9: the window from 10 is whole
    assert [r.seq for r in program_spans.cut(ring, 7, "A", lo_ns=10 * S)] \
        == [8]
    # ... but a window from 8.5 may have lost records, and set-up has
    assert program_spans.cut(ring, 7, "A", lo_ns=int(8.5 * S)) is None
    assert program_spans.cut(ring, 7, "A", hi_ns=10 * S) is None
    # the oldest record left started inside the window: lost, say so
    ring = [rec(7, "A", 10.2, 0.1), rec(8, "A", 10.5, 0.2)]
    assert program_spans.cut(ring, 7, "A", lo_ns=10 * S) is None


def test_in_window_is_loud_about_overwritten_records(monkeypatch):
    ring = [rec(7, "A", 10.2, 0.1), rec(8, "A", 10.5, 0.2)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 7))
    run = fake_run(10.0)
    assert program_spans.in_window(run, "A") is None
    assert run.said and "OVERWROTE 7 RECORDS" in run.said[0]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert [r.seq for r in program_spans.in_window(fake_run(10.3), "A")] \
        == [8]
    assert [r.seq for r in program_spans.in_setup(fake_run(10.3), "A")] \
        == [7]


def test_a_program_without_a_ring_gives_nothing_and_raises_nothing(
        monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    run = fake_run(10.0)
    assert program_spans.in_window(run, "A") is None
    assert program_spans.in_setup(run, "A") is None
    for name in (*NEW_TRAIN, *PREDICT_READERS):
        assert reader(name).read(run) is None, name
    assert run.said == []


def test_calls_gives_each_parent_its_own_children():
    ring = [rec(0, "P::a", 1.0, 0.1, "P"), rec(1, "P::b", 1.1, 0.2, "P"),
            rec(2, "P", 1.0, 0.4, rows=8),
            rec(3, "other", 1.5, 9.0),
            rec(4, "P::a", 2.0, 0.3, "P"), rec(5, "P::a", 2.3, 0.1, "P"),
            rec(6, "P", 2.0, 0.5, rows=4)]
    got = program_spans.calls(ring, "P")
    assert [(p.seq, c) for p, c in got] == [
        (2, {"P::a": S // 10, "P::b": 2 * S // 10}),
        (6, {"P::a": 4 * S // 10})]


# ------------------------------------------------------------ training readers
def training_ring():
    ring = [rec(0, "Dataset::FindBins", 1.0, 2.0, rows=100),
            rec(1, "Dataset::Bin", 3.0, 5.0, rows=100),
            rec(2, "Dataset::Ship", 8.0, 0.5, rows=100, groups=4)]
    seq, t = 3, 20.0
    for it in range(1, 41):
        # the first 5 of the window (9..13) dispatch into an empty queue,
        # the later ones wait for the device
        busy = 0.002 if it < 14 else 0.3
        ring.append(rec(seq, "GBDT::FusedIter", t, busy, "GBDT::Iteration"))
        dur = busy + 0.001
        if it % 8 == 0:
            # 9 passes a tree up to the poll at 24, 10 after it
            passes = 9 * it + max(0, it - 24)
            ring.append(rec(seq + 1, "GBDT::FlagPoll", t + busy, 0.8,
                            "GBDT::Iteration", iteration=it,
                            hist_passes=passes))
            dur, seq = dur + 0.8, seq + 1
        ring.append(rec(seq + 1, "GBDT::Iteration", t, dur, step_num=it))
        seq, t = seq + 2, t + 1.0
    return ring


def test_training_readers_on_a_hand_made_ring(monkeypatch):
    ring = training_ring()
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    run = fake_run(27.5)      # iterations 9..40 in the window: polls 16..40
    assert reader("iter_dispatch_ms_per_tree").read(run) \
        == pytest.approx(2.0)
    assert "over the window's 32 iterations" in run.said[-1]
    assert reader("flag_poll_wait_ms_per_tree").read(run) \
        == pytest.approx(4 * 800.0 / 32)
    # (9*40+16 - 9*16) / (40 - 16): 8 trees of 9 passes, 16 of 10
    assert reader("hist_passes_per_tree").read(run) \
        == pytest.approx((8 * 9 + 16 * 10) / 24)
    assert reader("dataset_bin_s").read(run) == pytest.approx(7.0)
    assert reader("dataset_ship_s").read(run) == pytest.approx(0.5)


def test_hist_passes_with_one_poll_in_the_window_counts_from_the_one_before(
        monkeypatch):
    ring = [r for r in training_ring() if r.start_unix_ns < 36 * S]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    # polls at 8 (set-up) and 16 (window)
    assert reader("hist_passes_per_tree").read(fake_run(29.5)) \
        == pytest.approx(9.0)
    # only the poll at 8, in the window: from the counter's zero
    ring = [r for r in ring if r.start_unix_ns < 28 * S]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert reader("hist_passes_per_tree").read(fake_run(19.0)) \
        == pytest.approx(9.0)
    # no poll at all: nothing to read
    assert reader("hist_passes_per_tree").read(fake_run(28.5)) is None


# ------------------------------------------------------------- predict readers
def test_predict_readers_wait_for_their_cell(manifest):
    listed = {m["name"] for m in manifest["per_layer"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for name in PREDICT_READERS:
        mod = reader(name)
        assert mod.NAME == name and mod.LAYER == "basic"
        assert mod.MOVES == "score_rows_per_s"
        # they enter the manifest with the scoring cell and its metric
        assert (name in listed) == (mod.MOVES in e2e)


def test_predict_readers_without_and_with_a_predict_span(monkeypatch):
    ring = training_ring()
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    for name in PREDICT_READERS:
        assert reader(name).read(fake_run(19.0)) is None, name
    calls = []
    for i, rows in enumerate((64, 64, 1000)):   # the last: a holdout check
        t, k = 100.0 + i, 10 * i
        calls += [
            rec(k, "Predict::RoutingTables", t, 0.001, "Predict"),
            rec(k + 1, "Predict::Rebin", t + .001, 0.010 * (i + 1), "Predict"),
            rec(k + 2, "Predict::PackShip", t + .1, 0.004, "Predict"),
            rec(k + 3, "Predict::NodeTables", t + .2, 0.020, "Predict",
                trees=5),
            rec(k + 4, "Predict::Walk", t + .3, 0.001, "Predict"),
            rec(k + 5, "Predict::Readback", t + .4, 0.015, "Predict"),
            rec(k + 6, "Predict", t, 0.5, rows=rows, trees=5, path="device",
                reason="")]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring + calls, 0))
    run = fake_run(99.0, {"batch_rows": 64})
    assert reader("predict_rebin_ms_per_call").read(run) \
        == pytest.approx(15.0)
    assert reader("predict_tables_ms_per_call").read(run) \
        == pytest.approx(21.0)
    assert reader("predict_ship_ms_per_call").read(run) == pytest.approx(4.0)
    assert reader("predict_readback_ms_per_call").read(run) \
        == pytest.approx(15.0)
    # a mix that states no batch size counts every call
    assert reader("predict_rebin_ms_per_call").read(fake_run(99.0)) \
        == pytest.approx(20.0)


# ------------------------------------------------------------------ rehearsal
@pytest.mark.parametrize("cell", ["higgs_train", "mslr_train"])
def test_rehearsal_prints_the_new_metrics_by_name(cell, manifest):
    """The CPU keeps the eager iteration unless told otherwise; the chip runs
    the fused one, so the rehearsal asks for it with the program's own
    switch (the driver's runs set nothing)."""
    assert cell in {w["name"] for w in manifest["workloads"]}
    env = dict(os.environ, JAX_PLATFORMS="cpu", LGBTPU_FUSE_ITER="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell, "--seed",
         str(2**31 + 11), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, text=True, capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert NEW_TRAIN <= set(line["metrics"]), sorted(line["metrics"])
    for name in NEW_TRAIN:
        assert line["metrics"][name]["value"] is None
    assert "OVERWROTE" not in proc.stdout
