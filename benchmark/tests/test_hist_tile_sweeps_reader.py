"""`hist_tile_sweeps_per_tree` on `GBDT::FlagPoll` records as the program
writes them for a table of sixteen M-tiles (recorded from the `epsilon_train`
rehearsal: iteration, hist_passes, root_pass, hist_tiles, hist_m_rows), on
the records of a program from before the kernel tiled, and by name in the
cell's own rehearsal with the fused iteration on."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH, REPO, load_module

import program_spans
from program_spans import Record

S = 10**9
reader = load_module(BENCH / "layers" / "hist_tile_sweeps_per_tree.py")
passes = load_module(BENCH / "layers" / "hist_passes_per_tree.py")


def poll(seq, at_s, iteration, hist_passes, **more):
    args = dict(iteration=iteration, hist_passes=hist_passes, **more)
    return Record(seq, "GBDT::FlagPoll", "GBDT::Iteration", int(at_s * S),
                  S // 2, args)


def tiled(seq, at_s, iteration, hist_passes):
    return poll(seq, at_s, iteration, hist_passes, root_pass="factored",
                hist_tiles=16, hist_m_rows=128000)


def fake_run(window_start_s):
    return SimpleNamespace(window_start=float(window_start_s), traffic={},
                           say=lambda _: None, spans={"traced_trees": 5})


def test_sweeps_are_passes_times_the_records_tiles(monkeypatch):
    # polls every 8 trees; 9 passes a tree up to tree 16, 8.5 after it
    ring = [tiled(0, 10.0, 8, 72), tiled(1, 20.0, 16, 144),
            tiled(2, 30.0, 24, 212), tiled(3, 40.0, 32, 280)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    run = fake_run(15.0)                  # polls 16, 24, 32 in the window
    assert passes.read(run) == pytest.approx(8.5)
    assert reader.read(run) == pytest.approx(16 * 8.5)
    assert reader.NAME == "hist_tile_sweeps_per_tree"
    assert (reader.UNIT, reader.LAYER, reader.MOVES) == (
        "sweeps/tree", "pallas.stream_kernel", "train_s_per_tree")


def test_one_poll_in_the_window_counts_from_the_one_before(monkeypatch):
    ring = [tiled(0, 10.0, 8, 72), tiled(1, 20.0, 16, 144)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert reader.read(fake_run(15.0)) == pytest.approx(16 * 9.0)
    # the only poll there is: from the counter's zero at iteration 0
    monkeypatch.setattr(program_spans, "ring", lambda: (ring[:1], 0))
    assert reader.read(fake_run(5.0)) == pytest.approx(16 * 9.0)
    assert reader.read(fake_run(11.0)) is None


def test_a_program_that_states_no_tiles_reads_nothing(monkeypatch):
    """The parent's records (PR 27/28: iteration, hist_passes, root_pass):
    the metric is left out of the line, and nothing is raised."""
    ring = [poll(0, 10.0, 8, 72, root_pass="factored"),
            poll(1, 20.0, 16, 144, root_pass="factored")]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert passes.read(fake_run(5.0)) == pytest.approx(9.0)
    assert reader.read(fake_run(5.0)) is None
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert reader.read(fake_run(5.0)) is None


def test_listed_for_the_wide_cell_alone(manifest):
    entry = [m for m in manifest["per_layer"] if m["name"] == reader.NAME]
    assert len(entry) == 1 and entry[0]["workloads"] == ["epsilon_train"]
    assert entry[0]["source"] == "program_counter"


def test_rehearsal_of_the_wide_cell_prints_it_by_name(manifest):
    """The chip runs the fused iteration over the stream kernel, whose flag
    polls carry the counts; the CPU rehearsal asks for both with the
    program's own switches.  Whenever the window held a poll to count passes
    from, the sweeps are printed beside them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", LGBTPU_FUSE_ITER="1",
               LGBTPU_HIST_BACKEND="stream",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "epsilon_train",
         "--seed", str(2**31 + 13), "--seconds", "6", "--trace", "1",
         "--rehearse"],
        cwd=REPO, env=env, text=True, capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = set(line["metrics"])
    assert {"dataset_bin_s", "dataset_ship_s", "compile_s"} <= got, got
    assert ("hist_tile_sweeps_per_tree" in got) \
        == ("hist_passes_per_tree" in got), sorted(got)
    if "hist_tile_sweeps_per_tree" in got:
        assert line["metrics"]["hist_tile_sweeps_per_tree"] == {
            "value": None, "unit": "sweeps/tree"}
    assert "OVERWROTE" not in proc.stdout
