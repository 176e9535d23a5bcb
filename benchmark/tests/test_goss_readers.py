"""The five per-layer metrics of the sampled cell (`higgs_goss_train`) on
hand-made records and device operations whose names are copied from that
cell's own trace (my chip run, PR 37, seed 3000003804; layouts left out),
on the parent's records, which lack the fields, and on the recorded trace
of the dense cell, where each device reader reads nothing."""
import types
from types import SimpleNamespace

import pytest

from conftest import BENCH, load_module

import program_spans
import trace_reduction as tr
from program_spans import Record

S = 10**9
MS = 10**6
N, CAP = 31404032, 11796480
sampled = load_module(BENCH / "layers" / "sampled_rows_pct.py")
hist_rows = load_module(BENCH / "layers" / "hist_rows_pct.py")
compact = load_module(BENCH / "layers" / "sample_compact_ms_per_tree.py")
replay = load_module(BENCH / "layers" / "route_replay_ms_per_tree.py")
roofline = load_module(BENCH / "layers" / "route_replay_roofline.py")
whole = load_module(BENCH / "layers" / "hist_kernel_ms_per_tree.py")

THRESHOLD_SORT = ("%sort.40 = (f32[31400192], s32[31400192]) sort("
                  "f32[31400192] %get-tuple-element.2809, s32[31400192] "
                  "%iota.167), dimensions={0}, is_stable=true")
PARTITION_SORT = ("%sort.7 = (s32[31404032], s32[31404032]) sort(s32[31404032]"
                  " %bitcast.29, s32[31404032] %iota.70), dimensions={0}")
SCAN_SORT = ("%sort.43 = (f32[255], s32[255]) sort(f32[255] "
             "%get-tuple-element.3385, s32[255] %iota.434), dimensions={0}")
TABLE_GATHER = ("%fusion.46 = s8[11796480,32] fusion(s8[32,31404032] "
                "%packed.1, s32[11796480] %fusion.265), kind=kCustom")
WEIGHT_GATHER = ("%fusion.47 = f32[11796480,8] fusion(f32[8,31404032] "
                 "%dynamic-update-slice.99, s32[11796480] %fusion.265)")
TRANSPOSE = ("%select_bitcast_fusion = f32[8,11796480] fusion("
             "f32[11796480,8] %fusion.47, pred[11796480] %copy-done.19)")
COMPACT_PASS = ("%route_and_hist.7 = (s32[1,11796480], s32[1792,128], "
                "f32[1,64]) custom-call(s8[32,11796480] %get-tuple-element.44"
                "), custom_call_target=\"tpu_custom_call\"")
REPLAY = ("%route_replay.1 = s32[1,31404032] custom-call(s32[1] %bitcast.64, "
          "s8[32,31404032] %packed.1, f32[6360,255] %get-tuple-element.1801),"
          " custom_call_target=\"tpu_custom_call\"")
SCORE_ADD = ("%add.706 = f32[31400192] add(f32[31400192] %state_score.1, "
             "f32[31400192] %slice_reduce_fusion)")


def poll(seq, at_s, iteration, hist_passes, mode=None, sampled_rows=None,
         compact_rows=None):
    args = dict(iteration=iteration, hist_passes=hist_passes,
                root_pass="factored", hist_tiles=1, hist_m_rows=1792)
    if mode is not None:
        args.update(sample_mode=mode, sampled_rows=sampled_rows,
                    compact_rows=compact_rows, compact_overflow=0,
                    route_only_passes=1 if compact_rows else 0)
    return Record(seq, "GBDT::FlagPoll", "GBDT::Iteration", int(at_s * S),
                  S // 2, args)


def plan(seq, at_s, rows, capacity):
    return Record(seq, "GBDT::SamplePlan", "GBDT::FusedIter", int(at_s * S),
                  1000, dict(rows=rows, expected_fraction=0.30,
                             capacity=capacity))


def fake_run(window_start_s, ops=None, trees=5):
    sizes = {"rows": 31500000, "holdout": {"rows": 100000}}
    reduced = None
    if ops is not None:
        reduced = tr.Reduced({"/device:TPU:0": ops},
                             [("bench.update", 0, 5000 * MS)])
    return SimpleNamespace(
        window_start=float(window_start_s), traffic={}, say=lambda _: None,
        spans={"traced_trees": trees}, sized=sizes.__getitem__,
        reduced=reduced, setup={},
        peak=lambda: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


RING = [plan(0, 4.0, 31400192, CAP),
        poll(1, 3.0, 10, 80, "none", 31400000, 0),
        poll(2, 10.0, 16, 129, "goss", 8792387, CAP),
        poll(3, 20.0, 32, 261, "goss", 8790579, CAP),
        poll(4, 30.0, 48, 392, "goss", 8791000, CAP)]


def test_counters_read_the_windows_sampled_polls(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: (RING, 0))
    run = fake_run(5.0)
    mean = (8792387 + 8790579 + 8791000) / 3
    assert sampled.read(run) == pytest.approx(100 * mean / 31400000)
    assert hist_rows.read(run) == pytest.approx(100 * CAP / 31400192)
    # an unsampled poll (the sampler's warm-up) is no reading of either
    assert sampled.read(fake_run(2.0)) == pytest.approx(sampled.read(run))
    for mod, unit in ((sampled, "%"), (hist_rows, "%")):
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
            unit, "models.gbdt", "train_s_per_tree")


def test_fall_back_and_lost_plan(monkeypatch):
    """Compaction that fell back reads 100; without a SamplePlan record the
    share is over the rows trained on."""
    ring = [poll(0, 10.0, 16, 129, "goss", 8792387, CAP),
            poll(1, 20.0, 32, 261, "goss", 8790579, 0)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    assert hist_rows.read(fake_run(5.0)) == pytest.approx(
        (100 * CAP / 31400000 + 100.0) / 2)
    assert compact.compact_rows(fake_run(5.0)) == CAP


def test_the_parents_records_read_nothing(monkeypatch):
    ring = [poll(0, 10.0, 16, 129), poll(1, 20.0, 32, 261)]
    monkeypatch.setattr(program_spans, "ring", lambda: (ring, 0))
    run = fake_run(5.0, ops=[(SCORE_ADD, MS, MS)])
    assert sampled.read(run) is None and hist_rows.read(run) is None
    assert compact.read(run) is None
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert sampled.read(run) is None and hist_rows.read(run) is None
    assert compact.read(run) is None and compact.compact_rows(run) == 0


def test_device_readers_on_the_cells_operations(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: (RING, 0))
    ops, at = [], MS
    for tree in range(5):
        for name, ms in ((THRESHOLD_SORT, 98), (PARTITION_SORT, 77),
                         (TABLE_GATHER, 335), (WEIGHT_GATHER, 223),
                         (TRANSPOSE, 1), (COMPACT_PASS, 17), (SCAN_SORT, 1),
                         (REPLAY, 46), (SCORE_ADD, 1)):
            ops.append((name, at, ms * MS))
            at += (ms + 1) * MS
    run = fake_run(5.0, ops=ops)
    # the sorts of a million rows and what has the compact length; not the
    # pass over the compact view (a Pallas call), the scan's sort, the rest
    assert compact.read(run) == pytest.approx(98 + 77 + 335 + 223 + 1)
    assert replay.read(run) == pytest.approx(46)
    assert whole.read(run) == pytest.approx(17)      # the replay is not in it
    # without the polls' capacity only the sorts count
    monkeypatch.setattr(program_spans, "ring", lambda: (RING[:2], 0))
    assert compact.read(fake_run(5.0, ops=ops)) == pytest.approx(98 + 77)


def test_replay_roofline_counts_rounds_from_the_passes(monkeypatch):
    monkeypatch.setattr(program_spans, "ring", lambda: (RING, 0))
    run = fake_run(5.0, ops=[(REPLAY, MS, 46 * MS), (COMPACT_PASS, 60 * MS,
                                                     17 * MS)])
    passes = (392 - 129) / 32
    by_ops = 2 * (passes - 1) * 24 * 255 * N / 197e12
    by_bytes = (32 * N + 4 * N) / 819e9
    assert by_ops > by_bytes                         # bf16 compute binds
    assert roofline.read(run) == pytest.approx(100 * by_ops / 0.046)
    assert roofline.macs(8, 255, N) == 8 * 24 * 255 * N
    assert roofline.table_bytes("s8", 32, N) == 36 * N
    assert roofline.table_bytes("s32", 8, N) == 36 * N
    # a root-only tree: the bytes bind, and the share stays under 100
    monkeypatch.setattr(program_spans, "ring", lambda: (
        [poll(0, 10.0, 16, 16), poll(1, 20.0, 32, 32)], 0))
    assert roofline.read(run) == pytest.approx(100 * by_bytes / 0.046)
    # a call whose text does not carry its operands reads nothing
    bare = fake_run(5.0, ops=[("%route_replay.1 = s32[1,31404032] "
                               "custom-call()", MS, 46 * MS)])
    assert roofline.read(bare) is None
    assert roofline.read(fake_run(5.0, ops=[(COMPACT_PASS, MS, MS)])) is None


RECORDED = BENCH / "tests" / "data" / "v5e_train.xplane.pb"


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_the_dense_cells_recorded_trace_reads_nothing(monkeypatch):
    """`higgs_train`'s trace: no sort of a million rows, no replay."""
    monkeypatch.setattr(program_spans, "ring", lambda: (
        [poll(0, 10.0, 16, 129, "none", 31400000, 0)], 0))
    run = types.SimpleNamespace(
        reduced=tr.load(str(RECORDED)), spans={"traced_trees": 5}, setup={},
        window_start=5.0, traffic={}, say=lambda _: None,
        peak=lambda: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert compact.read(run) is None
    assert replay.read(run) is None and roofline.read(run) is None
    assert whole.read(run) > 0


def test_listed_for_the_sampled_cell_alone(manifest):
    names = [m["name"] for m in manifest["per_layer"]]
    for mod, better, source in (
            (sampled, "lower", "program_counter"),
            (hist_rows, "lower", "program_counter"),
            (compact, "lower", "device_trace"),
            (replay, "lower", "device_trace"),
            (roofline, "higher", "device_trace")):
        entry = manifest["per_layer"][names.index(mod.NAME)]
        assert entry == dict(
            name=mod.NAME, unit=mod.UNIT, better=better, source=source,
            layer=mod.LAYER, moves=mod.MOVES,
            workloads=["higgs_goss_train"])
