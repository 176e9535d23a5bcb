"""The readers of set-up, memory and the window's 16-tree intervals
(poll_timeline.py and the seven layers/ files that go through it) on
hand-made rings: known intervals, one slow interval, one nine-pass interval,
the profiled stretch of a traced run, two polls only, an overwritten ring,
and the parent's record shape without the new fields."""
from types import SimpleNamespace

import pytest

from conftest import BENCH, load_module

import poll_timeline
import program_spans
from program_spans import Record

READERS = ("program_compile_s", "iter_compile_s", "setup_peak_hbm_gb",
           "train_hbm_gb", "poll_tree_ms_spread_pct",
           "poll_pass_ms_spread_pct", "poll_gap_ms_per_tree")
S = 10**9
WINDOW = 100.0                       # the first measured instant, seconds
HBM = {"hbm_in_use_bytes": 5_000_000_000, "hbm_peak_bytes": 6_800_000_000}


def rec(name, start_s, dur_s, parent=None, **args):
    return Record(0, name, parent, round(start_s * S), round(dur_s * S),
                  args or None)


def numbered(records):
    """In the order the spans ended, as the program's ring holds them."""
    ordered = sorted(records, key=poll_timeline.end_ns)
    return [r._replace(seq=i) for i, r in enumerate(ordered)]


def fake_run(traced_trees=None):
    said = []
    spans = {} if traced_trees is None else {"traced_trees": traced_trees}
    return SimpleNamespace(window_start=WINDOW, traffic={}, spans=spans,
                           say=said.append, said=said)


def read(name, run):
    return load_module(BENCH / "layers" / f"{name}.py").read(run)


def setup(hbm=HBM, second_program=False):
    """Set-up as the program records it: the ship, 3 eager programs, the
    fused iteration (13 s), one program from the cache; a sampled job's
    second fused program (45 s) on request."""
    def compiled(start, dur, entry, trace, cache="miss", peak=None, **more):
        fields = dict(hbm, hbm_peak_bytes=peak) if hbm and peak else hbm
        return rec("Runtime::Compile", start, dur, entry=entry, trace=trace,
                   cache=cache, trace_ns=2 * S, lower_ns=S, **fields, **more)
    out = [rec("Dataset::Ship", 20.0, 3.0, rows=1000, groups=28, **hbm),
           compiled(24.0, 0.5, None, None),
           compiled(25.0, 0.25, None, None),
           compiled(26.0, 0.25, "route_and_hist", 1),
           compiled(30.0, 13.0, "fused_iter", 1, peak=6_834_000_000),
           compiled(44.0, 0.125, None, None, cache="hit")]
    if second_program:
        out.append(compiled(50.0, 45.0, "fused_iter", 2,
                            peak=6_900_000_000,
                            signature="(float32[8], int32[])"))
    return out


def window(ends, passes, hbm=HBM, first_iteration=16, every=16,
           poll_s=0.25, in_use=None):
    """Polls that END at `ends` (seconds after the window's start), the
    cumulative `passes` at each, and for every poll but the last a launch
    that starts 2 ms after it and takes 3 ms."""
    out = []
    for i, (end, seen) in enumerate(zip(ends, passes)):
        fields = dict(hbm)
        if hbm and in_use:
            fields["hbm_in_use_bytes"] = in_use[i]
        out.append(rec("GBDT::FlagPoll", WINDOW + end - poll_s, poll_s,
                       parent="GBDT::Iteration",
                       iteration=first_iteration + every * i,
                       hist_passes=seen, **fields))
        if i + 1 < len(ends):
            out.append(rec("GBDT::FusedIter", WINDOW + end + 0.002, 0.003,
                           parent="GBDT::Iteration"))
    return out


@pytest.fixture
def ring(monkeypatch):
    def put(records, overwritten=0):
        monkeypatch.setattr(program_spans, "ring",
                            lambda: (numbered(records), overwritten))
    return put


# ---------------------------------------------------------------- arithmetic
def test_intervals_run_from_one_polls_end_to_the_next():
    polls = [r for r in window([8, 16, 24.5, 32], [128, 256, 384, 528])
             if r.name == poll_timeline.POLL]
    ivs = poll_timeline.intervals(polls)
    assert [(iv.iteration, iv.trees, iv.passes) for iv in ivs] == [
        (32, 16, 128), (48, 16, 128), (64, 16, 144)]
    assert [iv.ns for iv in ivs] == [8 * S, 8.5 * S, 7.5 * S]
    assert poll_timeline.ms_per_tree(ivs) == [500.0, 531.25, 468.75]
    assert poll_timeline.ms_per_pass(ivs)[0] == 62.5
    # an interval that began before the given moment is left out
    late = poll_timeline.intervals(polls, round((WINDOW + 8.001) * S))
    assert [iv.iteration for iv in late] == [48, 64]
    assert "to 48: 16 trees, 531.250 ms/tree" in poll_timeline.tree_series(ivs)
    assert "to 48: 128 passes, 66.406 ms/pass" in poll_timeline.pass_series(ivs)


def test_spread_is_range_over_median_and_needs_two_values():
    assert poll_timeline.spread_pct([500.0, 525.0, 500.0]) == 5.0
    assert poll_timeline.spread_pct([500.0, 510.0]) == pytest.approx(
        100 * 10 / 505)
    assert poll_timeline.spread_pct([500.0]) is None
    assert poll_timeline.spread_pct([]) is None


def test_gap_is_to_the_end_of_the_first_launch_that_starts_after_the_poll():
    recs = window([8, 16, 24], [128, 256, 384])
    polls = [r for r in recs if r.name == poll_timeline.POLL]
    launches = [r for r in recs if r.name == poll_timeline.LAUNCH]
    # a launch that started before the poll ended is not its next dispatch
    launches.append(rec("GBDT::FusedIter", WINDOW + 7.9, 0.05))
    assert poll_timeline.gaps(polls, launches) == [(16, 5_000_000),
                                                  (32, 5_000_000)]
    assert poll_timeline.gaps(polls[-1:], launches) == []


# ------------------------------------------------------------ the seven, read
def test_known_intervals(ring):
    ring(setup() + window([8, 16, 24, 32], [128, 256, 384, 512],
                          in_use=[5_000_000_000, 5_100_000_000,
                                  5_050_000_000, 5_000_000_000]))
    run = fake_run()
    assert read("program_compile_s", run) == 0.5 + 0.25 + 0.25 + 13.0
    assert read("iter_compile_s", run) == 13.0
    assert read("setup_peak_hbm_gb", run) == 6.834
    assert read("train_hbm_gb", run) == 5.1
    assert read("poll_tree_ms_spread_pct", run) == 0.0
    assert read("poll_pass_ms_spread_pct", run) == 0.0
    # three polls have a next dispatch, 5 ms each, over the window's launches
    assert read("poll_gap_ms_per_tree", run) == pytest.approx(15.0 / 3)
    said = "\n".join(run.said)
    assert "fused_iter 1, 13.000, 2.000, 1.000" in said      # who cost what
    assert "no entry (eager) 2, 0.750" in said
    assert "4 programs compiled, 1 fetched from the cache" in said
    assert "Dataset::Ship 5.000000 / 6.800000" in said
    assert "at 32: 5.100000 / 6.800000" in said
    assert "to 48: 16 trees, 500.000 ms/tree" in said
    assert "to 48: 128 passes, 62.500 ms/pass" in said
    assert "after 16: 5.000" in said


def test_one_slow_interval_shows_in_both_spreads_and_in_the_log(ring):
    ring(setup() + window([8, 16, 24.4, 32.4], [128, 256, 384, 512]))
    run = fake_run()
    assert read("poll_tree_ms_spread_pct", run) == pytest.approx(5.0)
    assert read("poll_pass_ms_spread_pct", run) == pytest.approx(5.0)
    assert any("to 48: 16 trees, 525.000 ms/tree" in line
               for line in run.said)
    assert any("to 48: 128 passes, 65.625 ms/pass" in line
               for line in run.said)


def test_one_nine_pass_interval_is_work_not_noise(ring):
    # the third interval's trees take nine passes and nine eighths the time
    ring(setup() + window([8, 16, 25, 33], [128, 256, 400, 528]))
    run = fake_run()
    assert read("poll_tree_ms_spread_pct", run) == pytest.approx(12.5)
    assert read("poll_pass_ms_spread_pct", run) == pytest.approx(0.0)
    assert any("to 48: 16 trees, 562.500 ms/tree" in line
               for line in run.said)
    assert any("to 48: 144 passes, 62.500 ms/pass" in line
               for line in run.said)


def test_a_sampled_job_pays_for_two_fused_programs(ring):
    ring(setup(second_program=True)
         + window([8, 16, 24], [128, 256, 384]))
    run = fake_run()
    assert read("iter_compile_s", run) == 13.0 + 45.0
    assert read("program_compile_s", run) == 14.0 + 45.0
    assert read("setup_peak_hbm_gb", run) == 6.834      # the FIRST program's
    assert any("trace 2: backend 45.000 s" in line
               and "signature (float32[8], int32[])" in line
               for line in run.said)


def test_a_warm_cache_reads_zero_not_none(ring):
    warm = [r._replace(args=dict(r.args, cache="hit")) if r.name
            == poll_timeline.COMPILE else r for r in setup()]
    ring(warm + window([8, 16, 24], [128, 256, 384]))
    run = fake_run()
    assert read("program_compile_s", run) == 0.0
    assert read("iter_compile_s", run) == 0.0
    assert read("setup_peak_hbm_gb", run) == 6.834


def test_the_interval_that_holds_the_profilers_stop_is_left_out(ring):
    """higgs_goss_train's traced run: 12 warm-up trees, the poll at 16
    inside the five profiled trees, then the drain and the profiler's stop
    (4 s here) inside the interval to 32."""
    steps = [rec("GBDT::Iteration", WINDOW + 0.3 * i, 0.29, step_num=13 + i)
             for i in range(5)]
    traced = [rec("GBDT::FusedIter", WINDOW + 0.3 * i + 0.01, 0.2,
                  parent="GBDT::Iteration") for i in range(4)]
    polls = window([1.19, 10.2, 15.2, 20.2, 25.2], [128, 256, 384, 512, 640])
    ring(setup() + steps + traced + polls)
    run = fake_run(traced_trees=5)
    assert poll_timeline.traced_end_ns(run) == round((WINDOW + 1.49) * S)
    assert [iv.iteration for iv in poll_timeline.window_intervals(run)] \
        == [48, 64, 80]
    assert read("poll_tree_ms_spread_pct", run) == pytest.approx(0.0)
    # the profiled poll's gap is in the log and not in the metric: three
    # gaps of 5 ms over the three launches after the profiled stretch
    assert read("poll_gap_ms_per_tree", run) == pytest.approx(15.0 / 3)
    assert read("poll_gap_ms_per_tree", fake_run()) == pytest.approx(
        20.0 / 8)
    assert any("after 16: 5.000; after 32: 5.000" in line
               for line in run.said)
    # untraced, the same ring counts the long interval too
    assert [iv.iteration for iv in
            poll_timeline.window_intervals(fake_run())] == [32, 48, 64, 80]


@pytest.mark.parametrize("name", READERS)
def test_two_polls_only_is_a_rehearsal(ring, name):
    ring(setup() + window([8, 16], [128, 256]))
    run = fake_run()
    assert read(name, run) is None and run.said == []


@pytest.mark.parametrize("name", READERS)
def test_an_overwritten_ring_is_none_and_loud(ring, name):
    recs = numbered(setup() + window([8, 16, 24, 32], [128, 256, 384, 512]))
    # the oldest record left ended inside the window: some of it is lost
    lost = [r for r in recs if poll_timeline.end_ns(r) > (WINDOW + 9) * S]
    ring(lost, overwritten=len(recs) - len(lost))
    run = fake_run()
    assert read(name, run) is None
    assert run.said and "OVERWROTE" in run.said[0]


@pytest.mark.parametrize("name", READERS)
def test_the_parents_record_shape_is_none(ring, name):
    """A commit from before the fields: polls with `iteration` and
    `hist_passes` alone, a ship without HBM, no `Runtime::Compile`."""
    parent = [r for r in setup(hbm={}) if r.name != poll_timeline.COMPILE]
    ring(parent + window([8, 16, 24, 32], [128, 256, 384, 512], hbm={}))
    run = fake_run()
    assert read(name, run) is None and run.said == []


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_a_ring_is_none(monkeypatch, name):
    monkeypatch.setattr(program_spans, "ring", lambda: None)
    assert read(name, fake_run()) is None


@pytest.mark.parametrize("name", ("program_compile_s", "iter_compile_s",
                                  "setup_peak_hbm_gb"))
def test_compile_records_of_a_cpu_are_not_read(ring, name):
    """XLA:CPU keeps no allocator statistics: the records are there, the HBM
    fields are not, and a rehearsal gets no number under a device's name."""
    ring(setup(hbm={}) + window([8, 16, 24], [128, 256, 384], hbm={}))
    assert read(name, fake_run()) is None


def test_the_seven_are_appended_for_all_five_cells(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-len(READERS):] == list(READERS)
    for name in READERS:
        assert by_name[name]["workloads"] == cells
        assert by_name[name]["better"] == "lower"
    assert {by_name[n]["moves"] for n in READERS[:2]} == {"setup_s"}
    assert {by_name[n]["moves"] for n in READERS[2:4]} == {"peak_hbm_gb"}
    assert {by_name[n]["moves"] for n in READERS[4:]} == {"train_s_per_tree"}
