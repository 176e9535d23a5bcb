"""Layer-boundary spans (telemetry/tracer.py SpanTracer.boundary): always
live, written to the profiler's trace and to a bounded in-process ring at
once; the device-side histogram-pass counter; what went with the second
accumulator (utils/timer.py); and the records the host writes where it
already stands still: `Runtime::Compile` for every program compiled or
fetched, and the one HBM reading on the ship, the bind, the compile and the
flag poll."""
import glob
import os
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry as tel
from lightgbm_tpu.telemetry import tracer as tracer_mod

from conftest import make_synthetic_binary, make_synthetic_multiclass

FIX = Path(__file__).parent / "fixtures"
STREAM = {"verbosity": -1, "hist_backend": "stream", "num_leaves": 15,
          "min_data_in_leaf": 5, "max_bin": 63, "learning_rate": 0.1}


@pytest.fixture(autouse=True)
def _clean():
    tel.disable()
    tel.reset()
    tel.reset_counters()
    yield
    tel.disable()
    tel.reset()


@pytest.fixture
def fused(monkeypatch):
    """The chip's default iteration (one launch, flags polled every
    eval_fetch_freq trees), asked for on the CPU with the program's switch."""
    monkeypatch.setenv("LGBTPU_FUSE_ITER", "1")


def _booster(params, X, y):
    return lgb.Booster(dict(params), lgb.Dataset(X, label=y))


def _names(records):
    return [r.name for r in records]


def _layer_spans():
    """The ring without the `Runtime::Compile` records: whether a call
    compiles depends on what the process has run before it."""
    return [r for r in tel.recent_spans() if r.name != "Runtime::Compile"]


# ------------------------------------------------------------------ the tracer
def test_span_disabled_is_still_the_shared_null_span_and_boundary_is_live():
    assert not tel.enabled()
    assert tel.span("x") is tracer_mod._NULL_SPAN
    with tel.boundary("Layer::Outer", rows=3) as outer:
        with tel.boundary("Layer::Inner"):
            pass
        outer.set(path="device")
    inner, outer = tel.recent_spans()
    assert (inner.name, inner.parent) == ("Layer::Inner", "Layer::Outer")
    assert (outer.name, outer.parent) == ("Layer::Outer", None)
    assert outer.args == {"rows": 3, "path": "device"} and inner.args is None
    assert (inner.seq, outer.seq) == (0, 1)
    assert outer.start_unix_ns <= inner.start_unix_ns
    assert outer.duration_ns >= inner.duration_ns > 0
    # ... and in the phase totals, though telemetry is off and no Chrome
    # event was buffered
    assert tel.global_tracer.phase_counts() == {"Layer::Inner": 1,
                                                "Layer::Outer": 1}
    assert tel.global_tracer.events == []
    assert tel.recent_spans(name="Layer::Inner") == [inner]
    assert tel.recent_spans(since_unix_ns=outer.start_unix_ns + 10**12) == []


def test_boundary_emits_chrome_events_when_telemetry_is_on():
    tel.enable()
    with tel.boundary("Layer::X", k=2) as sp:
        sp.set(reason="r")
    ev = [(e["name"], e["ph"]) for e in tel.global_tracer.events]
    assert ev == [("Layer::X", "B"), ("Layer::X", "E")]
    assert tel.global_tracer.events[0]["args"] == {"k": 2}
    assert tel.global_tracer.events[1]["args"] == {"k": 2, "reason": "r"}
    assert _names(tel.recent_spans()) == ["Layer::X"]


def test_ring_is_bounded_and_counts_what_it_overwrote(monkeypatch):
    monkeypatch.setattr(tracer_mod, "_RING_SIZE", 8)
    tr = tracer_mod.SpanTracer()
    for i in range(20):
        with tr.boundary("S", i=i):
            pass
    recs = tr.recent_spans()
    assert len(recs) == 8 and tr.ring_overwritten == 12
    assert [r.seq for r in recs] == list(range(12, 20))
    assert [r.args["i"] for r in recs] == list(range(12, 20))
    assert tr.phase_counts()["S"] == 20          # totals outlive the ring
    tr.reset()
    assert tr.recent_spans() == [] and tr.ring_overwritten == 0
    s = tel.summary()
    assert s["recent_spans"] == [] and s["recent_spans_overwritten"] == 0
    assert s["hist_passes"] == {"count": 0, "small": 0, "scan_slots": 0,
                                "iteration": 0}


def test_parents_are_kept_per_thread():
    seen = {}

    def worker():
        with tel.boundary("T::Child"):
            pass
        seen["done"] = True

    with tel.boundary("T::Main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
    assert seen.get("done") and not t.is_alive()
    child = tel.recent_spans(name="T::Child")[0]
    assert child.parent is None      # not the other thread's open span


def test_an_exception_closes_the_span_and_the_stack():
    with pytest.raises(ValueError):
        with tel.boundary("E::Outer"):
            with tel.boundary("E::Inner"):
                raise ValueError("x")
    with tel.boundary("E::Next"):
        pass
    assert [(r.name, r.parent) for r in tel.recent_spans()] == [
        ("E::Inner", "E::Outer"), ("E::Outer", None), ("E::Next", None)]


# --------------------------------------------------------------- the training
def test_profiler_trace_holds_the_iteration_spans_nested_and_in_step_order(
        fused, tmp_path):
    X, y = make_synthetic_binary(n=1500, f=6)
    bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=2), X, y)
    bst.update()                              # compile outside the trace
    tel.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            bst.update()
        jax.block_until_ready(bst.engine.score)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    data = jax.profiler.ProfileData.from_file(files[0])
    ours = []
    for plane in data.planes:
        assert not plane.name.startswith("/device:")    # a CPU trace
        for line in plane.lines:
            ours += [(e.name, int(e.start_ns), int(e.duration_ns),
                      dict(e.stats)) for e in line.events
                     if e.name.startswith("lgbtpu.")]
    steps = [e for e in ours if e[0] == "lgbtpu.GBDT::Iteration"]
    fused_ = [e for e in ours if e[0] == "lgbtpu.GBDT::FusedIter"]
    polls = [e for e in ours if e[0] == "lgbtpu.GBDT::FlagPoll"]
    assert [e[3]["step_num"] for e in steps] == [2, 3, 4]
    assert [e[1] for e in steps] == sorted(e[1] for e in steps)
    assert len(fused_) == 3 and [e[3]["iteration"] for e in polls] == [2, 4]
    for step, f in zip(steps, fused_):        # nested, on one clock
        assert step[1] <= f[1] and f[1] + f[2] <= step[1] + step[2]
    for p in polls:
        assert any(s[1] <= p[1] and p[1] + p[2] <= s[1] + s[2]
                   for s in steps)
    # the ring holds the same spans, with parents, telemetry off
    assert not tel.enabled()
    ring = tel.recent_spans()
    assert _names(ring) == [
        "GBDT::FusedIter", "GBDT::FlagPoll", "GBDT::Iteration",
        "GBDT::FusedIter", "GBDT::Iteration",
        "GBDT::FusedIter", "GBDT::FlagPoll", "GBDT::Iteration"]
    assert all(r.parent == "GBDT::Iteration" for r in ring
               if r.name != "GBDT::Iteration")
    assert [r.args["step_num"] for r in ring
            if r.name == "GBDT::Iteration"] == [2, 3, 4]


def test_flag_poll_once_per_fetch_and_no_new_host_sync(fused):
    X, y = make_synthetic_binary(n=1500, f=6)
    bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=4), X, y)
    bst.update()            # the first iteration also launches set-up work
    tel.reset()
    syncs0, launches0 = tel.host_sync_count(), tel.launch_count()
    for _ in range(8):
        bst.update()
    assert bst.engine._fused_last
    polls = tel.recent_spans(name="GBDT::FlagPoll")
    assert [p.args["iteration"] for p in polls] == [4, 8]
    # one blocking read per poll and one launch per iteration, as before
    assert tel.host_sync_count() - syncs0 == 2
    assert tel.launch_count() - launches0 == 8
    assert len(tel.recent_spans(name="GBDT::Iteration")) == 8
    assert len(tel.recent_spans(name="GBDT::FusedIter")) == 8
    # telemetry on keeps the spans and still syncs at the polls only
    tel.enable()
    syncs0 = tel.host_sync_count()
    for _ in range(4):
        bst.update()
    assert tel.host_sync_count() - syncs0 == 2     # the poll + its record
    assert len(tel.recent_spans(name="GBDT::FlagPoll")) == 3
    assert len(tel.recent_spans(name="GBDT::FusedIter")) == 12


def test_eager_iteration_polls_under_the_same_span(monkeypatch):
    monkeypatch.setenv("LGBTPU_FUSE_ITER", "0")
    X, y = make_synthetic_binary(n=800, f=5)
    bst = _booster({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                   X, y)
    for _ in range(3):
        bst.update()
    assert not bst.engine._fused_last
    names = _names(_layer_spans())
    assert names.count("GBDT::Iteration") == 3
    assert names.count("GBDT::FlagPoll") == 3
    assert names.count("GBDT::TrainTree") == 3
    assert "GBDT::FusedIter" not in names
    assert tel.hist_pass_count() == 0       # counted on the fused path only


def _depth_rounds(tree):
    """Rounds a full tree took when every round may split every leaf: its
    depth (leaves at most double a round)."""
    def depth(node, d=0):
        if "leaf_index" in node or "leaf_value" in node:
            return d
        return max(depth(node["left_child"], d + 1),
                   depth(node["right_child"], d + 1))
    return depth(tree["tree_structure"])


def test_hist_pass_count_is_what_grow_tree_took(fused):
    """A toy where the passes are known: 8 equal cells of 3 binary features,
    a distinct target in each, 8 leaves.  Best-first, one split a round,
    takes root + 7 passes a tree; the stream backend's budget of 64 splits
    a round takes root + depth 3."""
    cells = np.array([[a, b, c] for a in (0, 1) for b in (0, 1)
                      for c in (0, 1)], np.float64)
    X = np.repeat(cells, 64, axis=0)
    y = X @ np.array([4.0, 2.0, 1.0])
    base = {"objective": "regression", "num_leaves": 8, "verbosity": -1,
            "min_data_in_leaf": 1, "eval_fetch_freq": 2,
            "hist_backend": "stream"}
    for extra, per_tree in (({"max_splits_per_round": 1}, 8), ({}, 4)):
        tel.reset_counters()
        bst = _booster(dict(base, **extra), X, y)
        for _ in range(4):
            bst.update()
        assert bst.engine._fused_last
        trees = bst.dump_model()["tree_info"]
        assert [t["num_leaves"] for t in trees] == [8] * 4
        assert [_depth_rounds(t) for t in trees] == [3] * 4
        assert tel.hist_pass_count() == 4 * per_tree, extra
        assert tel.hist_pass_iteration() == 4
        polls = tel.recent_spans(name="GBDT::FlagPoll")[-2:]
        assert [p.args["hist_passes"] for p in polls] == [2 * per_tree,
                                                          4 * per_tree]
        # float weights: the root goes through the 64-slot kernel's S = 1
        # call, and the poll says so
        assert [p.args["root_pass"] for p in polls] == ["onehot"] * 2


def test_flag_poll_names_the_factored_root_and_counts_it_once(fused):
    """use_quantized_grad over u8-layout bins: the root histogram is the
    factored contraction, the poll record says so, and the root still counts
    as ONE pass over the rows — hist_passes reads what it read before."""
    X, y = make_synthetic_binary(n=1200, f=6)
    counts = {}
    for quant in (False, True):
        tel.reset_counters()
        bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=2,
                            use_quantized_grad=quant,
                            max_splits_per_round=1, num_leaves=4), X, y)
        for _ in range(2):
            bst.update()
        assert bst.engine._fused_last
        assert [t["num_leaves"] for t in
                bst.dump_model()["tree_info"]] == [4, 4]
        poll = tel.recent_spans(name="GBDT::FlagPoll")[-1]
        assert poll.args["root_pass"] == ("factored" if quant else "onehot")
        counts[quant] = poll.args["hist_passes"]
    # root + 3 one-split rounds a tree on either path
    assert counts[True] == counts[False] == 2 * 4


def test_hist_pass_count_is_what_grow_tree_k_took(fused):
    """The lockstep multiclass grower: one pass serves all K classes, so a
    round counts once.  7-leaf trees: best-first, one split a round, is
    root + 6; the budget of 64 is root + 3 rounds (2, 4, 7 leaves)."""
    X, y = make_synthetic_multiclass(n=900, f=6, k=3)
    for extra, per_iter in (({"max_splits_per_round": 1}, 7), ({}, 4)):
        tel.reset_counters()
        bst = _booster({"objective": "multiclass", "num_class": 3,
                        "num_leaves": 7, "min_data_in_leaf": 5,
                        "verbosity": -1, "hist_backend": "stream",
                        "eval_fetch_freq": 3, **extra}, X, y)
        for _ in range(3):
            bst.update()
        assert bst.engine._fused_last and bst.engine._mc_batched_last
        trees = bst.dump_model()["tree_info"]
        assert [t["num_leaves"] for t in trees] == [7] * 9
        assert tel.hist_pass_count() == 3 * per_iter, extra
        assert tel.hist_pass_iteration() == 3


def test_counter_restarts_with_a_rebuilt_state_and_resets(fused):
    X, y = make_synthetic_binary(n=1200, f=6)
    bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=2), X, y)
    for _ in range(2):
        bst.update()
    first = tel.hist_pass_count()
    assert first > 0
    # score surgery (what a checkpoint restore or a rollback does) rebuilds
    # the state: the device count starts at 0 again, the published one
    # only ever grows
    bst.engine.score = bst.engine.score + 0.0
    for _ in range(2):
        bst.update()
    assert tel.hist_pass_count() > first
    assert tel.hist_pass_iteration() == 4
    tel.reset_counters()
    assert (tel.hist_pass_count(), tel.hist_pass_iteration()) == (0, 0)


@pytest.mark.parametrize("name", ["binary", "multiclass", "binary_int"])
def test_fused_trees_are_byte_identical_to_the_parents(fused, name):
    """tests/fixtures/fused_parent_*.model were written by the commit before
    the pass counter entered the growers' loop state and the fused state;
    fused_parent_binary_int.model (use_quantized_grad: the int8 path, whose
    root pass is the factored contraction since) by the commit before that
    contraction — its int32 sums are exact, so the trees may not move."""
    if name.startswith("binary"):
        X, y = make_synthetic_binary(n=2000, f=8)
        quant = name == "binary_int"
        bst = lgb.train(dict(STREAM, objective="binary",
                             use_quantized_grad=quant),
                        lgb.Dataset(X, label=y), num_boost_round=5)
        assert bst.engine._root_pass == ("factored" if quant else "onehot")
    else:
        X, y = make_synthetic_multiclass(n=1500, f=8, k=3)
        bst = lgb.train(dict(STREAM, objective="multiclass", num_class=3),
                        lgb.Dataset(X, label=y), num_boost_round=4)
        assert bst.engine._mc_batched_last
    assert bst.engine._fused_last
    # the rounds that split one and two leaves take the small-slot pass on
    # the int8 path (two a 15-leaf tree here), and on no other
    poll = tel.recent_spans(name="GBDT::FlagPoll")[-1]
    assert poll.args["iteration"] == bst.current_iteration()
    assert poll.args["hist_small_passes"] == (
        2 * 5 if name == "binary_int" else 0)
    assert poll.args["hist_small_passes"] == tel.hist_small_pass_count()
    got = bst.model_to_string().split("\nparameters:")[0]
    want = (FIX / f"fused_parent_{name}.model").read_text()
    if got != want:
        # integer structure to the byte; floats to the last digits XLA:CPU
        # may round differently on another host
        for a, b in zip(got.splitlines(), want.splitlines(), strict=True):
            ka, _, va = a.partition("=")
            kb, _, vb = b.partition("=")
            assert ka == kb
            if a == b or ka == "tree_sizes":
                continue
            np.testing.assert_allclose(
                [float(t) for t in va.split()],
                [float(t) for t in vb.split()], rtol=1e-6, atol=1e-9,
                err_msg=ka)


# ------------------------------------------------------- Dataset and predict
def test_dataset_construct_and_ship_leave_their_three_records():
    X, y = make_synthetic_binary(n=900, f=5)
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    find, fill = _layer_spans()
    assert (find.name, fill.name) == ("Dataset::FindBins", "Dataset::Bin")
    assert find.args == {"rows": 900} and find.parent is None
    assert find.start_unix_ns + find.duration_ns <= fill.start_unix_ns + 10**6
    ds.construct()                                  # built: nothing more
    assert len(_layer_spans()) == 2
    dd = ds.device_data()
    ship = tel.recent_spans(name="Dataset::Ship")
    assert len(ship) == 1 and ship[0].args == {"rows": 900, "groups": 5}
    assert dd.bins.shape[1] == 5
    # a validation set binned with the training mappers: Bin alone
    tel.reset()
    lgb.Dataset(X[:100], label=y[:100], reference=ds).construct()
    assert _names(_layer_spans()) == ["Dataset::Bin"]


@pytest.fixture
def trained():
    X, y = make_synthetic_binary(n=1500, f=6)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                    lgb.Dataset(X, label=y), num_boost_round=4)
    bst.engine.models                                # finalize now
    tel.reset()
    return bst, X


def test_predict_on_the_host_says_why(trained):
    bst, X = trained
    want = bst.predict(X, raw_score=True)
    walk, call = _layer_spans()
    assert (walk.name, walk.parent) == ("Predict::HostWalk", "Predict")
    assert call.name == "Predict" and call.parent is None
    assert call.args == {"rows": 1500, "trees": 4, "path": "host",
                         "reason": "1500 rows < 20000"}
    assert bst.last_predict_path == "host (1500 rows < 20000)"
    # a model loaded from a file has no engine to bin with
    tel.reset()
    loaded = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_array_equal(loaded.predict(X, raw_score=True), want)
    call = tel.recent_spans(name="Predict")[0]
    assert call.args["path"] == "host"
    assert call.args["reason"].startswith("no training engine")
    # the single-row fast path is a per-row loop's body: no span
    tel.reset()
    bst.predict(X[:1])
    assert _layer_spans() == []


def test_predict_on_the_device_leaves_all_six_children(trained, monkeypatch):
    bst, X = trained
    want = bst.predict(X, raw_score=True)
    tel.reset()
    monkeypatch.setattr(lgb.Booster, "_DEVICE_PREDICT_OFF_CHIP", True)
    monkeypatch.setattr(lgb.Booster, "_DEVICE_PREDICT_MIN_ROWS", 10)
    got = bst.predict(X, raw_score=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert bst.last_predict_path == "device"
    ring = _layer_spans()
    assert _names(ring) == [
        "Predict::RoutingTables", "Predict::Rebin", "Predict::PackShip",
        "Predict::NodeTables", "Predict::Walk", "Predict::Readback",
        "Predict"]
    assert all(r.parent == "Predict" for r in ring[:-1])
    assert ring[-1].args == {"rows": 1500, "trees": 4, "path": "device",
                             "reason": ""}
    assert sum(r.duration_ns for r in ring[:-1]) <= ring[-1].duration_ns


# ------------------------------------------------- Runtime::Compile records
def _compiles(entry="any"):
    return [r for r in tel.recent_spans(name="Runtime::Compile")
            if entry == "any" or r.args["entry"] == entry]


def test_a_watched_entry_leaves_one_compile_record_a_trace():
    import jax.numpy as jnp
    f = tel.watched_jit(lambda x: x * 2 + 1, name="doubler")
    x4, x5 = jnp.ones(4), jnp.ones(5)       # their own eager programs first
    tel.reset()
    f(x4)
    (first,) = _compiles()
    assert first.args["entry"] == "doubler" and first.args["trace"] == 1
    assert first.args["cache"] in ("miss", "hit") and first.parent is None
    assert first.duration_ns > 0 and first.args["trace_ns"] > 0 \
        and first.args["lower_ns"] > 0
    assert "signature" not in first.args
    # the record is retroactive: it starts its own duration ago
    assert first.start_unix_ns + first.duration_ns <= time.time_ns()
    f(x4)                                    # the same shapes: no compile
    assert len(_compiles()) == 1
    f(x5)                                    # another shape: trace 2, and why
    second = _compiles()[-1]
    assert len(_compiles()) == 2 and second.args["trace"] == 2
    assert second.args["signature"] == "(float32[5])"
    # XLA:CPU keeps no allocator statistics: no HBM field, nothing raised
    assert not {"hbm_in_use_bytes", "hbm_peak_bytes"} & set(second.args)
    # nothing but the ring: no Chrome event, but a phase total like any span
    assert tel.global_tracer.events == []
    assert tel.global_tracer.phase_counts()["Runtime::Compile"] == 2


def test_a_program_no_entry_asked_for_has_no_entry():
    import jax.numpy as jnp
    x = jnp.ones(7)
    tel.reset()
    with tel.boundary("Layer::SetUp"):
        jax.jit(lambda v: v - 3)(x)          # a plain jit
        jnp.cumsum(x)                        # an eager op: its own program
    plain, eager = _compiles()
    assert plain.args["entry"] is None and plain.args["trace"] is None
    assert eager.args["entry"] is None
    # the parent is the boundary open on the compiling thread
    assert plain.parent == eager.parent == "Layer::SetUp"


def test_the_outermost_entry_owns_the_program():
    import jax.numpy as jnp
    inner = tel.watched_jit(lambda x: x + 1, name="inner_entry")

    def body(x):
        # an eager program in the middle of a trace is nobody's entry
        return inner(x) * jnp.asarray(np.arange(6.0)).sum()
    outer = tel.watched_jit(body, name="outer_entry")
    x6, x9 = jnp.ones(6), jnp.ones(9)
    tel.reset()
    outer(x6)
    assert [r.args["entry"] for r in _compiles()][-1] == "outer_entry"
    assert _compiles("inner_entry") == []    # inlined: no program of its own
    assert all(r.args["entry"] is None for r in _compiles()[:-1])
    # an unwatched jit that inlines an entry compiles its own program
    tel.reset()
    jax.jit(lambda x: inner(x) * 3)(x9)
    (rec,) = _compiles()
    assert rec.args["entry"] is None
    # a trace that raises leaves no entry behind for the next program
    bad = tel.watched_jit(lambda x: x.nope, name="bad_entry")
    with pytest.raises(AttributeError):
        bad(x6)
    tel.reset()
    jax.jit(lambda x: x * 5)(x6)
    assert [r.args["entry"] for r in _compiles()] == [None]


def test_the_aot_path_leaves_the_same_record():
    import jax.numpy as jnp
    f = tel.watched_jit(lambda x: jnp.sin(x), name="aot_entry")
    x = jnp.ones(3)
    tel.reset()
    lowered = f.lower(x)
    assert _compiles() == []                 # lowered, nothing compiled yet
    jax.jit(lambda v: v * 7)(x)              # another program in between
    compiled = lowered.compile()
    other, rec = _compiles()
    assert other.args["entry"] is None
    assert rec.args["entry"] == "aot_entry" and rec.args["trace"] == 1
    assert rec.args["trace_ns"] > 0 and rec.args["lower_ns"] > 0
    np.testing.assert_allclose(compiled(x), np.sin(np.ones(3)), rtol=1e-6)
    # the same signature again: the watchdog counts it, jax finds the
    # executable in memory and compiles nothing - no record, and the entry
    # does not wait for the next program of the thread
    f.lower(x).compile()
    assert tel.recompile_counts()["aot_entry"] == 2
    assert len(_compiles("aot_entry")) == 1
    jax.jit(lambda v: v * 9)(x)
    assert _compiles()[-1].args["entry"] is None


def test_compile_records_are_kept_per_thread():
    import jax.numpy as jnp
    f = tel.watched_jit(lambda x: x * 11, name="threaded_entry")
    x = jnp.ones(13)
    tel.reset()

    def worker():
        with tel.boundary("T::Worker"):
            f(x)

    with tel.boundary("T::Main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=60)
        jax.jit(lambda v: v * 13)(x)
    assert not t.is_alive()
    theirs, mine = _compiles()
    assert (theirs.args["entry"], theirs.parent) == ("threaded_entry",
                                                     "T::Worker")
    assert (mine.args["entry"], mine.parent) == (None, "T::Main")


def test_the_fused_iteration_names_its_compile(fused):
    X, y = make_synthetic_binary(n=1500, f=6)
    bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=2), X, y)
    tel.reset()
    bst.update()
    iters = _compiles("fused_iter")
    assert len(iters) == 1 and iters[0].args["trace"] == 1
    assert iters[0].parent == "GBDT::FusedIter"
    # the kernels it inlines are entries too and compile nothing themselves
    assert _compiles("route_and_hist") == []
    n = len(_compiles())
    bst.update()                             # steady state: no record more
    assert len(_compiles()) == n


# ----------------------------------------------------------- the HBM reading
@pytest.fixture
def two_devices_report(monkeypatch):
    """`memory_stats` stubbed: device 0 holds less and peaked higher,
    device 1 holds more, the others keep no statistics."""
    stats = {0: {"bytes_in_use": 5_000_000_000,
                 "peak_bytes_in_use": 8_439_998_464},
             1: {"bytes_in_use": 5_100_000_000,
                 "peak_bytes_in_use": 8_439_344_640}}
    monkeypatch.setattr(type(jax.local_devices()[0]), "memory_stats",
                        lambda self: stats.get(self.id))
    return {"hbm_in_use_bytes": 5_100_000_000,
            "hbm_peak_bytes": 8_439_998_464}


def test_the_hbm_reading_is_the_fullest_device_in_bytes(two_devices_report):
    from lightgbm_tpu.telemetry import metrics
    assert tel.device_hbm_bytes() == two_devices_report
    # GB of 1e9, the benchmark's unit - 7.8604 under 2**30
    assert tel.device_memory_gb() == {"peak_hbm_gb": 8.44}
    assert tel.memory_snapshot()["peak_hbm_gb"] == 8.44
    assert tel.summary()["memory"]["peak_hbm_gb"] == 8.44
    assert metrics.device_memory_gb() == {"peak_hbm_gb": 8.44}


def test_without_allocator_statistics_the_fields_are_absent():
    assert jax.local_devices()[0].memory_stats() is None     # XLA:CPU
    assert tel.device_hbm_bytes() == {} and tel.device_memory_gb() == {}
    assert "peak_hbm_gb" not in tel.memory_snapshot()
    X, y = make_synthetic_binary(n=900, f=5)
    lgb.Dataset(X, label=y).construct().device_data()
    (ship,) = tel.recent_spans(name="Dataset::Ship")
    assert ship.args == {"rows": 900, "groups": 5}


def test_ship_poll_and_compile_carry_the_reading(fused, two_devices_report):
    X, y = make_synthetic_binary(n=1500, f=6)
    bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=2), X, y)
    for _ in range(4):
        bst.update()
    (ship,) = tel.recent_spans(name="Dataset::Ship")
    polls = tel.recent_spans(name="GBDT::FlagPoll")
    assert [p.args["iteration"] for p in polls] == [2, 4]
    for rec in (ship, *polls, *_compiles()):
        assert {k: rec.args[k] for k in two_devices_report} \
            == two_devices_report, rec.name
    assert _compiles("fused_iter")
    # per iteration and round a dispatch: nothing
    for name in ("GBDT::Iteration", "GBDT::FusedIter"):
        assert all(not {"hbm_in_use_bytes", "hbm_peak_bytes"}
                   & set(r.args or {}) for r in tel.recent_spans(name=name))


def test_the_poll_reads_memory_before_it_blocks(fused, monkeypatch):
    """The reading is taken while the device still works: before the
    poll's `device_get`, never after it."""
    from lightgbm_tpu.models import gbdt
    order = []
    real_get = jax.device_get
    monkeypatch.setattr(gbdt, "device_hbm_bytes",
                        lambda: order.append("hbm") or {})
    monkeypatch.setattr(
        gbdt.jax, "device_get",
        lambda x: order.append("fetch") or real_get(x))
    X, y = make_synthetic_binary(n=900, f=5)
    bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=2), X, y)
    bst.update()
    order.clear()
    bst.update()
    assert order == ["hbm", "fetch"]


def test_shard_bind_carries_the_reading(two_devices_report):
    X, y = make_synthetic_binary(n=2048, f=6)
    bst = _booster(dict(STREAM, objective="binary", tree_learner="data",
                        num_machines=2), X, y)
    bst.update()
    binds = tel.recent_spans(name="GBDT::ShardBind")
    assert binds, "the row mesh binds the objective's rows once"
    assert {k: binds[0].args[k] for k in two_devices_report} \
        == two_devices_report


# ------------------------------------------------------ the second accumulator
def test_timetag_report_lists_the_boundary_phases(fused, capsys, monkeypatch):
    from lightgbm_tpu.utils import timer
    X, y = make_synthetic_binary(n=900, f=5)
    bst = _booster(dict(STREAM, objective="binary", eval_fetch_freq=2), X, y)
    for _ in range(2):
        bst.update()
    report = timer.phase_report()
    by_name = {line.split(": ")[0]: line for line in report.splitlines()}
    for name in ("Dataset::FindBins", "Dataset::Bin", "Dataset::Ship",
                 "GBDT::Iteration", "GBDT::FusedIter", "GBDT::FlagPoll"):
        assert name in by_name and "ms/call" in by_name[name], name
    assert "(2 calls" in by_name["GBDT::Iteration"]
    totals = [float(line.split(": ")[1].split("s ")[0])
              for line in report.splitlines()]
    assert totals == sorted(totals, reverse=True)    # hot spots first
    # the at-exit hook prints it under the switch, and only under it
    monkeypatch.delenv("LIGHTGBM_TPU_TIMETAG", raising=False)
    timer._print_timers()
    assert capsys.readouterr().out == ""
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "1")
    timer._print_timers()
    out = capsys.readouterr().out
    assert out.startswith("[LightGBM-TPU] timers:\n") \
        and "GBDT::FusedIter" in out


def test_the_uncalled_helper_and_the_second_accumulator_are_gone():
    import lightgbm_tpu.utils as utils
    from lightgbm_tpu.utils import timer
    assert not hasattr(timer, "named_scope")
    assert "named_scope" not in utils.__all__
    src = Path(lgb.__file__).with_name("models") / "gbdt.py"
    assert "global_timer" not in src.read_text()
