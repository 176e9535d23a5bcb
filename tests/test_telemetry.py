"""Telemetry subsystem: span nesting, Chrome-trace roundtrip, per-iteration
training records, recompile watchdog, straggler aggregation, and the
zero-overhead disabled path — plus the timer/log satellite fixes."""
import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.telemetry as tel
from lightgbm_tpu.telemetry.tracer import _NULL_SPAN
from lightgbm_tpu.utils import log as logmod

from conftest import make_synthetic_regression


class _Recorder:
    def __init__(self):
        self.infos = []
        self.warnings = []

    def info(self, msg):
        self.infos.append(str(msg))

    def warning(self, msg):
        self.warnings.append(str(msg))


@pytest.fixture
def telemetry():
    tel.reset()
    tel.reset_watchdog()
    tel.configure(enabled=True)
    yield tel
    tel.disable()
    tel.reset()
    tel.reset_watchdog()
    tel.configure(enabled=False, metrics_out="", trace_out="")


@pytest.fixture
def logrec():
    rec = _Recorder()
    old = (logmod._logger, logmod._info_method_name,
           logmod._warning_method_name)
    old_verbosity = logmod.get_verbosity()
    logmod.register_logger(rec)
    logmod.set_verbosity(1)   # verbosity is process-global; pin it here
    yield rec
    logmod._logger, logmod._info_method_name, \
        logmod._warning_method_name = old
    logmod.set_verbosity(old_verbosity)


def _train_params(**overrides):
    p = {"objective": "regression", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbosity": -1, "telemetry": True}
    p.update(overrides)
    return p


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_events(telemetry):
    with tel.span("outer", kind="test"):
        with tel.span("inner"):
            time.sleep(0.001)
        with tel.span("inner"):
            pass
    events = tel.global_tracer.events
    names = [(e["name"], e["ph"]) for e in events]
    assert names == [("outer", "B"), ("inner", "B"), ("inner", "E"),
                     ("inner", "B"), ("inner", "E"), ("outer", "E")]
    # begin/end timestamps nest: outer B <= inner B, inner E <= outer E
    outer_b, outer_e = events[0]["ts"], events[-1]["ts"]
    assert outer_b <= events[1]["ts"] <= events[2]["ts"] <= outer_e
    # attributes ride on the begin event
    assert events[0]["args"] == {"kind": "test"}
    phases = tel.global_tracer.phase_snapshot()
    assert phases["inner"] <= phases["outer"]
    assert tel.global_tracer.phase_counts()["inner"] == 2


def test_trace_export_roundtrip(telemetry, tmp_path):
    with tel.span("region"):
        tel.instant("marker", detail=1)
        tel.global_tracer.counter("track", value=3.5)
    path = str(tmp_path / "trace.json")
    tel.export_trace(path)
    blob = json.loads(open(path).read())
    # Chrome trace-event envelope: Perfetto loads {"traceEvents": [...]}
    assert isinstance(blob["traceEvents"], list)
    assert blob["displayTimeUnit"] == "ms"
    phs = set()
    for ev in blob["traceEvents"]:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("B", "E", "X", "i", "C", "M")
        assert isinstance(ev["pid"], int)
        if ev["ph"] != "M":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        phs.add(ev["ph"])
    assert {"B", "E", "i", "C", "M"} <= phs
    # B/E balanced per thread
    for tid in {e["tid"] for e in blob["traceEvents"] if e["ph"] in "BE"}:
        seq = [e["ph"] for e in blob["traceEvents"]
               if e.get("tid") == tid and e["ph"] in "BE"]
        depth = 0
        for ph in seq:
            depth += 1 if ph == "B" else -1
            assert depth >= 0
        assert depth == 0


def test_zero_overhead_when_disabled():
    tel.disable()
    tel.reset()
    # the disabled fast path hands back ONE shared no-op object: a single
    # boolean check, no allocation, nothing recorded
    assert tel.span("a") is tel.span("b") is _NULL_SPAN
    with tel.span("a"):
        pass
    tel.instant("x")
    tel.global_tracer.counter("x", v=1)
    tel.inc("c")
    tel.gauge("g", 1.0)
    tel.observe("h", 0.1)
    tel.record({"event": "x"})
    assert tel.global_tracer.events == []
    snap = tel.global_registry.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["num_records"] == 0


def test_param_scoped_telemetry_does_not_leak_across_boosters(tmp_path):
    """Model B trained without telemetry params must not inherit model A's
    sink or instrumentation (param-driven enablement is per-model)."""
    sink = str(tmp_path / "a.jsonl")
    X, y = make_synthetic_regression(n=300, f=4)
    try:
        lgb.train(_train_params(telemetry_out=sink), lgb.Dataset(X, label=y),
                  num_boost_round=2)
        assert len(open(sink).readlines()) == 2
        lgb.train({"objective": "regression", "num_leaves": 7,
                   "min_data_in_leaf": 5, "verbosity": -1},
                  lgb.Dataset(X, label=y), num_boost_round=2)
        assert not tel.enabled()
        assert len(open(sink).readlines()) == 2   # no contamination
    finally:
        tel.configure(enabled=False, metrics_out="", trace_out="")
        tel.reset()


def test_train_disabled_emits_nothing():
    tel.disable()
    tel.reset()
    X, y = make_synthetic_regression(n=300, f=4)
    lgb.train(_train_params(telemetry=False), lgb.Dataset(X, label=y),
              num_boost_round=2)
    assert tel.global_registry.records == []
    assert tel.global_tracer.events == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_instruments(telemetry):
    tel.inc("c", 2)
    tel.inc("c")
    tel.gauge("g", 4.25)
    tel.observe("h", 0.002)
    tel.observe("h", 0.2)
    snap = tel.global_registry.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 4.25
    h = snap["histograms"]["h"]
    assert h["count"] == 2
    assert h["min_s"] == pytest.approx(0.002)
    assert h["max_s"] == pytest.approx(0.2)
    assert h["mean_s"] == pytest.approx(0.101)


def test_quantiles_value_on_bucket_bound(telemetry):
    """A quantile landing EXACTLY on a cumulative-bucket boundary must
    report from the bucket holding the value, not the next one.  With 19
    of 20 samples at the 2.0 bound, ``0.95 * 20`` is 19.000000000000004
    in binary — an unguarded walk steps past bucket 2.0 and interpolates
    inside (2.0, 4.0]."""
    for _ in range(19):
        tel.observe("qb", 2.0, bounds=(1.0, 2.0, 4.0))
    tel.observe("qb", 5.0, bounds=(1.0, 2.0, 4.0))
    q = tel.quantiles("qb", qs=(0.5, 0.95, 0.99))
    assert q["p50"] == 2.0          # clamped up to the observed min
    assert q["p95"] == 2.0          # ON the bound, not past it
    assert q["p99"] == pytest.approx(4.8)   # inside the last bucket


def test_quantiles_single_bucket_degenerate(telemetry):
    tel.observe("q1", 0.5, bounds=(1.0,))
    q = tel.quantiles("q1", qs=(0.5, 0.99))
    # every quantile clamps into [min, max] of the observations
    assert q["p50"] == 0.5 and q["p99"] == 0.5


# ---------------------------------------------------------------------------
# per-iteration training records
# ---------------------------------------------------------------------------

def test_train_emits_iteration_records(telemetry, tmp_path):
    metrics_path = str(tmp_path / "metrics.jsonl")
    trace_path = str(tmp_path / "trace.json")
    rounds = 4
    X, y = make_synthetic_regression(n=500, f=5)
    bst = lgb.train(
        _train_params(telemetry_out=metrics_path, trace_out=trace_path),
        lgb.Dataset(X, label=y), num_boost_round=rounds)
    # one JSONL record per boosting iteration
    lines = [json.loads(l) for l in open(metrics_path)]
    iters = [r for r in lines if r.get("event") == "iteration"]
    assert len(iters) == rounds
    for i, r in enumerate(iters):
        assert r["iteration"] == i + 1
        assert r["wall_s"] > 0
        assert 2 <= r["num_leaves"] <= 7
        assert r["phases"]  # boosting/grow splits present
        # XLA:CPU keeps no allocator stats: no peak, and no estimate
        # under another name in its place
        assert "peak_hbm_gb" not in r and "device_hbm_gb" not in r
        assert "host_rss_gb" in r
    assert any("boosting_s" in r["phases"] for r in iters)
    assert any("grow_s" in r["phases"] for r in iters)
    # trace written by train() and Perfetto-loadable, with per-iter spans
    blob = json.loads(open(trace_path).read())
    iter_begins = [e for e in blob["traceEvents"]
                   if e["name"] == "GBDT::Iteration" and e["ph"] == "B"]
    assert len(iter_begins) == rounds
    # summary rolls everything up
    s = bst.telemetry_summary()
    assert s["train"]["iterations_recorded"] == rounds
    assert s["train"]["total_s"] > 0
    assert s["recompiles"]["grow_tree"]["compiles"] >= 1
    assert "GBDT::Iteration" in s["phases"]
    assert s["counters"]["train/iterations"] == rounds


def test_log_telemetry_callback(telemetry, logrec):
    X, y = make_synthetic_regression(n=300, f=4)
    lgb.train(_train_params(verbosity=1), lgb.Dataset(X, label=y),
              num_boost_round=3, callbacks=[lgb.log_telemetry(period=1)])
    lines = [m for m in logrec.infos if "[telemetry]" in m]
    assert len(lines) == 3
    assert "iter" in lines[0] and "ms" in lines[0]


# ---------------------------------------------------------------------------
# recompile watchdog
# ---------------------------------------------------------------------------

def test_watchdog_counts_and_warns_on_shape_change(telemetry, logrec):
    f = tel.watched_jit(lambda x: x * 2.0, name="unit_fn", warn_after=1)
    f(jnp.ones(4))
    f(jnp.zeros(4))          # cache hit: same shape/dtype, no retrace
    assert tel.recompile_counts()["unit_fn"] == 1
    assert logrec.warnings == []
    f(jnp.ones(8))           # forced shape change -> retrace -> warning
    assert tel.recompile_counts()["unit_fn"] == 2
    warns = [w for w in logrec.warnings if "unit_fn" in w]
    assert len(warns) == 1
    assert "recompiled" in warns[0]
    assert "float32[8]" in warns[0]      # offending shapes/dtypes included
    # the warning also lands in the trace as an instant event
    names = [e["name"] for e in tel.global_tracer.events if e["ph"] == "i"]
    assert "recompile:unit_fn" in names


def test_watchdog_fires_on_midtraining_retrace(telemetry, logrec):
    """reset_parameter mid-training re-jits the grower — the watchdog must
    flag the retrace of the same (engine, entry-point) pair."""
    X, y = make_synthetic_regression(n=400, f=5)
    cb = lgb.reset_parameter(lambda_l2=[0.0, 0.0, 0.5, 0.5])
    lgb.train(_train_params(telemetry_recompile_threshold=1, verbosity=0),
              lgb.Dataset(X, label=y), num_boost_round=4, callbacks=[cb])
    warns = [w for w in logrec.warnings
             if "grow_tree" in w and "recompiled" in w]
    assert warns, f"no recompile warning in {logrec.warnings!r}"
    s = tel.watchdog_summary()
    assert s["grow_tree"]["max_per_entry"] >= 2
    assert s["grow_tree"]["warned"] >= 1


def test_watchdog_silent_for_fresh_models(telemetry, logrec):
    """Two independent boosters each compile once: per-entry counters must
    not bleed across engines (a fresh model is not a retrace)."""
    X, y = make_synthetic_regression(n=300, f=4)
    for n in (300, 200):
        lgb.train(_train_params(telemetry_recompile_threshold=1,
                                verbosity=0),
                  lgb.Dataset(X[:n], label=y[:n]), num_boost_round=2)
    assert [w for w in logrec.warnings if "grow_tree" in w] == []
    assert tel.watchdog_summary()["grow_tree"]["max_per_entry"] == 1


# ---------------------------------------------------------------------------
# multi-host straggler aggregation
# ---------------------------------------------------------------------------

def test_straggler_report_single_host(telemetry):
    from lightgbm_tpu.parallel.straggler import straggler_report
    rep = straggler_report([0.1, 0.11, 0.09])
    assert rep["hosts"] == 1
    assert rep["median_host_mean_s"] == pytest.approx(0.1, rel=0.1)
    assert rep["skew"] == pytest.approx(1.0)
    assert rep in tel.global_registry.records


def test_straggler_report_flags_slow_host(telemetry, logrec):
    from lightgbm_tpu.parallel.straggler import straggler_report
    stats = np.array([[10, 0.10, 0.12],
                      [10, 0.10, 0.11],
                      [10, 0.30, 0.40],
                      [10, 0.11, 0.12]])
    rep = straggler_report([0.1] * 10, warn_skew=1.25,
                           _all_host_stats=stats)
    assert rep["hosts"] == 4
    assert rep["slowest_host"] == 2
    assert rep["skew"] >= 2.0
    assert any("straggler" in w for w in logrec.warnings)
    # balanced hosts: info line, no warning
    logrec.warnings.clear()
    even = np.array([[10, 0.10, 0.12], [10, 0.105, 0.11]])
    rep = straggler_report([0.1] * 10, warn_skew=1.25, _all_host_stats=even)
    assert rep["skew"] < 1.25
    assert not logrec.warnings


@pytest.mark.slow
def test_straggler_reports_in_multiprocess_training(tmp_path):
    """Real 2-process jax.distributed run: the straggler allgather fires
    every K iterations and rank 0's summary carries the report."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    X, y = make_synthetic_regression(n=1200, f=6)
    data_path = str(tmp_path / "train.csv")
    np.savetxt(data_path, np.column_stack([y, X]), delimiter=",")
    from lightgbm_tpu.parallel.cluster import train_distributed
    from lightgbm_tpu.utils.log import LightGBMError
    try:
        bst = train_distributed(
            {"objective": "regression", "num_leaves": 7,
             "min_data_in_leaf": 5, "verbosity": -1, "telemetry": True,
             "telemetry_straggler_every": 2},
            data_path, num_boost_round=6, num_processes=2)
    except LightGBMError as e:
        if "Multiprocess computations aren't implemented" in str(e):
            pytest.skip("jax CPU backend lacks multiprocess collectives")
        raise
    s = bst.telemetry_summary_
    assert s["train"]["iterations_recorded"] == 6
    assert "straggler" in s, f"no straggler report in {list(s)}"
    assert s["straggler"]["hosts"] == 2
    assert s["straggler"]["skew"] >= 1.0


# ---------------------------------------------------------------------------
# satellite: Timer fixes
# ---------------------------------------------------------------------------

def test_timer_env_read_lazily(monkeypatch):
    from lightgbm_tpu.utils.timer import Timer
    t = Timer()
    monkeypatch.delenv("LIGHTGBM_TPU_TIMETAG", raising=False)
    assert not t.enabled
    # env set AFTER construction must be honored (was frozen at import)
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "1")
    assert t.enabled
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "0")
    assert not t.enabled
    t.enable()
    assert t.enabled            # override beats env
    t.disable()
    monkeypatch.setenv("LIGHTGBM_TPU_TIMETAG", "1")
    assert not t.enabled
    t.reset_enabled()
    assert t.enabled


def test_timer_report_sorted_by_total_with_mean():
    from lightgbm_tpu.utils.timer import Timer
    t = Timer()
    t.enable()
    with t.scope("cold"):
        pass
    with t.scope("hot"):
        time.sleep(0.02)
    with t.scope("warm"):
        time.sleep(0.005)
    lines = t.report().splitlines()
    assert [l.split(":")[0] for l in lines] == ["hot", "warm", "cold"]
    assert all("ms/call" in l for l in lines)


# ---------------------------------------------------------------------------
# satellite: log handler guard
# ---------------------------------------------------------------------------

def test_no_duplicate_handlers_on_reimport():
    import importlib
    import logging
    shared = logging.getLogger("lightgbm_tpu")
    before = list(shared.handlers)
    # a reload defines LightGBMError anew; modules that import it at call
    # time would then raise a class that `lgb.LightGBMError` (bound at the
    # first import) no longer names, and a later test file on this worker
    # would miss it in pytest.raises: put the first class back
    error_class = logmod.LightGBMError
    old_level = shared.level
    try:
        importlib.reload(logmod)     # simulates a second import of the module
        assert shared.handlers == before
        # a pre-configured level must survive re-import untouched
        shared.setLevel(logging.ERROR)
        importlib.reload(logmod)
        assert shared.level == logging.ERROR
    finally:
        shared.setLevel(old_level)
        logmod.LightGBMError = error_class
