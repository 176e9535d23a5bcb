"""Compiled-program cost observability (docs/OBSERVABILITY.md "Cost model
& profiling"): XLA flops/HBM capture per watched_jit entry, roofline
verdicts, the AOT compile/execute accounting fix, counter resets, the
host+device profile session, and the perf-regression sentinel."""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.telemetry as tel
from lightgbm_tpu.telemetry import costmodel

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def telemetry_cost():
    tel.reset()
    tel.reset_watchdog()
    tel.reset_counters()
    tel.configure(enabled=True, cost_capture="full")
    yield tel
    tel.configure(enabled=False, metrics_out="", trace_out="",
                  cost_capture="auto")
    tel.reset()
    tel.reset_watchdog()
    tel.reset_counters()


def _sentinel():
    spec = importlib.util.spec_from_file_location(
        "perf_sentinel", REPO / "scripts" / "perf_sentinel.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train(params_extra=None, rows=1500, iters=3):
    rs = np.random.RandomState(3)
    X = rs.randn(rows, 8).astype(np.float32)
    y = (X[:, 0] + 0.3 * rs.randn(rows) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "telemetry": True, **(params_extra or {})}
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=iters), X


# ---------------------------------------------------------------------------
# capture: training entries, summary/metrics/JSONL export
# ---------------------------------------------------------------------------

def test_training_entries_have_full_cost_records(telemetry_cost):
    bst, _ = _train({"telemetry_cost": "full"})
    cost = bst.telemetry_summary()["cost"]
    assert cost["enabled"] and cost["mode"] == "full"
    for name in ("grow_tree", "gradients"):
        rec = cost["entries"][name]
        assert rec["available"]
        assert rec["flops"] > 0
        assert rec["bytes_accessed"] > 0
        assert rec["peak_hbm_bytes"] > 0
        assert rec["verdict"] in ("compute-bound", "hbm-bound")
        assert rec["intensity"] == pytest.approx(
            rec["flops"] / rec["bytes_accessed"], rel=1e-3)
    # the roofline the verdicts were judged against rides along
    assert cost["roofline"]["ridge_intensity"] > 0
    # dispatch-weighted totals accumulated across the run
    assert cost["totals"]["flops"] > 0
    assert cost["totals"]["hbm_bytes"] > 0


def test_per_iteration_records_carry_flops_and_bytes(telemetry_cost):
    _train({"telemetry_cost": "full"}, iters=4)
    recs = [r for r in tel.global_registry.records
            if r.get("event") == "iteration"]
    assert len(recs) == 4
    # steady-state iterations execute the captured programs, so the
    # per-iteration flops/hbm_bytes fields are positive
    assert all(r["flops"] > 0 for r in recs[1:])
    assert all(r["hbm_bytes"] > 0 for r in recs[1:])
    snap = tel.global_registry.snapshot()
    assert snap["counters"]["cost/flops"] > 0
    assert snap["counters"]["cost/hbm_bytes"] > 0


def test_cost_gauges_reach_prometheus_exposition(telemetry_cost):
    _train({"telemetry_cost": "full"})
    text = tel.registry_text()
    assert "# TYPE lgbtpu_cost_grow_tree_flops gauge" in text
    assert "lgbtpu_cost_grow_tree_peak_hbm_bytes" in text
    assert "lgbtpu_cost_gradients_flops" in text


def test_lowered_mode_skips_the_second_compile(telemetry_cost):
    tel.configure(enabled=True, cost_capture="lowered")
    _train()   # params telemetry only; configured mode stays "lowered"
    recs = costmodel.cost_records()
    rec = recs["grow_tree"]
    assert rec["available"] and rec["source"] == "lowered"
    assert rec["flops"] > 0
    # memory analysis needs the compiled executable — absent by design
    assert "peak_hbm_bytes" not in rec


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs a >=4-device mesh")
def test_fused_iter_has_a_cost_record(telemetry_cost):
    """The one-launch-per-iteration mesh program is the most expensive
    entry in the system — its cost record is the headline attribution."""
    rs = np.random.RandomState(5)
    X = rs.randn(4096, 8).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "telemetry": True,
                     "telemetry_cost": "full", "tree_learner": "data",
                     "hist_backend": "stream", "mesh_shape": "data:4"},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    assert bst.engine._fused_last, "fused path did not engage"
    rec = bst.telemetry_summary()["cost"]["entries"]["fused_iter"]
    assert rec["available"]
    assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
    assert rec["peak_hbm_bytes"] > 0
    assert rec["verdict"] in ("compute-bound", "hbm-bound")


def test_serve_predict_has_a_cost_record(telemetry_cost, tmp_path):
    bst, X = _train()
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    from lightgbm_tpu.serving.registry import ModelRegistry
    reg = ModelRegistry(path, max_batch=16)
    reg.current().predict(X[:4], raw_score=True)
    rec = costmodel.cost_records()["serve_predict"]
    assert rec["available"] and rec["flops"] >= 0
    assert rec["verdict"] in ("compute-bound", "hbm-bound")


# ---------------------------------------------------------------------------
# roofline classification
# ---------------------------------------------------------------------------

def test_roofline_verdict_splits_on_the_ridge(monkeypatch):
    monkeypatch.setattr(costmodel, "_balance", None)
    monkeypatch.setenv("LGBTPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("LGBTPU_PEAK_BW", "1e10")   # ridge = 100 flops/byte
    try:
        assert costmodel.machine_balance()["ridge_intensity"] == 100.0
        assert costmodel.roofline_verdict(1e9, 1e6)["verdict"] == \
            "compute-bound"    # intensity 1000
        assert costmodel.roofline_verdict(1e6, 1e6)["verdict"] == \
            "hbm-bound"        # intensity 1
        assert costmodel.roofline_verdict(1.0, 0.0)["verdict"] == \
            "unavailable"
    finally:
        costmodel._balance = None   # drop the env-poisoned cache


# ---------------------------------------------------------------------------
# AOT surface + counter resets (watchdog satellites)
# ---------------------------------------------------------------------------

def test_aot_lower_compile_counts_and_captures(telemetry_cost):
    f = tel.watched_jit(lambda x: x * 2.0 + 1.0, name="aot_entry",
                        warn_after=0)
    x = jnp.ones((32,), jnp.float32)
    compiled = f.lower(x).compile()
    # the AOT compile is on the books: one trace for the entry
    assert tel.recompile_counts()["aot_entry"] == 1
    assert tel.global_registry.snapshot()["counters"][
        "recompile/aot_entry"] == 1
    # ... and the compiled executable was captured for free
    rec = costmodel.cost_records()["aot_entry"]
    assert rec["available"] and rec["source"] == "aot"
    assert rec["peak_hbm_bytes"] > 0
    # executions through the AOT object count as launches
    l0 = tel.launch_count()
    f0, _ = costmodel.dispatch_totals()
    compiled(x)
    assert tel.launch_count() == l0 + 1
    assert costmodel.dispatch_totals()[0] > f0


def test_aot_compile_of_warm_signature_still_counts(telemetry_cost):
    f = tel.watched_jit(lambda x: x + 1.0, name="aot_warm", warn_after=0)
    x = jnp.ones((8,), jnp.float32)
    f(x)    # normal dispatch traces + compiles
    assert tel.recompile_counts()["aot_warm"] == 1
    # lower() now hits the jaxpr cache, but .compile() is a REAL second
    # XLA compile of the entry — it must not vanish from the counters
    f.lower(x).compile()
    assert tel.recompile_counts()["aot_warm"] == 2


def test_reset_counters_zeroes_the_globals(telemetry_cost):
    f = tel.watched_jit(lambda x: x - 1.0, name="reset_probe",
                        warn_after=0)
    f(jnp.ones((4,), jnp.float32))
    tel.note_host_sync()
    assert tel.launch_count() > 0 and tel.host_sync_count() > 0
    tel.reset_counters()
    assert tel.launch_count() == 0 and tel.host_sync_count() == 0


# ---------------------------------------------------------------------------
# graceful degradation: unavailable is never zero
# ---------------------------------------------------------------------------

class _RaisingJit:
    def lower(self, *a, **k):
        raise RuntimeError("backend refuses AOT lowering")


class _EmptyCostLowered:
    def cost_analysis(self):
        return {}

    def compile(self):
        raise RuntimeError("no compile either")


class _EmptyCostJit:
    def lower(self, *a, **k):
        return _EmptyCostLowered()


def _fresh_entry(name):
    e = tel.WatchEntry(name, 0)
    e.count = 1   # one trace happened, nothing captured yet
    return e


def test_capture_failure_yields_unavailable_not_zero(telemetry_cost):
    t0 = costmodel.dispatch_totals()
    entry = _fresh_entry("degraded_raise")
    costmodel.after_dispatch(entry, _RaisingJit(), (), {})
    rec = costmodel.cost_records()["degraded_raise"]
    assert rec["available"] is False
    assert rec["verdict"] == "unavailable"
    assert "flops" not in rec     # no fabricated zero
    # unavailable entries contribute nothing to the totals
    assert costmodel.dispatch_totals() == t0
    # and the capture is not retried every dispatch
    assert entry.cost_seen == entry.count


def test_empty_cost_analysis_is_unavailable(telemetry_cost):
    entry = _fresh_entry("degraded_empty")
    costmodel.after_dispatch(entry, _EmptyCostJit(), (), {})
    rec = costmodel.cost_records()["degraded_empty"]
    assert rec["available"] is False and rec["verdict"] == "unavailable"


def test_sentinel_skips_unavailable_entries():
    sentinel = _sentinel()
    measured = {"entries": {"grow_tree": {"available": False,
                                          "error": "no cost analysis"}},
                "launches_per_iter": 1.0}
    budgets = {"tolerance": 0.1,
               "entries": {"grow_tree": {"flops": 1.0}}}   # absurdly low
    violations, skipped, checks = sentinel.compare_budgets(measured,
                                                           budgets)
    # an unavailable measurement must SKIP (with a notice), never pass as
    # a 0-flops "100% improvement" nor fail the absurd budget
    assert violations == [] and checks == 0
    assert any("unavailable" in s for s in skipped)


# ---------------------------------------------------------------------------
# perf sentinel: budgets + history
# ---------------------------------------------------------------------------

def test_sentinel_budget_compare_pass_and_fail():
    sentinel = _sentinel()
    measured = {"entries": {"grow_tree": {"flops": 100.0,
                                          "peak_hbm_bytes": 1000.0}},
                "launches_per_iter": 3.0}
    budgets = {"tolerance": 0.1, "launches_per_iter_max": 5,
               "entries": {"grow_tree": {"flops": 120,
                                         "peak_hbm_bytes": 1100}}}
    violations, _, checks = sentinel.compare_budgets(measured, budgets)
    assert violations == [] and checks == 3
    bad = {"tolerance": 0.1, "launches_per_iter_max": 2,
           "entries": {"grow_tree": {"flops": 80}}}
    violations, _, _ = sentinel.compare_budgets(measured, bad)
    assert len(violations) == 2
    assert any("grow_tree.flops" in v for v in violations)
    assert any("launches_per_iter" in v for v in violations)


def test_sentinel_cli_exit_codes(tmp_path):
    measured = {"entries": {"grow_tree": {"flops": 100.0}},
                "launches_per_iter": 1.0}
    cur = tmp_path / "current.json"
    cur.write_text(json.dumps(measured))
    ok_budget = tmp_path / "ok.json"
    ok_budget.write_text(json.dumps(
        {"entries": {"grow_tree": {"flops": 200}}}))
    bad_budget = tmp_path / "bad.json"
    bad_budget.write_text(json.dumps(
        {"entries": {"grow_tree": {"flops": 10}}}))
    script = str(REPO / "scripts" / "perf_sentinel.py")

    def run(budget):
        return subprocess.run(
            [sys.executable, script, "--budgets", str(budget),
             "--current", str(cur)],
            capture_output=True, text=True, timeout=60)

    assert run(ok_budget).returncode == 0
    r = run(bad_budget)
    assert r.returncode == 1
    assert "REGRESSION" in r.stderr


def test_repo_budgets_manifest_is_well_formed():
    """PERF_BUDGETS.json stays loadable and covers the tier-1 training
    entries (the full measured gate runs in run_all_tests.sh)."""
    budgets = json.loads((REPO / "PERF_BUDGETS.json").read_text())
    assert 0 < budgets["tolerance"] < 1
    for name in ("grow_tree", "gradients", "serve_predict"):
        entry = budgets["entries"][name]
        assert entry["flops"] > 0 and entry["peak_hbm_bytes"] > 0


def _hist_line(metric, value, date, host="box"):
    return json.dumps({"metric": metric, "value": value, "date": date,
                       "host": host}) + "\n"


def test_sentinel_history_regression_and_direction(tmp_path):
    sentinel = _sentinel()
    hist = tmp_path / "hist.jsonl"
    hist.write_text(
        _hist_line("x_s_per_tree", 1.00, "2026-01-01") +
        _hist_line("x_s_per_tree", 1.02, "2026-01-02") +
        _hist_line("x_s_per_tree", 2.50, "2026-01-03") +     # slower: bad
        _hist_line("serve_qps", 100.0, "2026-01-01") +
        _hist_line("serve_qps", 102.0, "2026-01-02") +
        _hist_line("serve_qps", 40.0, "2026-01-03") +        # slower: bad
        _hist_line("young_metric", 5.0, "2026-01-03"))       # < min_runs
    violations, notices, checks = sentinel.check_history(
        str(hist), tolerance=0.25, min_runs=3)
    assert checks == 2 and len(violations) == 2
    assert any("x_s_per_tree" in v for v in violations)
    assert any("serve_qps" in v for v in violations)
    assert any("young_metric" in n for n in notices)
    # same data, healthy latest values -> clean
    hist.write_text(
        _hist_line("x_s_per_tree", 1.00, "2026-01-01") +
        _hist_line("x_s_per_tree", 1.02, "2026-01-02") +
        _hist_line("x_s_per_tree", 0.97, "2026-01-03") +
        _hist_line("serve_qps", 100.0, "2026-01-01") +
        _hist_line("serve_qps", 102.0, "2026-01-02") +
        _hist_line("serve_qps", 108.0, "2026-01-03"))
    violations, _, checks = sentinel.check_history(str(hist))
    assert violations == [] and checks == 2


def test_repo_history_file_is_well_formed():
    """The committed BENCH_HISTORY.jsonl (seeded from the BENCH_r0*
    archives) parses as one record per line with the fields the
    sentinel keys on.  The live regression gate over this file runs in
    run_all_tests.sh — re-running it here would couple the unit suite
    to mutable bench data."""
    lines = (REPO / "BENCH_HISTORY.jsonl").read_text().splitlines()
    assert lines
    for line in lines:
        row = json.loads(line)
        assert isinstance(row["metric"], str)
        assert isinstance(row["value"], (int, float))
        assert row["date"]


# ---------------------------------------------------------------------------
# profile session: one merged host+device Perfetto timeline
# ---------------------------------------------------------------------------

def test_profile_session_merges_host_and_device_trace(telemetry_cost,
                                                      tmp_path):
    from lightgbm_tpu.telemetry.profile import ProfileSession
    out = tmp_path / "prof"
    session = ProfileSession(str(out)).start()
    try:
        with tel.span("ProfiledRegion"):
            f = tel.watched_jit(lambda x: (x @ x).sum(),
                                name="profiled_mm", warn_after=0)
            f(jnp.ones((64, 64), jnp.float32)).block_until_ready()
    finally:
        info = session.stop()
    assert info.get("device_trace_error") is None, info
    assert info["shards"] == 2
    blob = json.loads(Path(info["merged_trace"]).read_text())
    names = {e.get("name") for e in blob["traceEvents"]}
    # host span and device-side events share one timeline
    assert "ProfiledRegion" in names
    shard_info = blob["otherData"]["shards"]
    assert len(shard_info) == 2 and all(s["aligned"] for s in shard_info)
    device_events = [s["events"] for s in shard_info
                     if "device" in s["path"]][0]
    assert device_events > 0


# ---------------------------------------------------------------------------
# the peak table (PR 22): int8 peak for the quantized kernel, unknown TPU kinds
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def _balance_for(monkeypatch, platform, kind):
    import jax
    monkeypatch.setattr(costmodel, "_balance", None)
    monkeypatch.delenv("LGBTPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("LGBTPU_PEAK_BW", raising=False)
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeDevice(platform, kind)])
    try:
        return costmodel.machine_balance()
    finally:
        costmodel._balance = None


def test_v5e_peaks_include_the_int8_peak(monkeypatch):
    """A v5e reports device_kind "TPU v5 lite"; the quantized stream
    kernel's one-hot contraction runs against the int8 peak, not bf16."""
    bal = _balance_for(monkeypatch, "tpu", "TPU v5 lite")
    assert bal["peak_flops_per_s"] == 197e12
    assert bal["peak_int8_ops_per_s"] == 393e12
    assert bal["peak_hbm_bytes_per_s"] == 819e9
    assert bal["platform"] == "tpu" and bal["device_kind"] == "TPU v5 lite"


def test_unknown_tpu_kind_is_an_error_not_a_default(monkeypatch):
    with pytest.raises(ValueError, match="no published peaks.*TPU v9 mega"):
        _balance_for(monkeypatch, "tpu", "TPU v9 mega")
    # both overrides together stand in for a new part
    monkeypatch.setenv("LGBTPU_PEAK_FLOPS", "1e15")
    monkeypatch.setenv("LGBTPU_PEAK_BW", "1e12")
    monkeypatch.setattr(costmodel, "_balance", None)
    import jax
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeDevice("tpu", "TPU v9 mega")])
    try:
        assert costmodel.machine_balance()["ridge_intensity"] == 1000.0
    finally:
        costmodel._balance = None


def test_cpu_keeps_its_default_and_has_no_int8_peak(monkeypatch):
    bal = _balance_for(monkeypatch, "cpu", "cpu")
    assert bal["peak_int8_ops_per_s"] is None
    assert bal["ridge_intensity"] == 10.0
