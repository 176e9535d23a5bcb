"""Perf-regression sentinel: cost budgets + bench-history compare.

Wall-clock on a shared test box is noisy; XLA flops, peak-HBM bytes, and
launches-per-iteration are not — they are properties of the compiled
programs.  The sentinel therefore gates on two complementary surfaces
(docs/OBSERVABILITY.md "Perf-regression sentinel"):

**Budget mode** (``--budgets PERF_BUDGETS.json --measure``): trains the
manifest's fixed small workload with full cost capture
(telemetry/costmodel.py), exercises the serving predictor, and compares
each watched entry's measured flops / peak-HBM / launches-per-iter
against its budget ceiling.  Deterministic on any box — silent compute
bloat (an accidental f32 upcast, a lost fusion, a new per-round gather)
fails here even when wall-clock noise would hide it.  Entries whose
backend reports no cost analysis are ``unavailable`` and are SKIPPED
with a notice — never treated as zero (a zero would read as a 100%
improvement and grandfather real regressions under a later budget
refresh).

**History mode** (``--history BENCH_HISTORY.jsonl``): compares the
newest bench value per (metric, host) against the median of its
predecessors on the SAME host, directional per metric (qps up is good,
s/tree down is good), with a noise tolerance.  Hosts with fewer than
``--min-runs`` entries are skipped with a notice, so the gate is safe to
run everywhere and only bites where history exists.

Exit status: 0 = all checks passed/skipped, 1 = regression, 2 = usage /
manifest error.  ``--current FILE`` substitutes a saved measurement for
``--measure`` (fixture injection for tests; also useful to re-judge one
measurement against edited budgets without retraining).
"""
import argparse
import json
import os
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# the budget workload: small, fixed, seeded — flops/HBM are then pure
# functions of the compiled programs, comparable across boxes
DEFAULT_WORKLOAD = {
    "rows": 20_000, "features": 16, "num_leaves": 31, "max_bin": 63,
    "iters": 4, "seed": 7,
}
# fields a budget entry may bound (ceilings; measured must stay under
# budget * (1 + tolerance))
BUDGET_FIELDS = ("flops", "bytes_accessed", "peak_hbm_bytes")


def measure(workload: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Train the fixed workload + exercise serving with full cost capture;
    returns {entries, launches_per_iter, workload, platform}."""
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry import costmodel
    from lightgbm_tpu.telemetry.profile import _synthetic_data

    w = {**DEFAULT_WORKLOAD, **(workload or {})}
    X, y = _synthetic_data(int(w["rows"]), int(w["features"]),
                           int(w["seed"]))
    params = {
        "objective": "binary", "num_leaves": int(w["num_leaves"]),
        "max_bin": int(w["max_bin"]), "learning_rate": 0.1,
        "verbosity": -1, "telemetry": True, "telemetry_cost": "full",
    }
    # an exported LGBTPU_COST (e.g. "off" on a dev box) overrides the
    # param and would let the gate pass vacuously with zero checks —
    # the sentinel's measurement MUST run at full capture
    cost_env = os.environ.pop("LGBTPU_COST", None)
    try:
        telemetry.reset_watchdog()
        telemetry.reset_counters()
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=int(w["iters"]))
        if costmodel.mode() != "full":
            raise RuntimeError(
                f"cost capture resolved to {costmodel.mode()!r}, not "
                "'full' — the budget measurement would be vacuous")
    finally:
        if cost_env is not None:
            os.environ["LGBTPU_COST"] = cost_env
    # ingest entry: the streamed chunked bin-and-ship program
    # (ingest_ship, device_data.ship_binned_chunks) — forced on via the
    # env override so the CPU sentinel box compiles it too
    ship_env = os.environ.get("LGBTPU_INGEST_SHIP")
    os.environ["LGBTPU_INGEST_SHIP"] = "1"
    try:
        ship_ds = lgb.Dataset(X, label=y, params={
            "verbosity": -1, "ingest_mode": "stream",
            "ingest_chunk_rows": max(4096, int(w["rows"]) // 4),
            "max_bin": int(w["max_bin"])})
        ship_ds.device_data()
    finally:
        if ship_env is None:
            os.environ.pop("LGBTPU_INGEST_SHIP", None)
        else:
            os.environ["LGBTPU_INGEST_SHIP"] = ship_env
    # serving entries: the bucketed compiled predictor (serve_predict)
    # and the stacked multi-tenant dispatch (serve_predict_multi) — two
    # same-shape tenants through ONE grouped window, so the stacked
    # program's cost is attributable on the same fixed workload
    with tempfile.TemporaryDirectory(prefix="lgb_sentinel_") as td:
        path = os.path.join(td, "model.txt")
        bst.save_model(path)
        from lightgbm_tpu.serving.registry import ModelRegistry
        reg = ModelRegistry(path, max_batch=64)
        reg.current().predict(X[:8], raw_score=True)
        import shutil
        from lightgbm_tpu.serving.multimodel import MultiModelRegistry
        path_b = os.path.join(td, "model_b.txt")
        shutil.copy(path, path_b)
        sidecar = path + ".quality.json"
        if os.path.exists(sidecar):
            shutil.copy(sidecar, path_b + ".quality.json")
        mreg = MultiModelRegistry({"a": path, "b": path_b},
                                  max_batch=64, warmup=False)
        mreg.raw_scores_grouped([(mreg.current("a"), X[:8]),
                                 (mreg.current("b"), X[:8])])
    from lightgbm_tpu.telemetry import global_registry
    recs = [r for r in global_registry.records
            if r.get("event") == "iteration" and "launches" in r]
    # steady state: the first iteration carries the compile-time eager
    # setup dispatches — budgets bound the repeated per-iteration cost
    steady = [float(r["launches"]) for r in recs[1:]] or \
        [float(r["launches"]) for r in recs]
    launches_per_iter = max(steady) if steady else 0.0
    entries: Dict[str, Any] = {}
    unavailable: List[str] = []
    for name, rec in costmodel.cost_records().items():
        if rec.get("available"):
            entries[name] = {k: rec[k] for k in
                             (*BUDGET_FIELDS, "intensity", "verdict")
                             if k in rec}
        else:
            unavailable.append(name)
            entries[name] = {"available": False,
                             "error": rec.get("error", "")}
    # feature-parallel grow program (tree_learner=feature): measured in a
    # SUBPROCESS on a forced 4-device CPU platform (this process's device
    # count is fixed at jax init) with the fused path off, so grow_tree is
    # its own watched jit and its XLA cost is attributable
    entries["grow_tree_feature"] = _measure_feature_grow(w)
    if entries["grow_tree_feature"].get("available") is False:
        unavailable.append("grow_tree_feature")
    # the packed-int16-wire quantized grow program (4-device CPU mesh),
    # in a subprocess for the same jax-init reasons as the feature entry
    entries["grow_tree_packed16"] = _measure_backend_grow(
        w, {"hist_backend": "stream", "tree_learner": "data",
            "use_quantized_grad": True, "hist_packed_width": 16}, 4)
    if entries["grow_tree_packed16"].get("available") is False:
        unavailable.append("grow_tree_packed16")
    # 2D rows x feature-groups grow program (docs/DISTRIBUTED.md "2D
    # mesh"): data:2,feature:2 on the same 4-device CPU mesh — segsum
    # pinned because the 2D path forbids stream and the sentinel must
    # watch ONE deterministic backend
    entries["grow_tree_mesh2d"] = _measure_backend_grow(
        w, {"tree_learner": "data", "mesh_shape": "data:2,feature:2",
            "hist_backend": "segsum"}, 4)
    if entries["grow_tree_mesh2d"].get("available") is False:
        unavailable.append("grow_tree_mesh2d")
    from lightgbm_tpu.runtime import device_record
    return {
        "workload": w,
        **device_record(),
        "entries": entries,
        "launches_per_iter": round(launches_per_iter, 3),
        "unavailable": sorted(unavailable),
    }


# the children's platform (cpu), virtual device count, compile cache and
# import path arrive in the environment _child_env builds
_FEATURE_CHILD = r"""
import json, sys
w = json.loads(sys.argv[1])
import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import costmodel
from lightgbm_tpu.telemetry.profile import _synthetic_data
X, y = _synthetic_data(int(w["rows"]), int(w["features"]), int(w["seed"]))
params = {"objective": "binary", "num_leaves": int(w["num_leaves"]),
          "max_bin": int(w["max_bin"]), "learning_rate": 0.1,
          "verbosity": -1, "telemetry": True, "telemetry_cost": "full",
          "tree_learner": "feature"}
bst = lgb.train(params, lgb.Dataset(X, label=y),
                num_boost_round=int(w["iters"]))
assert bst.engine._feature_mode
rec = costmodel.cost_records().get("grow_tree",
                                   {"available": False,
                                    "error": "no grow_tree cost record"})
print("FEATURE_COST " + json.dumps(rec))
"""


_BACKEND_CHILD = r"""
import json, sys
w = json.loads(sys.argv[1])
extra = json.loads(sys.argv[2])
import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import costmodel
from lightgbm_tpu.telemetry.profile import _synthetic_data
X, y = _synthetic_data(int(w["rows"]), int(w["features"]), int(w["seed"]))
params = {"objective": "binary", "num_leaves": int(w["num_leaves"]),
          "max_bin": int(w["max_bin"]), "learning_rate": 0.1,
          "verbosity": -1, "telemetry": True, "telemetry_cost": "full"}
params.update(extra)
bst = lgb.train(params, lgb.Dataset(X, label=y),
                num_boost_round=int(w["iters"]))
assert bst.engine._grow_params.hist_backend == extra["hist_backend"]
rec = costmodel.cost_records().get("grow_tree",
                                   {"available": False,
                                    "error": "no grow_tree cost record"})
print("BACKEND_COST " + json.dumps(rec))
"""


def _child_env(n_dev):
    """Sentinel children count XLA flops/bytes on the CPU platform with
    ``n_dev`` virtual devices, the fused iteration off (so grow_tree is
    its own watched jit) and no caller A/B knob leaking in."""
    from lightgbm_tpu.runtime import child_env
    env = child_env("cpu", n_cpu_devices=n_dev)
    env["LGBTPU_FUSE_ITER"] = "0"
    for k in ("LGBTPU_COST", "LGBTPU_HIST_BACKEND",
              "LGBTPU_HIST_PACKED_WIDTH", "LGBTPU_ROUTE_FUSION",
              "LGBTPU_HIST_COMMS"):
        env.pop(k, None)
    return env


def _measure_backend_grow(w, extra, n_dev):
    """Cost record of a hist-backend grow program variant on the fixed
    workload (subprocess; n_dev > 0 forces a CPU virtual mesh).  Failure
    -> unavailable, never zero."""
    import subprocess
    try:
        r = subprocess.run(
            [sys.executable, "-c", _BACKEND_CHILD, json.dumps(w),
             json.dumps(extra)],
            capture_output=True, text=True, timeout=600,
            env=_child_env(n_dev))
    except subprocess.TimeoutExpired:
        return {"available": False, "error": "backend-grow child timed out"}
    for line in r.stdout.splitlines():
        if line.startswith("BACKEND_COST "):
            rec = json.loads(line[len("BACKEND_COST "):])
            if rec.get("available"):
                return {k: rec[k] for k in
                        ("flops", "bytes_accessed", "peak_hbm_bytes",
                         "intensity", "verdict") if k in rec}
            return {"available": False, "error": rec.get("error", "?")}
    tail = (r.stdout + r.stderr)[-500:].replace("\n", " | ")
    return {"available": False,
            "error": f"backend-grow child failed (rc={r.returncode}): "
                     f"{tail}"}


def _measure_feature_grow(w):
    """Cost record of the feature-parallel grow program on the fixed
    workload (4-device CPU mesh, subprocess).  Failure -> unavailable,
    never zero."""
    import subprocess
    try:
        r = subprocess.run(
            [sys.executable, "-c", _FEATURE_CHILD, json.dumps(w)],
            capture_output=True, text=True, timeout=600,
            env=_child_env(4))
    except subprocess.TimeoutExpired:
        return {"available": False, "error": "feature-grow child timed out"}
    for line in r.stdout.splitlines():
        if line.startswith("FEATURE_COST "):
            rec = json.loads(line[len("FEATURE_COST "):])
            if rec.get("available"):
                return {k: rec[k] for k in
                        ("flops", "bytes_accessed", "peak_hbm_bytes",
                         "intensity", "verdict") if k in rec}
            return {"available": False, "error": rec.get("error", "?")}
    tail = (r.stdout + r.stderr)[-500:].replace("\n", " | ")
    return {"available": False,
            "error": f"feature-grow child failed (rc={r.returncode}): "
                     f"{tail}"}


def compare_budgets(measured: Dict[str, Any], budgets: Dict[str, Any]
                    ) -> Tuple[List[str], List[str], int]:
    """(violations, skipped_notices, checks_run) for one measurement."""
    tol = float(budgets.get("tolerance", 0.10))
    violations: List[str] = []
    skipped: List[str] = []
    checks = 0
    m_entries = measured.get("entries", {})
    for name, limits in sorted(budgets.get("entries", {}).items()):
        got = m_entries.get(name)
        if got is None:
            skipped.append(f"{name}: not exercised by the sentinel "
                           "workload (no cost record)")
            continue
        if got.get("available") is False:
            skipped.append(f"{name}: cost analysis unavailable on this "
                           f"backend ({got.get('error', '?')}) — budget "
                           "NOT judged (unavailable is never zero)")
            continue
        for field in BUDGET_FIELDS:
            if field not in limits:
                continue
            limit = float(limits[field])
            val = got.get(field)
            if val is None:
                skipped.append(f"{name}.{field}: not captured "
                               "(lowered-only record?) — skipped")
                continue
            checks += 1
            if float(val) > limit * (1.0 + tol):
                violations.append(
                    f"{name}.{field}: measured {float(val):.6g} exceeds "
                    f"budget {limit:.6g} (+{tol:.0%} tolerance) — "
                    f"{float(val) / limit:.2f}x")
    lpi_max = budgets.get("launches_per_iter_max")
    if lpi_max is not None:
        checks += 1
        lpi = float(measured.get("launches_per_iter", 0.0))
        if lpi > float(lpi_max):
            violations.append(
                f"launches_per_iter: measured {lpi} exceeds budget "
                f"{lpi_max} — dispatch-count bloat")
    return violations, skipped, checks


def _metric_direction(metric: str) -> int:
    """+1 = higher is better (throughput), -1 = lower is better."""
    m = metric.lower()
    return +1 if ("qps" in m or "throughput" in m
                  or "rows_per_s" in m) else -1


def check_history(path: str, tolerance: float = 0.25, min_runs: int = 3
                  ) -> Tuple[List[str], List[str], int]:
    """Latest value per (metric, host) vs the median of its same-host
    predecessors; returns (violations, notices, checks_run)."""
    if not os.path.exists(path):
        return [], [f"no history file at {path} — nothing to compare"], 0
    rows: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if isinstance(row, dict) and row.get("metric") is not None \
                    and isinstance(row.get("value"), (int, float)):
                rows.append(row)
    groups: Dict[Tuple[str, str], List[Dict[str, Any]]] = {}
    for row in rows:
        key = (str(row["metric"]), str(row.get("host", "unknown")))
        groups.setdefault(key, []).append(row)
    violations: List[str] = []
    notices: List[str] = []
    checks = 0
    for (metric, host), grp in sorted(groups.items()):
        if len(grp) < min_runs:
            notices.append(f"{metric}@{host}: {len(grp)} run(s) < "
                           f"{min_runs} — wall-clock compare skipped")
            continue
        grp = sorted(grp, key=lambda r: str(r.get("date", "")))
        latest = float(grp[-1]["value"])
        # baseline = median of the most recent prior runs: a years-old
        # 100x-slower entry must not dilute the bar the latest run clears
        prior = grp[max(0, len(grp) - 6):-1]
        base = statistics.median(float(r["value"]) for r in prior)
        if base <= 0.0:
            notices.append(f"{metric}@{host}: non-positive baseline "
                           f"{base} — skipped")
            continue
        checks += 1
        direction = _metric_direction(metric)
        if direction < 0 and latest > base * (1.0 + tolerance):
            violations.append(
                f"{metric}@{host}: latest {latest:.6g} is "
                f"{latest / base:.2f}x the median of the last "
                f"{len(prior)} prior runs ({base:.6g}; +{tolerance:.0%} "
                "tolerance, lower is better)")
        elif direction > 0 and latest < base * (1.0 - tolerance):
            violations.append(
                f"{metric}@{host}: latest {latest:.6g} is "
                f"{latest / base:.2f}x the median of the last "
                f"{len(prior)} prior runs ({base:.6g}; -{tolerance:.0%} "
                "tolerance, higher is better)")
    return violations, notices, checks


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python scripts/perf_sentinel.py",
        description="Gate compiled-program cost budgets and bench "
                    "wall-clock history against regressions.")
    ap.add_argument("--budgets", default=None,
                    help="PERF_BUDGETS.json manifest path")
    ap.add_argument("--measure", action="store_true",
                    help="measure the budget workload in-process")
    ap.add_argument("--current", default=None,
                    help="saved measurement JSON instead of --measure")
    ap.add_argument("--history", default=None,
                    help="BENCH_HISTORY.jsonl path for wall-clock compare")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="history noise tolerance (default 0.25)")
    ap.add_argument("--min-runs", type=int, default=3,
                    help="history entries per (metric, host) needed "
                         "before comparing (default 3)")
    ap.add_argument("--save-measurement", default=None,
                    help="write the --measure result JSON here (budget "
                         "recalibration workflow)")
    args = ap.parse_args(argv)
    if not args.budgets and not args.history:
        ap.error("nothing to do: pass --budgets and/or --history")

    all_violations: List[str] = []
    if args.budgets:
        try:
            with open(args.budgets) as fh:
                budgets = json.load(fh)
        except (OSError, ValueError) as e:
            print(f"perf_sentinel: cannot read budgets {args.budgets!r}: "
                  f"{e}", file=sys.stderr)
            return 2
        if args.current:
            try:
                with open(args.current) as fh:
                    measured = json.load(fh)
            except (OSError, ValueError) as e:
                print(f"perf_sentinel: cannot read measurement "
                      f"{args.current!r}: {e}", file=sys.stderr)
                return 2
        elif args.measure:
            measured = measure(budgets.get("workload"))
        else:
            ap.error("--budgets needs --measure or --current FILE")
        if args.save_measurement:
            tmp = f"{args.save_measurement}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(measured, fh, indent=2)
            os.replace(tmp, args.save_measurement)
        violations, skipped, checks = compare_budgets(measured, budgets)
        for s in skipped:
            print(f"perf_sentinel: NOTICE {s}")
        print(f"perf_sentinel: budgets — {checks} check(s), "
              f"{len(violations)} violation(s), {len(skipped)} skipped "
              f"[platform {measured.get('platform', '?')}]")
        all_violations += violations

    if args.history:
        violations, notices, checks = check_history(
            args.history, tolerance=args.tolerance, min_runs=args.min_runs)
        for s in notices:
            print(f"perf_sentinel: NOTICE {s}")
        print(f"perf_sentinel: history — {checks} comparison(s), "
              f"{len(violations)} regression(s)")
        all_violations += violations

    for v in all_violations:
        print(f"perf_sentinel: REGRESSION {v}", file=sys.stderr)
    if all_violations:
        print("perf_sentinel: FAIL — see regressions above (recalibrate "
              "PERF_BUDGETS.json only for UNDERSTOOD cost changes, with "
              "the measurement attached)", file=sys.stderr)
        return 1
    print("perf_sentinel: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
