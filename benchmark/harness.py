"""What the loop kinds share (loops/ holds one file a loop kind and nothing
else): splitting the generated table, building the
Booster through the public entry points, and the checks that decide
`correct` (all outside the timed window, all in the benchmark's own NumPy)."""
import time

import numpy as np

import quality
import reference_walk


def split(run, data):
    """(train, holdout) dicts.  The holdout is the table's tail: a count of
    rows, or of whole queries where the generator gives query sizes."""
    hold = run.sized("holdout")
    X, y = data["X"], data["y"]
    if "sizes" in data:
        sizes = data["sizes"]
        q = len(sizes) - int(hold["queries"])
        d = int(sizes[:q].sum())
        return ({"X": X[:d], "y": y[:d], "sizes": sizes[:q]},
                {"X": X[d:], "y": y[d:], "sizes": sizes[q:]})
    d = len(y) - int(hold["rows"])
    return {"X": X[:d], "y": y[:d]}, {"X": X[d:], "y": y[d:]}


def build_booster(run):
    """Generate the cell's table, split it, lgb.Dataset -> construct ->
    lgb.Booster, timed to the moment the bins are on the device(s).
    -> (booster, params, rows trained on, holdout)"""
    import jax
    import lightgbm_tpu as lgb
    t = time.perf_counter()
    train, holdout = split(run, run.make(run.sized("rows")))
    run.setup["generate_s"] = time.perf_counter() - t
    params = {**run.sized("params"), **run.traffic.get("params", {}),
              "verbosity": -1}
    t = time.perf_counter()
    ds = lgb.Dataset(train["X"], label=train["y"], group=train.get("sizes"),
                     params=params)
    ds.construct()
    t_host = time.perf_counter()
    bst = lgb.Booster(params, ds)
    jax.block_until_ready(bst.engine.dd.bins)
    run.setup["dataset_construct_s"] = time.perf_counter() - t
    run.setup["dataset_host_bin_s"] = t_host - t
    return bst, params, len(train["y"]), holdout


def drain(bst):
    import jax
    jax.block_until_ready(bst.engine.score)


def recompile_counts():
    from lightgbm_tpu import telemetry
    return dict(telemetry.recompile_counts())


def start_window(run):
    """Set-up ends here.  -> the program's trace counts, for end_window."""
    run.setup["compile_s"] = run.clock.backend_s
    run.setup["programs_compiled_or_fetched"] = run.clock.programs
    traces = recompile_counts()
    run.start_window()
    return traces


def end_window(run, traces):
    """Straight after the window's last instant, before any check compiles
    a program of its own.  -> the two checks on compilation."""
    return {"no_program_compiled_in_window": run.compiled_in_window() == 0,
            "no_retrace_after_warmup": recompile_counts() == traces}


def check_model(run, bst, params, n_train, holdout, first_tree=0):
    """The checks every cell makes on the model it grew or scored with.
    -> (checks dict of name -> bool, dump, list of trees at fault)"""
    gate = run.sized("gate")
    check = run.config["predict_check"]
    checks = {}
    dump = bst.dump_model()
    n_trees = len(dump["tree_info"])
    faults, worst = reference_walk.tree_faults(
        dump, n_train, params["num_leaves"], first=first_tree,
        count_slack=run.sized("leaf_count_slack_rows"))
    checks["trees_full_and_counts_sum_to_n"] = not faults
    checks["enough_trees_for_gate"] = n_trees >= gate["trees"]
    if params.get("tree_learner", "serial") != "serial":
        mesh = bst.engine.mesh
        checks["mesh_takes_the_cells_chips"] = (
            mesh is not None and mesh.devices.size == run.cell["chips"])
    raw = bst.predict(holdout["X"], raw_score=True,
                      num_iteration=gate["trees"])
    checks["holdout_predict_on_device"] = (
        run.rehearse or bst.last_predict_path == "device")
    score = quality.evaluate(gate["metric"], holdout["y"], raw,
                             holdout.get("sizes"))
    checks["quality_gate"] = score >= gate["min"]
    rng = np.random.default_rng([run.seed, 99])
    idx = rng.choice(len(raw), min(check["rows"], len(raw)), replace=False)
    want = reference_walk.walk(dump, holdout["X"][idx], gate["trees"])
    diff = float(np.max(np.abs(raw[idx] - want)))
    checks["predict_matches_reference_walk"] = bool(
        np.all(np.isfinite(raw)) and np.allclose(
            raw[idx], want, rtol=check["rtol"], atol=check["atol"]))
    run.say(f"model: {n_trees} trees, {len(faults)} at fault "
            f"(tree, leaves, count sum - N: {faults[:6]}; largest "
            f"|count sum - N| {worst}); "
            f"{gate['metric']} of first {gate['trees']} trees "
            f"{score:.5f} (gate {gate['min']}); predict path "
            f"{bst.last_predict_path!r}; max |predict - walk| {diff:.3g}")
    return checks, dump, faults
