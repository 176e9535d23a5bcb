"""Loop kind `train`: grow trees one `Booster.update()` after the other until
the host clock passes --seconds, device drained at both ends and nowhere in
between (the program's own flag poll stays).

traffic parameters: warmup_trees, trace_trees, params (overrides)."""
import time

from harness import (build_booster, check_model, drain, end_window,
                     start_window)


def run(run):
    import jax
    tr = run.traffic
    bst, params, n_train, holdout = build_booster(run)
    programs = run.clock.programs
    t = time.perf_counter()
    for _ in range(tr["warmup_trees"]):
        bst.update()
    drain(bst)
    run.setup["warmup_s"] = time.perf_counter() - t
    run.say(f"warm-up: {tr['warmup_trees']} trees, "
            f"{run.clock.programs - programs} programs")
    traces = start_window(run)
    attempted = raised = 0
    stopped = False
    if run.trace:
        prof = run.profiler()
        prof.start()
        dispatch = []
        for i in range(tr["trace_trees"]):
            with jax.profiler.TraceAnnotation("bench.update", tree=i):
                t = time.perf_counter()
                stopped = bst.update()
                dispatch.append(time.perf_counter() - t)
            attempted += 1
        with jax.profiler.TraceAnnotation("bench.drain"):
            drain(bst)
        run.reduced = prof.stop()
        run.spans["update_return_s"] = dispatch
        run.spans["traced_trees"] = len(dispatch)

    t0 = time.perf_counter()
    stamps = []
    while not stopped:
        attempted += 1
        try:
            stopped = bst.update()
        except Exception as e:  # noqa: BLE001 — counted; the run says so
            run.say(f"update raised: {e!r}")
            raised += 1
        stamps.append(time.perf_counter() - t0)
        if stamps[-1] >= run.seconds:
            break
    drain(bst)
    wall = time.perf_counter() - t0
    compiled = end_window(run, traces)
    trees = len(stamps) - raised
    run.say(f"window: {trees} trees in {wall:.3f} s; host returned from "
            f"update() at (s, every 8th tree): "
            + " ".join(f"{s:.2f}" for s in stamps[7::8]))

    first = tr["warmup_trees"]
    checks, _, faults = check_model(run, bst, params, n_train, holdout,
                                    first_tree=first)
    checks.update(compiled, training_did_not_stop_early=not stopped)
    return {"metrics": {"train_s_per_tree": wall / max(trees, 1)},
            "attempted": attempted, "failed": raised + len(faults),
            "checks": checks, "correct": all(checks.values())}
