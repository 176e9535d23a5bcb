"""Loop kind `score`: one caller, closed loop — `Booster.predict(raw_score=
True)` on one raw float32 batch after the other until the host clock passes
--seconds.  Set-up trains the model in this process (a model loaded from a
file walks on the host).

traffic parameters: model_trees, batch_rows, distinct_batches, warmup_calls,
trace_calls, params (overrides)."""
import statistics
import time

import numpy as np

import reference_walk
from harness import (build_booster, check_model, drain, end_window,
                     start_window)


def run(run):
    import jax
    tr = run.traffic
    bst, params, n_train, holdout = build_booster(run)
    t = time.perf_counter()
    batch_rows = run.mix("batch_rows")
    batches = [run.make(batch_rows, stream=1 + i)["X"]
               for i in range(tr["distinct_batches"])]
    run.setup["generate_s"] += time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(run.mix("model_trees")):
        bst.update()
    drain(bst)
    run.setup["train_model_s"] = time.perf_counter() - t
    if run.rehearse:
        # the CPU rehearsal walks the same path: interpreted kernel, and the
        # tiny batch admitted
        type(bst)._DEVICE_PREDICT_OFF_CHIP = True
        type(bst)._DEVICE_PREDICT_MIN_ROWS = 1
    t = time.perf_counter()
    for i in range(tr["warmup_calls"]):
        bst.predict(batches[i % len(batches)], raw_score=True)
    run.setup["warmup_s"] = time.perf_counter() - t
    traces = start_window(run)
    first_out = {}
    off_device = 0

    def call(i):
        b = i % len(batches)
        out = bst.predict(batches[b], raw_score=True)
        nonlocal off_device
        if bst.last_predict_path != "device":
            off_device += 1
        if b not in first_out:
            first_out[b] = out
        return out

    calls = 0
    if run.trace:
        prof = run.profiler()
        prof.start()
        for i in range(tr["trace_calls"]):
            with jax.profiler.TraceAnnotation("bench.predict", call=i):
                call(calls)
            calls += 1
        run.reduced = prof.stop()

    t0 = time.perf_counter()
    lat = []
    raised = 0
    while True:
        t = time.perf_counter()
        try:
            call(calls)
        except Exception as e:  # noqa: BLE001 — counted; the run says so
            run.say(f"predict raised: {e!r}")
            raised += 1
        now = time.perf_counter()
        lat.append(now - t)
        calls += 1
        if now - t0 >= run.seconds:
            break
    wall = time.perf_counter() - t0
    compiled = end_window(run, traces)
    done = len(lat) - raised
    p95 = (statistics.quantiles(lat, n=20)[-1] if len(lat) >= 20
           else max(lat))
    run.say(f"window: {done} calls of {batch_rows} rows in {wall:.3f} s; "
            f"p95 over {len(lat)} samples, median "
            f"{statistics.median(lat) * 1e3:.2f} ms, max "
            f"{max(lat) * 1e3:.2f} ms")

    checks, dump, faults = check_model(run, bst, params, n_train, holdout)
    check = run.config["predict_check"]
    rng = np.random.default_rng([run.seed, 98])
    ok = True
    for b, out in first_out.items():
        idx = rng.choice(batch_rows, min(check["rows"], batch_rows),
                         replace=False)
        want = reference_walk.walk(dump, batches[b][idx])
        ok &= bool(np.allclose(out[idx], want, rtol=check["rtol"],
                               atol=check["atol"]))
        ok &= bool(np.array_equal(
            bst.predict(batches[b], raw_score=True), out))
    checks["every_batch_matches_reference_walk_and_repeats"] = ok
    checks["every_call_on_device"] = off_device == 0
    checks.update(compiled)
    return {"metrics": {"score_rows_per_s": done * batch_rows / wall,
                        "score_p95_ms": p95 * 1e3},
            "attempted": calls, "failed": raised + off_device,
            "checks": checks, "correct": all(checks.values())}
