"""Profile one training iteration on device and aggregate op durations
from the chrome trace (dev tool).

Usage: python scripts/profile_grow.py [rows]
       PROFILE_TASK=ranking python scripts/profile_grow.py [docs]
(BENCH_EXTRA_PARAMS merges into the training params for either task.)

PROFILE_TRACE_OUT=<path> additionally records the profiled iterations
through the telemetry span tracer and writes the host-side Chrome trace
there (load it in the same Perfetto tab as the device trace to line up
host phases against device ops).
"""
import glob
import gzip
import json
import os
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def main():
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.runtime import (CHECKOUT, configure_compile_cache,
                                      require_tpu)
    configure_compile_cache()
    # device op durations from a CPU trace would be CPU times
    dev = require_tpu("profile_grow (device trace)")
    print(f"profiling on {dev['device_kind']} x{dev['device_count']}")

    ranking = os.environ.get("PROFILE_TASK", "") == "ranking"
    default_rows = 2_270_000 if ranking else 10_500_000
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else default_rows
    params = {"objective": "binary", "num_leaves": 255, "learning_rate": 0.1,
              "max_bin": 63, "verbosity": -1,
              "use_quantized_grad": True, "num_grad_quant_bins": 64}
    extra = os.environ.get("BENCH_EXTRA_PARAMS", "")
    if extra:
        params.update(json.loads(extra))
    if ranking:
        import bench as B
        X, y, sizes = B.make_mslr_like(rows, 136)
        params["objective"] = "lambdarank"
        ds = lgb.Dataset(X, label=y, group=sizes)
    else:
        rs = np.random.RandomState(7)
        X = rs.randn(rows, 28).astype(np.float32)
        y = (rs.rand(rows) < 0.5).astype(np.float64)
        ds = lgb.Dataset(X, label=y)
    host_trace = os.environ.get("PROFILE_TRACE_OUT", "")
    from lightgbm_tpu import telemetry as tel
    if host_trace:
        tel.configure(enabled=True, trace_out=host_trace)
    bst = lgb.Booster(params, ds)
    for _ in range(3):      # warmup: compile everything
        bst.update()
    bst.engine.score.block_until_ready()

    # under chiprun_out/ so a run through the chip tool brings it back
    tdir = str(CHECKOUT / "chiprun_out" / "profile_grow")
    shutil.rmtree(tdir, ignore_errors=True)
    with jax.profiler.trace(tdir):
        t0 = time.time()
        for _ in range(3):
            bst.update()
        bst.engine.score.block_until_ready()
        wall = time.time() - t0
    print(f"3 iters wall: {wall*1e3:.1f} ms ({wall/3*1e3:.1f} ms/iter)")
    if host_trace:
        tel.flush()
        s = bst.telemetry_summary()
        print(f"host trace written to {host_trace}; phases:",
              {k: v["total_s"] for k, v in s.get("phases", {}).items()})

    files = glob.glob(f"{tdir}/**/*.trace.json.gz", recursive=True)
    if not files:
        print("no trace files found under", tdir)
        return
    agg = defaultdict(float)
    cnt = defaultdict(int)
    total = 0.0
    for fpath in files:
        with gzip.open(fpath, "rt") as fh:
            tr = json.load(fh)
        # device lanes only: pick pids whose process name mentions TPU/device
        dev_pids = set()
        for ev in tr.get("traceEvents", []):
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                nm = ev.get("args", {}).get("name", "")
                if "TPU" in nm or "Device" in nm or "/device" in nm:
                    dev_pids.add(ev.get("pid"))
        for ev in tr.get("traceEvents", []):
            if ev.get("ph") != "X" or ev.get("pid") not in dev_pids:
                continue
            name = ev.get("name", "?")
            dur = float(ev.get("dur", 0.0))
            agg[name] += dur
            cnt[name] += 1
            total += dur
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:35]
    print(f"total device op time {total/1e3:.1f} ms across {len(files)} files")
    for name, dur in top:
        print(f"{dur/1e3:9.2f} ms  x{cnt[name]:<5d} {name[:110]}")


if __name__ == "__main__":
    main()
