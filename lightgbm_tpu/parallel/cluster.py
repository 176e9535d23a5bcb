"""Local multi-process cluster launcher — the Dask orchestration analog.

Reference: python-package/lightgbm/dask.py — `_train` (dask.py:124-215)
discovers the workers holding data parts, assembles the `machines=` list,
opens ports, and runs `_train_part` on every worker; the model of one worker
becomes the result. The TPU-native redesign:

  * worker discovery / machines list  -> a free localhost port +
    `jax.distributed.initialize` (the process mesh IS the cluster)
  * `client.scatter` of data parts    -> sharded FILE ingest: every rank
    loads only its own row range (parallel/dist_data.py; queries stay
    whole on one rank for ranking)
  * `_train_part` per worker          -> the SAME SPMD `lgb.train` call in
    every process with `tree_learner=data|feature|voting`
  * result from one worker            -> rank 0 serializes the model (all
    ranks hold identical trees — histogram psum makes training replicated)

`train_distributed` below packages that recipe as a SUPERVISOR (the
reference Network layer survives flaky links; this survives flaky
processes, docs/ROBUSTNESS.md):

  * all worker processes are polled CONCURRENTLY — the first nonzero exit
    kills the peers and fails the attempt immediately instead of blocking
    on rank order until the full timeout;
  * every worker heartbeats a per-rank file each iteration
    (robustness/heartbeat.py); a stale beat past ``hang_timeout`` reaps a
    worker wedged inside a collective;
  * with ``dist_retries > 0`` a failed cohort is relaunched (backoff
    ``dist_backoff`` seconds, doubling per retry) from the NEWEST VALID
    snapshot rank 0 wrote (``snapshot_freq`` checkpoints), resuming
    bit-identically instead of losing the run.

On a real TPU pod, run the body yourself instead: one process per host
executing `lgb.init_distributed()` + `lgb.train(...)` (see
parallel/launcher.py) — there is deliberately no pod-ssh automation here.

The sklearn-style `DaskLGBM{Classifier,Regressor,Ranker}` wrappers are NOT
mirrored: they exist to adapt dask collections to sklearn's fit(X, y), but
the scatter mechanism here is file sharding, so the natural unit is the
data path + params dict that `train_distributed` already takes.
"""
from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from ..config import resolve_aliases
from ..utils.log import LightGBMError, log_info, log_warning

_WORKER = r"""
import json, os, sys
spec = json.load(open(sys.argv[1]))
rank = int(sys.argv[2])
# platform and compile cache arrive in the environment the supervisor built
# (runtime.child_env): JAX_PLATFORMS is always set here, never inherited
import jax
if os.environ["JAX_PLATFORMS"] == "cpu":
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(spec["coordinator"], num_processes=spec["nproc"],
                           process_id=rank)
import lightgbm_tpu as lgb
from lightgbm_tpu.robustness.heartbeat import heartbeat_callback
ds = lgb.Dataset(spec["data"])
valid_sets = [lgb.Dataset(p, reference=ds) for p in spec["valid"]]
evals = {}
cbs = [lgb.record_evaluation(evals)] if valid_sets else []
cbs.append(heartbeat_callback(
    os.path.join(spec["heartbeat_dir"], "hb_%d" % rank)))
bst = lgb.train(spec["params"], ds, num_boost_round=spec["rounds"],
                valid_sets=valid_sets,
                valid_names=spec["valid_names"] or None,
                callbacks=cbs)
if rank == 0:
    out = {"model": bst.model_to_string(), "evals": evals,
           "best_iteration": bst.best_iteration}
    import lightgbm_tpu.telemetry as _tel
    if _tel.enabled():   # however the params spelled it (aliases, sinks)
        out["telemetry"] = bst.telemetry_summary()
    tmp = sys.argv[3] + ".tmp"
    json.dump(out, open(tmp, "w"))
    os.replace(tmp, sys.argv[3])
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            size = fh.tell()
            fh.seek(max(0, size - n))
            return fh.read().decode(errors="replace")
    except OSError:
        return "<no worker log>"


def _run_cohort(spec: Dict[str, Any], td: str, out_path: str, attempt: int,
                timeout: float, hang_timeout: Optional[float],
                startup_grace: float, python: str,
                env: Dict[str, str]) -> Optional[str]:
    """Launch one worker cohort and babysit it to completion.

    Returns None on success or a failure description.  All processes are
    polled together: the first nonzero exit — or a heartbeat gone stale
    past ``hang_timeout`` — kills every peer at once (the old behavior
    awaited rank 0 first, so a crashed rank 1 left the driver blocked for
    the full timeout)."""
    n = spec["nproc"]
    spec_path = os.path.join(td, f"spec_{attempt}.json")
    # atomic: a worker that starts early must never read a half-written spec
    from ..robustness.checkpoint import atomic_open
    with atomic_open(spec_path, "w") as fh:
        json.dump(spec, fh)
    for r in range(n):
        for stale in (out_path, os.path.join(td, f"hb_{r}")):
            if os.path.exists(stale):
                os.unlink(stale)
    log_paths = [os.path.join(td, f"worker_{r}.log") for r in range(n)]
    logs = [open(p, "ab") for p in log_paths]
    procs = [subprocess.Popen(
        [python, "-c", _WORKER, spec_path, str(r), out_path],
        env=env, stdout=logs[r], stderr=subprocess.STDOUT)
        for r in range(n)]
    start = time.monotonic()
    err: Optional[str] = None
    try:
        while True:
            rcs = [p.poll() for p in procs]
            bad = next(((r, rc) for r, rc in enumerate(rcs)
                        if rc not in (None, 0)), None)
            if bad is not None:
                err = (f"worker {bad[0]}/{n} failed (exit {bad[1]}):\n"
                       f"{_tail(log_paths[bad[0]])}")
                break
            if all(rc == 0 for rc in rcs):
                break
            elapsed = time.monotonic() - start
            if elapsed > timeout:
                err = f"cohort timed out after {timeout:.0f}s"
                break
            if hang_timeout is not None:
                now = time.time()
                for r in range(n):
                    if rcs[r] is not None:
                        continue
                    hb = os.path.join(td, f"hb_{r}")
                    if os.path.exists(hb):
                        age = now - os.path.getmtime(hb)
                        if age > hang_timeout:
                            err = (f"worker {r}/{n} heartbeat stale "
                                   f"({age:.0f}s > hang_timeout="
                                   f"{hang_timeout:.0f}s); presumed hung")
                            break
                    elif elapsed > max(startup_grace, hang_timeout):
                        err = (f"worker {r}/{n} produced no heartbeat "
                               f"within {elapsed:.0f}s; presumed hung "
                               "during startup")
                        break
                if err:
                    break
            time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:  # pragma: no cover
                pass
        for f in logs:
            f.close()
    return err


def train_distributed(params: Dict[str, Any], data_path: str,
                      num_boost_round: int = 100,
                      num_processes: int = 2,
                      valid_paths: Optional[List[str]] = None,
                      valid_names: Optional[List[str]] = None,
                      platform: str = "cpu",
                      timeout: float = 1200.0,
                      python: str = sys.executable,
                      hang_timeout: Optional[float] = None,
                      startup_grace: float = 180.0):
    """Train over `num_processes` local worker processes, each ingesting its
    own row shard of `data_path` (and of each `valid_paths` entry), and
    return the finished Booster.

    The dask.py `_train` analog for one machine, run under a supervisor:
    workers connect through `jax.distributed`, shard the file by rows
    (whole query groups per rank for ranking objectives), and run the
    standard data-parallel SPMD training program. Defaults to
    `tree_learner=data` when params don't choose one. `evals_result_` and
    `best_iteration` from rank 0 are set on the returned Booster.

    Fault tolerance (docs/ROBUSTNESS.md): `timeout` bounds each attempt;
    `hang_timeout` (seconds, None = off) reaps workers whose per-iteration
    heartbeat goes stale; params `dist_retries`/`dist_backoff` relaunch a
    failed cohort from the newest valid snapshot (rank 0 checkpoints every
    `snapshot_freq` iterations — defaulted on when retries are enabled)."""
    if num_processes < 2:
        raise LightGBMError("train_distributed needs num_processes >= 2; "
                            "call lgb.train directly for one process")
    if not Path(data_path).exists():
        raise LightGBMError(f"data_path not found: {data_path}")
    params = resolve_aliases(dict(params))
    params.setdefault("tree_learner", "data")
    retries = int(params.get("dist_retries", 0) or 0)
    backoff = float(params.get("dist_backoff", 2.0) or 0.0)
    if retries > 0:
        # retry without snapshots would replay the whole run — checkpoint
        # often enough that a relaunch loses at most ~10% of the work
        params.setdefault("snapshot_freq", max(1, num_boost_round // 10))
    from ..runtime import child_env, require_chips
    require_chips(num_processes, platform,
                  f"train_distributed(num_processes={num_processes})")
    td = tempfile.mkdtemp(prefix="lgb_tpu_cluster_")
    params.setdefault("output_model", os.path.join(td, "ckpt.txt"))
    output_model = str(params["output_model"])
    env = child_env(platform)
    env["PYTHONUNBUFFERED"] = "1"
    spec = {
        "nproc": num_processes,
        "params": dict(params),
        "data": str(data_path),
        "valid": [str(p) for p in (valid_paths or [])],
        "valid_names": list(valid_names) if valid_names else None,
        "rounds": int(num_boost_round),
        "heartbeat_dir": td,
    }
    out_path = os.path.join(td, "result.json")
    try:
        attempt = 0
        while True:
            # fresh port per attempt: the previous coordinator socket may
            # still be in TIME_WAIT
            spec["coordinator"] = f"localhost:{_free_port()}"
            err = _run_cohort(spec, td, out_path, attempt, timeout,
                              hang_timeout, startup_grace, python, env)
            if err is None:
                break
            attempt += 1
            if attempt > retries:
                raise LightGBMError(
                    f"train_distributed failed after {attempt} attempt(s) "
                    f"({retries} retries allowed): {err}")
            delay = backoff * (2 ** (attempt - 1))
            log_warning(f"train_distributed attempt {attempt}/{retries + 1} "
                        f"failed: {err.splitlines()[0]} — relaunching in "
                        f"{delay:.1f}s")
            if delay > 0:
                time.sleep(delay)
            from ..robustness.checkpoint import latest_valid_snapshot
            # params check included: a stale snapshot from an earlier run
            # with different training params would fail every worker's
            # load_checkpoint and burn all retries. Fall back to the
            # user's own resume_from (if any) when this run hasn't sealed
            # a newer snapshot yet — never silently discard a requested
            # continuation
            snap = (latest_valid_snapshot(output_model,
                                          params=spec["params"],
                                          expect_processes=num_processes)
                    or params.get("resume_from") or None)
            wp = dict(spec["params"])
            if snap is not None:
                wp["resume_from"] = snap
                log_info(f"train_distributed: cohort will resume from {snap}")
            else:
                wp.pop("resume_from", None)
                log_info("train_distributed: no valid snapshot; cohort "
                         "restarts from scratch")
            spec["params"] = wp
        with open(out_path) as fh:
            result = json.load(fh)
    finally:
        shutil.rmtree(td, ignore_errors=True)
    from ..basic import Booster
    bst = Booster(model_str=result["model"])
    bst.evals_result_ = result["evals"]
    if result.get("best_iteration"):
        bst.best_iteration = result["best_iteration"]
    if result.get("telemetry"):
        # rank 0's telemetry rollup (iteration records, straggler reports,
        # recompiles); Booster.telemetry_summary() answers from this when
        # set, since the driver process's own registry saw no training
        bst.telemetry_summary_ = result["telemetry"]
    log_info(f"train_distributed: {num_processes} workers done, "
             f"{bst.num_trees()} trees")
    return bst
