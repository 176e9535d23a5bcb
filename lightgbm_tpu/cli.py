"""Command-line application: config-file driven train/predict.

Reference: src/application/application.cpp:217 (Application::Run dispatching
task=train/predict), src/io/config.cpp (KV parsing: command-line pairs
override the config file).

Usage:
    python -m lightgbm_tpu config=train.conf [key=value ...]
    python -m lightgbm_tpu task=train data=train.csv objective=binary ...
    python -m lightgbm_tpu task=predict data=test.csv input_model=model.txt
    python -m lightgbm_tpu task=pipeline data=train.csv fresh_data=new.csv \
        valid=holdout.csv serve_fleet_dir=/srv/fleet observe_window_s=30
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

from .basic import Booster, Dataset
from .config import resolve_aliases
from .engine import train as engine_train
from .utils.log import LightGBMError, log_info


def parse_config_file(path: str) -> Dict[str, str]:
    """key = value lines; '#' comments (reference: config file format)."""
    out: Dict[str, str] = {}
    with open(path) as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line or "=" not in line:
                continue
            k, _, v = line.partition("=")
            out[k.strip()] = v.strip()
    return out


def parse_args(argv: List[str]) -> Dict[str, str]:
    params: Dict[str, str] = {}
    cli: Dict[str, str] = {}
    for tok in argv:
        if "=" not in tok:
            raise LightGBMError(f"unknown argument {tok!r} (expected key=value)")
        k, _, v = tok.partition("=")
        cli[k.strip()] = v.strip()
    if "config" in cli:
        params.update(parse_config_file(cli.pop("config")))
    params.update(cli)   # command line overrides the config file
    return params


def _coerce(params: Dict[str, str]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in params.items():
        if isinstance(v, str):
            low = v.lower()
            if low in ("true", "false"):
                out[k] = low == "true"
                continue
            try:
                out[k] = int(v)
                continue
            except ValueError:
                pass
            try:
                out[k] = float(v)
                continue
            except ValueError:
                pass
        out[k] = v
    return out


def _maybe_init_network(params: Dict[str, Any]) -> int:
    """machines/num_machines wiring (reference: the Dask module's machine
    list assembly, python-package/lightgbm/dask.py:196-215, and the socket
    linker's find-own-rank, src/network/linkers_socket.cpp:83): each
    machine locates itself in the `machines` list (or machine_list file)
    by local address + local_listen_port, then the whole job connects via
    jax.distributed with entry 0 as the coordinator.  Returns this
    process's rank (0 when single-machine)."""
    import socket

    nm = int(params.get("num_machines", 1) or 1)
    if nm <= 1:
        return 0
    machines = str(params.get("machines", "") or "")
    if not machines:
        mlf = params.get("machine_list_filename", "")
        if mlf:
            if not Path(str(mlf)).exists():
                raise LightGBMError(f"machine list file {mlf!r} not found")
            rows = [ln.split() for ln in
                    Path(str(mlf)).read_text().splitlines() if ln.strip()]
            machines = ",".join(f"{r[0]}:{r[1]}" for r in rows if len(r) >= 2)
    if not machines:
        raise LightGBMError(
            "num_machines > 1 requires machines= or machine_list_filename= "
            "(reference: Network::Init needs the machine list)")
    entries = [m.strip() for m in machines.split(",") if m.strip()]
    if len(entries) < nm:
        raise LightGBMError(
            f"machines lists {len(entries)} entries < num_machines={nm}")
    entries = entries[:nm]
    env_rank = os.environ.get("LIGHTGBM_TPU_MACHINE_RANK")
    if env_rank is not None:
        try:
            rank = int(env_rank)
        except ValueError:
            raise LightGBMError(
                f"LIGHTGBM_TPU_MACHINE_RANK={env_rank!r} is not an integer")
        if not 0 <= rank < nm:
            raise LightGBMError(
                f"LIGHTGBM_TPU_MACHINE_RANK={rank} out of range for "
                f"num_machines={nm} (ranks are 0-based)")
    else:
        port = str(params.get("local_listen_port", 12400))
        local = {"127.0.0.1", "localhost", socket.gethostname()}
        try:
            local.add(socket.gethostbyname(socket.gethostname()))
        except OSError:
            pass

        def _is_local(addr: str) -> bool:
            if addr in local:
                return True
            # binding succeeds only on a local interface address — covers
            # hosts whose hostname maps to 127.0.1.1-style entries while
            # the machines list carries the interface IP (the reference's
            # linkers_socket.cpp enumerates interfaces for the same reason)
            try:
                with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                    s.bind((addr, 0))
                local.add(addr)
                return True
            except OSError:
                return False
        # exact ip:port match first (localhost simulations need the port to
        # disambiguate), then address-only (distinct real hosts)
        rank = next((i for i, e in enumerate(entries)
                     if _is_local(e.rsplit(":", 1)[0])
                     and e.rsplit(":", 1)[-1] == port), None)
        if rank is None:
            addr_matches = [i for i, e in enumerate(entries)
                            if e.rsplit(":", 1)[0] in local]
            if len(addr_matches) > 1:
                # several local entries but none with our listen port:
                # guessing one would give two processes the same rank and
                # hang the coordinator — fail loud instead
                raise LightGBMError(
                    f"local_listen_port={port} matches none of the local "
                    f"machine entries {[entries[i] for i in addr_matches]}; "
                    "set local_listen_port to this process's entry or set "
                    "LIGHTGBM_TPU_MACHINE_RANK")
            rank = addr_matches[0] if addr_matches else None
        if rank is None:
            raise LightGBMError(
                "this machine is not in the machines list; set "
                "LIGHTGBM_TPU_MACHINE_RANK to pick a rank explicitly")
    from .parallel.launcher import init_distributed
    init_distributed(coordinator_address=entries[0], num_processes=nm,
                     process_id=rank)
    log_info(f"machine rank {rank}/{nm} connected (coordinator "
             f"{entries[0]})")
    return rank


def run_train(params: Dict[str, Any]) -> None:
    data_path = params.get("data")
    if not data_path:
        raise LightGBMError("task=train requires data=<file>")
    _maybe_init_network(params)
    ds = Dataset(str(data_path), params=dict(params))
    valid_sets, valid_names = [], []
    vspec = params.get("valid", params.get("valid_data", ""))
    if vspec:
        for vp in str(vspec).split(","):
            vp = vp.strip()
            if vp:
                valid_sets.append(Dataset(vp, reference=ds,
                                          params=dict(params)))
                valid_names.append(vp.rsplit("/", 1)[-1])
    num_rounds = int(params.get("num_iterations", 100))
    bst = engine_train(params, ds, num_boost_round=num_rounds,
                       valid_sets=valid_sets or None,
                       valid_names=valid_names or None,
                       init_model=params.get("input_model") or None)
    out_model = str(params.get("output_model", "LightGBM_model.txt"))
    bst.save_model(out_model)
    log_info(f"Finished training; model saved to {out_model}")
    stats = getattr(ds, "ingest_stats", None) or {}
    log_info("ingest summary: mode=%s cache_hit=%s"
             % (stats.get("mode", "inmem"),
                stats.get("cache_hit", False)))
    from . import telemetry as _telemetry
    if _telemetry.enabled():
        import json
        s = bst.telemetry_summary()
        line = {k: s[k] for k in ("train", "memory", "telemetry_out",
                                  "trace_out") if k in s}
        line["recompiles"] = {k: v["compiles"]
                              for k, v in s.get("recompiles", {}).items()}
        log_info(f"telemetry summary: {json.dumps(line)}")


def run_predict(params: Dict[str, Any]) -> None:
    data_path = params.get("data")
    model_path = params.get("input_model")
    if not data_path or not model_path:
        raise LightGBMError("task=predict requires data=<file> and "
                            "input_model=<file>")
    from .dataset_io import load_data_file
    X, label, _ = load_data_file(str(data_path), dict(params))
    bst = Booster(model_file=str(model_path))
    if X.shape[1] == bst.num_feature() - 1 and label is not None:
        # the file carried no label column: undo the default label strip
        # (reference predicts on files with the training-data format, label
        # included and ignored; a label-less file is also accepted)
        X = np.column_stack([label, X])
    raw = bool(params.get("predict_raw_score", False))
    leaf = bool(params.get("predict_leaf_index", False))
    contrib = bool(params.get("predict_contrib", False))
    pred = bst.predict(X, raw_score=raw, pred_leaf=leaf, pred_contrib=contrib)
    out = str(params.get("output_result", "LightGBM_predict_result.txt"))
    pred2 = np.atleast_2d(np.asarray(pred))
    if pred2.shape[0] == 1 and np.asarray(pred).ndim == 1:
        pred2 = pred2.T
    # tmp + os.replace (the robustness checkpoint helper, streaming so a
    # many-million-row output never materializes in RAM): a killed predict
    # job never leaves a truncated result file behind
    from .robustness.checkpoint import atomic_write_lines
    atomic_write_lines(out, (
        "\t".join(f"{v:.18g}" for v in np.atleast_1d(row)) + "\n"
        for row in pred2))
    log_info(f"Finished prediction; results saved to {out}")


def run_refit(params: Dict[str, Any]) -> None:
    """Refit leaf values of an existing model on new data (reference:
    Application task=refit, application.cpp:236; GBDT::RefitTree)."""
    data_path = params.get("data")
    model_path = params.get("input_model")
    if not data_path or not model_path:
        raise LightGBMError("task=refit requires data=<file> and "
                            "input_model=<file>")
    from .dataset_io import load_data_file
    X, label, _ = load_data_file(str(data_path), dict(params))
    if label is None:
        raise LightGBMError("task=refit requires labeled data")
    bst = Booster(model_file=str(model_path), params=dict(params))
    out = bst.refit(X, label,
                    decay_rate=float(params.get("refit_decay_rate", 0.9)))
    out_model = str(params.get("output_model", "LightGBM_model.txt"))
    out.save_model(out_model)
    log_info(f"Finished refit; model saved to {out_model}")


def run_save_binary(params: Dict[str, Any]) -> None:
    """Bin the data file once and save the reusable binary dataset
    (reference: Application task=save_binary, application.cpp:217)."""
    data_path = params.get("data")
    if not data_path:
        raise LightGBMError("task=save_binary requires data=<file>")
    ds = Dataset(str(data_path), params=dict(params))
    ds.construct()
    out = str(params.get("output_model", str(data_path) + ".bin"))
    ds.save_binary(out)
    log_info(f"Finished save_binary; dataset saved to {out}")


def run_convert_model(params: Dict[str, Any]) -> None:
    """Convert a model file to JSON (reference: task=convert_model,
    application.cpp; the reference's if-else C++ codegen is a non-goal —
    the JSON dump carries the same tree structure)."""
    model_path = params.get("input_model")
    if not model_path:
        raise LightGBMError("task=convert_model requires input_model=<file>")
    import json
    bst = Booster(model_file=str(model_path))
    out = str(params.get("convert_model", params.get(
        "output_model", "model_convert.json")))
    from .robustness.checkpoint import atomic_open
    with atomic_open(out, "w") as fh:
        json.dump(bst.dump_model(), fh, indent=2)
    log_info(f"Finished convert_model; JSON saved to {out}")


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print(__doc__)
        return 1
    from .runtime import configure_compile_cache
    configure_compile_cache()
    params = _coerce(resolve_aliases(parse_args(list(argv))))
    task = str(params.get("task", "train"))
    if task == "train":
        run_train(params)
    elif task in ("predict", "prediction", "test"):
        run_predict(params)
    elif task == "refit":
        run_refit(params)
    elif task == "save_binary":
        run_save_binary(params)
    elif task == "convert_model":
        run_convert_model(params)
    elif task == "pipeline":
        # closed-loop freshness: train → refit-on-fresh-data → validation
        # gate → atomic fleet promotion → observe/auto-rollback
        # (docs/ROBUSTNESS.md "Closed-loop freshness")
        from .pipeline import run_pipeline
        report = run_pipeline(params)
        return 0 if report.get("ok") else 1
    elif task == "serve":
        # online inference server (docs/SERVING.md); blocks until SIGTERM.
        # serve_replicas > 1 runs the replica-fleet supervisor (restart
        # with backoff, fleet-wide promotion, fanout front)
        from .serving.server import run_server
        return run_server(params)
    else:
        raise LightGBMError(f"unknown task {task!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
