"""train() and cv().

Reference: python-package/lightgbm/engine.py — train (:109), cv (:626), CVBooster (:356).
"""
from __future__ import annotations

import collections
import copy
from typing import Any, Callable, Dict, Iterable, List, Optional, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .callback import CallbackEnv, EarlyStopException
from .config import Config, resolve_aliases
from .robustness import chaos as _chaos
from .runtime import configure_compile_cache
from .utils.log import LightGBMError, log_info, log_warning


def train(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          feval: Optional[Union[Callable, List[Callable]]] = None,
          init_model: Optional[Union[str, Booster]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          resume_from: Optional[str] = None) -> Booster:
    """Train a booster (reference: engine.py:109).

    ``resume_from`` (or param ``resume_from``/``resume``) names a
    checkpoint written by ``snapshot_freq`` training: the manifest is
    validated (checksums, params identity, topology), the trees become
    the init model, the engine state (score, RNG streams) is restored,
    and the loop continues from the snapshot iteration BIT-IDENTICALLY to
    a run that was never interrupted (docs/ROBUSTNESS.md).  Callback
    state is NOT checkpointed: an early-stopping window restarts at the
    resume point, so runs that stop early may stop differently."""
    configure_compile_cache()
    params = resolve_aliases(dict(params or {}))
    # popped so the resumed booster's params (and saved params block) match
    # the uninterrupted run's exactly
    resume_from = resume_from or params.pop("resume_from", None) or None
    params.pop("resume_from", None)
    if "num_iterations" in params:
        num_boost_round = int(params["num_iterations"])
    params["num_iterations"] = num_boost_round
    if params.get("objective") is None:
        params.setdefault("objective", "regression")
    first_metric_only = bool(params.get("first_metric_only", False))

    if init_model is not None and isinstance(init_model, str):
        init_model = Booster(model_file=init_model)

    start_iteration = 0
    resume_state = None
    if resume_from:
        if init_model is not None:
            raise LightGBMError(
                "pass either init_model or resume_from, not both (a "
                "checkpoint already carries its model)")
        from .robustness.checkpoint import load_checkpoint
        model_str, manifest, resume_state = load_checkpoint(
            str(resume_from), params=params)
        init_model = Booster(model_str=model_str)
        start_iteration = int(manifest["iteration"])
        if start_iteration >= num_boost_round:
            log_warning(
                f"resume_from checkpoint is at iteration {start_iteration} "
                f">= num_boost_round={num_boost_round}; nothing to train")
        log_info(f"resuming from {resume_from} at iteration "
                 f"{start_iteration}/{num_boost_round}")

    booster = Booster(params=params, train_set=train_set)
    ingest_stats = getattr(train_set, "ingest_stats", None)
    if ingest_stats:
        # one-line ingest provenance next to the training log: which
        # loader built the binned data and whether the cache served it
        log_info(
            "ingest: mode=%s cache_hit=%s rows=%s rows/s=%s "
            "peak_rss_gb=%.2f" % (
                ingest_stats.get("mode"), ingest_stats.get("cache_hit"),
                ingest_stats.get("rows"), ingest_stats.get("rows_per_s"),
                ingest_stats.get("peak_rss_bytes", 0) / 1e9))
    if init_model is not None:
        # true continued training: load the trees into the engine and keep
        # boosting (reference: boosting.cpp:42-90, gbdt.cpp:259-263); trees are
        # deep-copied so DART rescaling cannot mutate the caller's booster
        if init_model._engine is not None:
            trees = copy.deepcopy(list(init_model.engine.models))
            k = init_model.engine.num_tree_per_iteration
        else:
            trees = copy.deepcopy(list(init_model._loaded_trees.trees))
            k = init_model._loaded_trees.num_tree_per_iteration
        booster.engine.load_init_model(
            trees, k, skip_score_rebuild=resume_state is not None)
    if resume_state is not None:
        from .robustness.checkpoint import restore_state
        restore_state(booster, resume_state)
    if valid_sets:
        if valid_names is not None and len(valid_names) != len(valid_sets):
            raise LightGBMError(
                f"Length of valid_names ({len(valid_names)}) does not match "
                f"valid_sets ({len(valid_sets)})")
        names = valid_names or [f"valid_{i}" for i in range(len(valid_sets))]
        for vs, name in zip(valid_sets, names):
            if vs is train_set:
                # training data as its own valid set (reference naming)
                booster.engine.add_valid(train_set, "training",
                                         booster.engine.train_metrics)
            else:
                booster.add_valid(vs, name)

    callbacks = list(callbacks or [])
    es_rounds = params.get("early_stopping_round", 0)
    if es_rounds and int(es_rounds) > 0 and valid_sets:
        callbacks.append(callback_mod.early_stopping(
            int(es_rounds), first_metric_only,
            verbose=params.get("verbosity", 1) >= 1,
            min_delta=params.get("early_stopping_min_delta", 0.0)))
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks
                       if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    snapshot_freq = int(params.get("snapshot_freq", -1) or -1)
    snapshot_keep = int(params.get("snapshot_keep", -1) or -1)
    output_model = str(params.get("output_model", "LightGBM_model.txt"))

    # profile_out wraps the whole boosting loop in a device-trace session
    # (jax.profiler + host spans merged onto one Perfetto timeline,
    # docs/OBSERVABILITY.md "Cost model & profiling")
    profile_dir = str(params.get("profile_out", "") or "")
    profile_session = None
    if profile_dir:
        from .telemetry.profile import ProfileSession
        profile_session = ProfileSession(profile_dir).start()

    evaluation_result_list: List = []
    try:
        for i in range(start_iteration, num_boost_round):
            for cb in callbacks_before:
                cb(CallbackEnv(model=booster, params=params, iteration=i,
                               begin_iteration=0,
                               end_iteration=num_boost_round,
                               evaluation_result_list=[]))
            finished = booster.update()
            if snapshot_freq > 0 and (i + 1) % snapshot_freq == 0:
                # periodic crash-consistent checkpoint: tmp + os.replace
                # with a sealed manifest, resumable via resume_from
                # (reference: gbdt.cpp:259-263 Train snapshots;
                # docs/ROBUSTNESS.md)
                booster.checkpoint(output_model, i + 1, keep=snapshot_keep)
            _chaos.maybe_kill(i + 1)

            evaluation_result_list: List = []
            if valid_sets is not None or feval is not None:
                if booster.engine.valid_sets:
                    evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in callbacks_after:
                    cb(CallbackEnv(model=booster, params=params, iteration=i,
                                   begin_iteration=0,
                                   end_iteration=num_boost_round,
                                   evaluation_result_list=evaluation_result_list))
            except EarlyStopException as e:
                booster.best_iteration = e.best_iteration + 1
                evaluation_result_list = e.best_score or []
                break
            if finished:
                log_info("Stopped training because there are no more leaves "
                         "that meet the split requirements")
                break
        else:
            # loop ran to num_boost_round: growth may have stopped between
            # the engine's deferred finished-flag polls — drop any trailing
            # no-op trees so the saved model matches the reference's
            # immediate stop
            booster.engine._trim_trailing_trivial()
        booster.engine.flush_nan_guard()
    finally:
        if profile_session is not None:
            # the session must never cost the caller a trained booster —
            # an export/merge failure (ENOSPC, unreadable shard) logs and
            # moves on, and never masks an exception from the loop above
            try:
                info = profile_session.stop()
                log_info(f"profile: merged host+device timeline at "
                         f"{info['merged_trace']} ({info['merged_events']} "
                         f"events, {info['shards']} shards)")
            except Exception as e:  # noqa: BLE001
                log_warning(f"profile: session export failed "
                            f"({type(e).__name__}: {e}) — training result "
                            "is unaffected")

    if evaluation_result_list:
        best: Dict[str, Dict[str, float]] = collections.defaultdict(dict)
        for item in evaluation_result_list:
            best[item[0]][item[1]] = item[2]
        booster.best_score = dict(best)
    from . import telemetry as _tel
    if _tel.enabled():
        # write the configured Chrome-trace file (trace_out param) now that
        # the span buffer covers the whole run
        _tel.flush()
    return booster


class CVBooster:
    """Ensemble of per-fold boosters (reference: engine.py:356)."""

    def __init__(self, model_file: Optional[str] = None):
        self.boosters: List[Booster] = []
        self.best_iteration = -1
        if model_file is not None:
            import json
            blob = json.loads(open(model_file).read())
            self.best_iteration = blob["best_iteration"]
            self.boosters = [Booster(model_str=s) for s in blob["boosters"]]

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name: str):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function

    def save_model(self, filename: str) -> "CVBooster":
        import json
        from .robustness.checkpoint import atomic_write_text
        blob = {"best_iteration": self.best_iteration,
                "boosters": [b.model_to_string() for b in self.boosters]}
        atomic_write_text(str(filename), json.dumps(blob))
        return self


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool):
    num_data = full_data.num_data()
    group = full_data.get_group()
    label = full_data.get_label()
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError("folds should be a generator/iterator of "
                                 "(train_idx, test_idx) or have a split method")
        if hasattr(folds, "split"):
            gr = np.repeat(np.arange(len(group)), group) if group is not None else None
            folds = folds.split(X=np.empty(num_data), y=label, groups=gr)
        return list(folds)
    rng = np.random.RandomState(seed)
    if group is not None:
        # group-aware folds: split whole queries
        nq = len(group)
        qidx = np.arange(nq)
        if shuffle:
            rng.shuffle(qidx)
        q_folds = np.array_split(qidx, nfold)
        qb = np.concatenate([[0], np.cumsum(group)])
        out = []
        for i in range(nfold):
            test_q = np.sort(q_folds[i])
            test_idx = np.concatenate([np.arange(qb[q], qb[q + 1]) for q in test_q]) \
                if len(test_q) else np.array([], np.int64)
            train_idx = np.setdiff1d(np.arange(num_data), test_idx)
            out.append((train_idx, test_idx))
        return out
    if stratified and label is not None:
        order = np.argsort(label, kind="stable")
        folds_idx = [order[i::nfold] for i in range(nfold)]
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        folds_idx = np.array_split(idx, nfold)
    out = []
    for i in range(nfold):
        test_idx = np.sort(folds_idx[i])
        train_idx = np.setdiff1d(np.arange(num_data), test_idx)
        out.append((train_idx, test_idx))
    return out


def _agg_cv_result(raw_results: List[List]):
    cvmap: Dict = collections.OrderedDict()
    metric_type: Dict = {}
    for one_result in raw_results:
        for item in one_result:
            key = f"{item[0]} {item[1]}"
            metric_type[key] = item[3]
            cvmap.setdefault(key, []).append(item[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k], float(np.std(v)))
            for k, v in cvmap.items()]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics: Optional[Union[str, List[str]]] = None,
       feval: Optional[Union[Callable, List[Callable]]] = None,
       init_model=None, fpreproc: Optional[Callable] = None,
       seed: int = 0, callbacks: Optional[List[Callable]] = None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """Cross-validation (reference: engine.py:626)."""
    configure_compile_cache()
    params = resolve_aliases(dict(params or {}))
    if "num_iterations" in params:
        num_boost_round = int(params["num_iterations"])
    if metrics is not None:
        params["metric"] = metrics
    obj = params.get("objective", "regression")
    if str(obj).startswith(("lambdarank", "rank_")) or train_set.get_group() is not None:
        stratified = False
    if not isinstance(obj, str):
        stratified = False

    train_set.construct()
    fold_indices = _make_n_folds(train_set, folds, nfold, params, seed,
                                 stratified, shuffle)
    cvbooster = CVBooster()
    fold_data = []
    for (tr_idx, te_idx) in fold_indices:
        tr = train_set.subset(tr_idx)
        te = train_set.subset(te_idx)
        if fpreproc is not None:
            tr, te, fold_params = fpreproc(tr, te, copy.deepcopy(params))
        else:
            fold_params = params
        bst = Booster(params=dict(fold_params), train_set=tr)
        bst.add_valid(te, "valid")
        if eval_train_metric:
            bst.engine.add_valid(tr, "train", bst.engine.train_metrics)
        cvbooster._append(bst)
        fold_data.append((tr, te))

    callbacks = list(callbacks or [])
    es_rounds = params.get("early_stopping_round", 0)
    if es_rounds and int(es_rounds) > 0:
        callbacks.append(callback_mod.early_stopping(
            int(es_rounds), bool(params.get("first_metric_only", False)),
            verbose=params.get("verbosity", 1) >= 1))
    callbacks_before = [cb for cb in callbacks if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    results: Dict[str, List[float]] = collections.defaultdict(list)
    for i in range(num_boost_round):
        for cb in callbacks_before:
            cb(CallbackEnv(model=cvbooster, params=params, iteration=i,
                           begin_iteration=0, end_iteration=num_boost_round,
                           evaluation_result_list=[]))
        for bst in cvbooster.boosters:
            bst.update()
        merged = _agg_cv_result([bst.eval_valid(feval) for bst in cvbooster.boosters])
        for (_, key, mean, _, std) in merged:
            results[f"{key}-mean"].append(mean)
            results[f"{key}-stdv"].append(std)
        try:
            for cb in callbacks_after:
                cb(CallbackEnv(model=cvbooster, params=params, iteration=i,
                               begin_iteration=0, end_iteration=num_boost_round,
                               evaluation_result_list=merged))
        except EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for k in list(results.keys()):
                results[k] = results[k][:cvbooster.best_iteration]
            break

    for bst in cvbooster.boosters:
        bst.engine.flush_nan_guard()
    if return_cvbooster:
        results["cvbooster"] = cvbooster  # type: ignore
    return dict(results)
