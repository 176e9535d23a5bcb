"""Parameter/config system.

TPU-native re-design of the reference config layer (reference: include/LightGBM/config.h:41,
src/io/config.cpp, src/io/config_auto.cpp — a flat struct of ~147 documented parameters plus a
>300-entry alias table generated from doc comments). Here the config is a plain dataclass; the
alias table is hand-maintained; unknown parameters warn (Python-style pass-through) instead of
being fatal, matching the Python-package behaviour.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Union

from .utils.log import log_warning

# ---------------------------------------------------------------------------
# Alias table (reference: src/io/config_auto.cpp alias map; config.cpp:23-98 resolution rules:
# first the canonical name wins, then aliases in table order).
# ---------------------------------------------------------------------------

_PARAM_ALIASES: Dict[str, List[str]] = {
    "config": ["config_file"],
    "task": ["task_type"],
    "objective": ["objective_type", "app", "application", "loss"],
    "boosting": ["boosting_type", "boost"],
    "data_sample_strategy": [],
    "data": ["train", "train_data", "train_data_file", "data_filename"],
    "valid": ["test", "valid_data", "valid_data_file", "test_data", "test_data_file",
              "valid_filenames"],
    "num_iterations": ["num_iteration", "n_iter", "num_tree", "num_trees", "num_round",
                       "num_rounds", "nrounds", "num_boost_round", "n_estimators",
                       "max_iter"],
    "learning_rate": ["shrinkage_rate", "eta"],
    "num_leaves": ["num_leaf", "max_leaves", "max_leaf", "max_leaf_nodes"],
    "tree_learner": ["tree", "tree_type", "tree_learner_type"],
    "num_threads": ["num_thread", "nthread", "nthreads", "n_jobs"],
    "device_type": ["device"],
    "seed": ["random_seed", "random_state"],
    "deterministic": [],
    "force_col_wise": [],
    "force_row_wise": [],
    "histogram_pool_size": ["hist_pool_size"],
    "max_depth": [],
    "min_data_in_leaf": ["min_data_per_leaf", "min_data", "min_child_samples",
                         "min_samples_leaf"],
    "min_sum_hessian_in_leaf": ["min_sum_hessian_per_leaf", "min_sum_hessian",
                                "min_hessian", "min_child_weight"],
    "bagging_fraction": ["sub_row", "subsample", "bagging"],
    "pos_bagging_fraction": ["pos_sub_row", "pos_subsample", "pos_bagging"],
    "neg_bagging_fraction": ["neg_sub_row", "neg_subsample", "neg_bagging"],
    "bagging_freq": ["subsample_freq"],
    "bagging_seed": ["bagging_fraction_seed"],
    "bagging_by_query": [],
    "feature_fraction": ["sub_feature", "colsample_bytree"],
    "feature_fraction_bynode": ["sub_feature_bynode", "colsample_bynode"],
    "feature_fraction_seed": [],
    "extra_trees": ["extra_tree"],
    "extra_seed": [],
    "early_stopping_round": ["early_stopping_rounds", "early_stopping",
                             "n_iter_no_change"],
    "early_stopping_min_delta": [],
    "first_metric_only": [],
    "max_delta_step": ["max_tree_output", "max_leaf_output"],
    "lambda_l1": ["reg_alpha", "l1_regularization"],
    "lambda_l2": ["reg_lambda", "lambda", "l2_regularization"],
    "linear_lambda": [],
    "min_gain_to_split": ["min_split_gain"],
    "drop_rate": ["rate_drop"],
    "max_drop": [],
    "skip_drop": [],
    "xgboost_dart_mode": [],
    "uniform_drop": [],
    "drop_seed": [],
    "top_rate": [],
    "other_rate": [],
    "min_data_per_group": [],
    "max_cat_threshold": [],
    "cat_l2": [],
    "cat_smooth": [],
    "max_cat_to_onehot": [],
    "top_k": ["topk"],
    "monotone_constraints": ["mc", "monotone_constraint", "monotonic_cst"],
    "monotone_constraints_method": ["monotone_constraining_method", "mc_method"],
    "monotone_penalty": ["monotone_splits_penalty", "ms_penalty", "mc_penalty"],
    "feature_contri": ["feature_contrib", "fc", "fp", "feature_penalty"],
    "forcedsplits_filename": ["fs", "forced_splits_filename", "forced_splits_file",
                              "forced_splits"],
    "refit_decay_rate": [],
    "cegb_tradeoff": [],
    "cegb_penalty_split": [],
    "cegb_penalty_feature_lazy": [],
    "cegb_penalty_feature_coupled": [],
    "path_smooth": [],
    "interaction_constraints": [],
    "verbosity": ["verbose"],
    "input_model": ["model_input", "model_in"],
    "output_model": ["model_output", "model_out"],
    "saved_feature_importance_type": [],
    "snapshot_freq": ["save_period"],
    "snapshot_keep": [],
    "resume_from": ["resume"],
    "linear_tree": ["linear_trees"],
    "max_bin": ["max_bins"],
    "max_bin_by_feature": [],
    "min_data_in_bin": [],
    "bin_construct_sample_cnt": ["subsample_for_bin"],
    "data_random_seed": ["data_seed"],
    "is_enable_sparse": ["is_sparse", "enable_sparse", "sparse"],
    "enable_bundle": ["is_enable_bundle", "bundle"],
    "use_missing": [],
    "zero_as_missing": [],
    "feature_pre_filter": [],
    "pre_partition": ["is_pre_partition"],
    "two_round": ["two_round_loading", "use_two_round_loading"],
    "ingest_mode": ["ingest"],
    "ingest_chunk_rows": ["ingest_batch_rows"],
    "ingest_cache": ["binned_cache"],
    "ingest_cache_path": ["binned_cache_path"],
    "ingest_sketch_size": ["sketch_size"],
    "header": ["has_header"],
    "label_column": ["label"],
    "weight_column": ["weight"],
    "group_column": ["group", "group_id", "query_column", "query", "query_id"],
    "ignore_column": ["ignore_feature", "blacklist"],
    "categorical_feature": ["cat_feature", "categorical_column", "cat_column",
                            "categorical_features"],
    "forcedbins_filename": [],
    "save_binary": ["is_save_binary", "is_save_binary_file"],
    "precise_float_parser": [],
    "parser_config_file": [],
    "start_iteration_predict": [],
    "num_iteration_predict": [],
    "predict_raw_score": ["is_predict_raw_score", "predict_rawscore", "raw_score"],
    "predict_leaf_index": ["is_predict_leaf_index", "leaf_index"],
    "predict_contrib": ["is_predict_contrib", "contrib"],
    "predict_disable_shape_check": [],
    "pred_early_stop": [],
    "pred_early_stop_freq": [],
    "pred_early_stop_margin": [],
    "output_result": ["predict_result", "prediction_result", "predict_name",
                      "prediction_name", "pred_name", "name_pred"],
    "convert_model_language": [],
    "convert_model": ["convert_model_file"],
    "objective_seed": [],
    "num_class": ["num_classes"],
    "is_unbalance": ["unbalance", "unbalanced_sets"],
    "scale_pos_weight": [],
    "sigmoid": [],
    "boost_from_average": [],
    "reg_sqrt": [],
    "alpha": [],
    "fair_c": [],
    "poisson_max_delta_step": [],
    "tweedie_variance_power": [],
    "lambdarank_truncation_level": [],
    "lambdarank_norm": [],
    "label_gain": [],
    "lambdarank_position_bias_regularization": [],
    "metric": ["metrics", "metric_types"],
    "metric_freq": ["output_freq"],
    "is_provide_training_metric": ["training_metric", "is_training_metric",
                                   "train_metric"],
    "eval_at": ["ndcg_eval_at", "ndcg_at", "map_eval_at", "map_at"],
    "multi_error_top_k": [],
    "auc_mu_weights": [],
    "num_machines": ["num_machine"],
    "local_listen_port": ["local_port", "port"],
    "time_out": [],
    "machine_list_filename": ["machine_list_file", "machine_list", "mlist"],
    "machines": ["workers", "nodes"],
    "gpu_platform_id": [],
    "gpu_device_id": [],
    "gpu_use_dp": [],
    "num_gpu": [],
    "use_quantized_grad": [],
    "num_grad_quant_bins": [],
    "quant_train_renew_leaf": [],
    "stochastic_rounding": [],
    # --- TPU-specific knobs (new in this framework) ---
    "hist_backend": [],          # auto | segsum | onehot | stream
    "hist_packed_width": ["histogram_packed_width"],  # 32 | 16 | 8
    "route_fusion": ["goss_route_fusion"],  # auto | on | off
    "hist_precision": [],        # auto | mixed (two-pass bf16, ~f32) | single
    "max_splits_per_round": [],  # batched leaf-wise: leaves split per device round
    "multiclass_batched": ["batched_multiclass"],
    "mesh_shape": [],            # e.g. "data:8" or "data:4,feature:2"
    "hist_comms": ["histogram_comms"],        # psum | reduce_scatter
    "hist_comms_dtype": ["histogram_comms_dtype"],  # f32 | bf16_pair
    "hist_comms_pipeline": ["histogram_comms_pipeline"],  # scatter chunks
    "row_compaction": ["sample_compaction"],  # auto | off | pad
    "fused_iter": ["fused_iteration"],        # auto | on | off
    "eval_fetch_freq": ["fetch_freq", "flag_poll_freq"],
    "tpu_dtype": [],             # f32 | bf16 accumulate dtype for histograms
    # --- robustness (docs/ROBUSTNESS.md) ---
    "nan_guard": ["nan_policy"],
    "dist_retries": [],
    "dist_backoff": [],
    # --- online serving (docs/SERVING.md) ---
    "serve_host": ["serving_host"],
    "serve_port": ["serving_port"],
    "serve_max_batch": ["serve_batch_size"],
    "serve_max_delay_ms": ["serve_batch_delay_ms"],
    "serve_queue_size": [],
    "serve_buckets": ["serve_bucket_ladder"],
    "serve_warmup": [],
    "serve_heartbeat": ["serve_heartbeat_file"],
    "serve_binary_port": ["binary_port", "serve_wire_port"],
    "serve_binary_accept_threads": ["binary_accept_threads"],
    "serve_models": ["model_roster", "serve_model_roster"],
    "serve_hbm_budget_mb": ["hbm_budget_mb", "serve_cache_budget_mb"],
    "serve_default_model": ["default_model_id"],
    "serve_explain_max_batch": ["explain_max_batch"],
    "serve_explain_queue_size": ["explain_queue_size"],
    "serve_explain_max_delay_ms": ["explain_max_delay_ms"],
    "serve_replicas": ["num_replicas", "serve_num_replicas"],
    "serve_fleet_mode": ["fleet_mode"],
    "serve_fleet_dir": ["fleet_dir"],
    "serve_deadline_ms": ["serve_deadline", "deadline_ms"],
    "serve_retries": [],
    "serve_retry_backoff_ms": [],
    "serve_breaker_failures": [],
    "serve_breaker_cooldown_s": [],
    "serve_restart_backoff_s": [],
    "serve_hang_timeout_s": ["serve_hang_timeout"],
    "serve_trace_sample": ["trace_sample_rate"],
    "serve_trace_tail": ["trace_tail_capacity"],
    "serve_access_log": ["access_log"],
    "serve_slo_availability": ["slo_availability_target"],
    "serve_slo_p99_ms": ["slo_p99_ms", "slo_latency_target_ms"],
    "serve_slo_window_s": ["slo_window"],
    "serve_slo_burn": ["slo_burn_threshold"],
    "quality_profile": ["quality_sidecar"],
    "quality_sample": ["drift_sample"],
    "quality_audit_sample": ["shadow_audit_sample"],
    "quality_min_rows": ["drift_min_rows"],
    "quality_topk": ["drift_topk"],
    "drift_threshold": ["drift_psi_threshold"],
    "drift_window_s": ["drift_window"],
    # --- closed-loop pipeline (docs/ROBUSTNESS.md) ---
    "pipeline_fresh_data": ["fresh_data"],
    "pipeline_refit_iterations": ["refit_iterations"],
    "pipeline_gate_margin": ["gate_margin"],
    "pipeline_observe_s": ["observe_window_s"],
    "pipeline_observe_poll_s": [],
    "pipeline_promote": [],
    "pipeline_model_id": ["model_id"],
    # --- telemetry (docs/OBSERVABILITY.md) ---
    "telemetry": ["enable_telemetry"],
    "telemetry_out": ["telemetry_output", "metrics_out"],
    "trace_out": ["trace_output", "trace_file"],
    "telemetry_recompile_threshold": ["recompile_warn_threshold"],
    "telemetry_straggler_every": ["straggler_check_every"],
    "telemetry_straggler_skew": ["straggler_warn_skew"],
    "telemetry_cost": ["cost_capture", "telemetry_cost_capture"],
    "profile_out": ["profile_dir", "profile_output"],
}

# alias -> canonical
_ALIAS_TO_CANONICAL: Dict[str, str] = {}
for _canon, _aliases in _PARAM_ALIASES.items():
    for _a in _aliases:
        _ALIAS_TO_CANONICAL[_a] = _canon


_OBJECTIVE_ALIASES = {
    "regression": "regression", "regression_l2": "regression", "l2": "regression",
    "mean_squared_error": "regression", "mse": "regression", "l2_root": "regression",
    "root_mean_squared_error": "regression", "rmse": "regression",
    "regression_l1": "regression_l1", "l1": "regression_l1",
    "mean_absolute_error": "regression_l1", "mae": "regression_l1",
    "huber": "huber", "fair": "fair", "poisson": "poisson",
    "quantile": "quantile", "mape": "mape",
    "mean_absolute_percentage_error": "mape",
    "gamma": "gamma", "tweedie": "tweedie",
    "binary": "binary",
    "multiclass": "multiclass", "softmax": "multiclass",
    "multiclassova": "multiclassova", "multiclass_ova": "multiclassova",
    "ova": "multiclassova", "ovr": "multiclassova",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda", "xentlambda": "cross_entropy_lambda",
    "lambdarank": "lambdarank",
    "rank_xendcg": "rank_xendcg", "xendcg": "rank_xendcg", "xe_ndcg": "rank_xendcg",
    "xe_ndcg_mart": "rank_xendcg", "xendcg_mart": "rank_xendcg",
    "none": "none", "null": "none", "custom": "none", "na": "none",
}

_METRIC_ALIASES = {
    "l1": "l1", "mean_absolute_error": "l1", "mae": "l1", "regression_l1": "l1",
    "l2": "l2", "mean_squared_error": "l2", "mse": "l2", "regression_l2": "l2",
    "regression": "l2",
    "rmse": "rmse", "root_mean_squared_error": "rmse", "l2_root": "rmse",
    "quantile": "quantile", "huber": "huber", "fair": "fair",
    "poisson": "poisson",
    "mape": "mape", "mean_absolute_percentage_error": "mape",
    "gamma": "gamma", "gamma_deviance": "gamma_deviance",
    "tweedie": "tweedie",
    "ndcg": "ndcg", "lambdarank": "ndcg", "rank_xendcg": "ndcg", "xendcg": "ndcg",
    "xe_ndcg": "ndcg", "xe_ndcg_mart": "ndcg", "xendcg_mart": "ndcg",
    "map": "map", "mean_average_precision": "map",
    "auc": "auc", "average_precision": "average_precision",
    "binary_logloss": "binary_logloss", "binary": "binary_logloss",
    "binary_error": "binary_error",
    "auc_mu": "auc_mu",
    "multi_logloss": "multi_logloss", "multiclass": "multi_logloss",
    "softmax": "multi_logloss", "multiclassova": "multi_logloss",
    "multiclass_ova": "multi_logloss", "ova": "multi_logloss", "ovr": "multi_logloss",
    "multi_error": "multi_error",
    "cross_entropy": "cross_entropy", "xentropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda", "xentlambda": "cross_entropy_lambda",
    "kullback_leibler": "kldiv", "kldiv": "kldiv",
    "r2": "r2",
    "": "", "none": "none", "null": "none", "custom": "none", "na": "none",
}


def canonical_objective(name: str) -> str:
    name = name.strip().lower()
    if name not in _OBJECTIVE_ALIASES:
        raise ValueError(f"Unknown objective: {name!r}")
    return _OBJECTIVE_ALIASES[name]


def canonical_metric(name: str) -> str:
    name = name.strip().lower()
    if name not in _METRIC_ALIASES:
        raise ValueError(f"Unknown metric: {name!r}")
    return _METRIC_ALIASES[name]


@dataclass
class Config:
    """Flat parameter set (reference: include/LightGBM/config.h:41)."""

    # Core
    task: str = "train"
    objective: str = "regression"
    boosting: str = "gbdt"
    data_sample_strategy: str = "bagging"
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    # serial | data | feature | voting (the reference's learner factory,
    # tree_learner.h:111; docs/DISTRIBUTED.md "choosing a tree_learner").
    # data shards rows (histogram reduce O(G*B)/round); feature shards
    # the feature-GROUP axis — zero histogram wire bytes, trees
    # bit-identical to serial; voting (PV-Tree) shards rows but reduces
    # only the elected top-2*top_k features' columns (O(2k*B)/round)
    tree_learner: str = "serial"
    num_threads: int = 0
    device_type: str = "tpu"
    seed: Optional[int] = None
    deterministic: bool = False

    # Learning control
    force_col_wise: bool = False
    force_row_wise: bool = False
    histogram_pool_size: float = -1.0
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    bagging_by_query: bool = False
    feature_fraction: float = 1.0
    feature_fraction_bynode: float = 1.0
    feature_fraction_seed: int = 2
    extra_trees: bool = False
    extra_seed: int = 6
    early_stopping_round: int = 0
    early_stopping_min_delta: float = 0.0
    first_metric_only: bool = False
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    linear_lambda: float = 0.0
    min_gain_to_split: float = 0.0
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    # GOSS: fraction of rows with the largest |grad*hess| always kept
    # (data_sample_strategy=goss); top_rate + other_rate must be <= 1.0,
    # and GOSS rejects an ACTIVE bagging config (bagging_freq > 0 with
    # bagging_fraction < 1.0) — both enforced like the reference's
    # Config::CheckParamConflict
    top_rate: float = 0.2
    # GOSS: uniformly sampled fraction of the remaining rows; their
    # gradients are amplified by (1 - top_rate) / other_rate
    other_rate: float = 0.1
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    # voting-parallel: each device votes for its local top_k features
    # per slot and the global top-2*top_k are elected for the histogram
    # reduce (voting_parallel_tree_learner.cpp:104/396) — the per-round
    # payload knob of tree_learner=voting
    top_k: int = 20
    monotone_constraints: Any = None
    monotone_constraints_method: str = "basic"
    monotone_penalty: float = 0.0
    feature_contri: Any = None
    forcedsplits_filename: str = ""
    # task=refit / task=pipeline leaf-value refit: new leaf value is
    # decay * old + (1 - decay) * refitted (reference: FitByExistingTree)
    refit_decay_rate: float = 0.9
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_penalty_feature_lazy: Any = None
    cegb_penalty_feature_coupled: Any = None
    path_smooth: float = 0.0
    interaction_constraints: Any = None
    verbosity: int = 1
    input_model: str = ""
    output_model: str = "LightGBM_model.txt"
    saved_feature_importance_type: int = 0
    snapshot_freq: int = -1
    # newest crash-consistent snapshots retained after each checkpoint
    # write (-1 = keep all; docs/ROBUSTNESS.md)
    snapshot_keep: int = -1
    # checkpoint path to resume training from; validates the manifest and
    # continues bit-identically to an uninterrupted run (alias: resume)
    resume_from: str = ""
    linear_tree: bool = False

    # Dataset
    max_bin: int = 255
    max_bin_by_feature: Any = None
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    data_random_seed: int = 1
    is_enable_sparse: bool = True
    enable_bundle: bool = True
    use_missing: bool = True
    zero_as_missing: bool = False
    feature_pre_filter: bool = True
    pre_partition: bool = False
    two_round: bool = False
    # streaming two-pass ingest (docs/INGEST.md): inmem materializes the
    # raw matrix before binning; stream reads O(ingest_chunk_rows) rows
    # at a time through a mergeable per-feature quantile sketch (pass 1)
    # and a chunked bin fill (pass 2); auto = stream for CSV/TSV files
    # >= 512 MB or whenever the binned cache is enabled
    ingest_mode: str = "auto"
    # rows per streamed chunk — the peak transient host allocation of
    # both ingest passes
    ingest_chunk_rows: int = 262144
    # memory-mapped binned cache: off | auto (open a valid cache, else
    # rebuild and write one) | read (require a valid cache) | rebuild
    # (ignore and rewrite); corrupt caches fall back to raw parsing
    # under auto and raise under read
    ingest_cache: str = "off"
    # cache file location; defaults to <data-file>.lgbcache
    ingest_cache_path: str = ""
    # per-feature sketch budget (distinct values tracked exactly):
    # boundaries are IDENTICAL to the in-memory loader while every
    # feature's sampled cardinality stays within it, and deterministic
    # approximate quantiles past it
    ingest_sketch_size: int = 16384
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: Any = ""
    forcedbins_filename: str = ""
    save_binary: bool = False
    precise_float_parser: bool = False

    # Predict
    start_iteration_predict: int = 0
    num_iteration_predict: int = -1
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    predict_disable_shape_check: bool = False
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0
    output_result: str = "LightGBM_predict_result.txt"

    # Objective
    objective_seed: int = 5
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    lambdarank_truncation_level: int = 30
    lambdarank_norm: bool = True
    label_gain: Any = None
    lambdarank_position_bias_regularization: float = 0.0

    # Metric
    metric: Any = ""
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: Any = None  # default [1,2,3,4,5]
    multi_error_top_k: int = 1
    auc_mu_weights: Any = None

    # Network (kept for API parity; TPU uses jax.distributed + mesh axes instead)
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_filename: str = ""
    machines: str = ""

    # GPU params accepted for compat (ignored on TPU)
    gpu_platform_id: int = -1
    gpu_device_id: int = -1
    gpu_use_dp: bool = False
    num_gpu: int = 1

    # Quantized-gradient training
    use_quantized_grad: bool = False
    num_grad_quant_bins: int = 4
    quant_train_renew_leaf: bool = False
    stochastic_rounding: bool = True

    # --- TPU-native knobs ---
    # histogram formulation: auto | segsum | onehot | stream (the rule
    # that resolves auto is ops/histogram.resolve_hist_backend). auto =
    # stream on a TPU at EVERY table width (a table whose one-hot does not
    # fit VMEM whole is cut into M-tiles of whole feature groups by the
    # kernel itself, so the fused iteration runs it too) and segsum (the
    # reference) elsewhere; under a mesh, stream where rows alone are
    # sharded and onehot (TPU) / segsum otherwise; onehot too where the
    # stream kernel does not take the job (over 2,048 leaves or 255 splits
    # a round). LGBTPU_HIST_BACKEND overrides for A/B.
    hist_backend: str = "auto"
    # packed quantized-gradient histogram width (bits per grad/hess field
    # on the mesh wire): 32 = exact int32 lanes (default); 16 packs each
    # (grad, hess) pair into ONE int32 lane — HALF the psum/psum_scatter
    # bytes per round; 8 packs the pair into one int16 lane — a QUARTER.
    # Requires use_quantized_grad with the stream backend; widths < 32
    # requantize with a shared power-of-two shift per round (documented-ulp,
    # parallel/comms.pack_gh_wire) and only change the WIRE — single-device
    # histograms stay exact int32. LGBTPU_HIST_PACKED_WIDTH overrides for
    # A/B experiments.
    hist_packed_width: int = 32
    # GOSS/bagging route fusion (docs/PERF.md "histogram-formulation
    # floor"): auto = under row compaction on the stream backend, skip the
    # per-round route-only FULL-data pass and replay every round's stored
    # route table over the full rows in ONE fused kernel launch after
    # growth (bit-identical — the replay applies the exact same table
    # steps); on/off force. LGBTPU_ROUTE_FUSION=1/0 overrides for A/B.
    route_fusion: str = "auto"
    hist_precision: str = "auto"   # auto = single on the TPU stream
                                   # backend (reference GPU default,
                                   # gpu_use_dp=false); mixed = ~f32
    # 0 = auto: 1 (exact best-first, the reference's leaf-wise order) on CPU
    # backends, 64 (batched rounds feeding the MXU) on TPU / stream. Batched
    # growth can deviate from best-first only when the leaf budget runs out
    # mid-round (children of just-split leaves aren't candidates yet).
    max_splits_per_round: int = 0
    # grow all K class trees in ONE widened lockstep program (one histogram
    # contraction serves every class's gradient channels); falls back to the
    # per-class scan when a constraint feature is active. Trees are
    # bit-identical either way — LGBTPU_MULTICLASS_BATCHED=1/0 forces the
    # choice for A/B experiments.
    multiclass_batched: bool = True
    # device mesh spec "axis:size[,axis:size]" (docs/DISTRIBUTED.md):
    # "data:D" shards rows (tree_learner=data) or histogram slots
    # (voting), "feature:D" shards feature groups (tree_learner=feature),
    # "data:R,feature:F" is the 2D rows x feature-groups mesh for the
    # both-huge regime (tree_learner=data only; docs/DISTRIBUTED.md
    # "2D mesh"). Empty = single-device.
    mesh_shape: str = ""
    # data-parallel histogram collective (docs/DISTRIBUTED.md): psum
    # all-reduces the full histogram block to every device each round;
    # reduce_scatter Reduce-Scatters feature-group slices so each device
    # receives only its G/D slice, finds splits shard-locally and
    # all-gathers only the tiny per-shard best-split records. Trees are
    # BIT-IDENTICAL either way; LGBTPU_HIST_COMMS=psum|reduce_scatter
    # forces the choice for A/B experiments. Applies to the row-sharded
    # stream path (tree_learner=data); constraint features fall back to
    # psum.
    hist_comms: str = "psum"
    # reduce_scatter wire dtype: f32, or bf16_pair — remote contributions
    # ride the HIGH half of the f32->bf16 high/low split (the hist
    # kernel's two-pass trick, pallas/stream_kernel._wsplit) at 2 bytes per
    # element while each device's own slice contribution stays exact f32
    # and the cross-device accumulation runs in f32. Halves the wire
    # payload; opt-in (not bit-identical to psum).
    hist_comms_dtype: str = "f32"
    # double-buffered reduce_scatter (docs/DISTRIBUTED.md "fused
    # iteration"): the per-round histogram psum_scatter is issued as this
    # many independent chunks along the slot/class axis so the XLA
    # scheduler overlaps one chunk's wire time against the next chunk's
    # packing/copy compute. Every element rides the same rank-ordered
    # reduction, so any value is BITWISE identical to 1; 0 = auto (2 in
    # reduce_scatter mode, 1 under psum; the bf16_pair wire pipelines
    # through its all_to_all instead, so the knob resolves to 1 there).
    # LGBTPU_HIST_COMMS_PIPELINE overrides for A/B experiments.
    hist_comms_pipeline: int = 0
    # whole-iteration fusion (docs/DISTRIBUTED.md "fused iteration &
    # sharded state"): gradients -> sampling -> tree growth -> score
    # update as ONE compiled launch per boosting iteration, with every
    # row-indexed array held permanently device-sharded across iterations
    # (explicit out-sharding == in-sharding, no host round trips on the
    # critical path). auto = on for single-chip TPU and for any
    # row-sharded stream mesh (single-chip CPU keeps the unfused path —
    # XLA:CPU re-fuses the gradient chain with last-ulp differences,
    # which would break the serial byte-identity suite); on/off force.
    # LGBTPU_FUSE_ITER=1/0 overrides for A/B experiments.
    fused_iter: str = "auto"
    # batched device-flag fetch cadence (iterations): the fused path
    # reads the finished flag, nan_guard flag, and sampled-row counters
    # in ONE device_get every this-many iterations instead of per-iter
    # blocking reads. 0 = auto (16 on TPU or under a fused mesh, 1
    # otherwise — matching the legacy finished-poll cadence).
    eval_fetch_freq: int = 0
    # GOSS/bagging row compaction (docs/PERF.md "sample-strategy
    # speedups"): auto = when a sampling mask is sparse enough, one
    # stable partition per tree compacts the in-bag rows so histogram
    # MACs scale with the SAMPLED row count (on the stream backend one
    # streaming kernel places every in-bag row by the count of those
    # before it: no sort, no gather; the other backends sort once per
    # tree); off = legacy dense masking
    # (masked rows still stream through the kernel); pad = partition but
    # keep the full row count (A/B reference — byte-identical trees to
    # auto, proving compaction drops only exact-zero work).
    # LGBTPU_COMPACT=auto|off|pad overrides for experiments.
    row_compaction: str = "auto"
    tpu_dtype: str = "f32"

    # --- robustness (docs/ROBUSTNESS.md) ---
    # non-finite gradient/hessian policy: warn (log + skip the poisoned
    # iteration), skip (silent skip), raise (abort), none (guard off)
    nan_guard: str = "warn"
    # supervised launcher: cohort relaunches from the newest valid
    # snapshot after a worker failure/hang, at most this many times
    dist_retries: int = 0
    # seconds before the first cohort relaunch (doubles each retry)
    dist_backoff: float = 2.0

    # --- online serving (docs/SERVING.md) ---
    # bind address of the JSON serving front end (python -m lightgbm_tpu.serve)
    serve_host: str = "127.0.0.1"
    # listen port; 0 picks an ephemeral port (printed at startup)
    serve_port: int = 12600
    # micro-batcher: max coalesced rows per device dispatch
    serve_max_batch: int = 256
    # micro-batcher: max milliseconds a request waits for batch-mates
    serve_max_delay_ms: float = 2.0
    # admission control: requests beyond this queue depth are rejected
    # with a structured overload response instead of buffered unboundedly
    serve_queue_size: int = 512
    # explicit row-count bucket ladder, e.g. "8,32,128" ("" = powers of
    # two from 8 up to serve_max_batch); batches pad to the next bucket so
    # every post-warmup dispatch reuses an already-traced XLA program
    serve_buckets: str = ""
    # pre-trace every bucket at model load, before the version swap
    serve_warmup: bool = True
    # heartbeat file the batch worker touches after every dispatch
    # (robustness liveness probe; "" = off)
    serve_heartbeat: str = ""
    # persistent-connection binary row wire next to HTTP (length-prefixed
    # f32 frames, docs/SERVING.md "Binary wire protocol"): -1 = off,
    # 0 = ephemeral port, > 0 = fixed port; in a fleet every replica
    # opens its own wire and publishes the port in replica_<r>.json
    serve_binary_port: int = -1
    # acceptor threads sharing the binary wire's listen socket (the
    # multi-accept front: connection setup never serializes behind one
    # thread)
    serve_binary_accept_threads: int = 2
    # multi-tenant serving roster "id=path[,id=path...]" ("" = single
    # model from input_model): every id becomes an HBM-resident tenant
    # behind /predict model_id routing, the wire v2 model field and
    # per-model SLO/drift isolation (docs/SERVING.md "Multi-tenant
    # serving")
    serve_models: str = ""
    # HBM byte budget (MiB) for the multi-tenant model cache: resident
    # device arrays beyond it are LRU-evicted (compiled programs stay;
    # readmission re-verifies the manifest and recompiles nothing);
    # 0 = unlimited
    serve_hbm_budget_mb: float = 0.0
    # which roster id answers requests that carry no model_id ("" = the
    # first entry of serve_models)
    serve_default_model: str = ""
    # /explain micro-batcher lane: max coalesced rows per SHAP dispatch
    # (contributions are k*(n_features+1) values per row — much heavier
    # than predictions, so the lane defaults far smaller)
    serve_explain_max_batch: int = 16
    # /explain admission control: queue depth beyond which explain
    # requests shed with a structured 503 (its own lane — explain
    # overload never sheds /predict traffic)
    serve_explain_queue_size: int = 64
    # /explain micro-batcher: max milliseconds an explain request waits
    # for batch-mates
    serve_explain_max_delay_ms: float = 2.0
    # replica fleet size for task=serve; > 1 runs the fleet supervisor
    # (N replica processes + restart-with-backoff + fleet-wide promotion,
    # docs/SERVING.md "Fleet architecture") instead of one process
    serve_replicas: int = 1
    # how clients reach the fleet: "front" routes through the fanout
    # front (deadline/retry/backoff + per-replica circuit breaker);
    # "reuseport" binds every replica to serve_port via SO_REUSEPORT
    # (kernel load-balancing; falls back to "front" where unavailable)
    serve_fleet_mode: str = "front"
    # shared fleet state/promotion directory ("" = private tmpdir);
    # holds the promote.json pointer, per-replica endpoints + heartbeats
    serve_fleet_dir: str = ""
    # default per-request budget in ms when the body carries no
    # deadline_ms (propagated through admission + batching so expired
    # requests are shed, never scored); 0 = no deadline
    serve_deadline_ms: float = 10000.0
    # fanout front: retry attempts beyond the first, each on a different
    # replica, splitting the remaining deadline budget
    serve_retries: int = 2
    # fanout front: base backoff between retry attempts (jittered,
    # doubling per attempt, capped by the remaining budget)
    serve_retry_backoff_ms: float = 25.0
    # per-replica circuit breaker: consecutive errors/timeouts that trip
    # it open (overload 503s do not count — shed is not broken)
    serve_breaker_failures: int = 5
    # circuit breaker: seconds a tripped replica gets no traffic before
    # ONE half-open probe (success closes, failure re-opens)
    serve_breaker_cooldown_s: float = 2.0
    # fleet supervisor: base delay before restarting a dead/hung replica
    # (jittered, doubling per consecutive restart, capped at 30 s)
    serve_restart_backoff_s: float = 0.5
    # fleet supervisor: SIGKILL+restart a replica whose heartbeat file
    # goes stale past this many seconds (0 = hang detection off)
    serve_hang_timeout_s: float = 10.0
    # head-sampling probability for per-request trace spans: the front
    # (or a standalone replica) decides once per request and propagates
    # the decision in the X-LGBTPU-Trace header; 0 = no request tracing
    serve_trace_sample: float = 0.01
    # bounded ring capacity for tail-captured requests (errored or
    # SLO-violating — kept regardless of head sampling), shown in /stats
    serve_trace_tail: int = 256
    # structured JSONL access log ("" = off): a file path standalone;
    # a DIRECTORY in fleet mode (access_front.jsonl + per-replica files)
    serve_access_log: str = ""
    # availability SLO target: fraction of requests NOT failing with a
    # non-503 error (503 sheds are load management, not outages);
    # the error budget 1 - target feeds the burn-rate monitor
    serve_slo_availability: float = 0.999
    # latency SLO: 99% of 200 responses must land under this many ms;
    # 0 disables the latency dimension
    serve_slo_p99_ms: float = 0.0
    # fast burn-rate window in seconds (the slow window is 12x longer;
    # an alert needs BOTH above serve_slo_burn, clears on the fast one)
    serve_slo_window_s: float = 60.0
    # burn-rate alert threshold: budget consumed this many times faster
    # than steady-state fires the SLO alert (Google SRE workbook pairing)
    serve_slo_burn: float = 14.4
    # write the .quality.json reference-profile sidecar next to the model
    # on save_model (per-feature bin histograms + score/label histograms
    # + holdout metric; docs/OBSERVABILITY.md "Data & model quality")
    quality_profile: bool = True
    # serving: per-BATCH sampling probability for drift accumulation
    # (feature/score histograms vs the reference profile); 0 disables
    # drift monitoring entirely, default is small so the binary-wire hot
    # path pays ~nothing
    quality_sample: float = 0.01
    # serving: per-request sampling probability for the train-vs-serve
    # shadow audit (background Booster.predict re-score, bitwise f64
    # compare against the wire-returned values); 0 disables the audit
    quality_audit_sample: float = 0.01
    # minimum sampled rows in the fast window before the drift alert is
    # allowed to fire (thin traffic must not page)
    quality_min_rows: int = 200
    # how many top-drifted features /drift and the drift/feature/<i>/*
    # gauges report (bounds the per-feature metric cardinality)
    quality_topk: int = 5
    # PSI level at which the drift alert fires: the fast AND slow windows
    # must both reach it (fires), the fast window alone clears it;
    # 0.2 is the textbook "significant shift" level
    drift_threshold: float = 0.2
    # fast drift window in seconds (the slow window is 12x longer,
    # mirroring the SLO burn-rate pairing)
    drift_window_s: float = 60.0

    # --- closed-loop pipeline: task=pipeline (docs/ROBUSTNESS.md
    # "Closed-loop freshness") ---
    # fresh/appended rows for the refit stage (file path, streamed via
    # the ingest pipeline so fresh data never needs to fit in RAM)
    pipeline_fresh_data: str = ""
    # boosting rounds continued on the fresh data before the device leaf
    # refit (0 = leaf-value refit only, no new trees)
    pipeline_refit_iterations: int = 2
    # validation gate: allowed holdout-metric regression of the candidate
    # vs the baseline model (same units as the metric; 0 = must not
    # regress at all)
    pipeline_gate_margin: float = 0.0
    # post-promotion observation window in seconds: an SLO burn or drift
    # alert inside it triggers automatic rollback to the prior
    # generation (0 = no watch, promotion is final)
    pipeline_observe_s: float = 0.0
    # poll period of the rollback watcher inside the observation window
    pipeline_observe_poll_s: float = 0.5
    # write the promotion pointer on gate pass (false = dry run: train,
    # refit and gate the candidate but leave the fleet untouched)
    pipeline_promote: bool = True
    # multi-tenant promotion keying: the roster model_id this pipeline
    # run refits/gates/promotes — generations advance per (model_id,
    # generation) so promoting one tenant leaves its siblings' pointers
    # (and served bytes) untouched; "" = the fleet's default pointer
    pipeline_model_id: str = ""

    # --- telemetry (docs/OBSERVABILITY.md) ---
    # master switch: span tracer + metrics registry + per-iteration records
    telemetry: bool = False
    # JSONL sink for per-iteration training records ("" = memory only)
    telemetry_out: str = ""
    # Chrome/Perfetto trace-event JSON written at the end of train()
    trace_out: str = ""
    # recompile watchdog warns once a jitted entry point traces > N times
    telemetry_recompile_threshold: int = 2
    # allgather per-host iteration times every K iterations (multi-host)
    telemetry_straggler_every: int = 50
    # warn when the slowest host's mean iter time exceeds skew x median
    telemetry_straggler_skew: float = 1.25
    # XLA cost capture per watched_jit entry (docs/OBSERVABILITY.md "Cost
    # model & profiling"): auto/lowered = flops + bytes from the lowered
    # module whenever telemetry is on (~1 ms per compile, no extra XLA
    # compile); full = also AOT-compile for the peak-HBM memory analysis
    # (one extra compile per entry); off = never (env LGBTPU_COST wins)
    telemetry_cost: str = "auto"
    # directory for a jax.profiler device-trace session wrapped around
    # train() ("" = off): writes the device trace, the host span shard,
    # and one merged host+device Perfetto timeline (same machinery as
    # `python -m lightgbm_tpu.telemetry.profile`)
    profile_out: str = ""

    def __post_init__(self) -> None:
        self._unknown: Dict[str, Any] = {}

    # -- construction ------------------------------------------------------

    @classmethod
    def from_params(cls, params: Optional[Dict[str, Any]]) -> "Config":
        cfg = cls()
        cfg.update(params or {})
        return cfg

    def update(self, params: Dict[str, Any]) -> None:
        resolved = resolve_aliases(params)
        fields = {f.name for f in dataclasses.fields(self)}
        for key, value in resolved.items():
            if (key in _VECTOR_FIELDS and isinstance(value, str)
                    and value.strip()):
                # conf-file vector syntax "1,3,5" (reference:
                # Config::GetIntVector / GetDoubleVector, config.h)
                elt = _VECTOR_FIELDS[key]
                value = [elt(tok) for tok in value.split(",") if tok.strip()]
            if key in fields:
                setattr(self, key, _coerce(getattr(self, key), value))
            else:
                self._unknown[key] = value
        self._check()

    def _check(self) -> None:
        """Parameter conflict resolution (reference: Config::CheckParamConflict,
        src/io/config.cpp)."""
        if self.num_leaves < 2:
            self.num_leaves = 2
        obj = canonical_objective(str(self.objective)) if isinstance(self.objective, str) else "none"
        if obj in ("multiclass", "multiclassova") and self.num_class < 2:
            raise ValueError("num_class must be >= 2 for multiclass objectives")
        if obj not in ("multiclass", "multiclassova") and self.num_class != 1:
            if obj != "none":
                raise ValueError("num_class must be 1 for non-multiclass objectives")
        from .robustness.guards import VALID_MODES
        if str(self.nan_guard).strip().lower() not in VALID_MODES:
            raise ValueError(
                f"nan_guard={self.nan_guard!r} is not one of "
                f"{', '.join(repr(m) for m in VALID_MODES)}")
        from .utils.log import LightGBMError
        if str(self.row_compaction).strip().lower() not in (
                "auto", "off", "pad"):
            raise LightGBMError(
                f"row_compaction={self.row_compaction!r} is not one of "
                "'auto', 'off', 'pad'")
        if str(self.fused_iter).strip().lower() not in ("auto", "on", "off"):
            raise LightGBMError(
                f"fused_iter={self.fused_iter!r} is not one of "
                "'auto', 'on', 'off'")
        if str(self.telemetry_cost).strip().lower() not in (
                "auto", "off", "lowered", "full"):
            raise LightGBMError(
                f"telemetry_cost={self.telemetry_cost!r} is not one of "
                "'auto', 'off', 'lowered', 'full'")
        if self.eval_fetch_freq < 0:
            raise LightGBMError(
                f"eval_fetch_freq={self.eval_fetch_freq} must be >= 0 "
                "(0 = auto)")
        if str(self.ingest_mode).strip().lower() not in (
                "auto", "stream", "inmem"):
            raise LightGBMError(
                f"ingest_mode={self.ingest_mode!r} is not one of "
                "'auto', 'stream', 'inmem'")
        if str(self.ingest_cache).strip().lower() not in (
                "", "off", "auto", "read", "rebuild"):
            raise LightGBMError(
                f"ingest_cache={self.ingest_cache!r} is not one of "
                "'off', 'auto', 'read', 'rebuild'")
        if self.ingest_chunk_rows < 256:
            raise LightGBMError(
                f"ingest_chunk_rows={self.ingest_chunk_rows} must be "
                ">= 256")
        if self.ingest_sketch_size < 256:
            raise LightGBMError(
                f"ingest_sketch_size={self.ingest_sketch_size} must be "
                ">= 256")
        if self.hist_comms_pipeline < 0:
            raise LightGBMError(
                f"hist_comms_pipeline={self.hist_comms_pipeline} must be "
                ">= 0 (0 = auto)")
        if self.serve_binary_port < -1 or self.serve_binary_port > 65535:
            raise LightGBMError(
                f"serve_binary_port={self.serve_binary_port} must be -1 "
                "(off), 0 (ephemeral), or a TCP port <= 65535")
        if self.serve_binary_accept_threads < 1:
            raise LightGBMError(
                f"serve_binary_accept_threads="
                f"{self.serve_binary_accept_threads} must be >= 1")
        if not 0.0 <= self.serve_trace_sample <= 1.0:
            raise LightGBMError(
                f"serve_trace_sample={self.serve_trace_sample} must be a "
                "probability in [0, 1]")
        if self.serve_trace_tail < 1:
            raise LightGBMError(
                f"serve_trace_tail={self.serve_trace_tail} must be >= 1")
        if not 0.0 < self.serve_slo_availability < 1.0:
            raise LightGBMError(
                f"serve_slo_availability={self.serve_slo_availability} "
                "must be a fraction in (0, 1), e.g. 0.999")
        if self.serve_slo_p99_ms < 0:
            raise LightGBMError(
                f"serve_slo_p99_ms={self.serve_slo_p99_ms} must be >= 0 "
                "(0 disables the latency SLO)")
        if self.serve_slo_window_s <= 0:
            raise LightGBMError(
                f"serve_slo_window_s={self.serve_slo_window_s} must be "
                "> 0")
        if self.serve_slo_burn <= 0:
            raise LightGBMError(
                f"serve_slo_burn={self.serve_slo_burn} must be > 0")
        if self.serve_models:
            # fail at config time, not at first routed request: the
            # roster grammar is id=path[,id=path...]
            from .serving.multimodel import parse_model_roster
            roster = parse_model_roster(self.serve_models)
            if self.serve_default_model and \
                    self.serve_default_model not in roster:
                raise LightGBMError(
                    f"serve_default_model={self.serve_default_model!r} "
                    "is not an id in serve_models")
        if self.serve_hbm_budget_mb < 0:
            raise LightGBMError(
                f"serve_hbm_budget_mb={self.serve_hbm_budget_mb} must be "
                ">= 0 (0 = unlimited)")
        if self.serve_explain_max_batch < 1:
            raise LightGBMError(
                f"serve_explain_max_batch={self.serve_explain_max_batch} "
                "must be >= 1")
        if self.serve_explain_queue_size < 1:
            raise LightGBMError(
                f"serve_explain_queue_size="
                f"{self.serve_explain_queue_size} must be >= 1")
        if self.serve_explain_max_delay_ms < 0:
            raise LightGBMError(
                f"serve_explain_max_delay_ms="
                f"{self.serve_explain_max_delay_ms} must be >= 0")
        if not 0.0 <= self.quality_sample <= 1.0:
            raise LightGBMError(
                f"quality_sample={self.quality_sample} must be a "
                "probability in [0, 1]")
        if not 0.0 <= self.quality_audit_sample <= 1.0:
            raise LightGBMError(
                f"quality_audit_sample={self.quality_audit_sample} must "
                "be a probability in [0, 1]")
        if self.quality_min_rows < 1:
            raise LightGBMError(
                f"quality_min_rows={self.quality_min_rows} must be >= 1")
        if self.quality_topk < 1:
            raise LightGBMError(
                f"quality_topk={self.quality_topk} must be >= 1")
        if self.drift_threshold <= 0:
            raise LightGBMError(
                f"drift_threshold={self.drift_threshold} must be > 0")
        if self.drift_window_s <= 0:
            raise LightGBMError(
                f"drift_window_s={self.drift_window_s} must be > 0")
        if not 0.0 <= self.refit_decay_rate <= 1.0:
            raise LightGBMError(
                f"refit_decay_rate={self.refit_decay_rate} must be in "
                "[0, 1]")
        if self.pipeline_refit_iterations < 0:
            raise LightGBMError(
                f"pipeline_refit_iterations={self.pipeline_refit_iterations}"
                " must be >= 0")
        if self.pipeline_observe_s < 0:
            raise LightGBMError(
                f"pipeline_observe_s={self.pipeline_observe_s} must be "
                ">= 0")
        if self.pipeline_observe_poll_s <= 0:
            raise LightGBMError(
                f"pipeline_observe_poll_s={self.pipeline_observe_poll_s} "
                "must be > 0")
        # GOSS parameter conflicts (reference: Config::CheckParamConflict,
        # src/io/config.cpp — "cannot use bagging in GOSS" and the sampled
        # fractions must partition the data)
        use_goss = (str(self.data_sample_strategy).strip().lower() == "goss"
                    or str(self.boosting).strip().lower() == "goss")
        if use_goss:
            if self.top_rate < 0.0 or self.other_rate < 0.0:
                raise LightGBMError(
                    f"GOSS rates must be non-negative, got top_rate="
                    f"{self.top_rate}, other_rate={self.other_rate}")
            if self.top_rate + self.other_rate > 1.0:
                raise LightGBMError(
                    f"top_rate + other_rate must be <= 1.0 for GOSS, got "
                    f"{self.top_rate} + {self.other_rate} = "
                    f"{self.top_rate + self.other_rate}")
            bagging_on = (self.bagging_fraction < 1.0
                          or self.pos_bagging_fraction < 1.0
                          or self.neg_bagging_fraction < 1.0)
            if self.bagging_freq > 0 and bagging_on:
                # only an ACTIVE bagging config conflicts (the reference's
                # CheckParamConflict gate: bagging needs freq > 0 AND a
                # sub-1.0 fraction — plain or pos/neg-balanced); an
                # inactive bagging_freq stays accepted for compatibility
                raise LightGBMError(
                    "GOSS (data_sample_strategy=goss) cannot be combined "
                    "with bagging; set bagging_freq=0 (reference: "
                    "Config::CheckParamConflict)")
        if self.boosting == "rf":
            if not (self.bagging_freq > 0 and 0.0 < self.bagging_fraction < 1.0):
                # rf requires bagging (reference: config.cpp CheckParamConflict)
                self.bagging_freq = max(self.bagging_freq, 1)
                if not (0.0 < self.bagging_fraction < 1.0):
                    self.bagging_fraction = 0.9

    def to_dict(self) -> Dict[str, Any]:
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d.update(self._unknown)
        return d


# vector-valued params that conf files/CLI pass as comma-separated strings
# (reference: the Config::GetIntVector/GetDoubleVector fields, config.h)
_VECTOR_FIELDS: Dict[str, Any] = {
    "eval_at": int,
    "label_gain": float,
    "monotone_constraints": int,
    "feature_contri": float,
    "cegb_penalty_feature_lazy": float,
    "cegb_penalty_feature_coupled": float,
    "max_bin_by_feature": int,
    "auc_mu_weights": float,
}


def _coerce(current: Any, value: Any) -> Any:
    """Coerce a user-supplied value to the type of the dataclass default."""
    if isinstance(current, bool):
        if isinstance(value, str):
            return value.strip().lower() in ("true", "1", "yes", "+")
        return bool(value)
    if isinstance(current, int) and not isinstance(value, bool):
        try:
            return int(value)
        except (TypeError, ValueError):
            return value
    if isinstance(current, float):
        try:
            return float(value)
        except (TypeError, ValueError):
            return value
    return value


def resolve_aliases(params: Dict[str, Any]) -> Dict[str, Any]:
    """Map aliased parameter names to canonical ones.

    Canonical name in the dict wins over aliases; among aliases the first in table
    order wins, with a warning on conflicts (reference: config.cpp:23-98
    KeyAliasTransform)."""
    out: Dict[str, Any] = {}
    alias_hits: Dict[str, List[str]] = {}
    for key, value in params.items():
        canon = _ALIAS_TO_CANONICAL.get(key, key)
        if canon != key:
            alias_hits.setdefault(canon, []).append(key)
        if canon in out:
            if key == canon:
                out[canon] = value  # canonical name wins
            else:
                log_warning(
                    f"{key} is set with {value}, {canon}={out[canon]} will be used. "
                    f"Current value: {canon}={out[canon]}")
        else:
            out[canon] = value
    # canonical name in original params always wins over any alias
    for canon, hits in alias_hits.items():
        if canon in params:
            out[canon] = params[canon]
    return out


_ConfigAliases = _PARAM_ALIASES  # exported name parity with python-package basic.py:513


def get_all_param_names() -> List[str]:
    return [f.name for f in dataclasses.fields(Config)]
