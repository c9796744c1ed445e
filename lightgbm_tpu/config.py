"""Config / flag system.

Single source of truth for every training parameter: the ``_PARAMS`` registry
below declares name, type, default and aliases; the alias table and setters the
reference generates from ``config.h`` doc comments via
``helpers/parameter_generator.py`` (reference: include/LightGBM/config.h:52-561,
src/io/config_auto.cpp:10-285) are instead derived at import time from this one
table.  Parsing accepts ``key=value`` strings (CLI / config file) and Python
dicts, resolves aliases, coerces types, and cross-validates conflicting
parameters (reference: src/io/config.cpp:318-433 ``CheckParamConflict``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from .utils.log import log_warning


class _P:
    """One parameter spec: (default, aliases)."""

    __slots__ = ("default", "aliases", "ptype")

    def __init__(self, default, aliases=(), ptype=None):
        self.default = default
        self.aliases = tuple(aliases)
        self.ptype = ptype if ptype is not None else type(default)


# The full parameter registry.  Matches the reference's Config::parameter_set
# (src/io/config_auto.cpp:172-285) and alias_table (config_auto.cpp:10-170).
_PARAMS: Dict[str, _P] = {
    # -- core --
    "config": _P("", ["config_file"]),
    "task": _P("train", ["task_type"]),
    "objective": _P("regression", ["objective_type", "app", "application"]),
    "boosting": _P("gbdt", ["boosting_type", "boost"]),
    "data": _P("", ["train", "train_data", "train_data_file", "data_filename"]),
    "valid": _P([], ["test", "valid_data", "valid_data_file", "test_data",
                     "test_data_file", "valid_filenames"], ptype=list),
    "num_iterations": _P(100, ["num_iteration", "n_iter", "num_tree", "num_trees",
                               "num_round", "num_rounds", "num_boost_round",
                               "n_estimators"]),
    "learning_rate": _P(0.1, ["shrinkage_rate", "eta"]),
    "num_leaves": _P(31, ["num_leaf", "max_leaves", "max_leaf"]),
    "tree_learner": _P("serial", ["tree", "tree_type", "tree_learner_type"]),
    "num_threads": _P(0, ["num_thread", "nthread", "nthreads", "n_jobs"]),
    "device_type": _P("tpu", ["device"]),
    "seed": _P(0, ["random_seed", "random_state"]),
    # -- learning control --
    "max_depth": _P(-1),
    "min_data_in_leaf": _P(20, ["min_data_per_leaf", "min_data", "min_child_samples"]),
    "min_sum_hessian_in_leaf": _P(1e-3, ["min_sum_hessian_per_leaf", "min_sum_hessian",
                                         "min_hessian", "min_child_weight"]),
    "bagging_fraction": _P(1.0, ["sub_row", "subsample", "bagging"]),
    "pos_bagging_fraction": _P(1.0, ["pos_sub_row", "pos_subsample", "pos_bagging"]),
    "neg_bagging_fraction": _P(1.0, ["neg_sub_row", "neg_subsample", "neg_bagging"]),
    "bagging_freq": _P(0, ["subsample_freq"]),
    "bagging_seed": _P(3, ["bagging_fraction_seed"]),
    "feature_fraction": _P(1.0, ["sub_feature", "colsample_bytree"]),
    "feature_fraction_bynode": _P(1.0, ["sub_feature_bynode", "colsample_bynode"]),
    "feature_fraction_seed": _P(2),
    "early_stopping_round": _P(0, ["early_stopping_rounds", "early_stopping"]),
    "first_metric_only": _P(False),
    "max_delta_step": _P(0.0, ["max_tree_output", "max_leaf_output"]),
    "lambda_l1": _P(0.0, ["reg_alpha"]),
    "lambda_l2": _P(0.0, ["reg_lambda", "lambda"]),
    "min_gain_to_split": _P(0.0, ["min_split_gain"]),
    "drop_rate": _P(0.1, ["rate_drop"]),
    "max_drop": _P(50),
    "skip_drop": _P(0.5),
    "xgboost_dart_mode": _P(False),
    "uniform_drop": _P(False),
    "drop_seed": _P(4),
    "top_rate": _P(0.2),
    "other_rate": _P(0.1),
    "min_data_per_group": _P(100),
    "max_cat_threshold": _P(32),
    "cat_l2": _P(10.0),
    "cat_smooth": _P(10.0),
    "max_cat_to_onehot": _P(4),
    "top_k": _P(20, ["topk"]),
    "monotone_constraints": _P([], ["mc", "monotone_constraint"], ptype=list),
    "feature_contri": _P([], ["feature_contrib", "fc", "fp", "feature_penalty"],
                         ptype=list),
    "forcedsplits_filename": _P("", ["fs", "forced_splits_filename",
                                     "forced_splits_file", "forced_splits"]),
    "refit_decay_rate": _P(0.9),
    "cegb_tradeoff": _P(1.0),
    "cegb_penalty_split": _P(0.0),
    "cegb_penalty_feature_lazy": _P([], ptype=list),
    "cegb_penalty_feature_coupled": _P([], ptype=list),
    # -- IO --
    "verbosity": _P(1, ["verbose"]),
    "max_bin": _P(255),
    "max_bin_by_feature": _P([], ptype=list),
    "min_data_in_bin": _P(3),
    "bin_construct_sample_cnt": _P(200000, ["subsample_for_bin"]),
    "histogram_pool_size": _P(-1.0, ["hist_pool_size"]),
    "data_random_seed": _P(1, ["data_seed"]),
    "output_model": _P("LightGBM_model.txt", ["model_output", "model_out"]),
    "snapshot_freq": _P(-1, ["save_period"]),
    "input_model": _P("", ["model_input", "model_in"]),
    "output_result": _P("LightGBM_predict_result.txt",
                        ["predict_result", "prediction_result", "predict_name",
                         "prediction_name", "pred_name", "name_pred"]),
    "initscore_filename": _P("", ["init_score_filename", "init_score_file",
                                  "init_score", "input_init_score"]),
    "valid_data_initscores": _P([], ["valid_data_init_scores", "valid_init_score_file",
                                     "valid_init_score"], ptype=list),
    "pre_partition": _P(False, ["is_pre_partition"]),
    "enable_bundle": _P(True, ["is_enable_bundle", "bundle"]),
    "max_conflict_rate": _P(0.0),
    "is_enable_sparse": _P(True, ["is_sparse", "enable_sparse", "sparse"]),
    "sparse_threshold": _P(0.8),
    "use_missing": _P(True),
    "zero_as_missing": _P(False),
    "two_round": _P(False, ["two_round_loading", "use_two_round_loading"]),
    "save_binary": _P(False, ["is_save_binary", "is_save_binary_file"]),
    "header": _P(False, ["has_header"]),
    "label_column": _P("", ["label"]),
    "weight_column": _P("", ["weight"]),
    "group_column": _P("", ["group", "group_id", "query_column", "query", "query_id"]),
    "ignore_column": _P("", ["ignore_feature", "blacklist"]),
    "categorical_feature": _P("", ["cat_feature", "categorical_column", "cat_column"]),
    "predict_raw_score": _P(False, ["is_predict_raw_score", "predict_rawscore",
                                    "raw_score"]),
    "predict_leaf_index": _P(False, ["is_predict_leaf_index", "leaf_index"]),
    "predict_contrib": _P(False, ["is_predict_contrib", "contrib"]),
    "num_iteration_predict": _P(-1),
    "pred_early_stop": _P(False),
    "pred_early_stop_freq": _P(10),
    "pred_early_stop_margin": _P(10.0),
    "convert_model_language": _P(""),
    "convert_model": _P("gbdt_prediction.cpp", ["convert_model_file"]),
    # -- objective --
    "num_class": _P(1, ["num_classes"]),
    "is_unbalance": _P(False, ["unbalance", "unbalanced_sets"]),
    "scale_pos_weight": _P(1.0),
    "sigmoid": _P(1.0),
    "boost_from_average": _P(True),
    "reg_sqrt": _P(False),
    "alpha": _P(0.9),
    "fair_c": _P(1.0),
    "poisson_max_delta_step": _P(0.7),
    "tweedie_variance_power": _P(1.5),
    "max_position": _P(20),
    "lambdamart_norm": _P(True),
    "label_gain": _P([], ptype=list),
    # -- metric --
    "metric": _P([], ["metrics", "metric_types"], ptype=list),
    "metric_freq": _P(1, ["output_freq"]),
    "is_provide_training_metric": _P(False, ["training_metric", "is_training_metric",
                                             "train_metric"]),
    "eval_at": _P([1, 2, 3, 4, 5], ["ndcg_eval_at", "ndcg_at", "map_eval_at", "map_at"],
                  ptype=list),
    "multi_error_top_k": _P(1),
    # -- network (reference: socket/MPI machine list; here: JAX mesh over ICI/DCN) --
    "num_machines": _P(1, ["num_machine"]),
    "local_listen_port": _P(12400, ["local_port", "port"]),
    "time_out": _P(120),
    "machine_list_filename": _P("", ["machine_list_file", "machine_list", "mlist"]),
    "machines": _P("", ["workers", "nodes"]),
    # -- multi-host lifecycle (parallel/distributed.py): explicit
    # jax.distributed world.  coordinator_address="" leaves init to the
    # launcher/env; num_hosts=0 / host_rank=-1 = auto-detect from the
    # binning_world() launch markers (SLURM/OMPI).  The
    # LIGHTGBM_TPU_COORDINATOR_ADDRESS/_NUM_HOSTS/_HOST_RANK env vars
    # win.  Runtime-only: per-host topology, never part of the model
    "coordinator_address": _P(""),
    "num_hosts": _P(0),
    "host_rank": _P(-1),
    # hardened collective seam: extra attempts after the first failure
    # of a host-level collective (retry-once default preserved), and
    # the per-attempt wall budget for collectives, barriers, and the
    # distributed-init handshake — a dead host then surfaces as an
    # error naming the missing rank instead of a hang
    "collective_retries": _P(1),
    "collective_timeout_s": _P(120.0),
    # -- device --
    "gpu_platform_id": _P(-1),
    "gpu_device_id": _P(-1),
    "gpu_use_dp": _P(False),
    # -- tpu-specific (new in this framework) --
    "tpu_histogram_backend": _P("auto"),   # auto | onehot | pallas
    "tpu_tree_impl": _P("auto"),           # auto | fused | segment | frontier
    "tpu_row_chunk": _P(0),                # 0 = auto-pick row chunk for histogram scan
    # frontier impl: leaves batched per growth round (0 = auto: fill the
    # 128-wide MXU tile, 8 channels x 16 leaves); 1 = strict best-first
    "tpu_frontier_width": _P(0),
    # frontier impl: only batch leaves whose gain >= ratio * round-best
    # gain — rounds adapt between strict (one dominant leaf) and fully
    # batched (many comparable leaves); 0.0 = pure top-K.  Default 0.0:
    # on-chip at the HIGGS shape the fuller rounds cut per-round
    # while-carry copies (0.766 -> 0.709 s/iter) at equal train AUC
    # (0.97110 vs 0.97102 @6it, within the bench A/B's 0.002 gate)
    "tpu_frontier_gain_ratio": _P(0.0),
    # boosting iterations dispatched as ONE device program (lax.scan over
    # the fused step), with tree fetches batched at the chunk boundary.
    # 0 = auto (chunk on TPU when the run is chunk-eligible, 1 elsewhere);
    # 1 disables chunking.  Auto-clamps to 1 when the iteration needs host
    # interaction (bagging re-draws, feature_fraction sampling, DART/RF
    # tree mutation, CEGB state, custom gradients, per-iter callbacks).
    # Attached valid sets no longer force the clamp: when every attached
    # metric is device-computable, the in-scan eval path (metric/device.py)
    # scores and evaluates them inside the scan at unchanged per-iteration
    # cadence; a custom feval or host-only metric still falls back to 1
    # (blocker named in the boost/inscan_blocked[...] telemetry gauge).
    "tpu_boost_chunk": _P(0, ["boost_chunk"]),
    "tpu_double_precision": _P(False),     # accumulate histograms in f64-equivalent
    # telemetry (utils/telemetry.py): 0 = off, 1 = counters/gauges/
    # timeline (default), 2 = + span ring buffer for Chrome trace export.
    # Env LIGHTGBM_TPU_TELEMETRY overrides; LIGHTGBM_TPU_TRACE_JSON
    # forces >= 2.
    "telemetry_level": _P(1),
    # CLI (task=train): write the versioned metrics JSON blob here after
    # training ("" = don't)
    "metrics_out": _P(""),
    # streaming run-health JSONL (utils/telemetry.HealthStream): one
    # atomically-appended record per iteration/eval/snapshot/fault while
    # training runs, consumable live via tools/run_monitor.py; a resumed
    # run compacts past the snapshot iteration and keeps appending.
    # Env LIGHTGBM_TPU_HEALTH_JSONL wins; "" = no stream
    "health_out": _P(""),
    # persistent on-disk XLA compilation cache so a restarted/resumed run
    # warm-starts its compiles: "" (default) = off, "1"/"true"/"on"/
    # "default" = on at <checkout>/.jax_cache, any other string = cache
    # directory path; JAX_COMPILATION_CACHE_DIR, where set, wins over
    # both.  Hits/misses surface as compile/cache_hits|misses telemetry
    # counters
    "compile_cache": _P(""),
    # measured per-dispatch device timing (utils/jitcost.py): every
    # cost-instrumented jit dispatch is timed wall-to-ready (sync on the
    # returned buffers) into the metrics blob's v4 ``timing`` section —
    # per-label count/total/mean/p50/p99 plus host dispatch-gap time —
    # yielding MEASURED FLOP/s and B/s next to the static XLA estimates.
    # Values (and models) are unchanged, but the sync serializes the
    # async pipeline: an opt-in measurement mode, never a benchmark
    # default.  Env LIGHTGBM_TPU_DEVICE_TIMING wins; runtime-only
    "device_timing": _P(False),
    # windowed programmatic jax-profiler capture: "START:END" opens the
    # profiler trace only for that half-open boosting-iteration span,
    # wrapping chunk dispatches in StepTraceAnnotation and phases in
    # TraceAnnotation so the device trace aligns with the host Chrome
    # trace.  Artifact dir: LIGHTGBM_TPU_PROFILE_DIR, else
    # lightgbm_tpu.profile; path + actual window land in the blob's
    # ``timing`` section.  "" = off.  Env LIGHTGBM_TPU_PROFILE_WINDOW
    # wins; runtime-only
    "profile_window": _P(""),
    # -- robustness (utils/faults.py, docs/ROBUSTNESS.md) --
    # blocking finiteness check on the boosted scores at chunk
    # boundaries (and per-iteration when chunking is off): a NaN/Inf
    # rolls the ensemble back to the last good iteration and raises
    # instead of silently shipping a poisoned model
    "check_nonfinite": _P(True),
    # CLI (task=train): discover the newest <output_model>.snapshot_iter_N
    # (with its .state sidecar) and continue bit-exactly from iteration N
    "resume": _P(False),
    # keep only the newest K snapshots, deleting older ones after each
    # successful snapshot write; 0 = keep all (reference save_period
    # keeps all)
    "snapshot_keep": _P(0),
    # deterministic fault injection spec (same grammar as the
    # LIGHTGBM_TPU_FAULTS env var, which wins per-site); "" = off
    "fault_injection": _P(""),
    # where the binned training matrix lives during boosting
    # (data/hostspill.py): "auto" = admission-check the estimated
    # working set against the device's reported HBM and start in the
    # host-spill (out-of-core) tier only when it does not fit;
    # "resident" = always keep it in HBM and never spill (the ladder
    # then ends at chunk size 1); "spill" = force the host-spill tier:
    # the matrix stays in host memory and is streamed into HBM as
    # fixed-order row-blocks per dispatch window.  Bit-identical models
    # either way.  Runtime-only: never serialized into the model
    "data_in_hbm": _P("auto"),
    # --- prediction service (lightgbm_tpu/serve, docs/SERVING.md) ---
    # how Booster.predict routes: "auto" = compiled stacked-tensor
    # routing (models/device_predict.py) when an accelerator is
    # attached, host tree walk otherwise; "on" = always the device
    # path (useful for parity testing on CPU); "off" = always the
    # host walk.  Output is bit-identical either way.  Runtime-only
    "predict_device": _P("auto"),
    # rows per serve dispatch AND the cap a micro-batching queue
    # drain coalesces up to; larger batches amortize dispatch
    # overhead at the price of padding small traffic up to a bucket
    "serve_max_batch": _P(256),
    # how long (ms) the serve queue holds the oldest pending request
    # hoping to coalesce more rows into the same dispatch; 0 =
    # dispatch-per-request (lowest latency, most dispatches)
    "serve_max_delay_ms": _P(2.0),
    # give-up budget for one queued serve request; a stuck dispatch
    # surfaces as a named ServeError instead of a hang
    "serve_queue_timeout_s": _P(30.0),
    # load-shedding bound on the micro-batch queue: total rows allowed
    # to sit pending; a submit that would exceed it is rejected with a
    # named ServeOverloadError (counted and health-streamed) instead of
    # growing the queue without bound.  0 = unbounded (pre-v20 behavior)
    "serve_max_queue_rows": _P(65536),
    # quality gate on hot model swap (ServeSession.swap / the refit
    # loop): the candidate is shadow-scored on a deterministic holdout
    # and rejected when its holdout metric is more than this fraction
    # worse than the incumbent's (or any output is non-finite); the old
    # model keeps serving and a swap_rejected record is emitted
    "swap_quality_threshold": _P(0.1),
    # seconds between DriftGate polls in the background refit loop
    # (serve/refit_loop.py): each drifted poll refits the booster on
    # fresh labeled data and pushes it through the gated swap
    "refit_poll_s": _P(30.0),
    # streaming serve-health JSONL (serve/health.py): the session
    # appends serve_start/serve_window/serve_admit/serve_fault/
    # serve_summary records through the same never-torn O_APPEND writer
    # training uses, consumable live via tools/serve_monitor.py.  Env
    # LIGHTGBM_TPU_SERVE_HEALTH_JSONL wins; "" = no stream
    "serve_health_out": _P(""),
    # seconds between serve_window records (QPS, stage p50/p99, pad and
    # coalesce fill ratios) while a serve session with a health stream
    # is alive; idle windows are still written so a wedged server is
    # distinguishable from an idle one
    "serve_health_window_s": _P(5.0),
    # model-and-data drift plane (obs/drift.py, metrics v7): when on, a
    # serve session accumulates per-(model, feature) bin-occupancy
    # counts from the already-binned device rows plus a bounded
    # reservoir of replied raw scores, and each serve_window close
    # emits a serve_drift record (per-feature PSI vs the training
    # baseline, score-shift JS).  Host-side accounting only: models
    # stay byte-identical and replies bit-identical either way
    "drift_detect": _P(False),
    # PSI at or above which a model counts as drifted: serve_drift
    # records flag it, the monitors render the DRIFT banner and
    # DriftGate.drifted() (the refit trigger) flips.  0.2 is the
    # classic "act" operating point (0.1 = watch)
    "drift_psi_threshold": _P(0.2),
    # how many of the worst-drifting features a serve_drift record
    # names (sorted by PSI, descending)
    "drift_topk": _P(5),
    # multi-tenant training scheduler (lightgbm_tpu/sched,
    # docs/SCHEDULING.md): path of a job spec file; a non-empty value
    # (or task=sched) runs the spec's jobs cooperatively time-sliced
    # on this process's device set instead of one training run
    "sched": _P(""),
    # chunk dispatches one job runs per scheduler time slice before the
    # next tenant is considered; the chunk boundary is the preemption
    # point, so a larger quantum trades fairness granularity for fewer
    # scheduler round-trips
    "sched_quantum_chunks": _P(4),
    # slice-picking policy: "round_robin" rotates tenants per quantum;
    # "fair" (alias fair_share) is the deficit policy — always run the
    # runnable job with the least accumulated device-seconds, weighted
    # by its share weight (measured via device_timing when on, slice
    # wall otherwise)
    "sched_policy": _P("round_robin"),
    # concurrently RESIDENT jobs; submissions beyond it queue (FIFO)
    # until a running job finishes
    "sched_max_jobs": _P(8),
    # scheduler health JSONL (sched_start/sched_admit/sched_slice/
    # sched_preempt_job/job_done/sched_summary records) through the
    # same never-torn O_APPEND writer training uses; tail it with
    # tools/sched_monitor.py.  "" = no stream
    "sched_health_out": _P(""),
    # fleet observability plane (obs/, metrics v6): every N iterations
    # ranks kv-allgather their per-collective enter/duration windows,
    # split collective wall into wait vs work seconds, and name the
    # straggler rank in a dist_window health record.  0 = sync only at
    # summary.  Multi-host runs only; host-side timing, so trained
    # models stay byte-identical with any value
    "fleet_obs_sync_iters": _P(0),
    # ping/pong exchanges per clock-offset estimate (obs/clockskew.py);
    # the minimum-RTT sample wins, so more pings tighten the bound
    "fleet_obs_clock_pings": _P(5),
}

# runtime-only knobs excluded from a saved model's ``parameters:``
# section: they describe how THIS process ran, not what was learned, and
# including them would make a resumed run's model differ byte-wise from
# an uninterrupted one
RUNTIME_ONLY_PARAMS = frozenset(["resume", "fault_injection",
                                 "compile_cache", "device_timing",
                                 "profile_window", "data_in_hbm",
                                 "coordinator_address", "num_hosts",
                                 "host_rank", "collective_retries",
                                 "collective_timeout_s",
                                 "predict_device", "serve_max_batch",
                                 "serve_max_delay_ms",
                                 "serve_queue_timeout_s",
                                 "serve_max_queue_rows",
                                 "swap_quality_threshold",
                                 "refit_poll_s",
                                 "serve_health_out",
                                 "serve_health_window_s",
                                 "drift_detect", "drift_psi_threshold",
                                 "drift_topk",
                                 "sched", "sched_quantum_chunks",
                                 "sched_policy", "sched_max_jobs",
                                 "sched_health_out",
                                 "telemetry_level", "metrics_out",
                                 "health_out",
                                 "fleet_obs_sync_iters",
                                 "fleet_obs_clock_pings"])

# alias -> canonical name
ALIAS_TABLE: Dict[str, str] = {}
for _name, _spec in _PARAMS.items():
    for _a in _spec.aliases:
        ALIAS_TABLE[_a] = _name

PARAMETER_SET = frozenset(_PARAMS)

_TRUE_SET = {"1", "t", "true", "y", "yes", "on"}
_FALSE_SET = {"0", "f", "false", "n", "no", "off"}

# objective alias strings (reference: docs in config.h:184-214 and
# ObjectiveFunction::CreateObjectiveFunction src/objective/objective_function.cpp:15)
OBJECTIVE_ALIASES = {
    "regression": "regression", "regression_l2": "regression", "l2": "regression",
    "mean_squared_error": "regression", "mse": "regression",
    "l2_root": "regression", "root_mean_squared_error": "regression",
    "rmse": "regression",
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
    "none": "none", "null": "none", "custom": "none", "na": "none",
}


def _coerce(name: str, value: Any, ptype: type) -> Any:
    """Coerce a raw (usually string) value to the parameter's type."""
    if ptype is list:
        if isinstance(value, (list, tuple)):
            return list(value)
        if isinstance(value, str):
            if not value:
                return []
            return [_maybe_num(v) for v in value.replace(";", ",").split(",")]
        return [value]
    if ptype is bool:
        if isinstance(value, bool):
            return value
        if isinstance(value, (int, float)):
            return bool(value)
        s = str(value).strip().lower()
        if s in _TRUE_SET:
            return True
        if s in _FALSE_SET:
            return False
        raise ValueError(f"cannot parse bool parameter {name}={value!r}")
    if ptype is int:
        return int(float(value))
    if ptype is float:
        return float(value)
    return str(value)


def _maybe_num(s: str) -> Any:
    s = s.strip()
    try:
        f = float(s)
        return int(f) if f == int(f) and "." not in s and "e" not in s.lower() else f
    except ValueError:
        return s


def resolve_alias(key: str) -> str:
    k = key.strip().lower()
    return ALIAS_TABLE.get(k, k)


def str2map(parameters: str) -> Dict[str, str]:
    """Parse whitespace-separated ``key=value`` pairs (reference Config::Str2Map)."""
    out: Dict[str, str] = {}
    for tok in parameters.split():
        kv2map(out, tok)
    return out


def kv2map(params: Dict[str, str], kv: str) -> None:
    kv = kv.strip()
    if not kv or kv.startswith("#"):
        return
    if "=" not in kv:
        log_warning(f"Unknown parameter {kv}")
        return
    k, v = kv.split("=", 1)
    k = k.strip()
    v = v.split("#", 1)[0].strip()
    if k in params and params[k] != v:
        log_warning(f"{k} is set with {params[k]}, will be overridden by {v}")
    params[k] = v


@dataclasses.dataclass
class Config:
    """Resolved training configuration.

    Construct with :meth:`from_params` from a dict of possibly-aliased keys.
    Unknown keys warn (matching the reference's tolerance of unknown params).
    """

    def __init__(self, **kwargs):
        for name, spec in _PARAMS.items():
            v = spec.default
            object.__setattr__(self, name,
                               list(v) if isinstance(v, list) else v)
        self.raw: Dict[str, Any] = {}
        self.update(kwargs)

    # -- construction --
    @classmethod
    def from_params(cls, params: Optional[Dict[str, Any]] = None,
                    **kwargs) -> "Config":
        merged = dict(params or {})
        merged.update(kwargs)
        return cls(**merged)

    @classmethod
    def from_string(cls, parameters: str) -> "Config":
        return cls(**str2map(parameters))

    def update(self, params: Dict[str, Any]) -> None:
        resolved: Dict[str, Any] = {}
        for k, v in params.items():
            name = resolve_alias(k)
            if name in resolved and resolved[name] != v:
                log_warning(f"{name} is set with {resolved[name]}, "
                            f"will be overridden by {v}")
            resolved[name] = v
        for name, v in resolved.items():
            if name not in _PARAMS:
                log_warning(f"Unknown parameter: {name}")
                self.raw[name] = v
                continue
            setattr(self, name, _coerce(name, v, _PARAMS[name].ptype))
            self.raw[name] = v
        self._post_process()

    # -- validation (reference Config::CheckParamConflict, config.cpp:318+) --
    def _post_process(self) -> None:
        self.objective = OBJECTIVE_ALIASES.get(
            str(self.objective).strip().lower(), self.objective)
        if isinstance(self.metric, str):
            self.metric = _coerce("metric", self.metric, list)
        self.metric = [str(m).strip().lower() for m in self.metric if str(m).strip()]
        if self.num_leaves < 2:
            log_warning("num_leaves must be >= 2; setting to 2")
            self.num_leaves = 2
        if self.max_bin < 2:
            raise ValueError("max_bin should be >= 2")
        if self.bagging_freq > 0 and not (0.0 < self.bagging_fraction <= 1.0):
            raise ValueError("bagging_fraction must be in (0, 1]")
        if not (0.0 < self.feature_fraction <= 1.0):
            raise ValueError("feature_fraction must be in (0, 1]")
        if not (0.0 <= self.tpu_frontier_gain_ratio <= 1.0):
            # > 1.0 would reject every leaf including the round best and
            # spin the growth loop forever
            raise ValueError("tpu_frontier_gain_ratio must be in [0, 1]")
        if self.objective in ("multiclass", "multiclassova") and self.num_class <= 1:
            raise ValueError("num_class must be > 1 for multiclass objectives")
        if (self.objective not in ("multiclass", "multiclassova", "none")
                and self.num_class != 1):
            raise ValueError("num_class must be 1 for non-multiclass objectives")
        if self.is_unbalance and self.scale_pos_weight != 1.0:
            raise ValueError(
                "is_unbalance and scale_pos_weight cannot both be set")
        # distributed learner implies a parallel tree learner choice stays valid
        tl = str(self.tree_learner).strip().lower()
        if tl in ("serial",):
            pass
        elif tl in ("feature", "feature_parallel", "data", "data_parallel",
                    "voting", "voting_parallel", "benchmark"):
            pass
        else:
            raise ValueError(f"Unknown tree learner type {self.tree_learner}")
        self.tree_learner = tl
        dib = str(self.data_in_hbm).strip().lower() or "auto"
        if dib not in ("auto", "resident", "spill"):
            raise ValueError("data_in_hbm must be one of auto, resident, "
                             f"spill (got {self.data_in_hbm!r})")
        if self.collective_retries < 0:
            raise ValueError("collective_retries must be >= 0")
        if self.collective_timeout_s <= 0:
            raise ValueError("collective_timeout_s must be > 0")
        if (self.coordinator_address and self.num_hosts > 0
                and self.host_rank >= self.num_hosts):
            raise ValueError(
                f"host_rank={self.host_rank} must be in "
                f"[0, num_hosts={self.num_hosts}) when "
                "coordinator_address is set (or -1 to auto-detect)")
        self.data_in_hbm = dib
        pd = str(self.predict_device).strip().lower() or "auto"
        if pd not in ("auto", "on", "off"):
            raise ValueError("predict_device must be one of auto, on, off "
                             f"(got {self.predict_device!r})")
        self.predict_device = pd
        if self.serve_max_batch < 1:
            raise ValueError("serve_max_batch must be >= 1")
        if self.serve_max_delay_ms < 0:
            raise ValueError("serve_max_delay_ms must be >= 0")
        if self.serve_queue_timeout_s <= 0:
            raise ValueError("serve_queue_timeout_s must be > 0")
        if self.serve_max_queue_rows < 0:
            raise ValueError("serve_max_queue_rows must be >= 0 "
                             "(0 = unbounded)")
        if self.swap_quality_threshold <= 0:
            raise ValueError("swap_quality_threshold must be > 0")
        if self.refit_poll_s <= 0:
            raise ValueError("refit_poll_s must be > 0")
        if self.serve_health_window_s <= 0:
            raise ValueError("serve_health_window_s must be > 0")
        if self.drift_psi_threshold <= 0:
            raise ValueError("drift_psi_threshold must be > 0")
        if self.drift_topk < 1:
            raise ValueError("drift_topk must be >= 1")
        sp = str(self.sched_policy).strip().lower() or "round_robin"
        sp = {"rr": "round_robin", "fair_share": "fair",
              "deficit": "fair"}.get(sp, sp)
        if sp not in ("round_robin", "fair"):
            raise ValueError(
                "sched_policy must be one of round_robin, fair "
                f"(got {self.sched_policy!r})")
        self.sched_policy = sp
        if self.sched_quantum_chunks < 1:
            raise ValueError("sched_quantum_chunks must be >= 1")
        if self.sched_max_jobs < 1:
            raise ValueError("sched_max_jobs must be >= 1")
        if self.fleet_obs_sync_iters < 0:
            raise ValueError("fleet_obs_sync_iters must be >= 0")
        if self.fleet_obs_clock_pings < 1:
            raise ValueError("fleet_obs_clock_pings must be >= 1")

    # -- accessors --
    def to_dict(self) -> Dict[str, Any]:
        return {name: getattr(self, name) for name in _PARAMS}

    def __repr__(self) -> str:
        diffs = {n: getattr(self, n) for n, s in _PARAMS.items()
                 if getattr(self, n) != s.default}
        return f"Config({diffs})"


def default_params() -> Dict[str, Any]:
    return {n: (list(s.default) if isinstance(s.default, list) else s.default)
            for n, s in _PARAMS.items()}
