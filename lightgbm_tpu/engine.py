"""Training loop: train() and cv().

Reference: python-package/lightgbm/engine.py — train (:19: pure-Python
driver around Booster.update with callbacks and early stopping),
cv (:373: query-aware/stratified fold construction + per-fold boosters).
"""

from __future__ import annotations

import collections
import copy
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .config import resolve_alias
from .utils.log import LightGBMError, log_info, log_warning


def estimate_working_set(params, data_shape, *, num_bins=None) -> int:
    """Estimated training working set in bytes for ``params`` (a dict
    or Config) over a ``(num_data, num_columns)`` dataset, without
    constructing a dataset or booster — the number the admission checks
    (``data_in_hbm=auto``, the sched plane's HBM gate, the serve
    registry) budget against.  See docs/TUNING.md."""
    from .models.gbdt import estimate_working_set as _estimate
    return _estimate(params, data_shape, num_bins=num_bins)


def _resolve_num_boost_round(params: Dict, num_boost_round: int) -> int:
    for k in list(params):
        if resolve_alias(k) == "num_iterations":
            num_boost_round = int(params.pop(k))
    return num_boost_round


def _importance_summary(booster, topk: int = 8) -> Optional[Dict]:
    """Top-K feature importances (split + gain, gain-ranked) for the
    health stream's summary record — model-shape observability on the
    training side (run_monitor renders it).  Best-effort: a booster
    that cannot report importances must not fail the summary write."""
    try:
        split = booster.feature_importance("split")
        gain = booster.feature_importance("gain")
        names = booster.feature_name()
        order = np.argsort(-gain, kind="stable")
        top = [{"feature": (names[i] if i < len(names)
                            else f"Column_{i}"),
                "split": int(split[i]),
                "gain": round(float(gain[i]), 6)}
               for i in (int(j) for j in order) if split[i] > 0][:topk]
        if not top:
            return None
        return {"feature_importance":
                {"top": top, "features_used": int((split > 0).sum())}}
    except Exception:
        return None


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj: Optional[Callable] = None, feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name: str = "auto", categorical_feature: str = "auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval: Union[bool, int] = True,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None) -> Booster:
    params = dict(params or {})
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    if fobj is not None:
        params["objective"] = "none"
    first_metric_only = bool(params.get("first_metric_only", False))

    if isinstance(init_model, str):
        init_booster = Booster(model_file=init_model)
    elif isinstance(init_model, Booster):
        init_booster = init_model
    else:
        init_booster = None

    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    booster = Booster(params=params, train_set=train_set)
    if init_booster is not None:
        from .models.serialization import load_trees_into
        raw = train_set.data if not isinstance(train_set.data, str) else None
        if raw is not None:
            raw = np.asarray(raw, dtype=np.float64)
            if raw.ndim == 1:
                raw = raw[:, None]
        load_trees_into(booster.gbdt, init_booster, raw_data=raw)
    if valid_sets:
        valid_names = valid_names or [f"valid_{i}"
                                      for i in range(len(valid_sets))]
        for vs, name in zip(valid_sets, valid_names):
            if vs is train_set:
                # the train set as a valid set is evaluated through the
                # train-score buffer under the name "training" (reference
                # engine.py:141-147); no separate score buffer exists
                booster._train_in_valid = True
                continue
            vs.reference = train_set
            booster.add_valid(vs, name)
    user_callbacks = list(callbacks or [])
    callbacks = list(user_callbacks)
    if verbose_eval is True:
        callbacks.append(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        callbacks.append(callback_mod.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        callbacks.append(callback_mod.early_stopping(
            early_stopping_rounds, first_metric_only,
            verbose=bool(verbose_eval)))
    if evals_result is not None:
        callbacks.append(callback_mod.record_evaluation(evals_result))
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks
                       if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    train_in_valid = getattr(booster, "_train_in_valid", False)

    # the profiler window is exception-safe (utils/phase.profile_session):
    # a callback or device error mid-training must not leak an open jax
    # profiler trace session
    from .utils import maybe_enable_compile_cache
    from .utils.phase import GLOBAL_TIMER, PROFILE_WINDOW, profile_session
    from .utils.telemetry import HEALTH, TELEMETRY
    # compile_cache= knob: persistent on-disk XLA compilation cache, so a
    # restarted/resumed run warm-starts its compiles (hits/misses surface
    # in the compile/cache_* telemetry counters)
    maybe_enable_compile_cache(booster.gbdt.config)

    # Chunked boosting: several iterations per device dispatch.  When
    # valid sets are attached and every metric is device-computable, the
    # in-scan eval path keeps the chunked dispatch: the scan body scores
    # the valid sets and computes the metrics per iteration, and the
    # loop below replays the per-iteration eval/callback/early-stopping
    # cadence from the fetched [T, n_cols] matrix at chunk boundaries —
    # bit-identical to per-iteration stepping.  A custom feval/fobj, a
    # before-iteration callback (e.g. reset_parameter), or a
    # host-computed metric forces per-iteration dispatch (the blocker is
    # named in the boost/inscan_blocked[...] gauge);
    # bagging/DART/GOSS clamps live in GBDT.boost_chunk_size.
    chunk = booster.gbdt.boost_chunk_size()
    use_inscan = False
    has_eval = bool(booster._valid_names or train_in_valid)
    user_after = [cb for cb in user_callbacks
                  if not getattr(cb, "before_iteration", False)]
    explicit = int(booster.gbdt.config.tpu_boost_chunk) != 0
    if callbacks_before or fobj is not None:
        chunk = 1
    elif has_eval and (chunk > 1 or explicit):
        blocker = ("feval" if feval is not None
                   else booster.setup_inscan_eval(train_in_valid))
        if blocker is None:
            use_inscan = True
        else:
            TELEMETRY.gauge_set(f"boost/inscan_blocked[{blocker}]", 1)
            chunk = 1
    elif chunk > 1 and not explicit and user_after:
        # auto chunking never changes a run's callback cadence
        chunk = 1
    # streaming run-health layer (health_out= / LIGHTGBM_TPU_HEALTH_JSONL):
    # per-iteration and per-eval records appended while the loop runs, so
    # a long job is observable before its finally-flush
    health_path = HEALTH.resolve_path(booster.gbdt.config)
    if health_path:
        HEALTH.open(health_path,
                    meta={"source": "engine",
                          "num_iterations": int(num_boost_round)})

    def replay_inscan(base_iter: int) -> bool:
        """The chunk's per-iteration metric rows through the normal
        callback cadence (print/record/early-stop see exactly what
        per-iteration stepping shows them); True when a callback stopped
        training inside the chunk."""
        for j, vals in booster.take_inscan_evals():
            jr = int(j) - base_iter
            evaluation_result_list = booster.inscan_result_list(vals)
            if HEALTH.active:
                HEALTH.record("eval", {
                    "iter": jr, "in_scan": True,
                    "metrics": {f"{dn}/{mn}": float(v)
                                for dn, mn, v, _ in evaluation_result_list}})
            try:
                for cb in callbacks_after:
                    cb(callback_mod.CallbackEnv(
                        model=booster, params=params, iteration=jr,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=evaluation_result_list))
            except callback_mod.EarlyStopException as e:
                booster.best_iteration = e.best_iteration + 1
                for item in e.best_score:
                    booster.best_score.setdefault(
                        item[0], {})[item[1]] = item[2]
                # the stop fired INSIDE the chunk: surplus tail-of-chunk
                # trees are discarded before they become model state, so
                # the final model matches a per-iteration early stop
                while booster.gbdt.current_iteration() > j + 1:
                    booster.gbdt.rollback_one_iter()
                return True
        return False

    # memory_session brackets the run with HBM gauge samples and owns the
    # optional background sampler's lifetime (stopped even when a callback
    # or device error raises out of the loop)
    failed = False
    try:
        with profile_session(booster.gbdt.config), \
                TELEMETRY.memory_session():
            i = 0
            # in-scan rows carry GBDT-global iteration indices; with an
            # init_model those are offset from the engine's 0-based count
            base_iter = (booster.gbdt.current_iteration()
                         if use_inscan else 0)
            while i < num_boost_round:
                step = min(chunk, num_boost_round - i)
                # a profile_window boundary splits the chunk so the
                # capture covers exactly the requested iteration span
                step = PROFILE_WINDOW.clamp_step(i, step)
                PROFILE_WINDOW.step(i)
                for cb in callbacks_before:
                    cb(callback_mod.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=None))
                if step > 1 or use_inscan:
                    should_stop = booster.update_chunk(step)
                else:
                    should_stop = booster.update(fobj=fobj)
                it = i + step - 1

                if use_inscan:
                    with GLOBAL_TIMER.phase("eval_replay"):
                        stopped_early = replay_inscan(base_iter)
                    if stopped_early or should_stop:
                        break
                    i += step
                    continue

                evaluation_result_list = []
                if booster._valid_names or train_in_valid:
                    if train_in_valid:
                        evaluation_result_list.extend(
                            booster.eval_train(feval))
                    evaluation_result_list.extend(booster.eval_valid(feval))
                if evaluation_result_list and HEALTH.active:
                    HEALTH.record("eval", {
                        "iter": int(it), "in_scan": False,
                        "metrics": {f"{dn}/{mn}": float(v)
                                    for dn, mn, v, _ in
                                    evaluation_result_list}})
                try:
                    with GLOBAL_TIMER.phase("callbacks"):
                        for cb in callbacks_after:
                            cb(callback_mod.CallbackEnv(
                                model=booster, params=params, iteration=it,
                                begin_iteration=0,
                                end_iteration=num_boost_round,
                                evaluation_result_list=(
                                    evaluation_result_list)))
                except callback_mod.EarlyStopException as e:
                    booster.best_iteration = e.best_iteration + 1
                    for item in e.best_score:
                        booster.best_score.setdefault(
                            item[0], {})[item[1]] = item[2]
                    break
                if should_stop:
                    break
                i += step
    except BaseException:
        failed = True
        raise
    finally:
        if failed:
            # a raising callback or device error must still leave the run's
            # telemetry on the returned/half-trained booster and flush the
            # Chrome trace — the partial run is often the one worth debugging
            booster.train_stats = TELEMETRY.stats()
            TELEMETRY.maybe_export_trace()
        if health_path:
            # settle the async tree pipeline so the last iterations'
            # records land before the summary; best-effort on the
            # failure path (the original exception stays primary)
            try:
                booster.gbdt.models
            except Exception:
                pass
            # summary record (aborted on the failure path) + descriptor
            # release; the digest stays in stats()' health section.
            # The summary carries the trained model's top-K feature
            # importances so the stream describes the model's shape,
            # not just the run's
            HEALTH.close(aborted=failed,
                         extra=_importance_summary(booster))
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.gbdt.current_iteration()
    # success path: snapshot AFTER the finalizing fetch above so the
    # attached counters match a later stats() call exactly
    booster.train_stats = TELEMETRY.stats()
    TELEMETRY.maybe_export_trace()
    return booster


class CVBooster:
    """Ensemble of per-fold boosters returned by cv(return_cvbooster=True)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, nfold: int, params: Dict, seed: int,
                  stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data()
    group = full_data.get_group()
    rng = np.random.RandomState(seed)
    if group is not None:
        # group-aware folds: split whole queries (engine.py:310-340)
        num_group = len(group)
        gidx = np.arange(num_group)
        if shuffle:
            rng.shuffle(gidx)
        boundaries = np.concatenate([[0], np.cumsum(group)]).astype(np.int64)
        folds_rows = [[] for _ in range(nfold)]
        folds_groups = [[] for _ in range(nfold)]
        for i, g in enumerate(gidx):
            f = i % nfold
            folds_rows[f].extend(range(boundaries[g], boundaries[g + 1]))
            folds_groups[f].append(int(group[g]))
        for f in range(nfold):
            test_rows = np.asarray(sorted(folds_rows[f]), dtype=np.int64)
            train_rows = np.setdiff1d(np.arange(num_data), test_rows)
            yield train_rows, test_rows
        return
    label = full_data.get_label()
    if stratified and label is not None:
        order = np.argsort(label, kind="stable")
        folds = [order[f::nfold] for f in range(nfold)]
    else:
        idx = np.arange(num_data)
        if shuffle:
            rng.shuffle(idx)
        folds = np.array_split(idx, nfold)
    for f in range(nfold):
        test_rows = np.sort(folds[f])
        train_rows = np.setdiff1d(np.arange(num_data), test_rows)
        yield train_rows, test_rows


def _agg_cv_result(raw_results):
    cvmap = collections.OrderedDict()
    metric_type = {}
    for one_result in raw_results:
        for one_line in one_result:
            key = f"{one_line[0]} {one_line[1]}"
            metric_type[key] = one_line[3]
            cvmap.setdefault(key, [])
            cvmap[key].append(one_line[2])
    return [("cv_agg", k, float(np.mean(v)), metric_type[k],
             float(np.std(v))) for k, v in cvmap.items()]


def cv(params: Dict, train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name: str = "auto",
       categorical_feature: str = "auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    params = dict(params or {})
    num_boost_round = _resolve_num_boost_round(params, num_boost_round)
    if metrics:
        params["metric"] = metrics
    if fobj is not None:
        params["objective"] = "none"
    obj_name = str(params.get("objective", "")).lower()
    if stratified and obj_name not in ("binary", "multiclass",
                                       "multiclassova"):
        stratified = False

    train_set.construct()
    if folds is None:
        folds = list(_make_n_folds(train_set, nfold, params, seed,
                                   stratified, shuffle))
    elif hasattr(folds, "split"):
        label = train_set.get_label()
        folds = list(folds.split(np.zeros(train_set.num_data()), label))

    cvbooster = CVBooster()
    fold_data = []
    for train_rows, test_rows in folds:
        tr = train_set.subset(train_rows)
        te = train_set.subset(test_rows)
        if fpreproc is not None:
            tr, te, fold_params = fpreproc(tr, te, dict(params))
        else:
            fold_params = params
        b = Booster(params=fold_params, train_set=tr)
        te.reference = tr
        b.add_valid(te, "valid")
        cvbooster.append(b)
        fold_data.append(b)

    callbacks = list(callbacks or [])
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        callbacks.append(callback_mod.early_stopping(
            early_stopping_rounds, verbose=False))
    if verbose_eval is True:
        callbacks.append(callback_mod.print_evaluation(show_stdv=show_stdv))
    elif isinstance(verbose_eval, int) and verbose_eval:
        callbacks.append(callback_mod.print_evaluation(verbose_eval,
                                                       show_stdv))
    callbacks.sort(key=lambda cb: getattr(cb, "order", 0))

    results = collections.defaultdict(list)
    for i in range(num_boost_round):
        for b in fold_data:
            b.update(fobj=fobj)
        raw = []
        for b in fold_data:
            one = []
            if eval_train_metric:
                one.extend(b.eval_train(feval))
            one.extend(b.eval_valid(feval))
            raw.append(one)
        agg = _agg_cv_result(raw)
        for _, key, mean, _, std in agg:
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
        try:
            for cb in callbacks:
                cb(callback_mod.CallbackEnv(
                    model=cvbooster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=agg))
        except callback_mod.EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for k in list(results):
                results[k] = results[k][: cvbooster.best_iteration]
            break
    if return_cvbooster:
        results["cvbooster"] = cvbooster
    return dict(results)
