"""Public Dataset / Booster API.

Mirrors the reference python-package surface (python-package/lightgbm/basic.py:
``Dataset`` :664 with lazy construction, ``Booster`` :1612 with
update/eval/predict/save) so user code written against LightGBM's Python API
ports over unchanged.  Instead of crossing a ctypes boundary into
lib_lightgbm.so, these classes drive the in-process TPU training stack
directly (core.dataset.TpuDataset + models.GBDT).
"""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config
from .core.dataset import TpuDataset
from .metric import default_metric_for_objective, metric_canonical_name
from .models.gbdt import GBDT
from .utils.log import LightGBMError, check, log_info, log_warning


def _pandas_categories(data) -> Optional[List[list]]:
    """Per-category-column category lists, in column order (None when the
    frame has no category columns / is not a frame)."""
    if not (hasattr(data, "dtypes") and hasattr(data, "columns")):
        return None
    out = [list(data[c].cat.categories) for c in data.columns
           if str(data[c].dtype) == "category"]
    return out or None


def _as_2d_float(data, num_features: Optional[int] = None,
                 pandas_categorical: Optional[List[list]] = None,
                 keep_float32: bool = False) -> np.ndarray:
    if (keep_float32 and isinstance(data, np.ndarray) and data.ndim == 2
            and data.dtype == np.float32):
        # binning reads a float32 table as it is (every value's float64
        # image is exact, and the native quantizer has an exact float32
        # path): a float64 copy of an 8.8 GB table is 17.6 GB and 40 s
        return data
    if hasattr(data, "dtypes") and hasattr(data, "columns") and any(
            str(dt) == "category" for dt in data.dtypes):
        # pandas DataFrame with category columns -> category CODES
        # (missing/unseen -> NaN), the reference's pandas handling.
        # ``pandas_categorical`` (recorded at train time and persisted in
        # the model file) pins the value->code mapping so predict frames
        # whose inferred category ORDER differs still encode correctly.
        n_cat = sum(1 for dt in data.dtypes if str(dt) == "category")
        if (pandas_categorical is not None
                and n_cat != len(pandas_categorical)):
            # positional matching would silently mis-align the mappings
            raise LightGBMError(
                f"train and predict/valid DataFrames have different "
                f"category-column counts ({len(pandas_categorical)} at "
                f"train, {n_cat} now)")
        cols = []
        cat_i = 0
        for c in data.columns:
            s = data[c]
            if str(s.dtype) == "category":
                if pandas_categorical is not None:
                    # vectorized re-code into the TRAIN category order
                    s = s.cat.set_categories(pandas_categorical[cat_i])
                codes = s.cat.codes.to_numpy().astype(np.float64)
                codes[codes < 0] = np.nan
                cols.append(codes)
                cat_i += 1
            else:
                cols.append(s.to_numpy(dtype=np.float64))
        data = np.stack(cols, axis=1)
    if hasattr(data, "values"):       # pandas
        data = data.values
    if hasattr(data, "toarray"):      # scipy sparse
        data = data.toarray()
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        # a 1-D vector is a single ROW when its length matches the model's
        # feature count (single-row predict), else a single column
        if num_features is not None and len(arr) == num_features:
            arr = arr[None, :]
        else:
            arr = arr[:, None]
    return arr


_PANDAS_CAT_KEY = "pandas_categorical:"


def _split_pandas_categorical(model_str: str):
    """Strip the trailing ``pandas_categorical:<json>`` line the Python
    layer appends to saved models (same file contract as the reference's
    python package, so either package reads the other's files).
    Returns (model_str_without_line, categories_or_None)."""
    import json
    idx = model_str.rfind("\n" + _PANDAS_CAT_KEY)
    if idx < 0:
        return model_str, None
    line = model_str[idx + 1 + len(_PANDAS_CAT_KEY):].strip()
    try:
        cats = json.loads(line)
    except json.JSONDecodeError:
        return model_str, None
    return model_str[:idx + 1], cats


class Dataset:
    """Lazily-constructed training dataset (reference basic.py:664)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List[int], List[str]] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._handle: Optional[TpuDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None
        # train-time category lists for pandas category columns (the
        # reference's pandas_categorical); set at construct, persisted in
        # saved models so predict frames encode consistently
        self.pandas_categorical: Optional[List[list]] = None

    # --------------------------------------------------------- construction
    def construct(self) -> "Dataset":
        if self._handle is not None:
            return self
        if isinstance(self.data, str):
            from .core.parser import load_file_to_dataset
            cfg = Config.from_params(self.params)
            self._handle = load_file_to_dataset(
                self.data, cfg,
                reference=(self.reference.construct()._handle
                           if self.reference is not None else None))
            return self
        cfg = Config.from_params(self.params)
        # scipy sparse input never densifies (TpuDataset.from_scipy bins
        # straight from the CSC slices; under EFB the bundled matrix is
        # built directly)
        is_sparse = (hasattr(self.data, "tocsr")
                     and not hasattr(self.data, "values"))
        if not is_sparse:
            # valid sets encode with the TRAINING frame's category lists;
            # the reference must be constructed first or its lists are
            # still unset (valid .construct() can legally run first)
            if self.reference is not None:
                self.reference.construct()
            self.pandas_categorical = (
                self.reference.pandas_categorical
                if self.reference is not None
                and self.reference.pandas_categorical is not None
                else _pandas_categories(self.data))
        data = (self.data if is_sparse
                else _as_2d_float(self.data,
                                  pandas_categorical=self.pandas_categorical,
                                  keep_float32=True))
        feature_names = None
        if isinstance(self.feature_name, (list, tuple)):
            feature_names = list(self.feature_name)
        elif hasattr(self.data, "columns"):
            feature_names = [str(c) for c in self.data.columns]
        cat_idx: List[int] = []
        if isinstance(self.categorical_feature, (list, tuple)):
            for c in self.categorical_feature:
                if isinstance(c, str):
                    if feature_names and c in feature_names:
                        cat_idx.append(feature_names.index(c))
                else:
                    cat_idx.append(int(c))
        elif self.categorical_feature == "auto":
            # params-level spec first: categorical_feature /
            # categorical_column aliases in the conf dialect (the path
            # the reference resolves in its C++ Config; its own test
            # suite sets 'categorical_column': 0 this way).  A params
            # LIST str()-ifies through Config, so strip brackets too.
            spec = str(cfg.categorical_feature or "").strip("[]() ")
            for tok in spec.split(","):
                tok = tok.strip()
                if not tok:
                    continue
                name = tok[5:] if tok.startswith("name:") else tok
                if feature_names and name in feature_names:
                    cat_idx.append(feature_names.index(name))
                else:
                    try:
                        cat_idx.append(int(name))
                    except ValueError:
                        raise LightGBMError(
                            f"categorical_feature entry {tok!r} is "
                            f"neither a column index nor a feature name")
            if hasattr(self.data, "dtypes"):
                for i, dt in enumerate(self.data.dtypes):
                    if str(dt) == "category" and i not in cat_idx:
                        cat_idx.append(i)
        ref_handle = None
        if self.reference is not None:
            ref_handle = self.reference.construct()._handle
        label = np.asarray(self.label, dtype=np.float64).ravel() \
            if self.label is not None else None
        make = TpuDataset.from_scipy if is_sparse else TpuDataset.from_numpy
        self._handle = make(
            data, label=label, config=cfg,
            weights=(np.asarray(self.weight, dtype=np.float64).ravel()
                     if self.weight is not None else None),
            group=(np.asarray(self.group) if self.group is not None else None),
            init_score=(np.asarray(self.init_score, dtype=np.float64)
                        if self.init_score is not None else None),
            categorical_features=cat_idx,
            feature_names=feature_names,
            reference=ref_handle)
        if self.used_indices is not None:
            self._subset_in_place(self.used_indices)
        return self

    def _subset_in_place(self, indices: np.ndarray) -> None:
        h = self._handle
        sub = TpuDataset()
        sub.num_data = len(indices)
        sub.num_total_features = h.num_total_features
        sub.bin_mappers = h.bin_mappers
        sub.used_feature_indices = h.used_feature_indices
        sub.max_num_bin = h.max_num_bin
        sub.bundle = h.bundle
        sub.feature_names = h.feature_names
        sub.monotone_constraints = h.monotone_constraints
        sub.feature_penalty = h.feature_penalty
        sub.binned = h.binned[indices]
        sub.metadata = h.metadata.subset(indices)
        sub.metadata.num_data = len(indices)
        self._handle = sub

    def subset(self, used_indices: Sequence[int],
               params: Optional[Dict] = None) -> "Dataset":
        """Row-subset view sharing bin mappers (Dataset::CopySubset,
        dataset.cpp:503)."""
        ds = Dataset(self.data, label=self.label, reference=self,
                     weight=self.weight, group=self.group,
                     feature_name=self.feature_name,
                     categorical_feature=self.categorical_feature,
                     params=params or self.params)
        ds.used_indices = np.asarray(sorted(used_indices), dtype=np.int64)
        ds.reference = self
        return ds

    # ------------------------------------------------------------- fields
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._handle is not None and label is not None:
            self._handle.metadata.set_label(
                np.asarray(label, dtype=np.float64).ravel())
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._handle is not None:
            self._handle.metadata.set_weights(
                np.asarray(weight, dtype=np.float64).ravel()
                if weight is not None else None)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._handle is not None and group is not None:
            self._handle.metadata.set_query(np.asarray(group))
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(
                np.asarray(init_score, dtype=np.float64)
                if init_score is not None else None)
        return self

    def set_field(self, field_name: str, data) -> "Dataset":
        if field_name == "label":
            return self.set_label(data)
        if field_name == "weight":
            return self.set_weight(data)
        if field_name == "group":
            return self.set_group(data)
        if field_name == "init_score":
            return self.set_init_score(data)
        raise LightGBMError(f"Unknown field name {field_name}")

    def get_field(self, field_name: str):
        self.construct()
        md = self._handle.metadata
        if field_name == "label":
            return md.label
        if field_name == "weight":
            return md.weights
        if field_name == "group":
            return (np.diff(md.query_boundaries)
                    if md.query_boundaries is not None else None)
        if field_name == "init_score":
            return md.init_score
        raise LightGBMError(f"Unknown field name {field_name}")

    def get_label(self):
        return self.get_field("label")

    def get_weight(self):
        return self.get_field("weight")

    def get_group(self):
        return self.get_field("group")

    def get_init_score(self):
        return self.get_field("init_score")

    def num_data(self) -> int:
        self.construct()
        return self._handle.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._handle.num_total_features

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        self._handle.save_binary(filename)
        return self

    def create_valid(self, data, label=None, **kwargs) -> "Dataset":
        return Dataset(data, label=label, reference=self, **kwargs)


class Booster:
    """Training-capable model handle (reference basic.py:1612)."""

    def __init__(self, params: Optional[Dict] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        params = dict(params or {})
        self.params = params
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._valid_names: List[str] = []
        self._valid_sets: List["Dataset"] = []
        if train_set is not None:
            check(isinstance(train_set, Dataset),
                  "Training data should be a Dataset instance")
            # merge dataset-level params under booster params
            merged = dict(train_set.params or {})
            merged.update(params)
            self.config = Config.from_params(merged)
            train_set.params = merged
            train_set.construct()
            from .objective import create_objective
            from .models.boosting_factory import create_boosting
            self.objective = create_objective(self.config)
            if self.objective is not None:
                self.objective.init(train_set._handle.metadata,
                                    train_set._handle.num_data)
            self.gbdt = create_boosting(self.config, train_set._handle,
                                        self.objective)
            self.train_set = train_set
            self.pandas_categorical = train_set.pandas_categorical
            self._setup_metrics()
        elif model_file is not None or model_str is not None:
            from .models.serialization import load_model
            if model_file is not None:
                from .utils.file_io import open_file
                with open_file(model_file) as fh:
                    model_str = fh.read()
            model_str, self.pandas_categorical = \
                _split_pandas_categorical(model_str)
            self.gbdt, self.config, self.objective = load_model(model_str)
            self.train_set = None
        else:
            raise LightGBMError(
                "Booster needs train_set, model_file or model_str")

    # ----------------------------------------------------------- internals
    def _setup_metrics(self):
        names = list(self.config.metric)
        if not names:
            d = default_metric_for_objective(self.config.objective)
            if d:
                names = [d]
        seen = []
        for n in names:
            c = metric_canonical_name(n) or n
            if c not in seen:
                seen.append(c)
        self._metric_names = seen
        self.gbdt.setup_metrics(seen)

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        self.gbdt.add_valid_data(name, data._handle)
        self._valid_names.append(name)
        self._valid_sets.append(data)
        self._setup_metrics()
        return self

    # ------------------------------------------------------------ training
    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True when no further splits are
        possible (LGBM_BoosterUpdateOneIter, c_api.cpp:1143).  A new
        ``train_set`` swaps the training data first
        (LGBM_BoosterResetTrainingData; bins must align)."""
        if train_set is not None and train_set is not self.train_set:
            train_set.construct()
            # alignment is checked inside reset_train_data; the objective
            # and metrics re-bind only after it succeeds (atomic swap)
            self.gbdt.reset_train_data(train_set._handle)
            if self.objective is not None:
                h = train_set._handle
                self.objective.init(h.metadata, h.num_data)
            self.train_set = train_set
            self._setup_metrics()
        if fobj is not None:
            score = self.gbdt.train_score
            grad, hess = fobj(np.asarray(score).ravel(), self.train_set)
            return self.gbdt.train_one_iter(np.asarray(grad),
                                            np.asarray(hess))
        return self.gbdt.train_one_iter()

    def update_chunk(self, chunk: int) -> bool:
        """Run up to ``chunk`` boosting iterations as one on-device
        program with tree fetches batched at the chunk boundary
        (tpu_boost_chunk); falls back to a single iteration when the
        configuration needs per-iteration host work."""
        return self.gbdt.train_chunk(int(chunk))

    def setup_inscan_eval(self, include_train: bool = False):
        """Attach the device-side in-scan eval program (metric/device.py)
        so chunked updates score the valid sets and compute the attached
        metrics per iteration on-device.  Returns None on success or a
        short blocker string when a metric/objective isn't
        device-computable."""
        return self.gbdt.setup_inscan_eval(include_train)

    def take_inscan_evals(self) -> List:
        """Pop [(iteration, metric_row)] produced by in-scan eval since
        the last call (rows appear as their chunks materialize)."""
        return self.gbdt.take_inscan_evals()

    def inscan_result_list(self, vals) -> List:
        """One in-scan metric row -> [(set, metric, value, higher_better)],
        the eval_train/eval_valid result shape."""
        return self.gbdt.inscan_result_list(vals)

    def get_stats(self) -> Dict:
        """Training telemetry snapshot (utils/telemetry.py): phase
        seconds, transfer/compile/network counters, gauges and the
        per-iteration timeline, plus (v3) top-level ``schema`` and
        ``telemetry_level`` keys — downstream tools branch on those
        instead of sniffing sections — and a ``health`` digest when the
        run wrote a health stream.  ``engine.train`` attaches the same
        dict as ``booster.train_stats`` at the end of training."""
        from .utils.telemetry import TELEMETRY
        return TELEMETRY.stats()

    def rollback_one_iter(self) -> "Booster":
        self.gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self):
        return self.gbdt.current_iteration

    def num_trees(self) -> int:
        return len(self.gbdt.models)

    def num_model_per_iteration(self) -> int:
        return self.gbdt.num_tree_per_iteration

    def estimate_working_set(self) -> int:
        """Estimated device working set of training this booster, in
        bytes — the exact resolved-layout number the internal admission
        checks (``data_in_hbm=auto``, the sched plane's HBM gate) use
        for this run.  For a pre-construction estimate from a config and
        a ``(num_data, num_columns)`` shape alone, use module-level
        :func:`lightgbm_tpu.estimate_working_set`."""
        if self.train_set is None:
            raise LightGBMError(
                "estimate_working_set needs a training booster; for a "
                "model-only handle call lightgbm_tpu."
                "estimate_working_set(config, data_shape) instead")
        return self.gbdt._estimate_working_set()

    # ---------------------------------------------------------------- eval
    def _feval_preds(self, score) -> np.ndarray:
        """What feval receives: objective-TRANSFORMED predictions (the
        reference's GetPredictAt applies ConvertOutput for built-in
        objectives; raw margins only without one), class-major flat."""
        score = np.asarray(score)
        if self.objective is not None:
            score = np.asarray(self.objective.convert_output(score))
        return score.ravel()

    def eval_train(self, feval=None) -> List:
        out = [("training", name, val, hb)
               for name, val, hb in self.gbdt.eval_train()]
        if feval is not None:
            name, val, hb = feval(self._feval_preds(self.gbdt.train_score),
                                  self.train_set)
            out.append(("training", name, val, hb))
        return out

    def eval_valid(self, feval=None) -> List:
        out = []
        for i, name in enumerate(self._valid_names):
            out.extend([(name, mname, val, hb)
                        for mname, val, hb in self.gbdt.eval_valid(i)])
            if feval is not None and i < len(self._valid_sets):
                # custom metric on objective-transformed valid scores,
                # same contract as eval_train
                mname, val, hb = feval(
                    self._feval_preds(self.gbdt.valid_scores[i]),
                    self._valid_sets[i])
                out.append((name, mname, val, hb))
        return out

    # ------------------------------------------------------------- predict
    def predict(self, data, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                **kwargs) -> np.ndarray:
        if num_iteration is None or num_iteration < 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        n_feat = self.gbdt.max_feature_idx + 1
        X = _as_2d_float(data, n_feat,
                         pandas_categorical=getattr(
                             self, "pandas_categorical", None))
        if X.shape[1] != n_feat:
            raise LightGBMError(
                f"The number of features in data ({X.shape[1]}) is not the "
                f"same as it was in training data ({n_feat})")
        if pred_contrib:
            from .models.shap import predict_contrib
            return predict_contrib(self.gbdt, X, num_iteration)
        return self.gbdt.predict(X, num_iteration=num_iteration,
                                 raw_score=raw_score, pred_leaf=pred_leaf)

    def refit(self, data, label, weight=None, decay_rate: float = None
              ) -> "Booster":
        """Re-estimate every leaf's output on fresh (data, label) without
        changing the tree structure (reference ``Booster.refit``): each
        leaf blends its old value with the gradient-optimal one,
        ``new = decay * old + (1 - decay) * opt``.  Lets a served model
        absorb new data without a retrain; returns self."""
        from .core.metadata import Metadata
        from .models.refit import refit_model
        if not self.gbdt.models:
            raise LightGBMError("cannot refit a model with no trees")
        leaf_preds = np.asarray(self.predict(data, pred_leaf=True),
                                dtype=np.int32)
        if leaf_preds.ndim == 1:
            leaf_preds = leaf_preds[:, None]
        md = Metadata(leaf_preds.shape[0])
        md.init(leaf_preds.shape[0])
        md.set_label(np.asarray(label))
        if weight is not None:
            md.set_weights(np.asarray(weight))
        config = self.config
        if decay_rate is not None:
            import copy
            config = copy.copy(config)
            config.refit_decay_rate = float(decay_rate)
        refit_model(self.gbdt, md, leaf_preds, config)
        return self

    def serve(self, model_id: str = None, num_iteration: int = -1,
              session=None, **overrides):
        """A compiled micro-batching prediction handle for this model
        (lightgbm_tpu/serve, docs/SERVING.md).  Knobs come from this
        booster's ``serve_*`` params unless overridden; pass an existing
        :class:`~lightgbm_tpu.serve.ServeSession` to co-host several
        models in one device pack and one queue."""
        from .serve import ServeHandle, ServeSession
        owns = session is None
        if owns:
            session = ServeSession.from_config(self.config, **overrides)
        mid = session.load(self, model_id=model_id,
                           num_iteration=num_iteration)
        return ServeHandle(session, mid, owns_session=owns)

    # ---------------------------------------------------------------- model
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        # atomic tmp + os.replace for local paths: a crash mid-write
        # leaves the previous model (or nothing), never a torn file
        from .utils.file_io import atomic_write_text
        atomic_write_text(filename,
                          self.model_to_string(num_iteration,
                                               start_iteration))
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        import json

        from .models.serialization import save_model_to_string
        s = save_model_to_string(self.gbdt, self.config,
                                 num_iteration or -1, start_iteration)
        if getattr(self, "pandas_categorical", None):
            # same trailing-line contract as the reference python package
            s += "\n" + _PANDAS_CAT_KEY \
                + json.dumps(self.pandas_categorical) + "\n"
        return s

    def dump_model(self, num_iteration: Optional[int] = None) -> Dict:
        from .models.serialization import dump_model_dict
        return dump_model_dict(self.gbdt, self.config, num_iteration or -1)

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        return self.gbdt.feature_importance(importance_type, iteration)

    def feature_name(self) -> List[str]:
        return list(self.gbdt.feature_names)

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of the split threshold values the model uses for one
        feature (reference basic.py Booster.get_split_value_histogram).

        ``feature`` is a name or index; ``bins`` follows numpy.histogram
        (None = one bin per unique threshold).  Returns (counts, edges)
        like numpy, or a [k, 2] (SplitValue, Count) array of non-empty
        bins with ``xgboost_style=True``.
        """
        if isinstance(feature, str):
            names = self.feature_name()
            if feature not in names:
                raise LightGBMError(f"Unknown feature name {feature!r}")
            feature = names.index(feature)
        values = []
        for t in self.gbdt.models:
            n = t.num_leaves - 1
            for i in range(n):
                if (t.split_feature[i] == feature
                        and not (t.decision_type[i] & 1)):  # numerical only
                    values.append(float(t.threshold[i]))
        values = np.asarray(values, dtype=np.float64)
        if bins is None:
            bins = max(len(np.unique(values)), 1)
        counts, edges = np.histogram(values, bins=bins)
        if not xgboost_style:
            return counts, edges
        centers = (edges[:-1] + edges[1:]) / 2.0
        nz = counts > 0
        return np.stack([centers[nz], counts[nz].astype(np.float64)],
                        axis=1)

    # ------------------------------------------------------------- pickling
    def __getstate__(self):
        """Pickle via the text model (the reference Booster does the
        same): training state (dataset, device buffers, objective) does
        not survive — the restored Booster predicts and continues from
        the serialized trees only."""
        return {"model_str": self.model_to_string(),
                "params": self.params,
                "best_iteration": self.best_iteration,
                "best_score": self.best_score}

    def __setstate__(self, state):
        from .models.serialization import load_model
        self.params = state.get("params", {})
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self._valid_names = []
        self._valid_sets = []
        model_str, self.pandas_categorical = \
            _split_pandas_categorical(state["model_str"])
        self.gbdt, self.config, self.objective = load_model(model_str)
        self.train_set = None

    def set_network(self, machines, local_listen_port=12400,
                    listen_time_out=120, num_machines=1) -> "Booster":
        """Distributed setup: on TPU the mesh replaces the socket ring; this
        keeps the API seam (basic.py:1771 / LGBM_NetworkInit)."""
        from .parallel import network
        network.init_from_machines(machines, num_machines)
        return self

    def free_network(self) -> "Booster":
        from .parallel import network
        network.dispose()
        return self
