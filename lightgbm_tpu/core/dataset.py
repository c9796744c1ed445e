"""The binned training Dataset.

Reference: include/LightGBM/dataset.h:283-637 + src/io/dataset.cpp (Dataset,
FeatureGroup, bin storage) and src/io/dataset_loader.cpp (construction from
raw data: sample -> FindBin -> quantize all rows).

TPU-first design departure (SURVEY.md §7): instead of per-group
dense/sparse/4-bit bin storage classes with OpenMP push pipelines
(src/io/dense_bin.hpp:48, sparse_bin.hpp:73), the dataset is ONE dense
HBM-resident bin matrix ``[num_data, num_used_features]`` of uint8/uint16.
Everything downstream (histograms, partitions) is a vectorized XLA/Pallas op
over this matrix.  Sparse features stay dense here: bins compress the value
range to <=max_bin levels, so a column is 1-2 bytes/row regardless of sparsity
— EFB-style bundling becomes a pure memory optimization (later round) rather
than a correctness requirement.
"""

from __future__ import annotations

import struct
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..config import Config
from ..utils.log import check, log_fatal, log_info, log_warning
from .binning import (BIN_TYPE_CATEGORICAL, BIN_TYPE_NUMERICAL, BinMapper,
                      MISSING_NAN, MISSING_NONE, MISSING_ZERO)
from .bundle import BundleSpec, build_bundle, quantize_bundled
from .metadata import Metadata

_BINARY_MAGIC = b"lightgbm_tpu.dataset.v1\x00"


class FeatureInfo:
    """Per-used-feature metadata consumed by the tree learner.

    ``group``/``offset`` locate the feature inside the physical bin matrix
    (EFB bundling, core/bundle.py): column ``group`` holds this feature's
    bins at ``offset + bin``.  Unbundled datasets have group == the
    feature's own column and offset == 0.
    """

    __slots__ = ("num_bin", "missing_type", "default_bin", "is_categorical",
                 "monotone", "penalty", "group", "offset")

    def __init__(self, num_bin, missing_type, default_bin, is_categorical,
                 monotone=0, penalty=1.0, group=0, offset=0):
        self.num_bin = num_bin
        self.missing_type = missing_type
        self.default_bin = default_bin
        self.is_categorical = is_categorical
        self.monotone = monotone
        self.penalty = penalty
        self.group = group
        self.offset = offset


class _SortedSample:
    """The binning sample's columns, sorted: ``column(f)`` is what
    ``BinMapper.find_bin`` would make of column ``f`` itself (float64,
    ascending, NaNs counted and dropped, -0.0 as 0.0), from one
    ``np.sort`` over a slab of ``SLAB`` columns at a time.  Columns are
    asked for in order, so a slab is sorted once."""

    SLAB = 64

    def __init__(self, block: np.ndarray):
        self._block = block
        self._first = -1

    def column(self, f: int):
        first = f - f % self.SLAB
        if first != self._first:
            # a COPY (np.array): one float64 column transposed is already
            # contiguous, and sorting a view would sort the caller's data
            x = np.array(self._block[:, first:first + self.SLAB].T,
                         dtype=np.float64, order="C")
            x += 0.0
            x.sort(axis=1)              # NaNs last
            self._rows, self._first = x, first
            self._na = np.isnan(x).sum(axis=1)
        na = int(self._na[f - first])
        row = self._rows[f - first]
        return row[:len(row) - na], na


class TpuDataset:
    """Binned dataset: dense uint8/16 matrix + per-feature BinMappers + Metadata."""

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.bin_mappers: List[BinMapper] = []       # one per original feature
        self.used_feature_indices: np.ndarray = np.array([], dtype=np.int32)
        self.binned: Optional[np.ndarray] = None     # [N, F_used] uint8/uint16
        self.metadata = Metadata()
        self.feature_names: List[str] = []
        self.max_num_bin: int = 0
        self.monotone_constraints: Optional[List[int]] = None
        self.feature_penalty: Optional[List[float]] = None
        self.bundle: Optional[BundleSpec] = None   # EFB packing; None = plain
        self._device_binned = None

    # ------------------------------------------------------------ construction
    @classmethod
    def from_numpy(cls, data: np.ndarray, label: Optional[np.ndarray] = None,
                   config: Optional[Config] = None,
                   weights: Optional[np.ndarray] = None,
                   group: Optional[np.ndarray] = None,
                   init_score: Optional[np.ndarray] = None,
                   categorical_features: Sequence[int] = (),
                   feature_names: Optional[List[str]] = None,
                   reference: Optional["TpuDataset"] = None) -> "TpuDataset":
        """Build a dataset from a raw [N, F] float matrix.

        Mirrors DatasetLoader::CostructFromSampleData (dataset_loader.cpp:553):
        sample rows -> per-feature BinMapper::FindBin -> quantize every row.
        When ``reference`` is given, its bin mappers are reused so validation
        data aligns with training bins (Dataset::CreateValid, dataset.cpp:435).
        """
        cfg = config or Config()
        data = np.asarray(data)
        if data.ndim != 2:
            raise ValueError("data must be 2-dimensional [num_data, num_features]")
        n, num_features = data.shape
        ds = cls()
        ds.num_data = n
        ds.num_total_features = num_features
        ds.feature_names = (list(feature_names) if feature_names
                            else [f"Column_{i}" for i in range(num_features)])

        from ..utils.phase import GLOBAL_TIMER
        if reference is not None:
            check(reference.num_total_features == num_features,
                  "validation data has a different number of features")
            ds.bin_mappers = reference.bin_mappers
            ds.used_feature_indices = reference.used_feature_indices
            ds.max_num_bin = reference.max_num_bin
            ds.monotone_constraints = reference.monotone_constraints
            ds.feature_penalty = reference.feature_penalty
            ds.feature_names = list(reference.feature_names)
            ds.bundle = reference.bundle
        else:
            with GLOBAL_TIMER.phase("bin_find"):
                ds._fit_bin_mappers(data, cfg,
                                    set(int(c) for c in categorical_features))
                ds._build_bundle(cfg, lambda f, sample_idx=ds._sample_idx: (
                    np.asarray(data[sample_idx, ds.used_feature_indices[f]],
                               dtype=np.float64)))

        with GLOBAL_TIMER.phase("bin_quantize"):
            ds._quantize(data)
        ds.metadata.init(n)
        if label is not None:
            ds.metadata.set_label(label)
        if weights is not None:
            ds.metadata.set_weights(weights)
        if group is not None:
            ds.metadata.set_query(group)
        if init_score is not None:
            ds.metadata.set_init_score(init_score)
        return ds

    def _fit_bin_mappers(self, data: np.ndarray, cfg: Config,
                         categorical: set) -> None:
        sample_idx = self._pick_sample(data.shape[0], cfg)
        total = len(sample_idx)
        sorted_cols = None
        from ..parallel import network
        if (isinstance(data, np.ndarray) and data.dtype.kind == "f"
                and network.binning_world()[0] == 1):
            # the sampled [S, F] block gathered ONCE (a fancy-indexed
            # column apiece re-reads the whole table F times), then its
            # numerical columns sorted a slab at a time (a rank of a
            # multi-process run fits a strided share of the columns, one
            # at a time as before)
            block = (data if len(sample_idx) >= data.shape[0]
                     else data[sample_idx])
            sorted_cols = _SortedSample(block)
            data, sample_idx = block, slice(None)
        self._fit_bin_mappers_from_cols(
            cfg, categorical, data.shape[1],
            lambda f: np.asarray(data[sample_idx, f], dtype=np.float64),
            total, sorted_cols)

    def _fit_bin_mappers_from_cols(self, cfg: Config, categorical: set,
                                   num_features: int, col_vals_fn,
                                   total_sample_cnt: int,
                                   sorted_cols=None) -> None:
        """Shared bin-fitting tail for the dense and sparse constructors.

        ``col_vals_fn(f)`` returns feature f's sampled values; for sparse
        input these are the NONZEROS only — ``total_sample_cnt -
        len(values)`` values are implicitly zero (the reference's sparse
        FindBin convention, bin.cpp:210).

        Multi-process runs shard this loop: each rank fits the BinMappers
        of its modulo-strided feature subset from its local sample, then
        the serialized mappers are allgathered and merged — the
        reference's distributed bin finding
        (dataset_loader.cpp:933-1034).

        ``sorted_cols`` (a ``_SortedSample``) hands a numerical column
        over already sorted, many sorted in one call: the same
        BinMappers, bit for bit (tests/test_bulk_binning.py)."""
        from ..parallel import network
        world, rank = network.binning_world()
        max_bin_by_feature = list(cfg.max_bin_by_feature or [])

        def fit_one(f):
            bt = (BIN_TYPE_CATEGORICAL if f in categorical
                  else BIN_TYPE_NUMERICAL)
            mb = (max_bin_by_feature[f] if f < len(max_bin_by_feature)
                  else cfg.max_bin)
            kw = dict(total_sample_cnt=total_sample_cnt,
                      max_bin=mb, min_data_in_bin=cfg.min_data_in_bin,
                      min_split_data=cfg.min_data_in_leaf,
                      bin_type=bt, use_missing=cfg.use_missing,
                      zero_as_missing=cfg.zero_as_missing)
            if sorted_cols is not None and bt == BIN_TYPE_NUMERICAL:
                return BinMapper().find_bin_sorted(*sorted_cols.column(f),
                                                   **kw)
            return BinMapper().find_bin(col_vals_fn(f), **kw)

        if world > 1:
            local = {f: fit_one(f).to_dict()
                     for f in range(rank, num_features, world)}
            merged = {}
            for part in network.allgather_obj(local):
                merged.update(part)
            check(len(merged) == num_features,
                  "distributed bin finding did not cover every feature")
            self.bin_mappers = [BinMapper.from_dict(merged[f])
                                for f in range(num_features)]
        else:
            self.bin_mappers = [fit_one(f) for f in range(num_features)]
        used = [f for f, m in enumerate(self.bin_mappers) if not m.is_trivial]
        if not used:
            log_warning("There are no meaningful features, as all feature "
                        "values are constant.")
        self.used_feature_indices = np.asarray(used, dtype=np.int32)
        self.max_num_bin = max((self.bin_mappers[f].num_bin for f in used),
                               default=1)
        if cfg.monotone_constraints:
            mc = list(cfg.monotone_constraints)
            check(len(mc) == self.num_total_features,
                  "monotone_constraints length must equal number of features")
            self.monotone_constraints = [int(x) for x in mc]
        if cfg.feature_contri:
            fc = list(cfg.feature_contri)
            check(len(fc) == self.num_total_features,
                  "feature_contri length must equal number of features")
            self.feature_penalty = [float(x) for x in fc]

    @classmethod
    def from_scipy(cls, data, label: Optional[np.ndarray] = None,
                   config: Optional[Config] = None,
                   weights: Optional[np.ndarray] = None,
                   group: Optional[np.ndarray] = None,
                   init_score: Optional[np.ndarray] = None,
                   categorical_features: Sequence[int] = (),
                   feature_names: Optional[List[str]] = None,
                   reference: Optional["TpuDataset"] = None) -> "TpuDataset":
        """Build a dataset from a scipy sparse matrix WITHOUT densifying
        the raw values (LGBM_DatasetCreateFromCSR path, c_api.cpp:560).

        Bins are found from per-column nonzeros (implicit zeros counted via
        ``total_sample_cnt``, the reference's sparse FindBin convention,
        bin.cpp:210), and the quantized matrix is written column-by-column
        — peak extra memory is one dense column, and under EFB the result
        is the bundled [N, num_groups] matrix directly.
        """
        cfg = config or Config()
        csr = data.tocsr()
        n, num_features = csr.shape
        ds = cls()
        ds.num_data = n
        ds.num_total_features = num_features
        ds.feature_names = (list(feature_names) if feature_names
                            else [f"Column_{i}" for i in range(num_features)])
        csc = csr.tocsc()

        if reference is not None:
            check(reference.num_total_features == num_features,
                  "validation data has a different number of features")
            ds.bin_mappers = reference.bin_mappers
            ds.used_feature_indices = reference.used_feature_indices
            ds.max_num_bin = reference.max_num_bin
            ds.monotone_constraints = reference.monotone_constraints
            ds.feature_penalty = reference.feature_penalty
            ds.feature_names = list(reference.feature_names)
            ds.bundle = reference.bundle
        else:
            sample_idx = ds._pick_sample(n, cfg)
            sample_csc = (csc if len(sample_idx) >= n
                          else csr[sample_idx].tocsc())
            S = len(sample_idx)
            ds._fit_bin_mappers_from_cols(
                cfg, set(int(c) for c in categorical_features), num_features,
                lambda f: np.asarray(
                    sample_csc.data[sample_csc.indptr[f]:
                                    sample_csc.indptr[f + 1]],
                    dtype=np.float64),
                S)

            def sample_col(j):
                f = int(ds.used_feature_indices[j])
                out = np.zeros(S, dtype=np.float64)
                sl = slice(sample_csc.indptr[f], sample_csc.indptr[f + 1])
                out[sample_csc.indices[sl]] = sample_csc.data[sl]
                return out

            ds._build_bundle(cfg, sample_col)

        used = ds.used_feature_indices
        default_bins = np.asarray(
            [ds.bin_mappers[f].default_bin for f in used], dtype=np.int64)

        def col_bins(j):
            """Full [N] bin column of used feature j from the CSC slices;
            implicit zeros land on default_bin (== value_to_bin(0))."""
            f = int(used[j])
            m = ds.bin_mappers[f]
            out = np.full(n, default_bins[j], dtype=np.int64)
            sl = slice(csc.indptr[f], csc.indptr[f + 1])
            out[csc.indices[sl]] = m.value_to_bin(
                np.asarray(csc.data[sl], dtype=np.float64))
            return out

        if ds.bundle is not None:
            ds.binned = quantize_bundled(col_bins, ds.bundle, default_bins, n)
        else:
            dtype = np.uint8 if ds.max_num_bin <= 256 else np.uint16
            out = np.empty((n, len(used)), dtype=dtype)
            for j in range(len(used)):
                out[:, j] = col_bins(j).astype(dtype)
            ds.binned = out
        ds._device_binned = None
        ds.metadata.init(n)
        if label is not None:
            ds.metadata.set_label(label)
        if weights is not None:
            ds.metadata.set_weights(weights)
        if group is not None:
            ds.metadata.set_query(group)
        if init_score is not None:
            ds.metadata.set_init_score(init_score)
        return ds

    def _pick_sample(self, n: int, cfg: Config) -> np.ndarray:
        rng = np.random.RandomState(cfg.data_random_seed)
        sample_cnt = min(n, cfg.bin_construct_sample_cnt)
        self._sample_idx = (np.arange(n) if sample_cnt >= n
                            else rng.choice(n, sample_cnt, replace=False))
        return self._sample_idx

    def _build_bundle(self, cfg: Config, sample_col_fn) -> None:
        """EFB grouping from the binning sample (Dataset::Construct ->
        FastFeatureBundling, src/io/dataset.cpp:235-241).
        ``sample_col_fn(j)`` -> raw [S] float64 sample of used feature j.

        Multi-process runs take rank 0's grouping for everyone: the
        BundleSpec defines the physical column layout, and ranks deriving
        it from their own local samples could disagree — then sharded
        histograms would combine mismatched columns."""
        if not cfg.enable_bundle or len(self.used_feature_indices) <= 1:
            return
        from ..parallel import network
        world, rank = network.binning_world()
        used = self.used_feature_indices
        num_bins = np.asarray([self.bin_mappers[f].num_bin for f in used],
                              dtype=np.int64)
        spec = None
        if rank == 0:
            default_bins = np.asarray(
                [self.bin_mappers[f].default_bin for f in used],
                dtype=np.int64)
            sparse_rates = np.asarray(
                [self.bin_mappers[f].sparse_rate for f in used])

            def nonzero_fn(j):
                m = self.bin_mappers[used[j]]
                return m.value_to_bin(sample_col_fn(j)) != default_bins[j]

            S = len(self._sample_idx)
            spec = build_bundle(nonzero_fn, len(used), S, num_bins,
                                sparse_rates, cfg.sparse_threshold,
                                cfg.max_conflict_rate)
        if world > 1:
            groups = network.allgather_obj(
                spec.to_dict() if spec is not None else None)[0]
            spec = (BundleSpec.from_dict(groups, num_bins)
                    if groups is not None else None)
        self.bundle = spec
        if self.bundle is not None:
            log_info(f"EFB bundled {len(used)} features into "
                     f"{self.bundle.num_groups} groups")

    def _quantize(self, data: np.ndarray) -> None:
        used = self.used_feature_indices

        if self.bundle is not None:
            default_bins = np.asarray(
                [self.bin_mappers[f].default_bin for f in used],
                dtype=np.int64)

            def col_fn(j):
                return self.bin_mappers[used[j]].value_to_bin(
                    np.asarray(data[:, used[j]], dtype=np.float64))

            self.binned = quantize_bundled(col_fn, self.bundle, default_bins,
                                           data.shape[0])
            self._device_binned = None
            return
        dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
        # native one-pass quantizer for the NUMERICAL columns
        # (src/native/fastbin.cpp lgbmtpu_quantize_rows*) — the
        # per-column numpy loop paid 21-43s at 10.5M rows; categorical
        # columns (dict lookups) stay on the python path
        from .binning import BIN_TYPE_NUMERICAL
        from .native import quantize_rows_native
        out = np.empty((data.shape[0], len(used)), dtype=dtype)
        done = [False] * len(used)
        if isinstance(data, np.ndarray) and data.ndim == 2:
            num_pos = [j for j, f in enumerate(used)
                       if self.bin_mappers[f].bin_type
                       == BIN_TYPE_NUMERICAL]
            if num_pos:
                nat = quantize_rows_native(
                    data, [used[j] for j in num_pos], self.bin_mappers,
                    dtype)
                if nat is not None:
                    if len(num_pos) == len(used):
                        out = nat       # every column: no scatter by column
                    else:
                        out[:, num_pos] = nat
                    for j in num_pos:
                        done[j] = True
        for j, f in enumerate(used):
            if not done[j]:
                out[:, j] = self.bin_mappers[f].value_to_bin(
                    np.asarray(data[:, f], dtype=np.float64)).astype(dtype)
        self.binned = out
        self._device_binned = None

    # ---------------------------------------------------------------- accessors
    @property
    def num_used_features(self) -> int:
        return len(self.used_feature_indices)

    @property
    def num_columns(self) -> int:
        """Physical bin-matrix columns (== groups under EFB)."""
        return (self.bundle.num_groups if self.bundle is not None
                else len(self.used_feature_indices))

    @property
    def max_column_bin(self) -> int:
        """Max bins of any physical column (histogram bin-axis size)."""
        return (int(self.bundle.group_num_bin.max(initial=1))
                if self.bundle is not None else self.max_num_bin)

    @property
    def column_bins(self) -> np.ndarray:
        """Per-column bin counts (feature-parallel stripes balance on this,
        as the reference balances shards by #bins —
        feature_parallel_tree_learner.cpp:36-47)."""
        if self.bundle is not None:
            return np.asarray(self.bundle.group_num_bin, dtype=np.int64)
        return np.asarray([self.bin_mappers[f].num_bin
                           for f in self.used_feature_indices],
                          dtype=np.int64)

    def feature_infos(self) -> List[FeatureInfo]:
        infos = []
        for j, f in enumerate(self.used_feature_indices):
            m = self.bin_mappers[f]
            mono = 0
            if self.monotone_constraints is not None:
                mono = self.monotone_constraints[f]
            pen = 1.0
            if self.feature_penalty is not None:
                pen = self.feature_penalty[f]
            if self.bundle is not None:
                grp = int(self.bundle.feat_group[j])
                off = int(self.bundle.feat_offset[j])
            else:
                grp, off = j, 0
            infos.append(FeatureInfo(m.num_bin, m.missing_type, m.default_bin,
                                     m.is_categorical, mono, pen, grp, off))
        return infos

    def real_threshold(self, used_feature: int, bin_threshold: int) -> float:
        """Bin threshold -> real-valued threshold for the saved model
        (reference Dataset::RealThreshold)."""
        f = int(self.used_feature_indices[used_feature])
        return self.bin_mappers[f].bin_to_value(int(bin_threshold))

    def inner_feature_index(self, real_feature: int) -> int:
        hits = np.nonzero(self.used_feature_indices == real_feature)[0]
        return int(hits[0]) if len(hits) else -1

    def host_binned(self) -> np.ndarray:
        """Row-major [N, F] host bin matrix — the exact byte image
        ``device_binned`` uploads (shared with the host-spill store so
        resident and spilled training see identical device bytes)."""
        return self.binned

    def host_binned_T(self, row_multiple: int = 1, packed4: bool = False,
                      feature_multiple: int = 1) -> np.ndarray:
        """Host-side feature-major training layout — the exact byte
        image ``device_binned_T`` uploads (see there for the layout
        contract); factored out so the host-spill store streams the
        same bytes the resident path would."""
        n, f = self.binned.shape
        npad = (-n) % row_multiple
        if packed4:
            from ..ops.pallas_histogram import pack_bins_4bit
            t = pack_bins_4bit(np.pad(np.ascontiguousarray(self.binned.T),
                                      ((0, 0), (0, npad))))
            fpad = (-t.shape[0]) % feature_multiple
            return np.pad(t, ((0, fpad), (0, 0))) if fpad else t
        # one allocation at the padded size: the transposed copy is the
        # only pass over a table that may hold gigabytes
        t = np.zeros((f + (-f) % feature_multiple, n + npad),
                     self.binned.dtype)
        t[:f, :n] = self.binned.T
        return t

    def drop_device_cache(self) -> None:
        """Release the cached device copies of the bin matrix (the
        host-spill tier streams from the host arrays instead; keeping
        the device cache alive would defeat the spill)."""
        self._device_binned = None
        self._device_binned_T = None
        self._device_binned_T_key = None

    def device_binned(self):
        """The bin matrix as a device array (uploaded once, cached)."""
        import jax.numpy as jnp
        if self._device_binned is None:
            from ..utils.phase import GLOBAL_TIMER
            from ..utils.telemetry import TELEMETRY
            TELEMETRY.counter_add("transfer/h2d_bytes",
                                  int(self.binned.nbytes))
            with GLOBAL_TIMER.phase("h2d_upload"):
                self._device_binned = jnp.asarray(self.binned)
        return self._device_binned

    def device_binned_T(self, row_multiple: int = 1, packed4: bool = False,
                        feature_multiple: int = 1):
        """Feature-major [F, Npad] bin matrix, rows padded to a multiple of
        ``row_multiple`` (pad rows are bin 0; training must give them zero
        weight).  This is the training layout: each feature is a contiguous
        lane stream for the histogram kernels.  ``packed4`` packs two
        <=16-bin columns per byte (Dense4bitsBin equivalent,
        dense_nbits_bin.hpp:42): [ceil(F/2), Npad] on device.
        ``feature_multiple`` pads the feature axis with all-zero bin rows
        to whole feature tiles (ops/pallas_histogram.feature_tile), which
        no split reads."""
        import jax.numpy as jnp
        key = getattr(self, "_device_binned_T_key", None)
        if key != (row_multiple, packed4, feature_multiple):
            from ..utils.phase import GLOBAL_TIMER
            from ..utils.telemetry import TELEMETRY
            with GLOBAL_TIMER.phase("h2d_upload"):
                t = self.host_binned_T(row_multiple, packed4,
                                       feature_multiple)
                TELEMETRY.counter_add("transfer/h2d_bytes", int(t.nbytes))
                self._device_binned_T = jnp.asarray(t)
            self._device_binned_T_key = (row_multiple, packed4,
                                         feature_multiple)
        return self._device_binned_T

    def check_align(self, other: "TpuDataset") -> None:
        """Fatal unless ``other``'s bins align with this dataset's
        (Dataset::CheckAlign / BinMapper::CheckAlign, dataset.h:301,
        bin.h:86): binned routing on mismatched mappers is silently
        wrong, so the mismatch must be an error."""
        msg = ("Cannot use this dataset: its bin mappers differ from the "
               "training data's (construct it with the training set as "
               "reference)")
        if other.bin_mappers is self.bin_mappers:
            pass
        elif other.num_total_features != self.num_total_features:
            log_fatal(msg)
        else:
            for ma, mb in zip(self.bin_mappers, other.bin_mappers):
                if (ma.num_bin != mb.num_bin
                        or ma.bin_type != mb.bin_type
                        or ma.missing_type != mb.missing_type
                        # equal_nan: MISSING_NAN mappers end with a NaN bound
                        or not np.array_equal(ma.bin_upper_bound,
                                              mb.bin_upper_bound,
                                              equal_nan=True)
                        # categorical routing lives in the category->bin
                        # map, not the (unused) numerical bounds
                        or ma.bin_2_categorical != mb.bin_2_categorical):
                    log_fatal(msg)
        sb, ob = self.bundle, other.bundle
        if (sb is None) != (ob is None) or (
                sb is not None and ob is not sb
                and (not np.array_equal(sb.feat_group, ob.feat_group)
                     or not np.array_equal(sb.feat_offset, ob.feat_offset))):
            log_fatal("Cannot use this dataset: its EFB column layout "
                      "differs from the training data's")

    def create_valid(self, data, label: Optional[np.ndarray] = None,
                     **kwargs) -> "TpuDataset":
        if hasattr(data, "tocsr"):            # scipy sparse
            return TpuDataset.from_scipy(data, label=label, reference=self,
                                         **kwargs)
        return TpuDataset.from_numpy(data, label=label, reference=self, **kwargs)

    def add_features_from(self, other: "TpuDataset") -> None:
        """Merge another dataset's feature columns into this one
        (Dataset::addFeaturesFrom, src/io/dataset.cpp:AddFeaturesFrom;
        LGBM_DatasetAddFeaturesFrom).  Row counts must match; the source's
        metadata (labels etc.) is ignored, as in the reference."""
        from ..utils.log import check
        check(self.num_data == other.num_data,
              "Cannot add features from other Dataset with a different "
              "number of rows")
        check(self.bundle is None and other.bundle is None,
              "add_features_from does not support EFB-bundled datasets; "
              "construct with enable_bundle=false")
        offset = self.num_total_features
        self.bin_mappers = list(self.bin_mappers) + list(other.bin_mappers)
        self.used_feature_indices = np.concatenate([
            self.used_feature_indices,
            np.asarray(other.used_feature_indices, dtype=np.int32) + offset,
        ]).astype(np.int32)
        self.num_total_features += other.num_total_features
        self.feature_names = list(self.feature_names) + [
            (n if n not in self.feature_names else f"{n}_dup")
            for n in other.feature_names]
        if self.monotone_constraints is not None \
                or other.monotone_constraints is not None:
            a = self.monotone_constraints or [0] * offset
            b = other.monotone_constraints or [0] * other.num_total_features
            self.monotone_constraints = list(a) + list(b)
        if self.feature_penalty is not None \
                or other.feature_penalty is not None:
            a = self.feature_penalty or [1.0] * offset
            b = other.feature_penalty or [1.0] * other.num_total_features
            self.feature_penalty = list(a) + list(b)
        dtype = (np.uint16 if (self.binned.dtype == np.uint16
                               or other.binned.dtype == np.uint16)
                 else np.uint8)
        self.binned = np.concatenate(
            [self.binned.astype(dtype), other.binned.astype(dtype)], axis=1)
        self.max_num_bin = max(self.max_num_bin, other.max_num_bin)
        self._device_binned = None
        self._device_binned_T_key = None

    # ----------------------------------------------------------- binary cache
    def save_binary(self, filename: str) -> None:
        """Binary dataset cache (reference Dataset::SaveBinaryFile,
        dataset.cpp:624; format is ours, token-checked the same way)."""
        import json
        meta = {
            "num_data": self.num_data,
            "num_total_features": self.num_total_features,
            "feature_names": self.feature_names,
            "used_feature_indices": self.used_feature_indices.tolist(),
            "max_num_bin": self.max_num_bin,
            "bin_mappers": [m.to_dict() for m in self.bin_mappers],
            "has_weights": self.metadata.weights is not None,
            "has_query": self.metadata.query_boundaries is not None,
            "has_init_score": self.metadata.init_score is not None,
            "binned_dtype": str(self.binned.dtype),
            "bundle": (self.bundle.to_dict() if self.bundle is not None
                       else None),
        }
        blob = json.dumps(meta).encode()
        from ..utils.file_io import open_file
        with open_file(filename, "wb") as fh:
            fh.write(_BINARY_MAGIC)
            fh.write(struct.pack("<q", len(blob)))
            fh.write(blob)
            fh.write(self.binned.tobytes())
            fh.write(self.metadata.label.astype(np.float32).tobytes())
            if self.metadata.weights is not None:
                fh.write(self.metadata.weights.astype(np.float32).tobytes())
            if self.metadata.query_boundaries is not None:
                fh.write(struct.pack("<q", len(self.metadata.query_boundaries)))
                fh.write(self.metadata.query_boundaries.astype(np.int32).tobytes())
            if self.metadata.init_score is not None:
                fh.write(struct.pack("<q", len(self.metadata.init_score)))
                fh.write(self.metadata.init_score.astype(np.float64).tobytes())
        log_info(f"Saved binary dataset to {filename}")

    @classmethod
    def load_binary(cls, filename: str) -> "TpuDataset":
        import json

        from ..utils.file_io import open_file
        with open_file(filename, "rb") as fh:
            magic = fh.read(len(_BINARY_MAGIC))
            if magic != _BINARY_MAGIC:
                log_fatal(f"{filename} is not a lightgbm_tpu binary dataset")
            (blob_len,) = struct.unpack("<q", fh.read(8))
            meta = json.loads(fh.read(blob_len).decode())
            ds = cls()
            ds.num_data = meta["num_data"]
            ds.num_total_features = meta["num_total_features"]
            ds.feature_names = meta["feature_names"]
            ds.used_feature_indices = np.asarray(meta["used_feature_indices"],
                                                 dtype=np.int32)
            ds.max_num_bin = meta["max_num_bin"]
            ds.bin_mappers = [BinMapper.from_dict(d) for d in meta["bin_mappers"]]
            if meta.get("bundle") is not None:
                used_nb = np.asarray(
                    [ds.bin_mappers[f].num_bin
                     for f in ds.used_feature_indices], dtype=np.int64)
                ds.bundle = BundleSpec.from_dict(meta["bundle"], used_nb)
            dtype = np.dtype(meta["binned_dtype"])
            ncols = ds.num_columns
            nbytes = ds.num_data * ncols * dtype.itemsize
            ds.binned = np.frombuffer(fh.read(nbytes), dtype=dtype).reshape(
                ds.num_data, ncols).copy()
            ds.metadata.init(ds.num_data)
            ds.metadata.label = np.frombuffer(
                fh.read(4 * ds.num_data), dtype=np.float32).copy()
            if meta["has_weights"]:
                ds.metadata.weights = np.frombuffer(
                    fh.read(4 * ds.num_data), dtype=np.float32).copy()
            if meta["has_query"]:
                (qlen,) = struct.unpack("<q", fh.read(8))
                ds.metadata.query_boundaries = np.frombuffer(
                    fh.read(4 * qlen), dtype=np.int32).copy()
            if meta["has_init_score"]:
                (slen,) = struct.unpack("<q", fh.read(8))
                ds.metadata.init_score = np.frombuffer(
                    fh.read(8 * slen), dtype=np.float64).copy()
        return ds
