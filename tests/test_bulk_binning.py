"""Bulk bin finding: the binning sample gathered once and its columns sorted
a slab at a time (`core/dataset._SortedSample`) gives the BinMappers that
`BinMapper.find_bin` gives a column at a time, bit for bit; and a table of
dense columns is left unbundled without a column of it being read."""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.core.binning import BinMapper
from lightgbm_tpu.core.dataset import TpuDataset, _SortedSample

N = 20000


def _columns():
    rng = np.random.default_rng(5)
    return {
        "numerical": rng.standard_normal(N),
        "constant": np.full(N, 3.0),
        "few_distinct": rng.integers(0, 5, N).astype(float),
        "with_nan": np.where(rng.random(N) < 0.1, np.nan,
                             rng.standard_normal(N)),
        "zero_heavy": np.where(rng.random(N) < 0.9, 0.0,
                               rng.standard_normal(N)),
        "signed_zeros": np.where(rng.random(N) < 0.5, -0.0, 0.0)
        + (rng.random(N) < 0.2) * rng.standard_normal(N),
        "positive": rng.exponential(1.0, N),
        "negative": -rng.exponential(1.0, N),
        "nan_and_few": np.where(rng.random(N) < 0.3, np.nan,
                                rng.integers(-3, 3, N).astype(float)),
        "rounded": np.round(rng.standard_normal(N), 1),
        "denormal": np.where(rng.random(N) < 0.5, 0.0, 1e-40),
        "all_nan": np.full(N, np.nan),
    }


COLUMNS = _columns()
CONFIGS = {"bins63": {"max_bin": 63},
           "bins255_sampled": {"max_bin": 255,
                               "bin_construct_sample_cnt": 5000},
           "zero_as_missing": {"max_bin": 63, "zero_as_missing": True},
           "no_missing": {"max_bin": 31, "use_missing": False}}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def fitted(request):
    """The bulk path's mappers over every column kind (filler columns put
    them in different slabs), and what the per-column path needs."""
    cfg = Config.from_params(CONFIGS[request.param])
    names = sorted(COLUMNS)
    rng = np.random.default_rng(9)
    filler = rng.standard_normal((N, 70)).astype(np.float32)
    X = np.concatenate(
        [np.stack([COLUMNS[k] for k in names], 1).astype(np.float32),
         filler], axis=1)
    ds = TpuDataset()
    ds.num_total_features = X.shape[1]
    ds._fit_bin_mappers(X, cfg, set())
    return cfg, names, X, ds


@pytest.mark.parametrize("kind", sorted(COLUMNS))
def test_bulk_mapper_is_the_per_column_mapper(fitted, kind):
    cfg, names, X, ds = fitted
    f = names.index(kind)
    one = BinMapper().find_bin(
        np.asarray(X[ds._sample_idx, f], dtype=np.float64),
        total_sample_cnt=len(ds._sample_idx), max_bin=cfg.max_bin,
        min_data_in_bin=cfg.min_data_in_bin,
        min_split_data=cfg.min_data_in_leaf, use_missing=cfg.use_missing,
        zero_as_missing=cfg.zero_as_missing)
    bulk = ds.bin_mappers[f]
    assert bulk.bin_upper_bound.tobytes() == one.bin_upper_bound.tobytes()
    assert (bulk.num_bin, bulk.missing_type, bulk.default_bin,
            bulk.is_trivial) == (one.num_bin, one.missing_type,
                                 one.default_bin, one.is_trivial)
    assert (bulk.min_val, bulk.max_val, bulk.sparse_rate) == \
        (one.min_val, one.max_val, one.sparse_rate)
    if kind in ("constant", "all_nan"):
        assert bulk.is_trivial
    if kind == "with_nan" and cfg.use_missing and not cfg.zero_as_missing:
        assert np.isnan(bulk.bin_upper_bound[-1])


def test_sorted_sample_is_find_bins_own_sort():
    """A slab's rows are each column stably sorted without its NaNs, -0.0
    as 0.0: the sequence `find_bin` makes itself."""
    names = sorted(COLUMNS)
    block = np.stack([COLUMNS[k] for k in names], 1).astype(np.float32)
    sample = _SortedSample(block)
    for f, k in enumerate(names):
        row, na = sample.column(f)
        col = np.asarray(block[:, f], dtype=np.float64)
        want = np.sort(col[~np.isnan(col)] + 0.0, kind="stable")
        assert na == int(np.isnan(col).sum())
        assert row.tobytes() == want.tobytes(), k


def test_dense_table_is_left_unbundled_in_time_linear_in_columns():
    """No dense column is sparse enough to enter a bundle, so the search
    ends at the mappers' sparse rates: not one sampled column is read, let
    alone a pair compared."""
    rng = np.random.default_rng(2)
    X = rng.standard_normal((3000, 400)).astype(np.float32)
    cfg = Config.from_params({"max_bin": 63})
    ds = TpuDataset()
    ds.num_total_features = X.shape[1]
    ds._fit_bin_mappers(X, cfg, set())
    read = []
    ds._build_bundle(cfg, lambda j: read.append(j) or X[:, j])
    assert ds.bundle is None and read == []
    assert len(ds.used_feature_indices) == 400


def test_bin_finding_leaves_the_callers_table_alone():
    """One float64 column transposed is already contiguous: the slab that
    is sorted has to be a copy, not a view of the caller's rows."""
    rng = np.random.default_rng(4)
    X = rng.standard_normal((500, 1))
    X[::5] = np.nan
    before = X.copy()
    ds = TpuDataset()
    ds.num_total_features = 1
    ds._fit_bin_mappers(X, Config.from_params({"max_bin": 15}), set())
    assert np.array_equal(X, before, equal_nan=True)
