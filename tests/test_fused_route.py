"""Fused route+histogram kernels (ops/pallas_histogram.py r5).

The fused kernels fold the split's leaf_id routing into the histogram
pass (the reference's routing likewise rides the partition work,
src/treelearner/data_partition.hpp:111).  They must reproduce the
unfused route_split_windowed + histogram_segment/frontier pair exactly:
same leaf ids (including untouched blocks through the input/output
alias), same histograms, hence identical trees.
"""

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.core.dataset import TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objective import create_objective


def _train(X, y, impl, fused, monkeypatch, cat_feats=(), n_iters=3,
           **params):
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_ROUTE", "1" if fused else "0")
    cfg = Config(verbosity=-1, tpu_histogram_backend="pallas",
                 tpu_tree_impl=impl, **params)
    ds = TpuDataset.from_numpy(X, y, config=cfg,
                               categorical_features=list(cat_feats))
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    bst = GBDT(cfg, ds, obj)
    for _ in range(n_iters):
        bst.train_one_iter()
    return bst


def _assert_identical(a, b, X):
    assert len(a.models) == len(b.models)
    for i, (ta, tb) in enumerate(zip(a.models, b.models)):
        assert ta.num_leaves == tb.num_leaves, f"tree {i}"
        assert np.array_equal(ta.split_feature, tb.split_feature), i
        assert np.array_equal(ta.threshold_in_bin, tb.threshold_in_bin), i
        np.testing.assert_allclose(ta.leaf_value, tb.leaf_value,
                                   rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(a._raw_predict(X), b._raw_predict(X),
                               rtol=1e-6, atol=1e-7)


def test_kernel_self_check():
    from lightgbm_tpu.ops.pallas_histogram import _fused_route_self_check
    assert _fused_route_self_check()


@pytest.mark.parametrize("impl", ["segment", "frontier"])
def test_fused_matches_unfused(rng, monkeypatch, impl):
    """Numerical + categorical + NaN routing, multi-block, compaction."""
    n = 4000
    X = rng.normal(size=(n, 6))
    X[rng.random(size=n) < 0.1, 3] = np.nan
    X[:, 5] = rng.randint(0, 12, size=n)
    y = ((X[:, 0] + 0.5 * X[:, 1] > 0)
         | (X[:, 5] > 8)).astype(np.float64)
    kw = dict(objective="binary", num_leaves=31, max_bin=63,
              min_data_in_leaf=5)
    unfused = _train(X, y, impl, False, monkeypatch, cat_feats=[5], **kw)
    fused = _train(X, y, impl, True, monkeypatch, cat_feats=[5], **kw)
    assert fused._use_segment or impl == "frontier"
    _assert_identical(unfused, fused, X)


def test_fused_matches_unfused_packed4(rng, monkeypatch):
    """max_bin <= 15 selects the packed4 nibble layout; the in-kernel
    route must unpack the split column by parity."""
    n = 3000
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] - 0.7 * X[:, 2] > 0).astype(np.float64)
    kw = dict(objective="binary", num_leaves=15, max_bin=15,
              min_data_in_leaf=5)
    unfused = _train(X, y, "segment", False, monkeypatch, **kw)
    fused = _train(X, y, "segment", True, monkeypatch, **kw)
    assert fused.grower_params.packed4
    _assert_identical(unfused, fused, X)


def test_route_kernel_matches_xla_route(monkeypatch, rng):
    """route_window (aliased pallas window kernel) must reproduce the
    XLA windowed route bit-for-bit through a trained model: same trees,
    same predictions (LIGHTGBM_TPU_ROUTE_KERNEL=1 forces the kernel on
    the CPU interpret path; auto only engages on a real accelerator)."""
    import subprocess
    import sys

    import numpy as np

    code = """
import numpy as np, lightgbm_tpu as lgb, os
rng = np.random.RandomState(3)
X = rng.normal(size=(4000, 8)); y = (X[:,0] - 0.5*X[:,1] > 0).astype(float)
params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
          "tpu_histogram_backend": "pallas",
          "tpu_tree_impl": os.environ["IMPL"]}
bst = lgb.train(params, lgb.Dataset(X, y, params=params), 4)
np.save(os.environ["OUT"], bst.predict(X))
"""
    import os
    preds = {}
    for impl in ("segment", "frontier"):
        for tag, rk in (("xla", "0"), ("kernel", "1")):
            out = f"/tmp/route_ab_{impl}_{tag}.npy"
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       LIGHTGBM_TPU_ROUTE_KERNEL=rk, IMPL=impl, OUT=out)
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True)
            assert r.returncode == 0, r.stderr[-500:]
            preds[(impl, tag)] = np.load(out)
        d = np.abs(preds[(impl, "xla")] - preds[(impl, "kernel")]).max()
        assert d == 0.0, (impl, d)


@pytest.mark.parametrize("env,K,block_rows,want", [
    (None, 1, 32768, "k1"),         # auto, K = 1, the working set fits
    (None, 1, 262144, "off"),       # auto, K = 1, past the VMEM fit
    (None, 16, 32768, "off"),       # auto keeps the fusion to K = 1
    ("0", 1, 32768, "off"),
    ("1", 16, 32768, "k1"),         # forced: no K policy, no fit veto
], ids=["k1-fits", "k1-past-vmem", "k16-auto", "forced-off", "forced-on"])
def test_fused_route_policy_table(monkeypatch, env, K, block_rows, want):
    """The growers' one dispatch policy at the HIGGS shape (28 columns x
    64 bins), with the self-check taken as passed."""
    import lightgbm_tpu.ops.pallas_histogram as ph
    monkeypatch.setattr(ph, "_FUSED_ROUTE_CHECK", True)
    if env is None:
        monkeypatch.delenv("LIGHTGBM_TPU_FUSED_ROUTE", raising=False)
    else:
        monkeypatch.setenv("LIGHTGBM_TPU_FUSED_ROUTE", env)
    assert ph.fused_route_fits(28, 64, K, block_rows) == (
        block_rows == 32768)
    assert ph.fused_route_policy(K, 28, 64, block_rows, False) == want
