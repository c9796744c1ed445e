"""Frontier-batched grower (models/grower_frontier.py).

K=1 must reproduce the strict best-first segment tree exactly; K>1 is
"batched best-first" — same locally-greedy family, trees may differ
slightly, so quality (not structure) is asserted.  The K-leaf batched
kernel itself is pinned against per-leaf scans in test_pallas.py.
"""

import numpy as np

from lightgbm_tpu.config import Config
from lightgbm_tpu.core.dataset import TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objective import create_objective


def _train(X, y, impl, n_iters=3, **params):
    cfg = Config(verbosity=-1, tpu_histogram_backend="pallas",
                 tpu_tree_impl=impl, **params)
    ds = TpuDataset.from_numpy(X, y, config=cfg)
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    bst = GBDT(cfg, ds, obj)
    for _ in range(n_iters):
        bst.train_one_iter()
    return bst


def test_frontier_k1_matches_segment_exactly(rng):
    """With a 1-leaf batch every round is one strict best-first split, so
    the trees must be identical."""
    n = 2500
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] ** 2
         + rng.normal(size=n) * 0.2 > 0).astype(np.float64)
    seg = _train(X, y, "segment", objective="binary", num_leaves=15,
                 min_data_in_leaf=5, tpu_row_chunk=256)
    fro = _train(X, y, "frontier", objective="binary", num_leaves=15,
                 min_data_in_leaf=5, tpu_row_chunk=256,
                 tpu_frontier_width=1)
    assert len(seg.models) == len(fro.models)
    for i, (ts, tf) in enumerate(zip(seg.models, fro.models)):
        assert ts.num_leaves == tf.num_leaves, f"tree {i}"
        nsp = ts.num_leaves - 1
        assert np.array_equal(ts.split_feature[:nsp],
                              tf.split_feature[:nsp]), f"tree {i}"
        assert np.array_equal(ts.threshold_in_bin[:nsp],
                              tf.threshold_in_bin[:nsp]), f"tree {i}"
        np.testing.assert_allclose(ts.leaf_value[:ts.num_leaves],
                                   tf.leaf_value[:tf.num_leaves],
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(seg._raw_predict(X), fro._raw_predict(X),
                               rtol=1e-5, atol=1e-6)


def test_frontier_batched_quality(rng):
    """K=4 batched rounds: the tree fills its leaf budget, every split is
    locally optimal, and fit quality matches strict best-first closely."""
    n = 4000
    X = rng.normal(size=(n, 8))
    y = (X[:, 0] * 2 + np.sin(X[:, 1] * 2) + (X[:, 2] > 0.5)
         + rng.normal(size=n) * 0.1)
    seg = _train(X, y, "segment", objective="regression", num_leaves=31,
                 min_data_in_leaf=5, tpu_row_chunk=256, n_iters=10,
                 learning_rate=0.3)
    fro = _train(X, y, "frontier", objective="regression", num_leaves=31,
                 min_data_in_leaf=5, tpu_row_chunk=256,
                 tpu_frontier_width=4, n_iters=10, learning_rate=0.3)
    assert fro.models[0].num_leaves == 31
    mse_seg = float(np.mean((seg._raw_predict(X).ravel() - y) ** 2))
    mse_fro = float(np.mean((fro._raw_predict(X).ravel() - y) ** 2))
    assert mse_fro < mse_seg * 1.15, (mse_fro, mse_seg)
    assert mse_fro < 0.1 * y.var()


def test_frontier_respects_leaf_budget_and_gain_floor(rng):
    """A round near the leaf budget must not overshoot num_leaves, and a
    separable-in-one-split target stops early (gain prefix logic)."""
    n = 1200
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] > 0).astype(np.float64)      # one split suffices
    fro = _train(X, y, "frontier", objective="regression", num_leaves=12,
                 min_data_in_leaf=5, min_gain_to_split=1e-3,
                 tpu_row_chunk=256, tpu_frontier_width=8, n_iters=1)
    t = fro.models[0]
    assert t.num_leaves <= 12
    # the dominant first split must be on feature 0
    assert t.split_feature[0] == 0


def test_frontier_binary_accuracy_default_width(rng):
    """Auto width caps K at ~num_leaves/16, so a 31-leaf tree batches
    only 1-2 leaves per round and fit stays at strict-best-first level."""
    n = 3000
    X = rng.normal(size=(n, 10))
    logit = 2 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
    y = (logit + rng.normal(size=n) * 0.3 > 0).astype(np.float64)
    fro = _train(X, y, "frontier", objective="binary", num_leaves=31,
                 min_data_in_leaf=5, tpu_row_chunk=256, n_iters=8)
    p = 1.0 / (1.0 + np.exp(-fro._raw_predict(X).ravel()))
    acc = float(np.mean((p > 0.5) == y))
    assert acc > 0.92, acc


def test_frontier_with_efb_bundles(rng):
    """Frontier grower over an EFB-bundled dataset: group-space batched
    histograms expand to feature space in the scan, and split application
    maps features back to physical columns."""
    n, width, blocks = 2000, 8, 5
    X = np.zeros((n, width * blocks))
    picks = rng.randint(0, width, size=(n, blocks))
    for b in range(blocks):
        X[np.arange(n), b * width + picks[:, b]] = rng.normal(2, 1, n)
    y = (X[:, :width].sum(1) - X[:, width:2 * width].sum(1)
         + rng.normal(size=n) * 0.1)
    seg = _train(X, y, "segment", objective="regression", num_leaves=15,
                 min_data_in_leaf=5, tpu_row_chunk=256, n_iters=4)
    fro = _train(X, y, "frontier", objective="regression", num_leaves=15,
                 min_data_in_leaf=5, tpu_row_chunk=256,
                 tpu_frontier_width=1, n_iters=4)
    assert fro.train_set.bundle is not None
    # K=1 frontier == strict segment even through bundling
    np.testing.assert_allclose(seg._raw_predict(X), fro._raw_predict(X),
                               rtol=1e-5, atol=1e-6)


def test_frontier_gain_ratio_gate(rng):
    """With a dominant-gain target and a high gain ratio, rounds batch
    only comparable leaves — quality approaches strict best-first even at
    large K; ratio=0 batches everything with positive gain."""
    n = 3000
    X = rng.normal(size=(n, 8))
    y = (X[:, 0] * 3                      # one dominant direction
         + 0.1 * np.sin(X[:, 1]) + rng.normal(size=n) * 0.05)
    strict = _train(X, y, "segment", objective="regression", num_leaves=31,
                    min_data_in_leaf=5, tpu_row_chunk=256, n_iters=3)
    gated = _train(X, y, "frontier", objective="regression", num_leaves=31,
                   min_data_in_leaf=5, tpu_row_chunk=256,
                   tpu_frontier_width=16, tpu_frontier_gain_ratio=0.5,
                   n_iters=3)
    wide = _train(X, y, "frontier", objective="regression", num_leaves=31,
                  min_data_in_leaf=5, tpu_row_chunk=256,
                  tpu_frontier_width=16, tpu_frontier_gain_ratio=0.0,
                  n_iters=3)
    mse = lambda b: float(np.mean((b._raw_predict(X).ravel() - y) ** 2))
    m_strict, m_gated, m_wide = mse(strict), mse(gated), mse(wide)
    # the gate must not be WORSE than ungated batching, and must stay
    # close to strict
    assert m_gated <= m_wide * 1.02, (m_gated, m_wide)
    assert m_gated < m_strict * 1.10, (m_gated, m_strict)


def test_frontier_with_bagging_and_goss(rng):
    """Frontier grower under row subsampling: bagging masks rows via the
    member channel; GOSS amplifies small-gradient rows — both flow
    through the batched kernel's weight channels unchanged."""
    n = 3000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    bag = _train(X, y, "frontier", objective="binary", num_leaves=15,
                 min_data_in_leaf=5, tpu_row_chunk=256, n_iters=6,
                 bagging_fraction=0.6, bagging_freq=1)
    p = 1.0 / (1.0 + np.exp(-bag._raw_predict(X).ravel()))
    assert float(np.mean((p > 0.5) == y)) > 0.9

    from lightgbm_tpu.models.boosting_factory import create_boosting
    from lightgbm_tpu.objective import create_objective
    cfg = Config(verbosity=-1, tpu_histogram_backend="pallas",
                 tpu_tree_impl="frontier", objective="binary",
                 boosting="goss", num_leaves=15, min_data_in_leaf=5,
                 tpu_row_chunk=256, top_rate=0.3, other_rate=0.2)
    ds = TpuDataset.from_numpy(X, y, config=cfg)
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    goss = create_boosting(cfg, ds, obj)
    for _ in range(6):
        goss.train_one_iter()
    p = 1.0 / (1.0 + np.exp(-goss._raw_predict(X).ravel()))
    assert float(np.mean((p > 0.5) == y)) > 0.9


def test_frontier_multiclass_batched_roots_parity(rng):
    """Batched roots feed the FRONTIER grower's external-root branch
    (gbdt gates on _use_segment, which covers frontier too)."""
    n, C = 1200, 3
    X = rng.normal(size=(n, 5))
    y = np.argmax(X[:, :C] + rng.normal(size=(n, C)) * 0.3, axis=1)

    def train(force_eager):
        cfg = Config(verbosity=-1, objective="multiclass", num_class=C,
                     tpu_histogram_backend="pallas",
                     tpu_tree_impl="frontier", num_leaves=7,
                     min_data_in_leaf=5, tpu_row_chunk=256,
                     tpu_frontier_width=2)
        ds = TpuDataset.from_numpy(X, y.astype(np.float64), config=cfg)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        bst = GBDT(cfg, ds, obj)
        if force_eager:
            bst._fused_ok = False
        for _ in range(2):
            bst.train_one_iter()
        return bst

    fused = train(False)
    eager = train(True)
    assert fused._fused_fns[2] is not None
    np.testing.assert_allclose(fused._raw_predict(X),
                               eager._raw_predict(X),
                               rtol=1e-4, atol=1e-5)


def test_seg_stats_counters_via_outputs(rng, monkeypatch, capfd):
    """LIGHTGBM_TPU_SEG_STATS threads the scan/compaction counters out of
    the jit as a third output (compiled code carries no host callbacks,
    so they must NOT be debug.print'ed) and prints them host-side."""
    monkeypatch.setenv("LIGHTGBM_TPU_SEG_STATS", "1")
    n = 2500
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    for impl, k_expect in (("segment", 1), ("frontier", None)):
        bst = _train(X, y, impl, n_iters=2, objective="binary",
                     num_leaves=15, min_data_in_leaf=5, tpu_row_chunk=256)
        assert bst._raw_predict(X).size == n
        err = capfd.readouterr().err
        lines = [ln for ln in err.splitlines() if "seg stats" in ln]
        assert len(lines) >= 2, (impl, err)
        # counters are sane: scanned >= 1 N-equivalent, K as configured
        assert "N-equivalents" in lines[-1]
        if k_expect is not None:
            assert f"K={k_expect}" in lines[-1]
