"""Distributed tree-learner tests on the virtual 8-device CPU mesh.

The reference cannot test its parallel learners in one process (SURVEY.md
§4: no mock network; real multi-machine launches only).  Here the same
shard_map code path that runs on a TPU pod runs on 8 virtual CPU devices,
so data-/feature-/voting-parallel are exercised in-process and compared
against the serial learner.
"""

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb


def make_data(rng, n=2000, f=10):
    X = rng.normal(size=(n, f))
    y = X[:, 0] * 2 + np.sin(X[:, 1]) + (X[:, 2] > 0) + \
        rng.normal(size=n) * 0.1
    return X, y


@pytest.fixture(scope="module")
def devices():
    return jax.devices()


def _train(X, y, tree_learner, **extra):
    params = {"objective": "regression", "verbose": -1, "num_leaves": 15,
              "tree_learner": tree_learner, "max_bin": 63, "seed": 5}
    params.update(extra)
    ds = lgb.Dataset(X, y)
    return lgb.train(params, ds, num_boost_round=20, verbose_eval=False)


def test_mesh_available(devices):
    assert len(devices) == 8, "conftest should provide 8 virtual devices"


def test_data_parallel_matches_serial(rng):
    X, y = make_data(rng)
    serial = _train(X, y, "serial")
    data = _train(X, y, "data")
    ps = serial.predict(X)
    pd = data.predict(X)
    # identical split decisions up to float reduction order
    np.testing.assert_allclose(ps, pd, rtol=1e-3, atol=1e-4)
    mse = float(np.mean((pd - y) ** 2))
    assert mse < 0.1 * y.var()


def test_feature_parallel_matches_serial(rng):
    X, y = make_data(rng)
    serial = _train(X, y, "serial")
    feat = _train(X, y, "feature")
    np.testing.assert_allclose(serial.predict(X), feat.predict(X),
                               rtol=1e-3, atol=1e-4)


def test_voting_parallel_trains(rng):
    X, y = make_data(rng, n=4000)
    vot = _train(X, y, "voting", top_k=5)
    mse = float(np.mean((vot.predict(X) - y) ** 2))
    assert mse < 0.15 * y.var()


def test_voting_parallel_active_mask(rng):
    """top_k small enough that 2*top_k < num_features, so the election
    mask actually restricts candidates (the regression that shipped with
    an all-ones mask went unseen)."""
    X, y = make_data(rng, n=4000)
    vot = _train(X, y, "voting", top_k=2)
    mse = float(np.mean((vot.predict(X) - y) ** 2))
    assert mse < 0.2 * y.var()


def test_data_parallel_uneven_rows(rng):
    # 2003 % 8 != 0: exercises the zero-member row padding
    X, y = make_data(rng, n=2003)
    data = _train(X, y, "data")
    assert float(np.mean((data.predict(X) - y) ** 2)) < 0.1 * y.var()


def test_data_parallel_binary(rng):
    X = rng.normal(size=(2000, 8))
    yb = (X[:, 0] + X[:, 1] > 0).astype(float)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "tree_learner": "data"}
    bst = lgb.train(params, lgb.Dataset(X, yb), num_boost_round=15,
                    verbose_eval=False)
    acc = np.mean((bst.predict(X) > 0.5) == yb)
    assert acc > 0.9


def test_data_parallel_segment_matches_serial_segment(rng):
    """The distributed segment grower (psum_scatter stripes + max-gain
    SplitInfo merge) must grow the same trees as the serial segment grower
    (VERDICT r2 item 3: O(leaf) per-split cost must survive sharding)."""
    X, y = make_data(rng, n=3000, f=9)
    serial = _train(X, y, "serial", tpu_histogram_backend="pallas",
                    tpu_tree_impl="segment", tpu_row_chunk=256)
    assert serial.gbdt._use_segment
    data = _train(X, y, "data", tpu_histogram_backend="pallas",
                  tpu_tree_impl="segment", tpu_row_chunk=256)
    assert data.gbdt._use_segment
    np.testing.assert_allclose(serial.predict(X), data.predict(X),
                               rtol=1e-3, atol=1e-4)
    # same tree shapes — the split decisions matched, not just the fit
    for ts, td in zip(serial.gbdt.models, data.gbdt.models):
        assert ts.num_leaves == td.num_leaves


def test_data_parallel_segment_state_lives_on_the_mesh(rng, devices):
    """Rows-sharded mesh learners place the bin matrix and the per-row
    boosting state over the mesh once: left on the default device, every
    tree would re-shard the whole matrix from device 0."""
    D = len(devices)
    X, y = make_data(rng, n=3200, f=9)
    data = _train(X, y, "data", tpu_histogram_backend="pallas",
                  tpu_tree_impl="segment", tpu_row_chunk=256)
    g = data.gbdt
    assert g._use_segment and g._mesh.devices.size == D
    for arr, row_axis in ((g.bins, 1), (g.train_score, 1),
                          (g.bag_weight, 0)):
        shards = arr.addressable_shards
        assert sorted(s.device.id for s in shards) == list(range(D))
        assert all(s.data.shape[row_axis] == arr.shape[row_axis] // D
                   for s in shards), arr.sharding
    # nothing whole is cached for the default device either
    assert getattr(g.train_set, "_device_binned_T", None) is None
    assert float(np.mean((data.predict(X) - y) ** 2)) < 0.1 * y.var()


def test_data_parallel_segment_binary_uneven(rng):
    X, y = make_data(rng, n=2507, f=6)
    yb = (y > np.median(y)).astype(float)
    params = {"objective": "binary", "metric": "binary_logloss",
              "verbose": -1, "num_leaves": 15, "tree_learner": "data",
              "max_bin": 31, "tpu_histogram_backend": "pallas",
              "tpu_tree_impl": "segment", "tpu_row_chunk": 128}
    ds = lgb.Dataset(X, yb)
    bst = lgb.train(params, ds, num_boost_round=15, verbose_eval=False)
    assert bst.gbdt._use_segment
    p = bst.predict(X)
    ll = -np.mean(yb * np.log(p + 1e-9) + (1 - yb) * np.log(1 - p + 1e-9))
    assert ll < 0.6   # better than chance on a learnable target


def test_data_parallel_segment_packed4(rng):
    """Sharded segment grower with the 4-bit packed layout (max_bin<=15
    activates packing; rows shard, packed columns replicate per shard)."""
    X, y = make_data(rng, n=2600, f=7)
    serial = _train(X, y, "serial", tpu_histogram_backend="pallas",
                    tpu_tree_impl="segment", tpu_row_chunk=128, max_bin=15)
    assert serial.gbdt.grower_params.packed4
    data = _train(X, y, "data", tpu_histogram_backend="pallas",
                  tpu_tree_impl="segment", tpu_row_chunk=128, max_bin=15)
    assert data.gbdt._use_segment and data.gbdt.grower_params.packed4
    np.testing.assert_allclose(serial.predict(X), data.predict(X),
                               rtol=1e-3, atol=1e-4)


def test_voting_parallel_with_bundling(rng):
    """Voting election over an EFB-bundled dataset: votes are cast in
    feature space on locally-expanded histograms, reduced in column
    space (learners.reduce_voted)."""
    n, width, blocks = 2400, 10, 6
    X = np.zeros((n, width * blocks))
    picks = rng.randint(0, width, size=(n, blocks))
    for b in range(blocks):
        X[np.arange(n), b * width + picks[:, b]] = rng.normal(2, 1, n)
    yb = (X[:, :width].sum(1) - X[:, width:2 * width].sum(1) > 0).astype(float)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "tree_learner": "voting", "min_data_in_leaf": 5, "top_k": 8}
    ds = lgb.Dataset(X, yb, params=params)
    bst = lgb.train(params, ds, num_boost_round=15, verbose_eval=False)
    assert ds._handle.bundle is not None
    p = bst.predict(X)
    ll = -np.mean(yb * np.log(p + 1e-9) + (1 - yb) * np.log(1 - p + 1e-9))
    assert ll < 0.55


def test_balanced_stripes_by_bins():
    """Stripe boundaries cut per-shard Σbins skew (the reference balances
    feature-parallel shards by #bins,
    feature_parallel_tree_learner.cpp:36-47) while the width cap bounds
    every shard's static histogram block at 2x the even split."""
    from lightgbm_tpu.parallel.learners import _balanced_stripes
    rng = np.random.RandomState(0)
    # EFB-like skew: a few fat bundled columns among many tiny ones
    cb = np.concatenate([np.full(4, 255), rng.randint(2, 8, size=60)])
    D = 8
    starts, widths, per = _balanced_stripes(cb, D)
    sums = np.asarray([cb[s:s + w].sum() for s, w in zip(starts, widths)])
    assert sums.sum() == cb.sum()           # partition covers every column
    even = -(-len(cb) // D)
    assert per <= 2 * even                   # histogram block stays bounded
    ideal = cb.sum() / D
    # a fat column alone is ~2x the ideal shard load and the width cap
    # forces the small-column tail onto few shards, so the capped optimum
    # is one fat column + a slice of tail, not perfect balance
    assert sums.max() <= 1.5 * max(cb.max(), ideal), (sums, ideal)
    # and the even split must be far WORSE on this profile
    even_sums = np.asarray([cb[i * even:(i + 1) * even].sum()
                            for i in range(D)])
    assert sums.max() < 0.5 * even_sums.max()

    # a profile the even split already handles optimally is never worsened
    s2, w2, p2 = _balanced_stripes(np.asarray([3, 5]), 2)
    assert list(w2) == [1, 1] and p2 == 1

    # degenerate: one giant column among few — no empty-shard blowup
    s3, w3, p3 = _balanced_stripes(np.asarray([10000] + [1] * 15), 4)
    assert w3.sum() == 16 and p3 <= 2 * 4


def test_feature_parallel_skewed_bundles(rng):
    """Feature-parallel over an EFB dataset whose bundles concentrate
    bins in few physical columns still matches the serial learner."""
    n = 2000
    # 3 dense high-cardinality features + 40 sparse one-hot-ish columns
    # that EFB packs into few bundles
    dense = rng.normal(size=(n, 3))
    width, blocks = 10, 4
    sparse = np.zeros((n, width * blocks))
    picks = rng.randint(0, width, size=(n, blocks))
    for b in range(blocks):
        sparse[np.arange(n), b * width + picks[:, b]] = rng.normal(2, 1, n)
    X = np.hstack([dense, sparse])
    y = dense[:, 0] * 2 + sparse[:, :width].sum(1) \
        + rng.normal(size=n) * 0.1
    serial = _train(X, y, "serial", max_bin=255, min_data_in_leaf=5)
    feat = _train(X, y, "feature", max_bin=255, min_data_in_leaf=5)
    assert feat.gbdt.train_set.bundle is not None, \
        "EFB must bundle the sparse block or this test covers nothing"
    np.testing.assert_allclose(serial.predict(X), feat.predict(X),
                               rtol=1e-3, atol=1e-4)


def test_data_parallel_frontier_matches_serial_frontier(rng):
    """Frontier grower under shard_map (rows sharded, one reduce-scatter
    per K-leaf round) == serial frontier grower, same batch width."""
    X, y = make_data(rng, n=2600, f=7)
    serial = _train(X, y, "serial", tpu_histogram_backend="pallas",
                    tpu_tree_impl="frontier", tpu_row_chunk=128,
                    tpu_frontier_width=4)
    data = _train(X, y, "data", tpu_histogram_backend="pallas",
                  tpu_tree_impl="frontier", tpu_row_chunk=128,
                  tpu_frontier_width=4)
    np.testing.assert_allclose(serial.predict(X), data.predict(X),
                               rtol=1e-3, atol=1e-4)
    for ts, td in zip(serial.gbdt.models, data.gbdt.models):
        assert ts.num_leaves == td.num_leaves


def test_seg_stats_under_data_parallel(rng, monkeypatch, capfd):
    """Under the data-parallel wrappers the per-device counters come back
    stacked (out_specs P(axis)); one printed row per device."""
    monkeypatch.setenv("LIGHTGBM_TPU_SEG_STATS", "1")
    n = 4000
    X = rng.normal(size=(n, 6))
    y = X[:, 0] + 0.5 * X[:, 1] + rng.normal(size=n) * 0.1
    bst = _train(X, y, "data", tpu_histogram_backend="pallas",
                 tpu_tree_impl="segment", tpu_row_chunk=256)
    assert bst.gbdt._use_segment
    err = capfd.readouterr().err
    rows = [ln for ln in err.splitlines() if "seg stats" in ln]
    assert len(rows) >= 8, err[:2000]
    assert any("dev7" in ln for ln in rows), rows[:9]


def test_feature_parallel_segment_matches_serial_segment(rng):
    """Feature-parallel on the O(leaf) segment grower (VERDICT r4 item
    6): data replicated, per-shard column-stripe histograms over the
    leaf's confinement interval, max-gain SplitInfo merge — same trees
    as the serial segment grower (the reference's feature-parallel
    learner inherits the serial O(leaf) machinery,
    feature_parallel_tree_learner.cpp:74-75)."""
    X, y = make_data(rng, n=3000, f=9)
    serial = _train(X, y, "serial", tpu_histogram_backend="pallas",
                    tpu_tree_impl="segment", tpu_row_chunk=256)
    assert serial.gbdt._use_segment
    feat = _train(X, y, "feature", tpu_histogram_backend="pallas",
                  tpu_tree_impl="segment", tpu_row_chunk=256)
    assert feat.gbdt._use_segment
    np.testing.assert_allclose(serial.predict(X), feat.predict(X),
                               rtol=1e-3, atol=1e-4)
    for ts, tf in zip(serial.gbdt.models, feat.gbdt.models):
        assert ts.num_leaves == tf.num_leaves


def test_feature_parallel_frontier_matches_serial_frontier(rng):
    X, y = make_data(rng, n=2600, f=7)
    serial = _train(X, y, "serial", tpu_histogram_backend="pallas",
                    tpu_tree_impl="frontier", tpu_row_chunk=128,
                    tpu_frontier_width=4)
    feat = _train(X, y, "feature", tpu_histogram_backend="pallas",
                  tpu_tree_impl="frontier", tpu_row_chunk=128,
                  tpu_frontier_width=4)
    assert feat.gbdt._use_segment
    np.testing.assert_allclose(serial.predict(X), feat.predict(X),
                               rtol=1e-3, atol=1e-4)


def test_voting_parallel_segment_full_election_matches_serial(rng):
    """With top_k >= F every feature is elected, so voting-parallel on
    the segment grower must equal the serial segment grower exactly —
    the no-subtract both-children path and the voted psum reduce under
    row sharding are the only moving parts."""
    X, y = make_data(rng, n=3000, f=9)
    serial = _train(X, y, "serial", tpu_histogram_backend="pallas",
                    tpu_tree_impl="segment", tpu_row_chunk=256)
    vote = _train(X, y, "voting", tpu_histogram_backend="pallas",
                  tpu_tree_impl="segment", tpu_row_chunk=256, top_k=20)
    assert vote.gbdt._use_segment
    np.testing.assert_allclose(serial.predict(X), vote.predict(X),
                               rtol=1e-3, atol=1e-4)


def test_voting_parallel_segment_quality_bound(rng):
    """PV-Tree's approximation quality claim, in-process: a REAL election
    (top_k < F) must stay within a few percent of the exact data-parallel
    learner on heldout loss (VERDICT r4 weak item: voting previously had
    only trains-level assertions)."""
    X, y = make_data(rng, n=3000, f=10)
    yb = (y > np.median(y)).astype(float)
    kw = dict(tpu_histogram_backend="pallas", tpu_tree_impl="segment",
              tpu_row_chunk=256, objective="binary")
    data = _train(X, yb, "data", **kw)
    vote = _train(X, yb, "voting", top_k=3, **kw)
    assert vote.gbdt._use_segment

    def ll(b):
        p = np.clip(b.predict(X), 1e-9, 1 - 1e-9)
        return -np.mean(yb * np.log(p) + (1 - yb) * np.log(1 - p))

    assert ll(vote) < ll(data) * 1.10 + 0.02


def test_voting_parallel_frontier_trains(rng):
    X, y = make_data(rng, n=2600, f=7)
    vote = _train(X, y, "voting", tpu_histogram_backend="pallas",
                  tpu_tree_impl="frontier", tpu_row_chunk=128,
                  tpu_frontier_width=4, top_k=20)
    assert vote.gbdt._use_segment
    mse = float(np.mean((vote.predict(X) - y) ** 2))
    assert mse < 0.1 * y.var()


@pytest.mark.parametrize("learner", ["data", "feature", "voting"])
def test_mesh_segment_learners_run_no_lookahead(rng, monkeypatch, learner):
    """The strict grower's lookahead lane sets are the serial learner's:
    under every mesh wrapper the segment grower builds the program it
    built before them — its lookahead counters stay 0 and the model is,
    bit for bit, that of a build whose shape admits one lane set."""
    import lightgbm_tpu.models.grower_seg as gs
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    monkeypatch.setenv("LIGHTGBM_TPU_SEG_STATS", "1")
    X, y = make_data(rng, n=2400, f=7)
    kw = dict(tpu_histogram_backend="pallas", tpu_tree_impl="segment",
              tpu_row_chunk=128, num_leaves=24)

    def train():
        ds = lgb.Dataset(X, y)
        params = {"objective": "regression", "verbose": -1,
                  "tree_learner": learner, "max_bin": 63, "seed": 5, **kw}
        return lgb.train(params, ds, num_boost_round=3, verbose_eval=False)

    TELEMETRY.reset()
    a = train()
    assert a.gbdt._use_segment
    counters = TELEMETRY.stats()["counters"]
    assert counters["seg/splits"] > 0
    assert counters["seg/lookahead_hits"] == 0
    assert counters["seg/lookahead_filled"] == 0
    assert counters["seg/route_only_blocks"] == 0
    monkeypatch.setattr(gs, "lookahead_width", lambda *a: 1)
    b = train()
    assert a.model_to_string() == b.model_to_string()
