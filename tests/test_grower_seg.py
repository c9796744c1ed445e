"""Segment grower (models/grower_seg.py) end-to-end parity vs the fused
grower.

The segment grower must produce the SAME leaf-wise tree as the fused
grower up to histogram summation order (grower_seg.py docstring): same
topology, same split features/thresholds, near-same outputs (bf16 hi/lo
histogram channels vs f32).  Pallas runs in interpret mode on the CPU CI
mesh, so these tests cover the real kernel logic minus mosaic codegen.

Shapes are chosen to cross the compaction milestones (4 and 16 leaves)
and to exercise categorical splits, NaN missing routing, bagging weights,
and multi-iteration training.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.config import Config
from lightgbm_tpu.core.dataset import TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objective import create_objective


def _train_pair(X, y, rng, n_iters=3, **params):
    """Train fused-onehot and segment boosters on identical data."""
    cat_feats = params.pop("categorical_feature", [])
    out = []
    for backend, impl in (("onehot", "fused"), ("pallas", "segment")):
        cfg = Config(verbosity=-1, tpu_histogram_backend=backend,
                     tpu_tree_impl=impl, **params)
        ds = TpuDataset.from_numpy(X, y, config=cfg,
                                   categorical_features=cat_feats)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        bst = GBDT(cfg, ds, obj)
        for _ in range(n_iters):
            bst.train_one_iter()
        out.append(bst)
    fused, seg = out
    assert seg._use_segment, "segment grower was not selected"
    return fused, seg


def _assert_tree_parity(fused, seg, X, tol=5e-3, gain_floor=1e-2):
    """Same topology for every split whose gain is above float noise
    (zero-gain ties legitimately break differently between the f32 onehot
    and bf16 hi/lo pallas histograms), near-same predictions overall."""
    assert len(fused.models) == len(seg.models)
    compared = 0
    for i, (tf, ts) in enumerate(zip(fused.models, seg.models)):
        nf = min(tf.num_leaves, ts.num_leaves) - 1
        # leaf-wise growth is best-first, so gains are non-increasing;
        # compare the prefix of meaningful splits
        k = 0
        while (k < nf and tf.split_gain[k] > gain_floor
               and ts.split_gain[k] > gain_floor):
            k += 1
        assert np.array_equal(tf.split_feature[:k],
                              ts.split_feature[:k]), f"tree {i}"
        assert np.array_equal(tf.threshold_in_bin[:k],
                              ts.threshold_in_bin[:k]), f"tree {i}"
        compared += k
    assert compared > 0, "no meaningful splits compared"
    p_f = fused._raw_predict(X)
    p_s = seg._raw_predict(X)
    assert np.abs(p_f - p_s).max() < tol


def test_segment_parity_binary_compaction(rng):
    """31 leaves crosses the 4- and 16-leaf compaction milestones."""
    n = 3000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] ** 2
         + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    fused, seg = _train_pair(X, y, rng, n_iters=3, objective="binary",
                             num_leaves=31, max_bin=63, min_data_in_leaf=5)
    _assert_tree_parity(fused, seg, X)


def test_segment_parity_packed4(rng):
    """max_bin=15 activates the 4-bit packed layout (Dense4bitsBin
    equivalent): two columns per byte, in-kernel nibble unpack.  The
    grown trees must match the unpacked fused grower."""
    n = 3000
    X = rng.normal(size=(n, 7))
    y = (X[:, 0] + 0.6 * X[:, 1] - 0.2 * X[:, 2] ** 2
         + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    fused, seg = _train_pair(X, y, rng, n_iters=3, objective="binary",
                             num_leaves=31, max_bin=15, min_data_in_leaf=5)
    assert seg.grower_params.packed4, "packed4 layout was not selected"
    # physical bin rows = ceil(columns / 2)
    assert seg.bins.shape[0] == -(-seg.train_set.num_columns // 2)
    _assert_tree_parity(fused, seg, X)


def test_segment_parity_missing_nan(rng):
    n = 2000
    X = rng.normal(size=(n, 5))
    X[rng.uniform(size=(n, 5)) < 0.15] = np.nan
    y = (np.where(np.isnan(X[:, 0]), 0.5, np.nan_to_num(X[:, 0]) > 0)
         + 0.4 * np.nan_to_num(X[:, 1]) + 0.3 * np.nan_to_num(X[:, 2]) ** 2
         + 0.05 * rng.normal(size=n)).astype(np.float64)
    fused, seg = _train_pair(X, y, rng, n_iters=2, objective="regression",
                             num_leaves=15, max_bin=31, min_data_in_leaf=10)
    _assert_tree_parity(fused, seg, X)


def test_segment_parity_categorical(rng):
    n = 2500
    Xc = rng.randint(0, 12, size=n)
    Xn = rng.normal(size=(n, 3))
    X = np.column_stack([Xc.astype(np.float64), Xn])
    effect = np.array([1.5, -2, 0.3, 2, -1, 0.8, -0.2, 1.1, -1.7, 0.5,
                       2.2, -0.9])
    y = effect[Xc] + Xn[:, 0] + 0.1 * rng.normal(size=n)
    fused, seg = _train_pair(X, y, rng, n_iters=2, objective="regression",
                             num_leaves=15, max_bin=63, min_data_in_leaf=20,
                             categorical_feature=[0])
    assert any(t.num_cat > 0 for t in fused.models), \
        "no categorical split exercised"
    _assert_tree_parity(fused, seg, X)


def test_segment_parity_bagging(rng):
    n = 2400
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] * X[:, 1] + 0.2 * rng.normal(size=n)).astype(np.float64)
    fused, seg = _train_pair(X, y, rng, n_iters=3, objective="regression",
                             num_leaves=12, max_bin=31,
                             bagging_fraction=0.7, bagging_freq=1,
                             bagging_seed=7, min_data_in_leaf=5)
    _assert_tree_parity(fused, seg, X)


def test_segment_grower_direct_leaf_id(rng):
    """Grower-level check: the segment grower's returned leaf_id (mapped
    back to original row order) matches the fused grower's."""
    from lightgbm_tpu.models.grower import GrowerParams, make_grow_tree
    from lightgbm_tpu.models.grower_seg import make_grow_tree_segment
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    import jax

    n, F, B, L, rb = 1024, 4, 16, 8, 256
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    # real signal so split gains sit well above bf16 rounding noise
    g = (-(bins[:, 0] >= B // 2).astype(np.float32)
         - 0.5 * (bins[:, 1] % 3 == 0)
         + 0.25 * bins[:, 2] / B
         + 0.1 * rng.normal(size=n)).astype(np.float32)
    h = np.ones(n, np.float32)
    member = (rng.uniform(size=n) < 0.9).astype(np.float32)
    fmeta = FeatureMeta(
        num_bin=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        is_cat=jnp.zeros(F, bool),
        monotone=jnp.zeros(F, jnp.int32),
        penalty=jnp.ones(F, jnp.float32))
    fmask = jnp.ones(F, jnp.float32)
    key = jax.random.PRNGKey(0)
    params = GrowerParams(num_leaves=L,
                          split=SplitParams(min_data_in_leaf=2.0))

    tree_f, lid_f = make_grow_tree(B, params)(
        jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(member), fmeta, fmask, key)
    params_s = params._replace(hist_backend="pallas")
    tree_s, lid_s, _ = make_grow_tree_segment(B, params_s, rb)(
        jnp.asarray(bins.T.copy()), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(member), fmeta, fmask, key)

    assert int(tree_f.num_leaves) == int(tree_s.num_leaves)
    nl = int(tree_f.num_leaves) - 1
    np.testing.assert_array_equal(np.asarray(tree_f.split_feature)[:nl],
                                  np.asarray(tree_s.split_feature)[:nl])
    np.testing.assert_array_equal(np.asarray(tree_f.threshold_bin)[:nl],
                                  np.asarray(tree_s.threshold_bin)[:nl])
    # leaf assignment identical for member rows (pad/non-member rows are
    # still routed, so compare all real rows)
    np.testing.assert_array_equal(np.asarray(lid_f), np.asarray(lid_s))
    assert np.abs(np.asarray(tree_f.leaf_value)
                  - np.asarray(tree_s.leaf_value)).max() < 1e-3


def test_multiclass_batched_roots_parity(rng):
    """Multiclass: all C class-trees' root histograms computed in ONE
    kernel pass (histogram_all with stacked channel sets) must grow the
    same trees as per-class root scans (the non-fused eager path)."""
    n, C = 1500, 3
    X = rng.normal(size=(n, 5))
    y = np.argmax(X[:, :C] + rng.normal(size=(n, C)) * 0.3, axis=1)

    def train(force_eager):
        cfg = Config(verbosity=-1, objective="multiclass", num_class=C,
                     tpu_histogram_backend="pallas",
                     tpu_tree_impl="segment", num_leaves=7,
                     min_data_in_leaf=5, tpu_row_chunk=256)
        ds = TpuDataset.from_numpy(X, y.astype(np.float64), config=cfg)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        bst = GBDT(cfg, ds, obj)
        if force_eager:
            bst._fused_ok = False      # per-class root scans, no batching
        for _ in range(3):
            bst.train_one_iter()
        return bst

    fused = train(False)
    eager = train(True)
    assert fused._fused_fns is not None and fused._fused_fns[2] is not None, \
        "batched roots should be active for serial multiclass segment"
    assert len(fused.models) == len(eager.models) == 9
    for i, (tf, te) in enumerate(zip(fused.models, eager.models)):
        assert tf.num_leaves == te.num_leaves, f"tree {i}"
        nsp = tf.num_leaves - 1
        assert np.array_equal(tf.split_feature[:nsp],
                              te.split_feature[:nsp]), f"tree {i}"
    np.testing.assert_allclose(fused._raw_predict(X), eager._raw_predict(X),
                               rtol=1e-4, atol=1e-5)


def test_multiclass_batched_roots_parity_packed4(rng):
    """Batched roots through the 4-bit packed layout (max_bin<=15)."""
    n, C = 1200, 3
    X = rng.normal(size=(n, 6))
    y = np.argmax(X[:, :C] + rng.normal(size=(n, C)) * 0.3, axis=1)

    def train(force_eager):
        cfg = Config(verbosity=-1, objective="multiclass", num_class=C,
                     tpu_histogram_backend="pallas", max_bin=15,
                     tpu_tree_impl="segment", num_leaves=7,
                     min_data_in_leaf=5, tpu_row_chunk=256)
        ds = TpuDataset.from_numpy(X, y.astype(np.float64), config=cfg)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        bst = GBDT(cfg, ds, obj)
        assert bst.grower_params.packed4
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


def test_segment_epoch_edges(rng, monkeypatch):
    """Epoch-while edge cases: a tiny compaction budget (compact after
    nearly every split -> many epochs), a 2-leaf tree (single split,
    inner loop exits on the leaf budget), and unsplittable data (root
    only; the outer loop must terminate without a split)."""
    import lightgbm_tpu.models.grower_seg as gs
    n = 2000
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(np.float64)

    with monkeypatch.context() as mp:
        mp.setattr(gs, "compaction_budget_blocks", lambda *a: 1)
        fused, seg = _train_pair(X, y, rng, n_iters=2, objective="binary",
                                 num_leaves=15, max_bin=31,
                                 min_data_in_leaf=5)
        _assert_tree_parity(fused, seg, X)
    # context exit restores the module default for the sub-cases below

    fused2, seg2 = _train_pair(X, y, rng, n_iters=1, objective="binary",
                               num_leaves=2, max_bin=31,
                               min_data_in_leaf=5)
    assert seg2.models[0].num_leaves == 2
    _assert_tree_parity(fused2, seg2, X)

    y_const = np.zeros(n)
    _, seg3 = _train_pair(X, y_const, rng, n_iters=1,
                          objective="regression", num_leaves=15,
                          max_bin=31, min_data_in_leaf=5)
    # the all-constant iteration is dropped entirely (reference
    # semantics, gbdt.cpp:543-551) — the point here is only that the
    # epoch-while terminated without a split instead of hanging
    assert seg3.models == []


def test_segment_parity_wide_features_gather_compaction(rng):
    """60 features packs past _MAX_SORT_OPERANDS, so compaction takes the
    argsort+gather path (the variadic TPU sort's compile time explodes
    with operand count — 2026-08-01 on-chip finding); trees must match
    the fused grower exactly either way."""
    from lightgbm_tpu.models.grower_seg import _MAX_SORT_OPERANDS
    n, F = 2500, 60
    assert F // 4 + 5 > _MAX_SORT_OPERANDS  # the path under test engages
    X = rng.normal(size=(n, F))
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] ** 2
         + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    fused, seg = _train_pair(X, y, rng, n_iters=3, objective="binary",
                             num_leaves=31, max_bin=63, min_data_in_leaf=5)
    # 57 of the 60 features are pure noise: deep-tail splits tie at the
    # f32-vs-bf16 histogram precision floor and legitimately pick
    # different noise features (verified identical with the sort path),
    # so compare the strong-signal prefix exactly + predictions overall
    for tf, ts in zip(fused.models, seg.models):
        assert np.array_equal(np.asarray(tf.split_feature)[:16],
                              np.asarray(ts.split_feature)[:16])
        assert np.array_equal(np.asarray(tf.threshold_in_bin)[:16],
                              np.asarray(ts.threshold_in_bin)[:16])
    # rows that fall through a divergent noise-tie split land in other
    # leaves (a few % per tree); a BROKEN permutation would scramble
    # nearly every row, so bound the affected fraction, not the max
    diff = np.abs(fused._raw_predict(X) - seg._raw_predict(X))
    assert np.mean(diff > 1e-3) < 0.25
    assert np.median(diff) < 1e-4


def test_compact_state_sort_vs_gather_exact(rng, monkeypatch):
    """Deterministic parity of compact_state's two implementations: the
    multi-operand sort path and the argsort+gather path must produce the
    IDENTICAL permuted layout on the same _SegState (both are stable
    sorts on the same key, so even duplicate leaf_ids tie-break the same
    way).  This closes the 25%-tolerance window the end-to-end
    wide-feature test above has to allow for noise-feature gain ties —
    the compaction itself is exact."""
    import lightgbm_tpu.models.grower_seg as gs
    import types

    F4, n, L, rb = 8, 256, 8, 8
    assert F4 // 4 + 5 <= gs._MAX_SORT_OPERANDS  # sort path engages
    binsT = jnp.asarray(rng.randint(0, 64, size=(F4, n)), dtype=jnp.uint8)
    # channels 0-5 live, 6-7 structurally zero (pack_channels layout —
    # both compaction paths only carry the live ones)
    w8 = jnp.zeros((8, n), dtype=jnp.bfloat16).at[:6].set(
        jnp.asarray(rng.normal(size=(6, n)), dtype=jnp.bfloat16))
    st = gs.fresh_state(
        binsT, w8, n, L, G_cols=F4, B=64, F=F4, max_blocks=n // rb,
        G0=1.0, H0=float(n), C0=float(n),
        fmeta=types.SimpleNamespace(cegb_used0=None),
        p=types.SimpleNamespace(use_cegb_coupled=False))
    # scattered leaf assignment with duplicates and one empty leaf
    lid = rng.randint(0, L, size=n)
    lid[lid == L - 2] = 0  # leaf L-2 empty: exercises the empty-interval fixup
    st = st._replace(leaf_id=jnp.asarray(lid, dtype=jnp.int32))

    by_sort = gs.compact_state(st, L, rb)
    monkeypatch.setattr(gs, "_MAX_SORT_OPERANDS", 0)  # force gather path
    by_gather = gs.compact_state(st, L, rb)

    for field in ("binsT", "w8", "order", "leaf_id", "leaf_lo", "leaf_hi"):
        a = np.asarray(getattr(by_sort, field))
        b = np.asarray(getattr(by_gather, field))
        assert np.array_equal(a, b), f"compact_state paths differ on {field}"
    # sanity: the layout really is leaf-sorted and a true permutation
    assert np.all(np.diff(np.asarray(by_sort.leaf_id)) >= 0)
    assert np.array_equal(np.sort(np.asarray(by_sort.order)), np.arange(n))


def _grid_case(shape, rng):
    """(X, y, categorical features, tpu_tree_impl, parameters)."""
    base = dict(num_leaves=31, min_data_in_leaf=5, tpu_row_chunk=256)
    if shape == "categorical":
        n = 2500
        Xc = rng.randint(0, 12, size=n)
        X = np.column_stack([Xc.astype(np.float64),
                             rng.normal(size=(n, 3))])
        y = np.sin(Xc) + X[:, 1] + 0.1 * rng.normal(size=n)
        return X, y, [0], "segment", dict(
            base, objective="regression", max_bin=63,
            min_data_per_group=20, cat_smooth=1.0)
    if shape == "tiled96":
        # four feature tiles a pass (test_feature_tiles.py's fixture)
        X = rng.normal(size=(3000, 300)).astype(np.float32)
        y = (X[:, :8].sum(axis=1) > 0).astype(np.float64)
        return X, y, [], "segment", dict(base, objective="binary",
                                         max_bin=63, tpu_row_chunk=1024)
    X = rng.normal(size=(3000, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] ** 2
         + 0.1 * rng.normal(size=3000) > 0).astype(np.float64)
    return X, y, [], ("frontier" if shape == "frontier_k4" else "segment"), \
        dict(base, objective="binary",
             max_bin=15 if shape == "packed4" else 63,
             **({"tpu_frontier_width": 4} if shape == "frontier_k4"
                else {}))


@pytest.mark.parametrize("shape", ["binary", "categorical", "packed4",
                                   "tiled96", "frontier_k4"])
def test_grid_steps_are_scanned_blocks_times_tiles(shape, rng, monkeypatch,
                                                   request):
    """What pins `hist_grid_ratio` at 1.0: a kernel's grid is its
    interval's blocks, so over grown trees the steps that accumulating
    passes dispatched are the blocks they scanned, once a feature tile.
    (A pass over an empty interval would dispatch one masked step and
    count it, `grid_of`; no leaf that is split has one, and a pass that
    only routes counts neither a block nor a step.)"""
    from lightgbm_tpu.models import gbdt, grower_seg
    if shape == "tiled96":
        request.getfixturevalue("tiles_of_96")
    seen = []
    monkeypatch.setattr(gbdt, "print_seg_stats", seen.append)
    monkeypatch.setenv("LIGHTGBM_TPU_SEG_STATS", "1")
    X, y, cats, impl, params = _grid_case(shape, rng)
    cfg = Config(verbosity=-1, tpu_histogram_backend="pallas",
                 tpu_tree_impl=impl, **params)
    ds = TpuDataset.from_numpy(X, y, config=cfg, categorical_features=cats)
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    bst = GBDT(cfg, ds, obj)
    assert bst.grower_params.packed4 == (shape == "packed4")
    for _ in range(2):
        bst.train_one_iter()
    assert len(seen) == 2
    for stats in seen:
        st = grower_seg.SegStats._make(np.asarray(stats))
        tiles = {"tiled96": 4, "frontier_k4": 0}.get(shape, 1)
        assert st.feature_tiles == tiles        # the frontier grower: none
        assert st.batch_k == (4 if shape == "frontier_k4" else 1)
        assert st.scanned_blocks > st.max_blocks > 1
        assert st.grid_steps == st.scanned_blocks * max(tiles, 1)


# ---------------------------------------------- the compaction trigger's
# budget (grower_seg.compaction_budget_blocks)

def test_budget_at_the_two_cells():
    """``higgs63-train``'s shape keeps 9 N to the block (the program of
    before); ``epsilon63-train``'s, where a compaction costs a third of
    a pass, lands in the flat optimum of the replay."""
    from lightgbm_tpu.models import grower_seg as gs
    assert gs.compaction_budget_blocks(28, 64, 36_765_696, 32_768,
                                       False) == 10_098
    eps = gs.compaction_budget_blocks(2000, 64, 1_105_920, 8_192, False)
    assert 2.5 * 135 <= eps <= 5 * 135
    higgs = gs.compaction_unit_costs(28, 64, 36_765_696, False)
    wide = gs.compaction_unit_costs(2000, 64, 1_105_920, False)
    assert (higgs["path"], wide["path"]) == ("sort", "gather")
    # the chip's own readings (PERF.md sections 5 and 6): 2.82 and 185.5
    # ns a row of a pass, 20.7 and 64.5 of a compaction
    assert higgs["pass_ns_per_row"] == pytest.approx(2.82, rel=0.1)
    assert wide["pass_ns_per_row"] == pytest.approx(185.5, rel=0.1)
    assert higgs["compaction_ns_per_row"] == pytest.approx(20.7, rel=0.05)
    assert wide["compaction_ns_per_row"] == pytest.approx(64.5, rel=0.05)
    # and alone at the two sides of the switch, 10.5M rows: 22.8 and 49.6
    assert gs.compaction_unit_costs(44, 64, 10_502_144, False)[
        "compaction_ns_per_row"] == pytest.approx(22.8, rel=0.1)
    assert gs.compaction_unit_costs(48, 64, 10_502_144, False)[
        "compaction_ns_per_row"] == pytest.approx(49.6, rel=0.1)


def test_budget_is_monotone_in_the_cost_ratio(monkeypatch):
    """More passes a compaction, more scanning before one; never under
    2 N (the first epoch would re-sort a table of one or two leaves) nor
    over 9 N."""
    from lightgbm_tpu.models import grower_seg as gs
    nb, rb = 640, 16_384
    budgets = []
    for c in (0.0, 0.1, 0.35, 1.0, 2.0, 4.0, 7.6, 30.0):
        monkeypatch.setattr(
            gs, "compaction_unit_costs", lambda *a, c=c: {
                "path": "sort", "pass_ns_per_row": 1.0,
                "compaction_ns_per_row": c})
        budgets.append(gs.compaction_budget_blocks(44, 64, nb * rb, rb,
                                                   False))
    assert budgets == sorted(budgets)
    assert budgets[0] >= 2 * nb and budgets[-1] == 9 * nb
    assert budgets[0] < budgets[-1]
    assert all(isinstance(b, int) for b in budgets)


@pytest.mark.parametrize("columns,bins,packed4", [
    (44, 64, False), (48, 64, False), (28, 64, False), (2000, 64, False),
    (88, 16, True), (90, 16, True)],
    ids=["44_sorts", "48_gathers", "higgs", "epsilon", "packed_88_sorts",
         "packed_90_gathers"])
def test_budget_assumes_the_path_compact_state_takes(columns, bins, packed4):
    """On both sides of ``_MAX_SORT_OPERANDS`` the cost is reckoned for
    the path the compaction takes for the table as ``grow`` pads it:
    a variadic sort of the packed words, or a two-operand sort and
    gathers."""
    import jax
    from lightgbm_tpu.models import grower_seg as gs
    from lightgbm_tpu.ops.pallas_histogram import feature_tile
    rb, n, L = 8, 64, 4
    # grow()'s padding of the physical bin rows
    n_phys = (columns + 1) // 2 if packed4 else columns
    tile = feature_tile(columns, bins)
    tiles = -(-columns // tile)
    n_phys += (-n_phys) % ((tile // 2 if packed4 else tile)
                           if tiles > 1 else 4)
    assert n_phys == gs.table_bin_rows(columns, bins, packed4)
    st = gs.fresh_state(jnp.zeros((n_phys, n), jnp.uint8),
                        jnp.zeros((8, n), jnp.bfloat16), n, L, 4, 16, 4,
                        n // rb, 0.0, 1.0, 1.0, None,
                        gs.GrowerParams(num_leaves=L))
    jaxpr = jax.make_jaxpr(lambda s: gs.compact_state(s, L, rb))(st)
    widest = max(len(e.invars) for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "sort")
    took = "gather" if widest == 2 else "sort"
    assert widest in (2, gs._sort_operands(n_phys))
    assert took == gs.compaction_unit_costs(columns, bins, n * 1000,
                                            packed4)["path"]
    assert (took == "sort") == (gs._sort_operands(n_phys)
                                <= gs._MAX_SORT_OPERANDS)
