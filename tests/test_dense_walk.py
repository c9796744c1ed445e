"""The in-scan walk without levels (`gbdt._dense_tree_rows`) against the
gather walk it replaces (`gbdt._route_tree_rows`): every tree that a small
training grows, walked over held-out bins both ways, must give the same leaf
values bit for bit and the same levels, and `gbdt._eval_walk` the same
carries; `_dense_walk_plan` keeps the gather walk past the dense walk's room
and for bins past 256 values."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt as gm


def _numeric(rng, n, f=6, nan=False, zeros=False):
    X = rng.standard_normal((n, f))
    y = X[:, 0] + 0.5 * X[:, 1] - X[:, 2] * X[:, 3]
    if nan:
        X[rng.random((n, f)) < 0.15] = np.nan
    if zeros:
        X[rng.random((n, f)) < 0.3] = 0.0
    return X, y + 0.1 * rng.standard_normal(n)


def _categorical(rng, n):
    # 90 categories: bins past 32, so the bitsets span several words
    cat = rng.integers(0, 90, n)
    effect = rng.standard_normal(90) * 2.0
    X = np.column_stack([cat.astype(np.float64),
                         rng.standard_normal((n, 3))])
    return X, effect[cat] + X[:, 1]


def _bundled(rng, n, blocks=6, width=8):
    # each block of `width` columns holds one nonzero a row: EFB bundles it
    X = np.zeros((n, blocks * width))
    picks = rng.integers(0, width, (n, blocks))
    vals = rng.normal(2.0, 1.0, (n, blocks))
    for b in range(blocks):
        X[np.arange(n), b * width + picks[:, b]] = vals[:, b]
    return X, X[:, :width].sum(1) - X[:, width:2 * width].sum(1)


def _multiclass(rng, n):
    X = rng.standard_normal((n, 5))
    return X, np.argmax(X[:, :3] + 0.3 * rng.standard_normal((n, 3)),
                        axis=1).astype(np.float64)


BASE = {"objective": "regression", "max_bin": 63, "min_data_in_leaf": 5,
        "verbose": -1, "tpu_boost_chunk": 2}
CASES = {
    "leaves_2": (_numeric, {"num_leaves": 2}, {}),
    "leaves_31_nan_missing": (_numeric, {"num_leaves": 31},
                              {"nan": True}),
    "leaves_31_zero_missing": (_numeric, {"num_leaves": 31,
                                          "zero_as_missing": True},
                               {"zeros": True}),
    "leaves_255": (_numeric, {"num_leaves": 255, "min_data_in_leaf": 2},
                   {}),
    "fewer_leaves_than_slots": (_numeric, {"num_leaves": 31,
                                           "min_data_in_leaf": 120}, {}),
    "max_depth_3": (_numeric, {"num_leaves": 31, "max_depth": 3}, {}),
    "categorical_multiword": (_categorical, {"num_leaves": 15,
                                             "max_cat_to_onehot": 4,
                                             "min_data_per_group": 5,
                                             "cat_smooth": 1.0}, {}),
    "efb_bundled": (_bundled, {"num_leaves": 15}, {}),
    "multiclass_two_valid_sets": (_multiclass, {
        "num_leaves": 15, "objective": "multiclass", "num_class": 3}, {}),
}


@functools.lru_cache(maxsize=None)
def _grown(case):
    """(booster, [valid TpuDataset], [per-iteration per-class host
    TreeArrays]) of a four-round training of ``case``."""
    make, params, kw = CASES[case]
    rng = np.random.default_rng(35)
    n = 2400 if case == "leaves_255" else 1200
    X, y = make(rng, n, **kw)
    cat = [0] if case.startswith("categorical") else "auto"
    ds = lgb.Dataset(X[:n // 2], y[:n // 2], categorical_feature=cat,
                     params=dict(BASE, **params))
    valid = [ds.create_valid(X[n // 2:], y[n // 2:])]
    if case.startswith("multiclass"):
        valid.append(ds.create_valid(X[:n // 4], y[:n // 4]))
    trees, fetch = [], gm.fetch_tree_chunk

    def spy(ints_all, floats_all, leaves):
        chunk = fetch(ints_all, floats_all, leaves)
        trees.extend(chunk)
        return chunk

    gm.fetch_tree_chunk = spy
    try:
        bst = lgb.train(dict(BASE, **params), ds, num_boost_round=4,
                        verbose_eval=False)
        _ = bst.gbdt.models
    finally:
        gm.fetch_tree_chunk = fetch
    return bst, [v.construct()._handle for v in valid], trees


def _depth_bound(gbdt):
    gp = gbdt.grower_params
    return (gp.max_depth + 1) if gp.max_depth > 0 else gp.num_leaves


def _single_leaf(arrays):
    return arrays._replace(num_leaves=np.int32(1),
                           leaf_value=arrays.leaf_value + np.float32(0.25))


@pytest.mark.parametrize("case", sorted(CASES) + ["single_leaf"])
def test_dense_walk_is_the_gather_walk(case):
    bst, valid, trees = _grown("leaves_31_nan_missing"
                               if case == "single_leaf" else case)
    g = bst.gbdt
    bound = _depth_bound(g)
    flat = [a for per_class in trees for a in per_class]
    if case == "single_leaf":
        flat = [_single_leaf(a) for a in flat[:2]]
    flat = [jax.tree_util.tree_map(jnp.asarray, a) for a in flat]
    L = g.grower_params.num_leaves
    nl = [int(a.num_leaves) for a in flat]
    if case == "leaves_255":
        assert max(nl) == 255 == L
    if case == "fewer_leaves_than_slots":
        assert max(nl) < L
    if case == "max_depth_3":
        assert max(int(np.max(a.leaf_depth)) for a in flat) == 3
    if case == "categorical_multiword":
        cats = [a.cat_bitset[:n - 1][a.is_cat[:n - 1]]
                for a, n in zip(flat, nl)]
        assert any((c[:, 1:] != 0).any() for c in cats)
    if case == "efb_bundled":
        assert g.fmeta.feat_group is not None
    gather = jax.jit(gm._route_tree_rows, static_argnums=(3,))
    for vset in valid:
        vb = vset.device_binned()
        (rows,) = gm._dense_walk_plan([vb], L)
        dense = jax.jit(gm._dense_tree_rows, static_argnums=(3, 4))
        for arrays in flat:
            want, want_levels = gather(arrays, vb, g.fmeta, bound)
            got, got_levels = dense(arrays, vb, g.fmeta, bound, rows)
            want, got = np.asarray(want), np.asarray(got)
            assert np.array_equal(want.view(np.uint32), got.view(np.uint32))
            assert int(got_levels) == int(want_levels)
            if int(arrays.num_leaves) > 1:
                assert int(got_levels) == int(
                    np.max(arrays.leaf_depth[:int(arrays.num_leaves)]))
            else:
                assert int(got_levels) == 0
                assert (got == arrays.leaf_value[0]).all()

    # the carries: every class's tree folded into every valid set
    vbins = [v.device_binned() for v in valid]
    C = g.num_tree_per_iteration
    start = [jnp.asarray(np.random.default_rng(1).standard_normal(
        (C, vb.shape[0])).astype(np.float32)) for vb in vbins]

    def fold(plan):
        saved = gm._dense_walk_plan
        gm._dense_walk_plan = plan
        try:
            carry, levels = start, 0
            for i, arrays in enumerate(flat):
                carry, walked = gm._eval_walk(
                    carry, vbins, arrays, g.fmeta, i % C,
                    jnp.float32(0.1), bound)
                levels += int(walked)
            return [np.asarray(c) for c in carry], levels
        finally:
            gm._dense_walk_plan = saved

    want, want_levels = fold(lambda vbins, leaves: None)
    got, got_levels = fold(gm._dense_walk_plan)
    assert got_levels == want_levels
    for w, d in zip(want, got):
        assert np.array_equal(w.view(np.uint32), d.view(np.uint32))


def test_plan_keeps_the_gather_walk_past_its_room():
    def shape(n, dtype=jnp.uint8):
        return jax.ShapeDtypeStruct((n, 28), dtype)

    # the cell's shape: four blocks of 125,184 rows
    assert gm._dense_walk_plan([shape(500_000)], 255) == (125_184,)
    assert gm._dense_walk_plan([shape(300), shape(70_000)], 31) == (
        512, 70_144)
    # bins past 256 values are not all bfloat16 integers
    assert gm._dense_walk_plan([shape(500_000, jnp.uint16)], 255) is None
    assert gm._dense_walk_plan([shape(300), shape(300, jnp.uint16)],
                               31) is None
    # past the room: at the cell's 500,000 rows (8 bytes a row and leaf,
    # 6 a node and leaf) up to 534 leaves; at 1,000 rows the [L - 1, L]
    # signs stop it between 8,000 and 10,000
    assert gm._dense_walk_plan([shape(500_000)], 534) is not None
    assert gm._dense_walk_plan([shape(500_000)], 535) is None
    assert gm._dense_walk_plan([shape(1000)], 8000) is not None
    assert gm._dense_walk_plan([shape(1000)], 10_000) is None
