"""The plain reference at a second width: routing that reads only the split
columns, the block it sizes from the shape, the host's candidate gains, and
a follower at 300 columns held against the float64 witness."""

import os

import numpy as np
import pytest

import compare
import datagen
import reference
import witness
from manifest import HERE, load_module

cost_tool = load_module(os.path.join(HERE, "tools", "reference_cost.py"),
                        "reference_cost")


def route_by_selects(X, feat, thr, path, depth):
    """The route of before PR 29, kept as the oracle: one select a column."""
    import jax.numpy as jnp
    xs = jnp.zeros((X.shape[0], feat.shape[0]), jnp.float32)
    for f in range(X.shape[1]):
        xs = jnp.where(feat[None, :] == f, X[:, f:f + 1], xs)
    d = jnp.where(xs > thr[None, :], 1.0, -1.0).astype(jnp.float32)
    match = jnp.dot(d, path, preferred_element_type=jnp.float32)
    return match == depth[None, :]


@pytest.mark.parametrize("n_feat", [5, 28, 300])
@pytest.mark.parametrize("used", ["few", "many"])
def test_route_is_the_old_route(n_feat, used):
    import jax
    import jax.numpy as jnp
    rs = np.random.default_rng(n_feat)
    X = rs.standard_normal((512, n_feat), dtype=np.float32)
    leaves = 63
    tree = cost_tool.synthetic_tree(rs, n_feat, leaves,
                                    np.linspace(-1.5, 1.5, 31))
    if used == "few":
        tree.split_feature = np.where(tree.split_feature % 2, 1, n_feat - 1)
    else:
        tree.split_feature = np.arange(leaves - 1) % n_feat
    tables = [jnp.asarray(t) for t in reference.tree_tables(tree, 64, 64)]
    new = np.asarray(jax.jit(reference.route)(jnp.asarray(X), *tables))
    old = np.asarray(jax.jit(route_by_selects)(jnp.asarray(X), *tables))
    assert np.array_equal(new, old)
    assert np.all(new.sum(axis=1) == 1)          # every row in one leaf
    assert len(np.unique(new.argmax(axis=1))) > 8


def test_block_rows_follow_the_shape():
    assert reference.block_rows(28, 64, 256) == 16384
    assert reference.block_rows(2000, 64, 256) == 2048
    sizes = [reference.block_rows(f, b, leaves)
             for f in (5, 28, 136, 300, 968, 2000, 20000, 10 ** 6)
             for b in (16, 64, 256) for leaves in (8, 256, 1024)]
    for r in sizes:
        assert reference.MIN_BLOCK <= r <= reference.MAX_BLOCK
        assert r & (r - 1) == 0                  # a power of two
        # what `dot_t` divides a block by
        assert r % 256 == 0 and r % min(r, reference.PARTIAL_ROWS) == 0
    assert max(sizes) == 16384 and min(sizes) == 256
    # wider never takes a larger block
    by_width = [reference.block_rows(f, 64, 256) for f in range(5, 4000, 37)]
    assert by_width == sorted(by_width, reverse=True)


def test_upload_pads_the_last_block_only(monkeypatch):
    rs = np.random.default_rng(3)
    X = rs.standard_normal((1000, 7), dtype=np.float32)
    monkeypatch.setattr(reference, "SLAB_BYTES", 3 * 256 * 7 * 4)
    for view in (X, X[::2], X[:768]):            # a strided view goes up too
        blocks = np.asarray(reference._upload_blocks(view, 256))
        n = len(view)
        assert blocks.shape == (-(-n // 256), 7, 256)    # rows last
        flat = blocks.swapaxes(1, 2).reshape(-1, 7)
        assert np.array_equal(flat[:n], view) and not flat[n:].any()


def gains_by_einsum(fol, hist, anc, node, split_feature):
    """The host's part of before PR 29, kept as the oracle: einsum's loop
    and every [S, F, B] array whole."""
    S, L = anc.shape
    h4 = hist.reshape(L, 3, fol.F, -1)
    nh = np.einsum("sl,lkfb->skfb", anc, h4)

    def score(g, h):
        return g * g / (h + reference.K_EPS)

    tot = node[:, :, None, None]
    lg, lh, lc = nh[:, 0], nh[:, 1], nh[:, 2]
    rg, rh, rc = tot[:, 0] - lg, tot[:, 1] - lh, tot[:, 2] - lc
    ok = ((lc >= fol.min_data) & (rc >= fol.min_data)
          & (lh >= fol.min_hess) & (rh >= fol.min_hess)
          & np.isfinite(fol.cand_np)[None])
    cg = (score(lg, lh) + score(rg, rh)
          - score(node[:, 0], node[:, 1])[:, None, None])
    cg = np.where(ok, cg, -np.inf)
    mine = np.arange(fol.F)[None, :] == split_feature[:, None]
    return (cg.reshape(S, -1).max(axis=1),
            np.where(mine[:, :, None], -np.inf, cg).reshape(S, -1).max(axis=1))


@pytest.fixture(scope="module")
def wide():
    """Rows at 300 columns (a width the program's kernels admit) and a model
    the program trains on them, on whatever backend the test runs."""
    import lightgbm_tpu as lgb
    rows, n_feat = 6000, 300
    X, y, _ = datagen.make({"generator": "higgs_proxy", "features": n_feat,
                            "noise": 0.5}, rows, np.random.default_rng(29))
    params = {"objective": "binary", "max_bin": 63, "num_leaves": 15,
              "learning_rate": 0.1, "min_sum_hessian_in_leaf": 5.0,
              "feature_fraction_bynode": 0.5, "verbose": -1}
    bst = lgb.train(dict(params), lgb.Dataset(X, y, params=dict(params)),
                    num_boost_round=4, verbose_eval=False)
    return X, y, params, reference.parse_model(bst.model_to_string())


def test_follower_at_300_columns_against_the_witness(wide, monkeypatch):
    X, y, params, trees = wide
    assert len({int(f) for t in trees for f in t.split_feature}) > 5
    cand = reference.candidate_thresholds(trees, X.shape[1], 64)
    fol = reference.Follower(X, y, params, cand, params["num_leaves"])
    assert fol.block_rows == reference.block_rows(300, 64, 16) == 8192
    oracle = []
    real = fol._candidate_gains
    monkeypatch.setattr(
        fol, "_candidate_gains",
        lambda *a: oracle.append(gains_by_einsum(fol, *a)) or real(*a))
    host = witness.HostFollower(X, y, params)
    try:
        for i, tree in enumerate(trees[:3]):
            facts = compare.facts_of_tree(tree,
                                          fol.init_score if i == 0 else 0.0)
            r = fol.step(tree, facts.leaf_value)
            w = host.step(tree)
            assert r.unrouted == 0
            assert np.array_equal(r.leaf_c, w["count"])
            # PERF.md section 2 has 4e-8 at the cell's size; here step 1
            # gives every row the one hessian, whose float32 rounding
            # (gradients are float32 by the configuration) the sums share
            assert compare._worst_gap(r.leaf_h, w["h"]) < 2e-7
            assert compare._worst_gap(r.leaf_g, w["g"]) < 2e-6
            assert compare._worst_gap(r.leaf_value, w["value"]) < 2e-6
            # the host's part, bit for bit the whole-array einsum's
            best, other = oracle[-1]
            assert np.array_equal(r.best_gain, best)
            assert np.array_equal(r.other_gain, other)
            # the split the program made is one of the candidates
            assert np.all(r.best_gain >= r.gain * (1 - 1e-5))
            assert np.all(r.other_gain <= r.best_gain)
            # and the program's own record agrees with both
            assert compare._worst_gap(facts.leaf_value, r.leaf_value) < 1e-4
        summed = fol.sum_forest(trees)
    finally:
        fol.close()
    # every tree of the run summed over every row: the witness's leaves
    want = sum(np.asarray(t.leaf_value)[host.leaves_of(t)] for t in trees)
    assert np.max(np.abs(summed - want)) < 1e-6
