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
            # DYN_GRID pinned on: =0 would silently veto the forced
            # kernel leg and both legs would compare the XLA path
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       LIGHTGBM_TPU_DYN_GRID="1",
                       LIGHTGBM_TPU_ROUTE_KERNEL=rk, IMPL=impl, OUT=out)
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True)
            assert r.returncode == 0, r.stderr[-500:]
            preds[(impl, tag)] = np.load(out)
        d = np.abs(preds[(impl, "xla")] - preds[(impl, "kernel")]).max()
        assert d == 0.0, (impl, d)


# ------------------------------------------------------------------ fused-K
# PR 16: histogram_frontier_fusedk routes the round's K splits AND
# accumulates ALL 2K children in one pass.  Bit-identity contract: the
# fused pass must equal routing the ids first (numpy reference) and
# running histogram_frontier over the SAME 2K targets — both concat the
# same masked channel sets into the same one-hot matmul in the same
# chunk order, so every accumulator column is the identical f32 dot.


def test_fused_k_kernel_self_check():
    from lightgbm_tpu.ops.pallas_histogram import _fused_k_self_check
    assert _fused_k_self_check()


@pytest.mark.parametrize("K", [1, 4, 16])
def test_fused_k_bit_identity_kernel(K):
    """K routes cycling the flavor set — numeric zero-missing rows,
    NaN-missing rows, categorical bitset, plain numeric — plus a null
    tail slot at K>1 (the grower's invalid-prefix shape)."""
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops.pallas_histogram import (histogram_frontier,
                                                   histogram_frontier_fusedk,
                                                   null_route,
                                                   pack_channels, pack_route)

    rng = np.random.RandomState(17)
    F, B, rb, nblk = 6, 16, 256, 8
    n = rb * nblk
    binsT_np = rng.randint(0, B, size=(F, n)).astype(np.uint8)
    # zero-missing rows: feature 0 carries its default bin often enough
    # that every parent routes some missing rows
    binsT_np[0, rng.random(n) < 0.3] = 2
    # NaN-missing rows: feature 2's NaN bin is B - 1
    binsT_np[2, rng.random(n) < 0.2] = B - 1
    binsT = jnp.asarray(binsT_np)
    w8 = pack_channels(jnp.asarray(rng.randn(n), jnp.float32),
                       jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32),
                       jnp.asarray((rng.random(n) < 0.9), jnp.float32))
    parents = 10 + np.arange(K, dtype=np.int32)
    news = 100 + np.arange(K, dtype=np.int32)
    lid_np = parents[rng.randint(0, K, size=n)].astype(np.int32)
    bl = jnp.arange(nblk, dtype=jnp.int32)
    nb = jnp.int32(nblk)
    bitset = jnp.asarray(
        rng.randint(0, 2**32, size=8, dtype=np.uint64).astype(np.uint32))

    class _M:
        feat_group = None
        feat_offset = None
        missing_type = jnp.asarray([1, 0, 2, 0, 0, 0], jnp.int32)
        default_bin = jnp.asarray([2, 0, 0, 0, 0, 0], jnp.int32)
        num_bin = jnp.full((F,), B, jnp.int32)

    def np_go_left(f, thr, dl, cat):
        fcol = binsT_np[f].astype(np.int64)
        mt = int(_M.missing_type[f])
        miss = ((mt == 1) & (fcol == int(_M.default_bin[f]))
                | (mt == 2) & (fcol == B - 1))
        if cat:
            w = np.asarray(bitset)[np.clip(fcol, 0, 255) // 32]
            return (w >> (np.clip(fcol, 0, 255) % 32)) & 1 > 0
        return np.where(miss, dl, fcol <= thr)

    # flavor cycle: (feature, cat, default_left); the tail slot of any
    # K > 1 case is a null route with -1 targets (invalid prefix slot)
    flavors = [(0, False, True), (1, True, False), (2, False, False),
               (3, False, True)]
    routes, exp = [], lid_np.copy()
    t2 = np.concatenate([parents, news]).astype(np.int32)
    for j in range(K):
        if K > 1 and j == K - 1:
            routes.append(null_route())
            t2[j] = t2[K + j] = -1
            continue
        f, cat, dl = flavors[j % len(flavors)]
        thr = B // 2 + (j % 3)
        routes.append(pack_route(int(parents[j]), int(news[j]), f, thr,
                                 dl, cat, bitset, _M, False))
        exp[(exp == parents[j]) & ~np_go_left(f, thr, dl, cat)] = news[j]
    lid2, hist = histogram_frontier_fusedk(
        binsT, w8, jnp.asarray(lid_np), bl, nb, jnp.asarray(t2),
        jnp.stack(routes), B, rb, K)
    assert np.array_equal(np.asarray(lid2), exp)
    ref = histogram_frontier(binsT, w8, jnp.asarray(exp), bl, nb,
                             jnp.asarray(t2), B, rb)
    assert np.array_equal(np.asarray(hist), np.asarray(ref))


def test_fused_k_fallback_on_self_check_failure(monkeypatch):
    """Env =1 runs the self-check; a raising check falls back cleanly,
    the failure is memoized, '!'/force bypass, =0 never consults it —
    and a vetoed K>1 policy request counts a fused_k_fallbacks event."""
    import lightgbm_tpu.ops.pallas_histogram as ph
    from lightgbm_tpu.utils.telemetry import TELEMETRY

    calls = []

    def boom():
        calls.append(1)
        raise RuntimeError("synthetic lowering failure")

    monkeypatch.setattr(ph, "_FUSED_K_CHECK", None)
    monkeypatch.setattr(ph, "_fused_k_self_check", boom)
    monkeypatch.setenv("LIGHTGBM_TPU_DYN_GRID", "1")
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_K", "1")
    assert ph.fused_k_enabled() is False
    assert ph.fused_k_enabled() is False
    assert len(calls) == 1, "self-check must be memoized"
    before = TELEMETRY.stats()["counters"].get("hist/fused_k_fallbacks",
                                               0)
    assert ph.fused_route_policy(8, 28, 64, 32768, False) != "fusedk"
    after = TELEMETRY.stats()["counters"].get("hist/fused_k_fallbacks", 0)
    assert after == before + 1
    # trailing '!' and force bypass the (failing) check; off never
    # consults it
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_K", "1!")
    assert ph.fused_k_enabled() is True
    assert ph.fused_route_policy(8, 28, 64, 32768, False) == "fusedk"
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_K", "force")
    assert ph.fused_k_enabled() is True
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_K", "0")
    assert ph.fused_k_enabled() is False
    assert len(calls) == 1


def test_fused_k_grower_matches_no_subtract(rng):
    """The fused-K round computes BOTH children from data — the same
    arithmetic family as CommHooks(no_subtract=True).  Same tree, same
    leaf ids, bit-exact (the subtraction-trick default differs in f32
    rounding, which is why that is not the comparison here)."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.models.grower import CommHooks, GrowerParams
    from lightgbm_tpu.models.grower_frontier import make_grow_tree_frontier
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams

    F, B, L, rb, K, n = 4, 16, 8, 256, 3, 2048
    binsT = jnp.asarray(rng.randint(0, B, size=(F, n)), jnp.uint8)
    grad = jnp.asarray(rng.randn(n), jnp.float32)
    hess = jnp.ones(n, jnp.float32)
    member = jnp.ones(n, jnp.float32)
    fmeta = FeatureMeta(num_bin=jnp.full(F, B, jnp.int32),
                        missing_type=jnp.zeros(F, jnp.int32),
                        default_bin=jnp.zeros(F, jnp.int32),
                        is_cat=jnp.zeros(F, bool),
                        monotone=jnp.zeros(F, jnp.int32),
                        penalty=jnp.ones(F, jnp.float32))
    gp = GrowerParams(num_leaves=L, hist_backend="pallas",
                      split=SplitParams(min_data_in_leaf=2.0))
    fmask = jnp.ones(F, jnp.float32)
    key = jax.random.PRNGKey(0)
    g_fk = make_grow_tree_frontier(B, gp, rb, batch_k=K, fused_k=True)
    g_ns = make_grow_tree_frontier(B, gp, rb, batch_k=K,
                                   comm=CommHooks(no_subtract=True))
    ta, la, sa = g_fk(binsT, grad, hess, member, fmeta, fmask, key)
    tb, lb, _ = g_ns(binsT, grad, hess, member, fmeta, fmask, key)
    assert np.array_equal(np.asarray(la), np.asarray(lb))
    import jax.tree_util as jtu
    for fa, fb in zip(jtu.tree_leaves(ta), jtu.tree_leaves(tb)):
        assert np.array_equal(np.asarray(fa), np.asarray(fb))
    # stats slot 5 counts the fused rounds (telemetry hist/fused_k_rounds)
    assert int(np.asarray(sa)[5]) > 0


def test_fused_packed_optin_decision(monkeypatch):
    """packed_acc forces the unfused pair unless LIGHTGBM_TPU_FUSED_PACKED
    opts the combined variant in (build-time decision, no training)."""
    import jax.numpy as jnp

    from lightgbm_tpu.models.grower import GrowerParams
    from lightgbm_tpu.models.grower_frontier import make_grow_tree_frontier
    from lightgbm_tpu.ops.pallas_histogram import fused_route_decisions
    from lightgbm_tpu.ops.split import SplitParams

    gp = GrowerParams(num_leaves=31, hist_backend="pallas",
                      split=SplitParams(min_data_in_leaf=2.0))
    monkeypatch.setenv("LIGHTGBM_TPU_DYN_GRID", "1")
    monkeypatch.setenv("LIGHTGBM_TPU_PACKED_ACC", "force")
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_K", "force")
    monkeypatch.delenv("LIGHTGBM_TPU_FUSED_PACKED", raising=False)
    make_grow_tree_frontier(16, gp, 256, batch_k=4)
    assert fused_route_decisions["frontier"] is False
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_PACKED", "1")
    make_grow_tree_frontier(16, gp, 256, batch_k=4)
    assert fused_route_decisions["frontier"] == "fusedk"
