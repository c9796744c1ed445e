"""Pallas histogram kernel correctness (interpret mode on CPU).

The real-TPU compiled path is exercised by bench.py and the driver's
entry-point checks; here we pin down numerics against the XLA one-hot
reference implementation.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import histogram_chunked
from lightgbm_tpu.ops.pallas_histogram import (empty_lookahead_slots,
                                               histogram_all,
                                               histogram_frontier,
                                               histogram_segment,
                                               histogram_segment_lookahead,
                                               histogram_segment_routed,
                                               leaf_histogram_pallas,
                                               null_route, pack_channels,
                                               unpack_hist)


def _ref_hist(bins, g, h, m, B):
    F = bins.shape[1]
    out = np.zeros((F, B, 3))
    for f in range(F):
        out[f, :, 0] = np.bincount(bins[:, f], weights=g * m, minlength=B)
        out[f, :, 1] = np.bincount(bins[:, f], weights=h * m, minlength=B)
        out[f, :, 2] = np.bincount(bins[:, f], weights=m, minlength=B)
    return out


def test_pack_channels_split_accuracy(rng):
    g = rng.normal(size=1000).astype(np.float32) * 7.3
    w8 = np.asarray(pack_channels(jnp.asarray(g), jnp.asarray(g),
                                  jnp.ones(1000, jnp.float32)))
    recon = w8[0].astype(np.float64) + w8[1].astype(np.float64)
    # hi+lo bf16 split carries ~16 mantissa bits
    assert np.abs(recon - g).max() <= np.abs(g).max() * 2 ** -15


@pytest.mark.parametrize("n,f,b", [(600, 5, 16), (1024, 3, 64)])
def test_histogram_all_matches_reference(rng, n, f, b):
    rb = 256
    npad = (-n) % rb
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    m = (rng.uniform(size=n) < 0.8).astype(np.float32)
    binsT = np.pad(bins.T, ((0, 0), (0, npad)))
    gp, hp, mp = (np.pad(x, (0, npad)) for x in (g, h, m))
    w8 = pack_channels(jnp.asarray(gp), jnp.asarray(hp), jnp.asarray(mp))
    out = unpack_hist(histogram_all(jnp.asarray(binsT), w8, b,
                                    block_rows=rb, interpret=True))
    exp = _ref_hist(bins, g, h, m, b)
    got = np.asarray(out, np.float64)
    assert np.abs(got[..., 2] - exp[..., 2]).max() < 1e-3       # counts exact
    scale = np.abs(exp).max()
    assert np.abs(got - exp).max() < max(1e-6, scale * 3e-4)


def test_histogram_all_packed4_matches_unpacked(rng):
    from lightgbm_tpu.ops.pallas_histogram import pack_bins_4bit
    n, f, b, rb = 1024, 6, 16, 256
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    m = np.ones(n, np.float32)
    w8 = pack_channels(jnp.asarray(g), jnp.asarray(h), jnp.asarray(m))
    plain = unpack_hist(histogram_all(jnp.asarray(bins.T.copy()), w8, b,
                                      block_rows=rb, interpret=True))
    packedT = pack_bins_4bit(bins.T)
    assert packedT.shape == (f // 2, n)
    packed = unpack_hist(histogram_all(jnp.asarray(packedT), w8, b,
                                       block_rows=rb, interpret=True,
                                       packed4=True))
    np.testing.assert_allclose(np.asarray(packed)[:f], np.asarray(plain),
                               rtol=1e-6, atol=1e-6)


# (start block, blocks) of a 6-block table
INTERVALS = {"empty": (1, 0), "one-block": (2, 1), "three-of-six": (1, 3),
             "whole": (0, 6)}


@pytest.mark.parametrize("interval", list(INTERVALS))
@pytest.mark.parametrize("kernel", ["segment", "frontier", "routed",
                                    "lookahead"])
def test_interval_kernels_against_numpy(kernel, interval):
    """Every kernel whose grid is its interval's length, against numpy
    over the rows of that interval: the plain segment kernel, the
    frontier kernel (the interval as a block list, two target leaves),
    the routed kernel under a route that matches nothing and the
    lookahead kernel with every slot empty (lane set 0; the other lane
    sets stay zero, the ids come back untouched).  An empty interval
    runs one masked grid step and returns zeros."""
    rng = np.random.default_rng(17)
    n_blk, f, b, rb = 6, 4, 16, 256
    n = n_blk * rb
    bins = rng.integers(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    m = (rng.uniform(size=n) < 0.8).astype(np.float32)
    # three leaves spread over every block
    lid = rng.choice(np.asarray([3, 5, 7], np.int32), size=n)
    start, blocks = INTERVALS[interval]
    inside = (np.arange(n) // rb >= start) & (np.arange(n) // rb
                                              < start + blocks)

    def want(leaf):
        sel = inside & (lid == leaf)
        return _ref_hist(bins[sel], g[sel], h[sel], m[sel], b)

    binsT = jnp.asarray(bins.T.copy())
    w8 = pack_channels(jnp.asarray(g), jnp.asarray(h), jnp.asarray(m))
    lid_d = jnp.asarray(lid)
    s0, nb = jnp.int32(start), jnp.int32(blocks)
    kw = dict(block_rows=rb, interpret=True)
    lid_out = None
    if kernel == "segment":
        got = {3: histogram_segment(binsT, w8, lid_d, s0, nb, jnp.int32(3),
                                    b, **kw)}
    elif kernel == "frontier":
        block_list = np.zeros(n_blk, np.int32)
        block_list[:blocks] = np.arange(start, start + blocks)
        out = histogram_frontier(binsT, w8, lid_d, jnp.asarray(block_list),
                                 nb, jnp.asarray([3, 5], jnp.int32), b, **kw)
        got = {3: out[0], 5: out[1]}
    elif kernel == "routed":
        lid_out, out = histogram_segment_routed(
            binsT, w8, lid_d, s0, nb, jnp.int32(3), null_route(), b, **kw)
        got = {3: out}
    else:
        lid_out, out = histogram_segment_lookahead(
            binsT, w8, lid_d, s0, nb, jnp.int32(3), null_route(),
            empty_lookahead_slots(7), nb, b, **kw)
        assert out.shape[0] == 8 and not np.asarray(out[1:]).any()
        got = {3: out[0]}
    if lid_out is not None:
        np.testing.assert_array_equal(np.asarray(lid_out), lid)
    for leaf, out in got.items():
        exp = want(leaf)
        out = np.asarray(unpack_hist(out), np.float64)
        if not blocks:
            assert not exp.any() and not out.any()
        assert np.abs(out[..., 2] - exp[..., 2]).max() < 1e-3   # counts
        assert np.abs(out - exp).max() < max(1e-6, np.abs(exp).max() * 3e-4)


def test_histogram_segment_restricts_to_leaf(rng):
    n, f, b, rb = 1024, 4, 16, 256
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    m = np.ones(n, np.float32)
    # 4 leaves striped across 4 blocks: leaf = block index
    lid = (np.arange(n) // rb).astype(np.int32)
    w8 = pack_channels(jnp.asarray(g), jnp.asarray(h), jnp.asarray(m))
    out = histogram_segment(jnp.asarray(bins.T.copy()), w8,
                            jnp.asarray(lid), jnp.int32(2), jnp.int32(2),
                            jnp.int32(2), b, block_rows=rb, interpret=True)
    got = np.asarray(unpack_hist(out), np.float64)
    sel = lid == 2
    exp = _ref_hist(bins[sel], g[sel], h[sel], m[sel], b)
    assert np.abs(got - exp).max() < max(1e-6, np.abs(exp).max() * 3e-4)


def test_grower_pallas_matches_onehot_tree(rng):
    """Same tiny problem grown with both backends: same structure, near-same
    outputs (bf16 hi/lo histogram vs f32)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.core.dataset import TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objective import create_objective

    n = 700
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)

    def train(backend):
        cfg = Config(objective="binary", num_leaves=8, max_bin=31,
                     min_data_in_leaf=10, num_iterations=3, verbosity=-1,
                     tpu_histogram_backend=backend)
        ds = TpuDataset.from_numpy(X, y, config=cfg)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        bst = GBDT(cfg, ds, obj)
        for _ in range(3):
            bst.train_one_iter()
        return bst

    b_ref = train("onehot")
    b_pal = train("pallas")
    assert b_pal.grower_params.hist_backend == "pallas"
    p_ref = b_ref._raw_predict(X)
    p_pal = b_pal._raw_predict(X)
    # structure parity: same leaf counts per tree
    for t_ref, t_pal in zip(b_ref.models, b_pal.models):
        assert t_ref.num_leaves == t_pal.num_leaves
    assert np.abs(p_ref - p_pal).max() < 5e-3


def test_histogram_frontier_matches_segment(rng):
    """K-leaf batched kernel == K separate segment scans; -1 targets are
    zero; the block list restricts the scan to the union of intervals."""
    from lightgbm_tpu.ops.pallas_histogram import histogram_frontier

    n, f, b, rb, K = 2048, 5, 16, 256, 4
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    m = np.ones(n, np.float32)
    # 8 leaves striped across 8 blocks: leaf = block index
    lid = (np.arange(n) // rb).astype(np.int32)
    w8 = pack_channels(jnp.asarray(g), jnp.asarray(h), jnp.asarray(m))
    binsT = jnp.asarray(bins.T.copy())

    targets = jnp.asarray([1, 3, 6, -1], jnp.int32)
    block_list = jnp.asarray([1, 3, 6, 0, 0, 0, 0, 0], jnp.int32)
    out = histogram_frontier(binsT, w8, jnp.asarray(lid), block_list,
                             jnp.int32(3), targets, b, block_rows=rb,
                             interpret=True)
    assert out.shape == (K, f, b, 8)
    for k, t in enumerate([1, 3, 6]):
        sel = lid == t
        exp = _ref_hist(bins[sel], g[sel], h[sel], m[sel], b)
        got = np.asarray(unpack_hist(out[k]), np.float64)
        assert np.abs(got - exp).max() < max(1e-6,
                                             np.abs(exp).max() * 3e-4), t
    # -1 target -> exactly zero
    assert float(jnp.abs(out[3]).max()) == 0.0
    # blocks outside the list contribute nothing even if the leaf strays
    # into them: leaf 1 rows exist only in block 1, which IS listed; now
    # ask for leaf 0 but list only block 3 -> zero histogram
    out2 = histogram_frontier(binsT, w8, jnp.asarray(lid),
                              jnp.asarray([3], jnp.int32), jnp.int32(1),
                              jnp.asarray([0, -1, -1, -1], jnp.int32), b,
                              block_rows=rb, interpret=True)
    assert float(jnp.abs(out2[0]).max()) == 0.0


def test_histogram_frontier_packed4(rng):
    from lightgbm_tpu.ops.pallas_histogram import (histogram_frontier,
                                                   pack_bins_4bit)
    n, f, b, rb = 1024, 6, 16, 256
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    m = np.ones(n, np.float32)
    lid = (np.arange(n) // rb).astype(np.int32)
    w8 = pack_channels(jnp.asarray(g), jnp.asarray(g), jnp.asarray(m))
    packedT = jnp.asarray(pack_bins_4bit(bins.T))
    out = histogram_frontier(packedT, w8, jnp.asarray(lid),
                             jnp.asarray([0, 1, 2, 3], jnp.int32),
                             jnp.int32(4),
                             jnp.asarray([2, 0, -1, -1], jnp.int32), b,
                             block_rows=rb, interpret=True, packed4=True)
    sel = lid == 2
    exp = _ref_hist(bins[sel], g[sel], g[sel], m[sel], b)
    got = np.asarray(unpack_hist(out[0]), np.float64)[:f]
    assert np.abs(got - exp).max() < max(1e-6, np.abs(exp).max() * 3e-4)


@pytest.mark.parametrize("packed4", [False, True])
def test_histogram_all_multi_channel_sets(rng, packed4):
    """histogram_all with C stacked 8-channel sets == C separate calls
    (multiclass batched roots), in both byte and 4-bit packed layouts."""
    n, f, b, rb, C = 1024, 4, 16, 256, 3
    bins = rng.randint(0, b, size=(n, f)).astype(np.uint8)
    gs = [rng.normal(size=n).astype(np.float32) for _ in range(C)]
    hs = [rng.uniform(0.1, 1.0, size=n).astype(np.float32)
          for _ in range(C)]
    m = (rng.uniform(size=n) < 0.7).astype(np.float32)
    from lightgbm_tpu.ops.pallas_histogram import pack_bins_4bit
    binsT = (jnp.asarray(pack_bins_4bit(bins.T)) if packed4
             else jnp.asarray(bins.T.copy()))
    w8m = jnp.concatenate([pack_channels(jnp.asarray(gs[c]),
                                         jnp.asarray(hs[c]),
                                         jnp.asarray(m)) for c in range(C)])
    multi = histogram_all(binsT, w8m, b, block_rows=rb, interpret=True,
                          packed4=packed4)
    assert multi.shape == (C, f, b, 8)
    for c in range(C):
        single = histogram_all(
            binsT, pack_channels(jnp.asarray(gs[c]), jnp.asarray(hs[c]),
                                 jnp.asarray(m)), b, block_rows=rb,
            interpret=True, packed4=packed4)
        np.testing.assert_allclose(np.asarray(multi[c]),
                                   np.asarray(single), rtol=1e-6,
                                   atol=1e-6)


def test_score_gather_add_matches_gather(rng):
    """One-hot-matmul scorer == plain table gather, exactly (f32)."""
    from lightgbm_tpu.ops.pallas_score import score_gather_add
    for n, L in ((1000, 7), (70000, 255), (32768, 300)):
        score = jnp.asarray(rng.normal(size=n).astype(np.float32))
        lid = jnp.asarray(rng.randint(0, L, size=n).astype(np.int32))
        table = jnp.asarray(rng.normal(size=L).astype(np.float32))
        got = np.asarray(score_gather_add(score, lid, table,
                                          interpret=True))
        want = np.asarray(score) + np.asarray(table)[np.asarray(lid)]
        np.testing.assert_array_equal(got, want)
