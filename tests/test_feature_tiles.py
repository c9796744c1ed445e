"""Feature tiles: a table too wide for one VMEM accumulator (2000 columns x
64 bins is 65.5 MB) is walked tile by tile by the routed segment kernels.

Held here, on the CPU in interpret mode: the tiled kernels against the
untiled ones bit for bit at a shape both take (the tile forced small through
the functions' own argument), the tiled passes against a numpy histogram at
300 columns, a model the segment grower trains over several tiles against
the XLA one-hot grower's, the shape arithmetic, and what GBDT does with the
growers that walk no tiles."""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models import grower_seg
from lightgbm_tpu.ops import pallas_histogram as ph
from lightgbm_tpu.utils.telemetry import TELEMETRY


class _Meta:
    """FeatureMeta-alike for pack_route: plain numerical columns."""

    feat_group = None
    feat_offset = None

    def __init__(self, F, B):
        self.missing_type = jnp.zeros(F, jnp.int32)
        self.default_bin = jnp.zeros(F, jnp.int32)
        self.num_bin = jnp.full((F,), B, jnp.int32)


def _table(F_log, B, rb, nblk, packed4, seed):
    """Bins, exactly representable channels and two leaves (3, 5) confined
    to blocks [1, nblk - 1), leaf 7 elsewhere."""
    rng = np.random.default_rng(seed)
    n = rb * nblk
    bins = rng.integers(0, B, (F_log, n)).astype(np.uint8)
    grad = rng.integers(-8, 9, n) / 4.0
    hess = rng.choice([0.5, 1.0], n)
    lid = np.full(n, 7, np.int32)
    lid[rb:(nblk - 1) * rb] = np.where(
        rng.random((nblk - 2) * rb) < 0.5, 3, 5)
    w8 = ph.pack_channels(jnp.asarray(grad, jnp.float32),
                          jnp.asarray(hess, jnp.float32),
                          jnp.ones(n, jnp.float32))
    binsT = jnp.asarray(ph.pack_bins_4bit(bins) if packed4 else bins)
    return bins, grad, hess, lid, binsT, w8


def _slots(K, live, F_log, B, packed4):
    """[K-1] lookahead slots: `live` rows of (leaf, smaller side is left,
    feature, threshold), the rest empty."""
    pad = K - 1 - len(live)
    cols = list(zip(*live))
    return ph.pack_lookahead_slots(
        jnp.asarray(cols[0] + (-1,) * pad, jnp.int32),
        jnp.asarray(cols[1] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[2] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[3] + (0,) * pad, jnp.int32),
        jnp.zeros(K - 1, bool), jnp.zeros(K - 1, bool),
        jnp.zeros((K - 1, 8), jnp.uint32), _Meta(F_log, B), packed4)


@pytest.mark.parametrize("packed4", [False, True], ids=["u8", "packed4"])
@pytest.mark.parametrize("entry", ["routed", "lookahead", "route_only"])
def test_tiled_kernel_is_the_untiled_kernel_bit_for_bit(entry, packed4):
    """Per (tile, block) the matmuls are the untiled kernel's over those
    columns in the same chunk order, and the route is idempotent: ids and
    every sum equal, bit for bit, slots whose split column lies in another
    tile than the one being accumulated included."""
    F_log, B, rb, nblk = 192, 16, 1024, 5
    tile = 64                       # logical columns: 3 tiles
    _, _, _, lid, binsT, w8 = _table(F_log, B, rb, nblk, packed4, 3)
    route = ph.pack_route(3, 9, 130, B // 2, True, False,
                          jnp.zeros(8, jnp.uint32), _Meta(F_log, B), packed4)
    args = (binsT, w8, jnp.asarray(lid), jnp.int32(1), jnp.int32(nblk - 2),
            jnp.int32(9), route)
    if entry == "routed":
        whole = ph.histogram_segment_routed(*args, B, rb, packed4=packed4)
        tiled = ph.histogram_segment_routed(*args, B, rb, packed4=packed4,
                                            feature_tile_cols=tile)
    else:
        slots = _slots(8, ((5, 0, 2, B // 2), (5, 1, 150, 3), (9, 1, 70, 5)),
                       F_log, B, packed4)
        n_acc = jnp.int32(0 if entry == "route_only" else nblk - 2)
        whole = ph.histogram_segment_lookahead(*args, slots, n_acc, B, rb,
                                               packed4=packed4)
        tiled = ph.histogram_segment_lookahead(*args, slots, n_acc, B, rb,
                                               packed4=packed4,
                                               feature_tile_cols=tile)
        if entry == "lookahead":
            assert all(np.asarray(whole[1][k]).any() for k in range(4))
    assert np.asarray(whole[1]).any() == (entry != "route_only")
    assert np.array_equal(np.asarray(whole[0]), np.asarray(tiled[0]))
    assert np.array_equal(np.asarray(whole[1]), np.asarray(tiled[1]))
    assert (np.asarray(tiled[0]) == 9).any()


def _numpy_hist(bins, grad, hess, member, B):
    """[F, B, 3] float64 sums of (grad, hess, 1) over `member` rows."""
    F = bins.shape[0]
    out = np.zeros((F, B, 3))
    rows = np.flatnonzero(member)
    for f in range(F):
        b = bins[f, rows]
        out[f, :, 0] = np.bincount(b, weights=grad[rows], minlength=B)
        out[f, :, 1] = np.bincount(b, weights=hess[rows], minlength=B)
        out[f, :, 2] = np.bincount(b, minlength=B)
    return out


@pytest.mark.parametrize("entry", ["root", "routed", "lookahead"])
def test_tiled_pass_against_numpy_at_300_columns(entry):
    """300 columns padded to 320, five tiles of 64: the root pass (a route
    that matches nothing), the routed pass and the lookahead lane sets hold
    numpy's sums exactly (channels chosen exactly representable)."""
    F, Fpad, B, rb, nblk, tile = 300, 320, 64, 512, 6, 64
    bins, grad, hess, lid, _, w8 = _table(F, B, rb, nblk, False, 11)
    binsT = jnp.asarray(np.concatenate(
        [bins, np.zeros((Fpad - F, bins.shape[1]), np.uint8)]))
    meta = _Meta(Fpad, B)
    if entry == "root":
        lid0 = jnp.zeros(lid.shape, jnp.int32)
        ids, out = ph.histogram_segment_lookahead(
            binsT, w8, lid0, jnp.int32(0), jnp.int32(nblk), jnp.int32(0),
            ph.null_route(), ph.empty_lookahead_slots(7), jnp.int32(nblk),
            B, rb, feature_tile_cols=tile)
        assert not np.asarray(ids).any()
        want = [np.ones(len(grad), bool)]
        got = [out[0]]
    else:
        thr, f = 20, 287
        route = ph.pack_route(3, 9, f, thr, True, False,
                              jnp.zeros(8, jnp.uint32), meta, False)
        routed = lid.copy()
        routed[(lid == 3) & (bins[f] > thr)] = 9
        args = (binsT, w8, jnp.asarray(lid), jnp.int32(1),
                jnp.int32(nblk - 2), jnp.int32(9), route)
        if entry == "routed":
            ids, out = ph.histogram_segment_routed(*args, B, rb,
                                                   feature_tile_cols=tile)
            got, want = [out], [routed == 9]
        else:
            live = ((5, 0, 4, 30), (5, 1, 299, 10), (9, 1, 130, 40))
            ids, out = ph.histogram_segment_lookahead(
                *args, _slots(8, live, Fpad, B, False), jnp.int32(nblk - 2),
                B, rb, feature_tile_cols=tile)
            got = [out[k] for k in range(4)]
            want = [routed == 9] + [
                (routed == leaf) & ((bins[sf] <= st) == bool(left))
                for leaf, left, sf, st in live]
            assert not np.asarray(out[4:]).any()
        assert np.array_equal(np.asarray(ids), routed)
    for g, member in zip(got, want):
        assert member.sum() > 100
        h = np.asarray(ph.unpack_hist(g))
        assert not h[F:, 1:].any()      # the padding is all bin 0
        np.testing.assert_array_equal(
            h[:F, :, :], _numpy_hist(bins, grad, hess, member, B))


WIDE = {"objective": "binary", "max_bin": 63, "num_leaves": 31,
        "learning_rate": 0.1, "min_sum_hessian_in_leaf": 5.0, "verbose": -1,
        "tpu_row_chunk": 1024, "tpu_boost_chunk": 2}


def _wide_rows(rows=6000, n_feat=300, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, n_feat), dtype=np.float32)
    w = np.random.default_rng(1).standard_normal(n_feat)
    y = (X @ w + rng.standard_normal(rows) * 4 > 0).astype(np.float64)
    return X, y


def _parse(bst):
    import os
    import sys
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if here not in sys.path:
        sys.path.insert(0, here)
    import reference
    return reference.parse_model(bst.model_to_string())


@pytest.mark.parametrize("against", ["onehot", "plain_reads"])
def test_wide_model_is_the_onehot_growers(tiles_of_96, against, monkeypatch):
    """300 columns x 64 bins x 31 leaves over 4 feature tiles and 6 row
    blocks: the segment grower's trees are `models/grower.py`'s, split for
    split.  Leaf values agree to 5e-5, not bit for bit: the two growers sum
    a leaf's gradients in different orders (bf16 hi+lo channels through the
    MXU a block at a time, against one float32 one-hot product), which
    moves -G/H in its last float32 digits and, from the second tree on,
    the gradients with it.  ``plain_reads``: against the segment grower
    that reads its two per-leaf table rows unpinned, the model is the same
    text: `grower_seg._pinned_row` moves bytes, no value."""
    X, y = _wide_rows()
    if against == "plain_reads":
        texts = []
        for plain in (False, True):
            if plain:
                monkeypatch.setattr(grower_seg, "_pinned_row",
                                    lambda table, i: (table[i], table))
            p = dict(WIDE, tpu_histogram_backend="pallas")
            bst = lgb.train(p, lgb.Dataset(X, y, params=dict(p)),
                            num_boost_round=4, verbose_eval=False)
            assert bst.gbdt._use_segment and bst.gbdt._bins_row_multiple == 96
            texts.append(bst.model_to_string())
        assert texts[0] == texts[1]
        return
    TELEMETRY.reset()
    models = {}
    for backend in ("pallas", "onehot"):
        p = dict(WIDE, tpu_histogram_backend=backend)
        bst = lgb.train(p, lgb.Dataset(X, y, params=dict(p)),
                        num_boost_round=4, verbose_eval=False)
        g = bst.gbdt
        assert g.grower_params.hist_backend == backend
        assert bool(g._use_segment) == (backend == "pallas")
        if backend == "pallas":
            assert g._bins_row_multiple == 96
            assert g.bins.shape == (384, 6144)
            st = TELEMETRY.stats()
            assert st["gauges"]["seg/feature_tiles"] == 4
            assert st["gauges"]["seg/leaf_hist_bytes"] == \
                2 * 31 * 300 * 64 * 3 * 4 // 1024 * 1024
            c = st["counters"]
            assert c["seg/grid_steps"] == 4 * c["seg/scanned_blocks"]
            assert c["seg/lookahead_hits"] > 0
        models[backend] = _parse(bst)
    used = set()
    for a, b in zip(models["pallas"], models["onehot"]):
        assert a.num_leaves == b.num_leaves == 31
        assert np.array_equal(a.split_feature, b.split_feature)
        assert np.array_equal(a.threshold, b.threshold)
        assert np.array_equal(a.left_child, b.left_child)
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, atol=5e-5)
        used |= {int(f) for f in a.split_feature}
    assert len({f // 96 for f in used}) == 4        # splits in every tile


@pytest.mark.parametrize("F,B,tiles,block", [
    (28, 64, 1, 32768), (136, 64, 1, 8192), (2000, 64, 16, 8192),
    (28, 256, 1, 32768)])
def test_shape_arithmetic(F, B, tiles, block):
    """What the cells' shapes resolve to: the narrow ones whole with the
    block rows they always had, 2000 x 64 in 16 tiles of 128 columns, each
    with eight lane sets inside the fused kernels' VMEM ceiling."""
    assert ph.supported(F, B, jnp.uint8)
    assert ph.feature_tiles(F, B) == tiles
    assert ph.pick_block_rows(F, B) == block
    assert ph.lookahead_width(F, B, block, False) == 8
    assert ph.fused_route_fits(F, B, 1, block, False, targets_k=8)
    tile = ph.feature_tile(F, B)
    assert tile * B * 128 * 4 <= (ph._SCOPED_VMEM_LIMIT if tiles == 1
                                  else ph._TILE_ACC_BYTES)
    if tiles > 1:
        assert tile % 32 == 0 and tile == 128


@pytest.mark.parametrize("F,B,dtype", [
    (28, 512, jnp.uint8), (28, 64, jnp.uint16), (136, 256, jnp.uint8),
    (2000, 256, jnp.uint8), (2000, 32, jnp.uint8), (4000, 16, jnp.uint8)])
def test_what_is_still_refused(F, B, dtype):
    """Tiles are admitted at 64 bins, where a tiled pass has run on the
    chip; past one accumulator at any other height the shape is refused as
    it always was (GBDT warns and takes the XLA one-hot grower)."""
    assert not ph.supported(F, B, dtype)
    assert ph.feature_tiles(F, B) == 1


def test_other_growers_stay_off_the_kernels_at_a_tiled_shape(tiles_of_96):
    """Only the serial segment grower's fused kernels walk tiles: the
    frontier grower at such a shape is refused the Pallas backend (with the
    warning every refused shape gets) and trains on the one-hot grower."""
    X, y = _wide_rows(rows=2000)
    p = dict(WIDE, tpu_histogram_backend="pallas", tpu_tree_impl="frontier",
             num_leaves=7)
    bst = lgb.train(p, lgb.Dataset(X, y, params=dict(p)), num_boost_round=1,
                    verbose_eval=False)
    assert bst.gbdt.grower_params.hist_backend == "onehot"
    assert not bst.gbdt._use_segment


def test_grower_refuses_tiles_without_the_fused_kernels(tiles_of_96,
                                                        monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_ROUTE", "0")
    X, y = _wide_rows(rows=2000)
    p = dict(WIDE, tpu_histogram_backend="pallas", num_leaves=7)
    with pytest.raises(ValueError, match="feature tiles"):
        lgb.train(p, lgb.Dataset(X, y, params=dict(p)), num_boost_round=1,
                  verbose_eval=False)


def test_device_table_is_padded_to_whole_tiles_once():
    X, y = _wide_rows(rows=1000, n_feat=70)
    ds = lgb.Dataset(X, y, params={"max_bin": 63, "verbose": -1})
    ds.construct()
    inner = ds._handle
    plain = inner.host_binned_T(256)
    padded = inner.host_binned_T(256, feature_multiple=32)
    assert plain.shape == (70, 1024) and padded.shape == (96, 1024)
    assert np.array_equal(padded[:70], plain) and not padded[70:].any()
    assert np.array_equal(plain[:, :1000], inner.binned.T)
