"""Lookahead lane sets of the strict segment grower (grower_seg.
lookahead_split, ops/pallas_histogram.histogram_segment_lookahead).

The oracle: a lookahead histogram depends on the data alone, and with no
compaction between filling and use it is summed over the same rows in the
same block and chunk order as the scan it replaces (blocks outside the leaf
add exact zeros).  So with compaction disabled the grower must return trees
and ``leaf_id`` BIT-IDENTICAL to the same program with no slot ever filled,
whatever the seed and shape.  With compaction on only the order of the sums
moves: same splits, same partition, values to f32 rounding.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu.models.grower_seg as gs
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.dataset import TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objective import create_objective
from lightgbm_tpu.ops import pallas_histogram as ph

RB = 256        # 12-16 blocks at these sizes: intervals that differ
SEEDS = range(8)
# stats slots, by name (grower_seg.SegStats)
(SCANNED, SORTS, GRID, MAXB, SPLITS, HITS, FILLED, ROUTE_ONLY) = map(
    gs.SegStats._fields.index,
    ("scanned_blocks", "compactions", "grid_steps", "max_blocks", "splits",
     "lookahead_hits", "lookahead_filled", "route_only_blocks"))


def _data(shape, rng):
    """(X, y, params, categorical features) of one of the shapes
    test_grower_seg.py covers, at more leaves than lane sets."""
    if shape == "binary":
        n = 3000
        X = rng.normal(size=(n, 6))
        y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] ** 2
             + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
        return X, y, dict(objective="binary", max_bin=63), []
    if shape == "packed4":
        n = 3000
        X = rng.normal(size=(n, 7))
        y = (X[:, 0] + 0.6 * X[:, 1] - 0.2 * X[:, 2] ** 2
             + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
        return X, y, dict(objective="binary", max_bin=15), []
    if shape == "missing_nan":
        n = 2500
        X = rng.normal(size=(n, 5))
        X[rng.uniform(size=(n, 5)) < 0.15] = np.nan
        y = (np.where(np.isnan(X[:, 0]), 0.5, np.nan_to_num(X[:, 0]) > 0)
             + 0.4 * np.nan_to_num(X[:, 1])
             + 0.3 * np.nan_to_num(X[:, 2]) ** 2
             + 0.05 * rng.normal(size=n)).astype(np.float64)
        return X, y, dict(objective="regression", max_bin=31), []
    if shape == "categorical":
        n = 2500
        Xc = rng.randint(0, 12, size=n)
        Xn = rng.normal(size=(n, 3))
        X = np.column_stack([Xc.astype(np.float64), Xn])
        effect = np.array([1.5, -2, 0.3, 2, -1, 0.8, -0.2, 1.1, -1.7, 0.5,
                           2.2, -0.9])
        y = effect[Xc] + Xn[:, 0] + 0.1 * rng.normal(size=n)
        return X, y, dict(objective="regression", max_bin=63,
                          min_data_per_group=20, cat_smooth=1.0), [0]
    if shape == "wide":
        # three feature tiles a pass and a compaction that gathers: it
        # costs about one pass, so the derived budget re-sorts often
        n = 3000
        X = rng.normal(size=(n, 300))
        y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] ** 2
             + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
        return X, y, dict(objective="binary", max_bin=63), []
    if shape == "chain":
        # one dominant feature: the child that keeps its parent's id is
        # split again and again, the case a stale entry would corrupt
        n = 3000
        X = rng.normal(size=(n, 4))
        y = np.exp(1.5 * X[:, 0]) + 0.05 * rng.normal(size=n)
        return X, y, dict(objective="regression", max_bin=63), []
    raise ValueError(shape)


class _Case:
    """One dataset's device operands, as GBDT lays them out for the
    segment grower (feature-major bins padded to the row block), and a
    seeded (grad, hess, member) triple for it."""

    def __init__(self, shape, leaves=40):
        rng = np.random.RandomState((SHAPES + SHAPES_MORE).index(shape))
        X, y, params, cats = _data("binary" if shape == "bagging"
                                   else shape, rng)
        cfg = Config(verbosity=-1, tpu_histogram_backend="pallas",
                     tpu_tree_impl="segment", tpu_row_chunk=RB,
                     num_leaves=leaves, min_data_in_leaf=5, **params)
        ds = TpuDataset.from_numpy(X, y, config=cfg,
                                   categorical_features=cats)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        bst = GBDT(cfg, ds, obj)
        assert bst._use_segment and bst.grower_params.row_chunk == RB
        self.shape, self.bst, self.y = shape, bst, np.asarray(y)
        self.bins = bst._device_bins()
        self.n, self.npad = ds.num_data, self.bins.shape[1]
        self.fmask = jnp.ones(bst.fmeta.num_bin.shape[0], jnp.float32)

    def operands(self, seed):
        rng = np.random.RandomState(1000 + seed)
        yc = self.y - self.y.mean()
        g = np.zeros(self.npad, np.float32)
        h = np.zeros(self.npad, np.float32)
        m = np.zeros(self.npad, np.float32)
        g[:self.n] = -yc + 0.3 * rng.normal(size=self.n)
        h[:self.n] = rng.uniform(0.5, 1.5, size=self.n)
        # bagging: a member mask of zeros and ones (pad rows always zero)
        m[:self.n] = (rng.uniform(size=self.n) < 0.7
                      if self.shape == "bagging" else 1.0)
        return jnp.asarray(g), jnp.asarray(h), jnp.asarray(m)

    def grow(self, grower, seed):
        g, h, m = self.operands(seed)
        tree, lid, stats = grower(self.bins, g, h, m, self.bst.fmeta,
                                  self.fmask, jax.random.PRNGKey(seed))
        return (jax.tree_util.tree_map(np.asarray, tree), np.asarray(lid),
                np.asarray(stats))

    def grower(self, mp, variant, waste=None, plain_reads=False):
        """A grower of this case's shape, traced under the patches:
        ``look`` the program as built, ``nofill`` the same program whose
        slots are never filled, ``k1`` one lane set (the program before
        lookahead).  ``waste`` tables of scanning replace the budget
        ``compaction_budget_blocks`` derives from the shape (1e6: never);
        ``plain_reads`` takes ``leaf_hist[leaf]`` and ``look_hist[leaf]``
        by plain indexing where they are used, unpinned (the program
        before ``_pinned_row``)."""
        if waste is not None:
            mp.setattr(gs, "compaction_budget_blocks",
                       lambda columns, bins, rows, rb, packed4: max(
                           1, int(waste * (rows // rb))))
        if plain_reads:
            mp.setattr(gs, "_pinned_row", lambda table, i: (table[i], table))
        if variant == "nofill":
            mp.setattr(gs, "_lookahead_pending",
                       lambda st, leaf, lo, hi: jnp.full(
                           st.look_ok.shape, gs.NEG_INF, jnp.float32))
        elif variant == "k1":
            mp.setattr(gs, "lookahead_width", lambda *a: 1)
        fn = gs.make_grow_tree_segment(self.bst.num_bins,
                                       self.bst.grower_params, RB)
        self.grow(fn, 0)            # trace now, under the patches
        return fn


def _assert_same_bits(a, b):
    (ta, la, _), (tb, lb, _) = a, b
    for name, x, y in zip(ta._fields, ta, tb):
        np.testing.assert_array_equal(x, y, err_msg=name)
    np.testing.assert_array_equal(la, lb)


def _assert_same_tree(a, b, rtol=2e-4):
    """Same splits and partition; sums to f32 rounding."""
    (ta, la, _), (tb, lb, _) = a, b
    assert int(ta.num_leaves) == int(tb.num_leaves)
    for name in ("split_feature", "threshold_bin", "default_left",
                 "left_child", "right_child", "leaf_count"):
        np.testing.assert_array_equal(getattr(ta, name), getattr(tb, name),
                                      err_msg=name)
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_allclose(ta.leaf_value, tb.leaf_value, rtol=rtol,
                               atol=1e-6)
    np.testing.assert_allclose(ta.split_gain, tb.split_gain, rtol=5e-3,
                               atol=1e-4)


SHAPES = ["binary", "packed4", "missing_nan", "categorical", "bagging",
          "chain"]
# not in the bit-identity matrix: built for the derived budget's case
SHAPES_MORE = ["wide"]


@pytest.fixture(scope="module")
def cases():
    return {}


def _case(cases, shape):
    if shape not in cases:
        # the wide table: leaves enough for several compactions a tree
        cases[shape] = _Case(shape, leaves=96 if shape == "wide" else 40)
    return cases[shape]


@pytest.mark.parametrize("shape,against",
                         [(s, "nofill") for s in SHAPES]
                         + [("binary", "plain_reads"),
                            ("chain", "plain_reads")])
def test_no_compaction_bit_identical_to_unfilled(cases, shape, against,
                                                 monkeypatch):
    """(a), (d): compaction disabled, more open leaves than lane sets:
    trees and leaf ids bit for bit those of the program that never fills a
    slot, on every seed; hits + scans = splits, filled >= hits.  A child
    that reuses its parent's id and took the parent's entry, a wrong
    side, a wrong membership: each would show here.  ``plain_reads``: and
    bit for bit, counters too, those of the same program with the two
    table rows read unpinned: the pin moves bytes, no value."""
    case = _case(cases, shape)
    with monkeypatch.context() as mp:
        look = case.grower(mp, "look", waste=1e6)
    with monkeypatch.context() as mp:
        ref = (case.grower(mp, "nofill", waste=1e6) if against == "nofill"
               else case.grower(mp, "look", waste=1e6, plain_reads=True))
    total_hits = 0
    for seed in SEEDS:
        a, b = case.grow(look, seed), case.grow(ref, seed)
        _assert_same_bits(a, b)
        sa, sb = a[2], b[2]
        assert sa[SORTS] == 0 and sb[SORTS] == 0
        assert sa[SPLITS] == sb[SPLITS] == int(a[0].num_leaves) - 1
        if against == "nofill":
            assert sb[HITS] == sb[FILLED] == sb[ROUTE_ONLY] == 0
        else:
            np.testing.assert_array_equal(sa, sb)
        # every pass covers all blocks here: the root's, then one a scan
        scans = sa[SCANNED] // sa[MAXB] - 1
        assert sa[HITS] + scans == sa[SPLITS]
        assert sa[ROUTE_ONLY] == sa[HITS] * sa[MAXB]
        assert sa[FILLED] >= sa[HITS]
        # grid steps are those of accumulating passes, dynamic grids
        assert sa[GRID] == sa[SCANNED]
        assert sa[SPLITS] > 16, "fewer open leaves than lane sets"
        total_hits += sa[HITS]
    assert total_hits > 0, "no split was served by a lookahead histogram"


@pytest.mark.parametrize(
    "shape,waste",
    [(s, w) for s in ("binary", "categorical", "bagging")
     for w in (9.0, 0.5, 0.01)] + [("wide", None)],
    ids=[f"{w}-{s}" for s in ("binary", "categorical", "bagging")
         for w in ("default", "often", "every_split")] + ["derived-wide"])
def test_compaction_same_tree(cases, shape, waste, monkeypatch):
    """(b): with compaction on (down to one after nearly every split, the
    epoch edge), a lookahead histogram filled before a sort and used after
    it gives the splits and partition of the unfilled program and of the
    one-lane-set program; only the order of the sums differs.
    ``derived``: the budget as the function gives it for a wide table,
    where a compaction is cheap and a tree takes several."""
    case = _case(cases, shape)
    if waste is None:
        budget = gs.compaction_budget_blocks(300, case.bst.num_bins,
                                             case.npad, RB, False)
        assert budget < 6 * (case.npad // RB)      # 9 N at 28 columns
    with monkeypatch.context() as mp:
        look = case.grower(mp, "look", waste=waste)
    with monkeypatch.context() as mp:
        ref = case.grower(mp, "nofill", waste=waste)
    with monkeypatch.context() as mp:
        k1 = case.grower(mp, "k1", waste=waste)
    sorts = hits = 0
    seeds = range(2 if waste is None else 4)
    for seed in seeds:
        a = case.grow(look, seed)
        _assert_same_tree(a, case.grow(ref, seed))
        _assert_same_tree(a, case.grow(k1, seed))
        sorts += a[2][SORTS]
        hits += a[2][HITS]
        assert a[2][FILLED] >= a[2][HITS]
    assert hits > 0
    if waste is None:
        assert sorts >= 2 * len(seeds), "several compactions a tree"
    elif waste < 9.0:
        assert sorts > 0, "no compaction fell between fill and use"


@pytest.mark.parametrize("waste", [1e6, 0.5], ids=["never", "often"])
def test_hit_reads_and_refills_against_a_numpy_follower(cases, waste,
                                                        monkeypatch):
    """One loop body holds the read of ``look_hist[leaf]`` (used when the
    leaf is a hit) and the scatter of the pass's lookahead rows (a miss
    refills other leaves' rows; a hit accumulates nothing, so its scatter
    drops every row).  With channels that float32 sums exactly, every
    leaf's and node's hessian sum and count and every leaf's value are
    those numpy takes from the returned partition: a row read after the
    scatter wrote it, a stale row or another leaf's would give children
    whose recorded sums are not their rows'."""
    case = _case(cases, "binary")
    with monkeypatch.context() as mp:
        look = case.grower(mp, "look", waste=waste)
    rng = np.random.RandomState(7)
    g = np.zeros(case.npad, np.float32)
    h = np.zeros(case.npad, np.float32)
    m = np.zeros(case.npad, np.float32)
    g[:case.n] = np.clip(np.round(4 * (0.5 - case.y + 0.4 * rng.normal(
        size=case.n))), -8, 8) / 4
    h[:case.n] = rng.choice([0.5, 1.0], case.n)
    m[:case.n] = 1.0
    tree, lid, stats = look(case.bins, jnp.asarray(g), jnp.asarray(h),
                            jnp.asarray(m), case.bst.fmeta, case.fmask,
                            jax.random.PRNGKey(0))
    tree = jax.tree_util.tree_map(np.asarray, tree)
    lid, stats = np.asarray(lid)[:case.n], np.asarray(stats)
    nl = int(tree.num_leaves)
    assert stats[SPLITS] == nl - 1 > 16
    assert 0 < stats[HITS] < stats[SPLITS] and stats[FILLED] > stats[HITS]
    assert (stats[SORTS] > 0) == (waste < 1e6)
    G = np.bincount(lid, weights=g[:case.n], minlength=nl)
    H = np.bincount(lid, weights=h[:case.n], minlength=nl)
    C = np.bincount(lid, minlength=nl)
    np.testing.assert_array_equal(tree.leaf_weight[:nl], H.astype(np.float32))
    np.testing.assert_array_equal(tree.leaf_count[:nl], C)
    np.testing.assert_allclose(tree.leaf_value[:nl], -G / H, rtol=1e-6)

    def sums(child):
        if child < 0:
            return H[~child], C[~child]
        (hl, cl), (hr, cr) = (sums(tree.left_child[child]),
                              sums(tree.right_child[child]))
        assert tree.internal_weight[child] == np.float32(hl + hr)
        assert tree.internal_count[child] == cl + cr
        return hl + hr, cl + cr

    assert sums(0) == (H.sum(), case.n)


@pytest.mark.parametrize("plain_reads", [False, True],
                         ids=["pinned", "plain_reads"])
def test_one_lane_set_builds_the_program_of_before(cases, plain_reads,
                                                   monkeypatch):
    """A shape where one lane set fills the budget runs no lookahead: its
    counters stay 0 and the kernel is the routed one.  Its ``look_hist``
    has no rows and is never read; ``leaf_hist[leaf]`` pinned or read
    plainly, the bits are the same."""
    case = _case(cases, "binary")
    with monkeypatch.context() as mp:
        k1 = case.grower(mp, "k1")
    a = case.grow(k1, 3)
    assert a[2][SPLITS] > 0
    assert a[2][HITS] == a[2][FILLED] == a[2][ROUTE_ONLY] == 0
    if plain_reads:
        with monkeypatch.context() as mp:
            plain = case.grower(mp, "k1", plain_reads=True)
        b = case.grow(plain, 3)
        _assert_same_bits(a, b)
        np.testing.assert_array_equal(a[2], b[2])


def test_pending_needs_containment_not_overlap():
    """(c): a neighbour that shares only a boundary block with the pass is
    not filled; a leaf inside the interval is; nor is the leaf being
    split, a leaf that holds an entry already, or one with nothing to
    gain."""
    L = 8
    st = gs.fresh_state(jnp.zeros((4, 8 * RB), jnp.uint8),
                        jnp.zeros((8, 8 * RB), jnp.bfloat16), 8 * RB, L, 4,
                        16, 4, 8, 0.0, 1.0, 1.0, None,
                        gs.GrowerParams(num_leaves=L), lookahead=True)
    #            0: split   1: inside  2: shares block 2  3: shares block 5
    #            4: inside, filled    5: inside, no gain  6: equal interval
    lo = jnp.asarray([2, 3, 0, 5, 2, 4, 2, 0], jnp.int32)
    hi = jnp.asarray([6, 5, 3, 8, 4, 6, 6, 0], jnp.int32)
    gain = jnp.asarray([9., 1., 2., 3., 4., 0., 5., gs.NEG_INF])
    st = st._replace(leaf_lo=lo, leaf_hi=hi,
                     best_f32=st.best_f32.at[:, 0].set(gain),
                     look_ok=st.look_ok.at[4].set(True))
    got = np.asarray(gs._lookahead_pending(st, jnp.int32(0), jnp.int32(2),
                                           jnp.int32(6)))
    assert list(np.flatnonzero(got > 0)) == [1, 6]
    np.testing.assert_array_equal(got[[1, 6]], [1., 5.])


class _Meta:
    """Minimal FeatureMeta-alike for pack_route (4 features: zero-missing,
    NaN-missing twice, none)."""
    feat_group = None
    feat_offset = None
    missing_type = jnp.asarray([1, 2, 2, 0], jnp.int32)
    default_bin = jnp.asarray([3, 0, 0, 0], jnp.int32)
    num_bin = jnp.full((4,), 16, jnp.int32)


def _kernel_operands(nblk, rb=4 * ph.CHUNK):
    rng = np.random.default_rng(5)
    F, B = 4, 16
    n = rb * nblk
    binsT = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    w8 = ph.pack_channels(
        jnp.asarray(rng.standard_normal(n), jnp.float32),
        jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32),
        jnp.ones(n, jnp.float32))
    lid = np.full(n, 7, np.int32)
    lid[rb:(nblk - 1) * rb] = rng.choice([3, 5, 6], (nblk - 2) * rb)
    bitset = jnp.asarray(rng.integers(0, 2**32, 8, dtype=np.uint64)
                         .astype(np.uint32))
    return binsT, w8, jnp.asarray(lid), bitset, B, rb


# (pending leaf, smaller side is left, feature, threshold, default_left,
# categorical): NaN-missing numeric to the right, a categorical bitset,
# the rows this very pass routes to leaf 9, a plain numeric one
_LIVE = ((5, 0, 2, 8, False, False), (5, 1, 1, 0, True, True),
         (9, 1, 3, 5, False, False), (6, 1, 0, 4, True, False))


def _slots(K, bitset, live=_LIVE):
    pad = K - 1 - len(live)
    cols = list(zip(*live))
    return ph.pack_lookahead_slots(
        jnp.asarray(cols[0] + (-1,) * pad, jnp.int32),
        jnp.asarray(cols[1] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[2] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[3] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[4] + (False,) * pad),
        jnp.asarray(cols[5] + (False,) * pad),
        jnp.tile(bitset[None], (K - 1, 1)), _Meta, False)


@pytest.mark.parametrize("K", [16, 6])
def test_kernel_lane_sets(K):
    """(c): lane set 0 and the routed ids are the routed kernel's (bit for
    bit over one block, to f32 rounding over several: the sums are kept
    as a pair across blocks); each lookahead lane set is bit for bit lane
    set 0 of a pass over the rows its (leaf, pending split, side)
    selects, and close to ``histogram_segment`` over them; empty slots
    and a route-only call return zeros."""
    from lightgbm_tpu.ops.split import routed_left
    binsT, w8, lid, bitset, B, rb = _kernel_operands(6)
    route = ph.pack_route(3, 9, 0, B // 2, True, False, bitset, _Meta,
                          False)
    s0, nb = jnp.int32(1), jnp.int32(4)
    lid1, h1 = ph.histogram_segment_routed(binsT, w8, lid, s0, nb,
                                           jnp.int32(9), route, B, rb)
    slots = _slots(K, bitset)
    lidk, hk = ph.histogram_segment_lookahead(
        binsT, w8, lid, s0, nb, jnp.int32(9), route, slots, nb, B, rb)
    np.testing.assert_array_equal(lidk, lid1)
    assert hk.shape == (K,) + h1.shape
    np.testing.assert_allclose(hk[0], h1, rtol=1e-6, atol=1e-5)
    # one block: no pair to carry, the routed kernel's bits
    one = jnp.int32(1)
    _, h1b = ph.histogram_segment_routed(binsT, w8, lid, s0, one,
                                         jnp.int32(9), route, B, rb)
    _, hkb = ph.histogram_segment_lookahead(
        binsT, w8, lid, s0, one, jnp.int32(9), route, slots, one, B, rb)
    np.testing.assert_array_equal(hkb[0], h1b)

    empty = ph.empty_lookahead_slots(K - 1)
    for k, (leaf, side, f, t, dl, cat) in enumerate(_LIVE, start=1):
        go = routed_left(binsT[f].astype(jnp.int32), t, dl, cat, bitset,
                         _Meta.missing_type[f], _Meta.default_bin[f],
                         _Meta.num_bin[f])
        marked = jnp.where((lid1 == leaf) & (go == bool(side)), 999, lid1)
        _, ref = ph.histogram_segment_lookahead(
            binsT, w8, marked, s0, nb, jnp.int32(999), ph.null_route(),
            empty, nb, B, rb)
        assert np.asarray(ref[0]).any()
        np.testing.assert_array_equal(hk[k], ref[0], err_msg=f"slot {k}")
        plain = ph.histogram_segment(binsT, w8, marked, s0, nb,
                                     jnp.int32(999), B, rb)
        np.testing.assert_allclose(hk[k], plain, rtol=1e-6, atol=1e-5)
    assert not np.asarray(hk[1 + len(_LIVE):]).any()

    lid0, h0 = ph.histogram_segment_lookahead(
        binsT, w8, lid, s0, nb, jnp.int32(9), route, slots, jnp.int32(0),
        B, rb)
    np.testing.assert_array_equal(lid0, lid1)
    assert not np.asarray(h0).any()


def test_kernel_lane_sets_packed4():
    """A slot's split feature is read by nibble parity out of the packed
    block, both parities."""
    rng = np.random.default_rng(9)
    F, rb, nblk = 4, 2 * ph.CHUNK, 3
    n = rb * nblk
    bins4 = rng.integers(0, 15, (F, n))
    packedT = jnp.asarray(ph.pack_bins_4bit(bins4))
    w8 = ph.pack_channels(
        jnp.asarray(rng.standard_normal(n), jnp.float32),
        jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32))
    lid = jnp.asarray(rng.choice([3, 5], n).astype(np.int32))

    class _M4(_Meta):
        num_bin = jnp.full((4,), 15, jnp.int32)
        missing_type = jnp.zeros(4, jnp.int32)
        default_bin = jnp.zeros(4, jnp.int32)

    zb = jnp.zeros((2, 8), jnp.uint32)
    slots = ph.pack_lookahead_slots(
        jnp.asarray([5, 5], jnp.int32), jnp.asarray([1, 0], jnp.int32),
        jnp.asarray([1, 2], jnp.int32), jnp.asarray([7, 4], jnp.int32),
        jnp.asarray([False, False]), jnp.asarray([False, False]), zb, _M4,
        True)
    nb = jnp.int32(nblk)
    _, hk = ph.histogram_segment_lookahead(
        packedT, w8, lid, jnp.int32(0), nb, jnp.int32(3), ph.null_route(),
        slots, nb, 16, rb, packed4=True)
    for k, member in ((1, bins4[1] <= 7), (2, bins4[2] > 4)):
        marked = jnp.where((lid == 5) & jnp.asarray(member), 999, lid)
        plain = ph.histogram_segment(packedT, w8, marked, jnp.int32(0), nb,
                                     jnp.int32(999), 16, rb, packed4=True)
        assert np.asarray(plain).any()
        np.testing.assert_allclose(hk[k], plain, rtol=1e-6, atol=1e-5)


def test_width_comes_from_the_shape():
    """K is ``frontier_width``'s up to one sublane group of slots, and 1
    where one lane set fills the budget."""
    assert ph.lookahead_width(28, 64, 32768, False) == 8
    assert ph.lookahead_width(28, 16, 32768, True) == 8
    assert ph.lookahead_width(136, 256, 4096, False) == \
        ph.frontier_width(136, 256) == 4
    assert ph.lookahead_width(2000, 256, 512, False) == 1
