"""Leaf-wise tree growth, fused on-device.

The TPU re-design of SerialTreeLearner::Train (serial_tree_learner.cpp:174-239).
The reference's per-split sequence — BeforeFindBestSplit / ConstructHistograms
/ FindBestSplitsFromHistograms / Split over index-list leaf partitions — is
re-expressed as ONE jitted ``lax.fori_loop`` whose state lives entirely in
HBM:

  * leaf membership is a dense ``leaf_id[N]`` vector (scatter-free splits by
    masked where) instead of DataPartition's index lists
    (data_partition.hpp:111);
  * per-leaf histograms are retained in a ``[num_leaves, F, B, 3]`` tensor —
    the HistogramPool (feature_histogram.hpp:654) without eviction since HBM
    comfortably holds all leaves;
  * only the smaller child is histogrammed from data; the larger child is
    parent - smaller (the subtraction trick, serial_tree_learner.cpp:494-497,
    596-597);
  * the leaf to split is the argmax of per-leaf best gains
    (serial_tree_learner.cpp:219), and tree topology is built with LightGBM's
    node numbering (Tree::Split, tree.h:407-445: new internal node =
    num_leaves-1, right child leaf = num_leaves, leaf refs stored as ~leaf).

Everything is traced once per (N, F, B, num_leaves, params) signature; no
host round-trips during growth.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.binning import MISSING_ZERO
from ..ops.histogram import histogram_chunked
from ..ops.split import (NEG_INF, FeatureMeta, SplitParams, best_split,
                         expand_group_hist, leaf_gain, leaf_output,
                         reconstruct_feature_column, routed_left)


class GrowerParams(NamedTuple):
    """Static growth hyper-parameters (folded into the jit signature)."""
    num_leaves: int = 31
    max_depth: int = -1
    feature_fraction_bynode: float = 1.0
    row_chunk: int = 0
    # "onehot": row-major [N, F] bins, XLA one-hot einsum (works anywhere);
    # "pallas": feature-major [F, Npad] bins, TPU pallas kernel
    # (ops/pallas_histogram.py)
    hist_backend: str = "onehot"
    # pallas-only: bins packed two <=16-bin columns per byte
    # (ops/pallas_histogram.pack_bins_4bit; reference Dense4bitsBin,
    # dense_nbits_bin.hpp:42) — halves bin-stream DMA and sort payload
    packed4: bool = False
    # logical bin-matrix columns (EFB groups); 0 = same as the physical
    # row count of the bins array (needed when packed4 obscures it)
    num_columns: int = 0
    # static: any feature carries a monotone constraint — enables per-leaf
    # [min, max] output-bound propagation (LeafSplits::SetValueConstraint,
    # src/treelearner/leaf_splits.hpp:50-53 + the mid-split handoff in
    # serial_tree_learner.cpp:892-903)
    use_monotone: bool = False
    # CEGB penalties (serial_tree_learner.cpp:527-618); split/coupled are
    # in-grower gain adjustments, lazy is handled by the fused grower only
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    use_cegb_coupled: bool = False
    use_cegb_lazy: bool = False
    # forced splits (ForceSplits, serial_tree_learner.cpp:642): static
    # BFS-ordered plan of (leaf, inner_feature, threshold_bin) applied to
    # the leading growth steps before best-gain growth
    forced_plan: tuple = ()
    split: SplitParams = SplitParams()

    @property
    def feature_major(self) -> bool:
        return self.hist_backend == "pallas"


class TreeArrays(NamedTuple):
    """Flat-array tree, device-resident; mirrors reference Tree storage
    (include/LightGBM/tree.h:330-404)."""
    num_leaves: jax.Array          # i32 scalar: leaves actually produced
    # internal nodes [num_leaves-1]
    split_feature: jax.Array       # i32 (index into used features)
    threshold_bin: jax.Array       # i32
    default_left: jax.Array        # bool
    is_cat: jax.Array              # bool
    cat_bitset: jax.Array          # u32 [num_leaves-1, 8]
    left_child: jax.Array          # i32 (>=0 internal, ~leaf for leaves)
    right_child: jax.Array         # i32
    split_gain: jax.Array          # f32
    internal_value: jax.Array      # f32
    internal_weight: jax.Array     # f32
    internal_count: jax.Array      # f32
    # leaves [num_leaves]
    leaf_value: jax.Array          # f32
    leaf_weight: jax.Array         # f32
    leaf_count: jax.Array          # f32
    leaf_parent: jax.Array         # i32
    leaf_depth: jax.Array          # i32


@jax.jit
def _pack_tree_device(t: TreeArrays):
    """Concatenate all tree fields into one i32 + one f32 buffer so the
    host fetch is two transfers instead of ~17 (each pays a full device
    round-trip)."""
    ints = jnp.concatenate([
        jnp.atleast_1d(t.num_leaves),
        t.split_feature, t.threshold_bin,
        t.default_left.astype(jnp.int32), t.is_cat.astype(jnp.int32),
        t.cat_bitset.astype(jnp.int32).ravel(),
        t.left_child, t.right_child,
        t.leaf_parent, t.leaf_depth,
    ])
    floats = jnp.concatenate([
        t.split_gain, t.internal_value, t.internal_weight,
        t.internal_count, t.leaf_value, t.leaf_weight, t.leaf_count,
    ])
    return ints, floats


def _count_fetch(*bufs) -> None:
    """Telemetry: one device->host tree fetch (however many transfers it
    batches) with its total payload bytes."""
    from ..utils.telemetry import TELEMETRY
    TELEMETRY.counter_add("transfer/fetch_calls")
    TELEMETRY.counter_add("transfer/fetch_bytes",
                          sum(int(b.nbytes) for b in bufs))


def fetch_tree_arrays(t: TreeArrays) -> TreeArrays:
    """Device TreeArrays -> host (numpy) TreeArrays via two transfers."""
    import numpy as np
    ints_d, floats_d = _pack_tree_device(t)
    ints_np, floats_np = np.asarray(ints_d), np.asarray(floats_d)
    _count_fetch(ints_np, floats_np)
    return unpack_tree_buffers(ints_np, floats_np, t.leaf_value.shape[0])


def fetch_tree_chunk(ints_all, floats_all, L: int) -> list:
    """Batched inverse of _pack_tree_device over a whole boosting chunk:
    stacked [T, C, len] device buffers -> [[TreeArrays] * C] * T host
    pytrees.  The entire chunk crosses the device boundary in TWO
    transfers; fetching tree-by-tree would pay 2*T*C round-trips."""
    import numpy as np
    ints_np = np.asarray(ints_all)
    floats_np = np.asarray(floats_all)
    _count_fetch(ints_np, floats_np)
    return [[unpack_tree_buffers(ints_np[t, k], floats_np[t, k], L)
             for k in range(ints_np.shape[1])]
            for t in range(ints_np.shape[0])]


def unpack_tree_buffers(ints, floats, L: int) -> TreeArrays:
    """Host-side inverse of _pack_tree_device."""
    import numpy as np
    n = L - 1

    def take(buf, pos, count, shape=None):
        out = buf[pos:pos + count]
        return (out.reshape(shape) if shape else out), pos + count

    p = 0
    num_leaves, p = take(ints, p, 1)
    split_feature, p = take(ints, p, n)
    threshold_bin, p = take(ints, p, n)
    default_left, p = take(ints, p, n)
    is_cat, p = take(ints, p, n)
    cat_bitset, p = take(ints, p, n * 8, (n, 8))
    left_child, p = take(ints, p, n)
    right_child, p = take(ints, p, n)
    leaf_parent, p = take(ints, p, L)
    leaf_depth, p = take(ints, p, L)
    q = 0
    split_gain, q = take(floats, q, n)
    internal_value, q = take(floats, q, n)
    internal_weight, q = take(floats, q, n)
    internal_count, q = take(floats, q, n)
    leaf_value, q = take(floats, q, L)
    leaf_weight, q = take(floats, q, L)
    leaf_count, q = take(floats, q, L)
    return TreeArrays(
        num_leaves=int(num_leaves[0]),
        split_feature=split_feature, threshold_bin=threshold_bin,
        default_left=default_left.astype(bool),
        is_cat=is_cat.astype(bool),
        cat_bitset=cat_bitset.astype(np.uint32),
        left_child=left_child, right_child=right_child,
        split_gain=split_gain, internal_value=internal_value,
        internal_weight=internal_weight, internal_count=internal_count,
        leaf_value=leaf_value, leaf_weight=leaf_weight,
        leaf_count=leaf_count, leaf_parent=leaf_parent,
        leaf_depth=leaf_depth,
    )


class _GrowState(NamedTuple):
    leaf_id: jax.Array
    num_leaves: jax.Array
    leaf_hist: jax.Array           # [L, F, B, 3]
    leaf_g: jax.Array              # [L]
    leaf_h: jax.Array
    leaf_c: jax.Array
    # per-leaf monotone output bounds (LeafSplits min_val_/max_val_)
    leaf_mono_lo: jax.Array        # [L]
    leaf_mono_hi: jax.Array        # [L]
    # CEGB bookkeeping: features used by any split so far ([F] 0/1), and
    # per-(feature, row) "row has paid for feature" marks ([F, N] i8 when
    # cegb_penalty_feature_lazy is active, else [1, 1])
    feat_used: jax.Array
    seen: jax.Array
    # per-leaf best-split cache (best_split_per_leaf_,
    # serial_tree_learner.h:153)
    # best-split cache PACKED into 3 tensors so each scan writes 3 rows
    # instead of 11 scalar scatters: f32 [L, 6] = (gain, left_g, left_h,
    # left_c, left_out, right_out); i32 [L, 4] = (feature, threshold,
    # default_left, is_cat); cat bitset [L, 8] u32
    best_f32: jax.Array
    best_i32: jax.Array
    best_cat_bitset: jax.Array
    tree: TreeArrays


def _node_feature_mask(base_mask, key, step, p: GrowerParams):
    if p.feature_fraction_bynode >= 1.0:
        return base_mask
    sub = jax.random.fold_in(key, step)
    m = jax.random.bernoulli(sub, p.feature_fraction_bynode,
                             base_mask.shape).astype(base_mask.dtype)
    m = m * base_mask
    # guarantee at least one usable feature
    return jnp.where(m.sum() > 0, m, base_mask)


def _cegb_split_coupled_adjust(feat_used, c, fmeta, p: GrowerParams):
    """[F] additive CEGB penalty: per-row split cost + coupled feature cost
    for not-yet-used features (serial_tree_learner.cpp:582-607)."""
    F = feat_used.shape[0]
    adjust = jnp.full(F, p.cegb_tradeoff * p.cegb_penalty_split,
                      jnp.float32) * c
    if p.use_cegb_coupled:
        adjust = adjust + p.cegb_tradeoff * fmeta.cegb_coupled * \
            (1.0 - feat_used)
    return adjust


def _cegb_gain_adjust(st: "_GrowState", leaf, c, in_leaf, fmeta,
                      p: GrowerParams):
    """Full CEGB penalty incl. the lazy per-(feature,row) cost for rows
    that have not yet paid for the feature (CalculateOndemandCosts,
    serial_tree_learner.cpp:527-547)."""
    if not (p.cegb_penalty_split > 0.0 or p.use_cegb_coupled
            or p.use_cegb_lazy):
        return None
    adjust = _cegb_split_coupled_adjust(st.feat_used, c, fmeta, p)
    if p.use_cegb_lazy:
        unseen = jnp.sum((1 - st.seen) * in_leaf[None, :].astype(jnp.int8),
                         axis=1).astype(jnp.float32)          # [F]
        adjust = adjust + p.cegb_tradeoff * fmeta.cegb_lazy * unseen
    return adjust


def mono_handoff(lo_p, hi_p, out_l, out_r, mono_f, cat):
    """Children's [lo, hi] output bounds after a split at
    mid=(left+right)/2 (serial_tree_learner.cpp:892-903).  Returns
    (lo_l, hi_l, lo_r, hi_r)."""
    mid = (out_l + out_r) / 2.0
    pos = ~cat & (mono_f > 0)
    neg = ~cat & (mono_f < 0)
    lo_l = jnp.where(neg, mid, lo_p)
    hi_l = jnp.where(pos, mid, hi_p)
    lo_r = jnp.where(pos, mid, lo_p)
    hi_r = jnp.where(neg, mid, hi_p)
    return lo_l, hi_l, lo_r, hi_r


def _leaf_scan(hist, g, h, c, depth, fmeta, fmask, p: GrowerParams,
               lo=None, hi=None, gain_adjust=None):
    """best_split for one leaf + depth gating."""
    info = best_split(hist, g, h, c, fmeta, p.split, fmask,
                      mono_lo=lo, mono_hi=hi, gain_adjust=gain_adjust)
    gain = info.gain
    if p.max_depth > 0:
        gain = jnp.where(depth >= p.max_depth, NEG_INF, gain)
    return info, gain


class CommHooks(NamedTuple):
    """Collective hooks injected by the parallel tree learners
    (SURVEY.md §2.5: the TPU equivalent of the Network reducers).

    ``reduce_hist(hist, G, H, C, fmeta)`` runs after every histogram build
    (data-parallel: psum / voting: vote + masked psum); ``reduce_stats(x)``
    reduces root scalar stats; ``merge_split(info)`` merges per-shard
    SplitInfos by max gain (feature-parallel: SyncUpGlobalBestSplit,
    parallel_tree_learner.h:356-397).  All default to identity (serial).

    ``no_subtract=True`` disables the parent-minus-smaller histogram trick
    and builds BOTH children's histograms from data.  Required whenever
    ``reduce_hist`` is not a plain linear reduction over a fixed feature
    set (voting-parallel: each call's vote elects a different feature
    subset, so parent and child histograms are masked inconsistently and
    their difference is meaningless).

    ``column_block`` (feature-parallel) returns this shard's
    ``(start_col, block_cols)`` so histogram CONSTRUCTION itself only
    touches the shard's column stripe — the reference histograms only the
    rank's own features (feature_parallel_tree_learner.cpp:36-75).  The
    stripe result is scattered back into a zero [F, B, 3] tensor at its
    offset; out-of-stripe features are masked by ``shard_feature_mask``.
    ``block_cols`` must be static (the same on every shard).
    """
    reduce_hist: object = None
    reduce_stats: object = None
    merge_split: object = None
    shard_feature_mask: object = None
    no_subtract: bool = False
    column_block: object = None
    # frontier-batched grower (grower_frontier.py) variants: the same
    # reductions over a whole K-leaf batch in one collective —
    # ``reduce_hist_batch([K, G, B, 3])`` and ``merge_split_batch(infos,
    # gains)`` with a leading batch axis on every SplitInfo field
    reduce_hist_batch: object = None
    merge_split_batch: object = None
    # ``uniform_scan(blocks)`` maps a per-shard scanned-block count to a
    # shard-UNIFORM value (data-parallel: pmax).  The strict segment
    # grower's epoch-while predicates gate on the scan counter, and a
    # while_loop whose body runs collectives must have shard-uniform trip
    # counts — per-shard confinement intervals differ, so the raw count
    # does not qualify.  None (serial) = identity.
    uniform_scan: object = None


def make_grow_tree(num_bins: int, params: GrowerParams,
                   comm: CommHooks = CommHooks(), wrap=None):
    """Build the jitted tree-growing function for a static (B, params).

    The returned ``grow(bins, grad, hess, member, fmeta, feature_mask, key)``
    takes the [N, F] bin matrix, per-row gradients/hessians (already weighted
    by metadata weights / GOSS amplification), a [N] inclusion weight vector
    (bagging mask), per-feature metadata arrays, a [F] per-tree feature mask,
    and a PRNG key; it returns ``(TreeArrays, leaf_id[N])`` where leaf ids
    follow LightGBM leaf numbering so ``leaf_value[leaf_id]`` is this tree's
    per-row raw prediction.
    """
    p = params
    L = p.num_leaves
    B = num_bins
    sp = p.split

    def hist_of(bins, grad, hess, member, G, H, C, fmeta):
        hist_bins = bins
        start = None
        if comm.column_block is not None:
            # feature-parallel: construct only this shard's column stripe
            start, ncols = comm.column_block(bins)
            if p.feature_major:
                hist_bins = lax.dynamic_slice_in_dim(bins, start, ncols,
                                                     axis=0)
            else:
                hist_bins = lax.dynamic_slice_in_dim(bins, start, ncols,
                                                     axis=1)
        if p.feature_major:
            from ..ops.pallas_histogram import leaf_histogram_pallas
            out = leaf_histogram_pallas(hist_bins, grad, hess, member, B,
                                        p.row_chunk, packed4=p.packed4)
            if p.num_columns:
                out = out[: p.num_columns]
        else:
            w = jnp.stack([grad * member, hess * member, member])
            out = histogram_chunked(hist_bins, w, B, p.row_chunk)
        if start is not None:
            ncols_total = bins.shape[0] if p.feature_major else bins.shape[1]
            full = jnp.zeros((ncols_total,) + out.shape[1:], out.dtype)
            out = lax.dynamic_update_slice_in_dim(full, out, start, axis=0)
        if comm.reduce_hist is not None:
            out = comm.reduce_hist(out, G, H, C, fmeta)
        return out

    def scan_leaf(st: _GrowState, leaf_idx, hist, g, h, c, depth, fmeta,
                  fmask):
        lo = hi = None
        if p.use_monotone:
            lo = st.leaf_mono_lo[leaf_idx]
            hi = st.leaf_mono_hi[leaf_idx]
        adjust = _cegb_gain_adjust(st, leaf_idx, c, st.leaf_id == leaf_idx,
                                   fmeta, p)
        # EFB: group-space histogram -> per-feature view (identity when
        # the dataset is unbundled)
        hist = expand_group_hist(hist, fmeta, g, h, c)
        info, gain = _leaf_scan(hist, g, h, c, depth, fmeta, fmask, p,
                                lo=lo, hi=hi, gain_adjust=adjust)
        if comm.merge_split is not None:
            info, gain = comm.merge_split(info, gain)
        f32 = jnp.stack([gain, info.left_g, info.left_h, info.left_c,
                         info.left_out, info.right_out]).astype(jnp.float32)
        i32 = jnp.stack([info.feature, info.threshold,
                         info.default_left.astype(jnp.int32),
                         info.is_cat.astype(jnp.int32)])
        return st._replace(
            best_f32=st.best_f32.at[leaf_idx].set(f32),
            best_i32=st.best_i32.at[leaf_idx].set(i32),
            best_cat_bitset=st.best_cat_bitset.at[leaf_idx].set(info.cat_bitset),
        )

    def grow(bins, grad, hess, member, fmeta: FeatureMeta, feature_mask, key):
        # G = physical bin-matrix columns (EFB groups); F = logical
        # features the scans see.  Equal when unbundled.
        if p.feature_major:
            G_cols, n = bins.shape
        else:
            n, G_cols = bins.shape
        F = fmeta.num_bin.shape[0]
        if comm.shard_feature_mask is not None:
            feature_mask = comm.shard_feature_mask(feature_mask)

        def do_split(st: _GrowState, step, forced=None):
            new_leaf = st.num_leaves
            node = st.num_leaves - 1

            if forced is None:
                leaf = jnp.argmax(st.best_f32[:, 0]).astype(jnp.int32)
                bf = st.best_f32[leaf]
                bi = st.best_i32[leaf]
                f = bi[0]
                t = bi[1]
                dl = bi[2].astype(bool)
                cat = bi[3].astype(bool)
                bitset = st.best_cat_bitset[leaf]
                Gl, Hl, Cl = bf[1], bf[2], bf[3]
                Gp, Hp, Cp = st.leaf_g[leaf], st.leaf_h[leaf], st.leaf_c[leaf]
                Gr, Hr, Cr = Gp - Gl, Hp - Hl, Cp - Cl
                out_l = bf[4]
                out_r = bf[5]
                gain = bf[0]
            else:
                # forced numerical split (ForceSplits,
                # serial_tree_learner.cpp:642): stats from the leaf's
                # retained histogram at the given threshold bin
                leaf = jnp.int32(forced[0])
                f = jnp.int32(forced[1])
                t = jnp.int32(forced[2])
                dl = jnp.asarray(False)
                cat = jnp.asarray(False)
                bitset = jnp.zeros(8, dtype=jnp.uint32)
                hist_row = expand_group_hist(
                    st.leaf_hist[forced[0]], fmeta, st.leaf_g[leaf],
                    st.leaf_h[leaf], st.leaf_c[leaf])[forced[1]]
                cum = jnp.cumsum(hist_row, axis=0)
                Gl, Hl, Cl = cum[forced[2], 0], cum[forced[2], 1], \
                    cum[forced[2], 2]
                # keep stats consistent with routed_left(dl=False): zero-
                # missing default-bin rows route RIGHT, so drop them from
                # the left sums when the default bin falls under the
                # threshold
                db = fmeta.default_bin[forced[1]]
                drop = ((fmeta.missing_type[forced[1]] == MISSING_ZERO)
                        & (db <= t))
                dbh = hist_row[db]
                Gl = jnp.where(drop, Gl - dbh[0], Gl)
                Hl = jnp.where(drop, Hl - dbh[1], Hl)
                Cl = jnp.where(drop, Cl - dbh[2], Cl)
                Gp, Hp, Cp = st.leaf_g[leaf], st.leaf_h[leaf], st.leaf_c[leaf]
                Gr, Hr, Cr = Gp - Gl, Hp - Hl, Cp - Cl
                lo_f, hi_f = -jnp.inf, jnp.inf
                if p.use_monotone:
                    lo_f = st.leaf_mono_lo[leaf]
                    hi_f = st.leaf_mono_hi[leaf]
                out_l = jnp.clip(leaf_output(Gl, Hl, sp.lambda_l1,
                                             sp.lambda_l2,
                                             sp.max_delta_step), lo_f, hi_f)
                out_r = jnp.clip(leaf_output(Gr, Hr, sp.lambda_l1,
                                             sp.lambda_l2,
                                             sp.max_delta_step), lo_f, hi_f)
                gain = (leaf_gain(Gl, Hl, sp.lambda_l1, sp.lambda_l2,
                                  sp.max_delta_step)
                        + leaf_gain(Gr, Hr, sp.lambda_l1, sp.lambda_l2,
                                    sp.max_delta_step)
                        - leaf_gain(Gp, Hp, sp.lambda_l1, sp.lambda_l2,
                                    sp.max_delta_step))

            col = f if fmeta.feat_group is None else fmeta.feat_group[f]
            if p.feature_major:
                # contiguous [1, N] stream — far cheaper than the strided
                # row-major column gather
                if p.packed4:
                    from ..ops.pallas_histogram import slice_packed_column
                    fcol = slice_packed_column(bins, col)
                else:
                    fcol = lax.dynamic_slice_in_dim(bins, col, 1,
                                                    axis=0)[0, :]
            else:
                fcol = lax.dynamic_slice_in_dim(bins, col, 1, axis=1)[:, 0]
            fcol = reconstruct_feature_column(fcol, f, fmeta)
            go_left = routed_left(fcol, t, dl, cat, bitset,
                                  fmeta.missing_type[f], fmeta.default_bin[f],
                                  fmeta.num_bin[f])
            in_leaf = st.leaf_id == leaf
            leaf_id = jnp.where(in_leaf & ~go_left, new_leaf, st.leaf_id)

            # monotone constraint handoff (serial_tree_learner.cpp:892-903)
            if p.use_monotone:
                lo_l, hi_l, lo_r, hi_r = mono_handoff(
                    st.leaf_mono_lo[leaf], st.leaf_mono_hi[leaf],
                    out_l, out_r, fmeta.monotone[f], cat)
                st = st._replace(
                    leaf_mono_lo=st.leaf_mono_lo
                    .at[leaf].set(lo_l).at[new_leaf].set(lo_r),
                    leaf_mono_hi=st.leaf_mono_hi
                    .at[leaf].set(hi_l).at[new_leaf].set(hi_r),
                )
            if p.use_cegb_coupled:
                st = st._replace(feat_used=st.feat_used.at[f].set(1.0))
            if p.use_cegb_lazy:
                st = st._replace(seen=st.seen.at[f].set(
                    jnp.maximum(st.seen[f],
                                in_leaf.astype(st.seen.dtype))))

            if comm.no_subtract:
                mem_l = (leaf_id == leaf).astype(grad.dtype) * member
                mem_r = (leaf_id == new_leaf).astype(grad.dtype) * member
                hist_left = hist_of(bins, grad, hess, mem_l, Gl, Hl, Cl,
                                    fmeta)
                hist_right = hist_of(bins, grad, hess, mem_r, Gr, Hr, Cr,
                                     fmeta)
            else:
                smaller_is_left = Cl <= Cr
                smaller = jnp.where(smaller_is_left, leaf, new_leaf)
                mem_small = (leaf_id == smaller).astype(grad.dtype) * member
                Gs = jnp.where(smaller_is_left, Gl, Gr)
                Hs = jnp.where(smaller_is_left, Hl, Hr)
                Cs = jnp.where(smaller_is_left, Cl, Cr)
                hist_small = hist_of(bins, grad, hess, mem_small, Gs, Hs, Cs,
                                     fmeta)
                hist_parent = st.leaf_hist[leaf]
                hist_large = hist_parent - hist_small
                hist_left = jnp.where(smaller_is_left, hist_small, hist_large)
                hist_right = jnp.where(smaller_is_left, hist_large,
                                       hist_small)
            leaf_hist = (st.leaf_hist.at[leaf].set(hist_left)
                         .at[new_leaf].set(hist_right))

            depth_child = st.tree.leaf_depth[leaf] + 1
            tree = st.tree
            parent = tree.leaf_parent[leaf]
            # re-point the parent's child slot from ~leaf to the new node
            # (Tree::Split's parent fixup, tree.h:411-419)
            pl = jnp.where((parent >= 0)
                           & (tree.left_child[jnp.maximum(parent, 0)] == ~leaf),
                           node, tree.left_child[jnp.maximum(parent, 0)])
            pr = jnp.where((parent >= 0)
                           & (tree.right_child[jnp.maximum(parent, 0)] == ~leaf),
                           node, tree.right_child[jnp.maximum(parent, 0)])
            left_child = tree.left_child.at[jnp.maximum(parent, 0)].set(pl)
            right_child = tree.right_child.at[jnp.maximum(parent, 0)].set(pr)
            left_child = left_child.at[node].set(~leaf)
            right_child = right_child.at[node].set(~new_leaf)

            tree = tree._replace(
                num_leaves=st.num_leaves + 1,
                split_feature=tree.split_feature.at[node].set(f),
                threshold_bin=tree.threshold_bin.at[node].set(t),
                default_left=tree.default_left.at[node].set(dl),
                is_cat=tree.is_cat.at[node].set(cat),
                cat_bitset=tree.cat_bitset.at[node].set(bitset),
                left_child=left_child,
                right_child=right_child,
                split_gain=tree.split_gain.at[node].set(gain),
                internal_value=tree.internal_value.at[node].set(
                    tree.leaf_value[leaf]),
                internal_weight=tree.internal_weight.at[node].set(Hp),
                internal_count=tree.internal_count.at[node].set(Cp),
                leaf_value=(tree.leaf_value.at[leaf].set(out_l)
                            .at[new_leaf].set(out_r)),
                leaf_weight=(tree.leaf_weight.at[leaf].set(Hl)
                             .at[new_leaf].set(Hr)),
                leaf_count=(tree.leaf_count.at[leaf].set(Cl)
                            .at[new_leaf].set(Cr)),
                leaf_parent=(tree.leaf_parent.at[leaf].set(node)
                             .at[new_leaf].set(node)),
                leaf_depth=(tree.leaf_depth.at[leaf].set(depth_child)
                            .at[new_leaf].set(depth_child)),
            )

            st = st._replace(
                leaf_id=leaf_id,
                num_leaves=st.num_leaves + 1,
                leaf_hist=leaf_hist,
                leaf_g=st.leaf_g.at[leaf].set(Gl).at[new_leaf].set(Gr),
                leaf_h=st.leaf_h.at[leaf].set(Hl).at[new_leaf].set(Hr),
                leaf_c=st.leaf_c.at[leaf].set(Cl).at[new_leaf].set(Cr),
                tree=tree,
            )
            fmask_l = _node_feature_mask(feature_mask, key, 2 * step, p)
            fmask_r = _node_feature_mask(feature_mask, key, 2 * step + 1, p)
            st = scan_leaf(st, leaf, hist_left, Gl, Hl, Cl, depth_child,
                           fmeta, fmask_l)
            st = scan_leaf(st, new_leaf, hist_right, Gr, Hr, Cr, depth_child,
                           fmeta, fmask_r)
            return st

        def body(step, st: _GrowState):
            can_split = jnp.max(st.best_f32[:, 0]) > 0.0
            return lax.cond(can_split,
                            lambda s: do_split(s, step),
                            lambda s: s, st)

        # ---- init root ----
        G0 = jnp.sum(grad * member)
        H0 = jnp.sum(hess * member)
        C0 = jnp.sum(member)
        if comm.reduce_stats is not None:
            # allreduce of the root (cnt, sum_g, sum_h) tuple
            # (data_parallel_tree_learner.cpp:311-357)
            G0, H0, C0 = (comm.reduce_stats(G0), comm.reduce_stats(H0),
                          comm.reduce_stats(C0))
        root_hist = hist_of(bins, grad, hess, member, G0, H0, C0, fmeta)
        neg = jnp.full(L, NEG_INF, dtype=jnp.float32)
        zeros_l = jnp.zeros(L, dtype=jnp.float32)
        tree0 = TreeArrays(
            num_leaves=jnp.int32(1),
            split_feature=jnp.zeros(L - 1, dtype=jnp.int32),
            threshold_bin=jnp.zeros(L - 1, dtype=jnp.int32),
            default_left=jnp.zeros(L - 1, dtype=bool),
            is_cat=jnp.zeros(L - 1, dtype=bool),
            cat_bitset=jnp.zeros((L - 1, 8), dtype=jnp.uint32),
            left_child=jnp.full(L - 1, -1, dtype=jnp.int32),
            right_child=jnp.full(L - 1, -1, dtype=jnp.int32),
            split_gain=jnp.zeros(L - 1, dtype=jnp.float32),
            internal_value=jnp.zeros(L - 1, dtype=jnp.float32),
            internal_weight=jnp.zeros(L - 1, dtype=jnp.float32),
            internal_count=jnp.zeros(L - 1, dtype=jnp.float32),
            leaf_value=zeros_l,
            leaf_weight=zeros_l.at[0].set(H0),
            leaf_count=zeros_l.at[0].set(C0),
            leaf_parent=jnp.full(L, -1, dtype=jnp.int32),
            leaf_depth=jnp.zeros(L, dtype=jnp.int32),
        )
        used0 = (fmeta.cegb_used0 if (p.use_cegb_coupled
                                      and fmeta.cegb_used0 is not None)
                 else jnp.zeros(F, dtype=jnp.float32))
        st = _GrowState(
            leaf_id=jnp.zeros(n, dtype=jnp.int32),
            num_leaves=jnp.int32(1),
            leaf_hist=jnp.zeros((L,) + root_hist.shape, dtype=jnp.float32)
                         .at[0].set(root_hist),
            leaf_g=zeros_l.at[0].set(G0),
            leaf_h=zeros_l.at[0].set(H0),
            leaf_c=zeros_l.at[0].set(C0),
            leaf_mono_lo=jnp.full(L, -jnp.inf, dtype=jnp.float32),
            leaf_mono_hi=jnp.full(L, jnp.inf, dtype=jnp.float32),
            feat_used=used0,
            seen=jnp.zeros((F, n) if p.use_cegb_lazy else (1, 1),
                           dtype=jnp.int8),
            best_f32=jnp.zeros((L, 6), dtype=jnp.float32)
                        .at[:, 0].set(neg),
            best_i32=jnp.zeros((L, 4), dtype=jnp.int32)
                        .at[:, 0].set(-1),
            best_cat_bitset=jnp.zeros((L, 8), dtype=jnp.uint32),
            tree=tree0,
        )
        fmask_root = _node_feature_mask(feature_mask, key, 2 * L, p)
        st = scan_leaf(st, 0, root_hist, G0, H0, C0, jnp.int32(0), fmeta,
                       fmask_root)
        # forced splits first (static plan, unrolled), then best-gain growth
        for s, fp in enumerate(p.forced_plan[: L - 1]):
            st = do_split(st, s, forced=fp)
        st = lax.fori_loop(min(len(p.forced_plan), L - 1), L - 1, body, st)
        return st.tree, st.leaf_id

    if wrap is not None:
        return wrap(grow)
    from ..utils.jitcost import cost_jit
    return cost_jit("grow/fused", jax.jit(grow))
