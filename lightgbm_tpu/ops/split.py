"""Best-split search over histograms, vectorized across (feature, threshold).

Re-expresses the reference's sequential two-direction scans
(FeatureHistogram::FindBestThresholdSequence,
src/treelearner/feature_histogram.hpp:508-650) as cumulative sums over the
bin axis with validity masks, so every (feature, threshold, direction)
candidate is evaluated in parallel on the VPU and the winner picked by one
argmax.  Gain math matches GetSplitGains / CalculateSplittedLeafOutput /
GetLeafSplitGainGivenOutput (feature_histogram.hpp:451-506): L1 soft
thresholding, L2, max_delta_step clamp, monotone-direction rejection.

Missing-value semantics (feature_histogram.hpp:91-116):
  * MissingType::None  — single right-to-left scan (missing impossible).
  * MissingType::Zero  — the zero bin is excluded from both running sums and
    from the candidate thresholds; its mass implicitly follows the default
    direction (default_left = True for the right-to-left scan).
  * MissingType::NaN   — the trailing NaN bin is excluded from the running
    sums; two scans try NaN-left and NaN-right.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO

K_EPSILON = 1e-15
NEG_INF = -jnp.inf


class FeatureMeta(NamedTuple):
    """Per-used-feature metadata as device arrays [F]."""
    num_bin: jax.Array       # i32
    missing_type: jax.Array  # i32 (0 none / 1 zero / 2 nan)
    default_bin: jax.Array   # i32
    is_cat: jax.Array        # bool
    monotone: jax.Array      # i32 (-1/0/+1)
    penalty: jax.Array       # f32 (feature_contri)
    # CEGB per-feature penalties (config cegb_penalty_feature_coupled /
    # _lazy, serial_tree_learner.cpp:582-618); None when CEGB unused
    cegb_coupled: jax.Array = None   # f32
    cegb_lazy: jax.Array = None      # f32
    # features already used by any split of the model so far (coupled
    # penalty waived; is_feature_used_in_split_, serial_tree_learner.h:169)
    cegb_used0: jax.Array = None     # f32 0/1
    # EFB bundling (core/bundle.py): physical bin-matrix column and bin
    # offset of each logical feature, plus the static [F, Bf] gather map
    # from the flattened [G*Bg] group histogram.  All None when the dataset
    # is unbundled (column == feature).
    feat_group: jax.Array = None     # i32 [F]
    feat_offset: jax.Array = None    # i32 [F]
    gather_idx: jax.Array = None     # i32 [F, Bf]; -1 = empty slot


class SplitParams(NamedTuple):
    """Static split hyper-parameters (python floats -> folded into jit)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    max_delta_step: float = 0.0
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    # static: dataset has categorical features at all.  False lets jit drop
    # the categorical candidate scans (incl. a per-call [F, B] argsort) from
    # the traced program — a large per-split saving on numerical datasets.
    has_cat: bool = True


class SplitInfo(NamedTuple):
    """Best split of one leaf — all scalars (reference SplitInfo,
    src/treelearner/split_info.hpp:22)."""
    gain: jax.Array
    feature: jax.Array        # i32 index into used features; -1 = no split
    threshold: jax.Array      # i32 bin threshold (numerical) or category bin set id
    default_left: jax.Array   # bool
    is_cat: jax.Array         # bool
    cat_bitset: jax.Array     # u32[8] bitset of left-going bins (categorical)
    left_g: jax.Array
    left_h: jax.Array
    left_c: jax.Array
    right_g: jax.Array
    right_h: jax.Array
    right_c: jax.Array
    left_out: jax.Array
    right_out: jax.Array


def expand_group_hist(hist, fmeta: FeatureMeta, parent_g, parent_h,
                      parent_c):
    """[G, Bg, 3] group histogram -> [F, Bf, 3] per-feature histogram.

    Identity when the dataset is unbundled.  For bundled features the
    stored slots are gathered out of the group column and the default-bin
    slot — which bundling never stores (core/bundle.py) — is reconstructed
    as ``leaf_total - sum(stored slots)``, the reference's
    Dataset::FixHistogram (src/io/dataset.cpp:948-967).  For unbundled
    features the same fix is a numerical no-op, so one uniform path
    serves both.
    """
    if fmeta.gather_idx is None:
        return hist
    gi = fmeta.gather_idx                                     # [F, Bf]
    flat = hist.reshape(-1, hist.shape[-1])                   # [G*Bg, 3]
    fh = flat[jnp.clip(gi, 0)] * (gi >= 0)[..., None]         # [F, Bf, 3]
    total = jnp.stack([parent_g, parent_h, parent_c]).astype(fh.dtype)
    Bf = fh.shape[1]
    db_onehot = (jnp.arange(Bf, dtype=jnp.int32)[None, :]
                 == fmeta.default_bin[:, None])               # [F, Bf]
    stored = jnp.sum(fh * (~db_onehot)[..., None], axis=1)    # [F, 3]
    fix = total[None, :] - stored                             # [F, 3]
    return jnp.where(db_onehot[..., None], fix[:, None, :], fh)


def reconstruct_feature_column(gcol, f, fmeta: FeatureMeta):
    """Per-row bin of logical feature ``f`` from its group's raw column
    (inverse of core/bundle.quantize_bundled for one feature)."""
    gcol = gcol.astype(jnp.int32)
    if fmeta.feat_group is None:
        return gcol
    off = fmeta.feat_offset[f]
    nb = fmeta.num_bin[f]
    in_range = (gcol >= off) & (gcol < off + nb)
    return jnp.where(in_range, gcol - off, fmeta.default_bin[f])


def _bit_test(bitset_row: jax.Array, idx: jax.Array) -> jax.Array:
    """bitset_row u32[8], idx i32 -> bool."""
    word = bitset_row[idx // 32]
    return ((word >> (idx % 32).astype(jnp.uint32)) & 1).astype(bool)


def routed_left(fcol, threshold, default_left, is_cat, cat_bitset,
                missing_type, default_bin, num_bin):
    """Which side each row goes (numerical <=threshold with missing routing,
    categorical bitset membership)."""
    fcol = fcol.astype(jnp.int32)
    is_missing = (((missing_type == MISSING_ZERO) & (fcol == default_bin))
                  | ((missing_type == MISSING_NAN) & (fcol == num_bin - 1)))
    num_left = jnp.where(is_missing, default_left, fcol <= threshold)
    cat_left = _bit_test(cat_bitset, jnp.clip(fcol, 0, 255))
    return jnp.where(is_cat, cat_left, num_left)


def threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(G, H, l1, l2, max_delta_step):
    """-ThresholdL1(G)/(H+l2), clamped to max_delta_step
    (CalculateSplittedLeafOutput, feature_histogram.hpp:453-460)."""
    out = -threshold_l1(G, l1) / (H + l2 + K_EPSILON)
    if max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def leaf_gain_given_output(G, H, l1, l2, out):
    sg = threshold_l1(G, l1)
    return -(2.0 * sg * out + (H + l2) * out * out)


def leaf_gain(G, H, l1, l2, max_delta_step):
    return leaf_gain_given_output(G, H, l1, l2,
                                  leaf_output(G, H, l1, l2, max_delta_step))


def _split_gain(Gl, Hl, Gr, Hr, p: SplitParams, mono, lo, hi,
                extra_l2: float = 0.0):
    l2 = p.lambda_l2 + extra_l2
    out_l = jnp.clip(leaf_output(Gl, Hl, p.lambda_l1, l2, p.max_delta_step), lo, hi)
    out_r = jnp.clip(leaf_output(Gr, Hr, p.lambda_l1, l2, p.max_delta_step), lo, hi)
    gain = (leaf_gain_given_output(Gl, Hl, p.lambda_l1, l2, out_l)
            + leaf_gain_given_output(Gr, Hr, p.lambda_l1, l2, out_r))
    mono_bad = ((mono > 0) & (out_l > out_r)) | ((mono < 0) & (out_l < out_r))
    return jnp.where(mono_bad, 0.0, gain)


def _numerical_candidates(hist, parent, fmeta: FeatureMeta, p: SplitParams,
                          lo, hi):
    """Gains for every (feature, threshold, direction) numerical candidate.

    Returns (gain [F, T, 2], left [F, T, 2, 3]) with T = B-1 thresholds;
    direction 0 = missing/default LEFT (the reference's dir=-1 scan),
    direction 1 = missing RIGHT (dir=+1).
    """
    F, B, _ = hist.shape
    b_idx = jnp.arange(B, dtype=jnp.int32)[None, :]              # [1, B]
    nb = fmeta.num_bin[:, None]
    mt = fmeta.missing_type[:, None]
    # the reference only applies missing-direction handling when num_bin > 2;
    # 2-bin features fall back to one plain scan (feature_histogram.hpp:96-110)
    use_missing = (mt != MISSING_NONE) & (nb > 2)
    nan_bin = jnp.where(mt == MISSING_NAN, nb - 1, -1)
    zero_skip = jnp.where(mt == MISSING_ZERO, fmeta.default_bin[:, None], -1)
    in_range = b_idx < nb
    excluded = ((b_idx == nan_bin) | (b_idx == zero_skip)) & use_missing
    eff = hist * (in_range & ~excluded)[:, :, None].astype(hist.dtype)
    cum = jnp.cumsum(eff, axis=1)                                 # [F, B, 3]
    total_eff = cum[:, -1:, :]
    cum_t = cum[:, :-1, :]                                        # [F, T, 3]

    parent = parent[None, None, :]                                # [1, 1, 3]
    # dir 0 (missing left): right side accumulated from the top, missing mass
    # falls to the left as parent - right.
    right0 = total_eff - cum_t
    left0 = parent - right0
    # dir 1 (missing right): left side accumulated from the bottom.
    left1 = cum_t
    right1 = parent - left1

    left = jnp.stack([left0, left1], axis=2)                      # [F, T, 2, 3]
    right = jnp.stack([right0, right1], axis=2)

    Gl, Hl, Cl = left[..., 0], left[..., 1] + K_EPSILON, left[..., 2]
    Gr, Hr, Cr = right[..., 0], right[..., 1] + K_EPSILON, right[..., 2]
    mono = fmeta.monotone[:, None, None]
    gain = _split_gain(Gl, Hl, Gr, Hr, p, mono, lo, hi)

    t_idx = jnp.arange(B - 1, dtype=jnp.int32)[None, :, None]     # [1, T, 1]
    nb3 = nb[:, :, None]
    mt3 = mt[:, :, None]
    um3 = use_missing[:, :, None]
    dir_idx = jnp.arange(2, dtype=jnp.int32)[None, None, :]
    valid = t_idx < nb3 - 1
    # NaN bin cannot be a left-inclusive threshold when NaN defaults left
    valid &= ~(um3 & (mt3 == MISSING_NAN) & (dir_idx == 0)
               & (t_idx >= nb3 - 2))
    # zero-type: the skipped zero bin is not a candidate threshold
    valid &= ~(um3 & (mt3 == MISSING_ZERO)
               & (t_idx == zero_skip[:, :, None]))
    # second direction only scanned for missing-capable features with >2 bins
    valid &= ~((dir_idx == 1) & ~um3)
    valid &= ~fmeta.is_cat[:, None, None]
    valid &= (Cl >= p.min_data_in_leaf) & (Cr >= p.min_data_in_leaf)
    valid &= (Hl >= p.min_sum_hessian_in_leaf) & (Hr >= p.min_sum_hessian_in_leaf)

    gain = jnp.where(valid, gain, NEG_INF)
    return gain, left


def _cat_used_bin_mask(hist, fmeta: FeatureMeta):
    """Bins a categorical scan may use: in range, and excluding the trailing
    NaN bin unless the feature is fully categorical
    (used_bin = num_bin - 1 + is_full_categorical,
    feature_histogram.hpp:130-131)."""
    B = hist.shape[1]
    b_idx = jnp.arange(B, dtype=jnp.int32)[None, :]
    nb = fmeta.num_bin[:, None]
    used = jnp.where(fmeta.missing_type[:, None] == MISSING_NAN, nb - 1, nb)
    return b_idx < used


def _categorical_onehot_candidates(hist, parent, fmeta: FeatureMeta,
                                   p: SplitParams, lo, hi):
    """One-hot categorical candidates: bin b alone goes left
    (FindBestThresholdCategorical one-hot branch, feature_histogram.hpp:139-170;
    note the one-hot branch uses plain lambda_l2, not cat_l2)."""
    F, B, _ = hist.shape
    left = hist                                                   # [F, B, 3]
    right = parent[None, None, :] - left
    Gl, Hl, Cl = left[..., 0], left[..., 1] + K_EPSILON, left[..., 2]
    Gr, Hr, Cr = right[..., 0], right[..., 1] + K_EPSILON, right[..., 2]
    gain = _split_gain(Gr, Hr, Gl, Hl, p, 0, lo, hi)

    valid = fmeta.is_cat[:, None] & _cat_used_bin_mask(hist, fmeta)
    valid &= (Cl >= p.min_data_in_leaf) & (Cr >= p.min_data_in_leaf)
    valid &= (Hl >= p.min_sum_hessian_in_leaf) & (Hr >= p.min_sum_hessian_in_leaf)
    gain = jnp.where(valid, gain, NEG_INF)
    return gain, left


def _categorical_sorted_candidates(hist, parent, fmeta: FeatureMeta,
                                   p: SplitParams, lo, hi):
    """Sorted-subset categorical scan: order bins by grad/hess ratio, take a
    prefix or suffix of the order as the left set
    (feature_histogram.hpp:118-300: sort by sum_gradients/(sum_hessians +
    cat_smooth), scan both directions up to max_cat_threshold, cat_l2).

    Returns (gain [F, B, 2], left [F, B, 2, 3], order [F, B]) where candidate
    (f, k, d) means: order positions <= k go LEFT (d=0), or order positions
    >= k go LEFT (d=1).
    """
    F, B, _ = hist.shape
    b_idx = jnp.arange(B, dtype=jnp.int32)[None, :]
    in_range = _cat_used_bin_mask(hist, fmeta)
    cnt = hist[..., 2]
    # only bins with cnt >= cat_smooth enter the order
    # (feature_histogram.hpp:172-175); excluded bins sort to the end with 0
    # contribution
    usable = in_range & (cnt >= p.cat_smooth)
    ratio = hist[..., 0] / (hist[..., 1] + p.cat_smooth)
    ratio = jnp.where(usable, ratio, jnp.inf)
    order = jnp.argsort(ratio, axis=1).astype(jnp.int32)          # [F, B]
    sorted_hist = jnp.take_along_axis(hist, order[:, :, None], axis=1)
    sorted_valid = jnp.take_along_axis(usable, order, axis=1)
    sorted_hist = sorted_hist * sorted_valid[:, :, None]

    pre = jnp.cumsum(sorted_hist, axis=1)                         # prefix sums
    total_eff = pre[:, -1:, :]
    suf = total_eff - pre + sorted_hist                           # suffix sums
    left = jnp.stack([pre, suf], axis=2)                          # [F, B, 2, 3]
    right = parent[None, None, None, :] - left

    Gl, Hl, Cl = left[..., 0], left[..., 1] + K_EPSILON, left[..., 2]
    Gr, Hr, Cr = right[..., 0], right[..., 1] + K_EPSILON, right[..., 2]
    # categorical splits ignore monotone constraints (GetSplitGains called
    # with monotone_type=0, feature_histogram.hpp:226)
    gain = _split_gain(Gl, Hl, Gr, Hr, p, 0, lo, hi, extra_l2=p.cat_l2)

    num_valid = sorted_valid.sum(axis=1).astype(jnp.int32)[:, None, None]
    k_idx = b_idx[:, :, None]
    left_size = jnp.where(jnp.arange(2)[None, None, :] == 0,
                          k_idx + 1, num_valid - k_idx)
    valid = fmeta.is_cat[:, None, None] & sorted_valid[:, :, None]
    # the moved set is capped at min(max_cat_threshold, (used_bin+1)/2)
    # categories (feature_histogram.hpp:192: max_num_cat).  Taking EVERY
    # usable category left is legal — rows in unlisted bins (the NaN
    # category, zero-count bins) still route right, so validity is
    # gated on DATA counts like the reference's scan, not on a strict
    # category subset (its test_categorical_handle_na isolates {0} left
    # with the NaN rows falling right by default).
    max_num_cat = jnp.minimum(int(p.max_cat_threshold), (num_valid + 1) // 2)
    valid &= (left_size >= 1) & (Cl > 0) & (Cr > 0)
    valid &= left_size <= max_num_cat
    valid &= (Cl >= p.min_data_in_leaf) & (Cr >= p.min_data_in_leaf)
    # the right (unmoved) side must keep at least min_data_per_group rows
    # (feature_histogram.hpp:216); the reference's cnt_cur_group run-length
    # gate thins candidates WITHIN the scan — omitted here (vectorized scan
    # evaluates each prefix independently), which can only consider more
    # candidates, never fewer.
    valid &= Cr >= float(p.min_data_per_group)
    valid &= (Hl >= p.min_sum_hessian_in_leaf) & (Hr >= p.min_sum_hessian_in_leaf)
    gain = jnp.where(valid, gain, NEG_INF)
    return gain, left, order


def build_cat_bitset(selected_bins_mask: jax.Array) -> jax.Array:
    """[B] bool -> u32[8] bitset (supports max_bin <= 256)."""
    B = selected_bins_mask.shape[0]
    pad = (-B) % 32
    m = jnp.pad(selected_bins_mask.astype(jnp.uint32), (0, pad)).reshape(-1, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, :]
    words = (m * weights).sum(axis=1).astype(jnp.uint32)
    out = jnp.zeros(8, dtype=jnp.uint32)
    return out.at[: words.shape[0]].set(words[:8])


def _all_candidates(hist, parent_g, parent_h, parent_c, fmeta: FeatureMeta,
                    p: SplitParams, lo, hi):
    """Shared candidate evaluation: per-feature family winners + gains."""
    F = hist.shape[0]
    parent = jnp.stack([parent_g, parent_h, parent_c]).astype(hist.dtype)

    gain_shift = leaf_gain(parent_g, parent_h + 2 * K_EPSILON,
                           p.lambda_l1, p.lambda_l2, p.max_delta_step)
    min_gain_shift = gain_shift + p.min_gain_to_split

    def fam_best(gain_flat):
        idx = jnp.argmax(gain_flat, axis=1)
        return idx, jnp.take_along_axis(gain_flat, idx[:, None], axis=1)[:, 0]

    num_gain, num_left = _numerical_candidates(hist, parent, fmeta, p, lo, hi)
    ni, ng = fam_best(num_gain.reshape(F, -1))

    if not p.has_cat:
        z = jnp.zeros(F, dtype=jnp.int32)
        fgain_out = jnp.where(ng > min_gain_shift,
                              (ng - min_gain_shift) * fmeta.penalty, NEG_INF)
        return dict(parent=parent, num_left=num_left, oh_left=None,
                    so_left=None, so_order=None, ni=ni, oi=z, si=z,
                    fam=z, fgain_out=fgain_out)

    oh_gain, oh_left = _categorical_onehot_candidates(hist, parent, fmeta,
                                                      p, lo, hi)
    so_gain, so_left, so_order = _categorical_sorted_candidates(
        hist, parent, fmeta, p, lo, hi)

    # categorical one-hot only for small-arity features (max_cat_to_onehot)
    use_onehot = (fmeta.num_bin[:, None] <= int(p.max_cat_to_onehot))
    oh_gain = jnp.where(use_onehot, oh_gain, NEG_INF)
    so_gain = jnp.where(use_onehot[:, :, None], NEG_INF, so_gain)

    oi, og = fam_best(oh_gain)
    si, sg = fam_best(so_gain.reshape(F, -1))

    fam_gains = jnp.stack([ng, og, sg], axis=1)                    # [F, 3]
    fam = jnp.argmax(fam_gains, axis=1)
    fgain = jnp.max(fam_gains, axis=1)
    splittable = fgain > min_gain_shift
    fgain_out = jnp.where(splittable,
                          (fgain - min_gain_shift) * fmeta.penalty, NEG_INF)
    return dict(parent=parent, num_left=num_left, oh_left=oh_left,
                so_left=so_left, so_order=so_order, ni=ni, oi=oi, si=si,
                fam=fam, fgain_out=fgain_out)


def per_feature_gains(hist: jax.Array, parent_g, parent_h, parent_c,
                      fmeta: FeatureMeta, params: SplitParams) -> jax.Array:
    """[F] best gain per feature (NEG_INF where unsplittable) — used by the
    voting-parallel learner's local vote
    (voting_parallel_tree_learner.cpp:170-201)."""
    c = _all_candidates(hist, parent_g, parent_h, parent_c, fmeta, params,
                        -jnp.inf, jnp.inf)
    return c["fgain_out"]


def best_split(hist: jax.Array, parent_g, parent_h, parent_c,
               fmeta: FeatureMeta, params: SplitParams,
               feature_mask: jax.Array, mono_lo=None, mono_hi=None,
               gain_adjust=None) -> SplitInfo:
    """Find the best split of one leaf from its [F, B, 3] histogram.

    Mirrors SerialTreeLearner::FindBestSplitsFromHistograms
    (serial_tree_learner.cpp:549-640): per-feature best threshold, then the
    per-leaf argmax over features with feature-fraction masking and penalty.
    ``gain_adjust`` is an optional [F] additive penalty subtracted from the
    per-feature gains before the argmax (CEGB, :582-618).
    """
    p = params
    F, B, _ = hist.shape
    lo = -jnp.inf if mono_lo is None else mono_lo
    hi = jnp.inf if mono_hi is None else mono_hi

    c = _all_candidates(hist, parent_g, parent_h, parent_c, fmeta, p, lo, hi)
    parent = c["parent"]
    num_left, oh_left = c["num_left"], c["oh_left"]
    so_left, so_order = c["so_left"], c["so_order"]
    ni, oi, si, fam = c["ni"], c["oi"], c["si"], c["fam"]
    fgain_out = jnp.where(feature_mask > 0, c["fgain_out"], NEG_INF)
    if gain_adjust is not None:
        fgain_out = jnp.where(fgain_out > NEG_INF, fgain_out - gain_adjust,
                              NEG_INF)

    best_f = jnp.argmax(fgain_out).astype(jnp.int32)
    best_gain = fgain_out[best_f]
    has_split = best_gain > NEG_INF

    fam_f = fam[best_f]
    T = B - 1
    # decode winner coordinates
    n_t = (ni[best_f] // 2).astype(jnp.int32)
    n_dir = (ni[best_f] % 2).astype(jnp.int32)
    left_num = num_left[best_f, n_t, n_dir]
    if p.has_cat:
        left_oh = oh_left[best_f, oi[best_f]]
        s_k = (si[best_f] // 2).astype(jnp.int32)
        s_dir = (si[best_f] % 2).astype(jnp.int32)
        left_so = so_left[best_f, s_k, s_dir]
        left_stats = jnp.where(fam_f == 0, left_num,
                               jnp.where(fam_f == 1, left_oh, left_so))
        threshold = jnp.where(
            fam_f == 0, n_t,
            jnp.where(fam_f == 1, oi[best_f], s_k)).astype(jnp.int32)
    else:
        left_stats = left_num
        threshold = n_t
    is_cat = fam_f > 0
    # default_left: numerical dir 0 = missing left; 2-bin NaN edge forces right
    dl = (fam_f == 0) & (n_dir == 0)
    nb_f = fmeta.num_bin[best_f]
    mt_f = fmeta.missing_type[best_f]
    dl = jnp.where((fam_f == 0) & (nb_f <= 2) & (mt_f == MISSING_NAN), False, dl)

    if p.has_cat:
        # categorical bitset of left-going bins
        b_idx = jnp.arange(B, dtype=jnp.int32)
        onehot_mask = b_idx == threshold
        order_f = so_order[best_f]
        pos = jnp.arange(B, dtype=jnp.int32)
        cnt_row = hist[best_f, :, 2]
        used_mask_f = _cat_used_bin_mask(hist, fmeta)[best_f]
        valid_bins = used_mask_f & (cnt_row >= p.cat_smooth)
        nvalid = valid_bins.sum().astype(jnp.int32)
        sel_sorted = jnp.where(s_dir == 0, pos <= s_k,
                               (pos >= s_k) & (pos < nvalid))
        sorted_mask = jnp.zeros(B, dtype=bool).at[order_f].set(sel_sorted)
        cat_mask = jnp.where(fam_f == 1, onehot_mask, sorted_mask & valid_bins)
        cat_bitset = build_cat_bitset(jnp.where(is_cat, cat_mask, False))
    else:
        cat_bitset = jnp.zeros(8, dtype=jnp.uint32)

    Gl, Hl, Cl = left_stats[0], left_stats[1], left_stats[2]
    Gr, Hr, Cr = parent[0] - Gl, parent[1] - Hl, parent[2] - Cl
    # cat_l2 applies only to the sorted-subset branch (fam 2); same clip
    # order as candidate scoring: max_delta_step inside, then constraints
    extra_l2 = jnp.where(fam_f == 2, p.cat_l2, 0.0)
    out_l = jnp.clip(leaf_output(Gl, Hl, p.lambda_l1,
                                 p.lambda_l2 + extra_l2, p.max_delta_step),
                     lo, hi)
    out_r = jnp.clip(leaf_output(Gr, Hr, p.lambda_l1,
                                 p.lambda_l2 + extra_l2, p.max_delta_step),
                     lo, hi)

    return SplitInfo(
        gain=jnp.where(has_split, best_gain, NEG_INF),
        feature=jnp.where(has_split, best_f, -1).astype(jnp.int32),
        threshold=threshold,
        default_left=dl,
        is_cat=is_cat,
        cat_bitset=cat_bitset,
        left_g=Gl, left_h=Hl, left_c=Cl,
        right_g=Gr, right_h=Hr, right_c=Cr,
        left_out=out_l, right_out=out_r,
    )
