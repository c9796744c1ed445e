"""GBDT: the boosting driver.

Reference: src/boosting/gbdt.{h,cpp} — Init (gbdt.cpp:49), TrainOneIter
(:450: boost-from-average -> GetGradients -> Bagging -> per-class tree train
-> RenewTreeOutput -> shrinkage -> score update -> constant-tree handling),
Bagging (:182-334), RollbackOneIter (:553), train/valid metric evaluation
(:578-660), feature importances.

TPU orchestration: the per-iteration hot path stays on device — gradients
(objective jnp fn), tree growth (fused grower), and the training-score update
(``score += leaf_value[leaf_id]`` gather).  Host work per iteration is O(1)
scalars plus optional leaf renewal / validation-set prediction.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..core.binning import MISSING_NAN, MISSING_ZERO
from ..core.dataset import TpuDataset
from ..ops.split import FeatureMeta, SplitParams
from ..utils.faults import FAULTS, InjectedFault, oom_error
from ..utils.jitcost import cost_jit
from ..utils.log import (LightGBMError, check, log_fatal, log_info,
                         log_warning)
from ..utils.phase import GLOBAL_TIMER as _PHASES
from ..utils.telemetry import HEALTH, TELEMETRY
from .grower import (GrowerParams, _pack_tree_device, fetch_tree_arrays,
                     fetch_tree_chunk, make_grow_tree, unpack_tree_buffers)
from .grower_seg import (print_seg_stats, seg_stats_columns,
                         seg_stats_enabled)
from .tree import Tree


class _PendingChunk(NamedTuple):
    """A chunk of ``length`` dispatched-but-unfetched iterations: the
    scan's stacked [T, C, len_ints]/[T, C, len_floats] device buffers,
    materialized host-side in two transfers at the chunk boundary.
    ``mvals`` is the in-scan evaluation's stacked [T, n_cols] metric
    rows (None when no eval program rides the chunk); ``wall_s`` is the
    chunk dispatch's host wall window (wall-to-ready under
    device_timing), carried into the health stream's iter records;
    ``seg_stats`` is the grower's stacked [T, C, SEG_STATS_SLOTS]
    counters (None for growers that return none)."""
    ints_all: jax.Array
    floats_all: jax.Array
    shrinkage: float
    length: int
    mvals: Optional[jax.Array] = None
    wall_s: Optional[float] = None
    seg_stats: Optional[jax.Array] = None


# batch-predict routing seams, one per static routing depth: the
# TreeStack's max_depth is the fori_loop bound and must stay a python
# int for AOT compilation, so it cannot ride through the jit arguments
_ROUTE_SEAMS: Dict[int, Any] = {}


def _route_seam(max_depth: int):
    fn = _ROUTE_SEAMS.get(max_depth)
    if fn is None:
        from .device_predict import predict_binned_leaves

        def leaves_fn(stack, bins, num_bin, default_bin):
            return predict_binned_leaves(
                stack._replace(max_depth=max_depth), bins, num_bin,
                default_bin)

        fn = cost_jit(f"predict/route[d{max_depth}]", jax.jit(leaves_fn))
        _ROUTE_SEAMS[max_depth] = fn
    return fn


def _record_seg_stats(rows: np.ndarray, trees: int,
                      block_rows: int) -> None:
    """A grower's counters, [*, SEG_STATS_SLOTS] over ``trees`` trees
    (one row a tree, or one a device and tree under the data-parallel
    wrappers), into the ``seg/*`` telemetry counters and gauges."""
    c = seg_stats_columns(rows)
    TELEMETRY.counter_add("seg/scanned_blocks", int(c.scanned_blocks.sum()))
    TELEMETRY.counter_add("seg/compactions", int(c.compactions.sum()))
    TELEMETRY.counter_add("seg/grid_steps", int(c.grid_steps.sum()))
    # what turns blocks into rows and totals into per-tree figures
    TELEMETRY.counter_add("seg/trees", int(trees))
    TELEMETRY.gauge_set("seg/block_rows", int(block_rows))
    # the budget the run compacted under (compaction_budget_blocks)
    TELEMETRY.gauge_set("seg/compact_budget_blocks",
                        int(c.compact_budget.max()))
    # the strict grower's shape facts: feature tiles a pass walks (1: the
    # table whole) and the bytes of its per-leaf histogram tables
    if c.feature_tiles.max():
        TELEMETRY.gauge_set("seg/feature_tiles", int(c.feature_tiles.max()))
        TELEMETRY.gauge_set("seg/leaf_hist_bytes",
                            1024 * int(c.leaf_hist_kib.max()))
    # the strict grower's splits and its lookahead lane sets (0 on the
    # paths that run none: a hit share of 0, not a missing one)
    if c.splits.sum():
        TELEMETRY.counter_add("seg/splits", int(c.splits.sum()))
        TELEMETRY.counter_add("seg/lookahead_hits",
                              int(c.lookahead_hits.sum()))
        TELEMETRY.counter_add("seg/lookahead_filled",
                              int(c.lookahead_filled.sum()))
        TELEMETRY.counter_add("seg/route_only_blocks",
                              int(c.route_only_blocks.sum()))


def _stack_seg_stats(stats_l):
    """One scan step's per-class grower counters as a scan output:
    ``([C, SEG_STATS_SLOTS],)``, or ``()`` where the grower returns none
    (static at trace time, so such a chunk program carries no output)."""
    return (jnp.stack([st[0] for st in stats_l]),) if stats_l[0] else ()


def _maybe_print_seg_stats(stats, block_rows: int) -> None:
    """The per-iteration paths: record and render one tree's counter
    output when LIGHTGBM_TPU_SEG_STATS asks for it (stats is () for
    growers that emit none, e.g. the fused one).  Fetching the stats
    vector blocks on the device here, so both stay gated on the env knob
    that opts into per-iteration synchronization; the chunk path records
    always, from the copy that rides with its tree buffers
    (``_entry_iter_arrays``)."""
    if stats and seg_stats_enabled():
        _record_seg_stats(np.asarray(stats[0]), 1, block_rows)
        print_seg_stats(stats[0])


def _auto_frontier_k(cfg, num_columns: int, num_bins: int) -> int:
    """Frontier batch width: explicit tpu_frontier_width wins; the auto
    width caps the batch at ~num_leaves/16 (rounded up) so small trees
    stay near strict best-first (K=16 on a 31-leaf tree is level-wise
    growth and measurably hurts fit) while 255-leaf benchmark trees get
    the full 16-leaf / 128-channel MXU tile.  Shared by the serial and
    data-parallel frontier learners so they always grow the same-width
    frontier."""
    if cfg.tpu_frontier_width > 0:
        TELEMETRY.gauge_set("grow/frontier_k", int(cfg.tpu_frontier_width))
        return cfg.tpu_frontier_width
    from ..ops.pallas_histogram import frontier_width
    k = min(frontier_width(num_columns, num_bins),
            max(1, -(-max(2, cfg.num_leaves) // 16)))
    TELEMETRY.gauge_set("grow/frontier_k", int(k))
    return k


def _round_up_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _build_forced_plan(train_set: TpuDataset, filename: str,
                       num_leaves: int) -> tuple:
    """forcedsplits_filename JSON -> static BFS plan of
    (leaf, inner_feature, threshold_bin) triples (ForceSplits,
    serial_tree_learner.cpp:642: breadth-first, left child keeps the
    parent's leaf id, right child takes the new one)."""
    import json
    from collections import deque
    with open(filename) as fh:
        root = json.load(fh)
    plan = []
    q = deque([(root, 0)])
    while q and len(plan) < num_leaves - 1:
        node, leaf = q.popleft()
        if not isinstance(node, dict) or "feature" not in node:
            continue
        real_f = int(node["feature"])
        inner = train_set.inner_feature_index(real_f)
        if inner < 0:
            log_warning(f"forced split on unused feature {real_f}; skipped")
            continue
        thr = float(node["threshold"])
        t_bin = int(np.asarray(train_set.bin_mappers[real_f].value_to_bin(
            np.asarray([thr], dtype=np.float64)))[0])
        step = len(plan)
        plan.append((int(leaf), int(inner), t_bin))
        if isinstance(node.get("left"), dict):
            q.append((node["left"], leaf))
        if isinstance(node.get("right"), dict):
            q.append((node["right"], step + 1))
    return tuple(plan)


def build_feature_meta(dataset: TpuDataset, config=None,
                       used_in_split=None) -> FeatureMeta:
    infos = dataset.feature_infos()
    F = len(infos)

    def per_feature(vals, default):
        """Real-feature-indexed config list -> [F] inner-feature array."""
        out = np.full(F, default, dtype=np.float64)
        if vals:
            for j, real in enumerate(dataset.used_feature_indices):
                if int(real) < len(vals):
                    out[j] = float(vals[int(real)])
        return jnp.asarray(out, dtype=jnp.float32)

    cegb_coupled = cegb_lazy = used0 = None
    if config is not None and (config.cegb_penalty_feature_coupled
                               or config.cegb_penalty_feature_lazy):
        cegb_coupled = per_feature(config.cegb_penalty_feature_coupled, 0.0)
        cegb_lazy = per_feature(config.cegb_penalty_feature_lazy, 0.0)
        used0 = jnp.asarray(used_in_split if used_in_split is not None
                            else np.zeros(F), dtype=jnp.float32)
    feat_group = feat_offset = gather_idx = None
    if dataset.bundle is not None:
        # static [F, Bf] gather map from the flattened [G * Bg] group
        # histogram; Bg/Bf are the pow2-padded histogram axes the grower
        # actually allocates (GBDT.reset_train_data uses the same rounding)
        Bg = _round_up_pow2(max(dataset.max_column_bin, 2))
        Bf = _round_up_pow2(max(dataset.max_num_bin, 2))
        gi = np.full((F, Bf), -1, dtype=np.int32)
        for j, info in enumerate(infos):
            gi[j, : info.num_bin] = (info.group * Bg + info.offset
                                     + np.arange(info.num_bin))
        feat_group = jnp.asarray([i.group for i in infos], dtype=jnp.int32)
        feat_offset = jnp.asarray([i.offset for i in infos],
                                  dtype=jnp.int32)
        gather_idx = jnp.asarray(gi)
    return FeatureMeta(
        num_bin=jnp.asarray([i.num_bin for i in infos], dtype=jnp.int32),
        missing_type=jnp.asarray([i.missing_type for i in infos],
                                 dtype=jnp.int32),
        default_bin=jnp.asarray([i.default_bin for i in infos],
                                dtype=jnp.int32),
        is_cat=jnp.asarray([i.is_categorical for i in infos], dtype=bool),
        monotone=jnp.asarray([i.monotone for i in infos], dtype=jnp.int32),
        penalty=jnp.asarray([i.penalty for i in infos], dtype=jnp.float32),
        cegb_coupled=cegb_coupled,
        cegb_lazy=cegb_lazy,
        cegb_used0=used0,
        feat_group=feat_group,
        feat_offset=feat_offset,
        gather_idx=gather_idx,
    )


def _add_tree_score_core(score, leaf_values, leaf_id):
    return score + leaf_values[leaf_id]


def _apply_tree_score_core(score, leaf_values, leaf_id, shrinkage):
    """Device-side score update straight from the grower's output — no host
    round-trip in the training loop (shrinkage folded in here; the stored
    model applies it at materialization)."""
    return score + shrinkage * leaf_values[leaf_id]


_add_tree_score = cost_jit("score/add", jax.jit(_add_tree_score_core))
_apply_tree_score = cost_jit("score/apply", jax.jit(_apply_tree_score_core))

# one-scalar finiteness reduce over the boosted scores (check_nonfinite
# guardrail): the device does the whole reduction, the host fetches one
# bool — run OUTSIDE any transfer guard wrapping the chunk dispatch
_all_finite = jax.jit(lambda x: jnp.isfinite(x).all())


def _grad_stats_core(grads, hesss):
    """Per-class gradient/hessian diagnostics for the health stream:
    [C, 8] f32 columns [gmin, gmax, g_l2, g_nonfinite, hmin, hmax,
    h_l2, h_nonfinite].  Pure jnp so the chunk scan body can inline it
    (one extra stacked scan output, zero extra dispatches) while the
    per-iteration paths call the jitted wrapper below — the reductions
    lower identically either way, keeping the records bit-identical at
    any chunk size (the same property the chunked trees rely on)."""
    def one(x):
        nonfinite = jnp.sum(~jnp.isfinite(x), axis=1).astype(jnp.float32)
        safe = jnp.where(jnp.isfinite(x), x, 0.0)
        return (jnp.min(safe, axis=1), jnp.max(safe, axis=1),
                jnp.sqrt(jnp.sum(safe * safe, axis=1)), nonfinite)
    with jax.named_scope("grad_stats"):
        return jnp.stack(one(grads) + one(hesss), axis=1)


_grad_stats = cost_jit("health/grad_stats", jax.jit(_grad_stats_core))


def _route_tree_rows(arrays, vbins, fmeta, depth_bound: int):
    """Per-row leaf values of one freshly-grown device tree over a
    row-major [Nv, G] binned matrix: the in-scan evaluation's valid-set
    score update (pure jnp, traced inside the chunk scan body).

    Same routing semantics as models/device_predict.route_one_tree, but
    over the grower's TreeArrays fields (pre-packing, per-feature
    missing types from fmeta instead of per-node decision bits) so the
    scan never needs host Tree objects.  Single-leaf trees start
    terminal at node -1 (= leaf ~(-1) = 0, whose value is 0), matching
    the train-score update's unconditional add."""
    sf, tb = arrays.split_feature, arrays.threshold_bin
    dl, ic, cb = arrays.default_left, arrays.is_cat, arrays.cat_bitset
    lc, rc = arrays.left_child, arrays.right_child
    n = vbins.shape[0]

    def step(_, node):
        internal = node >= 0
        safe = jnp.maximum(node, 0)
        f = sf[safe]
        col = f if fmeta.feat_group is None else fmeta.feat_group[f]
        fv = jnp.take_along_axis(
            vbins, col[:, None].astype(jnp.int32), axis=1)[:, 0] \
            .astype(jnp.int32)
        if fmeta.feat_group is not None:
            off = fmeta.feat_offset[f]
            in_range = (fv >= off) & (fv < off + fmeta.num_bin[f])
            fv = jnp.where(in_range, fv - off, fmeta.default_bin[f])
        mt = fmeta.missing_type[f]
        is_missing = (((mt == MISSING_ZERO)
                       & (fv == fmeta.default_bin[f]))
                      | ((mt == MISSING_NAN)
                         & (fv == fmeta.num_bin[f] - 1)))
        num_left = jnp.where(is_missing, dl[safe], fv <= tb[safe])
        word = cb[safe, jnp.clip(fv // 32, 0, 7)]
        cat_left = ((word >> (fv % 32).astype(jnp.uint32)) & 1) > 0
        go_left = jnp.where(ic[safe], cat_left, num_left)
        nxt = jnp.where(go_left, lc[safe], rc[safe])
        return jnp.where(internal, nxt, node)

    start = jnp.where(arrays.num_leaves <= 1,
                      jnp.full(n, -1, dtype=jnp.int32),
                      jnp.zeros(n, dtype=jnp.int32))
    # Stop at the tree's own depth: with max_depth unbounded depth_bound is
    # num_leaves, and a step past the deepest leaf moves no row yet still
    # pays its dozen [Nv] gathers (at 255 leaves and 500k held-out rows the
    # 255 fixed steps were ~10 s per tree on a v5e; the walk to the tree's
    # depth is in PERF.md section 5, `higgs63-train-eval`).
    levels, node = jax.lax.while_loop(
        lambda c: (c[0] < depth_bound) & jnp.any(c[1] >= 0),
        lambda c: (c[0] + 1, step(c[0], c[1])),
        (jnp.int32(0), start))
    return arrays.leaf_value[jnp.maximum(~node, 0)], levels


# The dense walk's room: a block of at most _WALK_BLOCK_ROWS rows, whose
# [rows, L] tables (each node's bin and the row's signs in bfloat16, the
# path agreements in float32: 8 bytes a cell) and the tree's [L - 1, L]
# path-sign matrix (its float32 build and bfloat16 copy: 6 bytes a cell)
# stay under _WALK_TEMP_BYTES.  Past that the gather walk takes the tree.
_WALK_BLOCK_ROWS = 1 << 17
_WALK_TEMP_BYTES = 1 << 29


def _dense_walk_plan(vbins, num_leaves: int) -> Optional[Tuple[int, ...]]:
    """Rows a block of the dense walk (`_dense_tree_rows`) holds over each
    valid set, or None where the gather walk (`_route_tree_rows`) takes
    ``num_leaves``-leaf trees: bins past 256 values are not all bfloat16
    integers, and past the room above the tables are too large.  Static:
    shapes and dtypes alone."""
    L = num_leaves
    plan = []
    for vb in vbins:
        if np.iinfo(vb.dtype).max > 256:
            return None
        n = max(int(vb.shape[0]), 1)
        blocks = -(-n // _WALK_BLOCK_ROWS)
        rows = -(-n // blocks)
        rows = -(-rows // 256) * 256
        if rows * L * 8 + (L - 1) * L * 6 > _WALK_TEMP_BYTES:
            return None
        plan.append(rows)
    return tuple(plan)


def _dense_tree_rows(arrays, vbins, fmeta, depth_bound: int, rows: int):
    """`_route_tree_rows` without levels: the same (per-row leaf values,
    levels), bit for bit, from every node's decision for every row at once.

    1. ``V[row, node]``, the row's bin in the node's column, is one MXU
       contraction of the bins against a one-hot [G, nodes] selection
       (bins are integers <= 255, bfloat16 integers, one product a cell:
       exact), and the node's decision follows `_route_tree_rows`
       elementwise: ``S`` = +1 left, -1 right, 0 for the slots past
       ``num_leaves - 1``.
    2. ``P[node, leaf]`` = +1 where the leaf lies under the node's left
       child, -1 under its right one, 0 elsewhere, walked up from every
       leaf's parent link.  ``S @ P`` (sums of +-1 products, exact in
       float32) reaches a leaf's path length exactly where the row agrees
       with every decision on its path: at one leaf, since any other
       leaf disagrees where the two paths part.  Leaves past
       ``num_leaves`` (no path, length 0) are masked; a single-leaf tree
       is leaf 0, as the gather walk's ``start = -1``.
    3. The levels are the deepest reached path, the gather walk's trip
       count.

    Rows go in blocks of ``rows`` (`_dense_walk_plan`), so the [rows, L]
    tables stay inside the walk's room."""
    L = arrays.leaf_value.shape[0]
    inner = L - 1
    nl = arrays.num_leaves
    node = jnp.arange(inner, dtype=jnp.int32)
    leaf = jnp.arange(L, dtype=jnp.int32)
    live = node < nl - 1
    sf = arrays.split_feature
    col = sf if fmeta.feat_group is None else fmeta.feat_group[sf]

    # every node's and leaf's parent and side (internal node i at slot i,
    # leaf l at slot inner + l), scattered from the children links
    kids = jnp.concatenate([arrays.left_child, arrays.right_child])
    slot = jnp.where(kids >= 0, kids, inner + ~kids)
    slot = jnp.where(jnp.concatenate([live, live]), slot, inner + L)
    up = jnp.full(inner + L, -1, jnp.int32).at[slot].set(
        jnp.concatenate([node, node]), mode="drop")
    side = jnp.zeros(inner + L, jnp.float32).at[slot].set(
        jnp.repeat(jnp.array([1.0, -1.0], jnp.float32), inner),
        mode="drop")

    def climb(c):
        d, cur, sgn, signs, length = c
        on = cur >= 0
        signs = signs.at[jnp.where(on, cur, inner), leaf].set(
            sgn, mode="drop")
        at = jnp.maximum(cur, 0)
        return (d + 1, jnp.where(on, up[at], -1), side[at], signs,
                length + on.astype(jnp.int32))

    _, _, _, signs, length = jax.lax.while_loop(
        lambda c: (c[0] < depth_bound) & jnp.any(c[1] >= 0), climb,
        (jnp.int32(0), up[inner + leaf], side[inner + leaf],
         jnp.zeros((inner, L), jnp.float32), jnp.zeros(L, jnp.int32)))
    signs = signs.astype(jnp.bfloat16)
    # a masked leaf's target is -1, which no agreement with a zero column
    # reaches
    target = jnp.where(leaf < nl, length, -1)

    nb = fmeta.num_bin[sf]
    db = fmeta.default_bin[sf]
    mt = fmeta.missing_type[sf]
    off = None if fmeta.feat_group is None else fmeta.feat_offset[sf]
    thr, dleft = arrays.threshold_bin, arrays.default_left
    words = arrays.cat_bitset.T
    lv = arrays.leaf_value
    n, G = vbins.shape
    sel = (jnp.arange(G, dtype=jnp.int32)[:, None]
           == col[None, :]).astype(jnp.bfloat16)

    def block(vb, with_cat):
        fv = jnp.dot(vb.astype(jnp.bfloat16), sel,
                     preferred_element_type=jnp.bfloat16).astype(jnp.int32)
        if off is not None:
            in_range = (fv >= off) & (fv < off + nb)
            fv = jnp.where(in_range, fv - off, db)
        is_missing = (((mt == MISSING_ZERO) & (fv == db))
                      | ((mt == MISSING_NAN) & (fv == nb - 1)))
        go_left = jnp.where(is_missing, dleft, fv <= thr)
        if with_cat:
            w = jnp.clip(fv >> 5, 0, 7)
            word = words[0]
            for j in range(1, 8):
                word = jnp.where(w == j, words[j], word)
            cat_left = ((word >> (fv & 31).astype(jnp.uint32)) & 1) > 0
            go_left = jnp.where(arrays.is_cat, cat_left, go_left)
        s = jnp.where(live, jnp.where(go_left, 1.0, -1.0), 0.0)
        agree = jnp.dot(s.astype(jnp.bfloat16), signs,
                        preferred_element_type=jnp.float32)
        hit = agree == target.astype(jnp.float32)
        # one hit a row: a sum from -0.0 is that leaf's value to the bit
        val = jax.lax.reduce(jnp.where(hit, lv, -0.0), np.float32(-0.0),
                             jax.lax.add, (1,))
        return val, jnp.max(jnp.where(hit, target, 0), axis=1)

    blocks = -(-n // rows)
    padded = jnp.pad(vbins, ((0, blocks * rows - n), (0, 0)))
    padded = padded.reshape(blocks, rows, G)

    def walk(with_cat):
        vals, depth = jax.lax.map(lambda vb: block(vb, with_cat), padded)
        return (vals.reshape(-1)[:n],
                jnp.max(depth.reshape(-1)[:n], initial=0))

    has_cat = jnp.any(arrays.is_cat & live)
    return jax.lax.cond(has_cat, lambda: walk(True), lambda: walk(False))


def _eval_walk(vscores, vbins, arrays, fmeta, k, shrinkage,
               depth_bound: int):
    """One freshly grown class-``k`` tree folded into every valid set's
    [C, Nv] score carry: (the new carries, the levels walked over all the
    sets).  The dense walk takes the tree where `_dense_walk_plan` admits
    it, else the gather walk; the scope is the walk's name in any device
    trace."""
    with jax.named_scope("eval_walk"):
        rows = _dense_walk_plan(vbins, arrays.leaf_value.shape[0])
        out, levels = [], jnp.int32(0)
        for i, (vs, vb) in enumerate(zip(vscores, vbins)):
            if rows is None:
                vals, walked = _route_tree_rows(arrays, vb, fmeta,
                                                depth_bound)
            else:
                vals, walked = _dense_tree_rows(arrays, vb, fmeta,
                                                depth_bound, rows[i])
            out.append(vs.at[k].add(shrinkage * vals))
            levels = levels + walked
        return out, levels


def _is_oom_error(e: BaseException) -> bool:
    """RESOURCE_EXHAUSTED-shaped device failures (real XlaRuntimeError
    allocation failures and injected chunk/oom faults) that the chunked
    loop may retry at a smaller chunk size."""
    msg = str(e)
    if ("RESOURCE_EXHAUSTED" not in msg
            and "out of memory" not in msg.lower()):
        return False
    return (isinstance(e, InjectedFault)
            or type(e).__name__ in ("XlaRuntimeError", "InternalError"))


# proactive-admission headroom: start in the host-spill tier when the
# estimated working set exceeds this fraction of the reported HBM
_ADMIT_FRACTION = 0.9


def working_set_bytes(num_data: int, num_columns: int, *,
                      num_tree_per_iteration: int = 1,
                      layout: Tuple[str, int, bool] = ("rows", 0, False),
                      itemsize: int = 1) -> int:
    """The working-set arithmetic shared by the internal pre-dispatch
    admission check (``GBDT._estimate_working_set``) and the public
    :func:`estimate_working_set`: the bin matrix in its device layout
    (``("T", row_multiple, packed4)`` pads rows to whole blocks and
    packs two sub-16-bin columns per byte; ``("rows", 0, False)`` is the
    plain row-major matrix), the f32 boosting state (scores, grads,
    hessians per class, bag weights, leaf ids), plus the largest CostJit
    ``memory_analysis`` working set already on record."""
    num_data, f = int(num_data), int(num_columns)
    kind, rm, packed4 = layout
    if kind == "T":
        npad_rows = num_data + ((-num_data) % max(1, int(rm)))
        mat_bytes = (-(-f // 2) * npad_rows if packed4
                     else f * npad_rows * int(itemsize))
    else:
        mat_bytes = num_data * f * int(itemsize)
    state_bytes = 4 * num_data * (3 * int(num_tree_per_iteration) + 2)
    return mat_bytes + state_bytes + TELEMETRY.cost_working_set()


def estimate_working_set(config, data_shape, *,
                         num_bins: Optional[int] = None) -> int:
    """Estimated training working set in bytes for ``config`` over a
    ``(num_data, num_columns)`` dataset — BEFORE constructing a dataset
    or booster, so admission control (serve registry, the sched plane's
    HBM gate, ``data_in_hbm=auto``) and users share one number.

    ``config`` is a :class:`~lightgbm_tpu.config.Config` or a params
    dict.  ``num_bins`` defaults to ``max_bin`` (the post-binning upper
    bound; a constructed dataset may resolve fewer bins and a slightly
    smaller matrix).  The single-device bin layout is resolved the same
    way training resolves it: the pallas kernels' feature-major padded/
    packed layout where they take the shape (whole, or in feature tiles
    with the bin rows padded to whole tiles: 2000 columns x 64 bins is
    16 tiles of 128), the row-major matrix otherwise.  A warm process
    adds its compiled programs' recorded
    temp+argument+output bytes; a cold one contributes 0.  See
    docs/TUNING.md (working-set budgeting)."""
    if not isinstance(config, Config):
        config = Config.from_params(dict(config))
    num_data, num_columns = (int(x) for x in tuple(data_shape))
    if num_data < 1 or num_columns < 1:
        raise LightGBMError(
            f"estimate_working_set needs a (num_data, num_columns) "
            f"shape with both >= 1, got {data_shape!r}")
    from ..objective import create_objective
    objective = create_objective(config)
    C = int(getattr(objective, "num_tree_per_iteration", 1) or 1)
    bins = int(num_bins) if num_bins else max(2, int(config.max_bin))
    layout: Tuple[str, int, bool] = ("rows", 0, False)
    choice = str(config.tpu_histogram_backend).strip().lower()
    if (choice != "onehot" and not config.gpu_use_dp
            and not config.tpu_double_precision):
        from ..ops.pallas_histogram import (feature_tile, pick_block_rows,
                                            supported)
        nb2 = _round_up_pow2(max(bins, 2))
        if supported(num_columns, nb2, np.dtype(np.uint8)):
            rb = (int(config.tpu_row_chunk) if config.tpu_row_chunk > 0
                  else pick_block_rows(num_columns, bins, num_data))
            layout = ("T", rb, bins <= 16)
            tile = feature_tile(num_columns, nb2)
            if tile < num_columns:
                num_columns = -(-num_columns // tile) * tile
    return working_set_bytes(num_data, num_columns,
                             num_tree_per_iteration=C, layout=layout)


class GBDT:
    """Gradient Boosted Decision Trees (boosting='gbdt')."""

    def __init__(self, config: Config, train_set: Optional[TpuDataset],
                 objective=None):
        self.config = config
        self.objective = objective
        # bind the config's telemetry level (env wins; see
        # utils/telemetry.py) and hook jax compile/retrace/cache events
        # before any tracing happens
        TELEMETRY.set_config_level(getattr(config, "telemetry_level", 1))
        TELEMETRY.set_config_timing(getattr(config, "device_timing",
                                            False))
        if TELEMETRY.level >= 1:
            TELEMETRY.install_jax_listeners()
        # arm fault injection for this run (env spec wins per-site) with
        # fresh occurrence counters — same lifecycle as the telemetry
        # level binding above; the collective retry policy binds at the
        # same point so every entry path (engine/sklearn/CLI) gets it
        FAULTS.configure(getattr(config, "fault_injection", ""))
        from ..parallel import network as _network
        _network.configure(config)
        self.train_set: Optional[TpuDataset] = None
        self._models: List[Tree] = []           # flat: iter-major, class-minor
        # finished trees whose device->host transfer is still in flight,
        # in iteration order: (first_iter, payload, grad_stats) where
        # payload is [(ints_dev, floats_dev, shrinkage)] * C or a
        # _PendingChunk, and grad_stats is the device-side health
        # diagnostics ([C, 8] / [T, C, 8]) or None when no stream runs
        self._pending: List[tuple] = []
        self._stop_flag = False
        self.num_tree_per_iteration = (
            objective.num_tree_per_iteration if objective is not None
            else max(1, config.num_class))
        self.shrinkage_rate = config.learning_rate
        self.iter_ = 0
        self.init_scores: List[float] = [0.0] * self.num_tree_per_iteration
        self.valid_sets: List[Tuple[str, TpuDataset]] = []
        self.valid_scores: List[np.ndarray] = []
        self.metrics = []
        self.valid_metrics: List[list] = []
        self.best_iter = -1
        self.feature_names: List[str] = []
        self._grow_fn = None
        self.max_feature_idx = 0
        self._inscan_evals: List[tuple] = []
        if train_set is not None:
            with _PHASES.phase("booster_init"):
                self.reset_train_data(train_set)

    # ----------------------------------------------------------------- setup
    def _resolve_hist_backend(self, parallel: bool,
                              walks_tiles: bool = True) -> str:
        """auto -> pallas on TPU when the kernel supports the shape
        (ops/pallas_histogram.supported); parallel learners and explicit
        double-precision requests stay on the XLA one-hot path, and so
        does a table that takes several feature tiles a pass under any
        grower but the serial segment one (``walks_tiles``), whose fused
        kernels alone walk them."""
        cfg = self.config
        choice = str(cfg.tpu_histogram_backend).strip().lower()
        if choice == "onehot":
            return "onehot"
        if choice == "pallas" or choice == "auto":
            import jax
            from ..ops.pallas_histogram import feature_tiles, supported
            nb2 = _round_up_pow2(max(self.train_set.max_column_bin, 2))
            shape_ok = (supported(self.train_set.num_columns, nb2,
                                  self.train_set.binned.dtype)
                        and (walks_tiles or feature_tiles(
                            self.train_set.num_columns, nb2) == 1))
            ok = (shape_ok and not parallel
                  and not cfg.gpu_use_dp and not cfg.tpu_double_precision)
            if choice == "pallas":
                if not ok:
                    log_warning("tpu_histogram_backend=pallas unsupported "
                                "for this dataset/learner; falling back "
                                "to onehot")
                    return "onehot"
                return "pallas"
            if jax.default_backend() != "tpu":
                return "onehot"
            if not shape_ok:
                # on the chip a shape the kernels cannot take never
                # selects the slow grower in silence
                log_warning(
                    f"the pallas histogram kernels of this learner do not "
                    f"fit {self.train_set.num_columns} columns x "
                    f"{self.train_set.max_column_bin} bins in VMEM; using "
                    f"the XLA one-hot grower")
            return "pallas" if ok else "onehot"
        return "onehot"

    def reset_train_data(self, train_set: TpuDataset) -> None:
        if self.train_set is not None and self.train_set is not train_set:
            # the reference CheckAligns on training-data reset too
            # (gbdt.cpp:827); existing trees' bin-space thresholds would
            # silently mis-route on differently-binned data
            self.train_set.check_align(train_set)
            # settle async-pipeline trees against the OLD score buffers
            # before they are replaced (the flush may rollback a stopped
            # iteration, which must not touch the new buffers)
            self._flush_pending()
        self.train_set = train_set
        self.num_data = train_set.num_data
        self.feature_names = list(train_set.feature_names)
        self.max_feature_idx = train_set.num_total_features - 1
        self._cegb_used = np.zeros(train_set.num_used_features,
                                   dtype=np.float64)
        self.fmeta = build_feature_meta(train_set, self.config,
                                        self._cegb_used)
        self._row_pad = 0
        # histogram bin axis is over physical COLUMNS (EFB groups); the
        # per-feature scan axis comes from fmeta.gather_idx when bundled
        self.num_bins = _round_up_pow2(max(train_set.max_column_bin, 2))
        cfg = self.config
        # Resolve the parallel layout FIRST so the histogram backend is
        # chosen for the learner that actually runs: a parallel request on
        # a single-device mesh falls back to the serial learner and must
        # keep the pallas/segment fast path (ADVICE.md round 1).
        tl = str(cfg.tree_learner).strip().lower()
        parallel = tl in ("data", "data_parallel", "feature",
                          "feature_parallel", "voting", "voting_parallel")
        mesh = None
        if parallel:
            from ..parallel import network
            # num_machines=1 (the default) means "use every device on the
            # mesh" — the TPU runtime already knows the slice topology
            mesh = network.init(cfg.num_machines if cfg.num_machines > 1
                                else 0)
            if mesh.devices.size <= 1:
                log_warning("Only one device available; using the serial "
                            "tree learner")
                parallel = False
                mesh = None
        # data-parallel keeps the segment fast path: rows shard cleanly and
        # histograms reduce linearly; feature/voting (and an explicit
        # fused-impl request) stay on the fused onehot grower, whose
        # row-major sharded layout is incompatible with the feature-major
        # pallas bins
        impl = str(cfg.tpu_tree_impl).strip().lower()
        # forced splits are a fused-grower feature: resolve them BEFORE the
        # layout choice, because a forced data-parallel run must fall back
        # to the fused grower's ROW-major sharded layout (a feature-major
        # pallas matrix sharded on axis 0 would split features, not rows)
        forced_plan = ()
        if cfg.forcedsplits_filename:
            if parallel and tl not in ("data", "data_parallel"):
                # the forced path reads this shard's leaf histogram without
                # a merge; feature/voting shards hold incomplete histograms
                # (column stripes / elected subsets), so forced stats would
                # diverge across devices.  Data-parallel psums full
                # histograms and is safe.
                log_warning("forcedsplits_filename is not supported by the "
                            "feature/voting-parallel learners; ignoring it")
            else:
                forced_plan = _build_forced_plan(train_set,
                                                 cfg.forcedsplits_filename,
                                                 max(2, cfg.num_leaves))
        data_mode = (tl in ("data", "data_parallel") and impl != "fused"
                     and not forced_plan)
        # feature-/voting-parallel on the O(leaf) growers are OPT-IN via
        # an explicit tpu_tree_impl (the auto default keeps the fused
        # grower those modes always had); every reference parallel
        # learner inherits the serial O(leaf) machinery
        # (feature_parallel_tree_learner.cpp:74-75)
        feature_mode = (tl in ("feature", "feature_parallel")
                        and impl in ("segment", "frontier")
                        and not forced_plan)
        voting_mode = (tl in ("voting", "voting_parallel")
                       and impl in ("segment", "frontier")
                       and not forced_plan)
        oleaf_mode = data_mode or feature_mode or voting_mode
        D = int(mesh.devices.size) if parallel else 1
        backend = self._resolve_hist_backend(
            parallel and not oleaf_mode,
            walks_tiles=(not parallel and impl in ("auto", "segment")
                         and not forced_plan
                         and not cfg.cegb_penalty_feature_lazy))
        rb = 0
        self._packed4 = False
        # bin rows the device table is padded to a multiple of: one
        # feature tile's where the segment kernels walk tiles
        self._bins_row_multiple = 1
        if backend == "pallas":
            from ..ops.pallas_histogram import (feature_tile, feature_tiles,
                                                pick_block_rows)
            # feature-parallel replicates rows (only split FINDING is
            # sharded); rows-sharded modes pad to whole blocks per shard
            rows_D = 1 if (parallel and feature_mode) else D
            rb = (cfg.tpu_row_chunk if cfg.tpu_row_chunk > 0 else
                  pick_block_rows(train_set.num_columns,
                                  self.num_bins,
                                  -(-self.num_data // rows_D)))
            # each shard's row count must be a whole number of blocks
            # 4-bit packing (Dense4bitsBin equivalent) for <=16-bin
            # datasets: two columns per byte halves the bin-stream DMA
            # and the compaction sort payload.  Feature-parallel column
            # stripes slice physical rows, so they keep unpacked bins
            # (a stripe boundary inside a packed byte would split it).
            self._packed4 = self.num_bins <= 16 and not (
                parallel and feature_mode)
            self._bins_layout = ("T", rb * rows_D, self._packed4)
            if feature_tiles(train_set.num_columns, self.num_bins) > 1:
                tile = feature_tile(train_set.num_columns, self.num_bins)
                self._bins_row_multiple = (tile // 2 if self._packed4
                                           else tile)
        else:
            self._bins_layout = ("rows", 0, False)
        # The rows-sharded mesh learners keep the bin matrix and the
        # per-row boosting state ON the mesh, placed once: left on the
        # default device, every tree's shard_map would re-shard the whole
        # matrix from device 0.
        self._row_sharding = None
        if parallel and backend == "pallas" and not feature_mode:
            from jax.sharding import NamedSharding, PartitionSpec
            self._row_sharding = NamedSharding(
                mesh, PartitionSpec(None, mesh.axis_names[0]))
        # memory-tier resolution (docs/ROBUSTNESS.md, rung 4 of the
        # recovery ladder) BEFORE any upload: a run whose working set
        # never fit starts out-of-core instead of crash-and-retrying
        self._spill_store = None
        self._bins_window = None
        self._bins_hold = 0
        self._spill_unavail = None
        self._data_tier = self._resolve_data_tier(parallel)
        if self._data_tier == "spill":
            self._activate_spill(train_set)
        else:
            try:
                # the resident upload is itself a bin-matrix h2d
                # transfer, so it hosts the oocore/h2d injection site:
                # "the matrix never fit" becomes deterministically
                # reproducible
                if FAULTS.enabled:
                    FAULTS.maybe_raise("oocore/h2d", oom_error)
                self._upload_resident_bins(train_set)
            except Exception as e:
                if (not _is_oom_error(e)
                        or self._spill_blocked_reason(parallel)):
                    raise
                TELEMETRY.fault_event(
                    "oom_spill", site="oocore/h2d", iteration=self.iter_,
                    detail="resident bin-matrix upload hit "
                           "RESOURCE_EXHAUSTED; spilling to host")
                log_warning("uploading the bin matrix to HBM failed with "
                            "RESOURCE_EXHAUSTED; continuing in the "
                            "host-spill (out-of-core) tier")
                self._data_tier = "spill"
                TELEMETRY.set_data_tier("spill")
                self._activate_spill(train_set)
        # rb threads through as the single block size for BOTH the bin
        # matrix padding and every kernel launch (grower + segment grower);
        # re-picking it at a kernel call site could desync from the padding
        infos = train_set.feature_infos()
        use_monotone = any(i.monotone != 0 for i in infos)
        use_cegb_coupled = bool(cfg.cegb_penalty_feature_coupled)
        use_cegb_lazy = bool(cfg.cegb_penalty_feature_lazy)
        if use_cegb_lazy and parallel:
            log_warning("cegb_penalty_feature_lazy is not supported by the "
                        "distributed learners; ignoring it")
            use_cegb_lazy = False
        self.grower_params = GrowerParams(
            num_leaves=max(2, cfg.num_leaves),
            max_depth=cfg.max_depth,
            feature_fraction_bynode=cfg.feature_fraction_bynode,
            row_chunk=rb,
            hist_backend=backend,
            packed4=self._packed4,
            num_columns=train_set.num_columns,
            use_monotone=use_monotone,
            cegb_tradeoff=float(cfg.cegb_tradeoff),
            cegb_penalty_split=float(cfg.cegb_penalty_split),
            use_cegb_coupled=use_cegb_coupled,
            use_cegb_lazy=use_cegb_lazy,
            forced_plan=forced_plan,
            split=SplitParams(
                lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
                max_delta_step=cfg.max_delta_step,
                min_data_in_leaf=float(cfg.min_data_in_leaf),
                min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
                min_gain_to_split=cfg.min_gain_to_split,
                cat_smooth=cfg.cat_smooth, cat_l2=cfg.cat_l2,
                max_cat_threshold=cfg.max_cat_threshold,
                max_cat_to_onehot=cfg.max_cat_to_onehot,
                min_data_per_group=cfg.min_data_per_group,
                has_cat=any(i.is_categorical for i in infos)))
        # forced splits and CEGB-lazy are fused-grower features
        self._use_segment = (backend == "pallas" and impl != "fused"
                             and not forced_plan and not use_cegb_lazy)
        if impl in ("segment", "frontier") and not self._use_segment:
            if parallel:
                log_warning(f"tpu_tree_impl={impl} needs the pallas "
                            "backend under this parallel layout; using "
                            "the fused grower")
            else:
                log_warning(f"tpu_tree_impl={impl} requires the pallas "
                            "histogram backend (and no forced splits / "
                            "CEGB-lazy); using the fused grower")
        bundle_fg = (train_set.bundle.feat_group
                     if train_set.bundle is not None else None)
        if parallel and self._use_segment and (feature_mode or voting_mode):
            from ..parallel.learners import (
                make_feature_parallel_oleaf_grower,
                make_voting_parallel_oleaf_grower)
            kw = dict(
                feat_group=bundle_fg, impl=impl,
                batch_k=(_auto_frontier_k(cfg, train_set.num_columns,
                                          self.num_bins)
                         if impl == "frontier" else 0),
                gain_ratio=float(cfg.tpu_frontier_gain_ratio))
            if feature_mode:
                self._grow_fn = make_feature_parallel_oleaf_grower(
                    self.num_bins, self.grower_params, mesh, rb,
                    train_set.num_columns,
                    column_bins=train_set.column_bins, **kw)
            else:
                self._grow_fn = make_voting_parallel_oleaf_grower(
                    self.num_bins, self.grower_params, mesh, rb,
                    train_set.num_columns, top_k=cfg.top_k, **kw)
            self._mesh = mesh
        elif parallel and self._use_segment and impl == "frontier":
            from ..parallel.learners import (
                make_data_parallel_frontier_grower)
            k = _auto_frontier_k(cfg, train_set.num_columns, self.num_bins)
            self._grow_fn = make_data_parallel_frontier_grower(
                self.num_bins, self.grower_params, mesh, rb,
                train_set.num_columns, feat_group=bundle_fg, batch_k=k,
                gain_ratio=float(cfg.tpu_frontier_gain_ratio))
            self._mesh = mesh
        elif parallel and self._use_segment:
            from ..parallel.learners import make_data_parallel_segment_grower
            self._grow_fn = make_data_parallel_segment_grower(
                self.num_bins, self.grower_params, mesh, rb,
                train_set.num_columns, feat_group=bundle_fg)
            self._mesh = mesh
        elif parallel:
            from ..parallel.learners import make_parallel_grower
            # pad rows to a multiple of the mesh size; pad rows carry
            # zero membership weight so they never contribute
            pad = (-self.num_data) % D
            if pad:
                self.bins = jnp.pad(self.bins, ((0, pad), (0, 0)))
                self._row_pad = pad
            self._grow_fn = make_parallel_grower(
                self.num_bins, self.grower_params, mesh, tl,
                top_k=cfg.top_k, num_columns=train_set.num_columns,
                feat_group=bundle_fg,
                column_bins=train_set.column_bins)
            self._mesh = mesh
        elif self._use_segment and impl == "frontier":
            # batched best-first: K splits per round, one K-leaf batched
            # histogram kernel whose matmul output fills the 128-wide MXU
            # tile (grower_frontier.py); opt-in — trees can differ
            # slightly from strict best-first when K > 1
            from .grower_frontier import make_grow_tree_frontier
            self._grow_fn = make_grow_tree_frontier(
                self.num_bins, self.grower_params, rb,
                batch_k=_auto_frontier_k(cfg, train_set.num_columns,
                                         self.num_bins),
                gain_ratio=float(cfg.tpu_frontier_gain_ratio))
        elif self._use_segment and impl in ("auto", "segment"):
            from .grower_seg import make_grow_tree_segment
            self._grow_fn = make_grow_tree_segment(
                self.num_bins, self.grower_params, rb)
        else:
            self._grow_fn = make_grow_tree(self.num_bins, self.grower_params)
        C = self.num_tree_per_iteration
        if self.iter_ > 0:
            # mid-boosting swap (GBDT::ResetTrainingData): the score buffer
            # must equal the existing model's raw prediction on the NEW
            # rows (per-row init scores folded in by the replay), or the
            # next iteration boosts against a zero model
            self.train_score = jnp.asarray(
                self._replay_model_scores(train_set), dtype=jnp.float32)
        elif train_set.metadata.init_score is not None:
            init = np.asarray(train_set.metadata.init_score, dtype=np.float32)
            self.train_score = jnp.asarray(
                init.reshape(C, self.num_data))
        else:
            self.train_score = jnp.zeros((C, self.num_data),
                                         dtype=jnp.float32)
        self._bag_rng = np.random.RandomState(cfg.bagging_seed)
        self._feat_rng = np.random.RandomState(cfg.feature_fraction_seed)
        self._key = jax.random.PRNGKey(cfg.seed)
        self.bag_weight = jnp.ones(self.num_data, dtype=jnp.float32)
        if self._row_sharding is not None:
            if self.num_data % D == 0:
                self.train_score = jax.device_put(self.train_score,
                                                  self._row_sharding)
                self.bag_weight = self._shard_rows(self.bag_weight)
            else:
                self._row_sharding = None
                log_warning(
                    f"{self.num_data} rows do not divide over {D} devices: "
                    f"scores, gradients and labels stay on one device and "
                    f"are re-sharded every iteration")
        # a stopped model may find splits again on fresh data
        self._stop_flag = False
        # init scores are already folded into a replayed buffer; re-running
        # boost-from-average would shift every valid score a second time
        self._boosted_from_average = self.iter_ > 0
        self._full_fmask = jnp.ones(train_set.num_used_features,
                                    dtype=jnp.float32)
        self._fused_fns = None
        self._fused_core = None
        self._obj_arrs = None
        self._chunk_fns: Dict[object, object] = {}
        self._shr_dev: Dict[float, jax.Array] = {}
        # a data swap invalidates the in-scan eval program (labels, bin
        # layout and metric bindings may all change); the engine/CLI
        # attach a fresh one via setup_inscan_eval when eligible
        self._inscan = None
        self._vscores_dev = None
        self._inscan_evals = []
        # OOM-degraded chunk-size ceiling (None = no ceiling): once a
        # chunk dispatch hits RESOURCE_EXHAUSTED the cap halves and
        # STICKS, so later chunks of the run skip the doomed sizes
        self._chunk_cap: Optional[int] = None

    # ------------------------------------------------------- memory tiers
    def _shard_rows(self, x: jax.Array) -> jax.Array:
        """A per-row [N] vector placed over the mesh's row shards."""
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(x, NamedSharding(
            self._row_sharding.mesh,
            PartitionSpec(self._row_sharding.spec[1])))

    def _spill_blocked_reason(self, parallel: bool) -> Optional[str]:
        """Why the host-spill tier is off the table for this run, or
        None when it is available."""
        if parallel or getattr(self, "_mesh", None) is not None:
            return ("distributed learners keep the bin matrix sharded "
                    "in HBM")
        if str(getattr(self.config, "data_in_hbm", "auto")).strip() \
                .lower() == "resident":
            return "data_in_hbm=resident pins the bin matrix in HBM"
        return None

    def _estimate_working_set(self) -> int:
        """Pre-dispatch estimate of the training working set in bytes:
        the bin matrix in its resolved device layout, the f32 boosting
        state (scores/grads/hessians per class, bag weights, leaf ids),
        plus the largest CostJit ``memory_analysis`` working set already
        on record (a resumed/warm process knows its compiled programs'
        temp+argument+output bytes; a cold one contributes 0)."""
        ts = self.train_set
        # bin rows as uploaded: padded to whole feature tiles where the
        # kernels walk tiles (two logical columns a row under packed4)
        per_row = 2 if self._bins_layout[2] else 1
        m = self._bins_row_multiple * per_row
        return working_set_bytes(
            self.num_data, ts.num_columns + (-ts.num_columns) % m,
            num_tree_per_iteration=self.num_tree_per_iteration,
            layout=self._bins_layout,
            itemsize=ts.binned.dtype.itemsize)

    def _resolve_data_tier(self, parallel: bool) -> str:
        """data_in_hbm=auto|resident|spill -> this run's starting tier.

        ``auto`` is the proactive admission check: estimated working
        set vs the device's reported HBM capacity
        (``TELEMETRY.device_memory_budget()``); backends without
        allocator stats (CPU) stay resident.  The ``oocore/admit``
        fault site forces the spill decision deterministically.  Every
        spill decision lands in the telemetry faults section as an
        ``oocore_admit`` event.  The tier is runtime-only state — it is
        never serialized into models or snapshots."""
        choice = str(getattr(self.config, "data_in_hbm", "auto")).strip() \
            .lower()
        blocked = self._spill_blocked_reason(parallel)
        if blocked is not None:
            if choice == "spill":
                log_warning(f"data_in_hbm=spill ignored: {blocked}")
            self._spill_unavail = blocked
            TELEMETRY.set_data_tier("resident")
            return "resident"
        if choice == "spill":
            TELEMETRY.fault_event("oocore_admit", site="oocore/admit",
                                  iteration=self.iter_,
                                  detail="forced by data_in_hbm=spill")
            TELEMETRY.set_data_tier("spill")
            return "spill"
        tier, detail = "resident", ""
        if FAULTS.enabled and FAULTS.check("oocore/admit"):
            tier, detail = "spill", "injected admission failure"
        else:
            budget = TELEMETRY.device_memory_budget()
            if budget:
                need = self._estimate_working_set()
                if need > _ADMIT_FRACTION * budget:
                    tier = "spill"
                    detail = (f"estimated working set ~{need} B vs "
                              f"{budget} B reported HBM")
        if tier == "spill":
            TELEMETRY.fault_event("oocore_admit", site="oocore/admit",
                                  iteration=self.iter_, detail=detail)
            log_warning(f"admission check: {detail}; starting in the "
                        "host-spill (out-of-core) tier")
        TELEMETRY.set_data_tier(tier)
        return tier

    def _upload_resident_bins(self, train_set: TpuDataset) -> None:
        """Resident tier: the cached whole-matrix device upload."""
        kind, rm, packed4 = self._bins_layout
        if kind == "T":
            if self._row_sharding is not None:
                # host -> mesh directly: no whole copy is staged on, or
                # cached for, the default device
                self.bins = jax.device_put(
                    train_set.host_binned_T(rm, packed4=packed4),
                    self._row_sharding)
            else:
                self.bins = train_set.device_binned_T(
                    rm, packed4=packed4,
                    feature_multiple=self._bins_row_multiple)
            self._row_pad = int(self.bins.shape[1]) - self.num_data
        else:
            self.bins = train_set.device_binned()

    def _activate_spill(self, train_set: TpuDataset) -> None:
        """Move the bin matrix to the host-spill tier: build the
        fixed-order row-block store over the exact bytes the resident
        path would upload (bit-identity by construction), and drop
        every resident device copy so its HBM is reclaimable."""
        from ..data.hostspill import HostSpillStore
        kind, rm, packed4 = self._bins_layout
        if kind == "T":
            mat = train_set.host_binned_T(
                rm, packed4=packed4,
                feature_multiple=self._bins_row_multiple)
            self._row_pad = int(mat.shape[1]) - self.num_data
            axis = 1
        else:
            mat = train_set.host_binned()
            axis = 0
        self._spill_store = HostSpillStore.from_matrix(mat, row_axis=axis)
        self.bins = None
        self._bins_window = None
        train_set.drop_device_cache()
        TELEMETRY.gauge_set("oocore/spill_bytes", self._spill_store.nbytes)
        TELEMETRY.gauge_set("oocore/block_rows",
                            self._spill_store.block_rows)

    def _device_bins(self):
        """The device bin matrix for the next dispatch.  Resident tier:
        the cached upload.  Spill tier: stream the host row-blocks into
        a fresh device matrix (data/hostspill.py) and keep it only for
        the current dispatch window — train_chunk releases it on exit,
        so between windows that HBM is reclaimable (the matrix IS
        resident during a window; the win is between-window headroom
        and allocator fragmentation recovery)."""
        if self.bins is not None:
            return self.bins
        if self._bins_window is None:
            with _PHASES.phase("h2d_stream"):
                self._bins_window = self._spill_store.stream_to_device()
        return self._bins_window

    def _release_bins_window(self) -> None:
        """Drop the spill tier's per-window device matrix (no-op when
        resident: self.bins keeps the only reference there)."""
        self._bins_window = None

    def _donated_carries_deleted(self) -> bool:
        """True when a failed dispatch consumed its donated score/key/
        vscore buffers — there is no device state left to retry from."""
        for buf in ((self.train_score, self._key)
                    + tuple(self._vscores_dev or ())):
            deleted = getattr(buf, "is_deleted", None)
            if deleted is not None and deleted():
                return True
        return False

    def _escalate_spill(self, err: BaseException) -> bool:
        """Reactive rung 3->4 of the recovery ladder: the chunk-size
        ladder bottomed out at 1 and dispatch still RESOURCE_EXHAUSTs —
        move the bin matrix to the host-spill tier and let the caller
        retry, instead of giving up.  Returns False (recording the
        reason for _oom_exhausted) when the tier is unavailable or
        already active."""
        if getattr(self, "_data_tier", "resident") == "spill":
            self._spill_unavail = "already at the host-spill tier"
            return False
        blocked = self._spill_blocked_reason(False)
        if blocked is not None:
            self._spill_unavail = blocked
            return False
        if self._donated_carries_deleted():
            self._spill_unavail = ("the failed dispatch consumed its "
                                   "donated score/key carries; no device "
                                   "state left to retry from")
            return False
        # same recovery pattern as the PR 7 vscores invalidation: drop
        # the device carry, re-upload from the host f64 truth at the
        # next dispatch (outside the transfer guard)
        self._vscores_dev = None
        self._activate_spill(self.train_set)
        self._data_tier = "spill"
        TELEMETRY.set_data_tier("spill")
        TELEMETRY.fault_event(
            "oom_spill", site="chunk/oom", iteration=self.iter_,
            detail="chunk ladder exhausted at size 1; bin matrix spilled "
                   "to host (out-of-core tier)")
        log_warning("dispatch still RESOURCE_EXHAUSTED at chunk size 1; "
                    "spilling the bin matrix to host memory and streaming "
                    "row-blocks per dispatch window (out-of-core tier)")
        return True

    def _replay_model_scores(self, dataset: TpuDataset) -> np.ndarray:
        """[C, N] f64 raw scores of the current model on ``dataset``: the
        dataset's per-row init scores (else zeros), every existing tree
        replayed over its binned rows, plus the scalar boost-from-average
        inits (gbdt.cpp AddValidDataset / ResetTrainingData).  Trees loaded
        from a model file are bin-remapped first."""
        C = self.num_tree_per_iteration
        models = self.models                 # flushes the async pipeline
        n_iter = self.iter_
        score = np.zeros((C, dataset.num_data), dtype=np.float64)
        if dataset.metadata.init_score is not None:
            score = np.asarray(dataset.metadata.init_score,
                               dtype=np.float64).reshape(
                                   C, dataset.num_data).copy()
        infos = dataset.feature_infos()
        for it in range(n_iter):
            for k in range(C):
                tree = models[it * C + k]
                if not tree.bins_aligned:
                    from .serialization import _remap_tree_to_bins
                    tree = _remap_tree_to_bins(tree, dataset)
                    # cache the remap ONLY against the training set (whose
                    # alignment is enforced); persisting a remap against an
                    # arbitrary valid set would silently re-route later
                    # binned passes through that set's bins
                    if dataset is self.train_set:
                        models[it * C + k] = tree
                score[k] += tree.predict_binned(dataset.binned, infos)
        for k in range(C):
            score[k] += self.init_scores[k]
        return score

    def add_valid_data(self, name: str, valid_set: TpuDataset) -> None:
        if self.train_set is not None:
            self.train_set.check_align(valid_set)
        # replay existing trees (continued training, gbdt.cpp
        # AddValidDataset)
        score = self._replay_model_scores(valid_set)
        self.valid_sets.append((name, valid_set))
        self.valid_scores.append(score)
        # the in-scan eval program binds the valid-set tuple at build time
        self._inscan = None
        self._vscores_dev = None

    # --------------------------------------------------------------- bagging
    def _bagging(self, iter_idx: int, grads, hesss):
        """Compute the per-iteration row-inclusion mask; may also rescale
        gradients (GOSS overrides).  Returns (grads, hesss)."""
        cfg = self.config
        need = (cfg.bagging_freq > 0 and
                (cfg.bagging_fraction < 1.0
                 or cfg.pos_bagging_fraction < 1.0
                 or cfg.neg_bagging_fraction < 1.0))
        if not need:
            return grads, hesss
        if iter_idx % cfg.bagging_freq != 0:
            return grads, hesss
        n = self.num_data
        if (cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0):
            # balanced bagging over positive/negative labels (gbdt.cpp:186-240)
            lab = np.asarray(self.train_set.metadata.label)
            mask = np.zeros(n, dtype=np.float32)
            pos = np.nonzero(lab > 0)[0]
            neg = np.nonzero(lab <= 0)[0]
            kp = int(len(pos) * cfg.pos_bagging_fraction)
            kn = int(len(neg) * cfg.neg_bagging_fraction)
            if kp > 0:
                mask[self._bag_rng.choice(pos, kp, replace=False)] = 1.0
            if kn > 0:
                mask[self._bag_rng.choice(neg, kn, replace=False)] = 1.0
        else:
            k = int(n * cfg.bagging_fraction)
            idx = self._bag_rng.choice(n, k, replace=False)
            mask = np.zeros(n, dtype=np.float32)
            mask[idx] = 1.0
        self.bag_weight = jnp.asarray(mask)
        return grads, hesss

    def _tree_feature_mask(self) -> jnp.ndarray:
        """Per-tree feature_fraction sampling (GetUsedFeatures,
        serial_tree_learner.cpp:273-321)."""
        F = self.train_set.num_used_features
        frac = self.config.feature_fraction
        if frac >= 1.0:
            return self._full_fmask
        k = max(1, int(F * frac))
        idx = self._feat_rng.choice(F, k, replace=False)
        mask = np.zeros(F, dtype=np.float32)
        mask[idx] = 1.0
        return jnp.asarray(mask)

    # ------------------------------------------------------------- iteration
    def _boost_from_average(self) -> None:
        cfg = self.config
        if (self._boosted_from_average or self.objective is None
                or not cfg.boost_from_average
                or self.train_set.metadata.init_score is not None):
            self._boosted_from_average = True
            return
        C = self.num_tree_per_iteration
        for k in range(C):
            init = self.objective.boost_from_score(k)
            if abs(init) > 1e-15:
                self.init_scores[k] = init
                self.train_score = self.train_score.at[k].add(init)
                for vs in self.valid_scores:
                    vs[k] += init
        self._boosted_from_average = True

    def _gradients(self):
        C = self.num_tree_per_iteration
        if C == 1:
            g, h = self.objective.get_gradients(self.train_score[0])
            return g[None, :], h[None, :]
        return self.objective.get_gradients(self.train_score)

    # trees may be fetched asynchronously (pipeline depth 1) when nothing
    # needs them on the host mid-iteration; DART/RF mutate freshly-grown
    # trees and opt out
    _async_trees = True
    # whole-iteration fusion (gradients + grow + score update in a single
    # jitted dispatch per tree) — subclasses whose bagging cannot run as
    # a device-side transform of the gradients opt out
    _fused_ok = True
    # the chunked loop (train_chunk) additionally requires every
    # per-iteration decision to live on device; subclasses whose _bagging
    # transforms gradients with host-side dispatch each iteration (GOSS)
    # opt out
    _chunk_capable = True
    # test seam: zero-arg context-manager factory wrapped around the chunk
    # dispatch (tests install jax.transfer_guard("disallow") here to prove
    # the chunk body never touches the host)
    _chunk_guard = None
    # in-scan evaluation (metric/device.py): the DeviceEval program the
    # chunk scan body runs per iteration, and the device-resident [C, Nv]
    # f32 valid-score carries it threads between dispatches.  None until
    # setup_inscan_eval attaches one; _vscores_dev is re-uploaded from
    # the host f64 buffers whenever it is invalidated (rollback, undo,
    # OOM degrade, data swap)
    _inscan = None
    _vscores_dev = None

    def _build_fused_step(self):
        """One jitted call per (gradient pass, per-class tree).  Keeping the
        iteration to two dispatches keeps the host off the device's
        critical path; it is also the natural unit for the driver's
        multichip dryrun."""
        import functools
        obj = self.objective
        pad = self._row_pad
        N = self.num_data
        C = self.num_tree_per_iteration
        grow_fn = self._grow_fn

        # device-array state of the objective (labels, per-class weights,
        # lambdarank bucket tables...) passed as explicit args: embedding
        # them as jit constants would bloat the compiled program by O(N)
        # bytes.  tree_flatten reaches arrays nested in lists/dicts (e.g.
        # rank.py's bucket structures).
        attr_leaves, attr_treedef = jax.tree_util.tree_flatten(
            dict(vars(obj)),
            is_leaf=lambda x: not isinstance(x, (list, tuple, dict)))
        arr_pos = [i for i, x in enumerate(attr_leaves)
                   if isinstance(x, jax.Array)]
        self._obj_arrs = [attr_leaves[i] for i in arr_pos]
        if self._row_sharding is not None:
            # labels / weights ride the mesh with the scores they meet
            self._obj_arrs = [self._shard_rows(a) if a.shape == (N,) else a
                              for a in self._obj_arrs]

        def _with_arrs(fn, arr_vals):
            leaves = list(attr_leaves)
            for i, v in zip(arr_pos, arr_vals):
                leaves[i] = v
            attrs = jax.tree_util.tree_unflatten(attr_treedef, leaves)
            saved = {k: getattr(obj, k) for k in attrs}
            for k, v in attrs.items():
                setattr(obj, k, v)
            try:
                return fn()
            finally:
                for k, v in saved.items():
                    setattr(obj, k, v)

        def grad_core(score, arrs):
            def run():
                if C == 1:
                    g, h = obj.get_gradients(score[0])
                    return g[None], h[None]
                return obj.get_gradients(score)
            with jax.named_scope("grad"):
                return _with_arrs(run, arrs)

        fused_grad = cost_jit("boost/gradients", jax.jit(grad_core))

        # multiclass batched roots: all C class-trees' root histograms in
        # ONE kernel pass (C x fewer full-data scans per iteration; the
        # 8*C output channels also pack the MXU tile better).  Serial
        # segment/frontier growers only — the distributed wrappers own
        # their histogram reduction, and the fused grower's layout is
        # row-major.
        batched_roots = (C > 1 and self._use_segment
                         and getattr(self, "_mesh", None) is None
                         # histogram_all takes a table whole or not at all
                         and self._bins_row_multiple == 1)
        if batched_roots:
            from ..ops.pallas_histogram import (channel_set_capacity,
                                                histogram_all,
                                                pack_channels, unpack_hist)
            G_cols = self.train_set.num_columns
            rb_ = self.grower_params.row_chunk
            packed4 = self.grower_params.packed4
            # the kernel's VMEM working set grows with the channel stack;
            # chunk the classes when num_class exceeds the budget
            cap = channel_set_capacity(G_cols, self.num_bins, rb_)

            @jax.named_scope("roots")
            def roots_core(grads, hesss, member, bins):
                if pad:
                    grads = jnp.pad(grads, ((0, 0), (0, pad)))
                    hesss = jnp.pad(hesss, ((0, 0), (0, pad)))
                    member = jnp.pad(member, (0, pad))
                outs = []
                for c0 in range(0, C, cap):
                    cs = range(c0, min(c0 + cap, C))
                    w8m = jnp.concatenate(
                        [pack_channels(grads[c], hesss[c], member)
                         for c in cs])                      # [len*8, Npad]
                    out = histogram_all(bins, w8m, self.num_bins, rb_,
                                        packed4=packed4)
                    if len(cs) == 1:
                        out = out[None]
                    outs.append(out)
                out = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
                return jax.vmap(unpack_hist)(out)[:, :G_cols]

            fused_roots = cost_jit("grow/roots", jax.jit(roots_core))
        else:
            fused_roots = roots_core = None

        # Resolve the scorer choice OUTSIDE the trace: the auto mode
        # runs a real on-device self-check (lowering + bit-exactness)
        # and falls back to the gather if the kernel misbehaves.
        # Serial only: outside the growers' shard_map the step is
        # partitioned by the compiler, which cannot split a Pallas call.
        if (self.grower_params.hist_backend == "pallas"
                and getattr(self, "_mesh", None) is None):
            from ..ops.pallas_score import scorer_available
            use_score_kernel = scorer_available()
        else:
            use_score_kernel = False

        def step_core_full(score, grads, hesss, member, bins, fmeta, fmask,
                           sub, shrinkage, k, roots=None):
            g_k, h_k = grads[k], hesss[k]
            if pad:
                g_k = jnp.pad(g_k, (0, pad))
                h_k = jnp.pad(h_k, (0, pad))
                member = jnp.pad(member, (0, pad))
            kw = {} if roots is None else {"root_hist": roots[k]}
            arrays, leaf_id, *stats = grow_fn(bins, g_k, h_k, member,
                                              fmeta, fmask, sub, **kw)
            if pad:
                leaf_id = leaf_id[:N]
            with jax.named_scope("score"):
                if use_score_kernel:
                    # one-hot-matmul scorer: the plain table gather
                    # lowers to ~1.6 GB/s on this backend
                    # (ops/pallas_score)
                    from ..ops.pallas_score import score_gather_add
                    new_row = score_gather_add(
                        score[k], leaf_id, shrinkage * arrays.leaf_value)
                else:
                    new_row = (score[k]
                               + shrinkage * arrays.leaf_value[leaf_id])
                score = score.at[k].set(new_row)
            with jax.named_scope("pack_tree"):
                ints_d, floats_d = _pack_tree_device(arrays)
            # the raw TreeArrays ride along for the in-scan eval variant,
            # which re-routes the valid sets through the freshly grown tree
            return score, ints_d, floats_d, tuple(stats), arrays

        def step_core(*a, **kw):
            return step_core_full(*a, **kw)[:4]

        fused_step = cost_jit(
            "grow/fused_step",
            functools.partial(jax.jit, donate_argnums=(0,))(step_core))

        self._fused_fns = (fused_grad, fused_step, fused_roots)
        # un-jitted building blocks; the chunked loop retraces them inside
        # its scan so a chunk body is op-for-op the per-iteration fused
        # path (bit-identical trees at any chunk size)
        self._fused_core = (grad_core, step_core, roots_core, step_core_full)

    def _get_chunk_fn(self, T: int, with_eval: bool = False):
        """One jitted program running ``T`` boosting iterations as a
        lax.scan over the fused step, stacking each iteration's packed
        tree buffers into [T, C, ...] on-device outputs.  The score and
        PRNG-key carries are donated so no buffer copies accumulate
        across chunks.

        With ``with_eval`` the scan additionally threads the valid-set
        score vectors through the carry, routes every freshly grown tree
        over each valid set's binned matrix, and runs the attached
        DeviceEval program per iteration — stacking a [T, n_cols + 1]
        matrix (the metrics, then the levels the walk took) onto the chunk
        outputs so eval cadence costs zero extra dispatches."""
        cache_key = (T, "eval") if with_eval else T
        fn = self._chunk_fns.get(cache_key)
        if fn is not None:
            return fn
        import functools
        if self._fused_core is None:
            self._build_fused_step()
        grad_core, step_core, roots_core, step_core_full = self._fused_core
        C = self.num_tree_per_iteration

        if with_eval:
            inscan = self._inscan
            gp = self.grower_params
            # static routing depth: max_depth when bounded, else the leaf
            # count (a path can't be longer than num_leaves - 1 splits)
            depth_bound = ((gp.max_depth + 1) if gp.max_depth > 0
                           else gp.num_leaves)

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
            def chunk_run_eval(score, key, vscores, member, bins, fmeta,
                               fmask, shrinkage, arrs, vbins, earrs):
                def body(carry, _):
                    score, key, vscores = carry
                    grads, hesss = grad_core(score, arrs)
                    gstats = _grad_stats_core(grads, hesss)
                    roots = (roots_core(grads, hesss, member, bins)
                             if roots_core is not None else None)
                    ints_l, floats_l, stats_l = [], [], []
                    levels = jnp.int32(0)
                    for k in range(C):
                        key, sub = jax.random.split(key)
                        (score, ints_d, floats_d, stats,
                         arrays) = step_core_full(
                            score, grads, hesss, member, bins, fmeta,
                            fmask, sub, shrinkage, jnp.int32(k), roots)
                        ints_l.append(ints_d)
                        floats_l.append(floats_d)
                        stats_l.append(stats)
                        vscores, walked = _eval_walk(
                            vscores, vbins, arrays, fmeta, k, shrinkage,
                            depth_bound)
                        levels = levels + walked
                    # the walk's levels ride out as one more column of
                    # the metric row: the same buffer, the same fetch
                    mvals = jnp.concatenate([
                        inscan.eval_fn(score, vscores, earrs),
                        levels.astype(jnp.float32)[None]])
                    return ((score, key, vscores),
                            (jnp.stack(ints_l), jnp.stack(floats_l),
                             gstats, mvals, _stack_seg_stats(stats_l)))

                carry, (ints_all, floats_all, gstats_all, mvals_all,
                        seg_all) = jax.lax.scan(
                    body, (score, key, vscores), None, length=T)
                score, key, vscores = carry
                return (score, key, vscores, ints_all, floats_all,
                        gstats_all, mvals_all, seg_all)

            chunk_run_eval = cost_jit(f"boost/chunk_eval[{T}]",
                                      chunk_run_eval)
            self._chunk_fns[cache_key] = chunk_run_eval
            return chunk_run_eval

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def chunk_run(score, key, member, bins, fmeta, fmask, shrinkage,
                      arrs):
            def body(carry, _):
                score, key = carry
                grads, hesss = grad_core(score, arrs)
                # health diagnostics ride the scan as one more stacked
                # output ([T, C, 8] total): zero extra dispatches, and
                # the tiny reduce is dwarfed by the histogram build
                gstats = _grad_stats_core(grads, hesss)
                roots = (roots_core(grads, hesss, member, bins)
                         if roots_core is not None else None)
                ints_l, floats_l, stats_l = [], [], []
                for k in range(C):
                    # same key stream as the per-iteration paths, so the
                    # same seed grows the same trees at any chunk size
                    key, sub = jax.random.split(key)
                    score, ints_d, floats_d, stats = step_core(
                        score, grads, hesss, member, bins, fmeta, fmask,
                        sub, shrinkage, jnp.int32(k), roots)
                    ints_l.append(ints_d)
                    floats_l.append(floats_d)
                    stats_l.append(stats)
                return ((score, key),
                        (jnp.stack(ints_l), jnp.stack(floats_l), gstats,
                         _stack_seg_stats(stats_l)))

            (score, key), (ints_all, floats_all, gstats_all,
                           seg_all) = jax.lax.scan(
                body, (score, key), None, length=T)
            return score, key, ints_all, floats_all, gstats_all, seg_all

        chunk_run = cost_jit(f"boost/chunk[{T}]", chunk_run)
        self._chunk_fns[cache_key] = chunk_run
        return chunk_run

    @property
    def models(self) -> List[Tree]:
        self._flush_pending()
        return self._models

    @models.setter
    def models(self, value) -> None:
        self._models = list(value)
        self._pending = []
        self._vscores_dev = None

    def _entry_iter_arrays(self, entry):
        """Normalize one pending entry into per-iteration host pytrees:
        [(iter_idx, [(TreeArrays, shrinkage)] * C, gstats, chunk_len,
        mvals_row, wall_s)].  A chunk entry fetches its stacked [T, C,
        ...] buffers here — two host transfers for the WHOLE chunk (the
        async copy started at dispatch), then pure numpy slicing.
        ``gstats`` is the [C, 8] grad/hess diagnostics row for the
        health stream (None when no stream is active — the device buffer
        is then never fetched); ``mvals_row`` is the in-scan eval
        program's [n_cols] metric row (None off the eval path), its
        fetch counted under ``transfer/eval_fetch_*``; ``wall_s`` is the
        chunk's dispatch wall window, attributed to the chunk's FIRST
        iteration (None elsewhere)."""
        iter_idx, payload, gstats = entry
        L = self.grower_params.num_leaves
        fetch_stats = gstats is not None and HEALTH.active
        if isinstance(payload, _PendingChunk):
            chunk = fetch_tree_chunk(payload.ints_all, payload.floats_all,
                                     L)
            gnp = np.asarray(gstats) if fetch_stats else None
            mv = None
            if payload.mvals is not None:
                mv = np.asarray(payload.mvals)
                # the in-scan eval row fetch is its own host transfer;
                # counted separately from the tree-buffer fetch_calls
                # (whose exact counts tests pin)
                TELEMETRY.counter_add("transfer/eval_fetch_calls")
                TELEMETRY.counter_add("transfer/eval_fetch_bytes",
                                      int(mv.nbytes))
                # its last column is the valid-set walk's levels
                TELEMETRY.counter_add("eval/walk_levels",
                                      int(mv[:, -1].sum()))
                # the walk's form is chosen from static shapes, so the
                # trees the dense form folded in are counted here
                dense = _dense_walk_plan(self._inscan.vbins, L) is not None
                TELEMETRY.counter_add("eval/dense_walk_trees",
                                      len(chunk) * len(chunk[0])
                                      if dense else 0)
                mv = mv[:, :-1]
            if payload.seg_stats is not None and TELEMETRY.level >= 1:
                # 9 int32 a tree that rode out with the tree buffers: no
                # sync of its own, and not a tree fetch (fetch_calls and
                # fetch_bytes count tree buffers alone)
                seg = np.asarray(payload.seg_stats)
                _record_seg_stats(seg, seg.shape[0] * seg.shape[1],
                                  self.grower_params.row_chunk)
            return [(iter_idx + t,
                     [(arrays, payload.shrinkage) for arrays in per_class],
                     gnp[t] if gnp is not None else None,
                     payload.length,
                     mv[t] if mv is not None else None,
                     payload.wall_s if t == 0 else None)
                    for t, per_class in enumerate(chunk)]
        pairs = []
        for (ints_d, floats_d, lr) in payload:
            ints_np, floats_np = np.asarray(ints_d), np.asarray(floats_d)
            TELEMETRY.counter_add("transfer/fetch_calls")
            TELEMETRY.counter_add("transfer/fetch_bytes",
                                  int(ints_np.nbytes)
                                  + int(floats_np.nbytes))
            pairs.append((unpack_tree_buffers(ints_np, floats_np, L), lr))
        return [(iter_idx, pairs,
                 np.asarray(gstats) if fetch_stats else None, 1, None,
                 None)]

    def _materialize_iter(self, pairs):
        """One iteration's [(TreeArrays, shrinkage)] -> (trees, all_const);
        constant outputs become Tree(1) placeholders."""
        trees = []
        all_const = True
        for arrays, lr in pairs:
            if int(arrays.num_leaves) <= 1:
                trees.append(Tree(1))
            else:
                all_const = False
                trees.append(Tree.from_grown(arrays, self.train_set, lr))
        return trees, all_const

    def _apply_valid_scores(self, trees) -> None:
        """Fold freshly-materialized trees into the valid-set score
        buffers.  The per-iteration async path never has valid sets
        attached (train_one_iter routes eager then); this feeds the
        chunked path, whose boundary flush must leave eval_valid
        current."""
        if not self.valid_sets:
            return
        infos = self.train_set.feature_infos()
        for (vname, vset), vscore in zip(self.valid_sets,
                                         self.valid_scores):
            for k, tree in enumerate(trees):
                if tree.num_leaves > 1:
                    vscore[k] += tree.predict_binned(vset.binned, infos)

    def _flush_pending(self, keep_latest: int = 0) -> None:
        """Materialize in-flight trees (oldest first) into self._models.

        A fully-constant iteration means training stopped there: its trees
        and every later pending iteration's are discarded (their score
        deltas undone), matching the reference's drop of the all-constant
        iteration (gbdt.cpp:543-551) — just detected one iteration (or
        chunk) late.

        Two phases an entry: ``fetch_wait`` blocks on the device->host
        copy, ``materialize`` builds the Tree objects on the host.
        """
        while len(self._pending) > keep_latest:
            with _PHASES.phase("fetch_wait"):
                per_iter = self._entry_iter_arrays(self._pending.pop(0))
            with _PHASES.phase("materialize"):
                if self._materialize_entry(per_iter):
                    return

    def _materialize_entry(self, per_iter) -> bool:
        """One fetched entry's iterations into self._models; True when an
        all-constant iteration stopped training."""
        for j, (iter_idx, pairs, gstats, clen, mrow,
                wall) in enumerate(per_iter):
            trees, all_const = self._materialize_iter(pairs)
            if all_const:
                rest = [(ii, self._materialize_iter(pp)[0])
                        for ii, pp, _g, _c, _m, _w in per_iter[j + 1:]]
                self._undo_pending_scores([(iter_idx, trees)] + rest
                                          + self._materialize_rest())
                self._pending = []
                self._stop_flag = True
                self.iter_ = iter_idx
                log_warning("Stopped training because there are no "
                            "more leaves that meet the split "
                            "requirements")
                return True
            self._models.extend(trees)
            self._note_trees(trees)
            self._apply_valid_scores(trees)
            self._health_emit(iter_idx, trees, gstats, clen, wall_s=wall)
            # in-scan eval rows surface only for materialized
            # iterations: tail-of-chunk rows past an all-constant
            # stop are discarded with their trees
            if mrow is not None:
                self._inscan_evals.append((iter_idx, mrow))
                TELEMETRY.counter_add("eval/points", len(mrow))
        return False

    def _note_trees(self, trees) -> None:
        """Record which features the model has split on, feeding the next
        iteration's CEGB coupled penalty (is_feature_used_in_split_,
        serial_tree_learner.h:169 — persists across trees)."""
        if not self.grower_params.use_cegb_coupled:
            return
        changed = False
        for t in trees:
            if t.num_leaves > 1:
                for f in np.unique(t.split_feature_inner[: t.num_leaves - 1]):
                    if not self._cegb_used[f]:
                        self._cegb_used[f] = 1.0
                        changed = True
        if changed:
            self.fmeta = self.fmeta._replace(
                cegb_used0=jnp.asarray(self._cegb_used, dtype=jnp.float32))

    def _materialize_rest(self):
        out = []
        for entry in self._pending:
            for iter_idx, pairs, _g, _c, _m, _w in self._entry_iter_arrays(
                    entry):
                out.append((iter_idx, self._materialize_iter(pairs)[0]))
        return out

    # ------------------------------------------------------- health stream
    def _health_emit(self, iter_idx: int, trees, gstats,
                     chunk_len: int, wall_s=None) -> None:
        """One ``iter`` health record: dispatched chunk size, per-tree
        shape stats, grad/hess diagnostics ([C, 8] from
        ``_grad_stats_core``), the HBM gauge, and — on the chunk's first
        iteration — the dispatch wall window (``dispatch_wall_s``).
        Emitted at tree materialization, so the async pipeline's records
        land in iteration order."""
        if not HEALTH.active:
            return
        rec: Dict[str, Any] = {"iter": int(iter_idx),
                               "chunk": int(chunk_len)}
        # memory tier of the bin matrix (resident / spill), so a live
        # monitor can see an out-of-core escalation mid-run
        rec["data_tier"] = getattr(self, "_data_tier", None) or "resident"
        if wall_s is not None:
            rec["dispatch_wall_s"] = round(float(wall_s), 6)
        tstats = []
        for t in trees:
            nl = int(t.num_leaves)
            n = max(nl - 1, 0)
            gains = np.asarray(t.split_gain[:n], dtype=np.float64)
            tstats.append({
                "leaves": nl,
                "depth": int(np.max(t.leaf_depth[:nl])) if nl > 1 else 0,
                "gain_sum": float(gains.sum()) if n else 0.0,
                "gain_max": float(gains.max()) if n else 0.0,
            })
        rec["trees"] = tstats
        if gstats is not None:
            g = np.asarray(gstats)
            rec["grad"] = {
                "min": [float(v) for v in g[:, 0]],
                "max": [float(v) for v in g[:, 1]],
                "l2": [float(v) for v in g[:, 2]],
                "nonfinite": [int(v) for v in g[:, 3]],
            }
            rec["hess"] = {
                "min": [float(v) for v in g[:, 4]],
                "max": [float(v) for v in g[:, 5]],
                "l2": [float(v) for v in g[:, 6]],
                "nonfinite": [int(v) for v in g[:, 7]],
            }
        hbm = TELEMETRY.memory_gauges()
        if hbm is not None:
            rec["hbm"] = hbm
        HEALTH.record("iter", rec)

    def _undo_pending_scores(self, iter_trees) -> None:
        """Subtract discarded iterations' contributions from train_score
        (rare: only when stop is detected late under bagging randomness)."""
        # the device valid-score carry already includes the discarded
        # trees; drop it and re-upload from the host f64 truth next chunk
        self._vscores_dev = None
        infos = self.train_set.feature_infos()
        for _, trees in iter_trees:
            for k, tree in enumerate(trees):
                if tree.num_leaves > 1:
                    delta = tree.predict_binned(self.train_set.binned, infos)
                    self.train_score = self.train_score.at[k].add(
                        -jnp.asarray(delta, dtype=jnp.float32))

    # ----------------------------------------------------- fault guardrails
    def _poison_scores(self) -> None:
        """grad/nonfinite injection: NaN the score buffer, so the next
        gradient pass (and everything downstream) goes non-finite the
        same way a diverged objective would."""
        self.train_score = self.train_score * jnp.float32(np.nan)

    def _raise_nonfinite(self, first_iter: int, count: int) -> None:
        obj = getattr(self.config, "objective", "?")
        span = (f"iteration {first_iter}" if count <= 1 else
                f"iterations {first_iter}..{first_iter + count - 1}")
        raise LightGBMError(
            f"Non-finite values in the boosted scores at {span} "
            f"(objective={obj}); the ensemble was rolled back to the "
            f"{first_iter} completed iteration(s) before it — check the "
            f"learning_rate/objective "
            f"for divergence, or set check_nonfinite=false to ship the "
            f"model anyway")

    def _scores_finite(self) -> bool:
        """check_nonfinite's read of the score buffer (True with the
        guardrail off).  The read blocks until everything dispatched has
        finished, so ``nonfinite_guard`` is the phase the host waits out
        an iteration's or a chunk's device work in."""
        if not getattr(self.config, "check_nonfinite", True):
            return True
        with _PHASES.phase("nonfinite_guard"):
            return bool(_all_finite(self.train_score))

    def _guard_nonfinite(self, it: int) -> None:
        """Per-iteration finiteness guardrail: on NaN/Inf scores, drop
        the just-trained iteration and raise (check_nonfinite)."""
        if self._scores_finite():
            return
        # settle the async pipeline first: a NaN iteration may grow an
        # all-constant tree, which the flush already discards (lowering
        # iter_ back to ``it``); only a materialized bad iteration needs
        # the explicit rollback
        self._flush_pending()
        if self.iter_ > it:
            self.rollback_one_iter()
        TELEMETRY.fault_event("nonfinite_rollback", site="grad/nonfinite",
                              iteration=it,
                              detail="iteration dropped")
        self._raise_nonfinite(it, 1)

    def _guard_chunk_nonfinite(self, first_iter: int, t: int) -> None:
        """Chunk-boundary guardrail, called BEFORE the chunk's pending
        trees are enqueued: a non-finite score buffer discards the whole
        failing chunk (its buffers never become trees), settles the
        still-good in-flight chunk, and raises."""
        if self._scores_finite():
            return
        self._flush_pending()        # older chunks are still good
        TELEMETRY.fault_event("nonfinite_rollback", site="grad/nonfinite",
                              iteration=first_iter,
                              detail=f"chunk of {t} iterations dropped")
        self._raise_nonfinite(first_iter, t)

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration; returns True if training should stop
        (no further splits possible), matching LGBM_BoosterUpdateOneIter
        semantics.  Wraps the implementation with the check_nonfinite
        guardrail (and its grad/nonfinite injection site)."""
        if self._stop_flag:
            return True
        if FAULTS.check("grad/nonfinite", n=self.iter_):
            self._poison_scores()
        it = self.iter_
        stop = self._train_one_iter_impl(grad, hess)
        # per-iteration dispatch outside a chunk window: the spilled
        # matrix is released per iteration (out-of-core pays one stream
        # per dispatch window, by definition)
        if getattr(self, "_bins_hold", 0) <= 0:
            self._release_bins_window()
        self._guard_nonfinite(it)
        return stop

    def _train_one_iter_impl(self, grad: Optional[np.ndarray] = None,
                             hess: Optional[np.ndarray] = None) -> bool:
        self._boost_from_average()
        C = self.num_tree_per_iteration
        if self.train_set.num_used_features == 0:
            # every feature is trivial (e.g. min_data_in_leaf >= num_data
            # prunes all split points): the reference trains a constant
            # model and stops (gbdt.cpp:543-551) — growing is pointless
            # and the growers assume F >= 1
            self._flush_pending()
            self._models.extend(Tree(1) for _ in range(C))
            self.iter_ += 1
            self._stop_flag = True
            log_warning("Stopped training because there are no more "
                        "leaves that meet the split requirements")
            return True
        use_async = (self._async_trees and not self.valid_sets
                     and (self.objective is None
                          or not self.objective.is_renew_tree_output))
        if (use_async and grad is None and self._fused_ok
                and self.objective is not None):
            return self._train_one_iter_fused()

        with _PHASES.phase("boost"):
            if grad is None or hess is None:
                if self.objective is None:
                    log_fatal("No objective and no custom gradients")
                grads, hesss = self._gradients()
            else:
                grads = jnp.asarray(np.asarray(grad, dtype=np.float32)
                                    .reshape(C, self.num_data))
                hesss = jnp.asarray(np.asarray(hess, dtype=np.float32)
                                    .reshape(C, self.num_data))
            grads, hesss = self._bagging(self.iter_, grads, hesss)
            # health diagnostics only when a stream consumes them — the
            # jitted reduce stays off the default hot path
            gstats = (_grad_stats(grads, hesss) if HEALTH.active
                      else None)

        bins = self._device_bins()
        if use_async:
            items = []
            for k in range(C):
                fmask = self._tree_feature_mask()
                self._key, sub = jax.random.split(self._key)
                g_k, h_k, member = grads[k], hesss[k], self.bag_weight
                if self._row_pad:
                    g_k = jnp.pad(g_k, (0, self._row_pad))
                    h_k = jnp.pad(h_k, (0, self._row_pad))
                    member = jnp.pad(member, (0, self._row_pad))
                with _PHASES.phase("grow"):
                    arrays, leaf_id, *stats = self._grow_fn(
                        bins, g_k, h_k, member, self.fmeta, fmask, sub)
                _maybe_print_seg_stats(stats,
                                       self.grower_params.row_chunk)
                if self._row_pad:
                    leaf_id = leaf_id[: self.num_data]
                with _PHASES.phase("score"):
                    self.train_score = self.train_score.at[k].set(
                        _apply_tree_score(self.train_score[k],
                                          arrays.leaf_value, leaf_id,
                                          jnp.float32(self.shrinkage_rate)))
                ints_d, floats_d = _pack_tree_device(arrays)
                self._start_host_copy(ints_d, floats_d)
                items.append((ints_d, floats_d, self.shrinkage_rate))
            self._pending.append((self.iter_, items, gstats))
            self.iter_ += 1
            # materialize older iterations; the newest stays in flight so
            # its fetch overlaps the next iteration's device work
            with _PHASES.phase("fetch"):
                self._flush_pending(keep_latest=1)
            TELEMETRY.mark_iteration(self.iter_ - 1)
            if self._stop_flag:
                return True
            return False

        should_stop = True
        infos = self.train_set.feature_infos()
        for k in range(C):
            fmask = self._tree_feature_mask()
            self._key, sub = jax.random.split(self._key)
            g_k, h_k, member = grads[k], hesss[k], self.bag_weight
            if self._row_pad:
                g_k = jnp.pad(g_k, (0, self._row_pad))
                h_k = jnp.pad(h_k, (0, self._row_pad))
                member = jnp.pad(member, (0, self._row_pad))
            with _PHASES.phase("grow"):
                arrays, leaf_id, *stats = self._grow_fn(
                    bins, g_k, h_k, member, self.fmeta, fmask, sub)
            _maybe_print_seg_stats(stats, self.grower_params.row_chunk)
            if self._row_pad:
                leaf_id = leaf_id[: self.num_data]
            with _PHASES.phase("fetch"):
                arrays = fetch_tree_arrays(arrays)
            nl = int(arrays.num_leaves)
            if nl <= 1:
                tree = Tree(1)
                self.models.append(tree)
                continue
            should_stop = False
            tree = Tree.from_arrays(arrays, self.train_set)
            # leaf renewal for percentile-fit objectives (L1/quantile/MAPE)
            if (self.objective is not None
                    and self.objective.is_renew_tree_output):
                leaf_np = np.asarray(leaf_id)
                score_np = np.asarray(self.train_score[k], dtype=np.float64)
                tree.set_leaf_values(self.objective.renew_tree_output(
                    tree.leaf_value, leaf_np, score_np))
            tree.apply_shrinkage(self.shrinkage_rate)
            # device score update via the grower's leaf assignment; pad the
            # leaf values to the static num_leaves so _add_tree_score
            # compiles once, not once per distinct tree size
            lv_np = np.zeros(self.grower_params.num_leaves, dtype=np.float32)
            lv_np[:nl] = tree.leaf_value[:nl]
            self.train_score = self.train_score.at[k].set(
                _add_tree_score(self.train_score[k], jnp.asarray(lv_np),
                                leaf_id))
            for (vname, vset), vscore in zip(self.valid_sets,
                                             self.valid_scores):
                vscore[k] += tree.predict_binned(vset.binned, infos)
            self.models.append(tree)

        if should_stop:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            # drop the all-constant iteration (gbdt.cpp:543-551)
            for _ in range(C):
                self.models.pop()
            return True
        self._note_trees(self._models[-C:])
        self.iter_ += 1
        self._health_emit(self.iter_ - 1, self._models[-C:],
                          np.asarray(gstats) if gstats is not None
                          else None, 1)
        TELEMETRY.mark_iteration(self.iter_ - 1)
        return False

    def _train_one_iter_fused(self) -> bool:
        """Async iteration with the whole device pipeline in two jitted
        dispatches (gradients; per-class grow + score update)."""
        C = self.num_tree_per_iteration
        if self._fused_fns is None:
            self._build_fused_step()
        fused_grad, fused_step, fused_roots = self._fused_fns
        with _PHASES.phase("boost"):
            grads, hesss = fused_grad(self.train_score, self._obj_arrs)
            # bagging runs AFTER the gradient dispatch (GOSS's device-side
            # select transforms the gradients; membership-mask baggings
            # ignore them) — same call the eager path makes
            grads, hesss = self._bagging(self.iter_, grads, hesss)
            gstats = (_grad_stats(grads, hesss) if HEALTH.active
                      else None)
        bins = self._device_bins()
        roots = None
        if fused_roots is not None:
            with _PHASES.phase("roots"):
                roots = fused_roots(grads, hesss, self.bag_weight,
                                    bins)
        items = []
        for k in range(C):
            fmask = self._tree_feature_mask()
            # identical key stream to the eager path, so the same seed
            # grows the same trees regardless of which path engages
            self._key, sub = jax.random.split(self._key)
            t0_grow = time.perf_counter()
            # instrumented parallel growers run inside the jitted step,
            # where their own wrapper is trace-time only; the fault
            # probe (collective/reduce_scatter etc.) and the per-tree
            # collective counters both live at this eager dispatch site
            coll_kind = getattr(self._grow_fn, "_collective_kind", None)
            if coll_kind is not None:
                from ..parallel import network
                network.probe_dispatch_collective(coll_kind)
            with _PHASES.phase("grow"):
                extra = () if roots is None else (roots,)
                self.train_score, ints_d, floats_d, stats_t = fused_step(
                    self.train_score, grads, hesss, self.bag_weight,
                    bins, self.fmeta, fmask, sub,
                    jnp.float32(self.shrinkage_rate), jnp.int32(k), *extra)
            if coll_kind is not None:
                from ..parallel import network
                network.record_collective(
                    coll_kind, self._grow_fn._collective_bytes,
                    time.perf_counter() - t0_grow)
            _maybe_print_seg_stats(stats_t, self.grower_params.row_chunk)
            self._start_host_copy(ints_d, floats_d)
            items.append((ints_d, floats_d, self.shrinkage_rate))
        self._pending.append((self.iter_, items, gstats))
        self.iter_ += 1
        with _PHASES.phase("fetch"):
            # CEGB coupled penalties need this iteration's splits noted
            # before the next grow call, so forgo the one-deep pipeline
            keep = 0 if self.grower_params.use_cegb_coupled else 1
            self._flush_pending(keep_latest=keep)
        TELEMETRY.mark_iteration(self.iter_ - 1)
        return bool(self._stop_flag)

    # ---------------------------------------------------------- chunked loop
    @staticmethod
    def _start_host_copy(*bufs) -> None:
        """Kick off the device->host DMA early so the later blocking
        np.asarray finds the bytes already on their way."""
        for buf in bufs:
            copy_async = getattr(buf, "copy_to_host_async", None)
            if copy_async is not None:
                try:
                    copy_async()
                except Exception:
                    pass

    def _chunk_ok(self) -> bool:
        """Whether multiple iterations can run without host interaction
        between them — the conditions under which tpu_boost_chunk
        auto-clamps to 1."""
        cfg = self.config
        if not (self._async_trees and self._fused_ok
                and self._chunk_capable and self.objective is not None):
            return False
        if self.objective.is_renew_tree_output:
            return False        # leaf renewal runs host percentile fits
        if getattr(self, "_mesh", None) is not None:
            return False        # distributed learners keep per-iter dispatch
        if cfg.feature_fraction < 1.0:
            return False        # per-tree host RNG (GetUsedFeatures)
        if cfg.bagging_freq > 0 and (cfg.bagging_fraction < 1.0
                                     or cfg.pos_bagging_fraction < 1.0
                                     or cfg.neg_bagging_fraction < 1.0):
            return False        # per-iteration host bagging re-draw
        if (self.grower_params.use_cegb_coupled
                or self.grower_params.use_cegb_lazy):
            return False        # split bookkeeping feeds the next grow
        if seg_stats_enabled():
            return False        # per-iteration counter printing
        return True

    def boost_chunk_size(self) -> int:
        """Resolved tpu_boost_chunk: an explicit value wins; auto (0)
        chunks on the TPU backend — where every dispatch and fetch pays a
        transport round-trip — and stays at 1 elsewhere.  Always 1 when
        the run needs host interaction between iterations (_chunk_ok)."""
        if self.train_set is None or not self._chunk_ok():
            return 1
        req = int(self.config.tpu_boost_chunk)
        if req != 0:
            return max(1, req)
        return 16 if jax.default_backend() == "tpu" else 1

    def train_chunk(self, chunk: int) -> bool:
        """Run up to ``chunk`` boosting iterations as ONE device program
        (lax.scan over the fused step), deferring every device->host tree
        fetch to the chunk boundary, where it overlaps the next chunk's
        device work.  Falls back to train_one_iter when the configuration
        needs host interaction mid-chunk.  Returns True when training
        stopped.

        Always trains exactly ``chunk`` iterations (the engine/CLI step
        accounting assumes it) unless training stops: a chunk dispatch
        that dies with RESOURCE_EXHAUSTED is retried at half the size,
        down to per-iteration dispatch, and the degraded ceiling sticks
        for the rest of the run (_chunk_cap).  Sub-chunk splitting is
        bit-exact — the chunk body consumes the same PRNG key stream at
        any chunk size."""
        T = int(chunk)
        if self._stop_flag:
            return True
        if ((T <= 1 and self._inscan is None) or not self._chunk_ok()
                or self.train_set.num_used_features == 0):
            return self.train_one_iter()
        self._boost_from_average()
        done = 0
        # one spill window per train_chunk call: the streamed matrix is
        # held across the dispatch loop and released on every exit path
        self._bins_hold = getattr(self, "_bins_hold", 0) + 1
        try:
            while done < T:
                if self._stop_flag:
                    return True
                cap = self._chunk_cap
                t = T - done if cap is None else min(T - done, cap)
                if t <= 1 and self._inscan is None:
                    try:
                        # per-iteration fallback still probes the OOM
                        # site: a persistent allocator failure must
                        # reach the next rung (spill) or the actionable
                        # give-up error, not silently complete
                        if FAULTS.enabled:
                            FAULTS.maybe_raise("chunk/oom", oom_error)
                        stop = self.train_one_iter()
                    except Exception as e:
                        if not _is_oom_error(e):
                            raise
                        if self._escalate_spill(e):
                            continue               # retry out-of-core
                        raise self._oom_exhausted(e)  # out of headroom
                    if stop:
                        return True
                    done += 1
                    continue
                try:
                    self._dispatch_chunk(t)
                except Exception as e:
                    if not _is_oom_error(e):
                        raise
                    if t <= 1:
                        # in-scan runs keep the scan path even at chunk
                        # 1; the chunk ladder has no smaller dispatch —
                        # the spill tier is the only rung left
                        if self._escalate_spill(e):
                            continue
                        raise self._oom_exhausted(e)
                    self._degrade_chunk(t, e)
                    continue                       # retry at the new cap
                done += t
            return bool(self._stop_flag)
        finally:
            self._bins_hold -= 1
            if self._bins_hold <= 0:
                self._release_bins_window()

    def _dispatch_chunk(self, t: int) -> None:
        """Dispatch one fused chunk of ``t`` iterations and enqueue its
        tree buffers.  Hosts the grad/nonfinite and chunk/oom injection
        sites and the chunk-boundary finiteness guardrail."""
        if FAULTS.enabled:
            for i in range(self.iter_, self.iter_ + t):
                if FAULTS.check("grad/nonfinite", n=i):
                    self._poison_scores()
                    break
            FAULTS.maybe_raise("chunk/oom", oom_error)
        inscan = self._inscan
        fn = self._get_chunk_fn(t, with_eval=inscan is not None)
        shr = self._shr_dev.get(self.shrinkage_rate)
        if shr is None:
            # device-resident constant: materialized OUTSIDE the guarded
            # dispatch so the chunk body itself stays transfer-free
            shr = jnp.float32(self.shrinkage_rate)
            self._shr_dev[self.shrinkage_rate] = shr
        if inscan is not None and self._vscores_dev is None:
            # (re-)upload the valid-score carry from the host f64 truth;
            # OUTSIDE the guarded region — this is a legitimate h2d copy
            self._vscores_dev = [
                jnp.asarray(np.asarray(vs, dtype=np.float32))
                for vs in self.valid_scores]
        first_iter = self.iter_
        # spill tier: reassemble the device matrix here, OUTSIDE the
        # transfer-guarded region below (streaming is a legitimate h2d
        # copy, like the vscores re-upload above)
        bins = self._device_bins()
        if inscan is not None:
            args = (self.train_score, self._key, self._vscores_dev,
                    self.bag_weight, bins, self.fmeta,
                    self._full_fmask, shr, self._obj_arrs,
                    inscan.vbins, inscan.arrays)
        else:
            args = (self.train_score, self._key, self.bag_weight,
                    bins, self.fmeta, self._full_fmask, shr,
                    self._obj_arrs)
        mvals_all = None
        # the chunk's dispatch wall window: host dispatch time by
        # default, wall-to-ready when device_timing syncs inside the
        # CostJit seam — carried into the health stream's iter records
        t0_wall = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("chunk",
                                              step_num=first_iter), \
                _PHASES.phase("chunk"):
            if self._chunk_guard is not None:
                with self._chunk_guard():
                    out = fn(*args)
            else:
                out = fn(*args)
            if inscan is not None:
                (self.train_score, self._key, self._vscores_dev, ints_all,
                 floats_all, gstats_all, mvals_all, seg_all) = out
            else:
                (self.train_score, self._key, ints_all, floats_all,
                 gstats_all, seg_all) = out
        wall_s = time.perf_counter() - t0_wall
        # before the chunk's buffers can become trees: a non-finite score
        # discards them and raises (older pending chunks stay good)
        self._guard_chunk_nonfinite(first_iter, t)
        seg_stats = seg_all[0] if seg_all else None
        self._start_host_copy(ints_all, floats_all, gstats_all, mvals_all,
                              seg_stats)
        self._pending.append((self.iter_, _PendingChunk(
            ints_all, floats_all, self.shrinkage_rate, t, mvals_all,
            wall_s, seg_stats), gstats_all))
        self.iter_ += t
        with _PHASES.phase("fetch"):
            # valid-set scores update at materialization, and eval at the
            # chunk boundary needs the chunk just dispatched — so forgo
            # the one-chunk-deep pipeline when valid sets are attached
            keep = 0 if (self.valid_sets or inscan is not None) else 1
            self._flush_pending(keep_latest=keep)
        TELEMETRY.gauge_set("boost/chunk_size", t)
        TELEMETRY.mark_iteration(self.iter_ - 1, count=t)

    def _degrade_chunk(self, t: int, err: BaseException) -> None:
        """Halve the chunk-size ceiling after an OOM-shaped dispatch
        failure, or give up (with the HBM picture) when retry is
        impossible because the dispatch consumed its donated carries."""
        if self._donated_carries_deleted():
            # donate_argnums handed the score/key/vscore buffers to
            # the failed execution; there is no state left to retry
            self._spill_unavail = ("the failed dispatch consumed its "
                                   "donated score/key carries; no device "
                                   "state left to retry from")
            raise self._oom_exhausted(err)
        # conservatively re-upload the valid-score carry: partial
        # execution may have touched it even when not deleted
        self._vscores_dev = None
        self._chunk_cap = max(1, t // 2)
        log_warning(f"chunk dispatch of {t} iterations failed with "
                    f"RESOURCE_EXHAUSTED; retrying at chunk size "
                    f"{self._chunk_cap} (ceiling sticks for this run)")
        TELEMETRY.fault_event("oom_degrade", site="chunk/oom",
                              iteration=self.iter_,
                              detail=f"chunk {t} -> {self._chunk_cap}")

    def _oom_exhausted(self, err: BaseException) -> LightGBMError:
        """The actionable give-up error once every rung of the recovery
        ladder is spent: names the iteration, the NEXT rung that could
        not be taken (so failures at the true ceiling are diagnosable),
        and the peak-HBM figure from the telemetry memory section
        (PR 3) when the backend reports one."""
        mem = TELEMETRY.stats().get("memory") or {}
        peak, limit = mem.get("peak_bytes_in_use"), mem.get("bytes_limit")
        if peak:
            hbm = f"; peak HBM {peak / 1e9:.2f} GB"
            if limit:
                hbm += f" of {limit / 1e9:.2f} GB limit"
        else:
            hbm = "; peak HBM unavailable (backend reports no memory stats)"
        if getattr(self, "_data_tier", "resident") == "spill":
            rung = ("; next rung: none — the bin matrix is already "
                    "streaming from host memory (out-of-core tier)")
        else:
            reason = (getattr(self, "_spill_unavail", None)
                      or "escalation was not attempted")
            rung = f"; next rung: spill unavailable: {reason}"
        return LightGBMError(
            f"device out of memory at iteration {self.iter_} even at "
            f"chunk size 1{rung}{hbm} — reduce num_leaves/max_bin or "
            f"shard the data across more devices ({err})")

    def refit(self, leaf_preds: np.ndarray) -> None:
        """Refit leaf outputs on the current training data given per-row
        leaf assignments [N, num_trees] (GBDT::RefitTree via
        LGBM_BoosterRefit, reference c_api.cpp)."""
        self._flush_pending()
        from .refit import refit_model
        refit_model(self, self.train_set.metadata, np.asarray(leaf_preds),
                    self.config)

    def rollback_one_iter(self) -> None:
        """Remove the last iteration's trees and scores (gbdt.cpp:553-576)."""
        self._flush_pending()
        if self.iter_ <= 0:
            return
        C = self.num_tree_per_iteration
        infos = self.train_set.feature_infos()
        for k in reversed(range(C)):
            tree = self.models.pop()
            if tree.num_leaves > 1:
                delta = tree.predict_binned(self.train_set.binned, infos)
                self.train_score = self.train_score.at[k].add(
                    -jnp.asarray(delta, dtype=jnp.float32))
                for (vname, vset), vscore in zip(self.valid_sets,
                                                 self.valid_scores):
                    vscore[k] -= tree.predict_binned(vset.binned, infos)
        self.iter_ -= 1
        # host f64 buffers are now the truth; the device carry is stale
        self._vscores_dev = None

    # ------------------------------------------------------------ prediction
    def current_iteration(self) -> int:
        # flush in-flight trees first: a trailing all-constant iteration is
        # detected (and iter_ lowered) only at materialization time
        self._flush_pending()
        return self.iter_

    @property
    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration

    def _raw_predict(self, X: np.ndarray, num_iteration: int = -1,
                     start_iteration: int = 0) -> np.ndarray:
        self._flush_pending()
        C = self.num_tree_per_iteration
        n_iter = self.iter_ if num_iteration <= 0 else min(num_iteration,
                                                           self.iter_)
        out = np.zeros((C, X.shape[0]), dtype=np.float64)
        for k in range(C):
            out[k] += self.init_scores[k]
        cfg = self.config
        freq = int(cfg.pred_early_stop_freq)
        # the reference only instantiates early stop for binary/multiclass
        # predictors; regression and ranking need every tree
        es_type_ok = (C > 1 or (self.objective is not None
                                and getattr(self.objective, "name", "")
                                in ("binary", "cross_entropy", "xentropy")))
        if bool(cfg.pred_early_stop) and freq > 0 and es_type_ok:
            # margin-based per-row early stop every `freq` trees
            # (prediction_early_stop.cpp:54-73 binary margin = 2|raw|,
            # :30-49 multiclass margin = top1 - top2)
            thr = float(cfg.pred_early_stop_margin)
            active = np.ones(X.shape[0], dtype=bool)
            for it in range(start_iteration, n_iter):
                if not active.any():
                    break
                Xa = X[active]
                for k in range(C):
                    out[k, active] += self.models[it * C + k].predict_raw(Xa)
                if (it + 1 - start_iteration) % freq == 0:
                    sub = out[:, active]
                    if C == 1:
                        margin = 2.0 * np.abs(sub[0])
                    else:
                        top2 = np.partition(sub, C - 2, axis=0)
                        margin = top2[-1] - top2[-2]
                    idx = np.nonzero(active)[0]
                    active[idx[margin > thr]] = False
            return out
        for it in range(start_iteration, n_iter):
            for k in range(C):
                out[k] += self.models[it * C + k].predict_raw(X)
        return out

    def _device_route_ok(self) -> bool:
        """Whether batch prediction may use the compiled stacked-tensor
        route (models/device_predict.py) instead of the host tree walk.
        Gated by the ``predict_device`` knob ("auto" = accelerator only —
        on CPU the jit round-trip would cost more than the walk), and
        requires the training BinMappers (file-loaded boosters without a
        bound dataset fall back) plus bin-aligned trees.  Per-row early
        stopping (pred_early_stop) is host-only by design."""
        pd = str(getattr(self.config, "predict_device", "off"))
        if pd == "off":
            return False
        if pd == "auto":
            try:
                if jax.default_backend() == "cpu":
                    return False
            except Exception:
                return False
        ds = getattr(self, "train_set", None)
        if ds is None or not getattr(ds, "bin_mappers", None) \
                or len(getattr(ds, "used_feature_indices", ())) == 0:
            return False
        cfg = self.config
        C = self.num_tree_per_iteration
        es_type_ok = (C > 1 or (self.objective is not None
                                and getattr(self.objective, "name", "")
                                in ("binary", "cross_entropy", "xentropy")))
        if (bool(cfg.pred_early_stop) and int(cfg.pred_early_stop_freq) > 0
                and es_type_ok):
            return False
        return all(getattr(t, "bins_aligned", True) for t in self.models)

    def _device_raw_predict(self, X: np.ndarray,
                            num_iteration: int = -1) -> np.ndarray:
        """[C, N] f64 raw scores via device routing, bit-identical to
        ``_raw_predict``: bins come from the exact host ``value_to_bin``,
        the device returns per-tree leaf INDICES, and the float64 leaf
        values are gathered host-side in the host walk's accumulation
        order.  Rows are padded to a power-of-two bucket so repeated
        predict calls reuse a handful of executables."""
        from .device_predict import stack_trees
        ds = self.train_set
        used = np.asarray(ds.used_feature_indices)
        C = self.num_tree_per_iteration
        n_iter = self.iter_ if num_iteration <= 0 else min(num_iteration,
                                                           self.iter_)
        trees = self.models[: n_iter * C]
        N = X.shape[0]
        bins = np.empty((N, len(used)), dtype=np.int32)
        for j, f in enumerate(used):
            m = ds.bin_mappers[int(f)]
            col = X[:, int(f)]
            b = m.value_to_bin(col)
            if m.is_categorical:
                # unseen categories -> -1 sentinel (value_to_bin's
                # num_bin-1 aliases a real bin); the router sends
                # negative categorical bins right like the float walk
                iv = np.where(np.isfinite(col), col, -1).astype(np.int64)
                if m.categorical_2_bin:
                    cats = np.fromiter(m.categorical_2_bin.keys(),
                                       dtype=np.int64)
                    seen = np.isin(iv, cats) & (iv >= 0)
                else:
                    seen = np.zeros(len(iv), dtype=bool)
                b = np.where(seen, b, -1)
            bins[:, j] = b
        bucket = 8
        while bucket < N:
            bucket <<= 1
        if bucket > N:
            bins = np.concatenate(
                [bins, np.zeros((bucket - N, bins.shape[1]),
                                dtype=np.int32)])
        stack = stack_trees(trees, len(used))
        num_bin = jnp.asarray([ds.bin_mappers[int(f)].num_bin
                               for f in used], dtype=jnp.int32)
        default_bin = jnp.asarray([ds.bin_mappers[int(f)].default_bin
                                   for f in used], dtype=jnp.int32)
        fn = _route_seam(stack.max_depth)
        leaves = np.asarray(fn(stack._replace(max_depth=None),
                               jnp.asarray(bins), num_bin,
                               default_bin))[:, :N]
        out = np.zeros((C, N), dtype=np.float64)
        for k in range(C):
            out[k] += self.init_scores[k]
        for t, tree in enumerate(trees):
            out[t % C] += tree.leaf_value[leaves[t]]
        return out

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False) -> np.ndarray:
        self._flush_pending()
        X = np.asarray(X, dtype=np.float64)
        if X.ndim == 1:
            X = X[None, :]
        C = self.num_tree_per_iteration
        if pred_leaf:
            n_iter = self.iter_ if num_iteration <= 0 else min(num_iteration,
                                                               self.iter_)
            leaves = np.zeros((X.shape[0], n_iter * C), dtype=np.int32)
            for i in range(n_iter * C):
                leaves[:, i] = self.models[i].apply_raw(X)
            return leaves
        if self._device_route_ok():
            raw = self._device_raw_predict(X, num_iteration)
        else:
            raw = self._raw_predict(X, num_iteration)
        if getattr(self, "average_output", False):
            n_iter = self.iter_ if num_iteration <= 0 else min(num_iteration,
                                                               self.iter_)
            raw = raw / max(n_iter, 1)
        if raw_score or self.objective is None:
            res = raw
        else:
            res = self.objective.convert_output(raw)
        if C == 1:
            return res[0]
        return res.T  # [N, C]

    # ------------------------------------------------------------------ eval
    def setup_metrics(self, metric_names: Sequence[str]) -> None:
        """Instantiate metrics for train + each valid set
        (GBDT::AddValidDataset / Init metric wiring, gbdt.cpp:49-130)."""
        from ..metric import create_metric
        self.metrics = []
        for name in metric_names:
            m = create_metric(name, self.config)
            if m is not None and self.train_set is not None:
                m.init(self.train_set.metadata, self.train_set.num_data)
                self.metrics.append(m)
        self.valid_metrics = []
        for (vname, vset) in self.valid_sets:
            ms = []
            for name in metric_names:
                m = create_metric(name, self.config)
                if m is not None:
                    m.init(vset.metadata, vset.num_data)
                    ms.append(m)
            self.valid_metrics.append(ms)

    def _eval_score(self, score: np.ndarray, metrics) -> List[Tuple]:
        out = []
        s = score[0] if (score.ndim > 1 and score.shape[0] == 1) else score
        for m in metrics:
            if hasattr(m, "eval_multi"):
                for k, v in zip(m.eval_at, m.eval_multi(s, self.objective)):
                    out.append((f"{m.name}@{k}", float(v), m.higher_better))
            else:
                out.append((m.name, float(m.eval(s, self.objective)),
                            m.higher_better))
        return out

    def eval_train(self) -> List[Tuple]:
        score = np.asarray(self.train_score, dtype=np.float64)
        return self._eval_score(score, self.metrics)

    def eval_valid(self, i: int) -> List[Tuple]:
        return self._eval_score(np.asarray(self.valid_scores[i]),
                                self.valid_metrics[i])

    # ------------------------------------------------------- in-scan eval
    def setup_inscan_eval(self, include_train: bool = False):
        """Try to attach a device-side eval program (metric/device.py) so
        the chunked scan computes the attached metrics per iteration.
        Returns None on success, or a short blocker string ("feval",
        "metric:<name>", "objective:<name>", "not_chunk_capable", ...)
        when the run must fall back to per-iteration host eval."""
        self._inscan = None
        self._vscores_dev = None
        self._inscan_evals = []
        # drop any stale eval-variant compilations (they close over the
        # previous DeviceEval program)
        self._chunk_fns = {k: v for k, v in self._chunk_fns.items()
                           if not isinstance(k, tuple)}
        if not self._chunk_ok():
            return "not_chunk_capable"
        from ..metric.device import build_device_eval
        prog, blocker = build_device_eval(self, include_train)
        if prog is None:
            return blocker
        self._inscan = prog
        # the rows every grown tree is walked over: with eval/walk_levels,
        # the walk's work
        TELEMETRY.gauge_set("eval/valid_rows",
                            sum(int(vb.shape[0]) for vb in prog.vbins))
        return None

    def inscan_result_list(self, vals) -> List[Tuple]:
        """One in-scan metric row -> the eval_train/eval_valid result
        shape: [(set_name, metric_name, value, higher_better)]."""
        return [(sname, mname, float(v), hb)
                for (sname, mname, hb), v in zip(self._inscan.columns,
                                                 vals)]

    def take_inscan_evals(self) -> List[Tuple]:
        """Pop the per-iteration metric rows materialized so far:
        [(iter_idx, np.ndarray[n_cols])], oldest first."""
        out = self._inscan_evals
        self._inscan_evals = []
        return out

    # ----------------------------------------------------------- importances
    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        """split counts or total gains per original feature
        (gbdt.h FeatureImportance)."""
        self._flush_pending()
        n_feat = self.max_feature_idx + 1
        out = np.zeros(n_feat, dtype=np.float64)
        C = self.num_tree_per_iteration
        n_iter = self.iter_ if iteration <= 0 else min(iteration, self.iter_)
        for tree in self.models[: n_iter * C]:
            n = tree.num_leaves - 1
            for i in range(n):
                f = int(tree.split_feature[i])
                if importance_type == "split":
                    out[f] += 1
                else:
                    out[f] += max(float(tree.split_gain[i]), 0.0)
        return out
