"""Segment grower: leaf-wise growth with per-split cost proportional to
leaf size.

The fused grower (grower.py) scans the FULL dataset for every split's
histogram, so a 255-leaf tree costs 254 full passes — the reference instead
pays O(leaf size) per split by keeping each leaf's rows contiguous
(DataPartition, src/treelearner/data_partition.hpp:111; OrderedBin
re-sorting, src/io/ordered_sparse_bin.hpp).  TPUs can't afford a physical
re-partition per split (data-dependent scatter), so this grower uses
*epoch compaction*:

  * rows live in a permuted order (``order[pos] -> original row``); each
    time the kernels have accumulated over a budget of blocks
    (``compaction_budget_blocks``: 2 to 9 tables, by what a compaction
    costs at the shape against a full pass) the whole layout is
    re-sorted by ``leaf_id``, with one stable variadic ``lax.sort`` or,
    for wide tables, an argsort and gathers (``compact_state``);
  * between compactions rows never move, so every leaf's rows stay
    *confined* to the block interval its nearest compacted ancestor
    occupied — descendants only refine within it;
  * each split's smaller-child histogram runs the scalar-prefetched
    pallas segment kernel (ops/pallas_histogram.histogram_segment) over
    just that confinement interval: DMA, compute and the kernel's grid
    scale with the interval;
  * where the shape leaves lane sets free (``lookahead_width``), that
    pass also fills them with the smaller-child histograms that the
    PENDING best splits of other leaves inside the interval will need,
    so a later split that finds its histogram ready only routes
    (``lookahead_split``: the same best-first tree from about half the
    scans).

Everything — splits, routing, compaction — is one ``lax.fori_loop`` inside
one jit; no host round-trips during growth.  Exact leaf-wise: the grown
tree is the same as the fused grower's up to histogram summation order.

Requires the pallas backend (feature-major [F, Npad] bins); serial learner
only — the distributed learners keep the fused grower for now.
"""

from __future__ import annotations

import math
import os as _os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_histogram import (NUM_CHANNELS, empty_lookahead_slots,
                                    feature_tile, fused_route_decisions,
                                    fused_route_policy,
                                    histogram_segment,
                                    histogram_segment_lookahead,
                                    histogram_segment_routed,
                                    lookahead_width, null_route,
                                    pack_channels, pack_lookahead_slots,
                                    pack_route,
                                    route_kernel_available, route_window,
                                    unpack_hist, unpack_nibble)
from ..ops.split import (NEG_INF, FeatureMeta, best_split, expand_group_hist,
                         reconstruct_feature_column, routed_left)
from .grower import (CommHooks, GrowerParams, TreeArrays,
                     _node_feature_mask, mono_handoff)

# above this many sort operands, compact via argsort + matrix gathers:
# XLA's variadic TPU sort compile time explodes with operand count
# (measured on v5e 2026-08-01: 12 operands at 56k rows = 94 s compile;
# the 39-operand sort a 136-feature dataset produces never finished
# inside a 70-minute budget and took the whole lambdarank-suite tier
# with it).  The gather path compiles in seconds; which of the two is
# cheaper a row depends on the table's width (compaction_unit_costs).
_MAX_SORT_OPERANDS = 16


def _sort_operands(bin_rows: int) -> int:
    """Operands of the compaction's variadic sort for a table of
    ``bin_rows`` physical bin rows: the key, the bin words (4 rows a
    word), the six live weight channels as 3 halfword pairs, the order."""
    return 1 + bin_rows // 4 + 3 + 1


# -- the compaction trigger's budget -----------------------------------
# Adaptive compaction: the layout is re-sorted once the histogram kernels
# have accumulated over ``compaction_budget_blocks`` blocks of confinement
# intervals since the last compaction.  Fixed leaf-count milestones let
# waste balloon on skewed trees (best-first growth keeps splitting inside
# one big segment); a scanned-blocks budget bounds it whatever the tree.
# The budget follows from two unit costs of the shape, one full
# accumulating pass (N) and one compaction (c x N):
#   * A pass covers the PARENT's confinement interval, so every level of
#     the tree costs ~1 N of scanning however often one compacts (~11 N a
#     255-leaf tree at best), and a lookahead lane set is only filled for
#     a pending leaf whose interval lies wholly inside the pass: right
#     after a compaction every leaf is alone in its interval, so early
#     compaction COSTS lookahead hits.  Under ~2 N the first epoch
#     re-sorts a table of one or two leaves.
#   * Replayed on the trees of epsilon63-train (tools/compaction_replay.py,
#     which reproduces the chip's seg/* counters tree for tree; PERF.md
#     section 6, PR 33) the best budget is ~2.25 N at c = 0.1, 3 N at 0.33,
#     4.5 N at 1-2, 7 N at 3 and 9 N from 4 up, each optimum flat over a
#     factor of two: 2.5 + 1.6 c tables, held between 2 and 9.
#   * 9 N is the knee of the chip sweeps where a compaction costs several
#     passes (c ~ 7.7 at 36.75M x 28 x 64, the 12-operand sort): one
#     compaction a tree, and nothing on the chip has timed more.
# The constants are a TPU v5e's (PERF.md sections 5 and 6; timed alone
# by tools/seg_pass_bench.py --unit-costs, my chip runs, PR 33).
_MXU_MACS_PER_NS = 98.5e3       # 197 TFLOP/s in bf16
# of that, in a full pass with every lane set live: 0.83-0.85 where the
# table goes whole (2.82 ns a row at 28 x 64, 4.37 at 44, 4.73 at 48),
# 0.92 in 16 feature tiles (185.5 ns a row at 2000 x 64)
_PASS_MXU_SHARE = 0.87
# the variadic sort with its word packing, a row and operand at 2**25
# rows; it grows as log2(rows)**2 (20.7 ns a row at 12 operands and
# 36.77M rows in higgs63-train's trace, 22.8 at 16 operands and 10.5M)
_SORT_NS_ROW_OPERAND = 1.707
# the gathers that follow the two-operand sort, alone on the chip: a row
# (49.6 ns at 48 columns and 10.5M rows, all but 3.5 of them here) and a
# byte of it (64.5 ns a row at 2,064 bytes and 1.1M rows)
_GATHER_NS_ROW = 46.0
_GATHER_NS_BYTE = 0.008
_BUDGET_BASE_N = 2.5
_BUDGET_N_PER_COST = 1.6
_BUDGET_MIN_N, _BUDGET_MAX_N = 2.0, 9.0


def table_bin_rows(columns: int, num_bins: int, packed4: bool) -> int:
    """Physical bin rows of the table as ``grow`` holds it: padded to
    whole sort words, or to whole feature tiles where a pass walks tiles."""
    tile = feature_tile(columns, num_bins)
    phys = (columns + 1) // 2 if packed4 else columns
    unit = (tile // 2 if packed4 else tile) if tile < columns else 4
    return -(-phys // unit) * unit


def compaction_unit_costs(columns: int, num_bins: int, rows: int,
                          packed4: bool) -> dict:
    """The trigger's two unit costs at this shape, in ns a row: a full
    accumulating pass (the padded-lane MXU model the kernel is measured
    against: every feature tile's columns x bins x 128 lanes of
    multiply-adds a row) and one ``compact_state`` on the ``path`` it
    takes for this width."""
    tile = feature_tile(columns, num_bins)
    pass_ns = (-(-columns // tile) * tile * num_bins * 128
               / _MXU_MACS_PER_NS / _PASS_MXU_SHARE)
    bin_rows = table_bin_rows(columns, num_bins, packed4)
    operands = _sort_operands(bin_rows)
    sort_ns = _SORT_NS_ROW_OPERAND * (math.log2(max(rows, 2)) / 25.0) ** 2
    if operands <= _MAX_SORT_OPERANDS:
        path, compact_ns = "sort", sort_ns * operands
    else:
        # the permutation's two-operand sort, then every byte of the row
        # (bins, six bf16 channels, order) gathered once
        path = "gather"
        compact_ns = (2 * sort_ns + _GATHER_NS_ROW
                      + _GATHER_NS_BYTE * (bin_rows + 12 + 4))
    return {"path": path, "pass_ns_per_row": pass_ns,
            "compaction_ns_per_row": compact_ns}


def compaction_budget_blocks(columns: int, num_bins: int, rows: int,
                             block_rows: int, packed4: bool) -> int:
    """Blocks the kernels may accumulate over between two compactions
    (a Python int, baked into the epoch loops' predicates at trace time):
    the more passes a compaction costs, the more tables of scanning go
    before one, between ``_BUDGET_MIN_N`` and ``_BUDGET_MAX_N``."""
    cost = compaction_unit_costs(columns, num_bins, rows, packed4)
    c = cost["compaction_ns_per_row"] / cost["pass_ns_per_row"]
    budget_n = min(max(_BUDGET_BASE_N + _BUDGET_N_PER_COST * c,
                       _BUDGET_MIN_N), _BUDGET_MAX_N)
    # compared against an i32 counter
    return min(max(1, int(budget_n * (rows // block_rows))), 2**31 - 1)


class SegStats(NamedTuple):
    """The growers' third jit output, slot by slot: an i32 vector a tree
    (one row a device under the data-parallel wrappers), fixed in width
    so every grower and wrapper agrees.  A slot a grower does not report
    stays 0 (``seg_stats_vector``): the lookahead counters where no lane
    sets run, the shape facts on the frontier grower."""
    scanned_blocks: object      # blocks that entered an accumulate
    compactions: object
    grid_steps: object          # kernel grid steps of accumulating passes
    max_blocks: object          # blocks of the whole table
    compact_budget: object      # blocks accumulated between compactions
    batch_k: object             # leaves split a round (1: strict)
    splits: object
    lookahead_hits: object      # splits served by a lookahead histogram
    lookahead_filled: object
    route_only_blocks: object
    feature_tiles: object       # tiles a pass walks (1: the table whole)
    leaf_hist_kib: object       # the per-leaf histogram tables


SEG_STATS_SLOTS = len(SegStats._fields)


def seg_stats_vector(**slots) -> jax.Array:
    """[SEG_STATS_SLOTS] i32 from the slots a grower reports, by name."""
    unknown = set(slots) - set(SegStats._fields)
    assert not unknown, unknown
    return jnp.stack([jnp.asarray(slots.get(f, 0), jnp.int32)
                      for f in SegStats._fields])


def seg_stats_columns(stats) -> SegStats:
    """A grower's counters (one vector, or rows of them a tree and
    device) as named columns on the host."""
    import numpy as np
    return SegStats(*np.asarray(stats).reshape(-1, SEG_STATS_SLOTS).T)


def seg_stats_enabled() -> bool:
    """When LIGHTGBM_TPU_SEG_STATS is set, the counters the growers
    return (``SegStats``) are printed per tree."""
    return bool(_os.environ.get("LIGHTGBM_TPU_SEG_STATS"))


def print_seg_stats(stats) -> None:
    """Host-side rendering of the counters a grower returned (compiled
    code carries no host callbacks, so this replaces the old
    jax.debug.print).  Accepts one [SEG_STATS_SLOTS] vector or a
    per-device concatenation of them.

    ``grid`` counts the kernel grid steps dispatched by accumulating
    passes, once for every feature tile that walks the interval."""
    import sys

    import numpy as np

    rows = np.asarray(stats).reshape(-1, SEG_STATS_SLOTS)
    for d, r in enumerate(map(SegStats._make, rows)):
        dev = f" dev{d}" if len(rows) > 1 else ""
        nb = max(int(r.max_blocks), 1)
        extra = ""
        if r.lookahead_filled:
            extra += (f", lookahead {int(r.lookahead_hits)} of "
                      f"{int(r.splits)} splits served "
                      f"({int(r.lookahead_filled)} filled, "
                      f"{int(r.route_only_blocks)} route-only blocks)")
        if r.feature_tiles > 1:
            extra += f", {int(r.feature_tiles)} feature tiles a pass"
        sys.stderr.write(
            f"seg stats{dev}: scanned {int(r.scanned_blocks)} blocks "
            f"({r.scanned_blocks / nb:.1f} N-equivalents), "
            f"grid {int(r.grid_steps)} steps "
            f"({r.grid_steps / nb:.1f} N-equivalents), "
            f"{int(r.compactions)} compactions, K={int(r.batch_k)}{extra}\n")
    sys.stderr.flush()


class _SegState(NamedTuple):
    binsT: jax.Array           # [F4, Npad] u8/i8, permuted
    w8: jax.Array              # [8, Npad] bf16 channels, permuted
    order: jax.Array           # [Npad] i32: pos -> original row
    leaf_id: jax.Array         # [Npad] i32 (permuted space)
    leaf_lo: jax.Array         # [L] i32 confinement start block
    leaf_hi: jax.Array         # [L] i32 confinement end block (exclusive)
    # blocks scanned by histogram kernels since the last compaction /
    # in total (adaptive-compaction accounting + perf introspection)
    scanned_since: jax.Array   # i32 scalar
    scanned_total: jax.Array   # i32 scalar
    grid_total: jax.Array      # i32 scalar: kernel grid steps dispatched
    num_sorts: jax.Array       # i32 scalar
    num_leaves: jax.Array
    leaf_hist: jax.Array       # [L, F, B, 3]
    leaf_g: jax.Array
    leaf_h: jax.Array
    leaf_c: jax.Array
    leaf_mono_lo: jax.Array    # [L] monotone output bounds
    leaf_mono_hi: jax.Array
    feat_used: jax.Array       # [F] CEGB coupled bookkeeping
    # best-split cache, PACKED so every scan writes 3 rows instead of 11
    # scalar scatters (each in-loop dynamic-update-slice costs fixed
    # overhead on TPU): f32 [L, 6] = (gain, left_g, left_h, left_c,
    # left_out, right_out); i32 [L, 4] = (feature, threshold,
    # default_left, is_cat); bitset [L, 8] u32
    best_f32: jax.Array
    best_i32: jax.Array
    best_cat_bitset: jax.Array
    tree: TreeArrays
    # lookahead histograms (strict grower, lookahead_split): the smaller
    # child that leaf l's cached best split will make, filled by another
    # leaf's pass over an interval holding all of l's rows.  An entry
    # belongs to the split it was filled for: valid from the fill until l
    # is split, whatever compactions fall between (they move rows, not
    # sums), and cleared then, so the child that keeps l's id starts
    # without one.  No rows / never set where the mechanism is off.
    look_hist: jax.Array       # [L, G, B, 3] f32 ([0, G, B, 3] when off)
    look_ok: jax.Array         # [L] bool
    # i32 scalars: splits committed, those served by a lookahead
    # histogram, histograms filled, blocks that were routed only
    num_splits: jax.Array
    look_hits: jax.Array
    look_filled: jax.Array
    route_only: jax.Array


def _pack_bins_words(binsT):
    """[F4, N] u8 -> [F4//4, N] i32 (4 features per word) for sort payload."""
    F4, n = binsT.shape
    b = binsT.astype(jnp.uint32).reshape(F4 // 4, 4, n)
    w = b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24)
    return w.astype(jnp.int32)


def _unpack_bins_words(words, dtype):
    W, n = words.shape
    u = words.astype(jnp.uint32)
    parts = [(u >> (8 * j)) & 0xFF for j in range(4)]
    return jnp.stack(parts, axis=1).reshape(W * 4, n).astype(dtype)


def _pack_w8_words(w8):
    """[8, N] bf16 -> [3, N] i32 for sort payload.

    Channels 5-7 are structurally zero (pack_channels pads g_hi/g_lo/
    h_hi/h_lo/member to 8 for the kernel's channel tile), so only 3 of
    the 4 halfword-pair words carry information — carrying the zero word
    through the multi-operand compaction sort was pure payload waste."""
    u = lax.bitcast_convert_type(w8, jnp.uint16).astype(jnp.uint32)  # [8,N]
    return (u[0:6:2] | (u[1:6:2] << 16)).astype(jnp.int32)


# lax.cond narrowing: a cond whose branches pass large arrays through
# unchanged still names them as branch OUTPUTS, and the merge can
# materialize copies of them every iteration (binsT is ~336 MB and w8
# ~168 MB at 10.5M rows — the round-4 trace measured 0.77 s/iter of such
# copies when the compact cond sat inside the per-split loop).  Each cond
# therefore carries ONLY the fields its true branch mutates — everything
# else reaches the branch as a closure capture — and the strict grower
# additionally keeps every remaining cond off the per-split path (epoch
# structure below).
_COMPACT_MUT = ("binsT", "w8", "order", "leaf_id", "leaf_lo", "leaf_hi",
                "scanned_since", "num_sorts")


def _take(st: _SegState, fields) -> tuple:
    return tuple(getattr(st, f) for f in fields)


def _put(st: _SegState, fields, vals) -> _SegState:
    return st._replace(**dict(zip(fields, vals)))


def cond_narrow(pred, fn, st: _SegState, fields) -> _SegState:
    """st -> lax.cond(pred, fn, identity, st) with the cond's carried
    operands narrowed to ``fields``."""
    rest = tuple(f for f in _SegState._fields if f not in fields)

    def true_branch(m):
        full_in = _put(st, fields, m)
        full_out = fn(full_in)
        # trace-time drift guard: a mutation to a non-carried field would
        # be silently DISCARDED by the narrowing — an untouched field is
        # the identical tracer object, so this fails loudly instead
        for f in rest:
            leaves_in = jax.tree_util.tree_leaves(getattr(full_in, f))
            leaves_out = jax.tree_util.tree_leaves(getattr(full_out, f))
            assert all(a is b for a, b in zip(leaves_in, leaves_out)), (
                f"cond_narrow: branch mutated non-carried field {f!r}; "
                f"add it to the mut list")
        return _take(full_out, fields)

    out = lax.cond(pred, true_branch, lambda m: m, _take(st, fields))
    return _put(st, fields, out)


def _segment_buckets(max_blocks: int) -> list:
    """Static window-size ladder of the XLA windowed route: a slice width
    is static but a leaf's confinement interval is data-dependent, so
    ``route_split_windowed`` lax.switches between a few widths, each an
    eighth of the one above, and takes the smallest covering the
    interval."""
    buckets = []
    b = max_blocks
    while b > 1:
        buckets.append(b)
        b = max(1, b // 8)
    buckets.append(1)
    return sorted(set(buckets))


def bucket_index(bucket_list, n_blocks) -> jax.Array:
    """Index of the smallest ladder bucket covering an ``n_blocks``-long
    interval."""
    nb = jnp.asarray(n_blocks, jnp.int32).reshape(())
    return jnp.minimum(jnp.sum(jnp.asarray(bucket_list, jnp.int32) < nb),
                       len(bucket_list) - 1)


def route_split_windowed(binsT, leaf_id, fmeta, packed4, rb,
                         f, t, dl, cat, bitset, leaf, new_leaf,
                         lo, n_blk):
    """Post-split ``leaf_id`` update confined to the parent's block
    interval — the routing half of the reference's O(leaf-size) split
    (DataPartition::Split, src/treelearner/data_partition.hpp:111).

    The parent's rows are confined to blocks [lo, lo+n_blk) (module
    docstring), so rows outside the window cannot match ``leaf`` and a
    full-N where() pass is pure waste — 254 of them per tree were the
    bulk of the growers' ~0.8 s/iter constant at 10.5M rows (round-4
    micro: route_pass ~51 ms/full-N vs 27 ms for a whole histogram
    pass).  The window is picked from the static ``_segment_buckets``
    ladder: ``lax.switch`` over a few dynamic-slice widths, smallest
    bucket covering the interval.  The
    window may over-cover (block granularity + bucket rounding + end
    clamping); rows of other leaves inside it fail the ``== leaf`` test
    and pass through unchanged.
    """
    n = leaf_id.shape[0]
    max_blocks = n // rb
    buckets = _segment_buckets(max_blocks)
    col = f if fmeta.feat_group is None else fmeta.feat_group[f]
    row = col // 2 if packed4 else col

    def make_branch(bs):
        S = bs * rb

        def br(lid):
            start = jnp.clip(lo * rb, 0, n - S).astype(jnp.int32)
            fwin = lax.dynamic_slice(binsT, (row, start), (1, S))[0]
            if packed4:
                fwin = unpack_nibble(fwin, col)
            fwin = reconstruct_feature_column(fwin, f, fmeta)
            go_left = routed_left(fwin, t, dl, cat, bitset,
                                  fmeta.missing_type[f],
                                  fmeta.default_bin[f], fmeta.num_bin[f])
            lwin = lax.dynamic_slice(lid, (start,), (S,))
            lwin = jnp.where((lwin == leaf) & ~go_left, new_leaf, lwin)
            return lax.dynamic_update_slice(lid, lwin, (start,))
        return br

    if len(buckets) == 1:
        return make_branch(buckets[0])(leaf_id)
    idx = bucket_index(buckets, n_blk)
    return lax.switch(idx, [make_branch(b) for b in buckets], leaf_id)


def apply_route(binsT, leaf_id, fmeta, packed4, rb, f, t, dl, cat,
                bitset, leaf, new_leaf, lo, n_blk, use_kernel: bool):
    """One split's confined leaf_id update, through the aliased pallas
    window kernel when available (writes only the window's blocks; the
    XLA switch path below materializes a full-N leaf_id per call —
    measured 0.18 s/iter of conditional copies at the HIGGS shape) or
    the XLA windowed path otherwise."""
    if use_kernel:
        route = pack_route(leaf, new_leaf, f, t, dl, cat, bitset, fmeta,
                           packed4)
        return route_window(binsT, leaf_id, lo, n_blk, route, rb,
                            packed4=packed4)
    return route_split_windowed(binsT, leaf_id, fmeta, packed4, rb, f, t,
                                dl, cat, bitset, leaf, new_leaf, lo, n_blk)


def stripe_histogram(binsT, start, ncols, kernel_fn, feat_axis: int):
    """Feature-parallel stripe scatter shared by the strict and frontier
    growers: histogram a column SLICE of the bin matrix, then place the
    result back at its offset in a zero tensor (the scan masks hide the
    zero columns).  ``kernel_fn(sub)`` maps the [ncols, N] slice to a
    histogram whose feature axis is ``feat_axis``."""
    sub = lax.dynamic_slice_in_dim(binsT, start, ncols, axis=0)
    part = kernel_fn(sub)
    shape = (part.shape[:feat_axis] + (binsT.shape[0],)
             + part.shape[feat_axis + 1:])
    out = jnp.zeros(shape, part.dtype)
    return lax.dynamic_update_slice_in_dim(out, part, start,
                                           axis=feat_axis)


def _unpermute(order, leaf_id):
    """leaf_id (permuted space) -> original row order.

    ``order[pos] -> original row`` is a permutation, so sorting
    (order, leaf_id) by order is an exact inverse permute.  The obvious
    ``zeros.at[order].set(leaf_id)`` is a full-N random SCATTER — the op
    class the round-3 sort-vs-gather micro measured ~10x slower than
    multi-operand sorts on this backend — and it runs once per tree, so
    the sort formulation keeps the unpermute off the per-iteration
    critical path."""
    return lax.sort((order, leaf_id), num_keys=1)[1]


def compact_state(st: _SegState, L: int, rb: int) -> _SegState:
    """Stable-sort the whole layout by leaf_id; leaves become contiguous
    segments and confinement intervals reset to them.  Shared by the
    strict and frontier growers (identical _SegState layout)."""
    W = st.binsT.shape[0] // 4
    wrows = 3       # the six live channels as halfword pairs
    if _sort_operands(st.binsT.shape[0]) <= _MAX_SORT_OPERANDS:
        operands = ((st.leaf_id,)
                    + tuple(_pack_bins_words(st.binsT))
                    + tuple(_pack_w8_words(st.w8))
                    + (st.order,))
        sorted_ops = lax.sort(operands, num_keys=1, is_stable=True)
        lid = sorted_ops[0]
        binsT = _unpack_bins_words(jnp.stack(sorted_ops[1:1 + W]),
                                   st.binsT.dtype)
        w8 = _unpack_w8_words(jnp.stack(sorted_ops[1 + W:1 + W + wrows]))
        order = sorted_ops[1 + W + wrows]
    else:
        # wide-feature path: 2-operand stable sort for the permutation,
        # then one gather per array (columns move as whole vectors)
        n = st.leaf_id.shape[0]
        lid, perm = lax.sort(
            (st.leaf_id, jnp.arange(n, dtype=jnp.int32)),
            num_keys=1, is_stable=True)
        with jax.named_scope("compact_gather"):
            # ``perm`` is a permutation: promised in bounds, the gather
            # builds no table-sized mask to fill the rows that miss
            def move(x):
                return x.at[..., perm].get(unique_indices=True,
                                           mode="promise_in_bounds")

            binsT = move(st.binsT)
            # channels 6-7 are structurally zero (pack_channels) — move
            # only the live ones, refill the rest (same trim the sort
            # path makes)
            w8 = jnp.concatenate(
                [move(st.w8[:6]),
                 jnp.zeros((st.w8.shape[0] - 6, st.w8.shape[1]),
                           st.w8.dtype)])
            order = move(st.order)
    leaves = jnp.arange(L, dtype=jnp.int32)
    starts = jnp.searchsorted(lid, leaves, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(lid, leaves, side="right").astype(jnp.int32)
    # block-granular bounds; empty/unused leaves get an empty interval
    leaf_lo = jnp.where(ends > starts, starts // rb, 0)
    leaf_hi = jnp.where(ends > starts, -(-ends // rb), 0)
    return st._replace(binsT=binsT, w8=w8, order=order, leaf_id=lid,
                       leaf_lo=leaf_lo, leaf_hi=leaf_hi,
                       scanned_since=jnp.int32(0),
                       num_sorts=st.num_sorts + 1)


def fresh_state(binsT, w8, n, L, G_cols, B, F, max_blocks, G0, H0, C0,
                fmeta, p, lookahead: bool = False) -> _SegState:
    """Initial _SegState + TreeArrays for a new tree (root covers
    everything).  Shared by the strict and frontier growers;
    ``lookahead`` sizes the lookahead-histogram table (else no rows)."""
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
    return _SegState(
        binsT=binsT, w8=w8,
        order=jnp.arange(n, dtype=jnp.int32),
        leaf_id=jnp.zeros(n, dtype=jnp.int32),
        leaf_lo=jnp.zeros(L, dtype=jnp.int32),
        leaf_hi=jnp.zeros(L, dtype=jnp.int32).at[0].set(max_blocks),
        scanned_since=jnp.int32(0),
        scanned_total=jnp.int32(0),
        grid_total=jnp.int32(0),
        num_sorts=jnp.int32(0),
        num_leaves=jnp.int32(1),
        leaf_hist=jnp.zeros((L, G_cols, B, 3), dtype=jnp.float32),
        leaf_g=zeros_l.at[0].set(G0),
        leaf_h=zeros_l.at[0].set(H0),
        leaf_c=zeros_l.at[0].set(C0),
        leaf_mono_lo=jnp.full(L, -jnp.inf, dtype=jnp.float32),
        leaf_mono_hi=jnp.full(L, jnp.inf, dtype=jnp.float32),
        feat_used=(fmeta.cegb_used0
                   if (p.use_cegb_coupled and fmeta.cegb_used0 is not None)
                   else jnp.zeros(F, dtype=jnp.float32)),
        best_f32=jnp.zeros((L, 6), dtype=jnp.float32).at[:, 0].set(neg),
        best_i32=jnp.zeros((L, 4), dtype=jnp.int32).at[:, 0].set(-1),
        best_cat_bitset=jnp.zeros((L, 8), dtype=jnp.uint32),
        tree=tree0,
        look_hist=jnp.zeros((L if lookahead else 0, G_cols, B, 3),
                            dtype=jnp.float32),
        look_ok=jnp.zeros(L, dtype=bool),
        num_splits=jnp.int32(0),
        look_hits=jnp.int32(0),
        look_filled=jnp.int32(0),
        route_only=jnp.int32(0),
    )


def _unpack_w8_words(words):
    """[3, N] i32 -> [8, N] bf16 (channels 5-7 restored as zeros)."""
    u = words.astype(jnp.uint32)
    lo = (u & 0xFFFF).astype(jnp.uint16)
    hi = (u >> 16).astype(jnp.uint16)
    inter = jnp.stack([lo, hi], axis=1).reshape(6, -1)
    ch6 = lax.bitcast_convert_type(inter, jnp.bfloat16)
    return jnp.concatenate(
        [ch6, jnp.zeros((NUM_CHANNELS - 6, ch6.shape[1]), jnp.bfloat16)])


def _pinned_row(table, i):
    """``(table[i], table)`` for a per-leaf table in the split loop's
    carry, with the read pinned ahead of every write of the table: the
    row is materialised here and the writes go to the barrier's table
    (why: ``grow``'s comment on the epoch loops)."""
    return lax.optimization_barrier(
        (lax.dynamic_index_in_dim(table, i, 0, keepdims=False), table))


def _lookahead_pending(st: _SegState, leaf, lo, hi) -> jax.Array:
    """[L] f32: the cached gain of every leaf whose smaller-child
    histogram a pass over blocks [lo, hi) for ``leaf``'s split may fill,
    NEG_INF elsewhere.  Such a leaf is open, is not the one being split,
    has a split worth making (gain > 0), holds no lookahead histogram
    yet, and its confinement interval lies WHOLLY inside [lo, hi):
    containment in blocks, not overlap, since after a compaction
    neighbours share a boundary block and a pass over one of them sees
    only part of the other."""
    L = st.look_ok.shape[0]
    gain = st.best_f32[:, 0]
    ok = ((st.leaf_lo >= lo) & (st.leaf_hi <= hi)
          & (st.leaf_hi > st.leaf_lo) & ~st.look_ok & (gain > 0.0)
          & (jnp.arange(L, dtype=jnp.int32) != leaf))
    return jnp.where(ok, gain, NEG_INF)


def make_grow_tree_segment(num_bins: int, params: GrowerParams,
                           block_rows: int, comm: CommHooks = CommHooks(),
                           wrap=None):
    """Build the jitted segment grower.

    Returned ``grow(binsT, grad, hess, member, fmeta, feature_mask, key)``
    takes feature-major bins [F, Npad] (Npad a multiple of block_rows; pad
    rows must carry member == 0) and returns ``(TreeArrays,
    leaf_id_original_order)`` exactly like the fused grower.

    ``comm`` hooks make this the data-parallel learner's core under
    ``shard_map`` (rows sharded; per-leaf cost stays O(leaf) per shard):
    ``reduce_hist`` runs on every leaf histogram, ``reduce_stats`` on the
    root scalars, ``merge_split`` on every per-leaf SplitInfo.
    """
    p = params
    L = p.num_leaves
    B = num_bins
    rb = block_rows
    # fused route+histogram: the split's leaf_id update rides the
    # smaller-child histogram pass instead of separate XLA passes over
    # the same blocks (self-checked on the live backend at build time).
    # Feature-parallel stripes (column_block) keep the unfused pair: the
    # histogram scans a column SLICE while the route needs the full
    # matrix (the winning split may live on another shard's stripe).
    fused_route = (fused_route_policy(1, p.num_columns or 64, B, rb,
                                      p.packed4) == "k1"
                   and comm.column_block is None)
    fused_route_decisions["segment"] = fused_route
    route_kernel = route_kernel_available()
    # lookahead lane sets (lookahead_split below): the serial learner's
    # fused split path only.  Under reduce_hist every lookahead histogram
    # would cross the wire, and voting and the feature stripes never run
    # the fused split path.  How many lane sets comes from the shape
    # (lookahead_width, at trace time): 1 builds today's program.
    lookahead_ok = (fused_route and not comm.no_subtract
                    and comm.reduce_hist is None)

    def hist_leaf(st: _SegState, leaf, G_cols, fmeta=None,
                  look_k: int = 1):
        """Returns (hist [G,B,3], blocks scanned)."""
        lo = st.leaf_lo[leaf]
        n_blk = st.leaf_hi[leaf] - lo
        if look_k > 1:
            # the lookahead kernel with every slot empty: the tree's one
            # Mosaic compile, and the root summed as the splits are
            _, outs = histogram_segment_lookahead(
                st.binsT, st.w8, st.leaf_id, lo, n_blk, leaf, null_route(),
                empty_lookahead_slots(look_k - 1), n_blk, B, rb,
                packed4=p.packed4)
            return unpack_hist(outs[0, :G_cols]), n_blk
        if comm.column_block is not None:
            # feature-parallel: histogram only this shard's column
            # stripe (the reference histograms only the rank's own
            # features, feature_parallel_tree_learner.cpp:36-75)
            start, ncols = comm.column_block(st.binsT)
            out = stripe_histogram(
                st.binsT, start, ncols,
                lambda sub: histogram_segment(sub, st.w8, st.leaf_id, lo,
                                              n_blk, leaf, B, rb,
                                              packed4=p.packed4),
                feat_axis=0)
        elif fused_route and not comm.no_subtract:
            # same kernel as the split path (one Mosaic compile), with a
            # match-nothing route; the aliased leaf_id passes through.
            # no_subtract comms never run the fused split path, so they
            # keep the plain kernel instead of paying the route's lid
            # write-back for nothing.
            _, out = histogram_segment_routed(
                st.binsT, st.w8, st.leaf_id, lo, n_blk, leaf,
                null_route(), B, rb, packed4=p.packed4)
        else:
            out = histogram_segment(st.binsT, st.w8, st.leaf_id, lo,
                                    n_blk, leaf, B, rb, packed4=p.packed4)
        h = unpack_hist(out[:G_cols])
        if comm.reduce_hist is not None:
            h = comm.reduce_hist(h, None, None, None, fmeta)
        return h, n_blk

    def _one_scan(hist, g, h, c, depth, fmeta, fmask, key, step,
                  lo, hi, feat_used):
        fmask_node = _node_feature_mask(fmask, key, step, p)
        if comm.shard_feature_mask is not None:
            fmask_node = comm.shard_feature_mask(fmask_node)
        adjust = None
        if p.cegb_penalty_split > 0.0 or p.use_cegb_coupled:
            from .grower import _cegb_split_coupled_adjust
            adjust = _cegb_split_coupled_adjust(feat_used, c, fmeta, p)
        # EFB: group-space histogram -> per-feature view
        hist = expand_group_hist(hist, fmeta, g, h, c)
        info = best_split(hist, g, h, c, fmeta, p.split, fmask_node,
                          mono_lo=lo if p.use_monotone else None,
                          mono_hi=hi if p.use_monotone else None,
                          gain_adjust=adjust)
        gain = info.gain
        if comm.merge_split is not None:
            info, gain = comm.merge_split(info, gain)
        if p.max_depth > 0:
            gain = jnp.where(depth >= p.max_depth, NEG_INF, gain)
        return info, gain

    def _write_scans(st: _SegState, leaf_idx, infos, gains):
        """leaf_idx/gains [k], infos batched SplitInfo; 3 packed scatters."""
        f32 = jnp.stack([gains, infos.left_g, infos.left_h, infos.left_c,
                         infos.left_out, infos.right_out],
                        axis=-1).astype(jnp.float32)
        i32 = jnp.stack([infos.feature, infos.threshold,
                         infos.default_left.astype(jnp.int32),
                         infos.is_cat.astype(jnp.int32)], axis=-1)
        return st._replace(
            best_f32=st.best_f32.at[leaf_idx].set(f32),
            best_i32=st.best_i32.at[leaf_idx].set(i32),
            best_cat_bitset=st.best_cat_bitset.at[leaf_idx].set(
                infos.cat_bitset),
        )

    def scan_leaf(st: _SegState, leaf_idx, hist, g, h, c, depth, fmeta,
                  fmask, key, step):
        info, gain = _one_scan(hist, g, h, c, depth, fmeta, fmask, key,
                               step, st.leaf_mono_lo[leaf_idx],
                               st.leaf_mono_hi[leaf_idx], st.feat_used)
        leaves = jnp.asarray(leaf_idx, jnp.int32)[None]
        batched = jax.tree_util.tree_map(lambda x: x[None], info)
        return _write_scans(st, leaves, batched, gain[None])

    def scan_pair(st: _SegState, leaves2, hists2, g2, h2, c2, depth, fmeta,
                  fmask, key, steps2):
        """Both children of a split evaluated in ONE vmapped scan — halves
        the per-split chain of small ops vs two sequential scans."""
        infos, gains = jax.vmap(
            lambda hh, g, h, c, s, blo, bhi: _one_scan(
                hh, g, h, c, depth, fmeta, fmask, key, s, blo, bhi,
                st.feat_used)
        )(hists2, g2, h2, c2, steps2, st.leaf_mono_lo[leaves2],
          st.leaf_mono_hi[leaves2])
        return _write_scans(st, leaves2, infos, gains)

    @jax.named_scope("compact")
    def compact(st: _SegState) -> _SegState:
        return compact_state(st, L, rb)

    def grow(binsT, grad, hess, member, fmeta: FeatureMeta, feature_mask,
             key, root_hist=None):
        # G_cols = logical bin-matrix columns (EFB groups); F = logical
        # features (fmeta/feature_mask space); binsT rows are PHYSICAL
        # (half of G_cols under 4-bit packing).
        # ``root_hist`` [G, B, 3], when given, replaces the root's own
        # full-data scan (multiclass batched roots: GBDT computes every
        # class-tree's root histogram in ONE kernel pass).  Serial only —
        # the distributed wrappers never pass it.
        n_phys, n = binsT.shape
        G_cols = p.num_columns or (2 * n_phys if p.packed4 else n_phys)
        F = fmeta.num_bin.shape[0]
        assert n % rb == 0, (n, rb)
        max_blocks = n // rb
        # feature tiles a pass of the routed segment kernels walks (1: the
        # table whole, today's program).  Those kernels alone walk tiles.
        tile_cols = feature_tile(G_cols, B)
        n_tiles = -(-G_cols // tile_cols)
        if n_tiles > 1 and not (fused_route and not comm.no_subtract):
            raise ValueError(
                f"{G_cols} columns x {B} bins take {n_tiles} feature tiles, "
                f"which only the fused route+histogram kernels of the "
                f"serial segment grower walk; they are off here")
        # pad physical rows to a multiple of 4 for the sort word packing,
        # or to whole feature tiles (GBDT uploads the table so padded: no
        # copy is made here)
        fpad = (-n_phys) % ((tile_cols // 2 if p.packed4 else tile_cols)
                            if n_tiles > 1 else 4)
        if fpad:
            binsT = jnp.pad(binsT, ((0, fpad), (0, 0)))

        # grid-step accounting: a call's grid is its interval's blocks
        # (one masked step for an empty interval), once a feature tile
        def grid_of(nb):
            return n_tiles * jnp.maximum(nb, 1)

        with jax.named_scope("quantize_pack"):
            w8 = pack_channels(grad, hess, member)
        G0 = jnp.sum(grad * member)
        H0 = jnp.sum(hess * member)
        C0 = jnp.sum(member)
        if comm.reduce_stats is not None:
            # allreduce of the root (cnt, sum_g, sum_h) tuple
            # (data_parallel_tree_learner.cpp:311-357)
            G0, H0, C0 = (comm.reduce_stats(G0), comm.reduce_stats(H0),
                          comm.reduce_stats(C0))

        # lane sets a split pass fills; a tree of L leaves never has more
        # than L - 2 leaves pending beside the one being split
        look_k = (min(lookahead_width(G_cols, B, rb, p.packed4), L - 1)
                  if lookahead_ok else 1)

        def lookahead_split(st: _SegState, leaf, smaller, route, lo, hi):
            """The fused split pass with lookahead lane sets.  Returns
            (state, routed ids, smaller child's histogram, blocks that
            accumulated).

            hit: an earlier pass left the smaller child of ``leaf``'s
            cached split in ``look_hist`` — the call only routes (n_acc
            0: one kernel, one call site, no cond carrying the bin matrix
            on the per-split path).  miss: the pass accumulates, and its
            other lane sets take the pending leaves inside the interval
            (``_lookahead_pending``), highest cached gain first.  A
            leaf's cached split and its count are written once, when its
            histogram is scanned, so the side picked here (do_split's own
            ``Cl <= Cr``) is the side do_split will want, and the rows
            are a function of the data alone."""
            look_row, look_hist = _pinned_row(st.look_hist, leaf)
            hit = st.look_ok[leaf]
            pending = jnp.where(hit, NEG_INF,
                                _lookahead_pending(st, leaf, lo, hi))
            gains, cand = lax.top_k(pending, look_k - 1)
            live = gains > 0.0
            ci, cf = st.best_i32[cand], st.best_f32[cand]
            slots = pack_lookahead_slots(
                jnp.where(live, cand, -1),
                cf[:, 3] <= st.leaf_c[cand] - cf[:, 3],
                ci[:, 0], ci[:, 1], ci[:, 2], ci[:, 3],
                st.best_cat_bitset[cand], fmeta, p.packed4)
            blk = jnp.where(hit, 0, hi - lo)
            leaf_id, outs = histogram_segment_lookahead(
                st.binsT, st.w8, st.leaf_id, lo, hi - lo, smaller, route,
                slots, blk, B, rb, packed4=p.packed4)
            hists = unpack_hist(outs[:, :G_cols])
            hist_small = jnp.where(hit, look_row, hists[0])
            put = jnp.where(live, cand, L)          # L: dropped
            st = st._replace(
                look_hist=look_hist.at[put].set(hists[1:], mode="drop"),
                # the entry of ``leaf`` was its own split's and is spent:
                # the child that keeps the id starts without one
                look_ok=(st.look_ok.at[put].set(True, mode="drop")
                         .at[leaf].set(False)),
                look_hits=st.look_hits + hit.astype(jnp.int32),
                look_filled=st.look_filled + jnp.sum(live, dtype=jnp.int32),
                route_only=st.route_only + jnp.where(hit, hi - lo, 0))
            return st, leaf_id, hist_small, blk

        def do_split(st: _SegState):
            # split ordinal (feature_fraction_bynode key folding); the
            # epoch-while structure has no fori index, but num_leaves-1
            # counts splits identically
            step = st.num_leaves - 1
            leaf = jnp.argmax(st.best_f32[:, 0]).astype(jnp.int32)
            new_leaf = st.num_leaves
            node = st.num_leaves - 1

            bi = st.best_i32[leaf]
            bf = st.best_f32[leaf]
            f = bi[0]
            t = bi[1]
            dl = bi[2].astype(bool)
            cat = bi[3].astype(bool)
            bitset = st.best_cat_bitset[leaf]

            # children inherit the parent's confinement interval; routing
            # only needs to touch that window
            lo, hi = st.leaf_lo[leaf], st.leaf_hi[leaf]
            Gl, Hl, Cl = bf[1], bf[2], bf[3]
            Gp, Hp, Cp = st.leaf_g[leaf], st.leaf_h[leaf], st.leaf_c[leaf]
            Gr, Hr, Cr = Gp - Gl, Hp - Hl, Cp - Cl
            smaller_is_left = Cl <= Cr
            smaller = jnp.where(smaller_is_left, leaf, new_leaf)

            if fused_route and not comm.no_subtract:
                # route + smaller-child histogram in ONE kernel pass over
                # the parent interval (histogram_segment_routed)
                with jax.named_scope("hist_split"):
                    route = pack_route(leaf, new_leaf, f, t, dl, cat,
                                       bitset, fmeta, p.packed4)
                    if look_k > 1:
                        st, leaf_id, hist_small, blk = lookahead_split(
                            st, leaf, smaller, route, lo, hi)
                    else:
                        leaf_id, out = histogram_segment_routed(
                            st.binsT, st.w8, st.leaf_id, lo, hi - lo,
                            smaller, route, B, rb, packed4=p.packed4)
                        hist_small = unpack_hist(out[:G_cols])
                        blk = hi - lo
                if comm.reduce_hist is not None:
                    hist_small = comm.reduce_hist(hist_small, None, None,
                                                  None, fmeta)
            else:
                with jax.named_scope("route"):
                    leaf_id = apply_route(
                        st.binsT, st.leaf_id, fmeta, p.packed4, rb,
                        f, t, dl, cat, bitset, leaf, new_leaf, lo, hi - lo,
                        route_kernel)

            st = st._replace(
                leaf_id=leaf_id,
                leaf_lo=st.leaf_lo.at[new_leaf].set(lo),
                leaf_hi=st.leaf_hi.at[new_leaf].set(hi),
            )
            # monotone constraint handoff (serial_tree_learner.cpp:892-903)
            if p.use_monotone:
                lo_l, hi_l, lo_r, hi_r = mono_handoff(
                    st.leaf_mono_lo[leaf], st.leaf_mono_hi[leaf],
                    bf[4], bf[5],
                    fmeta.monotone[f], cat)
                st = st._replace(
                    leaf_mono_lo=st.leaf_mono_lo
                    .at[leaf].set(lo_l).at[new_leaf].set(lo_r),
                    leaf_mono_hi=st.leaf_mono_hi
                    .at[leaf].set(hi_l).at[new_leaf].set(hi_r),
                )
            if p.use_cegb_coupled:
                st = st._replace(feat_used=st.feat_used.at[f].set(1.0))

            leaf_hist = st.leaf_hist
            if comm.no_subtract:
                # voting-parallel: each call's election masks differ, so
                # parent-minus-smaller is invalid (CommHooks doc) — build
                # BOTH children from data over the same interval
                with jax.named_scope("hist_split"):
                    hist_left, _b1 = hist_leaf(st, leaf, G_cols, fmeta)
                    hist_right, _b2 = hist_leaf(st, new_leaf, G_cols,
                                                fmeta)
                blk = _b1 + _b2
                grid_blk = grid_of(_b1) + grid_of(_b2)
            else:
                if not fused_route:
                    with jax.named_scope("hist_split"):
                        hist_small, blk = hist_leaf(st, smaller, G_cols,
                                                    fmeta)
                # a pass that only routed accumulated over no grid step
                grid_blk = (jnp.where(blk > 0, grid_of(blk), 0)
                            if look_k > 1 else grid_of(blk))
                hist_parent, leaf_hist = _pinned_row(leaf_hist, leaf)
                hist_large = hist_parent - hist_small
                hist_left = jnp.where(smaller_is_left, hist_small,
                                      hist_large)
                hist_right = jnp.where(smaller_is_left, hist_large,
                                       hist_small)
            # the epoch-while predicates gate on scanned_since, so it must
            # be shard-uniform under the distributed wrappers (CommHooks
            # doc); scanned_total stays the shard-local truth for stats
            blk_u = (comm.uniform_scan(blk)
                     if comm.uniform_scan is not None else blk)
            st = st._replace(scanned_since=st.scanned_since + blk_u,
                             scanned_total=st.scanned_total + blk,
                             grid_total=st.grid_total + grid_blk)
            leaf_hist = (leaf_hist.at[leaf].set(hist_left)
                         .at[new_leaf].set(hist_right))

            depth_child = st.tree.leaf_depth[leaf] + 1
            tree = st.tree
            parent = tree.leaf_parent[leaf]
            pl_ = jnp.where((parent >= 0)
                            & (tree.left_child[jnp.maximum(parent, 0)]
                               == ~leaf),
                            node, tree.left_child[jnp.maximum(parent, 0)])
            pr = jnp.where((parent >= 0)
                           & (tree.right_child[jnp.maximum(parent, 0)]
                              == ~leaf),
                           node, tree.right_child[jnp.maximum(parent, 0)])
            left_child = tree.left_child.at[jnp.maximum(parent, 0)].set(pl_)
            right_child = tree.right_child.at[jnp.maximum(parent, 0)].set(pr)
            left_child = left_child.at[node].set(~leaf)
            right_child = right_child.at[node].set(~new_leaf)

            out_l = bf[4]
            out_r = bf[5]
            tree = tree._replace(
                num_leaves=st.num_leaves + 1,
                split_feature=tree.split_feature.at[node].set(f),
                threshold_bin=tree.threshold_bin.at[node].set(t),
                default_left=tree.default_left.at[node].set(dl),
                is_cat=tree.is_cat.at[node].set(cat),
                cat_bitset=tree.cat_bitset.at[node].set(bitset),
                left_child=left_child,
                right_child=right_child,
                split_gain=tree.split_gain.at[node].set(bf[0]),
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
                num_leaves=st.num_leaves + 1,
                num_splits=st.num_splits + 1,
                leaf_hist=leaf_hist,
                leaf_g=st.leaf_g.at[leaf].set(Gl).at[new_leaf].set(Gr),
                leaf_h=st.leaf_h.at[leaf].set(Hl).at[new_leaf].set(Hr),
                leaf_c=st.leaf_c.at[leaf].set(Cl).at[new_leaf].set(Cr),
                tree=tree,
            )
            with jax.named_scope("split_scan"):
                st = scan_pair(
                    st, jnp.stack([leaf, new_leaf]),
                    jnp.stack([hist_left, hist_right]),
                    jnp.stack([Gl, Gr]), jnp.stack([Hl, Hr]),
                    jnp.stack([Cl, Cr]), depth_child, fmeta, feature_mask,
                    key, jnp.stack([2 * step, 2 * step + 1]))
            return st

        # adaptive compaction (module docstring): amortize the sort against
        # the histogram DMA it saves.  Structured as EPOCH loops — an
        # inner while that splits unconditionally until the scan budget is
        # spent, and an outer loop that compacts between epochs.  The
        # round-3 form (one fori_loop whose body wrapped do_split and
        # compact in per-split lax.conds) made XLA materialize the conds'
        # carried operands through the identity branches every split:
        # the compact cond alone copied binsT+w8+order+leaf_id (~590 MB
        # at 10.5M rows) 254x/tree — 0.77 s/iter of pure copy in the
        # round-4 v5e profiler trace.  With the split work in
        # the loop PREDICATE instead of a cond, nothing is copied; the
        # compact cond now executes once per epoch (~#compactions/tree).
        # The same class of copy, inside do_split: a row read from a table
        # in the split loop's carry (leaf_hist[leaf], look_hist[leaf]) is
        # taken before any write of that table and pinned (_pinned_row).
        # A read the compiler can sink into a writer costs two copies of
        # the table a split, and a relayout each way where the scatter
        # keeps its own layout: four copies of 392 MB a split at 2000
        # columns x 64 bins, 1.77 s of a 5.35 s iteration (ledger, PR 30;
        # tests/test_tpu_compile.py holds the loop to none).
        limit_blocks = compaction_budget_blocks(G_cols, B, n, rb, p.packed4)

        def can_grow(st: _SegState):
            return (st.num_leaves < L) & (jnp.max(st.best_f32[:, 0]) > 0.0)

        def epoch(st: _SegState) -> _SegState:
            st = lax.while_loop(
                lambda s: can_grow(s) & (s.scanned_since < limit_blocks),
                do_split, st)
            # compact only when another epoch follows (skip the pointless
            # final sort when growth ended mid-epoch)
            st = cond_narrow(can_grow(st)
                             & (st.scanned_since >= limit_blocks),
                             compact, st, _COMPACT_MUT)
            return st

        st = fresh_state(binsT, w8, n, L, G_cols, B, F, max_blocks,
                         G0, H0, C0, fmeta, p, lookahead=look_k > 1)
        if root_hist is None:
            with jax.named_scope("hist_root"):
                root_hist, root_blk = hist_leaf(st, jnp.int32(0), G_cols,
                                                fmeta, look_k)
        else:
            # external batched pass: charge the same scan cost so the
            # adaptive-compaction accounting is unchanged
            root_blk = jnp.int32(max_blocks)
        st = st._replace(leaf_hist=st.leaf_hist.at[0].set(root_hist),
                         scanned_since=root_blk, scanned_total=root_blk,
                         grid_total=jnp.int32(n_tiles * max_blocks))
        with jax.named_scope("split_scan"):
            st = scan_leaf(st, 0, root_hist, G0, H0, C0, jnp.int32(0),
                           fmeta, feature_mask, key, 2 * L)
        st = lax.while_loop(can_grow, epoch, st)
        with jax.named_scope("unpermute"):
            leaf_id_orig = _unpermute(st.order, st.leaf_id)
        # scan/compaction counters always leave the jit as a third output
        # (stable arity; no host callbacks, so no jax.debug.print, in
        # compiled code) — printing them is gated on
        # LIGHTGBM_TPU_SEG_STATS at the call sites
        stats = seg_stats_vector(
            scanned_blocks=st.scanned_total, compactions=st.num_sorts,
            grid_steps=st.grid_total, max_blocks=max_blocks,
            compact_budget=limit_blocks, batch_k=1,
            splits=st.num_splits, lookahead_hits=st.look_hits,
            lookahead_filled=st.look_filled,
            route_only_blocks=st.route_only, feature_tiles=n_tiles,
            leaf_hist_kib=(st.leaf_hist.size + st.look_hist.size)
            * 4 // 1024)
        return st.tree, leaf_id_orig, stats

    if wrap is not None:
        return wrap(grow)
    from ..utils.jitcost import cost_jit
    return cost_jit("grow/segment", jax.jit(grow))
