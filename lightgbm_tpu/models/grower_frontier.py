"""Frontier-batched segment grower: K splits per round, one batched
histogram kernel call.

The strict best-first segment grower (grower_seg.py) histograms ONE
leaf's smaller child per split, so the one-hot matmul's output is 8
channels wide and the MXU runs at ~6% utilization (PERF_NOTES round 3:
2.65 ns/row is that design's ceiling).  This grower splits the TOP-K
leaves of the candidate pool per round and computes all K smaller-child
histograms in a single ``histogram_frontier`` call whose matmul output
carries K x 8 = 128 channels — a full MXU lane tile — over the UNION of
the K leaves' confinement blocks (a prefetched block list, so DMA is
proportional to the union, with sibling leaves sharing blocks).

Semantics: "batched best-first".  Each round splits the K highest-gain
leaves of the pool simultaneously; with K=1 the tree is exactly the
strict best-first tree.  For K>1 a round may split a leaf that strict
best-first would have starved in favor of a just-created child, so trees
can differ slightly — the same locally-greedy family as the reference's
leaf-wise growth, traded for a 16x denser matmul.  Opt-in via
``tpu_tree_impl=frontier`` (config.py); the default remains the strict
grower.  The reference has no equivalent switch: its GPU learner
(src/treelearner/gpu_tree_learner.cpp) keeps strict leaf-wise order and
pays per-leaf kernel launches instead.

Distributed: parallel/learners.make_data_parallel_frontier_grower runs
this grower under shard_map — rows sharded, the whole [K, G, B, 3] batch
reduce-scattered in ONE collective per round (K x fewer collective
launches than the strict grower), and all 2K children's SplitInfos
merged in one all_gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_histogram import (frontier_width,
                                    fused_route_decisions,
                                    fused_route_policy,
                                    histogram_frontier,
                                    histogram_frontier_routed, null_route,
                                    pack_channels, pack_route,
                                    route_kernel_available, unpack_hist)
from ..ops.split import (NEG_INF, FeatureMeta, best_split,
                         expand_group_hist)
from .grower import (CommHooks, GrowerParams, _node_feature_mask,
                     mono_handoff)
from .grower_seg import (_COMPACT_MUT, _SegState, _unpermute,
                         apply_route, compact_state,
                         compaction_budget_blocks, cond_narrow,
                         fresh_state, seg_stats_vector, stripe_histogram)


def make_grow_tree_frontier(num_bins: int, params: GrowerParams,
                            block_rows: int, batch_k: int = 0,
                            gain_ratio: float = 0.0,
                            comm=None, wrap=None):
    """Build the jitted frontier-batched grower.

    Same call contract as make_grow_tree_segment:
    ``grow(binsT, grad, hess, member, fmeta, feature_mask, key)`` ->
    ``(TreeArrays, leaf_id_original_order)``.

    ``comm`` (CommHooks) makes this the data-parallel learner's core
    under shard_map: ``reduce_hist_batch`` reduce-scatters the whole
    [K, G, B, 3] batch in one collective, ``merge_split_batch`` merges
    all 2K children's SplitInfos by max gain in one all_gather.
    """
    p = params
    L = p.num_leaves
    B = num_bins
    rb = block_rows
    comm = comm or CommHooks()
    K = batch_k or frontier_width(
        p.num_columns or 64, B)
    K = max(1, min(K, L - 1))
    # a ratio above 1 would gate out even the round-best leaf and hang
    # the growth loop; config validates, this clamp guards direct callers
    gain_ratio = min(max(float(gain_ratio), 0.0), 1.0)
    # fused route+histogram (fused_route_policy): auto keeps it to
    # K == 1.  Feature-parallel stripes keep the unfused pair — the
    # histogram scans a column slice, the route needs the full matrix.
    fused_route = (fused_route_policy(K, p.num_columns or 64, B, rb,
                                      p.packed4) == "k1"
                   and comm.column_block is None)
    fused_route_decisions["frontier"] = fused_route
    route_kernel = route_kernel_available()

    def _one_scan(st, hist, g, h, c, depth, fmeta, fmask, key, step,
                  lo, hi):
        fmask_node = _node_feature_mask(fmask, key, step, p)
        if comm.shard_feature_mask is not None:
            fmask_node = comm.shard_feature_mask(fmask_node)
        adjust = None
        if p.cegb_penalty_split > 0.0 or p.use_cegb_coupled:
            from .grower import _cegb_split_coupled_adjust
            adjust = _cegb_split_coupled_adjust(st.feat_used, c, fmeta, p)
        hist = expand_group_hist(hist, fmeta, g, h, c)
        info = best_split(hist, g, h, c, fmeta, p.split, fmask_node,
                          mono_lo=lo if p.use_monotone else None,
                          mono_hi=hi if p.use_monotone else None,
                          gain_adjust=adjust)
        gain = info.gain
        if p.max_depth > 0:
            gain = jnp.where(depth >= p.max_depth, NEG_INF, gain)
        return info, gain

    def _write_scans(st: _SegState, leaf_idx, infos, gains):
        f32 = jnp.stack([gains, infos.left_g, infos.left_h, infos.left_c,
                         infos.left_out, infos.right_out],
                        axis=-1).astype(jnp.float32)
        i32 = jnp.stack([infos.feature, infos.threshold,
                         infos.default_left.astype(jnp.int32),
                         infos.is_cat.astype(jnp.int32)], axis=-1)
        return st._replace(
            best_f32=st.best_f32.at[leaf_idx].set(f32, mode="drop"),
            best_i32=st.best_i32.at[leaf_idx].set(i32, mode="drop"),
            best_cat_bitset=st.best_cat_bitset.at[leaf_idx].set(
                infos.cat_bitset, mode="drop"),
        )

    def compact(st: _SegState) -> _SegState:
        return compact_state(st, L, rb)

    def grow(binsT, grad, hess, member, fmeta: FeatureMeta, feature_mask,
             key, root_hist=None):
        # ``root_hist`` [G, B, 3]: externally-computed root histogram
        # (multiclass batched roots); serial only, like grower_seg
        n_phys, n = binsT.shape
        G_cols = p.num_columns or (2 * n_phys if p.packed4 else n_phys)
        F = fmeta.num_bin.shape[0]
        assert n % rb == 0, (n, rb)
        max_blocks = n // rb
        fpad = (-n_phys) % 4
        if fpad:
            binsT = jnp.pad(binsT, ((0, fpad), (0, 0)))

        w8 = pack_channels(grad, hess, member)
        G0 = jnp.sum(grad * member)
        H0 = jnp.sum(hess * member)
        C0 = jnp.sum(member)
        if comm.reduce_stats is not None:
            G0, H0, C0 = (comm.reduce_stats(G0), comm.reduce_stats(H0),
                          comm.reduce_stats(C0))
        all_blocks = jnp.arange(max_blocks, dtype=jnp.int32)
        # grid-step accounting: histogram_frontier's grid is the union's
        # blocks, one masked step where it is empty
        def grid_of(nb):
            return jnp.maximum(nb, 1)

        def hist_batch(st: _SegState, targets, block_list, n_blocks,
                       routes=None, fmeta=None):
            """[K] targets (-1 = skip) -> (st, [K, G, B, 3]) over the
            union.  ``routes`` [K, 19] applies the round's K split routes
            inside the kernel (fused path) and updates st.leaf_id."""
            if comm.column_block is not None:
                # feature-parallel: batch-histogram only this shard's
                # column stripe (grower_seg.stripe_histogram)
                start, ncols = comm.column_block(st.binsT)
                out = stripe_histogram(
                    st.binsT, start, ncols,
                    lambda sub: histogram_frontier(
                        sub, st.w8, st.leaf_id, block_list, n_blocks,
                        targets, B, rb, packed4=p.packed4),
                    feat_axis=1)
            elif routes is not None:
                lid, out = histogram_frontier_routed(
                    st.binsT, st.w8, st.leaf_id, block_list, n_blocks,
                    targets, routes, B, rb, K, packed4=p.packed4)
                st = st._replace(leaf_id=lid)
            else:
                out = histogram_frontier(st.binsT, st.w8, st.leaf_id,
                                         block_list, n_blocks, targets, B,
                                         rb, packed4=p.packed4)
            h = unpack_hist(out[:, :G_cols])
            if comm.reduce_hist_batch is not None:
                h = comm.reduce_hist_batch(h, fmeta)
            return st, h

        def apply_split(st: _SegState, leaf, new_leaf, node):
            """Routing + tree-array bookkeeping for ONE split (the cheap
            per-split work; histograms and scans happen batched)."""
            bi = st.best_i32[leaf]
            bf = st.best_f32[leaf]
            f = bi[0]
            t = bi[1]
            dl = bi[2].astype(bool)
            cat = bi[3].astype(bool)
            bitset = st.best_cat_bitset[leaf]

            lo, hi = st.leaf_lo[leaf], st.leaf_hi[leaf]
            if not fused_route:
                # routing confined to the parent's inherited block
                # interval (grower_seg.route_split_windowed); the fused
                # path routes inside the batched histogram kernel instead
                leaf_id = apply_route(
                    st.binsT, st.leaf_id, fmeta, p.packed4, rb,
                    f, t, dl, cat, bitset, leaf, new_leaf, lo, hi - lo,
                    route_kernel)
                st = st._replace(leaf_id=leaf_id)

            Gl, Hl, Cl = bf[1], bf[2], bf[3]
            Gp, Hp, Cp = st.leaf_g[leaf], st.leaf_h[leaf], st.leaf_c[leaf]
            Gr, Hr, Cr = Gp - Gl, Hp - Hl, Cp - Cl

            st = st._replace(
                leaf_lo=st.leaf_lo.at[new_leaf].set(lo),
                leaf_hi=st.leaf_hi.at[new_leaf].set(hi),
            )
            if p.use_monotone:
                lo_l, hi_l, lo_r, hi_r = mono_handoff(
                    st.leaf_mono_lo[leaf], st.leaf_mono_hi[leaf],
                    bf[4], bf[5], fmeta.monotone[f], cat)
                st = st._replace(
                    leaf_mono_lo=st.leaf_mono_lo
                    .at[leaf].set(lo_l).at[new_leaf].set(lo_r),
                    leaf_mono_hi=st.leaf_mono_hi
                    .at[leaf].set(hi_l).at[new_leaf].set(hi_r),
                )
            if p.use_cegb_coupled:
                st = st._replace(feat_used=st.feat_used.at[f].set(1.0))

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
            right_child = (tree.right_child.at[jnp.maximum(parent, 0)]
                           .set(pr))
            left_child = left_child.at[node].set(~leaf)
            right_child = right_child.at[node].set(~new_leaf)

            tree = tree._replace(
                num_leaves=tree.num_leaves + 1,
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
                leaf_value=(tree.leaf_value.at[leaf].set(bf[4])
                            .at[new_leaf].set(bf[5])),
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
                leaf_g=st.leaf_g.at[leaf].set(Gl).at[new_leaf].set(Gr),
                leaf_h=st.leaf_h.at[leaf].set(Hl).at[new_leaf].set(Hr),
                leaf_c=st.leaf_c.at[leaf].set(Cl).at[new_leaf].set(Cr),
                tree=tree,
            )
            return st

        def round_body(st: _SegState):
            base = st.num_leaves
            budget = L - base
            gains_top, leaves_top = lax.top_k(st.best_f32[:, 0], K)
            # positive-gain prefix, clipped to the leaf budget; top_k
            # sorts descending so validity is a prefix and new leaf ids
            # are base + j.  The gain-ratio gate only batches leaves
            # comparable to the round's best: a dominant leaf grows
            # strictly best-first, a flat pool batches fully.
            valid = (gains_top > 0.0) & (jnp.arange(K) < budget)
            if gain_ratio > 0.0:
                valid &= gains_top >= gain_ratio * gains_top[0]
            # clamp to the longest true PREFIX once, here, so the apply
            # loop, the fused routes and the histogram targets can never
            # disagree if a future gate is non-monotone in j (new leaf
            # ids are base + j, which only works applied in order)
            valid &= jnp.cumsum(~valid) == 0
            leaves_top = leaves_top.astype(jnp.int32)
            new_leaves = base + jnp.arange(K, dtype=jnp.int32)
            nodes = base - 1 + jnp.arange(K, dtype=jnp.int32)

            # Cl/Cr from the cached SplitInfo decide the smaller child
            Cl = st.best_f32[leaves_top, 3]
            Cp = st.leaf_c[leaves_top]
            smaller_is_left = Cl <= Cp - Cl

            # 1) apply the valid splits sequentially (cheap VPU/scalar
            # work).  ``valid`` is a PREFIX of K (top_k sorts gains
            # descending and the budget/ratio gates preserve order), so a
            # traced-bound fori over the prefix applies each split
            # UNCONDITIONALLY — the old per-split lax.cond made XLA copy
            # its carried leaf_id (~42 MB) through the identity branch
            # every split (the same copy class the strict grower's epoch
            # restructure eliminated; round-4 trace).  n_valid is uniform
            # across shards: it derives from merged gains and the budget.
            def apply_one(j, s):
                return apply_split(s, leaves_top[j], new_leaves[j],
                                   nodes[j])
            parent_hist = st.leaf_hist[leaves_top]          # [K, G, B, 3]
            # ``valid`` is prefix-clamped above, so the popcount IS the
            # prefix length
            n_valid = jnp.sum(valid).astype(jnp.int32)
            st = lax.fori_loop(0, n_valid, apply_one, st)

            # 2) union block list of the K smaller children's confinement
            # intervals (children inherit the parent interval, so read
            # either child's bounds)
            lo_k = st.leaf_lo[leaves_top]
            hi_k = st.leaf_hi[leaves_top]
            in_int = ((all_blocks[None, :] >= lo_k[:, None])
                      & (all_blocks[None, :] < hi_k[:, None])
                      & valid[:, None])                     # [K, max_blocks]
            mask = jnp.any(in_int, axis=0)
            n_un = jnp.sum(mask).astype(jnp.int32)
            pos = jnp.cumsum(mask) - 1
            block_list = jnp.zeros(max_blocks, jnp.int32).at[
                jnp.where(mask, pos, max_blocks)].set(all_blocks,
                                                      mode="drop")

            # 3) ONE batched kernel pass for the round's histograms
            if fused_route:
                # the round's K routes ride the same pass (invalid slots
                # match nothing); split params still live in the best-*
                # cache — the scans that overwrite them run in step 4
                routes = jax.vmap(
                    lambda l, nl, v: jnp.where(
                        v,
                        pack_route(l, nl, st.best_i32[l, 0],
                                   st.best_i32[l, 1],
                                   st.best_i32[l, 2] == 1,
                                   st.best_i32[l, 3] == 1,
                                   st.best_cat_bitset[l], fmeta,
                                   p.packed4),
                        null_route()))(leaves_top, new_leaves, valid)
            else:
                routes = None
            smaller = jnp.where(smaller_is_left, leaves_top, new_leaves)
            targets = jnp.where(valid, smaller, -1)
            st, hist_small = hist_batch(st, targets, block_list, n_un,
                                        routes, fmeta)
            if comm.no_subtract:
                # voting-parallel: election masks differ per call, so
                # the subtraction trick is invalid — batch-histogram
                # the larger children from data too (routes applied)
                larger = jnp.where(smaller_is_left, new_leaves,
                                   leaves_top)
                targets_l = jnp.where(valid, larger, -1)
                _, hist_large = hist_batch(st, targets_l, block_list,
                                           n_un, None, fmeta)
                scanned = 2 * n_un
                grid_inc = 2 * grid_of(n_un)
            else:
                hist_large = parent_hist - hist_small
                scanned = n_un
                grid_inc = grid_of(n_un)
            sel = smaller_is_left[:, None, None, None]
            hist_left = jnp.where(sel, hist_small, hist_large)
            hist_right = jnp.where(sel, hist_large, hist_small)
            idx_l = jnp.where(valid, leaves_top, L)
            idx_r = jnp.where(valid, new_leaves, L)
            st = st._replace(
                leaf_hist=st.leaf_hist
                .at[idx_l].set(hist_left, mode="drop")
                .at[idx_r].set(hist_right, mode="drop"),
                scanned_since=st.scanned_since + scanned,
                scanned_total=st.scanned_total + scanned,
                grid_total=st.grid_total + grid_inc,
            )

            # 4) scan all 2K children in one vmapped pass
            leaves2 = jnp.concatenate([idx_l, idx_r])
            hists2 = jnp.concatenate([hist_left, hist_right])
            g2 = st.leaf_g[jnp.minimum(leaves2, L - 1)]
            h2 = st.leaf_h[jnp.minimum(leaves2, L - 1)]
            c2 = st.leaf_c[jnp.minimum(leaves2, L - 1)]
            depth2 = st.tree.leaf_depth[jnp.minimum(leaves2, L - 1)]
            steps2 = jnp.concatenate([2 * nodes, 2 * nodes + 1])
            safe = jnp.minimum(leaves2, L - 1)
            infos, gains = jax.vmap(
                lambda hh, g, h, c, d, s, blo, bhi: _one_scan(
                    st, hh, g, h, c, d, fmeta, feature_mask, key, s,
                    blo, bhi)
            )(hists2, g2, h2, c2, depth2, steps2,
              st.leaf_mono_lo[safe], st.leaf_mono_hi[safe])
            if comm.merge_split_batch is not None:
                infos, gains = comm.merge_split_batch(infos, gains)
            st = _write_scans(st, leaves2, infos, gains)

            # 5) adaptive compaction, same rule as the strict grower
            return cond_narrow(st.scanned_since >= limit_blocks,
                               compact, st, _COMPACT_MUT)

        limit_blocks = compaction_budget_blocks(G_cols, B, n, rb, p.packed4)

        st = fresh_state(binsT, w8, n, L, G_cols, B, F, max_blocks,
                         G0, H0, C0, fmeta, p)
        if root_hist is None:
            # all-null routes on the fused path: same kernel as the
            # round passes, so the root costs no extra Mosaic compile
            root_targets = jnp.full(K, -1, jnp.int32).at[0].set(0)
            root_routes = (jnp.tile(null_route(), (K, 1))
                           if fused_route else None)
            _, rh = hist_batch(st, root_targets, all_blocks,
                               jnp.int32(max_blocks), root_routes, fmeta)
            root_hist = rh[0]
        st = st._replace(leaf_hist=st.leaf_hist.at[0].set(root_hist),
                         scanned_since=jnp.int32(max_blocks),
                         scanned_total=jnp.int32(max_blocks),
                         grid_total=jnp.int32(max_blocks))
        info0, gain0 = _one_scan(st, root_hist, G0, H0, C0, jnp.int32(0),
                                 fmeta, feature_mask, key, 2 * L,
                                 st.leaf_mono_lo[0], st.leaf_mono_hi[0])
        infos0 = jax.tree_util.tree_map(lambda x: x[None], info0)
        gains0 = gain0[None]
        if comm.merge_split_batch is not None:
            infos0, gains0 = comm.merge_split_batch(infos0, gains0)
        st = _write_scans(st, jnp.asarray([0], jnp.int32), infos0, gains0)

        def cond(st):
            return (st.num_leaves < L) & (jnp.max(st.best_f32[:, 0]) > 0.0)

        st = lax.while_loop(cond, round_body, st)
        leaf_id_orig = _unpermute(st.order, st.leaf_id)
        # counters as a third jit output with stable arity (no in-jit
        # host callbacks); printing is env-gated at call sites
        stats = seg_stats_vector(
            scanned_blocks=st.scanned_total, compactions=st.num_sorts,
            grid_steps=st.grid_total, max_blocks=max_blocks,
            compact_budget=limit_blocks, batch_k=K)
        return st.tree, leaf_id_orig, stats

    if wrap is not None:
        return wrap(grow)
    from ..utils.jitcost import cost_jit
    return cost_jit("grow/frontier", jax.jit(grow))
