"""Distributed tree learners over a device mesh.

The reference parallelizes tree learning across machines with hand-rolled
socket/MPI collectives (SURVEY.md §2.7): data-parallel (rows sharded,
histogram reduce-scatter + best-split allreduce,
src/treelearner/data_parallel_tree_learner.cpp:209-601), feature-parallel
(full data, split finding sharded by feature, 2xSplitInfo max-gain allreduce,
feature_parallel_tree_learner.cpp:33-75), and voting-parallel (top-k vote to
cut reduce volume, voting_parallel_tree_learner.cpp:170-380).

Here each strategy is a set of collective hooks injected into the SAME fused
grower and executed under ``shard_map`` over a 1-D ``machines`` mesh axis:

  * data-parallel:    rows sharded; every leaf histogram ``psum_scatter``s
    so each shard owns one contiguous COLUMN stripe (the reference's
    ReduceScatter-then-scan §3.4 pattern), each shard scans only its
    stripe, and the winning SplitInfo merges by max-gain all_gather.
    Forced-split runs fall back to a full-histogram ``lax.psum`` (the
    forced path reads the local leaf histogram without a merge).
  * feature-parallel: data replicated; each shard histograms AND scans
    only its contiguous column stripe, and the per-leaf SplitInfos merge
    via all_gather + argmax on gain (the packed-SplitInfo max-gain
    allreduce).
  * voting-parallel:  rows sharded; each shard votes its local top-k
    features by local best gain, votes are psum'd, and only the 2*top_k
    globally-elected features' histograms are reduced.

Multi-host: initialize ``jax.distributed`` so ``jax.devices()`` spans hosts;
the same axis then rides ICI within a slice and DCN across hosts — no code
changes (the reference's machine-list/socket handshake has no equivalent
work here).
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..models.grower import CommHooks, GrowerParams, make_grow_tree
from ..ops.split import (NEG_INF, SplitInfo, SplitParams, expand_group_hist,
                         per_feature_gains)


def _instrument_grower(grow_fn, kind: str, tree_bytes: int):
    """Wrap a parallel grower so every tree records one collective
    entry (parallel/network.py counters): the static per-tree wire-byte
    estimate plus the host dispatch wall of the grow call (device
    collectives execute asynchronously inside the jitted grower, so
    dispatch wall is the honest host-side measure).

    The fused boosting step closes over the grower INSIDE a jit, where
    this Python wrapper only runs while tracing — recording there would
    count one bogus trace-time entry per compile instead of one per
    tree.  Tracing calls are skipped, and the kind/bytes tags are
    exposed as attributes so the fused dispatch site (gbdt.py) can
    record each eager step itself."""
    from . import network

    @functools.wraps(grow_fn)
    def grow(*args, **kwargs):
        if any(isinstance(a, jax.core.Tracer) for a in args):
            return grow_fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = grow_fn(*args, **kwargs)
        network.record_collective(kind, tree_bytes,
                                  time.perf_counter() - t0)
        return out
    grow._collective_kind = kind
    grow._collective_bytes = tree_bytes
    return grow


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _merge_split_by_gain(info: SplitInfo, gain, axis):
    """all_gather each SplitInfo field, keep the max-gain shard's
    (SyncUpGlobalBestSplit, parallel_tree_learner.h:356-397)."""
    gains = lax.all_gather(gain, axis)              # [D]
    winner = jnp.argmax(gains)
    merged = SplitInfo(*[lax.all_gather(f, axis)[winner] for f in info])
    return merged, gains[winner]


def _stripe_feature_mask(fmask, axis, start, per, feat_group):
    """Mask features whose physical COLUMN lies in [start, start+per) —
    the one place that maps a shard's column stripe back to feature space
    (identity column map when the dataset is unbundled)."""
    col = (jnp.asarray(np.asarray(feat_group), dtype=jnp.int32)
           if feat_group is not None
           else jnp.arange(fmask.shape[0], dtype=jnp.int32))
    stripe = (col >= start) & (col < start + per)
    return fmask * stripe.astype(fmask.dtype)


def _balanced_stripes(column_bins, D: int):
    """Contiguous column stripes with ~equal Σbins per shard (the
    reference re-balances feature-parallel shards by #bins,
    feature_parallel_tree_learner.cpp:36-47; an even column split skews
    badly when EFB bundles concentrate bins in few columns).

    Returns (starts [D], widths [D], per) where ``per`` is the max stripe
    width — the static column-block size every shard reads (narrower
    stripes mask the surplus columns out of the scan).  Because every
    shard's histogram block is ``per`` wide regardless of its own stripe,
    widths are capped at 2x the even split: bins-balance may only double
    the static block, never degenerate into one shard reading almost all
    columns.  Each boundary picks the side of the Σbins-crossing column
    closer to the target, so profiles the even split already handles
    optimally (e.g. [3, 5] on 2 shards) are never made worse."""
    cb = np.maximum(np.asarray(column_bins, dtype=np.int64), 1)
    G = len(cb)
    csum = np.cumsum(cb)
    total = int(csum[-1])
    even = -(-G // D)
    cap = min(2 * even, G)
    starts = np.zeros(D, dtype=np.int32)
    ends = np.zeros(D, dtype=np.int32)
    pos = 0
    for d in range(D):
        starts[d] = pos
        if d == D - 1:
            ends[d] = G
            break
        target = (d + 1) * total / D
        e = int(np.searchsorted(csum, target, side="left")) + 1
        # nearer boundary of the crossing column
        if e - 1 > pos and abs(csum[e - 2] - target) <= \
                abs(csum[e - 1] - target):
            e -= 1
        # feasibility: the remaining shards (cap wide each) must be able
        # to cover the remaining columns; this shard must respect cap
        e = max(e, pos, G - cap * (D - 1 - d))
        e = min(e, pos + cap, G)
        ends[d] = e
        pos = e
    widths = (ends - starts).astype(np.int32)
    assert int(widths.sum()) == G
    return starts, widths, int(widths.max(initial=1))


def _log_collective_estimate(mode: str, D: int, num_columns: int,
                             num_bins: int, num_leaves: int,
                             top_k: int = 0) -> int:
    """Static wire-byte estimate from mesh math (SURVEY §5: the TPU
    equivalent of the fork's Linkers byte counters, linkers.h:114-117).
    Ring allreduce moves ~2x the payload, reduce-scatter ~1x; the
    SplitInfo merge is ~14 scalars all_gathered per leaf scan.  Returns
    the per-tree byte total so the grower factories can feed the
    runtime collective counters (network.record_collective)."""
    from ..utils.log import log_info
    hist_bytes = num_columns * num_bins * 3 * 4
    per_split = {
        "data": hist_bytes,                # psum_scatter (reduce-scatter)
        "data_allreduce": 2 * hist_bytes,  # full-hist psum fallback
        "data_segment": hist_bytes,        # psum_scatter (reduce-scatter)
        # same total bytes as data_segment, but one K-batched launch per
        # round instead of one per split — K x fewer collectives
        "data_frontier": hist_bytes,
        "voting": 2 * hist_bytes * min(1.0, 2 * top_k / max(num_columns, 1))
        + num_columns * 4,                 # elected slices + vote psum
        "feature": 0,                      # scan-only; no hist crosses
    }.get(mode, 0)
    split_info = 14 * 4 * D * 2            # all_gather of 2 SplitInfos
    total = (num_leaves - 1) * (per_split + split_info)
    log_info(f"collective estimate [{mode}, D={D}]: "
             f"{per_split + split_info} B/split, "
             f"{total / 1e6:.1f} MB/tree on the wire")
    return int(total)


def _make_voting_reduce(axis, sp, top_k: int):
    """Voting-parallel histogram reduction (PV-Tree,
    voting_parallel_tree_learner.cpp:170-380): local top-k vote in
    FEATURE space, global election by psum'd votes, and only elected
    columns' histograms cross the wire."""
    def reduce_voted(h, G, H, C, fmeta):
        # vote in FEATURE space on the expanded view (identity when
        # unbundled), reduce in COLUMN space.  The vote must use LOCAL
        # leaf totals — G/H/C are already psum'd global stats, and
        # expanding the pre-reduce partial histogram with global totals
        # would inflate the reconstructed default-bin slot by the other
        # shards' mass.  Every row lands in exactly one bin of every
        # column, so column 0's bin-sum IS the local (g, h, count).
        loc = h[0].sum(axis=0)
        hf = expand_group_hist(h, fmeta, loc[0], loc[1], loc[2])
        local_gains = per_feature_gains(hf, loc[0], loc[1], loc[2],
                                        fmeta, sp)               # [F]
        F = local_gains.shape[0]
        k = min(top_k, F)
        gains_top, local_top = lax.top_k(local_gains, k)
        votes = jnp.zeros(F, dtype=jnp.int32).at[local_top].add(
            jnp.where(gains_top > NEG_INF, 1, 0))
        votes = lax.psum(votes, axis)
        k2 = min(2 * top_k, F)
        _, elected = lax.top_k(votes, k2)
        fmask = jnp.zeros(F, dtype=h.dtype).at[elected].set(1.0)
        if fmeta.feat_group is not None:
            # a column crosses the wire if ANY member feature is elected
            mask = jnp.zeros(h.shape[0], dtype=h.dtype) \
                .at[fmeta.feat_group].max(fmask)
        else:
            mask = fmask
        # only elected columns' histograms cross the wire; the rest are
        # zeroed so their candidates mask out in the scan
        return lax.psum(h * mask[:, None, None], axis)
    return reduce_voted


def make_parallel_grower(num_bins: int, params: GrowerParams, mesh: Mesh,
                         mode: str, top_k: int = 20,
                         num_columns: int = 0, feat_group=None,
                         column_bins=None):
    """shard_map-wrapped grower for mode in {'data', 'feature', 'voting'}.

    Argument order of the returned fn matches the serial grower:
    (bins, grad, hess, member, fmeta, feature_mask, key).
    ``num_columns``/``feat_group`` locate features in the physical bin
    matrix for the feature-parallel column stripes (EFB, core/bundle.py);
    ``column_bins`` (per-column bin counts) balances those stripes by
    Σbins the way the reference does.
    """
    axis = mesh.axis_names[0]
    D = int(mesh.devices.size)
    sp = params.split
    repl = P()

    if mode in ("data", "data_parallel"):
        # forced splits read the local leaf histogram without a merge, so
        # they need the full-histogram psum variant, not stripe ownership
        if num_columns > 0 and not params.forced_plan:
            # the reference's §3.4 pattern (data_parallel_tree_learner.cpp:
            # 437-447): reduce-scatter so each shard owns one contiguous
            # column stripe, scan only the stripe, merge the winning
            # SplitInfo by max gain — half the wire bytes of an allreduce
            # and no redundant scan work
            G = num_columns
            Gpad = -(-G // D) * D
            per = Gpad // D

            def reduce_hist(h, *_):
                hp = jnp.pad(h, ((0, Gpad - G), (0, 0), (0, 0)))
                mine = lax.psum_scatter(hp, axis, scatter_dimension=0,
                                        tiled=True)
                me = lax.axis_index(axis)
                out = jnp.zeros_like(hp)
                out = lax.dynamic_update_slice(out, mine, (me * per, 0, 0))
                return out[:G]

            def shard_mask(fmask):
                return _stripe_feature_mask(
                    fmask, axis, lax.axis_index(axis) * per, per,
                    feat_group)

            comm = CommHooks(
                reduce_hist=reduce_hist,
                reduce_stats=lambda x: lax.psum(x, axis),
                merge_split=lambda info, gain: _merge_split_by_gain(
                    info, gain, axis),
                shard_feature_mask=shard_mask)
        else:
            comm = CommHooks(
                reduce_hist=lambda h, G, H, C, f: lax.psum(h, axis),
                reduce_stats=lambda x: lax.psum(x, axis))
        in_specs = (P(axis, None), P(axis), P(axis), P(axis), repl, repl,
                    repl)
        out_specs = (repl, P(axis))
    elif mode in ("feature", "feature_parallel"):
        # every shard holds the FULL data but histograms and scans only a
        # contiguous COLUMN stripe; the winning SplitInfo merges by
        # max-gain and all shards split locally — the reference's
        # feature-parallel contract (feature_parallel_tree_learner.cpp:
        # 36-75, histograms only for the rank's own features).  Stripe
        # boundaries balance per-shard Σbins like the reference (:36-47)
        # when per-column bin counts are known; even column split is the
        # uniform-bins special case.
        column_block, shard_mask, per = _feature_stripes(
            mesh, num_columns, feat_group, column_bins)

        comm = CommHooks(
            merge_split=lambda info, gain: _merge_split_by_gain(
                info, gain, axis),
            shard_feature_mask=shard_mask,
            column_block=column_block)
        in_specs = (repl, repl, repl, repl, repl, repl, repl)
        out_specs = (repl, repl)
    elif mode in ("voting", "voting_parallel"):
        reduce_voted = _make_voting_reduce(axis, sp, top_k)
        # votes differ per histogram call, so parent/child histograms carry
        # different election masks; the subtraction trick is invalid here
        # and both children must be histogrammed from data
        comm = CommHooks(
            reduce_hist=reduce_voted,
            reduce_stats=lambda x: lax.psum(x, axis),
            no_subtract=True)
        in_specs = (P(axis, None), P(axis), P(axis), P(axis), repl, repl,
                    repl)
        out_specs = (repl, P(axis))
    else:
        raise ValueError(f"Unknown parallel tree learner mode {mode}")

    def wrap(grow):
        return jax.jit(_shard_map(grow, mesh, in_specs, out_specs))

    est_mode = mode.split("_")[0]
    if est_mode == "data" and (num_columns <= 0 or params.forced_plan):
        est_mode = "data_allreduce"        # the full-hist psum fallback
    tree_bytes = _log_collective_estimate(est_mode, D, num_columns or 0,
                                          num_bins, params.num_leaves,
                                          top_k)
    return _instrument_grower(
        make_grow_tree(num_bins, params, comm=comm, wrap=wrap),
        est_mode, tree_bytes)


def _stripe_setup(mesh: Mesh, num_columns: int, feat_group):
    """Shared data-parallel stripe scaffolding: (axis, D, Gpad, per,
    shard_mask, wrap-in/out specs).  Both the strict segment learner and
    the frontier learner shard rows on the mesh axis and own one
    contiguous reduced column stripe each."""
    axis = mesh.axis_names[0]
    D = int(mesh.devices.size)
    Gpad = -(-num_columns // D) * D
    per = Gpad // D

    def shard_mask(fmask):
        return _stripe_feature_mask(fmask, axis,
                                    lax.axis_index(axis) * per, per,
                                    feat_group)

    in_specs = (P(None, axis), P(axis), P(axis), P(axis), P(), P(), P())
    # third output: the grower's [SEG_STATS_SLOTS] counters, one row a device so
    # the host prints one seg-stats row per shard
    out_specs = (P(), P(axis), P(axis))
    return axis, D, Gpad, per, shard_mask, in_specs, out_specs


def make_data_parallel_segment_grower(num_bins: int, params: GrowerParams,
                                      mesh: Mesh, block_rows: int,
                                      num_columns: int, feat_group=None):
    """Data-parallel learner with the segment grower's O(leaf) per-split
    cost AND the reference's §3.4 communication pattern
    (data_parallel_tree_learner.cpp:437-447):

      * rows sharded over the mesh axis; each shard keeps its own permuted
        layout / confinement intervals / compaction (sorts are D× smaller
        and run in parallel);
      * every leaf histogram is ``psum_scatter``-reduced so each shard owns
        the reduced histogram of one CONTIGUOUS feature stripe — the wire
        carries reduce-scatter bytes only, not a full allreduce;
      * each shard scans only its stripe (scan feature-mask) and the
        winning SplitInfo is merged by max-gain all_gather
        (SyncUpGlobalBestSplit, parallel_tree_learner.h:356-397);
      * all shards then apply the winning split locally — no row data ever
        crosses the interconnect.
    """
    from ..models.grower_seg import make_grow_tree_segment

    G = num_columns
    axis, D, Gpad, per, shard_mask, in_specs, out_specs = _stripe_setup(
        mesh, G, feat_group)

    def reduce_hist(h, *_):
        # [G, B, 3] per-shard partials -> reduced COLUMN stripe per shard,
        # placed back at its offset (non-stripe rows zero; the scan masks
        # out their features)
        hp = jnp.pad(h, ((0, Gpad - G), (0, 0), (0, 0)))
        mine = lax.psum_scatter(hp, axis, scatter_dimension=0, tiled=True)
        me = lax.axis_index(axis)
        out = jnp.zeros_like(hp)
        out = lax.dynamic_update_slice(out, mine, (me * per, 0, 0))
        return out[:G]

    comm = CommHooks(
        reduce_hist=reduce_hist,
        reduce_stats=lambda x: lax.psum(x, axis),
        merge_split=lambda info, gain: _merge_split_by_gain(info, gain,
                                                            axis),
        shard_feature_mask=shard_mask,
        uniform_scan=lambda b: lax.pmax(b, axis))

    def wrap(grow):
        return jax.jit(_shard_map(grow, mesh, in_specs, out_specs))

    tree_bytes = _log_collective_estimate("data_segment", D, G, num_bins,
                                          params.num_leaves)
    return _instrument_grower(
        make_grow_tree_segment(num_bins, params, block_rows, comm=comm,
                               wrap=wrap),
        "data_segment", tree_bytes)


def make_data_parallel_frontier_grower(num_bins: int, params: GrowerParams,
                                       mesh: Mesh, block_rows: int,
                                       num_columns: int, feat_group=None,
                                       batch_k: int = 0,
                                       gain_ratio: float = 0.0):
    """Data-parallel frontier-batched learner: the K-splits-per-round
    grower (models/grower_frontier.py) under shard_map.

    Same wire pattern as the strict data-parallel segment learner —
    psum_scatter column stripes, stripe-masked scans, max-gain SplitInfo
    merge — but one collective carries the WHOLE [K, G, B, 3] round batch
    and one all_gather merges all 2K children's SplitInfos: K x fewer
    collective launches per tree, which matters on a latency-bound
    interconnect exactly the way the batched matmul matters on the MXU.
    """
    from ..models.grower import CommHooks
    from ..models.grower_frontier import make_grow_tree_frontier

    G = num_columns
    axis, D, Gpad, per, shard_mask, in_specs, out_specs = _stripe_setup(
        mesh, G, feat_group)

    def reduce_hist_batch(h, fmeta=None):
        # [K, G, B, 3] per-shard partials -> each shard owns the reduced
        # [K, stripe, B, 3] of one contiguous column stripe, placed back
        # at its offset (zeros elsewhere; stripe masks hide them)
        hp = jnp.pad(h, ((0, 0), (0, Gpad - G), (0, 0), (0, 0)))
        mine = lax.psum_scatter(hp, axis, scatter_dimension=1, tiled=True)
        me = lax.axis_index(axis)
        out = jnp.zeros_like(hp)
        out = lax.dynamic_update_slice(out, mine, (0, me * per, 0, 0))
        return out[:, :G]

    comm = CommHooks(
        reduce_stats=lambda x: lax.psum(x, axis),
        shard_feature_mask=shard_mask,
        reduce_hist_batch=reduce_hist_batch,
        merge_split_batch=lambda infos, gains: _merge_batch_by_gain(
            infos, gains, axis))

    def wrap(grow):
        return jax.jit(_shard_map(grow, mesh, in_specs, out_specs))

    tree_bytes = _log_collective_estimate("data_frontier", D, G, num_bins,
                                          params.num_leaves)
    return _instrument_grower(
        make_grow_tree_frontier(num_bins, params, block_rows,
                                batch_k=batch_k, gain_ratio=gain_ratio,
                                comm=comm, wrap=wrap),
        "data_frontier", tree_bytes)


def _feature_stripes(mesh: Mesh, num_columns: int, feat_group,
                     column_bins):
    """Feature-parallel stripe maps shared by the fused and O(leaf)
    learners: (column_block, shard_mask, per) with Σbins balancing
    (feature_parallel_tree_learner.cpp:36-47)."""
    axis = mesh.axis_names[0]
    D = int(mesh.devices.size)
    G = num_columns
    if column_bins is not None and len(column_bins) == G and D > 1:
        starts_np, widths_np, per = _balanced_stripes(column_bins, D)
    else:
        per = -(-G // D)
        starts_np = (np.arange(D) * per).astype(np.int32)
        widths_np = np.minimum(per, np.maximum(
            G - starts_np, 0)).astype(np.int32)
    block_starts_d = jnp.asarray(np.minimum(starts_np, max(G - per, 0))
                                 .astype(np.int32))
    starts_d = jnp.asarray(starts_np)
    widths_d = jnp.asarray(widths_np)

    def column_block(bins):
        return block_starts_d[lax.axis_index(axis)], per

    def shard_mask(fmask):
        me = lax.axis_index(axis)
        return _stripe_feature_mask(fmask, axis, starts_d[me],
                                    widths_d[me], feat_group)

    return column_block, shard_mask, per


def make_feature_parallel_oleaf_grower(num_bins: int, params: GrowerParams,
                                       mesh: Mesh, block_rows: int,
                                       num_columns: int, feat_group=None,
                                       column_bins=None,
                                       impl: str = "segment",
                                       batch_k: int = 0,
                                       gain_ratio: float = 0.0):
    """Feature-parallel learner on the O(leaf) segment/frontier growers.

    The reference's feature-parallel contract
    (feature_parallel_tree_learner.cpp:33-75) on the O(leaf) machinery:
    data REPLICATED on every shard; each shard histograms AND scans only
    its Σbins-balanced column stripe over the leaf's confinement
    interval; SplitInfos merge by max-gain all_gather; every shard then
    routes/compacts locally (identical layouts, no row data on the
    wire).  Histogram kernel cost is cut D× by the column slice — the
    interval scan structure is untouched.
    """
    from ..models.grower_frontier import make_grow_tree_frontier
    from ..models.grower_seg import make_grow_tree_segment

    axis = mesh.axis_names[0]
    D = int(mesh.devices.size)
    column_block, shard_mask, _per = _feature_stripes(
        mesh, num_columns, feat_group, column_bins)

    repl = P()
    in_specs = (repl,) * 7
    out_specs = (repl, repl, repl)

    def wrap(grow):
        return jax.jit(_shard_map(grow, mesh, in_specs, out_specs))

    tree_bytes = _log_collective_estimate("feature", D, num_columns,
                                          num_bins, params.num_leaves)
    if impl == "frontier":
        comm = CommHooks(
            shard_feature_mask=shard_mask, column_block=column_block,
            merge_split_batch=lambda infos, gains: _merge_batch_by_gain(
                infos, gains, axis))
        return _instrument_grower(
            make_grow_tree_frontier(num_bins, params, block_rows,
                                    batch_k=batch_k,
                                    gain_ratio=gain_ratio, comm=comm,
                                    wrap=wrap),
            "feature", tree_bytes)
    comm = CommHooks(
        merge_split=lambda info, gain: _merge_split_by_gain(info, gain,
                                                            axis),
        shard_feature_mask=shard_mask, column_block=column_block)
    return _instrument_grower(
        make_grow_tree_segment(num_bins, params, block_rows, comm=comm,
                               wrap=wrap),
        "feature", tree_bytes)


def _merge_batch_by_gain(infos, gains, axis):
    """[2K]-batched SyncUpGlobalBestSplit (shared by the data- and
    feature-parallel frontier learners)."""
    gall = lax.all_gather(gains, axis)              # [D, 2K]
    winner = jnp.argmax(gall, axis=0)               # [2K]
    pick = jnp.arange(gains.shape[0])
    merged = SplitInfo(*[lax.all_gather(f, axis)[winner, pick]
                         for f in infos])
    return merged, gall[winner, pick]


def make_voting_parallel_oleaf_grower(num_bins: int, params: GrowerParams,
                                      mesh: Mesh, block_rows: int,
                                      num_columns: int, feat_group=None,
                                      top_k: int = 20,
                                      impl: str = "segment",
                                      batch_k: int = 0,
                                      gain_ratio: float = 0.0):
    """Voting-parallel learner on the O(leaf) segment/frontier growers.

    PV-Tree (voting_parallel_tree_learner.cpp:170-380) with rows sharded
    like the data-parallel O(leaf) learners: each shard votes its local
    top-k features per histogram call, only the globally-elected columns'
    histograms are psum'd, and both children are histogrammed from data
    (election masks differ per call, so parent-minus-smaller is invalid
    — CommHooks.no_subtract).
    """
    from ..models.grower_frontier import make_grow_tree_frontier
    from ..models.grower_seg import make_grow_tree_segment

    G = num_columns
    axis, D, Gpad, per, _smask, in_specs, out_specs = _stripe_setup(
        mesh, G, feat_group)
    reduce_voted = _make_voting_reduce(axis, params.split, top_k)

    def wrap(grow):
        return jax.jit(_shard_map(grow, mesh, in_specs, out_specs))

    tree_bytes = _log_collective_estimate("voting", D, G, num_bins,
                                          params.num_leaves, top_k)
    if impl == "frontier":
        def reduce_batch(h, fmeta=None):
            # per-leaf elections over the [K, G, B, 3] round batch
            return jax.vmap(
                lambda hk: reduce_voted(hk, None, None, None, fmeta))(h)

        comm = CommHooks(
            reduce_stats=lambda x: lax.psum(x, axis),
            reduce_hist_batch=reduce_batch,
            merge_split_batch=lambda infos, gains: (infos, gains),
            no_subtract=True)
        return _instrument_grower(
            make_grow_tree_frontier(num_bins, params, block_rows,
                                    batch_k=batch_k,
                                    gain_ratio=gain_ratio, comm=comm,
                                    wrap=wrap),
            "voting", tree_bytes)
    comm = CommHooks(
        reduce_hist=reduce_voted,
        reduce_stats=lambda x: lax.psum(x, axis),
        no_subtract=True,
        uniform_scan=lambda b: lax.pmax(b, axis))
    return _instrument_grower(
        make_grow_tree_segment(num_bins, params, block_rows, comm=comm,
                               wrap=wrap),
        "voting", tree_bytes)
