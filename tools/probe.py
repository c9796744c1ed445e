"""Unified perf-probe CLI for the live backend (round-3 verdict: one
probe tool instead of nine scratch scripts).  Subcommands:

    python tools/probe.py train "rows,leaves,warmup,measure" ...
        End-to-end per-iteration time (same as tools/perf_probe.py;
        LIGHTGBM_TPU_SEG_STATS=1 adds scan/compaction counters).
    python tools/probe.py micro [N]
        Device-time microbench of the segment grower's N-scaled
        primitives (histogram / compaction sort / routing / scan) using
        in-jit repetition — (t(K)-t(1))/(K-1) is pure device compute,
        free of the host's dispatch overhead.
    python tools/probe.py sort [N]
        Compaction-strategy comparison: 13-operand lax.sort vs
        sort-(key,index)+gather, plus each part alone.
    python tools/probe.py compile [variant ...]
        AOT trace/compile-stage timing (variants: seg seg_nocompact
        fused kernel scan).
    python tools/probe.py trace [rows] [leaves]
        Capture a jax-profiler trace of 2 iterations and print the
        per-op device-time table from the xplane protobuf.
    python tools/probe.py parse-profile <logdir>
        Summarize an existing xplane dump.

Measurement rules (rounds 2-3): reduce outputs to scalars before fetching,
so that the fetch is not what is timed; identical chained dispatches can
be deduped, so every repetition must consume the previous output.
"""

import glob
import os
import sys
import time
from collections import defaultdict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

F_HIGGS = 28
B_HIGGS = 64


# --------------------------------------------------------------- train

def cmd_train(argv):
    from tools.perf_probe import run
    for spec in argv:
        r, l, w, m = (int(x) for x in spec.split(","))
        run(r, l, w, m)


# --------------------------------------------------------------- micro

def _chained_timer(K):
    """timed(make_fn, label): make_fn(reps) builds fn(binsT, w8, leaf_id)
    whose body runs `reps` chained repetitions; reports per-op device
    time from the K-vs-1 difference."""
    def timed(make_fn, label, args, scale=1.0):
        import jax
        f1 = jax.jit(make_fn(1))
        fK = jax.jit(make_fn(K))
        np.asarray(f1(*args)).sum()          # compile + first run
        np.asarray(fK(*args)).sum()
        ts = []
        for f in (f1, fK):
            t0 = time.perf_counter()
            np.asarray(f(*args)).sum()
            ts.append(time.perf_counter() - t0)
        per = (ts[1] - ts[0]) / (K - 1)
        print(f"{label}: {per*1e3:.2f} ms/op (t1={ts[0]*1e3:.1f} "
              f"tK={ts[1]*1e3:.1f}) -> x{scale:.0f}/tree = "
              f"{per * scale * 1e3:.0f} ms", flush=True)
        return per
    return timed


def cmd_micro(argv):
    N = int(argv[0]) if argv else 10_500_000
    K = 9
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbm_tpu.models.grower_seg import (_pack_bins_words,
                                                _pack_w8_words)
    from lightgbm_tpu.ops.pallas_histogram import (histogram_segment,
                                                   pack_channels,
                                                   pick_block_rows)
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams, best_split
    from lightgbm_tpu.utils import enable_jax_compilation_cache
    enable_jax_compilation_cache()

    F, B = F_HIGGS, B_HIGGS
    rb = pick_block_rows(F, B, N)
    npad = -(-N // rb) * rb
    nblk = npad // rb
    print(f"N={N} rb={rb} blocks={nblk} backend={jax.default_backend()}",
          flush=True)
    rng = np.random.RandomState(0)
    F4 = F + (-F) % 4
    binsT = jnp.asarray(rng.randint(0, B, size=(F4, npad),
                                    dtype=np.int64).astype(np.uint8))
    grad = jnp.asarray(rng.normal(size=npad).astype(np.float32))
    w8 = pack_channels(grad, jnp.ones(npad, jnp.float32),
                       jnp.ones(npad, jnp.float32))
    leaf_id = jnp.asarray(rng.randint(0, 2, size=npad).astype(np.int32))
    args = (binsT, w8, leaf_id)
    timed = _chained_timer(K)

    def mk_hist(reps):
        def fn(bT, w, lid):
            def body(i, acc):
                h = histogram_segment(bT, w, lid, jnp.int32(0),
                                      jnp.int32(nblk), i % 2, B, rb)
                return acc + h
            return lax.fori_loop(0, reps, body,
                                 jnp.zeros((F4, B, 8), jnp.float32))
        return fn
    # sum of smaller-child intervals/tree ~ 10N with default compaction
    timed(mk_hist, "hist_full_N", args, scale=10.0)

    # K=16 frontier kernel over the same full-N pass: the one-hot build
    # is shared across the 16 output-channel groups, so per-row cost
    # should approach the strict kernel's (NOT 16x) while producing 16
    # leaves' histograms — the MXU-utilization fix being measured
    from lightgbm_tpu.ops.pallas_histogram import histogram_frontier
    Kf = 16
    all_blocks = jnp.arange(nblk, dtype=jnp.int32)
    targets16 = jnp.arange(Kf, dtype=jnp.int32) % 2

    def mk_frontier(reps):
        def fn(bT, w, lid):
            def body(i, acc):
                h = histogram_frontier(bT, w, lid, all_blocks,
                                       jnp.int32(nblk),
                                       targets16 + (i % 2), B, rb)
                return acc + h[0]
            return lax.fori_loop(0, reps, body,
                                 jnp.zeros((F4, B, 8), jnp.float32))
        return fn
    timed(mk_frontier, f"hist_frontier_K{Kf}_full_N", args, scale=1.0)

    def mk_sort(reps):
        def fn(bT, w, lid):
            def body(i, lid_c):
                ops = ((lid_c + i,) + tuple(_pack_bins_words(bT))
                       + tuple(_pack_w8_words(w)))
                return lax.sort(ops, num_keys=1, is_stable=True)[0]
            return lax.fori_loop(0, reps, body, lid)
        return fn
    timed(mk_sort, "compact_sort", args, scale=4.0)

    # once-per-tree unpermute: random scatter vs 2-operand sort (the
    # growers use the sort form; this pair quantifies the difference)
    perm = jnp.asarray(rng.permutation(npad).astype(np.int32))

    def mk_unperm_scatter(reps):
        def fn(bT, w, lid):
            def body(i, lid_c):
                return jnp.zeros(npad, jnp.int32).at[perm].set(lid_c + i)
            return lax.fori_loop(0, reps, body, lid)
        return fn
    timed(mk_unperm_scatter, "unpermute_scatter", args, scale=1.0)

    def mk_unperm_sort2(reps):
        def fn(bT, w, lid):
            def body(i, lid_c):
                return lax.sort((perm, lid_c + i), num_keys=1)[1]
            return lax.fori_loop(0, reps, body, lid)
        return fn
    timed(mk_unperm_sort2, "unpermute_sort2", args, scale=1.0)

    # score update's [L]-table gather by a full-N index vector, vs the
    # one-hot-matmul pallas scorer that replaced it (ops/pallas_score)
    lv = jnp.asarray(rng.normal(size=256).astype(np.float32))

    def mk_table_gather(reps):
        def fn(bT, w, lid):
            def body(i, acc):
                return acc + lv[jnp.minimum(lid + i, 255)]
            return lax.fori_loop(0, reps, body,
                                 jnp.zeros(npad, jnp.float32))
        return fn
    timed(mk_table_gather, "score_table_gather", args, scale=1.0)

    from lightgbm_tpu.ops.pallas_score import score_gather_add

    def mk_score_kernel(reps):
        def fn(bT, w, lid):
            def body(i, acc):
                return score_gather_add(acc, jnp.minimum(lid + i, 255), lv)
            return lax.fori_loop(0, reps, body,
                                 jnp.zeros(npad, jnp.float32))
        return fn
    timed(mk_score_kernel, "score_onehot_kernel", args, scale=1.0)

    # per-skipped-grid-step cost: a 1-block interval dispatched on the
    # full-size grid pays (blocks-1) skipped steps; against the 1-block
    # grid the delta isolates the per-step overhead the bucket ladder
    # trades against compile variants
    from lightgbm_tpu.ops.pallas_histogram import _histogram_segment_fixed

    def mk_skip(grid):
        def mk(reps):
            def fn(bT, w, lid):
                def body(i, acc):
                    h = _histogram_segment_fixed(
                        bT, w, lid, jnp.int32(0), jnp.int32(1), i % 2, B,
                        rb, grid)
                    return acc + h
                return lax.fori_loop(0, reps, body,
                                     jnp.zeros((F4, B, 8), jnp.float32))
            return fn
        return mk
    timed(mk_skip(nblk), f"hist_1blk_on_{nblk}grid", args, scale=1.0)
    timed(mk_skip(1), "hist_1blk_on_1grid", args, scale=1.0)

    def mk_route(reps):
        def fn(bT, w, lid):
            def body(i, lid_c):
                fcol = lax.dynamic_slice_in_dim(bT, i % F, 1, axis=0)[0, :]
                go_left = fcol.astype(jnp.int32) <= 31
                in_leaf = lid_c == i % 7
                return jnp.where(in_leaf & ~go_left, i % 7 + 1, lid_c)
            return lax.fori_loop(0, reps, body, lid)
        return fn
    timed(mk_route, "route_pass", args, scale=254.0)

    fmeta = FeatureMeta(
        num_bin=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        is_cat=jnp.zeros(F, bool),
        monotone=jnp.zeros(F, jnp.int32),
        penalty=jnp.ones(F, jnp.float32))
    sp = SplitParams(has_cat=False)

    def mk_scan(reps):
        def fn(bT, w, lid):
            h0 = histogram_segment(bT, w, lid, jnp.int32(0), jnp.int32(1),
                                   jnp.int32(0), B, rb)
            hist = jnp.stack([h0[..., 0] + h0[..., 1],
                              h0[..., 2] + h0[..., 3],
                              h0[..., 4]], axis=-1)[:F]

            def body(i, acc):
                info = best_split(hist + acc * 1e-9, 1.0, float(N),
                                  float(N), fmeta, sp,
                                  jnp.ones(F, jnp.float32))
                return acc + info.gain
            return lax.fori_loop(0, reps, body, jnp.float32(0.0))
        return fn
    timed(mk_scan, "scan_one", args, scale=508.0)


# ---------------------------------------------------------------- sort

def cmd_sort(argv):
    N = int(argv[0]) if argv else 10_500_000
    K = 5
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lightgbm_tpu.models.grower_seg import (_pack_bins_words,
                                                _pack_w8_words)
    from lightgbm_tpu.ops.pallas_histogram import (pack_channels,
                                                   pick_block_rows)
    from lightgbm_tpu.utils import enable_jax_compilation_cache
    enable_jax_compilation_cache()

    rb = pick_block_rows(F_HIGGS, B_HIGGS, N)
    npad = -(-N // rb) * rb
    print(f"N={N} npad={npad} backend={jax.default_backend()}", flush=True)
    rng = np.random.RandomState(0)
    binsT = jnp.asarray(rng.randint(0, 64, size=(32, npad),
                                    dtype=np.int64).astype(np.uint8))
    w8 = pack_channels(jnp.asarray(rng.normal(size=npad).astype(np.float32)),
                       jnp.ones(npad, jnp.float32),
                       jnp.ones(npad, jnp.float32))
    lid0 = jnp.asarray(rng.randint(0, 256, size=npad).astype(np.int32))
    args = (binsT, w8, lid0)
    timed = _chained_timer(K)

    def reshuffle(lid, i):
        # cheap pseudo-random re-key so every chained sort does real work
        return ((lid * 1103515245 + i * 12345) & 0xFF).astype(jnp.int32)

    def mk_full(reps):
        def fn(bT, w, lid):
            def body(i, lid_c):
                ops = ((reshuffle(lid_c, i),) + tuple(_pack_bins_words(bT))
                       + tuple(_pack_w8_words(w))
                       + (jnp.arange(npad, dtype=jnp.int32),))
                return lax.sort(ops, num_keys=1, is_stable=True)[0]
            return jnp.sum(lax.fori_loop(0, reps, body, lid))
        return fn
    timed(mk_full, "sort13", args)

    def mk_pair(reps):
        def fn(bT, w, lid):
            def body(i, lid_c):
                keys = reshuffle(lid_c, i)
                _, perm = lax.sort(
                    (keys, jnp.arange(npad, dtype=jnp.int32)),
                    num_keys=1, is_stable=True)
                b2 = jnp.take(bT, perm, axis=1)
                w2 = jnp.take(w, perm, axis=1)
                return lid_c + b2[0].astype(jnp.int32) + \
                    w2[4].astype(jnp.int32)
            return jnp.sum(lax.fori_loop(0, reps, body, lid))
        return fn
    timed(mk_pair, "sort2+gather", args)

    def mk_pair_only(reps):
        def fn(bT, w, lid):
            def body(i, lid_c):
                keys = reshuffle(lid_c, i)
                s, perm = lax.sort(
                    (keys, jnp.arange(npad, dtype=jnp.int32)),
                    num_keys=1, is_stable=True)
                return lid_c + s + perm
            return jnp.sum(lax.fori_loop(0, reps, body, lid))
        return fn
    timed(mk_pair_only, "sort2_only", args)

    def mk_gather(reps):
        def fn(bT, w, lid):
            def body(i, acc):
                perm = (jnp.arange(npad, dtype=jnp.int32) * 7 + i) % npad
                b2 = jnp.take(bT, perm, axis=1)
                w2 = jnp.take(w, perm, axis=1)
                return acc + b2[0].astype(jnp.int32) + \
                    w2[4].astype(jnp.int32)
            return jnp.sum(lax.fori_loop(0, reps, body, lid))
        return fn
    timed(mk_gather, "gather_only", args)


# ------------------------------------------------------------- compile

def cmd_compile(argv):
    import jax
    import jax.numpy as jnp

    variants = argv or ["seg", "kernel", "scan", "fused"]
    N, F, B, L, RB = 65536, 28, 64, 255, 8192
    rng = np.random.RandomState(0)
    binsT = jnp.asarray(rng.randint(0, B, size=(F, N)).astype(np.uint8))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    member = jnp.ones(N, jnp.float32)
    key = jax.random.PRNGKey(0)
    from lightgbm_tpu.models.grower import GrowerParams
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    fmeta = FeatureMeta(
        num_bin=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        is_cat=jnp.zeros(F, bool),
        monotone=jnp.zeros(F, jnp.int32),
        penalty=jnp.ones(F, jnp.float32))
    fmask = jnp.ones(F, jnp.float32)
    params = GrowerParams(num_leaves=L, hist_backend="pallas",
                          split=SplitParams(min_sum_hessian_in_leaf=100.0,
                                            has_cat=False))

    def stage_time(name, make_lowered):
        t0 = time.perf_counter()
        lowered = make_lowered()
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        print(f"{name}: trace={t1-t0:.1f}s compile={t2-t1:.1f}s",
              flush=True)
        return compiled

    if "seg" in variants:
        from lightgbm_tpu.models.grower_seg import make_grow_tree_segment
        grow = make_grow_tree_segment(B, params, RB)
        stage_time("segment grower", lambda: grow.lower(
            binsT, g, g, member, fmeta, fmask, key))

    if "frontier" in variants:
        from lightgbm_tpu.models.grower_frontier import (
            make_grow_tree_frontier)
        grow = make_grow_tree_frontier(B, params, RB, batch_k=16)
        stage_time("frontier grower (K=16)", lambda: grow.lower(
            binsT, g, g, member, fmeta, fmask, key))

    if "seg_nocompact" in variants:
        import unittest.mock as _mock

        import lightgbm_tpu.models.grower_seg as gs
        with _mock.patch.object(gs, "COMPACT_WASTE", 2.0**30):
            grow = gs.make_grow_tree_segment(B, params, RB)
            stage_time("segment grower (compaction unreachable; cond "
                       "still traced)", lambda: grow.lower(
                           binsT, g, g, member, fmeta, fmask, key))

    if "fused" in variants:
        from lightgbm_tpu.models.grower import make_grow_tree
        grow = make_grow_tree(B, params)
        stage_time("fused grower (pallas hist)", lambda: grow.lower(
            binsT, g, g, member, fmeta, fmask, key))

    if "kernel" in variants:
        from lightgbm_tpu.ops.pallas_histogram import (histogram_segment,
                                                       pack_channels)
        w8 = pack_channels(g, g, member)
        lid = jnp.zeros(N, jnp.int32)

        @jax.jit
        def seg(binsT, w8, lid):
            return histogram_segment(binsT, w8, lid, jnp.int32(0),
                                     jnp.int32(2), jnp.int32(0), B, RB)

        stage_time("segment kernel alone",
                   lambda: seg.lower(binsT, w8, lid))

    if "scan" in variants:
        from lightgbm_tpu.ops.split import best_split

        @jax.jit
        def scan2(hist2):
            return jax.vmap(
                lambda h: best_split(h, jnp.float32(1.0), jnp.float32(2.0),
                                     jnp.float32(1e5), fmeta,
                                     params.split, fmask))(hist2)

        hist2 = jnp.ones((2, F, B, 3), jnp.float32)
        stage_time("vmapped pair best_split", lambda: scan2.lower(hist2))


# --------------------------------------------------------------- trace

TRACE_DIR = "/tmp/lgbtpu_trace"


def cmd_trace(argv):
    N = int(argv[0]) if argv else 10_500_000
    L = int(argv[1]) if len(argv) > 1 else 255
    import jax

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.core.dataset import TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objective import create_objective

    rng = np.random.RandomState(42)
    X = rng.normal(size=(N, 28)).astype(np.float32)
    y = (2 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
         + rng.normal(size=N) * 0.5 > 0).astype(np.float64)
    cfg = Config(objective="binary", num_leaves=L, max_bin=63,
                 learning_rate=0.1, min_sum_hessian_in_leaf=100.0,
                 verbosity=-1,
                 tpu_tree_impl=os.environ.get("LIGHTGBM_TPU_IMPL", "auto"))
    ds = TpuDataset.from_numpy(X, y, config=cfg)
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    booster = GBDT(cfg, ds, obj)
    for _ in range(2):
        booster.train_one_iter()
    jax.block_until_ready(booster.train_score)
    jax.profiler.start_trace(TRACE_DIR)
    for _ in range(2):
        booster.train_one_iter()
    jax.block_until_ready(booster.train_score)
    jax.profiler.stop_trace()
    _summarize_xplane(TRACE_DIR)


def _summarize_xplane(trace_dir):
    # the tensorboard_plugin_profile wheel in this image ships no
    # python protobufs; tensorflow's tsl copy of xplane_pb2 parses the
    # same .xplane.pb files
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no xplane under {trace_dir}"
    path = max(paths, key=os.path.getmtime)
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        xs.ParseFromString(fh.read())
    for plane in xs.planes:
        if "tpu" not in plane.name.lower():
            continue
        tot = defaultdict(float)
        cnt = defaultdict(int)
        for line in plane.lines:
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                tot[name] += ev.duration_ps / 1e12
                cnt[name] += 1
        items = sorted(tot.items(), key=lambda kv: -kv[1])
        total = sum(tot.values())
        print(f"== plane {plane.name}: lines={len(plane.lines)} "
              f"total={total:.3f}s (2 iters; includes overlap)")
        for name, sec in items[:40]:
            print(f"  {sec:8.3f}s x{cnt[name]:<7} {name[:110]}")


def cmd_parse_profile(argv):
    _summarize_xplane(argv[0] if argv else TRACE_DIR)


# ---------------------------------------------------------------- main

COMMANDS = {
    "train": cmd_train,
    "micro": cmd_micro,
    "sort": cmd_sort,
    "compile": cmd_compile,
    "trace": cmd_trace,
    "parse-profile": cmd_parse_profile,
}

if __name__ == "__main__":
    if len(sys.argv) < 2 or sys.argv[1] not in COMMANDS:
        print(__doc__)
        sys.exit(2)
    COMMANDS[sys.argv[1]](sys.argv[2:])
