"""One chip: what a full-N pass of the strict grower's split kernels costs
and how exact its sums are.

  * ns a row scanned: the routed kernel (one lane set) and the lookahead
    kernel with all of its K lane sets live and with none; a route-only
    pass (n_acc = 0) in us a block;
  * on the device, that every lookahead lane set equals lane set 0 of a
    pass over that slot's rows, bit for bit;
  * the error of one leaf's histogram against float64 sums on the host,
    for a leaf whose rows are spread over every block (what a lookahead
    lane set sums before the first compaction) and for the same number of
    rows packed into a tight interval (what the scan it replaces sums
    after one): the routed kernel's single f32 total against the lookahead
    kernel's (hi, lo) pair across blocks;
  * ``--unit-costs``: the two unit costs of the grower's compaction
    trigger alone, a full accumulating pass and one ``compact_state`` of
    the same table on the path its width takes, beside what
    ``grower_seg.compaction_budget_blocks`` reckons for them.

    python tools/seg_pass_bench.py [--rows 36750000] [--bins 64]
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from lightgbm_tpu.models import grower_seg as gs  # noqa: E402
from lightgbm_tpu.ops import pallas_histogram as ph  # noqa: E402


class _Meta:
    feat_group = None
    feat_offset = None

    def __init__(self, F, B):
        self.missing_type = jnp.zeros(F, jnp.int32)
        self.default_bin = jnp.zeros(F, jnp.int32)
        self.num_bin = jnp.full((F,), B, jnp.int32)


def _gaps(got, want):
    """Per-bin gaps of a [B] sum against float64, on the scale of the
    larger of the bin and the median bin."""
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    g = np.abs(np.asarray(got, np.float64) - want) / np.maximum(scale, 1e-300)
    return {"max": float(g.max()), "p50": float(np.median(g))}


def _timed(fn, reps):
    """Median seconds of ``reps`` calls after one that warms up."""
    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _compaction_costs(a, binsT, w8, n, nblk, rb):
    """One ``compact_state`` of the table alone (leaf ids of 64 leaves
    spread over every block, as before a tree's first compaction), and
    the trigger's own reckoning for this shape."""
    L = 255
    st = gs.fresh_state(binsT, w8, n, L, 4, 16, 4, nblk, 0.0, 1.0, 1.0, None,
                        gs.GrowerParams(num_leaves=L))
    st = st._replace(leaf_id=jax.random.randint(
        jax.random.PRNGKey(3), (n,), 0, 64, jnp.int32))
    compact = jax.jit(lambda s: gs.compact_state(s, L, rb))
    t0 = time.perf_counter()
    jax.block_until_ready(compact(st))      # the build, for the record
    first = time.perf_counter() - t0
    t = _timed(lambda: compact(st), a.reps)
    model = gs.compaction_unit_costs(a.features, a.bins, n, False)
    return {"compaction_path": model["path"],
            "compaction_s": t, "compaction_ns_per_row": t / n * 1e9,
            "compaction_first_call_s": first,
            "model_pass_ns_per_row": model["pass_ns_per_row"],
            "model_compaction_ns_per_row": model["compaction_ns_per_row"],
            "budget_blocks": gs.compaction_budget_blocks(
                a.features, a.bins, n, rb, False),
            "max_blocks": nblk}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=36_750_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--bins", type=int, default=64)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--shares", default="0.03,0.3",
                    help="the accuracy leaves' shares of the rows")
    ap.add_argument("--lanes", type=int, default=0,
                    help="lane sets of the lookahead kernel (0: what the "
                         "grower would pick, lookahead_width)")
    ap.add_argument("--unroll", type=int, default=0,
                    help="chunks a loop body of the lookahead kernel "
                         "(0: as built)")
    ap.add_argument("--unit-costs", action="store_true",
                    help="time a full pass and a compaction, nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the code on any backend; its times are not "
                         "device times")
    a = ap.parse_args()
    if jax.default_backend() != "tpu" and not a.rehearse:
        sys.exit("seg_pass_bench needs a TPU")
    if a.unroll:
        ph._LOOKAHEAD_UNROLL = a.unroll     # read when the kernel traces
    F, B = a.features, a.bins
    rb = ph.pick_block_rows(F, B, a.rows)
    n = -(-a.rows // rb) * rb
    nblk = n // rb
    meta = _Meta(F, B)
    kb, kg, kh, kl, km = jax.random.split(jax.random.PRNGKey(0), 5)
    # the table as the grower holds it (bin rows padded to whole sort
    # words or feature tiles), drawn 128 rows at a time (4 bytes a bin)
    F_rows = gs.table_bin_rows(F, B, False)
    binsT = jnp.concatenate([
        jax.random.randint(jax.random.fold_in(kb, r), (min(128, F - r), n),
                           0, B, jnp.int32).astype(jnp.uint8)
        for r in range(0, F, 128)]
        + [jnp.zeros((F_rows - F, n), jnp.uint8)] * (F_rows > F))
    # the second tree of a binary run: |g| near 0.5, h near 0.25
    p = jax.nn.sigmoid(0.13 * jnp.sign(jax.random.normal(kg, (n,)))
                       + 0.01 * jax.random.normal(kh, (n,)))
    y = (jax.random.uniform(kl, (n,)) < 0.5).astype(jnp.float32)
    grad, hess = p - y, p * (1.0 - p)
    w8 = ph.pack_channels(grad, hess, jnp.ones(n, jnp.float32))
    lid = jax.random.randint(km, (n,), 0, 24, jnp.int32)
    zero8 = jnp.zeros(8, jnp.uint32)
    route = ph.pack_route(0, 24, 3, B // 2, False, False, zero8, meta, False)
    K = a.lanes or ph.lookahead_width(F, B, rb, False)

    def slots_for(live):
        k = K - 1
        leaves = jnp.where(jnp.arange(k) < live,
                           jnp.arange(1, 1 + k, dtype=jnp.int32), -1)
        return ph.pack_lookahead_slots(
            leaves, jnp.arange(k) % 2,
            (5 + 3 * jnp.arange(k, dtype=jnp.int32)) % F,
            jnp.full(k, B // 3, jnp.int32), jnp.zeros(k, bool),
            jnp.zeros(k, bool), jnp.zeros((k, 8), jnp.uint32), meta, False)

    out = {"device": jax.devices()[0].device_kind,
           "rehearsal": bool(a.rehearse), "rows": n,
           "features": F, "bins": B, "block_rows": rb, "K": K,
           "unroll": ph._LOOKAHEAD_UNROLL}

    def timed(fn):
        return _timed(fn, a.reps)

    def note(key, value):
        out[key] = value
        print(f"{key} = {value}", file=sys.stderr, flush=True)

    s0, nb, tgt = jnp.int32(0), jnp.int32(nblk), jnp.int32(24)
    if not a.unit_costs:    # one kernel build less where only the pass counts
        t = timed(lambda: ph.histogram_segment_routed(
            binsT, w8, lid, s0, nb, tgt, route, B, rb))
        note("ns_per_row_routed", t / n * 1e9)
    for name, live in (("full", K - 1), ("empty", 0)):
        sl = slots_for(live)
        t = timed(lambda: ph.histogram_segment_lookahead(
            binsT, w8, lid, s0, nb, tgt, route, sl, nb, B, rb))
        note(f"ns_per_row_lookahead_{name}", t / n * 1e9)
    sl = slots_for(K - 1)
    t = timed(lambda: ph.histogram_segment_lookahead(
        binsT, w8, lid, s0, nb, tgt, route, sl, jnp.int32(0), B, rb))
    note("route_only_us_per_block", t / nblk * 1e6)
    if a.unit_costs:
        for key, value in _compaction_costs(a, binsT, w8, n, nblk,
                                            rb).items():
            note(key, value)
        print(json.dumps(out))
        return

    # every lane set against lane set 0 of a pass over its rows
    m = jnp.int32(min(nblk, 24))
    lid1, hk = ph.histogram_segment_lookahead(
        binsT, w8, lid, s0, m, tgt, route, sl, m, B, rb)
    from lightgbm_tpu.ops.split import routed_left
    empty = ph.empty_lookahead_slots(K - 1)
    ok = True
    for k in range(1, K):
        d = sl[k - 1]
        go = routed_left(binsT[d[2]].astype(jnp.int32), d[4],
                         d[5].astype(bool), d[6].astype(bool), zero8,
                         d[7], d[8], d[9])
        marked = jnp.where((lid1 == d[0]) & (go == (d[1] == 1)), 999, lid1)
        _, ref = ph.histogram_segment_lookahead(
            binsT, w8, marked, s0, m, jnp.int32(999), ph.null_route(),
            empty, m, B, rb)
        ok = ok and bool(jnp.array_equal(hk[k], ref[0]))
        ok = ok and float(jnp.abs(ref[0]).sum()) > 0
    out["lane_sets_bit_identical"] = ok

    # one leaf's sums against float64: rows spread over every block, and
    # as many rows packed into the first blocks
    g64 = np.asarray(grad, np.float64)
    h64 = np.asarray(hess, np.float64)
    b0 = np.asarray(binsT[0])
    draw = np.asarray(jax.random.uniform(jax.random.PRNGKey(7), (n,)))
    for share in (float(x) for x in a.shares.split(",") if x):
        spread = draw < share
        tight = np.arange(n) < int(spread.sum())
        for name, member in (("spread", spread), ("tight", tight)):
            want_g = np.bincount(b0[member], g64[member], minlength=B)
            want_h = np.bincount(b0[member], h64[member], minlength=B)
            ids = jnp.where(jnp.asarray(member), 7, 0).astype(jnp.int32)
            blocks = jnp.int32(nblk if name == "spread"
                               else -(-int(member.sum()) // rb))
            _, plain = ph.histogram_segment_routed(
                binsT, w8, ids, s0, blocks, jnp.int32(7), ph.null_route(),
                B, rb)
            _, pair = ph.histogram_segment_lookahead(
                binsT, w8, ids, s0, blocks, jnp.int32(7), ph.null_route(),
                empty, blocks, B, rb)
            for kind, h in (("f32_total", plain), ("hi_lo_pair", pair[0])):
                u = np.asarray(ph.unpack_hist(h))[0]
                key = f"share{share:g}_{name}_{kind}"
                out[key + "_grad_gap"] = _gaps(u[:, 0], want_g)
                out[key + "_hess_gap"] = _gaps(u[:, 1], want_h)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
