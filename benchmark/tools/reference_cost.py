"""What the plain reference costs at a shape, without the program:

    python3 benchmark/tools/reference_cost.py --rows 1100000 --features 2000

Rows from `higgs_proxy` and the seed, `--trees` synthetic trees of `--leaves`
leaves (any tree costs the reference the same: every row is routed through
every split and the histogram covers every candidate of every feature), then
what a run does after its window: the rows onto the device, three steps
followed, all the trees summed over every row, the comparison.  One JSON
line: seconds by part, the block the shape gave, the device and its peak
bytes, also as they stood after each part (a process's peak never falls:
the first part to show the last figure set it).  Whoever sizes a cell adds
this to the run's other parts (`README.md`, "The budget of a run") before
asking for the cell.  The numbers compared mean nothing here (the trees are
not grown from the rows); only the seconds and the bytes do.  It is never
part of a benchmark run.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402


def synthetic_tree(rs, n_feat: int, leaves: int, thresholds: np.ndarray):
    """A random tree in LightGBM's flat arrays: split s opens a leaf drawn
    from those open, which keeps its number on the left while leaf s + 1
    appears on the right."""
    import reference
    S = leaves - 1
    left = np.zeros(S, np.int64)
    right = np.zeros(S, np.int64)
    made_by = {0: None}                 # leaf -> (split, side) that holds it
    for s in range(S):
        k = int(rs.integers(0, s + 1))
        if made_by[k] is not None:
            p, side = made_by[k]
            (right if side else left)[p] = s
        left[s], right[s] = ~k, ~(s + 1)
        made_by[k], made_by[s + 1] = (s, 0), (s, 1)
    return reference.RefTree(
        num_leaves=leaves, shrinkage=0.1,
        split_feature=rs.integers(0, n_feat, S),
        threshold=thresholds[rs.integers(0, len(thresholds), S)],
        decision_type=np.full(S, 2, np.int64), left_child=left,
        right_child=right, split_gain=np.ones(S), internal_weight=np.ones(S),
        internal_count=np.ones(S, np.int64),
        leaf_value=rs.normal(size=leaves) * 0.01, leaf_weight=np.ones(leaves),
        leaf_count=np.ones(leaves, np.int64))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--features", type=int, required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--trees", type=int, default=32)
    ap.add_argument("--max-bin", type=int, default=63)
    args = ap.parse_args()

    import jax

    import compare
    import datagen
    import reference
    import run
    seconds, peak_after = {}, {}

    def note_peak(part):
        stats = jax.devices()[0].memory_stats() or {}
        peak_after[part] = int(stats.get("peak_bytes_in_use", 0))

    t = time.perf_counter()
    X, y = datagen.make({"generator": "higgs_proxy",
                         "features": args.features, "noise": 0.5},
                        args.rows, np.random.default_rng(args.seed))
    seconds["rows"] = time.perf_counter() - t
    rs = np.random.default_rng(args.seed + 1)
    quantiles = np.linspace(-2.2, 2.2, args.max_bin)
    trees = [synthetic_tree(rs, args.features, args.leaves, quantiles)
             for _ in range(args.trees)]
    params = {"objective": "binary", "max_bin": args.max_bin,
              "num_leaves": args.leaves, "learning_rate": 0.1,
              "min_sum_hessian_in_leaf": 100}
    n_bins = -(-(args.max_bin + 1) // 8) * 8

    # a run has used the device long before its reference starts
    jax.block_until_ready(jax.numpy.zeros(8))
    t_ref = time.perf_counter()
    cand = reference.candidate_thresholds(trees, args.features, n_bins)
    t = time.perf_counter()
    fol = reference.Follower(X, y, params, cand, args.leaves)
    jax.block_until_ready(fol.Xb)
    seconds["upload"] = time.perf_counter() - t
    note_peak("upload")
    device_s = []
    sums = fol._sums

    def timed_sums(*a):
        t = time.perf_counter()
        out = jax.block_until_ready(sums(*a))
        device_s.append(time.perf_counter() - t)
        return out

    fol._sums = timed_sums
    try:
        facts, readings, step_s = [], [], []
        for i, tree in enumerate(trees[:run.FOLLOWED_STEPS]):
            t = time.perf_counter()
            f = compare.facts_of_tree(tree, fol.init_score if i == 0 else 0.0)
            readings.append(fol.step(tree, f.leaf_value))
            facts.append(f)
            step_s.append(time.perf_counter() - t)
        note_peak("steps")
        t = time.perf_counter()
        summed = fol.sum_forest(trees)
        seconds["sum_forest"] = time.perf_counter() - t
        note_peak("sum_forest")
    finally:
        block = fol.block_rows
        fol.close()
    t = time.perf_counter()
    compare.first_steps(facts, readings)
    compare.score_gap(summed, summed, fol.init_score)
    seconds["compare"] = time.perf_counter() - t
    seconds["steps"] = step_s
    seconds["steps_device"] = device_s
    seconds["reference"] = time.perf_counter() - t_ref
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(json.dumps({
        "rows": args.rows, "features": args.features, "candidates": n_bins,
        "leaves": args.leaves, "trees": args.trees, "block_rows": block,
        "unrouted": int(sum(r.unrouted for r in readings)),
        "seconds": seconds, "peak_bytes_after": peak_after,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
                   "bytes_limit": int(stats.get("bytes_limit", 0))}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
