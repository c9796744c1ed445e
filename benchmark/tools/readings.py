"""Readings that set the limits of `correct`, several seeds in one process:

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 [--measure]

For each seed: the cell's rows, binning and the warm-up chunk through the
run's own code (`run.run_cell`), then the numbers the comparison reads for
the program, for the control (the reference in bfloat16 in the program's
place) and for the fault that leaves out every second row.  One JSON line a
seed on standard output and in `chiprun_out/readings_<cell>.jsonl`.  It
needs the chip like a run does; it is never part of a benchmark run.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run  # noqa: E402


def control_and_faults(ctx, with_witness=False):
    """What the comparison reads with, in the program's place: the reference
    in bfloat16 (the control); the reference over every second row (half of
    the batch left out); a split scan without the best feature; the first
    tree grown again at every step (a state left unchanged)."""
    import numpy as np

    import compare
    X, y, params, cand = ctx["X"], ctx["y"], ctx["params"], ctx["cand"]
    trees, readings = ctx["trees"][:run.FOLLOWED_STEPS], ctx["readings"]
    out = {}
    low = run.follow(X, y, params, trees, cand, tested=False,
                     precision="bfloat16", with_hist=False)[1]
    out["control_bfloat16"] = compare.control_readings(readings, low)
    import jax.numpy as jnp
    rounded = ctx["summed"].astype(jnp.bfloat16).astype(np.float32)
    out["control_bfloat16"]["score_gap"] = compare.score_gap(
        rounded, ctx["summed"], ctx["init_score"])
    half = run.follow(X[::2], y[::2], params, trees, cand, tested=False,
                      with_hist=False)[1]
    out["fault_half_rows"] = compare.control_readings(readings, half)
    out["fault_skipped_feature"] = compare.skipped_feature_shortfall(readings)
    again = [trees[0]] * len(trees)
    facts, under = run.follow(X, y, params, again, cand, with_hist=False)[:2]
    # every step's tree carries the init score, as the first does
    out["fault_state_unchanged"] = compare.first_steps(
        [facts[0]] * len(trees), under)
    if with_witness:
        import witness
        host = witness.HostFollower(X, y, params)
        out["witness"] = []
        for f, r, t in zip(ctx["facts"], readings, trees):
            w = host.step(t)
            out["witness"].append({
                "prog_count": f.leaf_count, "prog_h": f.leaf_weight,
                "prog_value": f.leaf_value, "prog_gain": f.gain,
                "prog_node_count": f.node_count, "prog_node_h": f.node_weight,
                "ref_count": r.leaf_c, "ref_g": r.leaf_g, "ref_h": r.leaf_h,
                "ref_value": r.leaf_value, "ref_gain": r.gain,
                "ref_node_count": r.node_c,
                "host_count": w["count"], "host_g": w["g"], "host_h": w["h"],
                "host_value": w["value"],
                "split_feature": t.split_feature, "threshold": t.threshold,
                "left_child": t.left_child, "right_child": t.right_child})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--measure", action="store_true",
                    help="train the measured chunks too (a full run)")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--witness", action="store_true",
                    help="also follow the steps in float64 numpy on the "
                         "host, and keep every per-leaf array in an .npz")
    args = ap.parse_args()
    man = run.Manifest()
    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"readings_{args.workload}.jsonl")
    worst = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        code, result, extras = run.run_cell(
            man, args.workload, seed, args.seconds, 0, args.rehearse,
            measure=args.measure,
            more_readings=lambda ctx: control_and_faults(ctx, args.witness))
        if result is None:
            return code
        for i, arrays in enumerate(extras.pop("witness", [])):
            import numpy as np
            np.savez(os.path.join(
                out_dir, f"witness_{args.workload}_{seed}_step{i + 1}.npz"),
                **{k: v for k, v in arrays.items() if v is not None})
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "correct": result["correct"],
                           "metrics": result["metrics"],
                           "steps": result["steps"], **extras})
        print(line, flush=True)
        with open(path, "a") as fh:
            fh.write(line + "\n")
        worst = max(worst, code)
    return worst


if __name__ == "__main__":
    sys.exit(main())
