"""Serving benchmark: BENCH_SERVE.json + trajectory records.

Measures the lightgbm_tpu/serve stack the way bench_suite.py measures
training: each model size runs in a child of its own (hard timeout, one
JSON result line per grid cell, stamped with the device it ran on) — one
process holds the chip at a time, and this parent never imports jax.  The
parent collects the grid into BENCH_SERVE.json and appends one digest line
per cell to BENCH_TRAJECTORY.jsonl, where tools/bench_gate.py gates the
p99 against the trailing median (+20%).  Without a TPU a child exits
non-zero and no record is produced.

The grid is (model size) x (batch bucket) x (serve_max_delay_ms):
requests of exactly one bucket's rows are pushed through the
micro-batching queue one at a time, so ``p50_s``/``p99_s`` are
END-TO-END request latencies (queue wait + padded compiled dispatch +
host f64 gather) and ``qps`` is requests/s (``rows_per_s`` = qps x
bucket rows).  The delay knob shows up directly: d0 dispatches
immediately, d4 holds the queue open ~4ms hoping for co-batchable
traffic that a closed-loop client never sends — the visible p50 gap IS
the latency-vs-throughput tradeoff the knob buys.

Every cell also re-checks the core serving contract: the serve result
must be bit-identical to ``Booster.predict`` on the same rows
(quality_ok), so a latency improvement can never silently buy itself
out of correctness.

Usage:
  python tools/bench_serve.py             # full grid -> BENCH_SERVE.json
  python tools/bench_serve.py --gate      # + bench_gate over trajectory
  python tools/bench_serve.py --smoke     # tiny single cell, no artifacts
"""

import argparse
import json
import os
import subprocess
import sys
import time

RESULT_TAG = "SERVE_RESULT_JSON:"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUCKETS = [16, 64]
DELAYS_MS = [0.0, 4.0]

# model size -> (rows, feats, iters, leaves, child timeout s)
SIZES = {
    "small": (20_000, 20, 60, 31, 900),
    "large": (30_000, 30, 100, 63, 2400),
}
SMOKE_SIZE = ("smoke", (2_000, 10, 10, 15, 300))


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, max(0, int(round(
        q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def run_child(size: str, smoke: bool) -> None:
    sys.path.insert(0, REPO)
    from lightgbm_tpu.utils import enable_jax_compilation_cache, require_tpu
    device = require_tpu("bench_serve.py")
    enable_jax_compilation_cache()
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import ServeSession
    from lightgbm_tpu.utils.telemetry import TELEMETRY

    if smoke:
        rows, feats, iters, leaves, _ = SMOKE_SIZE[1]
        buckets, delays, n_requests = [16], [0.0], 8
    else:
        rows, feats, iters, leaves, _ = SIZES[size]
        buckets, delays, n_requests = BUCKETS, DELAYS_MS, 60

    rng = np.random.RandomState(11)
    X = rng.normal(size=(rows, feats)).astype(np.float32)
    # two categorical columns + a NaN-missing column keep the measured
    # path the same one the parity tests bit-check
    X[:, -1] = rng.randint(0, 12, size=rows)
    X[:, -2] = rng.randint(0, 6, size=rows)
    X[rng.rand(rows) < 0.05, 0] = np.nan
    y = ((np.nan_to_num(X[:, 0]) + X[:, 1] + (X[:, -1] % 3 == 0))
         > 0.5).astype(np.float64)
    ds = lgb.Dataset(X, y, categorical_feature=[feats - 2, feats - 1])
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": leaves}, ds, num_boost_round=iters)
    backend = jax.default_backend()

    for bucket in buckets:
        reqs = [np.ascontiguousarray(
            X[rng.randint(0, rows, size=bucket)]) for _ in range(16)]
        refs = [bst.predict(r) for r in reqs[:4]]
        for delay in delays:
            TELEMETRY.reset()
            with ServeSession(max_batch=bucket,
                              max_delay_ms=delay) as sess:
                mid = sess.load(bst, model_id=size)
                for r in reqs[:2]:               # compile + warm
                    sess.predict(mid, r)
                lat = []
                t0 = time.perf_counter()
                for i in range(n_requests):
                    r = reqs[i % len(reqs)]
                    t = time.perf_counter()
                    sess.predict(mid, r)
                    lat.append(time.perf_counter() - t)
                wall = time.perf_counter() - t0
                ok = all(np.array_equal(ref, sess.predict(mid, rq))
                         for ref, rq in zip(refs, reqs))
            lat.sort()
            qps = n_requests / max(wall, 1e-9)
            print(RESULT_TAG + json.dumps({
                "config": f"serve-{size}-b{bucket}-d{delay:g}",
                "model": size, "backend": backend, "device": device,
                "trees": iters, "leaves": leaves, "features": feats,
                "bucket": bucket, "delay_ms": delay,
                "requests": n_requests,
                "qps": round(qps, 2),
                "rows_per_s": round(qps * bucket, 1),
                "p50_s": round(_percentile(lat, 0.50), 6),
                "p99_s": round(_percentile(lat, 0.99), 6),
                "quality_ok": bool(ok),
                "metrics": TELEMETRY.metrics_blob(),
            }), flush=True)


def _run_size(size: str, timeout_s: float, smoke: bool = False) -> list:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", size]
    if smoke:
        cmd.append("--smoke")
    try:
        proc = subprocess.run(cmd, timeout=timeout_s, capture_output=True,
                              cwd=REPO)
    except subprocess.TimeoutExpired:
        sys.stderr.write(f"bench_serve: {size} timed out ({timeout_s}s)\n")
        return []
    sys.stderr.write(proc.stderr.decode(errors="replace")[-2000:])
    if proc.returncode != 0:
        sys.stderr.write(f"bench_serve: {size} rc={proc.returncode}\n")
        return []
    out = []
    for line in proc.stdout.decode(errors="replace").splitlines():
        if line.startswith(RESULT_TAG):
            out.append(json.loads(line[len(RESULT_TAG):]))
    return out


def _append_trajectory(records: list) -> None:
    """Serve digest lines for tools/bench_gate.py: no training
    ``value``/``unit`` — the gated fields are ``p99_s`` (latency gate)
    and ``quality_ok`` (bit-identity flip gate)."""
    path = os.path.join(REPO, "BENCH_TRAJECTORY.jsonl")
    with open(path, "a") as fh:
        for r in records:
            fh.write(json.dumps({
                "schema": "lightgbm_tpu.trajectory/v1",
                "ts": round(time.time(), 3),
                "config": r["config"],
                "backend": r.get("backend"),
                "device": r.get("device"),
                "qps": r.get("qps"),
                "rows_per_s": r.get("rows_per_s"),
                "p50_s": r.get("p50_s"),
                "p99_s": r.get("p99_s"),
                "quality_ok": r.get("quality_ok"),
            }) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="serve-path latency/QPS grid -> BENCH_SERVE.json")
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny cell, no artifacts")
    ap.add_argument("--gate", action="store_true",
                    help="run tools/bench_gate.py over the trajectory "
                         "after appending")
    args = ap.parse_args(argv)
    if args.smoke:
        recs = _run_size(SMOKE_SIZE[0], SMOKE_SIZE[1][4], smoke=True)
        for r in recs:
            print(json.dumps(r if "metrics" not in r
                             else {k: v for k, v in r.items()
                                   if k != "metrics"}), flush=True)
        if not recs or not all(r.get("quality_ok") for r in recs):
            sys.stderr.write("bench_serve: smoke FAILED\n")
            return 1
        print("bench_serve: smoke ok")
        return 0
    records = []
    for size in SIZES:
        records.extend(_run_size(size, SIZES[size][4]))
    for r in records:
        print(json.dumps({k: v for k, v in r.items() if k != "metrics"}),
              flush=True)
    if not records:
        sys.stderr.write("bench_serve: no records produced\n")
        return 1
    with open(os.path.join(REPO, "BENCH_SERVE.json"), "w") as fh:
        json.dump(records, fh, indent=1)
    _append_trajectory(records)
    if args.gate:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import bench_gate
        return bench_gate.gate(os.path.join(REPO,
                                            "BENCH_TRAJECTORY.jsonl"))
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        run_child(sys.argv[2], "--smoke" in sys.argv[3:])
        sys.exit(0)
    sys.exit(main())
