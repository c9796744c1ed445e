"""Benchmark: HIGGS-proxy binary training throughput on one TPU chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}, stamped
with the device it ran on — or, without a TPU, nothing: it exits non-zero.

The box has zero egress, so the real HIGGS file (10.5M x 28 dense floats)
is proxied by synthetic data with the same feature count and the reference
GPU-benchmark config (max_bin=63, num_leaves=255, lr=0.1,
docs/GPU-Performance.rst:110-127).  Steady-state per-iteration time is
measured after warmup and extrapolated to the reference's 500 iterations.

Baseline: the reference's published HIGGS CPU time is 238.505 s for 500
iters on 10.5M rows (docs/Experiments.rst:101-116).  vs_baseline = ours /
baseline (< 1.0 beats the reference CPU; the GPU learner's wall-clock is
only published as a chart, >3x CPU per docs/GPU-Tutorial.rst:162).

One process holds the chip at a time: this parent never imports jax, and
each measurement (the tier, then its impl and chunk A/Bs) is a child of its
own that takes the chip, measures, and exits before the next starts.
"""

import json
import os
import subprocess
import sys
import time

N_FEATURES = 28
MAX_BIN = 63
NUM_LEAVES = 255
TOTAL_ITERS_REF = 500
BASELINE_500_ITERS_S_10M5 = 238.505  # reference CPU, 10.5M rows

# rows, warmup, measured iters, child timeout seconds: the REAL HIGGS row
# count (binned 10.5M x 28 is ~300MB, HBM-trivial; benching 1M flattered
# vs_baseline by hiding the N-scaled terms)
ROWS, WARMUP, MEASURE, TIMEOUT_S = 10_500_000, 2, 4, 2700
RESULT_TAG = "BENCH_RESULT_JSON:"


def run_tier_child(n_rows: int, warmup: int, measure: int) -> None:
    """Executed inside the tier subprocess; prints a tagged JSON result."""
    import jax

    from lightgbm_tpu.utils import enable_jax_compilation_cache, require_tpu
    device = require_tpu("bench.py")
    enable_jax_compilation_cache()

    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.core.dataset import TpuDataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objective import create_objective

    rng = np.random.RandomState(42)
    t0 = time.time()
    X = rng.normal(size=(n_rows, N_FEATURES)).astype(np.float32)
    logit = (2.0 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
             + 0.5 * np.sin(3 * X[:, 4]))
    y = (logit + rng.normal(size=n_rows) * 0.5 > 0).astype(np.float64)
    t_gen = time.time() - t0

    cfg = Config(objective="binary", num_leaves=NUM_LEAVES, max_bin=MAX_BIN,
                 learning_rate=0.1, min_sum_hessian_in_leaf=100.0,
                 verbosity=-1,
                 tpu_tree_impl=os.environ.get("LIGHTGBM_TPU_IMPL", "auto"),
                 tpu_boost_chunk=int(os.environ.get(
                     "LIGHTGBM_TPU_BOOST_CHUNK", "0")))
    t0 = time.time()
    ds = TpuDataset.from_numpy(X, y, config=cfg)
    t_bin = time.time() - t0

    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    t0 = time.time()
    booster = GBDT(cfg, ds, obj)
    t_setup = time.time() - t0

    # chunked dispatch (tpu_boost_chunk, LIGHTGBM_TPU_BOOST_CHUNK): run
    # several iterations per device program with one batched fetch at the
    # chunk boundary; chunk=1 is the classic per-iteration pipeline
    chunk = booster.boost_chunk_size()

    def run_iters(n: int) -> None:
        done = 0
        while done < n:
            step = min(chunk, n - done)
            if step > 1:
                booster.train_chunk(step)
            else:
                booster.train_one_iter()
            done += step

    t0 = time.time()
    run_iters(warmup)
    jax.block_until_ready(booster.train_score)
    t_warm = time.time() - t0

    from lightgbm_tpu.utils.phase import GLOBAL_TIMER, profile_session
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    GLOBAL_TIMER.reset()   # phase summary covers only the measured window
    TELEMETRY.reset()      # counters/timeline cover only the measured window
    with profile_session(), TELEMETRY.memory_session():
        t0 = time.time()
        run_iters(measure)
        jax.block_until_ready(booster.train_score)
        per_iter = (time.time() - t0) / measure

    backend = jax.default_backend()
    # report the grower that ACTUALLY ran (a requested frontier/segment
    # impl can fall back to the fused grower off-TPU or on unsupported
    # shapes — an A/B log must not attribute fused numbers to it)
    if getattr(booster, "_use_segment", False):
        impl = ("frontier" if cfg.tpu_tree_impl == "frontier"
                else "segment")
    else:
        impl = f"fused-{booster.grower_params.hist_backend}"
        if cfg.tpu_tree_impl not in ("auto", "fused"):
            impl += f" (requested {cfg.tpu_tree_impl})"
    # quality readout so impl A/B runs (LIGHTGBM_TPU_IMPL) compare
    # accuracy, not just speed: tie-corrected (midrank) train AUC from
    # the live score buffer
    score = np.asarray(booster.train_score[0], dtype=np.float64)[:n_rows]
    order = np.argsort(score, kind="stable")
    ranks = np.empty(n_rows)
    ranks[order] = np.arange(1, n_rows + 1)
    # midranks for tied scores (few distinct leaf values early on)
    uniq, inv, cnt = np.unique(score, return_inverse=True,
                               return_counts=True)
    rank_sum = np.zeros(len(uniq))
    np.add.at(rank_sum, inv, ranks)
    ranks = (rank_sum / cnt)[inv]
    n_pos = float(y.sum())
    n_neg = n_rows - n_pos
    auc = ((ranks[y > 0.5].sum() - n_pos * (n_pos + 1) / 2)
           / max(n_pos * n_neg, 1.0))
    # honest full-run accounting (round-2 verdict): a real 500-iter run
    # pays binning + setup + compile once on top of the steady state
    total_real = (t_bin + t_setup + t_warm
                  + per_iter * (TOTAL_ITERS_REF - warmup))
    sys.stderr.write(
        f"bench phases [{backend}/{impl}, {n_rows} rows]: gen={t_gen:.1f}s "
        f"bin={t_bin:.1f}s setup={t_setup:.1f}s "
        f"warmup({warmup})={t_warm:.1f}s per_iter={per_iter:.4f}s "
        f"full_500_iter_incl_overheads={total_real:.1f}s "
        f"train_auc@{warmup + measure}it={auc:.4f}\n")
    sys.stderr.write("bench " + GLOBAL_TIMER.summary() + "\n")
    # what the grower ACTUALLY decided at build time (the env gate and
    # vmem-fit veto make the bare self-check result misleading)
    from lightgbm_tpu.ops.pallas_histogram import fused_route_decisions
    fused_used = fused_route_decisions.get(
        "frontier" if impl == "frontier" else "segment")
    print(RESULT_TAG + json.dumps(
        {"per_iter": per_iter, "rows": n_rows, "backend": backend,
         "device": device,
         "impl": impl, "auc": round(auc, 5), "chunk": chunk,
         # full-run accounting for the north-star math: a real 500-iter
         # run pays these once (t_warm is COLD here; a warm-cache rerun
         # of the same child shows the persistent-cache number)
         "bin_s": round(t_bin, 1), "warmup_s": round(t_warm, 1),
         "full_500_incl_overheads_s": round(total_real, 1),
         "fused_route": fused_used,
         # structured telemetry for the measured window (phases, fetch
         # bytes, compile seconds, network counters) — cross-round
         # tooling reads THIS, not the stderr phase line
         "metrics": TELEMETRY.metrics_blob()}))


def run_tier(platform: str, rows: int, warmup: int, measure: int,
             timeout_s: float, impl_env: str | None = None,
             chunk_env: str | None = None):
    """One measurement in a child of its own, which takes the chip and
    exits.  ``platform`` only labels the tier for the A/B helpers."""
    env = dict(os.environ)
    if impl_env is not None:
        env["LIGHTGBM_TPU_IMPL"] = impl_env
    if chunk_env is not None:
        env["LIGHTGBM_TPU_BOOST_CHUNK"] = chunk_env
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           str(rows), str(warmup), str(measure)]
    proc = subprocess.run(cmd, env=env, timeout=timeout_s,
                          capture_output=True,
                          cwd=os.path.dirname(os.path.abspath(__file__)))
    sys.stderr.write(proc.stderr.decode(errors="replace"))
    if proc.returncode != 0:
        raise RuntimeError(f"tier child rc={proc.returncode}: "
                           f"{proc.stderr.decode(errors='replace')[-400:]}")
    for line in proc.stdout.decode(errors='replace').splitlines():
        if line.startswith(RESULT_TAG):
            return json.loads(line[len(RESULT_TAG):])
    raise RuntimeError("tier child produced no result line")


def maybe_ab_frontier(r, platform, rows, warmup, measure, timeout_s):
    """After a successful TPU tier, also measure tpu_tree_impl=frontier
    (the batched-MXU grower) and keep the faster result if its training
    quality matches — both are real shipped configurations, and the
    scoreboard should reflect the framework's best honest number.
    Skipped when the caller pinned an impl via LIGHTGBM_TPU_IMPL."""
    # gate on the MEASURED backend too: a tpu tier whose child silently
    # fell back to CPU must not spawn a second meaningless CPU run
    if (platform != "tpu" or r.get("backend") != "tpu"
            or os.environ.get("LIGHTGBM_TPU_IMPL")):
        return r
    if r.get("impl") == "frontier":        # auto already resolved to it
        return r
    try:
        r2 = run_tier(platform, rows, warmup, measure, timeout_s,
                      impl_env="frontier")
    except Exception as e:  # noqa: BLE001 — A/B must not kill the bench
        sys.stderr.write(f"bench: frontier A/B failed: "
                         f"{type(e).__name__}: {str(e)[-300:]}\n")
        return r
    sys.stderr.write(
        f"bench A/B: {r['impl']} per_iter={r['per_iter']:.4f} "
        f"auc={r.get('auc')} vs frontier per_iter={r2['per_iter']:.4f} "
        f"auc={r2.get('auc')}\n")
    quality_ok = (r2.get("auc") is None or r.get("auc") is None
                  or r2["auc"] >= r["auc"] - 0.002)
    if quality_ok and r2["per_iter"] < r["per_iter"]:
        return r2
    return r


def maybe_ab_chunked(r, platform, rows, warmup, measure, timeout_s):
    """After a successful tier, also measure the chunked boosting loop
    (tpu_boost_chunk: several iterations per device program, tree fetches
    batched at the chunk boundary) and keep the faster result at equal
    training quality.  The chunked and unchunked paths grow bit-identical
    trees (same PRNG stream, same fused step), so the auc gate is a
    safety net, not a tradeoff.  Skipped when the caller pinned a chunk
    size via LIGHTGBM_TPU_BOOST_CHUNK or the tier already ran chunked."""
    if os.environ.get("LIGHTGBM_TPU_BOOST_CHUNK") or r.get("chunk", 1) > 1:
        return r
    # whole number of chunks inside the measured window keeps per_iter
    # comparable; the winning impl from the frontier A/B is pinned so
    # both sides of THIS comparison run the same grower
    chunk = max(2, min(8, measure))
    impl_pin = os.environ.get("LIGHTGBM_TPU_IMPL")
    if impl_pin is None and r.get("impl") in ("frontier", "segment"):
        impl_pin = r["impl"]
    try:
        r2 = run_tier(platform, rows, warmup, measure, timeout_s,
                      impl_env=impl_pin, chunk_env=str(chunk))
    except Exception as e:  # noqa: BLE001 — A/B must not kill the bench
        sys.stderr.write(f"bench: chunked A/B failed: "
                         f"{type(e).__name__}: {str(e)[-300:]}\n")
        return r
    sys.stderr.write(
        f"bench A/B: chunk=1 per_iter={r['per_iter']:.4f} "
        f"auc={r.get('auc')} vs chunk={r2.get('chunk')} "
        f"per_iter={r2['per_iter']:.4f} auc={r2.get('auc')}\n")
    quality_ok = (r2.get("auc") is None or r.get("auc") is None
                  or r2["auc"] >= r["auc"] - 0.002)
    if quality_ok and r2["per_iter"] < r["per_iter"]:
        return r2
    return r


def main():
    try:
        r = run_tier("tpu", ROWS, WARMUP, MEASURE, TIMEOUT_S)
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        sys.stderr.write(f"bench: no record: {type(e).__name__}: "
                         f"{str(e)[-400:]}\n")
        sys.exit(1)
    r = maybe_ab_frontier(r, "tpu", ROWS, WARMUP, MEASURE, TIMEOUT_S)
    r = maybe_ab_chunked(r, "tpu", ROWS, WARMUP, MEASURE, TIMEOUT_S)
    total_500 = r["per_iter"] * TOTAL_ITERS_REF
    baseline = BASELINE_500_ITERS_S_10M5 * (r["rows"] / 10_500_000)
    sys.stderr.write(
        f"bench: extrapolated 500-iter {total_500:.1f}s vs baseline "
        f"{baseline:.1f}s on {r['rows']} rows "
        f"({r['backend']}/{r['impl']})\n")
    if r.get("metrics"):
        # human-readable digest of the structured blob (top phases,
        # transfer bytes, compile seconds) for the round log
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        from trace_report import summarize
        sys.stderr.write(summarize(r["metrics"]) + "\n")
    print(json.dumps({
        "metric": f"higgs_proxy_{r['rows']}r_500iter_train_time_"
                  f"{r['backend']}",
        "value": round(total_500, 2),
        "unit": "s",
        "vs_baseline": round(total_500 / baseline, 3),
        "device": r["device"],
        "impl": r["impl"],
        "chunk": r.get("chunk", 1),
        "train_auc": r.get("auc"),
        "warmup_s": r.get("warmup_s"),
        "full_500_incl_overheads_s": r.get("full_500_incl_overheads_s"),
        "fused_route": r.get("fused_route"),
        "metrics": r.get("metrics"),
    }))


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        run_tier_child(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    else:
        main()
