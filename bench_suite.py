"""Benchmark suite: BASELINE.json configs 2-5 (the headline binary
config stays in bench.py, whose single-JSON-line driver contract this
file must not disturb).

Each config runs in a child of its own with a hard timeout — one process
holds the chip at a time, and this parent never imports jax — emits one
QUALITY-GATED JSON line stamped with the device it ran on, and the
collection is written to BENCH_SUITE.json.  Without a TPU nothing is
measured and nothing is written: a config whose child fails produces no
record, and the run exits non-zero.

  * goss_regression       — L2 + boosting=goss (examples/regression;
                            no published reference number, gate = heldout
                            L2 halves the label variance)
  * multiclass_cat        — softmax + categorical features
                            (examples/multiclass_classification)
  * lambdarank_msltr      — MS LTR-shaped proxy (2.27M docs, 136 feats,
                            ~31k queries); reference: 215.320 s / 500
                            iters, NDCG@10 0.527371
                            (docs/Experiments.rst:101-146).  Labels are
                            synthetic (zero-egress box), so the quality
                            gate is a calibrated NDCG@10 floor on THIS
                            generator, not the published number; the
                            published time is still the vs_baseline
                            denominator.
  * spill_ab              — the same regression config trained twice:
                            data_in_hbm=resident vs forced host-spill
                            (out-of-core row-block streaming,
                            docs/ROBUSTNESS.md rung 4).  One record with
                            the spill wall as the gated value plus the
                            resident wall and peak-HBM deltas; quality_ok
                            additionally requires the two models to be
                            byte-identical (sha256 of model_to_string).

Usage:  python bench_suite.py [config ...]    (default: all four)
        python bench_suite.py --gate [config ...]
                              (also run tools/bench_gate.py over the
                              appended trajectory; exit 1 on wall/HBM/
                              quality regressions vs trailing history)
"""

import json
import os
import subprocess
import sys
import time

RESULT_TAG = "SUITE_RESULT_JSON:"
REPO = os.path.dirname(os.path.abspath(__file__))

# config -> (rows, warmup, measure, timeout_s)
SIZES = {
    "goss_regression": (2_000_000, 2, 4, 2400),
    "multiclass_cat": (1_000_000, 2, 4, 2400),
    # 4200s: the cold lambdarank compile at 2.27M rows blew the usual
    # 2700s budget (retired installation, 2026-08-01)
    "lambdarank_msltr": (2_270_000, 2, 4, 4200),
    # two children (resident + forced spill), so the per-child timeout
    # stays the usual single-run budget
    "spill_ab": (1_000_000, 2, 4, 2400),
}

# published reference wall-clocks for vs_baseline (500 iters, CPU,
# docs/Experiments.rst:101-116); None = no published number
REF_500_ITERS_S = {
    "goss_regression": None,
    "multiclass_cat": None,
    "lambdarank_msltr": 215.320,
    "spill_ab": None,
}
REF_ROWS = {"lambdarank_msltr": 2_270_296}
TOTAL_ITERS_REF = 500


def _gen_goss(rng, n):
    import numpy as np
    X = rng.normal(size=(n, 28)).astype(np.float32)
    y = (2.0 * X[:, 0] - X[:, 1] ** 2 + np.sin(3 * X[:, 2])
         + 0.3 * X[:, 3] * X[:, 4] + 0.2 * rng.normal(size=n))
    return X, y.astype(np.float64), {}


def _gen_multiclass(rng, n):
    import numpy as np
    X = rng.normal(size=(n, 28)).astype(np.float32)
    # 8 categorical columns, cardinality 16
    cats = rng.randint(0, 16, size=(n, 8))
    X[:, 20:28] = cats
    logits = np.stack([
        X[:, 0] + (cats[:, 0] % 5 == k) * 1.5
        + 0.5 * X[:, k % 4] * (1 if k % 2 else -1)
        for k in range(5)], axis=1)
    # 2x logit scale keeps Bayes error low enough that the 25-iteration
    # quality gate separates a working learner from a broken one
    # (calibrated: 0.78 at 25 iters vs ln(5)=1.609 untrained)
    y = np.argmax(2.0 * logits + rng.gumbel(size=(n, 5)), axis=1)
    return X, y.astype(np.float64), {
        "categorical_feature": list(range(20, 28)),
        "params": {"objective": "multiclass", "num_class": 5},
    }


def _gen_rank(rng, n):
    import numpy as np
    F = 136
    # MS LTR shape: ~72 docs/query
    sizes = []
    left = n
    while left > 0:
        s = min(int(rng.randint(40, 120)), left)
        sizes.append(s)
        left -= s
    group = np.asarray(sizes)
    X = rng.normal(size=(n, F)).astype(np.float32)
    score = (X[:, 0] + 0.7 * X[:, 1] - 0.5 * X[:, 2]
             + 0.3 * X[:, 3] * X[:, 4] + rng.normal(size=n) * 0.7)
    # per-query graded relevance 0-4 by score quintile
    y = np.zeros(n)
    pos = 0
    for s in sizes:
        sl = slice(pos, pos + s)
        order = np.argsort(np.argsort(score[sl]))
        y[sl] = np.minimum(4, (5 * order) // max(s, 1))
        pos += s
    return X, y, {"group": group,
                  "params": {"objective": "lambdarank",
                             "label_gain": ",".join(
                                 str((1 << i) - 1) for i in range(32))}}


def _ndcg_at_10(pred, y, group):
    import numpy as np
    pos, total, nq = 0, 0.0, 0
    disc = 1.0 / np.log2(np.arange(2, 13))
    for s in group:
        sl = slice(pos, pos + s)
        ys, ps = y[sl], pred[sl]
        k = min(10, s)
        top = np.argsort(-ps, kind="stable")[:k]
        dcg = float((((2.0 ** ys[top]) - 1) * disc[:k]).sum())
        ideal = np.sort(ys)[::-1][:k]
        idcg = float((((2.0 ** ideal) - 1) * disc[:k]).sum())
        if idcg > 0:
            total += dcg / idcg
            nq += 1
        pos += s
    return total / max(nq, 1)


def _impl_label(bst, requested: str) -> str:
    """bench.py:142-151's labeling contract: report the grower that
    ACTUALLY ran, and mark a pinned impl that fell back to fused so the
    scoreboard never attributes fused numbers to it."""
    req = str(requested).strip().lower()
    if getattr(bst.gbdt, "_use_segment", False):
        return "frontier" if req == "frontier" else "segment"
    label = "fused"
    if req not in ("auto", "fused"):
        label += f" (requested {req})"
    return label


def run_child(config: str, n_rows: int, warmup: int, measure: int) -> None:
    import jax
    sys.path.insert(0, REPO)
    from lightgbm_tpu.utils import enable_jax_compilation_cache, require_tpu
    device = require_tpu("bench_suite.py")
    enable_jax_compilation_cache()
    import numpy as np

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    gen = {"goss_regression": _gen_goss, "multiclass_cat": _gen_multiclass,
           "lambdarank_msltr": _gen_rank,
           "spill_ab": _gen_goss}[config]
    X, y, extra = gen(rng, n_rows)
    params = {"learning_rate": 0.1, "num_leaves": 255, "max_bin": 63,
              "min_sum_hessian_in_leaf": 100.0, "verbose": -1,
              "objective": "regression",
              # same A/B hooks as bench.py: LIGHTGBM_TPU_IMPL pins the
              # grower, LIGHTGBM_TPU_BOOST_CHUNK pins the chunk size
              # (0 = auto; the GOSS config self-clamps to 1)
              "tpu_tree_impl": os.environ.get("LIGHTGBM_TPU_IMPL",
                                              "auto"),
              "tpu_boost_chunk": int(os.environ.get(
                  "LIGHTGBM_TPU_BOOST_CHUNK", "0"))}
    params.update(extra.get("params", {}))
    # pins the frontier batch width, so that K∈{4,8,16} A/B cells
    # measure the width they name
    fk = int(os.environ.get("LIGHTGBM_TPU_FRONTIER_K", "0") or 0)
    if fk > 0:
        params["tpu_frontier_width"] = fk
    # spill A/B hook: the parent pins the memory tier per child
    # (runtime-only knob — it never reaches the serialized model)
    dib = os.environ.get("SUITE_DATA_IN_HBM")
    if dib:
        params["data_in_hbm"] = dib
    if config == "goss_regression":
        params["boosting"] = "goss"
    if config == "multiclass_cat":
        params["num_leaves"] = 31

    ds = lgb.Dataset(X, y, group=extra.get("group"),
                     categorical_feature=extra.get("categorical_feature",
                                                   "auto"))
    t0 = time.time()
    bst = lgb.Booster(params, ds)
    t_setup = time.time() - t0
    chunk = bst.gbdt.boost_chunk_size()

    def run_iters(n: int) -> None:
        done = 0
        while done < n:
            step = min(chunk, n - done)
            if step > 1:
                bst.update_chunk(step)
            else:
                bst.update()
            done += step

    t0 = time.time()
    run_iters(warmup)
    jax.block_until_ready(bst.gbdt.train_score)
    t_warm = time.time() - t0
    from lightgbm_tpu.utils.phase import GLOBAL_TIMER
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()      # counters/timeline cover only the measured window
    t0 = time.time()
    # memory_session brackets the window with HBM gauge samples (no-op on
    # backends without memory_stats) and owns the optional sampler thread
    with TELEMETRY.memory_session():
        run_iters(measure)
        jax.block_until_ready(bst.gbdt.train_score)
    per_iter = (time.time() - t0) / measure
    # snapshot BEFORE the quality-gate extra iterations below so the
    # blob matches the timed window
    metrics_blob = TELEMETRY.metrics_blob()

    # quality gates are calibrated at a FIXED 25-iteration budget so the
    # same floor applies to every tier (timing above covers only the
    # measured window; a 2+4-iteration model is too early to gate on)
    run_iters(max(0, 25 - warmup - measure))
    pred = bst.predict(X[:200_000])
    quality: dict = {}
    ok = True
    if config in ("goss_regression", "spill_ab"):
        l2 = float(np.mean((pred - y[:len(pred)]) ** 2))
        quality["l2"] = round(l2, 5)
        ok = l2 < 0.5 * float(np.var(y))
    elif config == "multiclass_cat":

        p = np.asarray(pred).reshape(-1, 5)
        yy = y[:len(p)].astype(int)
        ll = float(-np.mean(np.log(np.clip(
            p[np.arange(len(p)), yy], 1e-15, 1.0))))
        quality["multi_logloss"] = round(ll, 5)
        ok = ll < 0.9  # untrained = ln(5) ~ 1.609; calibrated floor
    elif config == "lambdarank_msltr":
        g = extra["group"]
        m = 0
        take = 0
        while take < len(g) and m + g[take] <= len(pred):
            m += g[take]
            take += 1
        nd = _ndcg_at_10(np.asarray(pred[:m]), y[:m], g[:take])
        quality["ndcg@10"] = round(nd, 5)
        # calibrated floor for this generator (full separability is
        # impossible: relevance has injected noise; smoke run measured
        # 0.846 at a THIRD of the gate budget)
        ok = nd > 0.80
    backend = jax.default_backend()
    # cheap cross-process identity witness: the spill A/B parent compares
    # the resident and forced-spill children by this digest
    import hashlib
    model_sha = hashlib.sha256(
        bst.model_to_string().encode()).hexdigest()
    print(RESULT_TAG + json.dumps({
        "config": config, "rows": n_rows, "backend": backend,
        "device": device,
        "per_iter": round(per_iter, 5), "setup_s": round(t_setup, 2),
        "warmup_s": round(t_warm, 2), "quality": quality,
        "quality_ok": bool(ok),
        "impl": _impl_label(bst, params["tpu_tree_impl"]),
        "chunk": chunk,
        "model_sha": model_sha,
        "metrics": metrics_blob,
    }))


def _run_child_record(config: str, rows: int, warmup: int,
                      measure: int, timeout_s: float,
                      env: dict) -> dict | None:
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           config, str(rows), str(warmup), str(measure)]
    try:
        proc = subprocess.run(cmd, env=env, timeout=timeout_s,
                              capture_output=True, cwd=REPO)
    except subprocess.TimeoutExpired:
        sys.stderr.write(f"suite: {config}/{rows} timed "
                         f"out ({timeout_s}s)\n")
        return None
    sys.stderr.write(proc.stderr.decode(errors="replace")[-2000:])
    if proc.returncode != 0:
        sys.stderr.write(
            f"suite: {config}/{rows} rc={proc.returncode}\n")
        return None
    for line in proc.stdout.decode(errors="replace").splitlines():
        if line.startswith(RESULT_TAG):
            return json.loads(line[len(RESULT_TAG):])
    return None


def _peak_hbm(rec: dict) -> int | None:
    return ((rec.get("metrics") or {}).get("memory")
            or {}).get("peak_bytes_in_use")


def _run_spill_ab() -> dict | None:
    """Resident-vs-forced-spill A/B on the same config and data: one
    trajectory record whose gated value is the SPILL wall (so an
    out-of-core streaming regression trips tools/bench_gate.py), with
    the resident wall and the peak-HBM delta riding along.  quality_ok
    also demands byte-identical models — the out-of-core tier's core
    contract."""
    config = "spill_ab"
    rows, warmup, measure, timeout_s = SIZES[config]
    pair = {}
    for tier in ("resident", "spill"):
        env = dict(os.environ, SUITE_DATA_IN_HBM=tier)
        pair[tier] = _run_child_record(config, rows, warmup, measure,
                                       timeout_s, env)
    res, spl = pair["resident"], pair["spill"]
    if res is None or spl is None:
        return None
    total_res = res["per_iter"] * TOTAL_ITERS_REF
    total_spl = spl["per_iter"] * TOTAL_ITERS_REF
    bit_identical = (res.get("model_sha") is not None
                     and res.get("model_sha") == spl.get("model_sha"))
    out = {
        "config": config,
        "metric": f"{config}_{spl['rows']}r_500iter_train_time_"
                  f"{spl['backend']}_spill",
        "value": round(total_spl, 2),
        "unit": "s",
        "device": spl["device"],
        "impl": spl["impl"],
        "chunk": spl.get("chunk", 1),
        "quality": dict(
            spl["quality"],
            spill_wall_ratio=round(total_spl / max(total_res, 1e-9), 3),
            bit_identical=bit_identical),
        "quality_ok": bool(spl["quality_ok"] and res["quality_ok"]
                           and bit_identical),
        "resident_value": round(total_res, 2),
        "metrics": spl.get("metrics"),
    }
    pr, ps = _peak_hbm(res), _peak_hbm(spl)
    if pr is not None and ps is not None:
        out["resident_peak_hbm_bytes"] = int(pr)
        out["spill_peak_hbm_bytes"] = int(ps)
        out["peak_hbm_delta_bytes"] = int(ps) - int(pr)
    return out


def run_config(config: str) -> dict | None:
    if config == "spill_ab":
        return _run_spill_ab()
    rows, warmup, measure, timeout_s = SIZES[config]
    env = dict(os.environ)
    r = _run_child_record(config, rows, warmup, measure, timeout_s, env)
    if r is None:
        return None
    # bench.py's promotion contract for the suite: a run whose auto impl
    # resolved to segment also measures the frontier grower and keeps it
    # when it is faster at held quality, so a default (env-free) run
    # reproduces the scoreboard numbers
    if r["impl"] == "segment" and "LIGHTGBM_TPU_IMPL" not in os.environ:
        r2 = _run_child_record(config, rows, warmup, measure, timeout_s,
                               dict(env, LIGHTGBM_TPU_IMPL="frontier"))
        if (r2 is not None and r2["impl"] == "frontier"
                and r2["quality_ok"]
                and r2["per_iter"] < r["per_iter"]):
            sys.stderr.write(
                f"suite A/B [{config}]: frontier "
                f"{r2['per_iter']:.4f} beats segment "
                f"{r['per_iter']:.4f} s/iter at held quality\n")
            r = r2
    total = r["per_iter"] * TOTAL_ITERS_REF
    ref = REF_500_ITERS_S.get(config)
    out = {
        "config": config,
        "metric": f"{config}_{r['rows']}r_500iter_train_time_"
                  f"{r['backend']}",
        "value": round(total, 2),
        "unit": "s",
        "device": r["device"],
        "impl": r["impl"],
        "chunk": r.get("chunk", 1),
        "quality": r["quality"],
        "quality_ok": r["quality_ok"],
        # the measured window's v2 telemetry blob (phases, transfer
        # bytes, memory/cost envelope) rides along with every record
        "metrics": r.get("metrics"),
    }
    if ref is not None:
        scaled = ref * r["rows"] / REF_ROWS.get(config, r["rows"])
        out["vs_baseline"] = round(total / scaled, 3)
    return out


def _append_trajectory(results: list) -> None:
    """One digest line per run appended to BENCH_TRAJECTORY.jsonl — the
    machine-readable perf trajectory across PRs (wall, peak HBM, est.
    FLOPs, and — on device_timing runs — the measured dispatch digest
    of the heaviest seam, which tools/bench_gate.py latency-gates).
    Null-tolerant: v1 blobs / CPU backends / timing-off runs leave the
    memory, cost and timing fields as null rather than breaking the
    append."""
    path = os.path.join(REPO, "BENCH_TRAJECTORY.jsonl")
    with open(path, "a") as fh:
        for r in results:
            m = r.get("metrics") or {}
            mem = m.get("memory") or {}
            cost = m.get("cost") or {}
            timing = m.get("timing") or {}
            # the heaviest measured seam (by synced wall) is the one a
            # latency regression would show up in first
            tlabels = timing.get("labels") or {}
            tname = max(tlabels, key=lambda k: tlabels[k].get(
                "total_s", 0.0)) if tlabels else None
            tentry = tlabels.get(tname) or {}
            # full per-label digest (count/mean/p99 per jit seam) so a
            # regression in a NON-heaviest seam is still visible in the
            # trajectory, plus the histogram-pass rollup bench_gate.py
            # latency-gates (heaviest label naming the hist kernels)
            dlabels = {k: {"count": v.get("count"),
                           "mean_s": v.get("mean_s"),
                           "p99_s": v.get("p99_s")}
                       for k, v in sorted(tlabels.items())} or None
            hname = max((k for k in tlabels if "hist" in k),
                        key=lambda k: tlabels[k].get("total_s", 0.0),
                        default=None)
            hentry = tlabels.get(hname) or {}
            # spill A/B records carry their resident-vs-spill deltas into
            # the trajectory; absent on every other config
            extra = {k: r[k] for k in ("resident_value",
                                       "resident_peak_hbm_bytes",
                                       "spill_peak_hbm_bytes",
                                       "peak_hbm_delta_bytes") if k in r}
            fh.write(json.dumps({
                "schema": "lightgbm_tpu.trajectory/v1",
                "ts": round(time.time(), 3),
                "config": r.get("config"),
                "metric": r.get("metric"),
                "value": r.get("value"),
                "unit": r.get("unit"),
                "impl": r.get("impl"),
                "chunk": r.get("chunk"),
                "quality_ok": r.get("quality_ok"),
                "peak_hbm_bytes": mem.get("peak_bytes_in_use"),
                "hbm_limit_bytes": mem.get("bytes_limit"),
                "est_flops": cost.get("flops_total"),
                "est_flops_per_s": cost.get("est_flops_per_s"),
                "dispatch_label": tname,
                "dispatch_mean_s": tentry.get("mean_s"),
                "dispatch_p99_s": tentry.get("p99_s"),
                "dispatch_labels": dlabels,
                "hist_pass_label": hname,
                "hist_pass_mean_s": hentry.get("mean_s"),
                "hist_pass_p99_s": hentry.get("p99_s"),
                "measured_flops_per_s": timing.get(
                    "measured_flops_per_s"),
                **extra,
            }) + "\n")


def main():
    configs = [a for a in sys.argv[1:] if not a.startswith("-")] \
        or list(SIZES)
    results, failed = [], []
    # A/B ladder runs suffix their records so each env cell forms its OWN
    # config series in the trajectory — bench_gate's per-config latency
    # baselines never mix a forced variant with the defaults
    tag = os.environ.get("SUITE_CONFIG_TAG", "")
    for config in configs:
        r = run_config(config)
        if r is None:
            failed.append(config)
            continue
        if tag:
            r["config"] = f"{r['config']}+{tag}"
            r["metric"] = f"{r.get('metric', config)}+{tag}"
        results.append(r)
        print(json.dumps(r), flush=True)
    if failed:
        sys.stderr.write(f"suite: no record for {failed}\n")
    if not results:
        sys.exit(1)
    _append_trajectory(results)
    # subset runs merge into the existing artifact instead of clobbering
    # the other configs' records
    path = os.path.join(REPO, "BENCH_SUITE.json")
    if set(configs) != set(SIZES):
        def config_of(rec):
            if "config" in rec:
                return rec["config"]
            # pre-"config"-field artifacts: longest-prefix fallback
            names = [n for n in SIZES
                     if rec.get("metric", "").startswith(n)]
            return max(names, key=len) if names else rec.get("metric", "")

        try:
            with open(path) as fh:
                old = {config_of(r): r for r in json.load(fh)}
        except (OSError, ValueError):
            old = {}
        for r in results:
            old[config_of(r)] = r
        results = list(old.values())
    with open(path, "w") as fh:
        json.dump(results, fh, indent=1)
    rc = 1 if failed else 0
    if "--gate" in sys.argv[1:]:
        # perf-regression sentinel: judge the lines just appended
        # against the trailing trajectory (tools/bench_gate.py) after
        # the artifacts are safely on disk
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import bench_gate
        rc = max(rc, bench_gate.gate(
            os.path.join(REPO, "BENCH_TRAJECTORY.jsonl")))
    sys.exit(rc)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        run_child(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
                  int(sys.argv[5]))
    else:
        main()
