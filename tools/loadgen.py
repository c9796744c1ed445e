"""Open-loop Poisson load generator for the serve stack.

The closed-loop client in bench_serve.py waits for each reply before
sending the next request, so the micro-batching coalescing window never
sees concurrent traffic (ROADMAP item 1).  This tool drives a
ServeSession the way real traffic does: arrivals are a Poisson process
at a target rate, submitted through the futures API WITHOUT waiting for
replies — the arrival clock never stalls on a slow dispatch, so queue
growth and coalescing behave as they would behind a real frontend.

Each grid cell (arrival rate x serve_max_delay_ms) runs a fixed
duration, records end-to-end latency per completed request via future
callbacks, and emits one record with achieved QPS, p50/p99, the mean
rows-per-batch the coalescing window actually built, and the serve
health stream's view of the same window.  Results merge into
BENCH_SERVE.json next to the closed-loop grid (config names
``loadgen-<size>-r<rate>-d<delay>``) and append trajectory digests that
tools/bench_gate.py gates on p99 like any other serve record.

``--shift`` exercises the drift plane instead of the queue: one session
with ``drift_detect`` armed replays a fixed sweep of training rows
untouched, then replays the same rows with one numerical column
displaced — a population shift the plane must flag (and a control sweep
with no displacement it must NOT flag).  Replies stay bit-checked
against Booster.predict throughout: the drift tap must never perturb
the scores it observes.  ``--smoke`` runs both and asserts the shifted
sweep's ``serve_drift`` record names the shifted column first.

``--swap`` drives open-loop traffic while a background thread refits
and hot-swaps the SAME model N times mid-flight: zero replies may
fail, every reply must be bit-identical to a generation that was live,
and the measured flip pauses (``swap_pause_p99_s``) land in the record
for tools/bench_gate.py to gate alongside ``shed_rate``.

Usage:
  python tools/loadgen.py                 # full sweep -> BENCH_SERVE.json
  python tools/loadgen.py --smoke         # ~2s burst, assertions, no artifacts
  python tools/loadgen.py --rate 200 --delay-ms 5 --duration 3
  python tools/loadgen.py --shift         # drift cells -> trajectory
  python tools/loadgen.py --swap          # hot-swap-under-load cell
"""

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# full-sweep grid: arrival rates (req/s) x coalescing windows (ms).
# Single-row requests: the realistic serving shape the closed-loop
# bench never exercises, and the one where coalescing matters most.
RATES = [50.0, 300.0]
DELAYS_MS = [0.0, 5.0]
DURATION_S = 2.5
# small model: the sweep measures the queue, not the tree walk
MODEL = ("small", dict(rows=5_000, feats=12, iters=30, leaves=31))


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1, max(0, int(round(
        q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _train(np, lgb, spec):
    rng = np.random.RandomState(7)
    X = rng.normal(size=(spec["rows"], spec["feats"])).astype(np.float32)
    X[:, -1] = rng.randint(0, 8, size=spec["rows"])
    X[rng.rand(spec["rows"]) < 0.05, 0] = np.nan
    y = ((np.nan_to_num(X[:, 0]) + X[:, 1]) > 0.5).astype(np.float64)
    ds = lgb.Dataset(X, y, categorical_feature=[spec["feats"] - 1])
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": spec["leaves"]}, ds,
                    num_boost_round=spec["iters"])
    return bst, X


def drive_open_loop(sess, model_id, reqs, rate, duration_s, seed=0,
                    drain_timeout_s=15.0, expected=None):
    """Submit Poisson arrivals at ``rate`` req/s for ``duration_s``
    seconds, never blocking on replies.  Returns (sent, latencies,
    errors, mismatches, wall_s): per-completed-request end-to-end
    seconds measured submit -> future callback.  When ``expected`` is
    given (Booster.predict references aligned with ``reqs``), every
    reply is bit-checked against it — parity under REAL coalescing,
    where the queue slices replies out of concatenated dispatches."""
    import numpy as np

    lat, errors, mismatches = [], [0], [0]
    lock = threading.Lock()
    pending = []

    def _done(fut, t_submit, idx):
        try:
            res = fut.result()
        except Exception:
            with lock:
                errors[0] += 1
            return
        dt = time.perf_counter() - t_submit
        bad = (expected is not None
               and not np.array_equal(res, expected[idx]))
        with lock:
            lat.append(dt)
            if bad:
                mismatches[0] += 1

    rng = random.Random(seed)
    t_start = time.perf_counter()
    t_end = t_start + duration_s
    next_t = t_start
    sent = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.002))
            continue
        idx = sent % len(reqs)
        t_submit = time.perf_counter()
        fut = sess.submit(model_id, reqs[idx])
        fut.add_done_callback(
            lambda f, t=t_submit, i=idx: _done(f, t, i))
        pending.append(fut)
        sent += 1
        next_t += rng.expovariate(rate)
    wall = time.perf_counter() - t_start
    deadline = time.monotonic() + drain_timeout_s
    for fut in pending:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        try:
            fut.result(timeout=remaining)
        except Exception:
            pass                  # already counted by the callback
    with lock:
        return sent, sorted(lat), errors[0], mismatches[0], wall


def run_cell(bst, X, size, rate, delay_ms, duration_s, max_batch=64,
             health_path="", window_s=1.0, seed=0):
    """One (rate, delay) cell on a fresh session; returns the result
    record (and leaves the health stream, when requested, on disk)."""
    import jax
    import numpy as np

    from lightgbm_tpu.serve import ServeSession
    from lightgbm_tpu.utils.telemetry import TELEMETRY

    reqs = [np.ascontiguousarray(X[i % X.shape[0]].reshape(1, -1))
            for i in range(64)]
    refs = [bst.predict(r) for r in reqs]
    TELEMETRY.reset()
    with ServeSession(max_batch=max_batch, max_delay_ms=delay_ms,
                      health_out=health_path,
                      health_window_s=window_s) as sess:
        mid = sess.load(bst, model_id=size)
        # pre-compile every pow2 bucket a coalesced drain can produce,
        # so the measured window sees steady-state dispatch costs;
        # direct dispatches bypass the queue, so they never contaminate
        # the health stream's request accounting
        b = 1
        while b <= max_batch:
            sess.predict_direct(mid, np.concatenate(
                [reqs[0]] * b) if b > 1 else reqs[0])
            b <<= 1
        # warmup dispatches out of the coalescing/counter measurement
        TELEMETRY.reset()
        TELEMETRY.gauge_set("serve/max_batch", max_batch)
        sent, lat, errors, mismatches, wall = drive_open_loop(
            sess, mid, reqs, rate, duration_s, seed=seed, expected=refs)
        stats = TELEMETRY.stats()
    counters = stats.get("counters", {})
    batches = counters.get("serve/batches", 0)
    rows = counters.get("serve/rows", 0)
    rec = {
        "config": f"loadgen-{size}-r{rate:g}-d{delay_ms:g}",
        "mode": "open-loop",
        "model": size, "backend": jax.default_backend(),
        "rate_target": rate, "delay_ms": delay_ms,
        "max_batch": max_batch,
        "duration_s": round(wall, 3),
        "requests": sent, "completed": len(lat), "errors": errors,
        "qps": round(len(lat) / max(wall, 1e-9), 2),
        "rows_per_batch": round(rows / batches, 3) if batches else None,
        "p50_s": (round(_percentile(lat, 0.50), 6) if lat else None),
        "p99_s": (round(_percentile(lat, 0.99), 6) if lat else None),
        "quality_ok": mismatches == 0,
    }
    serve_win = stats.get("serve")
    if serve_win:
        rec["window"] = serve_win
    return rec


def run_swap_cell(bst, X, name, n_swaps=3, rate=250.0, delay_ms=2.0,
                  duration_s=2.0, max_batch=64, health_path="", seed=0):
    """One hot-swap-under-load cell: open-loop Poisson traffic against
    model ``name`` while a background thread refits the booster and
    pushes ``n_swaps`` atomic hot swaps through the live session.

    Contracts asserted downstream (``--smoke``): zero failed replies
    across every flip, every reply bit-identical to a generation that
    was live during the run, and a bounded flip pause
    (``swap_pause_p99_s``, read from ``registry.swap_pauses``)."""
    import jax
    import numpy as np

    from lightgbm_tpu.serve import ServeSession
    from lightgbm_tpu.utils.telemetry import TELEMETRY

    reqs = [np.ascontiguousarray(X[i % X.shape[0]].reshape(1, -1))
            for i in range(64)]
    allreq = np.concatenate(reqs)
    rng = np.random.RandomState(seed)
    # generation 0's per-request references; the swapper appends each
    # new generation's BEFORE flipping it live, so the membership check
    # below never races the flip
    gens = [bst.predict(allreq)]
    gens_lock = threading.Lock()
    replies = []
    errors = [0]
    rep_lock = threading.Lock()
    TELEMETRY.reset()
    with ServeSession(max_batch=max_batch, max_delay_ms=delay_ms,
                      health_out=health_path,
                      health_window_s=0.5) as sess:
        mid = sess.load(bst, model_id=name)
        sess.predict_direct(mid, allreq[:1])         # compile
        # warm the flip path too (first .at[row].set compiles); an
        # identity swap, so generation-0 references stay valid
        sess.swap(mid, bst, gated=False)
        warm_pauses = len(sess.registry.swap_pauses)
        swaps_done = [0]
        stop = threading.Event()

        def swapper():
            # pace swaps across the traffic window but always complete
            # all n_swaps — the tail ones land during the drain, still
            # under load.  stop's only job is the pacing wait.
            gap = duration_s / (n_swaps + 1)
            for _ in range(n_swaps):
                stop.wait(gap)
                Xr = X[rng.choice(X.shape[0], 400, replace=False)]
                yr = ((np.nan_to_num(Xr[:, 0]) + Xr[:, 1]) > 0.5
                      ).astype(np.float64)
                bst.refit(Xr, yr, decay_rate=0.4)
                with gens_lock:
                    gens.append(bst.predict(allreq))
                sess.swap(mid, bst, gated=False)
                swaps_done[0] += 1

        def _done(fut, t_submit, idx):
            try:
                res = fut.result()
            except Exception:
                with rep_lock:
                    errors[0] += 1
                return
            dt = time.perf_counter() - t_submit
            with rep_lock:
                replies.append((idx, np.asarray(res).ravel(), dt))

        sw = threading.Thread(target=swapper, name="loadgen-swapper")
        sw.start()
        arr = random.Random(seed)
        t_start = time.perf_counter()
        t_end = t_start + duration_s
        next_t, sent, pending = t_start, 0, []
        try:
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    break
                if now < next_t:
                    time.sleep(min(next_t - now, 0.002))
                    continue
                idx = sent % len(reqs)
                t_submit = time.perf_counter()
                fut = sess.submit(mid, reqs[idx])
                fut.add_done_callback(
                    lambda f, t=t_submit, i=idx: _done(f, t, i))
                pending.append(fut)
                sent += 1
                next_t += arr.expovariate(rate)
        finally:
            stop.set()
            sw.join(timeout=30)
        wall = time.perf_counter() - t_start
        deadline = time.monotonic() + 15.0
        for fut in pending:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                fut.result(timeout=remaining)
            except Exception:
                pass              # already counted by the callback
        pauses = sorted(sess.registry.swap_pauses[warm_pauses:])
    counters = TELEMETRY.stats().get("counters", {})
    mismatches = 0
    with rep_lock, gens_lock:
        lat = sorted(dt for _, _, dt in replies)
        for idx, res, _ in replies:
            if not any(np.array_equal(res, g[idx:idx + 1])
                       for g in gens):
                mismatches += 1
    shed = counters.get("serve/shed_requests", 0)
    return {
        "config": f"loadgen-swap-{name}",
        "mode": "hot-swap", "backend": jax.default_backend(),
        "rate_target": rate, "delay_ms": delay_ms,
        "duration_s": round(wall, 3),
        "requests": sent, "completed": len(lat), "errors": errors[0],
        "qps": round(len(lat) / max(wall, 1e-9), 2),
        "swaps": swaps_done[0],
        "swap_pause_p99_s": (round(_percentile(pauses, 0.99), 6)
                             if pauses else None),
        "swap_pause_max_s": (round(pauses[-1], 6) if pauses else None),
        "shed_rate": round(shed / max(sent, 1), 6),
        "p50_s": (round(_percentile(lat, 0.50), 6) if lat else None),
        "p99_s": (round(_percentile(lat, 0.99), 6) if lat else None),
        "quality_ok": mismatches == 0,
    }


SHIFT_COL = 2          # numerical column displaced by the shift sweep
SHIFT_OFFSET = 6.0     # far outside the N(0,1) training range


def run_shift_cell(bst, X, name, shift_col=SHIFT_COL, offset=SHIFT_OFFSET,
                   health_path="", threshold=0.2, n_rows=256, seed=0):
    """One drift cell: a fixed sweep of distinct training rows through
    the real queue path with ``drift_detect`` armed, replayed untouched
    and then with ``shift_col`` displaced by ``offset`` (``offset=0``
    is the control: same traffic, no shift, no drift expected).  Every
    reply is bit-checked against Booster.predict — the drift tap rides
    the serve path but must never perturb it.  Returns the result
    record; the DriftGate verdict is read live before close, and the
    health stream (when requested) carries the ``serve_drift``
    records."""
    import jax
    import numpy as np

    from lightgbm_tpu.serve import ServeSession
    from lightgbm_tpu.utils.telemetry import TELEMETRY

    rng = np.random.RandomState(seed)
    idx = rng.choice(X.shape[0], size=min(n_rows, X.shape[0]),
                     replace=False)
    base = np.ascontiguousarray(X[idx])
    shifted = base.copy()
    shifted[:, shift_col] = np.nan_to_num(
        shifted[:, shift_col]) + offset
    reqs = [np.ascontiguousarray(r.reshape(1, -1))
            for phase in (base, shifted) for r in phase]
    allref = bst.predict(np.concatenate(reqs))
    errors = mismatches = completed = 0
    TELEMETRY.reset()
    with ServeSession(max_batch=32, max_delay_ms=2.0,
                      health_out=health_path, health_window_s=0.5,
                      drift_detect=True,
                      drift_psi_threshold=threshold) as sess:
        mid = sess.load(bst, model_id=name)
        futs = [sess.submit(mid, r) for r in reqs]
        for i, fut in enumerate(futs):
            try:
                res = fut.result(timeout=60)
            except Exception:
                errors += 1
                continue
            completed += 1
            if not np.array_equal(np.asarray(res).ravel(),
                                  allref[i:i + 1]):
                mismatches += 1
        live = sess.drift_gate.stats(mid) or {}
        drifted = sess.drift_gate.drifted(mid)
    top = (live.get("top") or [{}])[0]
    return {
        "config": f"loadgen-shift-{name}",
        "mode": "drift-shift", "backend": jax.default_backend(),
        "shift_col": shift_col, "offset": offset,
        "threshold": threshold,
        "requests": len(reqs), "completed": completed,
        "errors": errors,
        "quality_ok": mismatches == 0,
        "psi_max": live.get("psi_max"),
        "score_js": live.get("score_js"),
        "drift_rows": live.get("rows"),
        "drifted": drifted,
        "top_feature": top.get("feature"),
    }


def merge_bench_serve(records, path=None):
    """Fold new cells into BENCH_SERVE.json next to the closed-loop
    grid: same-config records are replaced, everything else kept."""
    path = path or os.path.join(REPO, "BENCH_SERVE.json")
    existing = []
    if os.path.exists(path):
        try:
            with open(path) as fh:
                existing = json.load(fh)
        except ValueError:
            existing = []
    new_names = {r["config"] for r in records}
    merged = [r for r in existing
              if r.get("config") not in new_names] + records
    with open(path, "w") as fh:
        json.dump(merged, fh, indent=1)
    return path


def append_trajectory(records, path=None):
    path = path or os.path.join(REPO, "BENCH_TRAJECTORY.jsonl")
    with open(path, "a") as fh:
        for r in records:
            fh.write(json.dumps({
                "schema": "lightgbm_tpu.trajectory/v1",
                "ts": round(time.time(), 3),
                "config": r["config"],
                "backend": r.get("backend"),
                "qps": r.get("qps"),
                "rows_per_batch": r.get("rows_per_batch"),
                "p50_s": r.get("p50_s"),
                "p99_s": r.get("p99_s"),
                "quality_ok": r.get("quality_ok"),
                # drift/swap cells only; absent keys keep older gate
                # versions and mixed trajectories shape-stable
                **{k: r[k] for k in ("psi_max", "drift_ok",
                                     "swap_pause_p99_s", "shed_rate")
                   if r.get(k) is not None},
            }) + "\n")


def _check_health_stream(path, completed):
    """The smoke's health-stream contract: every line parses (the
    O_APPEND writer never tears), the lifecycle kinds are present, the
    windows account for every completed request, and every latency
    quantile pair is finite and ordered."""
    problems = []
    recs = []
    with open(path, "rb") as fh:
        for ln, raw in enumerate(fh.read().split(b"\n")):
            if not raw.strip():
                continue
            try:
                recs.append(json.loads(raw))
            except ValueError:
                problems.append(f"torn/unparseable line {ln + 1}")
    kinds = [r.get("kind") for r in recs]
    for want in ("serve_start", "serve_window", "serve_summary"):
        if want not in kinds:
            problems.append(f"missing {want} record")
    wins = [r for r in recs if r.get("kind") == "serve_window"]
    win_requests = sum(r.get("requests", 0) for r in wins)
    if win_requests != completed:
        problems.append(f"windows account for {win_requests} requests, "
                        f"{completed} completed")
    summaries = [r for r in recs if r.get("kind") == "serve_summary"]
    if summaries and summaries[-1].get("requests") != completed:
        problems.append(
            f"summary says {summaries[-1].get('requests')} requests, "
            f"{completed} completed")
    import math

    def ordered(d):
        p50, p99 = d.get("p50_s"), d.get("p99_s")
        return (isinstance(p50, (int, float)) and math.isfinite(p50)
                and isinstance(p99, (int, float)) and math.isfinite(p99)
                and p50 <= p99)

    saw_stages = set()
    for w in wins:
        if w.get("requests") and not ordered(w):
            problems.append(f"window e2e quantiles not finite/ordered: "
                            f"{w.get('p50_s')} vs {w.get('p99_s')}")
        for name, d in (w.get("stages") or {}).items():
            saw_stages.add(name)
            if not ordered(d):
                problems.append(f"stage {name} quantiles not "
                                f"finite/ordered in a window")
    missing = {"t_queue", "t_coalesce", "t_dispatch",
               "t_reply"} - saw_stages
    if missing:
        problems.append(f"stage distributions never observed: "
                        f"{sorted(missing)}")
    return problems


def _stream_drift_records(path):
    """serve_drift records from a health stream, oldest first."""
    out = []
    with open(path, "rb") as fh:
        for raw in fh.read().split(b"\n"):
            if not raw.strip():
                continue
            try:
                rec = json.loads(raw)
            except ValueError:
                continue
            if rec.get("kind") == "serve_drift":
                out.append(rec)
    return out


def shift_sweep(bst, X, tmpdir=None, threshold=0.2):
    """Shifted + control drift cells.  Judges each cell's verdict via
    the HEALTH STREAM (the interface monitors and the refit loop
    consume), sets ``drift_ok`` on the records, and returns
    (records, problems)."""
    tmp = tmpdir or tempfile.mkdtemp(prefix="loadgen_shift_")
    feat = bst.feature_name()[SHIFT_COL]
    shift = run_shift_cell(
        bst, X, "shift", threshold=threshold, seed=11,
        health_path=os.path.join(tmp, "shift.serve.health.jsonl"))
    control = run_shift_cell(
        bst, X, "control", offset=0.0, threshold=threshold, seed=12,
        health_path=os.path.join(tmp, "control.serve.health.jsonl"))
    problems = []
    for rec in (shift, control):
        if rec["errors"] or rec["completed"] != rec["requests"]:
            problems.append(f"{rec['config']}: {rec['errors']} errors, "
                            f"{rec['completed']}/{rec['requests']} done")
        if not rec["quality_ok"]:
            problems.append(f"{rec['config']}: replies diverged from "
                            f"Booster.predict with the drift tap on")
    sdrift = _stream_drift_records(
        os.path.join(tmp, "shift.serve.health.jsonl"))
    shift_ok = True
    if not sdrift:
        shift_ok = False
        problems.append("shift stream: no serve_drift record emitted")
    else:
        last = sdrift[-1]
        if not last.get("drifted"):
            shift_ok = False
            problems.append(
                f"shift stream: shifted sweep not flagged "
                f"(psi_max={last.get('psi_max')} < {threshold})")
        top = (last.get("top") or [{}])[0].get("feature")
        if top != feat:
            shift_ok = False
            problems.append(f"shift stream: top drifting feature "
                            f"{top!r}, expected {feat!r}")
    cdrift = _stream_drift_records(
        os.path.join(tmp, "control.serve.health.jsonl"))
    control_ok = True
    if any(r.get("drifted") for r in cdrift):
        control_ok = False
        problems.append("control stream: unshifted sweep flagged as "
                        "drifted (false positive)")
    if cdrift and not all(
            isinstance(r.get("psi_max"), (int, float))
            and r["psi_max"] < threshold for r in cdrift):
        control_ok = False
        problems.append(
            f"control stream: psi_max "
            f"{[r.get('psi_max') for r in cdrift]} not under "
            f"threshold {threshold}")
    shift["drift_ok"] = shift_ok and shift["quality_ok"]
    control["drift_ok"] = control_ok and control["quality_ok"]
    return [shift, control], problems


def smoke():
    """~2s burst with assertions; exit 1 on any violated contract.
    The CI leg behind tools/verify_t1.sh --serve-smoke."""
    import numpy as np

    import lightgbm_tpu as lgb

    bst, X = _train(np, lgb, dict(rows=1_500, feats=8, iters=8,
                                  leaves=15))
    tmp = tempfile.mkdtemp(prefix="loadgen_smoke_")
    problems = []
    # cell 1: fast arrivals into an open coalescing window MUST batch
    hot = run_cell(bst, X, "smoke", rate=300.0, delay_ms=25.0,
                   duration_s=1.4, max_batch=64,
                   health_path=os.path.join(tmp, "hot.serve.health.jsonl"),
                   window_s=0.4)
    # cell 2: a trickle with no window degenerates to ~1 row/batch
    trickle = run_cell(bst, X, "smoke", rate=15.0, delay_ms=0.0,
                       duration_s=1.0, max_batch=64,
                       health_path=os.path.join(
                           tmp, "trickle.serve.health.jsonl"),
                       window_s=0.4)
    for rec in (hot, trickle):
        print("LOADGEN_RESULT_JSON:" + json.dumps(rec), flush=True)
        if rec["errors"] or rec["completed"] != rec["requests"]:
            problems.append(f"{rec['config']}: {rec['errors']} errors, "
                            f"{rec['completed']}/{rec['requests']} done")
        if not rec["quality_ok"]:
            problems.append(f"{rec['config']}: serve output diverged "
                            f"from Booster.predict")
    if not (hot["rows_per_batch"] and hot["rows_per_batch"] > 1.5):
        problems.append(f"coalescing never engaged at 300 req/s: "
                        f"rows_per_batch={hot['rows_per_batch']}")
    if not (trickle["rows_per_batch"]
            and trickle["rows_per_batch"] < 1.5):
        problems.append(f"trickle traffic unexpectedly batched: "
                        f"rows_per_batch={trickle['rows_per_batch']}")
    problems += [f"hot stream: {p}" for p in _check_health_stream(
        os.path.join(tmp, "hot.serve.health.jsonl"), hot["completed"])]
    problems += [f"trickle stream: {p}" for p in _check_health_stream(
        os.path.join(tmp, "trickle.serve.health.jsonl"),
        trickle["completed"])]
    # drift cells: the shifted sweep must be flagged with the shifted
    # column named first, the control sweep must stay quiet, and
    # replies stay bit-identical with the drift tap armed
    drift_recs, drift_problems = shift_sweep(bst, X, tmpdir=tmp)
    for rec in drift_recs:
        print("LOADGEN_RESULT_JSON:" + json.dumps(rec), flush=True)
    problems += drift_problems
    # hot-swap cell: traffic + 3 background swaps, zero failed replies,
    # every reply bit-identical to a live generation, flip pause bounded
    swap_rec = run_swap_cell(
        bst, X, "smoke", n_swaps=3, rate=200.0, duration_s=1.6,
        health_path=os.path.join(tmp, "swap.serve.health.jsonl"))
    print("LOADGEN_RESULT_JSON:" + json.dumps(swap_rec), flush=True)
    problems += swap_problems(swap_rec, n_swaps=3)
    for p in problems:
        sys.stderr.write(f"loadgen smoke: FAIL {p}\n")
    print(f"loadgen smoke: {'FAIL' if problems else 'ok'} "
          f"(hot {hot['rows_per_batch']} rows/batch at "
          f"{hot['qps']} qps, trickle {trickle['rows_per_batch']}, "
          f"shift psi_max {drift_recs[0]['psi_max']} vs control "
          f"{drift_recs[1]['psi_max']}, {swap_rec['swaps']} swaps with "
          f"pause p99 {swap_rec['swap_pause_p99_s']}s)")
    return 1 if problems else 0


def swap_problems(rec, n_swaps, pause_bound_s=1.0):
    """The hot-swap cell's contracts, as gate-able problem strings."""
    problems = []
    if rec["errors"] or rec["completed"] != rec["requests"]:
        problems.append(f"{rec['config']}: {rec['errors']} failed "
                        f"replies, {rec['completed']}/{rec['requests']} "
                        f"done (hot swap must be zero-downtime)")
    if not rec["quality_ok"]:
        problems.append(f"{rec['config']}: a reply matched NO live "
                        f"generation (snapshot pinning broke)")
    if rec["swaps"] != n_swaps:
        problems.append(f"{rec['config']}: {rec['swaps']}/{n_swaps} "
                        f"swaps completed")
    if rec["swap_pause_p99_s"] is None \
            or rec["swap_pause_p99_s"] > pause_bound_s:
        problems.append(f"{rec['config']}: flip pause p99 "
                        f"{rec['swap_pause_p99_s']}s exceeds "
                        f"{pause_bound_s}s")
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="open-loop Poisson serve load sweep "
                    "-> BENCH_SERVE.json")
    ap.add_argument("--smoke", action="store_true",
                    help="~2s burst with coalescing + health-stream + "
                         "drift assertions, no artifacts")
    ap.add_argument("--shift", action="store_true",
                    help="drift cells only: shifted + control sweeps "
                         "with drift_detect armed -> trajectory")
    ap.add_argument("--swap", action="store_true",
                    help="hot-swap cell: open-loop traffic while the "
                         "model is refitted and swapped mid-flight")
    ap.add_argument("--swaps", type=int, default=3,
                    help="--swap mode: background hot swaps per cell")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="single-cell mode: arrival rate req/s")
    ap.add_argument("--delay-ms", type=float, default=0.0,
                    help="single-cell mode: serve_max_delay_ms")
    ap.add_argument("--duration", type=float, default=DURATION_S,
                    help=f"seconds per cell (default {DURATION_S})")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--no-artifacts", action="store_true",
                    help="print records only; do not touch "
                         "BENCH_SERVE.json / the trajectory")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    from lightgbm_tpu.utils import enable_jax_compilation_cache
    enable_jax_compilation_cache()
    if args.smoke:
        return smoke()

    import numpy as np

    import lightgbm_tpu as lgb

    if args.swap:
        bst, X = _train(np, lgb, dict(rows=1_500, feats=8, iters=8,
                                      leaves=15))
        rec = run_swap_cell(bst, X, "small", n_swaps=args.swaps,
                            duration_s=max(args.duration, 1.5))
        print(json.dumps(rec), flush=True)
        problems = swap_problems(rec, n_swaps=args.swaps)
        for p in problems:
            sys.stderr.write(f"loadgen swap: FAIL {p}\n")
        if not args.no_artifacts:
            merge_bench_serve([rec])
            append_trajectory([rec])
            print("loadgen: merged 1 swap cell into BENCH_SERVE.json")
        return 1 if problems else 0

    if args.shift:
        bst, X = _train(np, lgb, dict(rows=1_500, feats=8, iters=8,
                                      leaves=15))
        records, problems = shift_sweep(bst, X)
        for rec in records:
            print(json.dumps(rec), flush=True)
        for p in problems:
            sys.stderr.write(f"loadgen shift: FAIL {p}\n")
        if not args.no_artifacts:
            merge_bench_serve(records)
            append_trajectory(records)
            print(f"loadgen: merged {len(records)} drift cell(s) into "
                  f"BENCH_SERVE.json")
        return 1 if problems else 0

    size, spec = MODEL
    bst, X = _train(np, lgb, spec)
    cells = ([(args.rate, args.delay_ms)] if args.rate > 0
             else [(r, d) for r in RATES for d in DELAYS_MS])
    records = []
    for i, (rate, delay) in enumerate(cells):
        rec = run_cell(bst, X, size, rate, delay, args.duration,
                       max_batch=args.max_batch, seed=i)
        records.append(rec)
        print(json.dumps(rec), flush=True)
    if not records:
        return 1
    coalesced = [r for r in records
                 if r.get("rows_per_batch") and r["rows_per_batch"] > 1.0]
    if not coalesced:
        sys.stderr.write("loadgen: WARNING no cell engaged the "
                         "coalescing window (rows_per_batch <= 1 "
                         "everywhere)\n")
    if not args.no_artifacts:
        merge_bench_serve(records)
        append_trajectory(records)
        print(f"loadgen: merged {len(records)} cell(s) into "
              f"BENCH_SERVE.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
