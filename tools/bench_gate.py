"""Perf-regression sentinel over BENCH_TRAJECTORY.jsonl.

BENCH_TRAJECTORY.jsonl (appended by bench_suite.py / bench.py rounds)
is the machine-readable perf trajectory across PRs: one digest line per
run with wall value, peak HBM, quality gate and FLOP estimates.  This
tool turns the trailing history into a GATE instead of a log: for each
config, the newest record is compared against the median of the
previous ``--window`` records, and the gate fails (exit 1) when

  * wall time regresses more than ``--wall-tol`` (default +15%),
  * peak HBM regresses more than ``--hbm-tol`` (default +20%),
  * the quality gate flips from held to failed,
  * measured dispatch latency (``dispatch_mean_s``, recorded by runs
    with ``device_timing=`` on) regresses more than ``--latency-tol``
    (default +20%), or
  * serve tail latency (``p99_s``, recorded by bench_serve.py) regresses
    more than ``--latency-tol`` over the trailing median,
  * the drift gate flips — ``drift_ok`` (recorded by loadgen --shift
    runs, true when the drift plane's verdict matched expectation)
    goes from held to failed — or ``psi_max`` regresses more than
    ``--psi-tol`` over the trailing median while sitting above the
    absolute noise floor (0.1 PSI; below it, sampling jitter dominates
    and the ratio gate stays silent),
  * the hot-swap flip pause (``swap_pause_p99_s``, recorded by loadgen
    --swap cells) regresses more than ``--latency-tol`` over the
    trailing median, or the shed rate (``shed_rate``) regresses more
    than ``--latency-tol`` — including shedding APPEARING where the
    trailing history shed nothing.

Serve records (bench_serve.py) carry ``qps``/``p50_s``/``p99_s`` and no
training ``value``/``unit``/``peak_hbm_bytes`` — every gate skips fields
a record does not have, so mixed trajectories gate cleanly.

A missing/empty trajectory, a config with no prior history, or records
without comparable fields all PASS with a "no history" notice — the
gate never blocks the first benchmark of a new config.

Usage:
  python tools/bench_gate.py                     # repo trajectory
  python tools/bench_gate.py --path X.jsonl --window 8 --wall-tol 0.10
  python tools/bench_gate.py --self-test         # fast CI smoke
  python tools/bench_gate.py --fleet-summary fleet_summary.json

``--fleet-summary`` gates a tools/fleet_monitor.py rollup instead of
the trajectory: schema pin, per-rank wait fractions in [0, 1],
straggler histogram consistency, per-subsystem fault counts.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_PATH = os.path.join(REPO, "BENCH_TRAJECTORY.jsonl")

FLEET_SUMMARY_SCHEMA = "lightgbm_tpu.fleet_summary/v1"


def load(path):
    """Trajectory records, oldest first.  Null-tolerant: a missing or
    empty file is just an empty history; torn lines are skipped."""
    records = []
    if not os.path.exists(path):
        return records
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                records.append(rec)
    return records


def _median(values):
    vals = sorted(values)
    n = len(vals)
    if n == 0:
        return None
    mid = n // 2
    return vals[mid] if n % 2 else 0.5 * (vals[mid - 1] + vals[mid])


def _config_of(rec):
    return rec.get("config") or rec.get("metric") or "?"


PSI_NOISE_FLOOR = 0.1


def evaluate(records, window=5, wall_tol=0.15, hbm_tol=0.20,
             latency_tol=0.20, psi_tol=0.50):
    """(failures, notes) over the trajectory.  The newest record of each
    config is judged against the median of up to ``window`` prior
    records of the same config; everything older informs, never gates."""
    failures, notes = [], []
    if not records:
        notes.append("no history: trajectory is empty or absent — pass")
        return failures, notes
    by_config = {}
    for rec in records:
        by_config.setdefault(_config_of(rec), []).append(rec)
    for config, recs in sorted(by_config.items()):
        newest, history = recs[-1], recs[:-1][-window:]
        if not history:
            notes.append(f"{config}: no history (first record) — pass")
            continue
        # quality flip: regressing from held quality is a failure even
        # when the timing looks fine
        held_before = any(r.get("quality_ok") for r in history)
        if held_before and newest.get("quality_ok") is False:
            failures.append(f"{config}: quality gate flipped to FAILED "
                            f"(held in trailing history)")
        value = newest.get("value")
        base_vals = [r["value"] for r in history
                     if isinstance(r.get("value"), (int, float))
                     and r["value"] > 0
                     and r.get("unit") == newest.get("unit")]
        base = _median(base_vals)
        if (isinstance(value, (int, float)) and value > 0
                and base is not None):
            ratio = value / base
            line = (f"{config}: {newest.get('metric', 'value')} "
                    f"{value:g}{newest.get('unit', '')} vs median "
                    f"{base:g} ({ratio - 1.0:+.1%})")
            if ratio > 1.0 + wall_tol:
                failures.append(f"{config}: wall {value:g}"
                                f"{newest.get('unit', '')} regressed "
                                f"{ratio - 1.0:+.1%} over median "
                                f"{base:g} (tol +{wall_tol:.0%})")
            else:
                notes.append(line + " — ok")
        else:
            notes.append(f"{config}: no comparable wall history — pass")
        hbm = newest.get("peak_hbm_bytes")
        hbm_base = _median([r["peak_hbm_bytes"] for r in history
                            if isinstance(r.get("peak_hbm_bytes"),
                                          (int, float))
                            and r["peak_hbm_bytes"] > 0])
        if (isinstance(hbm, (int, float)) and hbm > 0
                and hbm_base is not None):
            if hbm / hbm_base > 1.0 + hbm_tol:
                failures.append(
                    f"{config}: peak HBM {hbm:.0f}B regressed "
                    f"{hbm / hbm_base - 1.0:+.1%} over median "
                    f"{hbm_base:.0f}B (tol +{hbm_tol:.0%})")
        # measured dispatch latency (device_timing runs only): wall time
        # can hide a slower dispatch behind async pipelining — the
        # measured mean cannot
        lat = newest.get("dispatch_mean_s")
        lat_base = _median([r["dispatch_mean_s"] for r in history
                            if isinstance(r.get("dispatch_mean_s"),
                                          (int, float))
                            and r["dispatch_mean_s"] > 0])
        if (isinstance(lat, (int, float)) and lat > 0
                and lat_base is not None):
            if lat / lat_base > 1.0 + latency_tol:
                failures.append(
                    f"{config}: dispatch latency {lat * 1e3:.3f}ms "
                    f"regressed {lat / lat_base - 1.0:+.1%} over median "
                    f"{lat_base * 1e3:.3f}ms (tol +{latency_tol:.0%})")
            else:
                notes.append(f"{config}: dispatch latency "
                             f"{lat * 1e3:.3f}ms vs median "
                             f"{lat_base * 1e3:.3f}ms — ok")
        # histogram-pass latency (records with per-label dispatch
        # timing): the hist kernels are the iteration's dominant cost
        # post-route-window, so a regression here can hide inside a
        # steady wall when other phases happen to improve
        hp = newest.get("hist_pass_mean_s")
        hp_base = _median([r["hist_pass_mean_s"] for r in history
                           if isinstance(r.get("hist_pass_mean_s"),
                                         (int, float))
                           and r["hist_pass_mean_s"] > 0])
        if (isinstance(hp, (int, float)) and hp > 0
                and hp_base is not None):
            if hp / hp_base > 1.0 + latency_tol:
                failures.append(
                    f"{config}: hist pass {hp * 1e3:.3f}ms regressed "
                    f"{hp / hp_base - 1.0:+.1%} over median "
                    f"{hp_base * 1e3:.3f}ms (tol +{latency_tol:.0%})")
            else:
                notes.append(f"{config}: hist pass {hp * 1e3:.3f}ms vs "
                             f"median {hp_base * 1e3:.3f}ms — ok")
        # serve tail latency (bench_serve.py records): p99 is the
        # service-level promise, so it gates where mean would forgive a
        # fat tail
        p99 = newest.get("p99_s")
        p99_base = _median([r["p99_s"] for r in history
                            if isinstance(r.get("p99_s"), (int, float))
                            and r["p99_s"] > 0])
        if (isinstance(p99, (int, float)) and p99 > 0
                and p99_base is not None):
            if p99 / p99_base > 1.0 + latency_tol:
                failures.append(
                    f"{config}: serve p99 {p99 * 1e3:.3f}ms regressed "
                    f"{p99 / p99_base - 1.0:+.1%} over median "
                    f"{p99_base * 1e3:.3f}ms (tol +{latency_tol:.0%})")
            else:
                notes.append(f"{config}: serve p99 {p99 * 1e3:.3f}ms vs "
                             f"median {p99_base * 1e3:.3f}ms — ok")
        # drift gate (loadgen --shift records): drift_ok carries the
        # end-to-end verdict (shifted sweep detected, control clean,
        # replies bit-identical) — a flip from held is a failure like a
        # quality flip.  psi_max additionally ratio-gates against its
        # trailing median, but only above an absolute noise floor:
        # small-PSI windows move multiplicatively with sampling jitter
        # and would flap the gate.
        drift_held = any(r.get("drift_ok") for r in history)
        if drift_held and newest.get("drift_ok") is False:
            failures.append(f"{config}: drift gate flipped to FAILED "
                            f"(held in trailing history)")
        psi = newest.get("psi_max")
        psi_base = _median([r["psi_max"] for r in history
                            if isinstance(r.get("psi_max"), (int, float))
                            and r["psi_max"] > 0])
        if (isinstance(psi, (int, float)) and psi > 0
                and psi_base is not None):
            if (psi > PSI_NOISE_FLOOR
                    and psi / psi_base > 1.0 + psi_tol):
                failures.append(
                    f"{config}: psi_max {psi:.3f} regressed "
                    f"{psi / psi_base - 1.0:+.1%} over median "
                    f"{psi_base:.3f} (tol +{psi_tol:.0%}, floor "
                    f"{PSI_NOISE_FLOOR:g})")
            else:
                notes.append(f"{config}: psi_max {psi:.3f} vs median "
                             f"{psi_base:.3f} — ok")
        # hot-swap cells (loadgen --swap): the flip pause p99 is the
        # zero-downtime promise in seconds — it gates like a latency
        pause = newest.get("swap_pause_p99_s")
        pause_base = _median([r["swap_pause_p99_s"] for r in history
                              if isinstance(r.get("swap_pause_p99_s"),
                                            (int, float))
                              and r["swap_pause_p99_s"] > 0])
        if (isinstance(pause, (int, float)) and pause > 0
                and pause_base is not None):
            if pause / pause_base > 1.0 + latency_tol:
                failures.append(
                    f"{config}: swap pause p99 {pause * 1e3:.3f}ms "
                    f"regressed {pause / pause_base - 1.0:+.1%} over "
                    f"median {pause_base * 1e3:.3f}ms "
                    f"(tol +{latency_tol:.0%})")
            else:
                notes.append(f"{config}: swap pause p99 "
                             f"{pause * 1e3:.3f}ms vs median "
                             f"{pause_base * 1e3:.3f}ms — ok")
        # shed rate: a ratio gate where the cell historically shed, and
        # an appearance gate where it never did — a queue that starts
        # shedding at an unchanged arrival rate is a capacity regression
        shed = newest.get("shed_rate")
        shed_hist = [r["shed_rate"] for r in history
                     if isinstance(r.get("shed_rate"), (int, float))]
        if isinstance(shed, (int, float)) and shed_hist:
            shed_base = _median(shed_hist)
            if shed_base > 0 and shed / shed_base > 1.0 + latency_tol:
                failures.append(
                    f"{config}: shed rate {shed:.4f} regressed "
                    f"{shed / shed_base - 1.0:+.1%} over median "
                    f"{shed_base:.4f} (tol +{latency_tol:.0%})")
            elif shed_base == 0 and shed > 0:
                failures.append(
                    f"{config}: shedding appeared (rate {shed:.4f}) "
                    f"where the trailing history shed nothing")
            else:
                notes.append(f"{config}: shed rate {shed:.4f} vs "
                             f"median {shed_base:.4f} — ok")
    return failures, notes


def gate(path, window=5, wall_tol=0.15, hbm_tol=0.20, latency_tol=0.20,
         psi_tol=0.50, out=sys.stdout):
    failures, notes = evaluate(load(path), window, wall_tol, hbm_tol,
                               latency_tol, psi_tol)
    for note in notes:
        out.write(f"bench_gate: {note}\n")
    for failure in failures:
        out.write(f"bench_gate: FAIL {failure}\n")
    out.write(f"bench_gate: {'FAIL' if failures else 'PASS'} "
              f"({len(failures)} regression(s), {path})\n")
    return 1 if failures else 0


def validate_fleet_summary(summary):
    """Structural gate over a tools/fleet_monitor.py
    ``fleet_summary.json``: returns a list of problems (empty = valid).
    The CI fleet-smoke leg feeds its freshly-written summary through
    this, so a malformed v6 rollup fails the build, not the reader."""
    problems = []
    if not isinstance(summary, dict):
        return ["fleet summary is not a JSON object"]
    if summary.get("schema") != FLEET_SUMMARY_SCHEMA:
        problems.append(f"schema {summary.get('schema')!r} != "
                        f"{FLEET_SUMMARY_SCHEMA!r}")
    streams = summary.get("streams")
    if not isinstance(streams, dict) or not streams:
        problems.append("streams section missing or empty")
    else:
        for name, view in streams.items():
            if not isinstance(view, dict) or "status" not in view:
                problems.append(f"stream {name}: malformed view")
            elif not isinstance(view.get("records"), int) \
                    or view["records"] < 0:
                problems.append(f"stream {name}: bad record count "
                                f"{view.get('records')!r}")
    per_rank = summary.get("per_rank", {})
    if not isinstance(per_rank, dict):
        problems.append("per_rank is not an object")
    else:
        for rank, slot in per_rank.items():
            frac = slot.get("wait_fraction") \
                if isinstance(slot, dict) else None
            if not isinstance(frac, (int, float)) \
                    or not 0.0 <= frac <= 1.0:
                problems.append(f"rank {rank}: wait_fraction "
                                f"{frac!r} outside [0, 1]")
            for key in ("wait_s", "work_s"):
                v = slot.get(key) if isinstance(slot, dict) else None
                if not isinstance(v, (int, float)) or v < 0:
                    problems.append(f"rank {rank}: {key} {v!r} "
                                    f"negative or missing")
    hist = summary.get("straggler_hist", {})
    if not isinstance(hist, dict) or any(
            not isinstance(n, int) or n < 1 for n in hist.values()):
        problems.append("straggler_hist counts must be positive ints")
    elif isinstance(summary.get("windows"), int) \
            and sum(hist.values()) > summary["windows"]:
        problems.append("straggler_hist exceeds the window count")
    faults = summary.get("faults", {})
    if not isinstance(faults, dict) or any(
            not isinstance(n, int) or n < 0 for n in faults.values()):
        problems.append("faults section counts must be ints >= 0")
    if not isinstance(summary.get("complete"), bool):
        problems.append("complete flag missing or not a bool")
    return problems


def gate_fleet_summary(path, out=sys.stdout):
    try:
        with open(path) as fh:
            summary = json.load(fh)
    except (OSError, ValueError) as e:
        out.write(f"bench_gate: FAIL unreadable fleet summary "
                  f"{path}: {e}\n")
        return 1
    problems = validate_fleet_summary(summary)
    for p in problems:
        out.write(f"bench_gate: FAIL fleet summary: {p}\n")
    out.write(f"bench_gate: fleet summary "
              f"{'FAIL' if problems else 'PASS'} ({path})\n")
    return 1 if problems else 0


def self_test():
    """Fast smoke of the gate logic (no files, no history mutation)."""
    hist = [{"config": "c", "value": 10.0 + 0.1 * i, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": 1000,
             "dispatch_mean_s": 0.010 + 0.0001 * i}
            for i in range(4)]

    def verdict(newest):
        failures, _ = evaluate(hist + [newest])
        return bool(failures)

    checks = [
        ("empty history passes", evaluate([]) == ([], [
            "no history: trajectory is empty or absent — pass"])),
        ("first record passes",
         not evaluate([{"config": "new", "value": 1.0, "unit": "s"}])[0]),
        ("steady wall passes", not verdict(
            {"config": "c", "value": 10.2, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": 1000})),
        ("wall regression fails", verdict(
            {"config": "c", "value": 20.0, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": 1000})),
        ("hbm regression fails", verdict(
            {"config": "c", "value": 10.2, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": 5000})),
        ("quality flip fails", verdict(
            {"config": "c", "value": 10.2, "unit": "s",
             "quality_ok": False, "peak_hbm_bytes": 1000})),
        ("null fields pass", not verdict(
            {"config": "c", "value": None, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": None})),
        ("steady dispatch latency passes", not verdict(
            {"config": "c", "value": 10.2, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": 1000,
             "dispatch_mean_s": 0.0102})),
        ("dispatch latency regression fails", verdict(
            {"config": "c", "value": 10.2, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": 1000,
             "dispatch_mean_s": 0.020})),
        ("timing-off record passes latency gate", not verdict(
            {"config": "c", "value": 10.2, "unit": "s",
             "quality_ok": True, "peak_hbm_bytes": 1000,
             "dispatch_mean_s": None})),
    ]
    hhist = [{"config": "h", "value": 1.0, "unit": "s/iter",
              "quality_ok": True, "hist_pass_mean_s": 0.0124 + 0.0001 * i}
             for i in range(4)]

    def hverdict(newest):
        failures, _ = evaluate(hhist + [newest])
        return bool(failures)

    checks += [
        ("steady hist pass passes", not hverdict(
            {"config": "h", "value": 1.0, "unit": "s/iter",
             "quality_ok": True, "hist_pass_mean_s": 0.0126})),
        ("hist pass regression fails", hverdict(
            {"config": "h", "value": 1.0, "unit": "s/iter",
             "quality_ok": True, "hist_pass_mean_s": 0.020})),
        ("hist-field-free record passes hist gate", not hverdict(
            {"config": "h", "value": 1.0, "unit": "s/iter",
             "quality_ok": True, "hist_pass_mean_s": None})),
    ]
    # fused-K ladder records: the fused rounds
    # dispatch under the grower's own label, so hist_pass_label takes
    # the "grow/frontier[fused_hist_kK]" shape, and SUITE_CONFIG_TAG
    # makes the cell its own config series — the gate must baseline the
    # tagged series against itself, never the untagged defaults
    fkhist = [{"config": "goss_regression+fusedk8_force", "value": 30.0,
               "unit": "s", "quality_ok": True,
               "hist_pass_label": "grow/frontier[fused_hist_k8]",
               "hist_pass_mean_s": 0.41 + 0.002 * i} for i in range(4)]

    def fkverdict(newest):
        failures, _ = evaluate(hhist + fkhist + [newest])
        return bool(failures)

    checks += [
        ("fused-K labeled record steady passes", not fkverdict(
            {"config": "goss_regression+fusedk8_force", "value": 30.2,
             "unit": "s", "quality_ok": True,
             "hist_pass_label": "grow/frontier[fused_hist_k8]",
             "hist_pass_mean_s": 0.413})),
        ("fused-K hist pass regression fails", fkverdict(
            {"config": "goss_regression+fusedk8_force", "value": 30.2,
             "unit": "s", "quality_ok": True,
             "hist_pass_label": "grow/frontier[fused_hist_k8]",
             "hist_pass_mean_s": 0.60})),
        ("tagged cell never reads the untagged baseline", not evaluate(
            hhist + fkhist
            + [{"config": "goss_regression", "value": 200.0, "unit": "s",
                "quality_ok": True,
                "hist_pass_label": "grow/frontier[fused_hist_k8]",
                "hist_pass_mean_s": 5.0}])[0]),
    ]
    shist = [{"config": "serve-s-b16-d0", "qps": 1000.0 - 5 * i,
              "p50_s": 0.001, "p99_s": 0.004 + 0.0001 * i,
              "quality_ok": True} for i in range(4)]

    def sverdict(newest):
        failures, _ = evaluate(shist + [newest])
        return bool(failures)

    checks += [
        ("serve record w/o training fields passes", not sverdict(
            {"config": "serve-s-b16-d0", "qps": 990.0, "p50_s": 0.001,
             "p99_s": 0.0041, "quality_ok": True})),
        ("serve p99 regression fails", sverdict(
            {"config": "serve-s-b16-d0", "qps": 990.0, "p50_s": 0.001,
             "p99_s": 0.009, "quality_ok": True})),
        ("serve first record passes", not evaluate(
            [{"config": "serve-new", "qps": 5.0, "p99_s": 0.1}])[0]),
    ]
    # open-loop loadgen records (tools/loadgen.py): same p99 gate, but
    # the record shape carries rows_per_batch instead of bucket fields
    lhist = [{"config": "loadgen-small-r300-d5", "qps": 295.0 + i,
              "rows_per_batch": 6.0 + 0.1 * i, "p50_s": 0.004,
              "p99_s": 0.012 + 0.0002 * i, "quality_ok": True}
             for i in range(4)]

    def lverdict(newest):
        failures, _ = evaluate(lhist + [newest])
        return bool(failures)

    checks += [
        ("open-loop steady p99 passes", not lverdict(
            {"config": "loadgen-small-r300-d5", "qps": 297.0,
             "rows_per_batch": 6.2, "p50_s": 0.004, "p99_s": 0.0125,
             "quality_ok": True})),
        ("open-loop p99 regression fails", lverdict(
            {"config": "loadgen-small-r300-d5", "qps": 297.0,
             "rows_per_batch": 6.2, "p50_s": 0.004, "p99_s": 0.020,
             "quality_ok": True})),
        ("open-loop quality flip fails", lverdict(
            {"config": "loadgen-small-r300-d5", "qps": 297.0,
             "rows_per_batch": 6.2, "p50_s": 0.004, "p99_s": 0.0125,
             "quality_ok": False})),
        ("open-loop first record passes", not evaluate(
            [{"config": "loadgen-new-r50-d0", "qps": 49.0,
              "p99_s": 0.01}])[0]),
    ]
    # multi-tenant scheduler records (tools/submit_jobs.py workloads):
    # sched-only fields (fairness_index, queue_wait, cache hits) ride
    # along without tripping the field-specific gates; the wall gate
    # still judges the workload's end-to-end time, and quality_ok
    # carries the fairness-threshold verdict
    schist = [{"config": "sched-fair-3job", "value": 6.0 + 0.05 * i,
               "unit": "s", "quality_ok": True,
               "fairness_index": 0.95 - 0.001 * i,
               "queue_wait_s": 0.4, "cross_job_cache_hits": 2}
              for i in range(4)]

    def scverdict(newest):
        failures, _ = evaluate(schist + [newest])
        return bool(failures)

    checks += [
        ("sched steady wall passes", not scverdict(
            {"config": "sched-fair-3job", "value": 6.1, "unit": "s",
             "quality_ok": True, "fairness_index": 0.95,
             "queue_wait_s": 0.41, "cross_job_cache_hits": 2})),
        ("sched wall regression fails", scverdict(
            {"config": "sched-fair-3job", "value": 12.0, "unit": "s",
             "quality_ok": True, "fairness_index": 0.95,
             "queue_wait_s": 0.4, "cross_job_cache_hits": 2})),
        ("sched fairness flip fails", scverdict(
            {"config": "sched-fair-3job", "value": 6.1, "unit": "s",
             "quality_ok": False, "fairness_index": 0.45,
             "queue_wait_s": 0.4, "cross_job_cache_hits": 0})),
        ("sched first record passes", not evaluate(
            [{"config": "sched-rr-2job", "value": 3.0, "unit": "s",
              "fairness_index": 0.99}])[0]),
    ]
    # drift-plane records (tools/loadgen.py --shift cells): drift_ok is
    # a quality-style flip gate; psi_max ratio-gates only above the
    # absolute noise floor so small-sample jitter never flaps it
    dhist = [{"config": "loadgen-shift-control", "qps": 200.0,
              "p99_s": 0.010, "quality_ok": True, "drift_ok": True,
              "psi_max": 0.040 + 0.002 * i} for i in range(4)]

    def dverdict(newest):
        failures, _ = evaluate(dhist + [newest])
        return bool(failures)

    checks += [
        ("steady drift record passes", not dverdict(
            {"config": "loadgen-shift-control", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True, "drift_ok": True,
             "psi_max": 0.045})),
        ("drift_ok flip fails", dverdict(
            {"config": "loadgen-shift-control", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True, "drift_ok": False,
             "psi_max": 0.045})),
        ("psi_max below noise floor never ratio-gates", not dverdict(
            {"config": "loadgen-shift-control", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True, "drift_ok": True,
             "psi_max": 0.09})),
        ("psi_max regression over floor fails", dverdict(
            {"config": "loadgen-shift-control", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True, "drift_ok": True,
             "psi_max": 0.40})),
        ("drift-field-free record passes drift gate", not dverdict(
            {"config": "loadgen-shift-control", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True})),
        ("drift first record passes", not evaluate(
            [{"config": "loadgen-shift-new", "drift_ok": True,
              "psi_max": 1.2}])[0]),
    ]
    # hot-swap cells (tools/loadgen.py --swap): swap_pause_p99_s gates
    # like a latency, shed_rate gates on ratio AND on appearing where
    # the trailing history shed nothing
    whist = [{"config": "loadgen-swap-smoke", "qps": 200.0,
              "p99_s": 0.010, "quality_ok": True, "swaps": 3,
              "swap_pause_p99_s": 0.004 + 0.0001 * i, "shed_rate": 0.0}
             for i in range(4)]

    def wverdict(newest):
        failures, _ = evaluate(whist + [newest])
        return bool(failures)

    checks += [
        ("steady swap pause passes", not wverdict(
            {"config": "loadgen-swap-smoke", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True, "swaps": 3,
             "swap_pause_p99_s": 0.0042, "shed_rate": 0.0})),
        ("swap pause regression fails", wverdict(
            {"config": "loadgen-swap-smoke", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True, "swaps": 3,
             "swap_pause_p99_s": 0.02, "shed_rate": 0.0})),
        ("shedding appearing from zero fails", wverdict(
            {"config": "loadgen-swap-smoke", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True, "swaps": 3,
             "swap_pause_p99_s": 0.0042, "shed_rate": 0.05})),
        ("swap quality flip fails", wverdict(
            {"config": "loadgen-swap-smoke", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": False, "swaps": 3,
             "swap_pause_p99_s": 0.0042, "shed_rate": 0.0})),
        ("swap-field-free record passes swap gates", not wverdict(
            {"config": "loadgen-swap-smoke", "qps": 200.0,
             "p99_s": 0.010, "quality_ok": True})),
        ("swap first record passes", not evaluate(
            [{"config": "loadgen-swap-new", "swap_pause_p99_s": 0.5,
              "shed_rate": 0.5}])[0]),
    ]
    shed_hist = [{"config": "loadgen-swap-shed", "quality_ok": True,
                  "swap_pause_p99_s": 0.004, "shed_rate": 0.010}
                 for _ in range(4)]
    checks += [
        ("steady nonzero shed rate passes", not evaluate(
            shed_hist + [{"config": "loadgen-swap-shed",
                          "quality_ok": True,
                          "swap_pause_p99_s": 0.004,
                          "shed_rate": 0.011}])[0]),
        ("shed rate ratio regression fails", bool(evaluate(
            shed_hist + [{"config": "loadgen-swap-shed",
                          "quality_ok": True,
                          "swap_pause_p99_s": 0.004,
                          "shed_rate": 0.10}])[0])),
    ]
    # fleet-summary structural gate (tools/fleet_monitor.py output)
    good_fleet = {
        "schema": FLEET_SUMMARY_SCHEMA,
        "streams": {"rank0.health.jsonl": {
            "stream": "train", "status": "finished", "records": 20,
            "rank": 0, "faults": 0}},
        "per_rank": {"0": {"wait_s": 0.5, "work_s": 1.5,
                           "windows": 2, "wait_fraction": 0.25}},
        "straggler_hist": {"1": 2}, "windows": 2,
        "collective_calls": 9, "faults": {"train": 1},
        "clock_offsets": {}, "complete": True,
    }
    checks += [
        ("well-formed fleet summary passes",
         validate_fleet_summary(good_fleet) == []),
        ("fleet schema mismatch fails",
         bool(validate_fleet_summary(
             dict(good_fleet, schema="lightgbm_tpu.fleet_summary/v0")))),
        ("fleet wait_fraction out of range fails",
         bool(validate_fleet_summary(dict(
             good_fleet,
             per_rank={"0": {"wait_s": 0.5, "work_s": 1.5,
                             "wait_fraction": 1.5}})))),
        ("fleet straggler hist over window count fails",
         bool(validate_fleet_summary(
             dict(good_fleet, straggler_hist={"1": 5})))),
        ("fleet empty streams fails",
         bool(validate_fleet_summary(dict(good_fleet, streams={})))),
        ("fleet missing complete flag fails",
         bool(validate_fleet_summary(
             {k: v for k, v in good_fleet.items()
              if k != "complete"}))),
    ]
    bad = [name for name, ok in checks if not ok]
    for name, ok in checks:
        print(f"bench_gate self-test: {'ok' if ok else 'FAIL'} {name}")
    print(f"bench_gate self-test: {'FAIL' if bad else 'PASS'}")
    return 1 if bad else 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="fail on wall/HBM/quality regressions in the newest "
                    "BENCH_TRAJECTORY.jsonl records")
    ap.add_argument("--path", default=DEFAULT_PATH)
    ap.add_argument("--window", type=int, default=5,
                    help="trailing records per config forming the "
                         "baseline median (default 5)")
    ap.add_argument("--wall-tol", type=float, default=0.15,
                    help="allowed wall-time regression (default 0.15)")
    ap.add_argument("--hbm-tol", type=float, default=0.20,
                    help="allowed peak-HBM regression (default 0.20)")
    ap.add_argument("--latency-tol", type=float, default=0.20,
                    help="allowed measured dispatch-latency regression "
                         "(default 0.20; only gates device_timing runs)")
    ap.add_argument("--psi-tol", type=float, default=0.50,
                    help="allowed psi_max regression over the trailing "
                         "median (default 0.50; only gates above the "
                         f"{PSI_NOISE_FLOOR:g} PSI noise floor)")
    ap.add_argument("--self-test", action="store_true",
                    help="run the built-in smoke checks and exit")
    ap.add_argument("--fleet-summary", default=None,
                    help="validate a tools/fleet_monitor.py "
                         "fleet_summary.json instead of the "
                         "trajectory")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    if args.fleet_summary:
        return gate_fleet_summary(args.fleet_summary)
    return gate(args.path, args.window, args.wall_tol, args.hbm_tol,
                args.latency_tol, args.psi_tol)


if __name__ == "__main__":
    sys.exit(main())
