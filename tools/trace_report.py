"""Summarize a lightgbm_tpu metrics JSON blob for perf rounds.

Input: a metrics dict as produced by ``TELEMETRY.metrics_blob()`` /
``Booster.get_stats()`` — the blob the CLI writes for ``metrics_out=``,
``bench.py`` / ``bench_suite.py`` embed under ``"metrics"``, and
``engine.train`` attaches as ``booster.train_stats``.  The current
``lightgbm_tpu.metrics/v7`` schema and the older v6/v5/v4/v3/v2/v1
blobs are all accepted: every section is optional and renders as
``n/a`` when absent.

Usage:
  python tools/trace_report.py metrics.json          # a raw blob
  python tools/trace_report.py bench_record.json     # a bench.py record
                                                     # (reads .metrics)
  python tools/trace_report.py --diff a.json b.json  # phase/counter/
                                                     # memory/cost/
                                                     # timing deltas

Prints top phases, transfer bytes, compile counters/seconds, network
collective counters, the iteration count, (v2) the HBM memory envelope
and XLA cost-analysis utilization digest, (v3) the run-health stream
digest, (v4) the measured dispatch-timing table with
measured-vs-estimated utilization, (v6) the fleet plane's collective
wait-vs-work split with the straggler histogram, and (v7) the drift
plane's per-model PSI / score-JS verdicts — the digest VERDICT /
PERF_NOTES rounds quote instead of regex-parsing stderr tails.
"""

import json
import sys


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024.0 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GB"


def _fmt_rate(n: float, unit: str) -> str:
    n = float(n)
    for prefix in ("", "K", "M", "G", "T"):
        if abs(n) < 1000.0 or prefix == "T":
            return f"{n:.2f}{prefix}{unit}"
        n /= 1000.0
    return f"{n:.2f}T{unit}"


def summarize(stats: dict, top: int = 6) -> str:
    """Multi-line human-readable digest of one metrics blob."""
    lines = []
    mode = stats.get("mode", "?")
    lines.append(f"telemetry summary [version={stats.get('version', 'n/a')} "
                 f"level={stats.get('level', 'n/a')} mode={mode}]")

    phases = stats.get("phases") or {}
    if phases:
        total = sum(p.get("seconds", 0.0) for p in phases.values())
        ranked = sorted(phases.items(),
                        key=lambda kv: -kv[1].get("seconds", 0.0))[:top]
        parts = [f"{name}={p.get('seconds', 0.0):.3f}s/{p.get('count', 0)}"
                 for name, p in ranked]
        lines.append(f"  phases ({mode}) total={total:.3f}s: "
                     + " ".join(parts))
    else:
        lines.append("  phases: n/a")

    counters = stats.get("counters") or {}
    fetch_b = counters.get("transfer/fetch_bytes", 0)
    fetch_n = counters.get("transfer/fetch_calls", 0)
    h2d_b = counters.get("transfer/h2d_bytes", 0)
    if fetch_n or h2d_b:
        lines.append(f"  transfers: d2h {_fmt_bytes(fetch_b)} in "
                     f"{int(fetch_n)} fetches, h2d {_fmt_bytes(h2d_b)}")
    compiles = {k: v for k, v in counters.items()
                if k.startswith("compile/")}
    if compiles:
        lines.append(
            "  compile: "
            f"{int(compiles.get('compile/backend_compiles', 0))} backend "
            f"compiles ({compiles.get('compile/backend_compile_seconds', 0.0):.2f}s), "
            f"{int(compiles.get('compile/retraces', 0))} retraces "
            f"({compiles.get('compile/retrace_seconds', 0.0):.2f}s), "
            f"cache {int(compiles.get('compile/cache_hits', 0))} hits / "
            f"{int(compiles.get('compile/cache_misses', 0))} misses")
    seg = {k: v for k, v in counters.items() if k.startswith("seg/")}
    if seg:
        lines.append(f"  segment grower: "
                     f"{int(seg.get('seg/scanned_blocks', 0))} blocks "
                     f"scanned, {int(seg.get('seg/compactions', 0))} "
                     f"compactions")
    network = stats.get("network") or {}
    if network:
        parts = [f"{k}={v.get('calls', 0)}x/"
                 f"{_fmt_bytes(v.get('bytes', 0))}/"
                 f"{v.get('seconds', 0.0):.3f}s"
                 for k, v in sorted(network.items())]
        lines.append("  network: " + " ".join(parts))

    gauges = stats.get("gauges") or {}
    if gauges:
        parts = [f"{k}={v:g}" for k, v in sorted(gauges.items())]
        lines.append("  gauges: " + " ".join(parts))

    timeline = stats.get("timeline") or []
    if timeline:
        iters = sum(e.get("count", 1) for e in timeline)
        span = (timeline[-1].get("t", 0.0)
                - (timeline[0].get("t", 0.0) if len(timeline) > 1 else 0.0))
        lines.append(f"  timeline: {iters} iterations in "
                     f"{len(timeline)} marks over {span:.3f}s")

    spans = stats.get("spans") or {}
    if spans.get("recorded"):
        lines.append(f"  spans: {spans['recorded']} recorded, "
                     f"{spans.get('dropped', 0)} dropped "
                     f"(capacity {spans.get('capacity')})")

    lines.extend(_memory_lines(stats))
    lines.extend(_cost_lines(stats))
    lines.extend(_utilization_lines(stats))
    lines.extend(_timing_lines(stats))
    lines.extend(_fault_lines(stats))
    lines.extend(_health_lines(stats))
    lines.extend(_fleet_lines(stats))
    lines.extend(_drift_lines(stats))
    return "\n".join(lines)


def _memory_lines(stats: dict, top: int = 4) -> list:
    mem = stats.get("memory")
    if not mem:
        return ["  memory: n/a (backend reports no memory stats, "
                "or v1 blob)"]
    peak = mem.get("peak_bytes_in_use", 0)
    line = (f"  memory: peak {_fmt_bytes(peak)}, now "
            f"{_fmt_bytes(mem.get('bytes_in_use', 0))}, largest alloc "
            f"{_fmt_bytes(mem.get('largest_alloc', 0))}")
    limit = mem.get("bytes_limit")
    if limit:
        line += (f", limit {_fmt_bytes(limit)} "
                 f"({100.0 * peak / limit:.1f}% peak)")
    out = [line]
    phases = mem.get("phases") or {}
    if phases:
        ranked = sorted(phases.items(),
                        key=lambda kv: -kv[1].get("bytes_in_use_max",
                                                  0))[:top]
        parts = [f"{name}<={_fmt_bytes(p.get('bytes_in_use_max', 0))}"
                 f"/{p.get('samples', 0)}" for name, p in ranked]
        out.append("  memory by phase (max in-use/samples): "
                   + " ".join(parts))
    sampler = mem.get("sampler")
    if sampler:
        out.append(f"  memory sampler: {sampler.get('samples', 0)} samples "
                   f"@ {sampler.get('interval_ms', 0):g}ms")
    return out


def _cost_lines(stats: dict, top: int = 6) -> list:
    cost = stats.get("cost")
    if not cost:
        return ["  cost: n/a (no compiled-seam cost analysis in blob)"]
    labels = cost.get("labels") or {}
    ranked = sorted(labels.items(),
                    key=lambda kv: -kv[1].get("flops_total", 0.0))[:top]
    out = [f"  cost ({len(labels)} seams, "
           f"{cost.get('window_seconds', 0.0):.3f}s window): "
           f"{_fmt_rate(cost.get('flops_total', 0.0), 'FLOP')} total, "
           f"{_fmt_bytes(cost.get('bytes_total', 0.0))} accessed"]
    for name, e in ranked:
        out.append(
            f"    {name}: {e.get('calls', 0)} calls x "
            f"{_fmt_rate(e.get('flops', 0.0), 'FLOP')}/"
            f"{_fmt_bytes(e.get('bytes_accessed', 0.0))} "
            f"= {_fmt_rate(e.get('flops_total', 0.0), 'FLOP')} "
            f"({e.get('compiles', 0)} compiles)")
    return out


def _fault_lines(stats: dict, top: int = 8) -> list:
    faults = stats.get("faults")
    if not faults:
        return ["  faults: n/a (no injections or recoveries this run)"]
    counts = faults.get("counts") or {}
    parts = [f"{k}={int(v)}" for k, v in sorted(counts.items())]
    out = ["  faults: " + (" ".join(parts) if parts else "(events only)")]
    for ev in (faults.get("events") or [])[-top:]:
        desc = ev.get("kind", "?")
        if ev.get("site"):
            desc += f" @ {ev['site']}"
        if ev.get("iter") is not None:
            desc += f" iter {ev['iter']}"
        if ev.get("detail"):
            desc += f" ({ev['detail']})"
        out.append(f"    t={ev.get('t', 0.0):.3f}s {desc}")
    return out


def _health_lines(stats: dict) -> list:
    health = stats.get("health")
    if not health:
        return ["  health: n/a (no health_out stream this run, "
                "or pre-v3 blob)"]
    by_kind = health.get("by_kind") or {}
    parts = [f"{k}={int(v)}" for k, v in sorted(by_kind.items())]
    line = (f"  health: {int(health.get('records', 0))} records -> "
            f"{health.get('path', '?')}"
            + (f" [{' '.join(parts)}]" if parts else ""))
    last = health.get("last_iter")
    if isinstance(last, dict) and last.get("iter") is not None:
        line += f", last iter {int(last['iter'])}"
        if last.get("chunk"):
            line += f" (chunk={int(last['chunk'])})"
    nonfinite = health.get("nonfinite_total")
    out = [line]
    if nonfinite:
        out.append(f"  health ALERT: {int(nonfinite)} non-finite "
                   f"gradient/hessian values recorded")
    return out


def _fleet_lines(stats: dict) -> list:
    fleet = stats.get("fleet")
    if not fleet:
        return ["  fleet: n/a (single-host run, fleet_obs_sync_iters=0,"
                " or pre-v6 blob)"]
    out = [f"  fleet: {int(fleet.get('windows', 0))} attributed "
           f"window(s), sync every "
           f"{fleet.get('sync_iters', '?')} iteration(s)"]
    per_rank = fleet.get("per_rank") or {}
    for rank, slot in sorted(per_rank.items(),
                             key=lambda kv: str(kv[0])):
        frac = slot.get("wait_fraction")
        out.append(
            f"    rank{rank}: wait {slot.get('wait_s', 0.0):.3f}s / "
            f"work {slot.get('work_s', 0.0):.3f}s over "
            f"{int(slot.get('calls', 0))} collective call(s)"
            + (f" ({frac:.0%} waiting)"
               if isinstance(frac, (int, float)) else ""))
    hist = fleet.get("straggler_hist") or {}
    if hist:
        worst = max(hist, key=hist.get)
        out.append("    stragglers: "
                   + " ".join(f"rank{r}={n}x"
                              for r, n in sorted(hist.items()))
                   + f" — rank{worst} slowest most often")
    return out


def _drift_lines(stats: dict) -> list:
    drift = stats.get("drift")
    if not drift:
        return ["  drift: n/a (drift_detect off, no synced window,"
                " or pre-v7 blob)"]
    models = drift.get("models") or {}
    out = [f"  drift: {len(models)} model(s) vs training baseline,"
           f" psi threshold {drift.get('psi_threshold', '?')}"]
    for mid, rec in sorted(models.items()):
        js = rec.get("score_js")
        top = " ".join(f"{e.get('feature', '?')}={e.get('psi', 0):.3f}"
                       for e in (rec.get("top") or [])[:3])
        out.append(
            f"    {mid}: psi_max={rec.get('psi_max', 0):.3f}"
            + (f" score_js={js:.3f}" if isinstance(js, (int, float))
               else "")
            + f" over {rec.get('rows', '?')} row(s)"
            + (f"  [{top}]" if top else "")
            + ("  !! DRIFT" if rec.get("drifted") else ""))
    return out


def _utilization_lines(stats: dict) -> list:
    cost = stats.get("cost") or {}
    fps = cost.get("est_flops_per_s")
    bps = cost.get("est_bytes_per_s")
    if fps is None and bps is None:
        return []
    parts = []
    if fps is not None:
        parts.append(f"est {_fmt_rate(fps, 'FLOP/s')}")
    if bps is not None:
        parts.append(f"est {_fmt_rate(bps, 'B/s')} accessed")
    mem = stats.get("memory") or {}
    limit = mem.get("bytes_limit")
    if limit:
        parts.append(f"peak HBM {100.0 * mem.get('peak_bytes_in_use', 0) / limit:.1f}% of {_fmt_bytes(limit)}")
    return ["  utilization: " + ", ".join(parts)
            + "  (static XLA estimates over the wall window; an upper "
            "bound on achieved rates)"]


def _timing_lines(stats: dict, top: int = 6) -> list:
    timing = stats.get("timing")
    if not timing or not timing.get("enabled"):
        out = ["  timing: n/a (device_timing off, or pre-v4 blob)"]
        prof = (timing or {}).get("profile")
        if prof:
            out.append(_profile_line(prof))
        return out
    labels = timing.get("labels") or {}
    ranked = sorted(labels.items(),
                    key=lambda kv: -kv[1].get("total_s", 0.0))[:top]
    out = [f"  timing (measured wall-to-ready, {len(labels)} seams): "
           f"{timing.get('total_s', 0.0):.3f}s device-synced"]
    for name, e in ranked:
        line = (f"    {name}: {e.get('count', 0)} x "
                f"{e.get('mean_s', 0.0) * 1e3:.3f}ms mean "
                f"(p50 {e.get('p50_s', 0.0) * 1e3:.3f} / "
                f"p99 {e.get('p99_s', 0.0) * 1e3:.3f} / "
                f"max {e.get('max_s', 0.0) * 1e3:.3f}ms)")
        if e.get("gap_mean_s") is not None:
            line += f", gap {e['gap_mean_s'] * 1e3:.3f}ms mean"
        out.append(line)
    # measured vs estimated: static XLA FLOPs over the MEASURED seconds
    # next to the wall-window estimate — the gap is dispatch overhead +
    # how far the estimate's upper bound sits from achieved rates
    mfps = timing.get("measured_flops_per_s")
    efps = (stats.get("cost") or {}).get("est_flops_per_s")
    if mfps is not None:
        line = f"  utilization (measured): {_fmt_rate(mfps, 'FLOP/s')}"
        mbps = timing.get("measured_bytes_per_s")
        if mbps is not None:
            line += f", {_fmt_rate(mbps, 'B/s')} accessed"
        if efps:
            line += (f"  [{100.0 * mfps / efps:.1f}% of the "
                     "wall-window estimate]")
        out.append(line)
    prof = timing.get("profile")
    if prof:
        out.append(_profile_line(prof))
    return out


def _profile_line(prof: dict) -> str:
    line = f"  profile: {prof.get('kind', '?')} -> {prof.get('dir', '?')}"
    window = prof.get("window")
    if window:
        line += f" (iterations [{window[0]}, {window[1]})"
        req = prof.get("requested")
        if req and list(req) != list(window):
            line += f", requested [{req[0]}, {req[1]})"
        line += ")"
    return line


# ------------------------------------------------------------------ diff
def _phase_map(stats: dict) -> dict:
    return {k: v.get("seconds", 0.0)
            for k, v in (stats.get("phases") or {}).items()}


def _mem_scalars(stats: dict) -> dict:
    mem = stats.get("memory") or {}
    return {k: mem[k] for k in ("peak_bytes_in_use", "bytes_in_use",
                                "largest_alloc") if k in mem}


def _cost_scalars(stats: dict) -> dict:
    cost = stats.get("cost") or {}
    out = {k: cost[k] for k in ("flops_total", "bytes_total",
                                "est_flops_per_s") if k in cost}
    for name, e in (cost.get("labels") or {}).items():
        out[f"{name}.calls"] = e.get("calls", 0)
        out[f"{name}.flops_total"] = e.get("flops_total", 0.0)
    return out


def _timing_scalars(stats: dict) -> dict:
    timing = stats.get("timing") or {}
    out = {}
    if timing.get("total_s") is not None:
        out["total_s"] = timing["total_s"]
    for k in ("measured_flops_per_s", "measured_bytes_per_s"):
        if timing.get(k) is not None:
            out[k] = timing[k]
    for name, e in (timing.get("labels") or {}).items():
        out[f"{name}.mean_s"] = e.get("mean_s", 0.0)
        out[f"{name}.p99_s"] = e.get("p99_s", 0.0)
    return out


def _drift_scalars(stats: dict) -> dict:
    out = {}
    for mid, rec in ((stats.get("drift") or {}).get("models")
                     or {}).items():
        out[f"{mid}.psi_max"] = rec.get("psi_max", 0.0)
        if rec.get("score_js") is not None:
            out[f"{mid}.score_js"] = rec["score_js"]
        out[f"{mid}.rows"] = float(rec.get("rows", 0))
    return out


def _diff_section(title: str, a: dict, b: dict, fmt) -> list:
    keys = sorted(set(a) | set(b))
    if not keys:
        return [f"  {title}: n/a"]
    out = [f"  {title}:"]
    for k in keys:
        va, vb = a.get(k), b.get(k)
        if va is None:
            out.append(f"    {k}: n/a -> {fmt(vb)}")
        elif vb is None:
            out.append(f"    {k}: {fmt(va)} -> n/a")
        else:
            delta = vb - va
            if not delta and va == vb:
                continue
            pct = f" ({100.0 * delta / va:+.1f}%)" if va else ""
            out.append(f"    {k}: {fmt(va)} -> {fmt(vb)} "
                       f"[{'+' if delta >= 0 else ''}{fmt(delta)}{pct}]")
    if len(out) == 1:
        out.append("    (no change)")
    return out


def diff(a: dict, b: dict) -> str:
    """Human-readable deltas between two metrics blobs (a -> b)."""
    lines = [f"metrics diff [v{a.get('version', '?')} -> "
             f"v{b.get('version', '?')}]"]
    sec = lambda v: f"{v:.3f}s"
    num = lambda v: f"{v:g}"
    lines.extend(_diff_section("phases (seconds)", _phase_map(a),
                               _phase_map(b), sec))
    ca = {k: float(v) for k, v in (a.get("counters") or {}).items()}
    cb = {k: float(v) for k, v in (b.get("counters") or {}).items()}
    lines.extend(_diff_section("counters", ca, cb, num))
    lines.extend(_diff_section("memory (bytes)", _mem_scalars(a),
                               _mem_scalars(b), _fmt_bytes))
    lines.extend(_diff_section("cost", _cost_scalars(a),
                               _cost_scalars(b), num))
    lines.extend(_diff_section("timing (measured)", _timing_scalars(a),
                               _timing_scalars(b), num))
    lines.extend(_diff_section("drift", _drift_scalars(a),
                               _drift_scalars(b), num))
    return "\n".join(lines)


def _load(path: str) -> dict:
    with open(path) as fh:
        blob = json.load(fh)
    # accept a bench record wrapping the blob under "metrics"
    if "phases" not in blob and isinstance(blob.get("metrics"), dict):
        blob = blob["metrics"]
    return blob


def main(argv) -> int:
    if len(argv) == 3 and argv[0] == "--diff":
        print(diff(_load(argv[1]), _load(argv[2])))
        return 0
    if len(argv) != 1 or argv[0].startswith("--"):
        print(__doc__)
        return 2
    print(summarize(_load(argv[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
