"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up: rows from the seed, `lgb.Dataset` (host binning), one `lgb.train`
call whose first chunk compiles (or loads) the chunk program and warms up.
The measured window is the rest of that same call: k whole chunks, from the
stamp that ends the warm-up chunk to `lgb.train` having returned with every
tree in host memory.  Afterwards the model as serialised is held against
the plain reference (`reference.py`, `compare.py`), and with `--trace 1` a
slice of the profiler's trace is reduced to the per-layer metrics.

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and prints
no result.  `--rehearse` walks the same code at a tiny size on whatever
backend JAX finds, prints no metric and never exits 0.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from manifest import Manifest  # noqa: E402

SLICE_SECONDS = 6.0         # traced slice: one chunk boundary and what follows
FOLLOWED_STEPS = 3          # steps the reference follows
REHEARSAL = {"rows": 40_000, "num_leaves": 15, "min_sum_hessian_in_leaf": 5.0,
             "chunk": 4}


def say(msg: str) -> None:
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


class Spans:
    """The benchmark's own spans, on the host's clock: seconds by name."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t0)


def compile_counters() -> dict:
    """The program's compile counters (copy of chip_smoke.compile_counters)."""
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    c = TELEMETRY.stats()["counters"]
    return {k: c.get(f"compile/{k}", 0)
            for k in ("backend_compiles", "backend_compile_seconds",
                      "cache_hits", "cache_misses")}


def place_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or where
    JAX_COMPILATION_CACHE_DIR says."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    os.makedirs(placed, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


class Tracer:
    """Starts the profiler at the stamp that ends the warm-up chunk and stops
    it from a timer thread SLICE_SECONDS later."""

    def __init__(self, directory: str):
        self.directory = directory
        self.timer = None
        self.error = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench:slice_start"):
            pass
        self.timer = threading.Timer(SLICE_SECONDS, self._stop)
        self.timer.daemon = True
        self.timer.start()

    def _stop(self) -> None:
        import jax
        try:
            with jax.profiler.TraceAnnotation("bench:slice_stop"):
                pass
            jax.profiler.stop_trace()
        except Exception as e:      # read by the main thread in finish()
            self.error = e

    def finish(self) -> None:
        if self.timer is not None:
            self.timer.join()
        if self.error is not None:
            raise self.error


def train_cell(lgb, params, ds, valid, rounds, chunk, warm_chunks, tracer):
    """The one `lgb.train` call.  Returns the booster, the stamps at chunk
    boundaries, the window's start and end and the compile counters inside
    the window."""
    import jax
    stamps = []
    at_window = {}
    per_stamp = 1 if valid is None else chunk   # in-scan rows replay per iter
    calls = [0]

    def stamp(env):
        calls[0] += 1
        if calls[0] % per_stamp:
            return
        with jax.profiler.TraceAnnotation("bench:stamp"):
            jax.block_until_ready(env.model.gbdt.train_score)
        stamps.append(time.perf_counter())
        if len(stamps) == warm_chunks:
            at_window["counters"] = compile_counters()
            if tracer is not None:
                tracer.start()
                # the window starts where the tracer has started: its start
                # is not the program's time
                stamps[-1] = time.perf_counter()

    kwargs = {}
    if valid is not None:
        kwargs = {"valid_sets": [valid], "evals_result": {}}
    with jax.profiler.TraceAnnotation("bench:train"):
        bst = lgb.train(dict(params, tpu_boost_chunk=chunk), ds,
                        num_boost_round=rounds, verbose_eval=False,
                        callbacks=[stamp], **kwargs)
        with jax.profiler.TraceAnnotation("bench:fetch"):
            jax.block_until_ready(bst.gbdt.train_score)
            _ = bst.gbdt.models         # settles the pending tree fetch
    t_end = time.perf_counter()
    after = compile_counters()
    inside = {k: after[k] - at_window["counters"][k] for k in after}
    return bst, stamps, stamps[warm_chunks - 1], t_end, inside


def check_path(bst, chunk: int, auto_chunk: int, on_tpu: bool) -> None:
    """The checks chip_smoke.phase_train makes of what actually ran: the
    program's own defaults chose the segment grower on the Pallas backend,
    the chunk it trained with is the one `auto` resolves to, and every
    default kernel gate chose its kernel.  Off the TPU (rehearsal) the
    device-only ones are printed and not enforced."""
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.ops.pallas_score import scorer_available
    g = bst.gbdt
    checks = {
        "segment grower on the pallas backend":
            bool(g._use_segment) and g.grower_params.hist_backend == "pallas",
        f"trained with the chunk auto resolves to ({auto_chunk})":
            g.boost_chunk_size() == chunk == auto_chunk,
        "every default-on kernel gate chose its kernel":
            bool(ph.fused_route_available() and ph.route_kernel_available()
                 and scorer_available()
                 and ph.fused_route_decisions.get("segment") is True),
    }
    for what, ok in checks.items():
        if ok:
            say(f"check ok: {what}")
        elif on_tpu:
            raise SystemExit(f"benchmark: check failed: {what}")
        else:
            say(f"check NOT MET (rehearsal, not enforced): {what}")


def follow(X, y, params, trees, cand, *, tested=True, precision="float32",
           with_hist=True, sum_all=None):
    """The reference's readings under `trees`, one step each, and the facts
    of each tree as the program serialised it (the first carries the init
    score in its leaves).  `tested` also moves a second score vector by the
    serialised leaf values, whose loss the comparison reads.  With `sum_all`
    (every tree of the run) also every row's raw score under all of them,
    and the init score."""
    import compare
    import reference
    fol = reference.Follower(X, y, params, cand, int(params["num_leaves"]),
                             precision=precision, with_hist=with_hist)
    try:
        facts, readings = [], []
        for i, t in enumerate(trees):
            f = compare.facts_of_tree(t, fol.init_score if i == 0 else 0.0)
            readings.append(fol.step(t, f.leaf_value if tested else None))
            facts.append(f)
        summed = None if sum_all is None else fol.sum_forest(sum_all)
    finally:
        fol.close()
    return facts, readings, summed, fol.init_score


def follow_and_compare(X, y, params, trees, final_score, expected_trees):
    """The numbers compared, and what `tools/readings.py` needs to read the
    control and the faults against the same reference: the first steps under
    the program's trees, the scores the program ended with against the sum
    of ALL its serialised trees (warm-up and window), and what every tree of
    the run has to satisfy on its own."""
    import compare
    import reference
    n_bins = -(-(int(params["max_bin"]) + 1) // 8) * 8
    cand = reference.candidate_thresholds(trees, X.shape[1], n_bins)
    facts, readings, summed, init_score = follow(
        X, y, params, trees[:FOLLOWED_STEPS], cand, sum_all=trees)
    numbers = compare.first_steps(facts, readings)
    numbers["score_gap"] = compare.score_gap(final_score, summed, init_score)
    numbers.update(compare.forest_faults(
        trees, rows=len(y), num_leaves=int(params["num_leaves"]),
        min_data=float(params.get("min_data_in_leaf", 20)),
        min_hess=float(params.get("min_sum_hessian_in_leaf", 1e-3)),
        expected_trees=expected_trees))
    steps = [dict(compare.first_steps([f], [r]), **compare.step_details(f, r))
             for f, r in zip(facts, readings)]
    context = {"X": X, "y": y, "params": params, "trees": trees,
               "cand": cand, "facts": facts, "readings": readings,
               "summed": summed, "init_score": init_score}
    return numbers, steps, context


def run_cell(man: Manifest, name: str, seed: int, seconds: float, trace: int,
             rehearse: bool = False, *, measure: bool = True,
             keep_trace: bool = False, more_readings=None):
    """One run of cell `name` in this process.  Returns (exit code, result,
    extras); the result is None where the run has none to print.  `measure`
    off trains the warm-up chunk alone, and `more_readings(context)` reads
    the control and the planted faults against the same reference: both are
    for `tools/readings.py`, which sets limits, never for a benchmark run."""
    cell = man.cell(name)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    workload = man.workload(cell["name"])

    import jax
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not rehearse:
        say(f"benchmark: needs a TPU, JAX found {devices[0].platform} "
            f"({devices[0].device_kind}); no result is printed")
        return 2, None, {}
    if len(devices) < cell["chips"]:
        say(f"benchmark: cell {cell['name']} needs {cell['chips']} chip(s), "
            f"JAX found {len(devices)}")
        return 2, None, {}
    if cell["chips"] != 1:
        say("benchmark: this harness trains on one chip; a cell across chips "
            "brings its own entry")
        return 2, None, {}
    import numpy as np

    import lightgbm_tpu as lgb      # fails here in a bare directory
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    peaks = man.peaks(devices[0].device_kind) if on_tpu else None
    cache_dir = place_compile_cache()
    TELEMETRY.install_jax_listeners()
    spans = Spans()
    say(f"device: {devices[0].platform} {devices[0].device_kind} x"
        f"{len(devices)}; compile cache {cache_dir}")

    params = dict(config["params"])
    params.update(traffic.get("extra_params", {}))
    params.setdefault("verbose", -1)
    rows = int(config["data"]["rows"])
    valid_rows = int(traffic.get("valid_rows", 0))
    if rehearse:
        rows = REHEARSAL["rows"]
        valid_rows = min(valid_rows, rows // 10)
        params.update(num_leaves=REHEARSAL["num_leaves"],
                      min_sum_hessian_in_leaf=REHEARSAL[
                          "min_sum_hessian_in_leaf"],
                      tpu_histogram_backend="pallas")

    import datagen
    rng = np.random.default_rng(seed)
    with spans.span("gen_s"):
        X, y = datagen.make(config["data"], rows, rng)
        Xv = yv = None
        if valid_rows:
            Xv, yv = datagen.make(config["data"], valid_rows, rng)
    with spans.span("bin_s"):
        ds = lgb.Dataset(X, y, params=dict(params))
        ds.construct()
        valid = None
        if valid_rows:
            valid = ds.create_valid(Xv, yv)
            valid.construct()
    say(f"data: {rows} x {X.shape[1]} rows from seed {seed} in "
        f"{spans.seconds['gen_s']:.1f}s, binned in "
        f"{spans.seconds['bin_s']:.1f}s")

    # what `auto` resolves to, read from a booster with untouched parameters
    with spans.span("probe_s"):
        probe = lgb.Booster(params=dict(params), train_set=ds)
        auto_chunk = int(probe.gbdt.boost_chunk_size())
        del probe
        gc.collect()
    chunk = REHEARSAL["chunk"] if rehearse else auto_chunk
    if chunk < 2:
        say(f"benchmark: auto tpu_boost_chunk resolved to {auto_chunk}; the "
            f"cell times whole chunks of a chunked scan")
        return 1, None, {}
    warm_chunks = int(traffic.get("warmup_chunks", 1))
    k = max(1, int(seconds // float(workload["chunk_seconds"])))
    if rehearse:
        k = 1
    if not measure:
        k = 0
    rounds = chunk * (warm_chunks + k)
    say(f"train: chunk {chunk} (auto {auto_chunk}), {warm_chunks} warm-up + "
        f"{k} measured chunk(s) = {rounds} rounds")

    tracer = None
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    if trace:
        tracer = Tracer(trace_dir)
    before = compile_counters()
    bst, stamps, t0, t1, inside = train_cell(
        lgb, params, ds, valid, rounds, chunk, warm_chunks, tracer)
    setup_s = t0 - T_PROCESS
    window_s = t1 - t0
    iters = chunk * k
    at_end = compile_counters()
    compiles = {key: at_end[key] - before[key] for key in at_end}
    say(f"window: {window_s:.3f}s for {iters} iterations; set-up "
        f"{setup_s:.1f}s; chunks by the stamps "
        f"{[round(b - a, 3) for a, b in zip(stamps, stamps[1:])]}")
    say(f"compile: {compiles['backend_compile_seconds']:.1f}s in "
        f"{compiles['backend_compiles']} compiles, cache hits "
        f"{compiles['cache_hits']} misses {compiles['cache_misses']}; inside "
        f"the window: {inside}")
    if k and (inside["backend_compiles"] or inside["cache_misses"]):
        raise SystemExit("benchmark: something compiled inside the measured "
                         f"window: {inside}")
    if tracer is not None:
        tracer.finish()
    check_path(bst, chunk, auto_chunk, on_tpu)
    failed = rounds - int(bst.current_iteration())

    stats = devices[0].memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    say(f"memory: peak_bytes_in_use={peak_bytes} "
        f"bytes_limit={stats.get('bytes_limit')}")
    model_text = bst.model_to_string()
    final_score = np.asarray(bst.gbdt.train_score)[0, :rows]
    del bst, ds, valid
    gc.collect()

    import compare
    import reference
    t_ref = time.perf_counter()
    trees = reference.parse_model(model_text)
    numbers, steps, context = follow_and_compare(
        X, y, params, trees, final_score, expected_trees=rounds)
    extras = more_readings(context) if more_readings else {}
    correct, compared = compare.verdict(numbers, workload["limits"])
    correct = correct and failed == 0
    say(f"reference: {FOLLOWED_STEPS} steps followed and {len(trees)} trees "
        f"checked in {time.perf_counter() - t_ref:.1f}s")
    extras["numbers"] = numbers

    ctx = {"spans": spans.seconds, "compiles": compiles, "trees": trees,
           "window_trees": trees[chunk * warm_chunks:], "window_s": window_s,
           "iters": iters, "setup_s": setup_s, "peak_bytes": peak_bytes,
           "features": X.shape[1], "peaks": peaks, "trace": None,
           "config": config, "cell": cell}
    breakdown = None
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if trace:
        import xtrace
        t_red = time.perf_counter()
        try:
            ctx["trace"] = xtrace.reduce(xtrace.load(trace_dir))
        except ValueError as e:
            if on_tpu:
                raise
            say(f"rehearsal: {e}")
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = {"device_ops": ctx["trace"]["device_ops"],
                         "idle_gaps": ctx["trace"]["idle_gaps"]}
        say(f"trace: reduced in {time.perf_counter() - t_red:.1f}s")

    metrics = {}
    if k:
        group = "per_layer" if trace else "end_to_end"
        for m in man.metrics(group, cell["name"]):
            value = man.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for cname, value, limit in compared:
        say(f"compared: {cname} = {value!r} (limit {limit!r})")
    result = {"correct": bool(correct), "attempted": iters,
              "failed": max(failed, 0), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["steps"] = steps
    result["compared"] = {cname: {"value": value, "limit": limit}
                          for cname, value, limit in compared}
    if rehearse:
        result["metrics"] = {}
        result["rehearsal"] = True
        return 3, result, extras
    return 0, result, extras


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any backend; prints no metric and "
                         "never exits 0")
    args = ap.parse_args(argv)
    code, result, _ = run_cell(Manifest(), args.workload, args.seed,
                               args.seconds, args.trace, args.rehearse)
    if result is None:
        return code
    if args.rehearse:
        say("rehearsal: " + json.dumps(result))
        return code
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
