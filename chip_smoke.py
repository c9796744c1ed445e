"""Chip smoke: the main path, once, on the TPU, through the entry points a
user calls — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             one chip: train 40 rounds at the HIGGS
                                     shape, kernel self-checks, predict,
                                     save/load, serve
    python chip_smoke.py --chips 4   the path across chips and what it is
                                     compared with, and no other phase:
                                     tree_learner=data on the four-device
                                     mesh against the serial learner
    python chip_smoke.py --rehearse  the same control flow at a tiny size on
                                     whatever backend JAX finds (CPU: Pallas
                                     in interpret mode); never "ok": true

One process holds the chip from start to end.  The last line of stdout is
one JSON object, {"ok": ..., "device": {...}}; a run that found no TPU
prints no such line (or, with --rehearse, "ok": false) and exits non-zero.
Any phase that raises ends the run with "ok": false and a non-zero exit.

Data is synthetic, made from --seed at the HIGGS shape (10.5M x 28 float32,
the reference GPU benchmark's dataset, docs/GPU-Performance.rst) with the
reference's benchmark parameters and every tpu_* parameter at its default.
"""

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

N_FEATURES = 28
# docs/GPU-Performance.rst:110-127, plus held-out AUC
PARAMS = {"objective": "binary", "max_bin": 63, "num_leaves": 255,
          "learning_rate": 0.1, "min_sum_hessian_in_leaf": 100,
          "metric": "auc", "verbose": -1}
ROUNDS = 40            # chunk 16 + chunk 16 + tail 8
CHUNK = 16             # the auto tpu_boost_chunk on TPU
MESH_ROUNDS = 8
SERVE_SIZES = (1, 2, 7, 16, 33, 64, 100, 128, 255, 256, 300, 512)


def say(msg: str) -> None:
    print(msg, flush=True)


def make_data(rng, n):
    """The HIGGS-proxy generator bench.py uses (same features -> label
    rule), drawn in bulk as float32."""
    import numpy as np
    X = rng.standard_normal((n, N_FEATURES), dtype=np.float32)
    logit = (2.0 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
             + 0.5 * np.sin(3 * X[:, 4]))
    noise = rng.standard_normal(n, dtype=np.float32)
    return X, (logit + noise * 0.5 > 0).astype(np.float64)


def auc(score, y) -> float:
    """Tie-corrected (midrank) AUC."""
    import numpy as np
    uniq, inv, cnt = np.unique(score, return_inverse=True,
                               return_counts=True)
    mid = np.cumsum(cnt) - (cnt - 1) / 2.0
    ranks = mid[inv]
    n_pos = float(y.sum())
    n_neg = len(y) - n_pos
    return float((ranks[y > 0.5].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


class Run:
    """What every phase shares: the backend found, the sizes, the seed."""

    def __init__(self, args, devices):
        self.on_tpu = devices[0].platform == "tpu"
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
        self.devices = devices
        self.seed = args.seed
        tiny = args.rehearse
        self.n_train = 20_000 if tiny else 10_500_000
        self.n_valid = 4_000 if tiny else 500_000
        self.n_predict = 2_000 if tiny else 100_000

    def params(self, **rehearsal_only) -> dict:
        """The reference parameters.  Off the TPU `auto` resolves to the
        CPU's choices (XLA grower, chunk 1, host predict); the rehearsal
        names the TPU's so that it walks the same code, with small trees.
        On the TPU nothing is overridden."""
        if self.on_tpu:
            return dict(PARAMS)
        return dict(PARAMS, num_leaves=15, tpu_histogram_backend="pallas",
                    **rehearsal_only)

    def require(self, what: str, ok: bool) -> None:
        """A check of what actually ran.  Off the TPU (rehearsal) the
        device-only ones cannot hold; they are printed, not enforced."""
        if ok:
            say(f"  check ok: {what}")
        elif self.on_tpu:
            raise AssertionError(f"check failed: {what}")
        else:
            say(f"  check NOT MET (rehearsal, not enforced): {what}")


def phase_environment(run: Run) -> None:
    import importlib.metadata as md

    import jax
    import jaxlib

    from lightgbm_tpu.core import native
    from lightgbm_tpu.utils import enable_jax_compilation_cache
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device: platform={run.device['platform']} "
        f"kind={run.device['kind']} count={run.device['count']}")
    say(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    t0 = time.perf_counter()
    built = native.lib() is not None and native.text_lib() is not None
    say(f"native: {'built library loaded' if built else 'PYTHON FALLBACK'} "
        f"({time.perf_counter() - t0:.1f}s to build or load)")
    cache_dir = enable_jax_compilation_cache()
    entries = len(os.listdir(cache_dir))
    say(f"compile cache: {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f", {entries} entries at start)")
    TELEMETRY.install_jax_listeners()


def stamper():
    """A train callback that notes when each iteration's evaluation row
    is replayed, the device drained first."""
    import jax
    stamps = []

    def stamp(env):
        jax.block_until_ready(env.model.gbdt.train_score)
        stamps.append(time.perf_counter())

    return stamps, stamp


def compile_counters() -> dict:
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    c = TELEMETRY.stats()["counters"]
    return {k: c.get(f"compile/{k}", 0)
            for k in ("backend_compiles", "backend_compile_seconds",
                      "cache_hits", "cache_misses")}


def phase_train(run: Run):
    """lgb.Dataset + lgb.train at the HIGGS shape, valid set attached:
    two whole chunks of 16 and a tail of 8, evaluated in the scan."""
    import jax
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import pallas_histogram as ph
    from lightgbm_tpu.ops.pallas_score import scorer_available

    rng = np.random.default_rng(run.seed)
    t0 = time.perf_counter()
    X, y = make_data(rng, run.n_train)
    Xv, yv = make_data(rng, run.n_valid)
    t_gen = time.perf_counter() - t0

    t0 = time.perf_counter()
    train = lgb.Dataset(X, y)
    valid = train.create_valid(Xv, yv)
    train.construct()
    valid.construct()
    t_bin = time.perf_counter() - t0
    say(f"data: {run.n_train} x {N_FEATURES} float32 train + {run.n_valid} "
        f"held out, seed {run.seed}: generated in {t_gen:.1f}s, binned in "
        f"{t_bin:.1f}s")

    # in-scan evaluation replays a chunk's 16 rows after the chunk: the gap
    # before each chunk's first row is that chunk's wall time
    stamps, stamp = stamper()
    evals = {}
    before = compile_counters()
    t_start = time.perf_counter()
    bst = lgb.train(run.params(tpu_boost_chunk=CHUNK, predict_device="on"),
                    train, num_boost_round=ROUNDS,
                    valid_sets=[valid], evals_result=evals,
                    verbose_eval=False, callbacks=[stamp])
    jax.block_until_ready(bst.gbdt.train_score)
    t_train = time.perf_counter() - t_start
    after = compile_counters()

    g = bst.gbdt
    say(f"grower: use_segment={g._use_segment} "
        f"hist_backend={g.grower_params.hist_backend} "
        f"block_rows={g.grower_params.row_chunk} "
        f"chunk={g.boost_chunk_size()} "
        f"rounds_trained={bst.current_iteration()}")
    gates = {"fused_route_available": ph.fused_route_available(),
             "route_kernel_available": ph.route_kernel_available(),
             "scorer_available": scorer_available(),
             "fused_route_decisions": dict(ph.fused_route_decisions)}
    say(f"kernel gates: {json.dumps(gates)}")
    run.require("segment grower on the pallas backend",
                bool(g._use_segment)
                and g.grower_params.hist_backend == "pallas")
    run.require(f"resolved chunk size {CHUNK}",
                g.boost_chunk_size() == CHUNK)
    run.require(f"{ROUNDS} rounds trained, one eval row each",
                bst.current_iteration() == ROUNDS
                and len(stamps) == ROUNDS
                and len(evals["valid_0"]["auc"]) == ROUNDS)
    run.require("every default-on kernel gate chose its kernel",
                gates["fused_route_available"]
                and gates["route_kernel_available"]
                and gates["scorer_available"]
                and gates["fused_route_decisions"].get("segment") is True)

    first = stamps[0] - t_start
    second = stamps[CHUNK] - stamps[CHUNK - 1]
    tail = stamps[2 * CHUNK] - stamps[2 * CHUNK - 1]
    compiles = {k: after[k] - before[k] for k in after}
    say(f"train: {t_train:.1f}s wall; first chunk of {CHUNK} (compile "
        f"included) {first:.1f}s; second chunk {second:.3f}s = "
        f"{second / CHUNK:.4f} s/iter; tail of {ROUNDS - 2 * CHUNK} "
        f"(its own compile included) {tail:.1f}s")
    say(f"compile: {compiles['backend_compile_seconds']:.1f}s in "
        f"{compiles['backend_compiles']} backend compiles; persistent "
        f"cache hits={compiles['cache_hits']} "
        f"misses={compiles['cache_misses']}")
    stats = run.devices[0].memory_stats() or {}
    say(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")

    auc_scan = float(evals["valid_0"]["auc"][-1])
    auc_host = auc(bst.predict(Xv), yv)
    say(f"held-out AUC after {ROUNDS} rounds: {auc_scan:.6f} in the scan, "
        f"{auc_host:.6f} from predict() on the {run.n_valid} held-out rows")
    run.require("held-out AUC finite, above 0.9, scan and predict() agree",
                np.isfinite(auc_scan) and auc_scan > 0.9
                and abs(auc_scan - auc_host) < 1e-3)
    return bst, Xv[:run.n_predict]


def phase_self_checks(run: Run) -> None:
    """The default path's kernel self-checks, non-interpret on the chip."""
    from lightgbm_tpu.ops.pallas_histogram import kernel_self_checks
    results = kernel_self_checks()
    for name, err in results.items():
        say(f"kernel self-check: {'ok' if err is None else 'FAIL'} {name}"
            + ("" if err is None else f" ({err})"))
    bad = [n for n, err in results.items() if err is not None]
    if bad:
        raise AssertionError(f"default-path kernel self-checks failed: "
                             f"{bad}")


def phase_predict(run: Run, bst, Xq) -> None:
    """Device route vs host walk, and the saved model read back."""
    import numpy as np

    import lightgbm_tpu as lgb
    run.require("predict takes the device route",
                bst.gbdt._device_route_ok())
    t0 = time.perf_counter()
    on_device = bst.predict(Xq)
    t_dev = time.perf_counter() - t0
    routed = bst.config.predict_device
    bst.config.predict_device = "off"
    try:
        t0 = time.perf_counter()
        on_host = bst.predict(Xq)
        t_host = time.perf_counter() - t0
    finally:
        bst.config.predict_device = routed
    say(f"predict {len(Xq)} rows: device route {t_dev:.2f}s (compile "
        f"included), host walk {t_host:.2f}s, maxdiff "
        f"{float(np.abs(on_device - on_host).max())}")
    if not np.array_equal(on_device, on_host):
        raise AssertionError("device-route predict differs from the host "
                             "walk (the parity tests require bit equality)")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.txt")
        bst.save_model(path)
        size = os.path.getsize(path)
        reloaded = lgb.Booster(model_file=path).predict(Xq)
    diff = float(np.abs(reloaded - on_host).max())
    say(f"save_model -> Booster(model_file) -> predict: {size} bytes, "
        f"maxdiff {diff}")
    if diff != 0.0:
        raise AssertionError(f"reloaded model predicts differently: {diff}")


def phase_serve(run: Run, bst, Xq) -> None:
    import numpy as np
    rng = np.random.default_rng(run.seed + 1)
    lat = []
    with bst.serve(model_id="higgs") as handle:
        for rep in range(3):
            for n in SERVE_SIZES:
                rows = Xq[rng.integers(0, len(Xq), size=n)]
                t0 = time.perf_counter()
                reply = handle.predict(rows)
                lat.append(time.perf_counter() - t0)
                if not np.array_equal(reply, bst.predict(rows)):
                    raise AssertionError(
                        f"serve reply for {n} rows differs from "
                        f"Booster.predict")
    warm = sorted(lat[len(SERVE_SIZES):])
    say(f"serve: {len(lat)} requests of {SERVE_SIZES[0]}..{SERVE_SIZES[-1]} "
        f"rows, every reply bit-identical to Booster.predict; median "
        f"latency after the first pass {warm[len(warm) // 2] * 1e3:.2f} ms")


def run_one_chip(run: Run) -> None:
    bst, Xq = phase_train(run)
    phase_self_checks(run)
    phase_predict(run, bst, Xq)
    phase_serve(run, bst, Xq)


def run_mesh(run: Run) -> None:
    """tree_learner=data over the device mesh against the serial learner,
    same data, same rounds, same process."""
    import numpy as np

    import lightgbm_tpu as lgb

    D = len(run.devices)
    rng = np.random.default_rng(run.seed)
    X, y = make_data(rng, run.n_train)
    Xq = X[:run.n_predict]
    say(f"data: {run.n_train} x {N_FEATURES} float32, seed {run.seed}")

    def train(tree_learner):
        stamps, stamp = stamper()
        t0 = time.perf_counter()
        # a callback holds both learners to one dispatch per iteration,
        # which is all the mesh learner has
        bst = lgb.train(dict(run.params(), tree_learner=tree_learner),
                        lgb.Dataset(X, y), num_boost_round=MESH_ROUNDS,
                        verbose_eval=False, callbacks=[stamp])
        first = stamps[0] - t0
        per_iter = (stamps[-1] - stamps[1]) / (len(stamps) - 2)
        return bst, first, per_iter

    mesh_bst, first, per_iter = train("data")
    g = mesh_bst.gbdt
    say(f"mesh grower: tree_learner=data over {D} devices -> "
        f"use_segment={g._use_segment} "
        f"hist_backend={g.grower_params.hist_backend} "
        f"collective_kind={getattr(g._grow_fn, '_collective_kind', None)} "
        f"block_rows={g.grower_params.row_chunk}")
    say(f"mesh train: first iteration (compile included) {first:.1f}s, then "
        f"{per_iter:.4f} s/iter over {MESH_ROUNDS - 2} iterations")
    run.require("the mesh run used the Pallas segment grower under "
                "shard_map", bool(g._use_segment)
                and g.grower_params.hist_backend == "pallas"
                and getattr(g, "_mesh", None) is not None
                and g._mesh.devices.size == D)

    for name, arr, row_axis in (("bins", g.bins, 1),
                                ("train_score", g.train_score, 1),
                                ("bag_weight", g.bag_weight, 0)):
        shards = arr.addressable_shards
        rows = [s.data.shape[row_axis] for s in shards]
        say(f"sharding: {name} {tuple(arr.shape)} -> rows per device {rows} "
            f"on {sorted(s.device.id for s in shards)}")
        if (len(shards) != D
                or any(r != arr.shape[row_axis] // D for r in rows)):
            raise AssertionError(f"{name} is not sharded {D} ways by rows: "
                                 f"{arr.sharding}")

    texts = [c.as_text() for c in g._fused_fns[1].executables()]

    def ops(name):
        return sum(t.count(f" {name}(") + t.count(f" {name}-start(")
                   for t in texts)

    counts = {op: ops(op) for op in ("reduce-scatter", "all-gather",
                                     "all-reduce", "collective-permute")}
    counts["tpu_custom_call"] = sum(t.count("tpu_custom_call")
                                    for t in texts)
    # the compiler may lower a small psum_scatter to all-reduce +
    # dynamic-slice; the op it came from stays in the metadata
    counts["ops lowered from the grower's psum_scatter"] = sum(
        t.count("reduce_scatter") for t in texts)
    say(f"compiled step (grow/fused_step): {json.dumps(counts)}")
    if not (counts["all-gather"] and (
            counts["reduce-scatter"]
            or (counts["all-reduce"]
                and counts["ops lowered from the grower's psum_scatter"]))):
        raise AssertionError("the compiled grower holds no histogram "
                             "reduce-scatter and split all-gather")
    run.require("the Pallas kernels are in the compiled mesh step",
                counts["tpu_custom_call"] > 0)

    serial_bst, first_s, per_iter_s = train("serial")
    say(f"serial train on one device: first iteration (compile included) "
        f"{first_s:.1f}s, then {per_iter_s:.4f} s/iter")
    pm, ps = mesh_bst.predict(Xq), serial_bst.predict(Xq)
    leaves = [(a.num_leaves, b.num_leaves)
              for a, b in zip(mesh_bst.gbdt.models, serial_bst.gbdt.models)]
    say(f"parity on {len(Xq)} rows: max |mesh - serial| = "
        f"{float(np.abs(pm - ps).max()):.3g}; leaves per tree (mesh, "
        f"serial) {leaves}")
    # tests/test_parallel.py: identical split decisions up to float
    # reduction order
    np.testing.assert_allclose(pm, ps, rtol=1e-3, atol=1e-4)
    if any(a != b for a, b in leaves):
        raise AssertionError("mesh and serial trees differ in shape")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any backend; never prints ok: true")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        sys.stderr.write(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform} ({devices[0].device_kind})"
                         f"\n")
        return 2
    if len(devices) < args.chips:
        sys.stderr.write(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)\n")
        return 2
    import lightgbm_tpu  # noqa: F401 — fails here, before any result line

    run = Run(args, devices)
    try:
        phase_environment(run)
        if args.chips == 4:
            run_mesh(run)
        else:
            run_one_chip(run)
    except BaseException:
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": run.device}), flush=True)
        return 1
    print(json.dumps({"ok": run.on_tpu, "device": run.device}), flush=True)
    return 0 if run.on_tpu else 3


if __name__ == "__main__":
    sys.exit(main())
