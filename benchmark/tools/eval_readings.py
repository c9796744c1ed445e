"""Readings that set the limit of the held-out evaluation, several seeds in
one process:

    python3 benchmark/tools/eval_readings.py --workload <cell> --seeds 1,2,3

For each seed: the cell's rows and held-out rows, binning and the warm-up
chunk as `run.train_cell` trains it (`valid_sets`, `evals_result`, the
chunk `auto` resolves to), keeping `evals_result`; then every iteration's
in-scan AUC against the plain float64 reference (`eval_reference.py`) on the
raw held-out rows: `auc_gap`, the widest gap over the chunk's iterations.

The control and the two planted faults read the same number with the
program's own evaluation replayed on the device over the trees that
training fetched (`replay`: a scan over the chunk's `TreeArrays` of
`gbdt._eval_walk` and the attached `DeviceEval` program, the two pieces the
chunk scan runs after it has grown a tree).  Replayed as it is, it gives the
training's AUCs again (`replay_gap`, 0 where the bits agree), so a variant
differs from the program by its planted change alone:

  control_bfloat16     the valid-score carry kept in bfloat16
  fault_skipped_tree   one tree (the chunk's middle one) never added
  fault_metric_late    the metric computed before the iteration's tree is
                       added: the scores of iteration t - 1

Each variant reads two numbers: `auc_gap` as above, and `least`, the
NARROWEST gap among the iterations from the planted change on, which is
what a limit has to stay under to catch the change at whichever iteration
it shows.

One JSON line a seed on standard output and in
`chiprun_out/eval_readings_<cell>.jsonl`.  It needs the chip like a run
does (`--rehearse`: a tiny size on any backend); it is never part of a
benchmark run.
"""

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import eval_reference  # noqa: E402

VARIANTS = ("sound", "control_bfloat16", "fault_skipped_tree",
            "fault_metric_late")


@contextlib.contextmanager
def recorded():
    """What `replay` needs of a training made inside: the valid-score carry
    as it is uploaded before the first chunk, and every chunk's trees as the
    host fetched them."""
    import numpy as np

    from lightgbm_tpu.models import gbdt as gm
    rec = {"carry": None, "trees": []}
    dispatch, fetch = gm.GBDT._dispatch_chunk, gm.fetch_tree_chunk

    def spy_dispatch(self, t):
        if rec["carry"] is None:
            rec["carry"] = [np.asarray(vs, dtype=np.float32)
                            for vs in self.valid_scores]
        return dispatch(self, t)

    def spy_fetch(ints_all, floats_all, leaves):
        chunk = fetch(ints_all, floats_all, leaves)
        rec["trees"].extend(chunk)
        return chunk

    gm.GBDT._dispatch_chunk, gm.fetch_tree_chunk = spy_dispatch, spy_fetch
    try:
        yield rec
    finally:
        gm.GBDT._dispatch_chunk, gm.fetch_tree_chunk = dispatch, fetch


def skipped_tree(n_iter: int) -> int:
    """The iteration whose tree `fault_skipped_tree` leaves out."""
    return n_iter // 2


def replay(gbdt, rec, variant="sound"):
    """The in-scan evaluation of `gbdt` (a trained booster's, its
    `DeviceEval` program still attached) over the recorded trees, from the
    recorded carry: the `[T, n_cols]` metric matrix under `variant`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.models import gbdt as gm
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    inscan = gbdt._inscan
    gp = gbdt.grower_params
    depth_bound = (gp.max_depth + 1) if gp.max_depth > 0 else gp.num_leaves
    trees = rec["trees"]
    n_iter, n_class = len(trees), len(trees[0])
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.asarray(np.stack(leaves).reshape(
            (n_iter, n_class) + np.shape(leaves[0]))),
        *[arrays for per_class in trees for arrays in per_class])
    carried = jnp.bfloat16 if variant == "control_bfloat16" else jnp.float32
    skipped = skipped_tree(n_iter) if variant == "fault_skipped_tree" else -1
    shrinkage = jnp.float32(gbdt.shrinkage_rate)

    def body(vscores, step):
        t, per_class = step
        before = vscores
        up = [vs.astype(jnp.float32) for vs in vscores]
        for k in range(n_class):
            arrays = jax.tree_util.tree_map(lambda a, k=k: a[k], per_class)
            up, _ = gm._eval_walk(up, inscan.vbins, arrays, gbdt.fmeta, k,
                                  shrinkage, depth_bound)
        after = [jnp.where(t == skipped, vs, new.astype(carried))
                 for vs, new in zip(vscores, up)]
        read = before if variant == "fault_metric_late" else after
        mvals = inscan.eval_fn(gbdt.train_score,
                               [vs.astype(jnp.float32) for vs in read],
                               inscan.arrays)
        return after, mvals

    @jax.jit
    def run(carry, stacked):
        return jax.lax.scan(body, carry,
                            (jnp.arange(n_iter, dtype=jnp.int32), stacked))[1]

    carry = [jnp.asarray(c).astype(carried) for c in rec["carry"]]
    return np.asarray(run(carry, stacked))


def train_recorded(lgb, params, ds, valid, rounds, chunk):
    """`run.train_cell`'s call with `evals_result` kept: the booster, the
    per-iteration metric lists and what `replay` needs."""
    evals = {}
    with recorded() as rec:
        bst = lgb.train(dict(params, tpu_boost_chunk=chunk), ds,
                        num_boost_round=rounds, verbose_eval=False,
                        valid_sets=[valid], evals_result=evals)
        _ = bst.gbdt.models
    return bst, evals, rec


def gaps(gbdt, model_text, evals, rec, Xv, yv, metric="auc"):
    """`auc_gap` of the program and of every variant of the replay against
    the plain reference, and `replay_gap`: the replay as it is against the
    training's own values.  `gbdt` is the trained booster's (or anything
    with its `_inscan`, `grower_params`, `fmeta`, `shrinkage_rate` and
    `train_score`), `model_text` the model as it was serialised."""
    import numpy as np
    (by_metric,) = evals.values()
    program = [float(v) for v in by_metric[metric]]
    column = [m for _s, m, _h in gbdt._inscan.columns].index(metric)
    reference = eval_reference.auc_by_iteration(model_text, Xv, yv)
    out = {"auc_program": program, "auc_reference": reference,
           "program": eval_reference.auc_gap(program, reference)}
    for variant in VARIANTS:
        values = replay(gbdt, rec, variant)[:, column]
        if variant == "sound":
            out["replay_gap"] = float(np.max(np.abs(
                values.astype(np.float64) - np.asarray(program))))
        else:
            first = (skipped_tree(len(reference))
                     if variant == "fault_skipped_tree" else 0)
            out[variant] = {
                "auc_gap": eval_reference.auc_gap(list(values), reference),
                "least": float(min(abs(float(v) - r) for v, r in zip(
                    values[first:], reference[first:])))}
    return out


def one_seed(man, name, seed, rehearse):
    import gc

    import jax
    import numpy as np

    import datagen
    import run
    cell = man.cell(name)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearse:
        run.say(f"eval_readings: needs a TPU, JAX found "
                f"{devices[0].platform}")
        return None
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    run.place_compile_cache()
    TELEMETRY.install_jax_listeners()
    params = dict(config["params"])
    params.update(traffic.get("extra_params", {}))
    params.setdefault("verbose", -1)
    rows = int(config["data"]["rows"])
    valid_rows = int(traffic["valid_rows"])
    if rehearse:
        rows = run.REHEARSAL["rows"]
        valid_rows = min(valid_rows, rows // 10)
        params.update(num_leaves=run.REHEARSAL["num_leaves"],
                      min_sum_hessian_in_leaf=run.REHEARSAL[
                          "min_sum_hessian_in_leaf"],
                      tpu_histogram_backend="pallas")
    rng = np.random.default_rng(seed)
    X, y = datagen.make(config["data"], rows, rng)
    Xv, yv = datagen.make(config["data"], valid_rows, rng)
    ds = lgb.Dataset(X, y, params=dict(params))
    ds.construct()
    valid = ds.create_valid(Xv, yv)
    valid.construct()
    probe = lgb.Booster(params=dict(params), train_set=ds)
    chunk = (run.REHEARSAL["chunk"] if rehearse
             else int(probe.gbdt.boost_chunk_size()))
    del probe
    gc.collect()
    t0 = time.perf_counter()
    bst, evals, rec = train_recorded(lgb, params, ds, valid, chunk, chunk)
    train_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = gaps(bst.gbdt, bst.model_to_string(), evals, rec, Xv, yv)
    counters = bst.get_stats()["counters"]
    out.update(workload=name, seed=seed, rows=rows, valid_rows=valid_rows,
               chunk=chunk, train_s=train_s,
               readings_s=time.perf_counter() - t0,
               walk_levels=counters.get("eval/walk_levels"),
               device=devices[0].device_kind)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from manifest import Manifest
    man = Manifest()
    out_dir = os.path.join(man.root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"eval_readings_{args.workload}.jsonl")
    for seed in (int(s) for s in args.seeds.split(",")):
        out = one_seed(man, args.workload, seed, args.rehearse)
        if out is None:
            return 2
        line = json.dumps(out)
        print(line, flush=True)
        with open(path, "a") as fh:
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
