"""Device-side (jittable) metric kernels for in-scan evaluation.

The chunked boosting loop (models/gbdt.py ``train_chunk``) can carry the
valid-set score vectors through its lax.scan and evaluate the attached
built-in metrics per iteration ON DEVICE, returning a ``[T, n_cols]``
array that rides the existing async chunk fetch.  This module builds
that evaluation program from the host-side metric objects produced by
``GBDT.setup_metrics`` — same formulas as metric/__init__.py, expressed
in jnp over the device score buffers.

Numerics: the kernels run in f32 (the training dtype).  Probability
clipping uses 1e-7 instead of the host metrics' 1e-15 because
``1 - 1e-15`` rounds to exactly 1.0 in f32 and ``log(1 - p)`` would hit
log(0).  In-scan values are therefore bit-identical across chunk sizes
(same program, same state upload points) but only approximately equal
to the host f64 per-iteration path.
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# f32-safe probability clip (host metrics use 1e-15 in f64; see module doc)
_EPS = 1e-7

# metric canonical names with a device kernel below; everything else
# (map, cross_entropy_lambda, custom fevals) blocks in-scan evaluation
DEVICE_METRICS = frozenset({
    "l2", "rmse", "l1", "quantile", "huber", "fair", "poisson", "mape",
    "gamma", "gamma_deviance", "tweedie", "binary_logloss", "binary_error",
    "auc", "multi_logloss", "multi_error", "cross_entropy",
    "kullback_leibler", "ndcg",
})


class DeviceEval(NamedTuple):
    """A compiled-in evaluation program for the chunk scan body.

    ``eval_fn(train_score, vscores, arrays) -> [n_cols] f32`` is pure jnp
    (traceable inside the scan); ``arrays`` is the per-set device-array
    pytree passed as a jit argument (labels/weights/rank tables embedded
    as constants would bloat the program by O(N) bytes).  ``columns``
    maps the output vector to (set_name, metric_name, higher_better)
    rows in the legacy eval order: "training" first when requested, then
    the valid sets in attachment order."""
    columns: Tuple[Tuple[str, str, bool], ...]
    eval_fn: Callable
    arrays: Tuple[dict, ...]
    vbins: Tuple[jax.Array, ...]


def _link_for(objective) -> Optional[Callable]:
    """Device-side equivalent of ``objective.convert_output`` (np-based,
    unusable under jit) applied to a [C, N] raw score; None when the
    objective's link has no kernel here."""
    name = getattr(objective, "name", "")
    if name == "regression":
        if getattr(objective, "sqrt", False):
            return lambda s: jnp.sign(s) * s * s
        return lambda s: s
    if name in ("regression_l1", "huber", "fair", "quantile", "mape",
                "lambdarank"):
        return lambda s: s
    if name in ("binary", "multiclassova"):
        sig = float(objective.sigmoid)
        return lambda s: 1.0 / (1.0 + jnp.exp(-sig * s))
    if name == "multiclass":
        def softmax(s):
            e = jnp.exp(s - jnp.max(s, axis=0, keepdims=True))
            return e / jnp.sum(e, axis=0, keepdims=True)
        return softmax
    if name == "cross_entropy":
        return lambda s: 1.0 / (1.0 + jnp.exp(-s))
    if name == "cross_entropy_lambda":
        return lambda s: jnp.log1p(jnp.exp(s))
    if name in ("poisson", "gamma", "tweedie"):
        return jnp.exp
    return None


class _Blocked(Exception):
    def __init__(self, what: str):
        super().__init__(what)
        self.what = what


def _build_ndcg_tables(m) -> Tuple[dict, list]:
    """Host-precomputed rank tables for one NDCG metric: padded [Q, P]
    doc-index/mask/gain tables plus per-(query, k) 1/maxDCG (label-only,
    so computable once up front) and the position discounts."""
    b = np.asarray(m.boundaries, dtype=np.int64)
    Q = len(b) - 1
    ks = [int(k) for k in m.eval_at]
    P = int(max((b[1:] - b[:-1]).max(), 1)) if Q > 0 else 1
    idx = np.zeros((Q, P), dtype=np.int32)
    mask = np.zeros((Q, P), dtype=bool)
    gains = np.zeros((Q, P), dtype=np.float32)
    inv_max = np.zeros((Q, len(ks)), dtype=np.float32)
    perfect = np.zeros((Q, len(ks)), dtype=bool)
    lg = m.calc.label_gain
    for q in range(Q):
        s, e = int(b[q]), int(b[q + 1])
        L = e - s
        idx[q, :L] = np.arange(s, e)
        mask[q, :L] = True
        gains[q, :L] = lg[m.label[s:e].astype(np.int64)]
        for i, k in enumerate(ks):
            md = m.calc.cal_maxdcg_at_k(k, m.label[s:e])
            if md <= 0:
                perfect[q, i] = True       # no relevant docs = perfect
            else:
                inv_max[q, i] = 1.0 / md
    qw = (np.asarray(m.query_weights, dtype=np.float32)
          if m.query_weights is not None
          else np.ones(Q, dtype=np.float32))
    # [K, P] masked discounts: discount(pos) for pos < k, else 0
    disc = np.zeros((len(ks), P), dtype=np.float32)
    pos = np.arange(P)
    for i, k in enumerate(ks):
        disc[i] = np.where(pos < k, 1.0 / np.log2(2.0 + pos), 0.0)
    arrays = {
        "ndcg_idx": jnp.asarray(idx), "ndcg_mask": jnp.asarray(mask),
        "ndcg_gain": jnp.asarray(gains), "ndcg_inv": jnp.asarray(inv_max),
        "ndcg_perfect": jnp.asarray(perfect), "ndcg_qw": jnp.asarray(qw),
        "ndcg_disc": jnp.asarray(disc),
    }
    return arrays, ks


def _build_set_program(metrics, metadata, num_data: int, objective):
    """One eval set -> (columns, arrays dict, set_fn(raw [C, N], A))."""
    N = int(num_data)
    w_np = metadata.weights
    has_w = w_np is not None
    sum_w = float(np.sum(w_np)) if has_w else float(N)
    arrays = {"label": jnp.asarray(np.asarray(metadata.label,
                                              dtype=np.float32))}
    if has_w:
        arrays["w"] = jnp.asarray(np.asarray(w_np, dtype=np.float32))

    def avg(x, A):
        if has_w:
            return jnp.sum(x * A["w"]) / sum_w
        return jnp.mean(x)

    columns: List[Tuple[str, bool]] = []
    fns = []          # each: (p, raw, A) -> [k] f32

    def scalar(fn):
        return lambda p, raw, A: jnp.reshape(fn(p, raw, A), (1,))

    for m in metrics:
        name = m.name
        if name not in DEVICE_METRICS:
            raise _Blocked(name)
        cfg = m.config
        if name == "l2":
            fns.append(scalar(lambda p, raw, A: avg(
                (A["label"] - p[0]) ** 2, A)))
        elif name == "rmse":
            fns.append(scalar(lambda p, raw, A: jnp.sqrt(avg(
                (A["label"] - p[0]) ** 2, A))))
        elif name == "l1":
            fns.append(scalar(lambda p, raw, A: avg(
                jnp.abs(A["label"] - p[0]), A)))
        elif name == "quantile":
            a = float(cfg.alpha)
            def q_fn(p, raw, A, a=a):
                d = A["label"] - p[0]
                return avg(jnp.where(d >= 0, a * d, (a - 1.0) * d), A)
            fns.append(scalar(q_fn))
        elif name == "huber":
            a = float(cfg.alpha)
            def h_fn(p, raw, A, a=a):
                d = jnp.abs(A["label"] - p[0])
                return avg(jnp.where(d <= a, 0.5 * d * d,
                                     a * (d - 0.5 * a)), A)
            fns.append(scalar(h_fn))
        elif name == "fair":
            c = float(cfg.fair_c)
            def f_fn(p, raw, A, c=c):
                x = jnp.abs(A["label"] - p[0])
                return avg(c * c * (x / c - jnp.log1p(x / c)), A)
            fns.append(scalar(f_fn))
        elif name == "poisson":
            def po_fn(p, raw, A):
                pm = jnp.maximum(p[0], 1e-15)
                return avg(pm - A["label"] * jnp.log(pm), A)
            fns.append(scalar(po_fn))
        elif name == "mape":
            fns.append(scalar(lambda p, raw, A: avg(
                jnp.abs(A["label"] - p[0])
                / jnp.maximum(1.0, jnp.abs(A["label"])), A)))
        elif name == "gamma":
            def g_fn(p, raw, A):
                pm = jnp.maximum(p[0], 1e-15)
                x = A["label"] / pm
                return avg(x + jnp.log(pm)
                           - jnp.log(jnp.maximum(A["label"], 1e-15)), A)
            fns.append(scalar(g_fn))
        elif name == "gamma_deviance":
            def gd_fn(p, raw, A):
                pm = jnp.maximum(p[0], 1e-15)
                x = A["label"] / pm
                return avg(2.0 * (jnp.log(jnp.maximum(
                    1.0 / jnp.maximum(x, 1e-15), 1e-15)) + x - 1.0), A)
            fns.append(scalar(gd_fn))
        elif name == "tweedie":
            rho = float(cfg.tweedie_variance_power)
            def tw_fn(p, raw, A, rho=rho):
                pm = jnp.maximum(p[0], 1e-15)
                a = A["label"] * jnp.power(pm, 1.0 - rho) / (1.0 - rho)
                b = jnp.power(pm, 2.0 - rho) / (2.0 - rho)
                return avg(-a + b, A)
            fns.append(scalar(tw_fn))
        elif name in ("binary_logloss", "cross_entropy"):
            def bl_fn(p, raw, A):
                pc = jnp.clip(p[0], _EPS, 1.0 - _EPS)
                y = (A["label"] > 0).astype(jnp.float32)
                return avg(-(y * jnp.log(pc)
                             + (1.0 - y) * jnp.log(1.0 - pc)), A)
            fns.append(scalar(bl_fn))
        elif name == "binary_error":
            def be_fn(p, raw, A):
                pred = (p[0] > 0.5)
                y = (A["label"] > 0)
                return avg((pred != y).astype(jnp.float32), A)
            fns.append(scalar(be_fn))
        elif name == "kullback_leibler":
            def kl_fn(p, raw, A):
                pc = jnp.clip(p[0], _EPS, 1.0 - _EPS)
                y = jnp.clip(A["label"], _EPS, 1.0 - _EPS)
                return avg(y * jnp.log(y / pc)
                           + (1.0 - y) * jnp.log((1.0 - y) / (1.0 - pc)),
                           A)
            fns.append(scalar(kl_fn))
        elif name == "auc":
            def auc_fn(p, raw, A):
                # weighted rank-sum AUC with half credit inside tied-score
                # groups (metric/__init__.py AUCMetric, via segment_sum
                # over cumsum-derived group ids instead of np.reduceat)
                s = raw[0]
                order = jnp.argsort(s, stable=True)
                ss = s[order]
                ys = A["label"][order] > 0
                ws = (A["w"][order] if has_w
                      else jnp.ones_like(ss))
                pos_w = jnp.sum(ws * ys)
                neg_w = jnp.sum(ws * ~ys)
                new_grp = jnp.concatenate(
                    [jnp.zeros(1, dtype=jnp.int32),
                     (ss[1:] != ss[:-1]).astype(jnp.int32)])
                gid = jnp.cumsum(new_grp)
                grp_neg = jax.ops.segment_sum(
                    ws * ~ys, gid, num_segments=N,
                    indices_are_sorted=True)
                cum_before = jnp.cumsum(grp_neg) - grp_neg
                auc_sum = jnp.sum((cum_before[gid]
                                   + 0.5 * grp_neg[gid]) * ws * ys)
                ok = (pos_w > 0) & (neg_w > 0)
                return jnp.where(
                    ok, auc_sum / jnp.maximum(pos_w * neg_w, 1e-20), 1.0)
            fns.append(scalar(auc_fn))
        elif name == "multi_logloss":
            arrays.setdefault("label_i", jnp.asarray(
                np.asarray(metadata.label, dtype=np.int32)))
            def ml_fn(p, raw, A):
                pc = jnp.clip(p, _EPS, 1.0 - _EPS)
                picked = jnp.take_along_axis(
                    pc, A["label_i"][None, :], axis=0)[0]
                return avg(-jnp.log(picked), A)
            fns.append(scalar(ml_fn))
        elif name == "multi_error":
            arrays.setdefault("label_i", jnp.asarray(
                np.asarray(metadata.label, dtype=np.int32)))
            k = max(1, int(cfg.multi_error_top_k))
            def me_fn(p, raw, A, k=k):
                lab = A["label_i"]
                if k == 1:
                    err = (jnp.argmax(raw, axis=0).astype(jnp.int32)
                           != lab)
                else:
                    target = jnp.take_along_axis(
                        raw, lab[None, :], axis=0)[0]
                    rank = jnp.sum(raw > target[None, :], axis=0)
                    err = rank >= k
                return avg(err.astype(jnp.float32), A)
            fns.append(scalar(me_fn))
        elif name == "ndcg":
            nd_arrays, ks = _build_ndcg_tables(m)
            arrays.update(nd_arrays)
            sum_qw = float(np.asarray(nd_arrays["ndcg_qw"]).sum())
            def nd_fn(p, raw, A, sum_qw=sum_qw):
                s = raw[0]
                sq = jnp.where(A["ndcg_mask"], s[A["ndcg_idx"]],
                               -jnp.inf)                       # [Q, P]
                order = jnp.argsort(-sq, axis=1, stable=True)
                g_sorted = jnp.take_along_axis(A["ndcg_gain"], order,
                                               axis=1)
                dcg = jnp.einsum("qp,kp->kq", g_sorted,
                                 A["ndcg_disc"])               # [K, Q]
                nd = jnp.where(A["ndcg_perfect"].T, 1.0,
                               dcg * A["ndcg_inv"].T)
                return (jnp.sum(nd * A["ndcg_qw"][None, :], axis=1)
                        / max(sum_qw, 1e-20))
            fns.append(nd_fn)
            for k in ks:
                columns.append((f"{m.name}@{k}", m.higher_better))
            continue
        columns.append((name, m.higher_better))

    link = _link_for(objective)

    def set_fn(raw, A):
        p = link(raw)
        return jnp.concatenate([fn(p, raw, A) for fn in fns])

    return columns, arrays, set_fn


def build_device_eval(gbdt, include_train: bool):
    """Build the in-scan evaluation program for a GBDT with metrics set
    up.  Returns ``(DeviceEval, None)`` or ``(None, blocker)`` where the
    blocker string names the first non-device-computable piece (metric
    canonical name, ``objective:<name>`` or ``no_metrics``) — the caller
    surfaces it in a telemetry gauge and falls back to per-iteration
    eval."""
    if _link_for(gbdt.objective) is None:
        return None, f"objective:{getattr(gbdt.objective, 'name', '?')}"
    specs = []
    if include_train:
        specs.append(("training", gbdt.metrics, gbdt.train_set, -1))
    for i, (vname, vset) in enumerate(gbdt.valid_sets):
        specs.append((vname, gbdt.valid_metrics[i], vset, i))
    columns: List[Tuple[str, str, bool]] = []
    progs = []
    arrays = []
    try:
        for set_name, metrics, dset, src in specs:
            cols, arrs, set_fn = _build_set_program(
                metrics, dset.metadata, dset.num_data, gbdt.objective)
            columns.extend((set_name, mn, hb) for mn, hb in cols)
            progs.append((src, set_fn))
            arrays.append(arrs)
    except _Blocked as e:
        return None, e.what
    if not columns:
        return None, "no_metrics"
    # valid-set bin matrices, row-major [Nv, G] (binned against the train
    # set's reference mappers, so fmeta's group/offset remap applies)
    vbins = tuple(vset.device_binned() for _, vset in gbdt.valid_sets)

    def eval_fn(train_score, vscores, arrs_tuple):
        # the scope is the metrics' name in any device trace
        with jax.named_scope("eval_metric"):
            outs = []
            for (src, set_fn), A in zip(progs, arrs_tuple):
                s = train_score if src < 0 else vscores[src]
                outs.append(set_fn(s, A))
            return jnp.concatenate(outs)

    return DeviceEval(tuple(columns), eval_fn, tuple(arrays), vbins), None
