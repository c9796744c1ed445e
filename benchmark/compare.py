"""The comparison that decides `correct`.

`first_steps` holds what the timed path produced in its first steps (the
trees as serialised) against what the plain reference finds under them;
`forest_faults` counts what every tree of the run must satisfy on its own,
and `score_gap` holds the scores the program ended with against the sum of
all its serialised trees.
Each number compared has a limit of its own, read from `limits.json` by the
cell's name; `verdict` sets each beside its limit.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from reference import RefTree, StepReading


@dataclass
class TreeFacts:
    """The numbers of one tree that are held against the reference."""
    leaf_value: np.ndarray      # without the init score
    leaf_weight: np.ndarray
    leaf_count: np.ndarray
    node_weight: np.ndarray
    node_count: np.ndarray
    gain: np.ndarray


def facts_of_tree(t: RefTree, init_score: float) -> TreeFacts:
    """`init_score`: the objective's (`Follower.init_score`), which the
    first tree carries in its leaves; 0 for every later tree."""
    return TreeFacts(leaf_value=t.leaf_value - init_score,
                     leaf_weight=t.leaf_weight, leaf_count=t.leaf_count,
                     node_weight=t.internal_weight,
                     node_count=t.internal_count, gain=t.split_gain)


def facts_of_reading(r: StepReading) -> TreeFacts:
    """A reference's own reading in the program's place (the control)."""
    return TreeFacts(leaf_value=r.leaf_value, leaf_weight=r.leaf_h,
                     leaf_count=r.leaf_c, node_weight=r.node_h,
                     node_count=r.node_c, gain=r.gain)


def _gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Each entry's gap against its own size in the reference or the median
    entry's, whichever is larger."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    if len(want) == 0:
        return np.zeros(0)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return np.full(len(want), np.inf)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return np.abs(got - want) / np.maximum(scale, 1e-300)


def _worst_gap(got: np.ndarray, want: np.ndarray) -> float:
    g = _gaps(got, want)
    return float(g.max()) if len(g) else 0.0


def _argworst(got, want):
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or len(want) == 0:
        return None
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return int(np.argmax(np.abs(got - want) / np.maximum(scale, 1e-300)))


def step_details(f: TreeFacts, r: StepReading, cand=None) -> Dict:
    """Where one step's worst entries sit, for the record a failed run
    leaves: a few plain numbers by short names."""
    d: Dict = {}
    if (len(f.leaf_count) == len(r.leaf_c)
            and len(f.node_count) == len(r.node_c)):
        bad_l = np.flatnonzero(np.asarray(f.leaf_count) != r.leaf_c)
        bad_n = np.flatnonzero(np.asarray(f.node_count) != r.node_c)
        d["leaf_counts_off"] = [
            [int(i), int(f.leaf_count[i]), int(r.leaf_c[i])]
            for i in bad_l[:6]]
        d["node_counts_off"] = [
            [int(i), int(f.node_count[i]), int(r.node_c[i])]
            for i in bad_n[:6]]
        d["n_counts_off"] = [int(len(bad_l)), int(len(bad_n))]
    i = _argworst(f.leaf_value, r.leaf_value)
    if i is not None:
        d["worst_leaf"] = {"leaf": i, "got": float(f.leaf_value[i]),
                           "want": float(r.leaf_value[i]),
                           "rows": int(r.leaf_c[i]), "h": float(r.leaf_h[i])}
    i = _argworst(f.gain, r.gain)
    if i is not None:
        d["worst_gain"] = {"node": i, "got": float(f.gain[i]),
                           "want": float(r.gain[i]), "rows": int(r.node_c[i])}
    if r.best_gain is not None and len(r.gain):
        scale = np.maximum(np.abs(r.gain), np.median(np.abs(r.gain)))
        i = int(np.argmax((r.best_gain - r.gain) / np.maximum(scale, 1e-300)))
        d["worst_shortfall"] = {"node": i, "chosen": float(r.gain[i]),
                                "best": float(r.best_gain[i]),
                                "median_gain": float(np.median(r.gain)),
                                "rows": int(r.node_c[i])}
    return d


def first_steps(tested: List[TreeFacts], ref: List[StepReading]) -> Dict:
    """Numbers over the steps followed, each the worst over the steps.  For
    the four gaps taken leaf by leaf or node by node there is the widest
    (`<name>`), and the median and the ninth decile (`<name>_p50`, `_p90`),
    which stay steady where one small leaf swings the widest."""
    out = {"count_gap": 0.0, "unrouted_rows": 0, "loss_gap": 0.0}

    def hold(name, gaps):
        if not len(gaps):
            gaps = np.zeros(1)
        for key, value in ((name, np.max(gaps)),
                           (name + "_p50", np.median(gaps)),
                           (name + "_p90", np.quantile(gaps, 0.9))):
            out[key] = max(out.get(key, 0.0), float(value))

    for f, r in zip(tested, ref):
        out["count_gap"] = max(
            out["count_gap"],
            _worst_gap(np.concatenate([f.leaf_count, f.node_count]),
                       np.concatenate([r.leaf_c, r.node_c])))
        out["unrouted_rows"] += r.unrouted
        hold("leaf_value_gap", _gaps(f.leaf_value, r.leaf_value))
        hold("hessian_sum_gap",
             _gaps(np.concatenate([f.leaf_weight, f.node_weight]),
                   np.concatenate([r.leaf_h, r.node_h])))
        hold("split_gain_gap", _gaps(f.gain, r.gain))
        if r.best_gain is not None and len(r.gain):
            scale = np.maximum(np.abs(r.gain), np.median(np.abs(r.gain)))
            hold("best_split_shortfall",
                 (r.best_gain - r.gain) / np.maximum(scale, 1e-300))
        if r.tested_loss is not None:
            out["loss_gap"] = max(out["loss_gap"],
                                  abs(r.tested_loss - r.loss) / r.loss)
    return out


def score_gap(got: np.ndarray, want: np.ndarray, init_score: float) -> float:
    """The widest gap between the program's training scores after the run and
    the reference's sum of the serialised trees over the same rows, against
    the root mean square of what the trees added."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    moved = np.sqrt(np.mean((want.astype(np.float64) - init_score) ** 2))
    return float(np.max(np.abs(got.astype(np.float64) - want)) /
                 max(moved, 1e-300))


def forest_faults(trees: List[RefTree], *, rows: int, num_leaves: int,
                  min_data: float, min_hess: float,
                  expected_trees: int) -> Dict:
    """What every tree of the run has to satisfy on its own, counted over the
    whole forest, warm-up and window: as many trees as rounds, no more leaves
    than allowed, finite numbers, counts that add up from the leaves to the
    rows, the configuration's minima kept, no tree grown twice, and no split
    made while an open leaf offered a larger gain (best-first growth)."""
    faults = abs(len(trees) - expected_trees)
    prev = None
    for t in trees:
        S = t.num_leaves - 1
        if t.num_leaves > num_leaves or t.num_leaves < 1:
            faults += 1
        vals = [t.leaf_value, t.leaf_weight, t.split_gain, t.threshold,
                t.internal_weight]
        if not all(np.all(np.isfinite(v)) for v in vals):
            faults += 1
        if S == 0:
            prev = t
            continue
        if int(t.internal_count[0]) != rows:
            faults += 1

        def count_of(child):
            child = np.asarray(child)
            return np.where(child < 0, t.leaf_count[~np.minimum(child, -1)],
                            t.internal_count[np.maximum(child, 0)])

        if np.any(count_of(t.left_child) + count_of(t.right_child)
                  != t.internal_count):
            faults += 1
        if np.any(t.leaf_count < min_data):
            faults += 1
        if np.any(t.leaf_weight < min_hess * (1.0 - 1e-3)):
            faults += 1
        if np.any(t.split_gain <= 0):
            faults += 1
        if (prev is not None and prev.num_leaves == t.num_leaves
                and np.array_equal(prev.split_feature, t.split_feature)
                and np.array_equal(prev.threshold, t.threshold)
                and np.array_equal(prev.leaf_count, t.leaf_count)):
            # a step that left the scores where they were grows its tree again
            faults += 1
        # split j opens the leaf that split parent[j] made
        parent = np.full(S, -1)
        for s in range(S):
            for c in (t.left_child[s], t.right_child[s]):
                if c >= 0:
                    parent[c] = s
        g = t.split_gain
        j, i = np.meshgrid(np.arange(S), np.arange(S))
        open_then = (parent[j] < i) & (i < j)
        if np.any(open_then & (g[j] > g[i])):
            faults += 1
        prev = t
    return {"forest_faults": int(faults)}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [[name, number, limit], ...]).  The numbers compared are
    those the cell's `limits` name; each has to be there, finite and not
    above its limit."""
    rows = []
    ok = True
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"the limit {name!r} names no number that the "
                           f"comparison reads")
        value = numbers[name]
        ok = ok and bool(np.isfinite(value)) and value <= limit
        rows.append([name, float(value), float(limit)])
    return ok, rows


def skipped_feature_shortfall(ref: List[StepReading]) -> Dict:
    """What best_split_shortfall reads where a split scan leaves the best
    feature out: the fault planted in the reference's own candidates."""
    out: Dict = {}
    for r in ref:
        if r.other_gain is None or not len(r.gain):
            continue
        scale = np.maximum(np.abs(r.gain), np.median(np.abs(r.gain)))
        short = ((r.best_gain - np.maximum(r.other_gain, 0.0))
                 / np.maximum(scale, 1e-300))
        for key, value in (("best_split_shortfall", np.max(short)),
                           ("best_split_shortfall_p50", np.median(short)),
                           ("best_split_shortfall_p90",
                            np.quantile(short, 0.9))):
            out[key] = max(out.get(key, 0.0), float(value))
    return out


def control_readings(f32: List[StepReading],
                     low: List[StepReading]) -> Dict:
    """The control: the reference in the lower precision, put in the
    program's place and held against the reference proper."""
    held = [dataclasses.replace(r, tested_loss=c.loss, best_gain=None)
            for r, c in zip(f32, low)]
    out = first_steps([facts_of_reading(r) for r in low], held)
    # the control makes no split
    return {k: v for k, v in out.items()
            if not k.startswith("best_split_shortfall")}
