"""The plain reference of the evaluation: what `metric: auc` on a held-out
set has to read after every boosting iteration.

Float64 numpy on the host; it imports nothing of the program and nothing of
`reference.py`.  From the model as the program serialised it
(`Booster.model_to_string()`) and the RAW float32 held-out rows:

  (a) `parse_trees` reads each tree's split features, real-valued
      thresholds, children and leaf values;
  (b) `leaf_of_rows` walks every row down a tree by `x <= threshold` (left);
      the configuration has no missing value and no categorical column, so
      a model with a categorical split or a missing type, and rows with a
      NaN, are refused and not guessed at;
  (c) `scores_by_tree` adds the leaf values tree by tree.  A serialised
      model carries the init score (`boost_from_average`) inside the first
      tree's leaves and the shrinkage inside every leaf value, so the sum
      starts from 0 and what it holds after tree t is the raw score the
      program's valid-score carry has to hold after iteration t;
  (d) `auc` is the rank-sum AUC with half credit inside a group of tied
      scores, the definition `lightgbm_tpu/metric/__init__.py` states for
      `AUCMetric` (1.0 where a class is absent), written again from the
      definition: every (positive, negative) pair scores 1 where the
      positive ranks higher, 1/2 where the two tie.

`auc_by_iteration` is (c) and (d) together; `auc_gap` is the number a limit
is set on: the widest gap, over the iterations, between the program's
per-iteration AUC and this one.  The two can differ only where float32
scores (the program's carry, summed in float32 in the scan) order a
positive-negative pair otherwise than the float64 sums do, which takes two
rows whose scores agree to a float32 rounding, and by the rounding of the
program's own float32 rank sum (24 bits for a sum of Nv terms): a few 1e-7
at 500,000 rows (`PERF.md` section 2 has the readings), where a carry kept
in bfloat16, a tree left out of the carry or a metric one iteration late
read 1e-4 and more.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


class EvalTree(NamedTuple):
    split_feature: np.ndarray      # [S] int64, column of the raw table
    threshold: np.ndarray          # [S] float64
    left_child: np.ndarray         # [S] int64: >= 0 a split, ~leaf a leaf
    right_child: np.ndarray        # [S] int64
    leaf_value: np.ndarray         # [S + 1] float64, shrinkage inside


def _field(kv: dict, key: str, dtype) -> np.ndarray:
    return np.array(kv.get(key, "").split(), dtype=dtype)


def parse_trees(model_text: str) -> List[EvalTree]:
    """The trees of a LightGBM text model, in boosting order."""
    trees = []
    body = model_text.split("end of trees")[0]
    for block in body.split("\nTree=")[1:]:
        kv = {}
        for line in block.splitlines()[1:]:
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v.strip()
        if int(kv.get("num_cat", 0)) != 0:
            raise ValueError("eval_reference: the model has a categorical "
                             "split; this reference walks numerical ones")
        decision = _field(kv, "decision_type", np.int64)
        if np.any(decision & 1):
            raise ValueError("eval_reference: categorical decision type")
        if np.any((decision >> 2) & 3):
            raise ValueError("eval_reference: the model has a split with a "
                             "missing type; the configuration has no missing "
                             "value and this reference walks none")
        tree = EvalTree(_field(kv, "split_feature", np.int64),
                        _field(kv, "threshold", np.float64),
                        _field(kv, "left_child", np.int64),
                        _field(kv, "right_child", np.int64),
                        _field(kv, "leaf_value", np.float64))
        if len(tree.leaf_value) != int(kv["num_leaves"]):
            raise ValueError("eval_reference: leaf values do not number "
                             "num_leaves")
        trees.append(tree)
    return trees


def leaf_of_rows(tree: EvalTree, X: np.ndarray) -> np.ndarray:
    """The leaf each raw row ends in: `x <= threshold` goes left."""
    n = X.shape[0]
    if len(tree.split_feature) == 0:
        return np.zeros(n, np.int64)
    node = np.zeros(n, np.int64)
    while True:
        at = np.flatnonzero(node >= 0)
        if at.size == 0:
            return ~node
        nd = node[at]
        x = X[at, tree.split_feature[nd]].astype(np.float64)
        node[at] = np.where(x <= tree.threshold[nd], tree.left_child[nd],
                            tree.right_child[nd])


def scores_by_tree(trees: List[EvalTree], X: np.ndarray):
    """Yields the float64 raw score of every row after each tree."""
    if np.isnan(X).any():
        raise ValueError("eval_reference: a NaN among the held-out rows")
    score = np.zeros(X.shape[0], np.float64)
    for tree in trees:
        score = score + tree.leaf_value[leaf_of_rows(tree, X)]
        yield score


def auc(score: np.ndarray, label: np.ndarray) -> float:
    """Rank-sum AUC, half credit inside tied-score groups; 1.0 where the
    labels hold a single class."""
    pos = np.asarray(label) > 0
    n_pos = int(pos.sum())
    n_neg = int(pos.size - n_pos)
    if n_pos == 0 or n_neg == 0:
        return 1.0
    values, group = np.unique(np.asarray(score, np.float64),
                              return_inverse=True)
    neg_in_group = np.bincount(group[~pos], minlength=len(values))
    pos_in_group = np.bincount(group[pos], minlength=len(values))
    neg_below = np.cumsum(neg_in_group) - neg_in_group
    won = np.sum(pos_in_group.astype(np.float64)
                 * (neg_below + 0.5 * neg_in_group))
    return float(won / (float(n_pos) * float(n_neg)))


def auc_by_iteration(model_text: str, X: np.ndarray,
                     label: np.ndarray) -> List[float]:
    """The AUC on (X, label) after each tree of the serialised model."""
    return [auc(s, label)
            for s in scores_by_tree(parse_trees(model_text), X)]


def auc_gap(program: List[float], reference: List[float]) -> float:
    """The widest gap over the iterations; an iteration the program gave no
    value for is a gap of 1."""
    if len(program) != len(reference):
        return 1.0
    return float(max(abs(float(p) - r) for p, r in zip(program, reference)))
