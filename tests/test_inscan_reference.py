"""The in-scan evaluation of a held-out set (`gbdt._eval_walk`, the AUC of
`metric/device.py`) against the benchmark's plain float64 reference
(`benchmark/eval_reference.py`, which imports nothing of the program): every
iteration's AUC, at 63 bins, with chunks of 4 and of 1.

TOLERANCE.  The program carries float32 scores and sums its rank sum in
float32; the reference sums float64 leaf values and counts pairs exactly.
The two can differ by the rounding of the float32 AUC itself (6e-8 near 0.9)
and of its rank sum, and by pairs that float32 sums order otherwise than
float64 sums, which takes two rows whose scores agree to a float32 rounding.
At this size that reads 1e-8 to 1e-7; the same evaluation with the carry in
bfloat16 reads 1e-5 and more, a tree left out or a metric one iteration late
1e-3 and more.
"""

import functools
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.phase import GLOBAL_TIMER
from lightgbm_tpu.utils.telemetry import TELEMETRY

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for p in (BENCH, os.path.join(BENCH, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

import eval_readings  # noqa: E402
import eval_reference  # noqa: E402

TOLERANCE = 1e-6
ROUNDS = 8


def _rows(rng, n):
    X = rng.standard_normal((n, 10)).astype(np.float32)
    logit = 2.0 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
    return X, (logit + rng.standard_normal(n) > 0).astype(np.float64)


def _held_out(rng, pattern):
    X, y = _rows(rng, 1500)
    if pattern == "planted_ties":
        # every row three times, so every score is tied at least three
        # ways, and a third of the copies with the other label, so the
        # tied groups hold both classes: half credit decides the value
        X = np.concatenate([X, X, X])
        y = np.concatenate([y, y, 1.0 - y])
    elif pattern == "single_class":
        y = np.ones_like(y)
    return X, y


@functools.lru_cache(maxsize=None)
def _trained(chunk, pattern):
    rng = np.random.default_rng(20261005)
    X, y = _rows(rng, 6000)
    Xv, yv = _held_out(rng, pattern)
    params = {"objective": "binary", "metric": "auc", "max_bin": 63,
              "num_leaves": 15, "min_data_in_leaf": 20, "verbose": -1}
    ds = lgb.Dataset(X, y, params=dict(params))
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()
    bst, evals, rec = eval_readings.train_recorded(
        lgb, params, ds, ds.create_valid(Xv, yv), ROUNDS, chunk)
    stats = bst.get_stats()
    assert not [g for g in stats["gauges"] if g.startswith("boost/inscan_")]
    assert stats["counters"]["transfer/eval_fetch_calls"] == ROUNDS // chunk
    return eval_readings.gaps(bst.gbdt, bst.model_to_string(), evals, rec,
                              Xv, yv)


CASES = [(chunk, pattern, fault)
         for chunk in (4, 1)
         for pattern, fault in (
             ("planted_ties", None),
             ("planted_ties", "control_bfloat16"),
             ("planted_ties", "fault_skipped_tree"),
             ("planted_ties", "fault_metric_late"),
             ("single_class", None))]


@pytest.mark.parametrize("chunk,pattern,fault", CASES)
def test_inscan_auc_against_the_plain_reference(chunk, pattern, fault):
    got = _trained(chunk, pattern)
    assert len(got["auc_program"]) == len(got["auc_reference"]) == ROUNDS
    # the replay the control and the faults are planted in IS the program:
    # as it stands it gives the training's own values, to the bit
    assert got["replay_gap"] == 0.0
    if fault is None:
        assert got["program"] <= TOLERANCE, got
        if pattern == "single_class":
            assert got["auc_program"] == [1.0] * ROUNDS
        else:
            # the metric moves, so a stale or short carry would show
            assert len(set(got["auc_program"])) == ROUNDS
    elif fault == "control_bfloat16":
        # a first tree's 15 distinct scores survive bfloat16; the sums of
        # later iterations do not, so the control fails the widest gap
        assert got[fault]["auc_gap"] > TOLERANCE, got
    else:
        # a fault is caught at every iteration from the planted change on
        assert got[fault]["least"] > TOLERANCE, got


def test_reference_refuses_what_it_does_not_walk():
    rng = np.random.default_rng(7)
    X, y = _rows(rng, 2000)
    X[::5, 0] = np.nan
    bst = lgb.train({"objective": "binary", "max_bin": 63, "num_leaves": 7,
                     "verbose": -1}, lgb.Dataset(X, y), 2)
    with pytest.raises(ValueError, match="missing type"):
        eval_reference.parse_trees(bst.model_to_string())
    clean = np.nan_to_num(X)
    bst = lgb.train({"objective": "binary", "max_bin": 63, "num_leaves": 7,
                     "verbose": -1}, lgb.Dataset(clean, y), 2)
    with pytest.raises(ValueError, match="NaN"):
        eval_reference.auc_by_iteration(bst.model_to_string(), X, y)


def test_reference_auc_is_the_pair_count():
    """`eval_reference.auc` against the definition spelt out pair by pair
    on a small set with ties."""
    rng = np.random.default_rng(3)
    score = rng.integers(0, 6, 60).astype(np.float64)
    label = rng.integers(0, 2, 60)
    pos, neg = score[label > 0], score[label == 0]
    won = sum((p > n) + 0.5 * (p == n) for p in pos for n in neg)
    assert eval_reference.auc(score, label) == pytest.approx(
        won / (len(pos) * len(neg)), abs=1e-15)
    assert eval_reference.auc(score, np.ones(60)) == 1.0
