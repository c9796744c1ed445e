"""The necessary-work functions on a hand-built three-leaf tree."""

import numpy as np

import work
from reference import RefTree


def three_leaf_tree():
    # root (100 rows) -> leaf 0 (30) | node 1 (70) -> leaf 1 (50) | leaf 2 (20)
    return RefTree(
        num_leaves=3, shrinkage=0.1,
        split_feature=np.array([0, 1]), threshold=np.array([0.5, -0.25]),
        decision_type=np.array([2, 2]),
        left_child=np.array([-1, -2]), right_child=np.array([1, -3]),
        split_gain=np.array([9.0, 4.0]),
        internal_weight=np.array([25.0, 17.5]),
        internal_count=np.array([100, 70]),
        leaf_value=np.array([0.1, -0.2, 0.3]),
        leaf_weight=np.array([7.5, 12.5, 5.0]),
        leaf_count=np.array([30, 50, 20]))


def test_visits_are_root_plus_smaller_children():
    # root 100, split 0's smaller child 30, split 1's smaller child 20
    assert work.tree_visits(three_leaf_tree()) == 150


def test_single_leaf_tree_needs_no_pass():
    assert work.tree_visits(RefTree(num_leaves=1, shrinkage=1.0)) == 0


def test_bytes_ops_and_bound():
    w = work.histogram_work([three_leaf_tree()] * 2, features=28)
    assert w == {"visits": 300, "bytes": 300 * 36, "ops": 300 * 56}
    least = work.least_seconds(w, {"flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "hbm"
    assert least["seconds"] == 300 * 36 / 819e9
    assert work.least_seconds(w, {"flops_per_s": 1.0,
                                  "hbm_bytes_per_s": 819e9})["bound"] == "flops"
