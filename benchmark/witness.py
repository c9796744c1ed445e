"""A second witness: the same steps on the host in float64 numpy.

Where the program and the reference disagree, this says which of them a
third, slower and simpler computation sides with: gradients, routing by
`x <= threshold` node by node on row indices, and per-leaf sums by
`np.bincount`, all in float64 and with nothing on the device.  It takes
tens of seconds a tree at the cells' size, so no benchmark run calls it;
`tools/readings.py --witness` does.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np


class HostFollower:
    def __init__(self, X: np.ndarray, y: np.ndarray, params: Dict):
        if params.get("objective") != "binary":
            raise ValueError("the witness follows binary logloss only")
        self.X = X
        self.ysign = np.where(y > 0, 1.0, -1.0)
        self.lr = float(params["learning_rate"])
        self.sigmoid = float(params.get("sigmoid", 1.0))
        n = len(y)
        pavg = min(max(float((y > 0).sum()) / n, 1e-10), 1.0 - 1e-10)
        self.init_score = math.log(pavg / (1.0 - pavg)) / self.sigmoid
        self.score = np.full(n, self.init_score, np.float64)

    def leaves_of(self, tree) -> np.ndarray:
        """The leaf of every row, splitting row indices node by node."""
        n = len(self.score)
        leaf_of = np.zeros(n, np.int32)
        if tree.num_leaves == 1:
            return leaf_of
        stack = [(0, np.arange(n))]
        while stack:
            node, idx = stack.pop()
            if node < 0:
                leaf_of[idx] = ~node
                continue
            x = self.X[idx, int(tree.split_feature[node])].astype(np.float64)
            left = x <= tree.threshold[node]
            stack.append((int(tree.left_child[node]), idx[left]))
            stack.append((int(tree.right_child[node]), idx[~left]))
        return leaf_of

    def step(self, tree) -> Dict[str, np.ndarray]:
        """Per-leaf rows, gradient and hessian sums and values under `tree`;
        then the scores move by those values."""
        s, y, sg = self.score, self.ysign, self.sigmoid
        resp = -y * sg / (1.0 + np.exp(y * sg * s))
        g, h = resp, np.abs(resp) * (sg - np.abs(resp))
        leaf_of = self.leaves_of(tree)
        L = tree.num_leaves
        c = np.bincount(leaf_of, minlength=L)
        G = np.bincount(leaf_of, weights=g, minlength=L)
        H = np.bincount(leaf_of, weights=h, minlength=L)
        value = -self.lr * G / (H + 1e-15)
        self.score = s + value[leaf_of]
        return {"count": c, "g": G, "h": H, "value": value}
