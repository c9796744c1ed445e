"""The work a tree needs, counted from the tree and not from the program.

Histogram-based growth has to visit, for every tree, the root's rows once
and then, at every split, the rows of the smaller child (the larger child's
histogram is the parent's less the smaller's).  Each visit of a row reads
its F bin bytes and 8 bytes of gradient and hessian, and makes 2
accumulations per feature.  A program that visits more is doing work the
algorithm does not need; none can visit less.
"""

from __future__ import annotations

from typing import Dict, Iterable


def tree_visits(t) -> int:
    """Rows a histogram pass must visit to grow tree `t` (a RefTree)."""
    S = t.num_leaves - 1
    if S <= 0:
        return 0

    def count(child: int) -> int:
        return int(t.leaf_count[~child] if child < 0
                   else t.internal_count[child])

    visits = int(t.internal_count[0])
    for s in range(S):
        visits += min(count(int(t.left_child[s])),
                      count(int(t.right_child[s])))
    return visits


def histogram_work(trees: Iterable, features: int, bin_bytes: int = 1) -> Dict:
    """Visits, bytes and operations over `trees`."""
    visits = sum(tree_visits(t) for t in trees)
    return {"visits": visits,
            "bytes": visits * (features * bin_bytes + 8),
            "ops": visits * features * 2}


def least_seconds(work: Dict, peaks: Dict) -> Dict:
    """The least time the chip could take: the larger of operations over
    peak and bytes over peak bandwidth, and which of the two it is."""
    by_ops = work["ops"] / peaks["flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "hbm" if by_bytes >= by_ops else "flops"}
