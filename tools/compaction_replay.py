"""Replay a trained model's split sequence through the strict segment
grower's bookkeeping, on the host, under a given compaction budget.

The grower's kernel work a tree is decided by counts alone: which leaf is
split when (best-first: node i of a tree is its i-th split), how many rows
each child takes, and the grower's own rules (``models/grower_seg.py``):

  * a split's pass covers its leaf's confinement interval, in blocks; the
    children inherit it;
  * a compaction (``scanned_since`` at or over the budget, another split to
    come) sorts rows by leaf id, so every leaf's interval becomes the
    blocks its own rows touch;
  * a pass that accumulates also fills up to K - 1 lookahead lane sets:
    the pending leaves whose interval lies wholly inside the pass
    (``_lookahead_pending``), highest cached gain first; a leaf that holds
    one is split by a pass that only routes (a hit).

So a model's trees say what any budget would have cost, with no chip:
blocks scanned, compactions, lookahead hits and fills, route-only blocks,
and the total in full passes under the shape's unit costs.  ``fills``
counts the leaves that are split later; the grower also fills leaves that
never are (their cached gains are not in a model), which costs nothing and
displaces nothing: while both are pending, a leaf that is split later has
the higher gain.  Rows that pad the table to whole blocks are taken to
stay in leaf 0 (bin 0 goes left at a numerical split).

    python tools/compaction_replay.py MODEL.txt --rows 1100000 \
        --columns 2000 --bins 64 [--budgets 9,5,3,2] [--rule scanned]
"""

import argparse
import json
import sys
from typing import NamedTuple

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


class TreeShape(NamedTuple):
    """What the replay reads of one tree (``TreeArrays`` and ``Tree``
    both carry these): children (negative: ``~leaf``), the gain recorded
    at each split, rows a node and a leaf."""
    left_child: np.ndarray
    right_child: np.ndarray
    split_gain: np.ndarray
    internal_count: np.ndarray
    leaf_count: np.ndarray

    @classmethod
    def of(cls, tree) -> "TreeShape":
        n = int(tree.num_leaves) - 1
        return cls(*(np.asarray(getattr(tree, f))[:n + k] for f, k in (
            ("left_child", 0), ("right_child", 0), ("split_gain", 0),
            ("internal_count", 0), ("leaf_count", 1))))


class Replayed(NamedTuple):
    scanned_blocks: int
    compactions: int
    splits: int
    lookahead_hits: int
    lookahead_fills: int
    route_only_blocks: int

    def full_passes(self, max_blocks, compaction_cost, route_only_ratio):
        """The tree's kernel-plus-compaction time in full passes."""
        return ((self.scanned_blocks
                 + route_only_ratio * self.route_only_blocks) / max_blocks
                + compaction_cost * self.compactions)


def replay_tree(tree: TreeShape, rows: int, block_rows: int, lane_sets: int,
                budget_blocks: float, rule: str = "scanned",
                route_only_ratio: float = 0.0) -> Replayed:
    """One tree under one budget.  ``rule``: ``scanned`` is the grower's
    (blocks accumulated since the last compaction); ``waste`` counts
    instead the interval's blocks that served neither the split's smaller
    child nor a filled lane set, and route-only blocks at their cost
    ratio (the alternative ISSUE 33 weighed; the grower does not run it)."""
    n_splits = len(tree.left_child)
    max_blocks = -(-rows // block_rows)
    n_slots = n_splits + 1

    def count(child):
        return int(tree.internal_count[child] if child >= 0
                   else tree.leaf_count[~child])

    lo = np.zeros(n_slots, np.int64)
    hi = np.zeros(n_slots, np.int64)
    hi[0] = max_blocks
    held = np.zeros(n_slots, np.int64)      # rows in the layout, pads too
    held[0] = max_blocks * block_rows
    node_of = np.full(n_slots, -1, np.int64)    # the split a leaf waits for
    node_of[0] = 0 if n_splits else -1
    small = np.zeros(n_slots, np.int64)     # its smaller child's rows
    look_ok = np.zeros(n_slots, bool)
    gain = np.asarray(tree.split_gain, np.float64)
    ids = np.arange(n_slots)

    def pend(slot, node):
        node_of[slot] = node
        if node >= 0:
            small[slot] = min(count(tree.left_child[node]),
                              count(tree.right_child[node]))

    pend(0, node_of[0])
    since = float(max_blocks)               # the root's pass
    scanned, sorts, hits, fills, route_only = max_blocks, 0, 0, 0, 0
    # the leaf each node splits: left children keep the id
    slot_of = np.zeros(n_splits, np.int64)
    for i in range(n_splits):
        s, new = int(slot_of[i]), i + 1
        if since >= budget_blocks:
            ends = np.cumsum(held)
            starts = ends - held
            live = held > 0
            lo = np.where(live, starts // block_rows, 0)
            hi = np.where(live, -(-ends // block_rows), 0)
            since, sorts = 0.0, sorts + 1
        a, b = int(lo[s]), int(hi[s])
        if look_ok[s]:
            hits += 1
            route_only += b - a
            if rule == "waste":
                since += route_only_ratio * (b - a)
        else:
            # open (ids <= i) leaves that wait for a split are the ones
            # whose node_of is set; the rest is _lookahead_pending's test
            cand = np.flatnonzero(
                (node_of >= 0) & ~look_ok & (lo >= a) & (hi <= b)
                & (hi > lo) & (ids != s)
                & (gain[np.maximum(node_of, 0)] > 0.0))
            take = cand[np.argsort(-gain[node_of[cand]],
                                   kind="stable")][:lane_sets - 1]
            look_ok[take] = True
            fills += len(take)
            scanned += b - a
            used = small[s] + small[take].sum()
            since += ((b - a) - used / block_rows if rule == "waste"
                      else b - a)
        look_ok[s] = False
        left, right = tree.left_child[i], tree.right_child[i]
        lo[new], hi[new] = a, b
        held[new] = count(right)
        held[s] -= held[new]
        pend(s, left if left >= 0 else -1)
        pend(new, right if right >= 0 else -1)
        if left >= 0:
            slot_of[left] = s
        if right >= 0:
            slot_of[right] = new
    return Replayed(int(scanned), sorts, n_splits, hits, fills,
                    int(route_only))


def replay_model(trees, rows, block_rows, lane_sets, budget_blocks,
                 rule="scanned", route_only_ratio=0.0) -> Replayed:
    """The sums over a model's trees."""
    per_tree = [replay_tree(t, rows, block_rows, lane_sets, budget_blocks,
                            rule, route_only_ratio) for t in trees]
    return Replayed(*(int(sum(col)) for col in zip(*per_tree)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("model", help="a model file (plain or .gz)")
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--columns", type=int, required=True)
    ap.add_argument("--bins", type=int, default=64)
    ap.add_argument("--packed4", action="store_true")
    ap.add_argument("--budgets", default="",
                    help="budgets in tables (N), comma separated; default: "
                         "the grower's own for the shape, then a sweep")
    ap.add_argument("--rule", choices=("scanned", "waste"),
                    default="scanned")
    ap.add_argument("--compaction-cost", type=float, default=None,
                    help="one compaction in full passes (default: "
                         "grower_seg.compaction_unit_costs for the shape)")
    ap.add_argument("--route-only-ratio", type=float, default=0.05,
                    help="a route-only block over an accumulating one")
    a = ap.parse_args(argv)

    import gzip

    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import grower_seg as gs
    from lightgbm_tpu.ops import pallas_histogram as ph
    opener = gzip.open if a.model.endswith(".gz") else open
    with opener(a.model, "rt") as f:
        bst = lgb.Booster(model_str=f.read())
    trees = [TreeShape.of(t) for t in bst.gbdt.models if t.num_leaves > 1]
    rb = ph.pick_block_rows(a.columns, a.bins, a.rows)
    n = -(-a.rows // rb) * rb
    nb = n // rb
    leaves = max(len(t.leaf_count) for t in trees)
    K = min(ph.lookahead_width(a.columns, a.bins, rb, a.packed4), leaves - 1)
    cost = gs.compaction_unit_costs(a.columns, a.bins, n, a.packed4)
    c = (a.compaction_cost if a.compaction_cost is not None else
         cost["compaction_ns_per_row"] / cost["pass_ns_per_row"])
    own = gs.compaction_budget_blocks(a.columns, a.bins, n, rb, a.packed4)
    budgets = ([float(x) for x in a.budgets.split(",")] if a.budgets else
               [own / nb, 9, 7, 5, 4, 3.5, 3, 2.5, 2, 1.5, 1])
    # in blocks as the grower truncates them; the shape's own to the block
    in_blocks = [own if b == own / nb else max(1, int(b * nb))
                 for b in budgets]
    print(json.dumps({
        "trees": len(trees), "rows": n, "block_rows": rb, "max_blocks": nb,
        "lane_sets": K, "compaction_path": cost["path"],
        "compaction_in_passes": c, "route_only_ratio": a.route_only_ratio,
        "budget_blocks_of_the_shape": own, "rule": a.rule}))
    for b, blocks in zip(budgets, in_blocks):
        r = replay_model(trees, n, rb, K, blocks, a.rule, a.route_only_ratio)
        t = len(trees)
        print(json.dumps({
            "budget_N": round(b, 4),
            "scanned_N": round(r.scanned_blocks / nb / t, 3),
            "compactions": round(r.compactions / t, 3),
            "lookahead_hits": round(r.lookahead_hits / t, 2),
            "lookahead_fills": round(r.lookahead_fills / t, 2),
            "route_only_blocks": round(r.route_only_blocks / t, 1),
            "splits": round(r.splits / t, 2),
            "full_passes": round(r.full_passes(nb, c, a.route_only_ratio)
                                 / t, 3)}))


if __name__ == "__main__":
    main()
