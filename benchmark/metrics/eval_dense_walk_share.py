"""Boosting loop: share of the run's trees that the in-scan walk of the
held-out rows folded in by its dense form (`eval/dense_walk_trees`, counted
by the host at every fetched chunk, over the run's trees: warm-up and
window).  1.0 where every tree's node decisions and leaf came from two MXU
contractions; under it the gather walk, a round of per-row gathers a level,
took trees.  Nothing where the program has no such counter.  Moves
train_s_per_iter."""

from metrics import _program


def read(ctx):
    dense = _program.counter("eval/dense_walk_trees")
    trees = len(ctx["trees"])
    return dense / trees if dense is not None and trees else None
