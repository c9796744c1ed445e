"""Boosting loop: levels the in-scan walk of the held-out rows takes a tree
(`eval/walk_levels`, the walk's `while_loop` trip count summed over every
chunk of the run, over the run's trees: warm-up and window, as the counter
counts both).  A level pays a round of per-row gathers over the gauge
`eval/valid_rows`, so this times that is the walk's work a tree; with one
valid set it is the tree's depth.  Nothing where the program has no such
counter.  Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    levels = _program.counter("eval/walk_levels")
    trees = len(ctx["trees"])
    return levels / trees if levels is not None and trees else None
