"""Grower: compaction sorts a tree (`seg/compactions` over `seg/trees`).
Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    sorts = _program.counter("seg/compactions")
    trees = _program.counter("seg/trees")
    return sorts / trees if sorts is not None and trees else None
