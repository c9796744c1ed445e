"""Grower: GB (1e9 bytes) of the per-leaf histogram tables the segment grower
carries through a tree (gauge `seg/leaf_hist_bytes`: the leaf histograms and,
where lookahead lane sets run, as many again).  Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    held = _program.gauge("seg/leaf_hist_bytes")
    return held / 1e9 if held else None
