"""Kernels: feature tiles a pass of the segment histogram kernels walks at
this table's shape (gauge `seg/feature_tiles`): 1 where one accumulator holds
every column, 16 at 2000 columns x 64 bins.  `seg/grid_steps` counts a step
for every tile, so the bucket ladder's waste there is `seg/grid_steps` over
(`seg/scanned_blocks` x this).  Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    return _program.gauge("seg/feature_tiles")
