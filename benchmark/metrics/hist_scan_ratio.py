"""Grower: rows the histogram kernels scanned (the program's
`seg/scanned_blocks` counter times its `seg/block_rows` gauge) over the rows
the run's trees need (work.py: the root's rows plus the smaller child's at
every split).  The grower scans confinement intervals, not leaves, and
re-sorts only now and then, so it scans more: at least 1 by construction.
`None` unless the counters cover exactly the run's trees.  Moves
train_s_per_iter."""

import work
from metrics import _program


def read(ctx):
    blocks = _program.counter("seg/scanned_blocks")
    block_rows = _program.gauge("seg/block_rows")
    if not blocks or not block_rows \
            or _program.counter("seg/trees") != len(ctx["trees"]):
        return None
    need = sum(work.tree_visits(t) for t in ctx["trees"])
    return blocks * block_rows / need if need else None
