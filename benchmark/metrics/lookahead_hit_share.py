"""Grower: splits committed from a lookahead histogram, over all splits
(`seg/lookahead_hits` over `seg/splits`): such a split only routes, where
any other scans its interval.  0 on a path that fills no lane set, nothing
where the program has no such counters.  Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    hits = _program.counter("seg/lookahead_hits")
    splits = _program.counter("seg/splits")
    return hits / splits if hits is not None and splits else None
