"""Whole step: the least time the chip could take for the window's histogram
work (work.py, counted from the window's trees) over the window's wall time.
The work is bound by HBM bandwidth, not by operations, at these shapes."""

import work


def read(ctx):
    if not ctx["window_trees"] or ctx["peaks"] is None:
        return None
    need = work.least_seconds(
        work.histogram_work(ctx["window_trees"], ctx["features"]),
        ctx["peaks"])["seconds"]
    return 100.0 * need / ctx["window_s"]
