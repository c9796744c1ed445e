"""Kernels: the least time the chip could take for one iteration's histogram
work (work.py, counted from the window's trees, mean per tree) over the
Pallas time of one iteration: the Mosaic calls' share of the slice's steady
part (the slice less its boundary gap) times seconds per iteration.  The
Mosaic calls include the route and score kernels, whose time no name tells
apart yet, so the share reads low and never high."""

import work


def read(ctx):
    t = ctx["trace"]
    if t is None or t["mosaic_s"] <= 0 or not ctx["window_trees"]:
        return None
    steady = t["window_s"] - t["longest_gap_s"]
    pallas_per_iter = t["mosaic_s"] / steady * ctx["window_s"] / ctx["iters"]
    need = work.least_seconds(
        work.histogram_work(ctx["window_trees"], ctx["features"]),
        ctx["peaks"])["seconds"] / len(ctx["window_trees"])
    return 100.0 * need / pallas_per_iter
