"""Kernels: device time of Mosaic (Pallas) custom calls, as a share of all
operation time in the traced slice.  Moves train_s_per_iter."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["mosaic_s"] <= 0:
        return None
    return 100.0 * t["mosaic_s"] / (t["mosaic_s"] + t["other_s"])
