"""Grower: device time of operations that are not Mosaic (Pallas) calls, as a
share of all operation time in the traced slice.  Moves train_s_per_iter."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["mosaic_s"] + t["other_s"] <= 0:
        return None
    return 100.0 * t["other_s"] / (t["mosaic_s"] + t["other_s"])
