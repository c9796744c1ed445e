"""Seconds per boosting iteration: the whole window over all its iterations,
on the host's clock (end-to-end)."""


def read(ctx):
    return ctx["window_s"] / ctx["iters"]
