"""Boosting loop: the longest device-idle gap in the traced slice, which starts
at a chunk boundary with the device drained by the stamp: an upper bound on
the gap a user's run has there.  Moves train_s_per_iter."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else t["longest_gap_s"] * 1e3
