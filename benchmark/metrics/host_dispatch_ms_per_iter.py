"""Boosting loop: host milliseconds an iteration spent dispatching (the
program's `chunk` phase) over the steady timeline entries.  Moves
train_s_per_iter."""

from metrics import _program


def read(ctx):
    return _program.steady_ms_per_iter("chunk")
