"""Boosting loop: host milliseconds an iteration spent fetching its trees
(the program's `fetch` phase: the wait for the device-to-host copy and the
building of Tree objects together) over the steady timeline entries: host
time the chunk pipeline hides and a chunk of 1 will not.  Moves
train_s_per_iter."""

from metrics import _program


def read(ctx):
    return _program.steady_ms_per_iter("fetch")
