"""Boosting loop: host milliseconds an iteration spent handing a chunk's
in-scan metric rows to the after-iteration callbacks (the program's
`eval_replay` phase, `engine.replay_inscan`) over the steady timeline
entries, as `host_fetch_ms_per_iter` reads `fetch`: host time at a chunk
boundary during which nothing is dispatched.  In a benchmark run the
harness's own stamp callback is inside it.  Nothing where the program has no
such phase.  Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    return _program.steady_ms_per_iter("eval_replay")
