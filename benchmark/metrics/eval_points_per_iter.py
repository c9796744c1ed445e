"""Boosting loop: metric values the in-scan evaluation delivered to the
host an iteration (`eval/points` over the run's iterations, warm-up and
window): the columns of the metric matrix, 1.0 with one valid set and one
metric.  Under that, evaluation fell out of the scan for part of the run.
Nothing where the program has no such counter.  Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    points = _program.counter("eval/points")
    per_iter = int(ctx["config"]["params"].get("num_class", 1))
    iters = len(ctx["trees"]) // per_iter
    return points / iters if points is not None and iters else None
