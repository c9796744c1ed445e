"""Boosting loop: seconds one booster's construction took (the program's
`booster_init` phase over its count: the harness builds two, one only to
read what `auto` resolves to).  Moves setup_s."""

from metrics import _program


def read(ctx):
    return _program.phase_seconds("booster_init", per_count=True)
