"""Boosting loop: seconds the backend spent compiling in this run, from the
program's `compile/backend_compile_seconds` counter (near 0 from a warm
cache).  Moves setup_s."""


def read(ctx):
    return float(ctx["compiles"]["backend_compile_seconds"])
