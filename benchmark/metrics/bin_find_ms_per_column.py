"""Host: milliseconds a column's bin boundaries cost (the program's `bin_find`
phase over the table's columns).  Moves setup_s."""

from metrics import _program


def read(ctx):
    seconds = _program.phase_seconds("bin_find")
    if seconds is None or not ctx.get("features"):
        return None
    return 1e3 * seconds / ctx["features"]
