"""Host: seconds finding bin boundaries took (the program's `bin_find`
phase).  Moves setup_s."""

from metrics import _program


def read(ctx):
    return _program.phase_seconds("bin_find")
