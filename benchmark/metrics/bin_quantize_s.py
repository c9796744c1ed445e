"""Host: seconds quantizing the rows into bins took (the program's
`bin_quantize` phase).  Moves setup_s."""

from metrics import _program


def read(ctx):
    return _program.phase_seconds("bin_quantize")
