"""Kernels: grid steps the segment histogram kernels dispatched over the
blocks they scanned (`seg/grid_steps` over `seg/scanned_blocks`): what the
static bucket ladder wastes.  At least 1.  Moves train_s_per_iter."""

from metrics import _program


def read(ctx):
    steps = _program.counter("seg/grid_steps")
    blocks = _program.counter("seg/scanned_blocks")
    return steps / blocks if steps and blocks else None
