"""Device: `memory_stats()["peak_bytes_in_use"]` read after the window and
before the reference runs, in GB (1e9 bytes)."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
